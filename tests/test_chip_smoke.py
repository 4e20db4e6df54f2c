"""``chip_smoke.py``, the driver's chip check, rehearsed on the CPU at its
``--dry`` sizes: every phase runs and compares, and without ``--dry`` a
CPU backend is refused with no result line."""

import json
import os
import subprocess
import sys

import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("chips", [1, 4])
def test_dry_run_passes_every_phase(capsys, chips):
    assert chip_smoke.main(["--dry", "--chips", str(chips)]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    last = json.loads(out[-1])
    assert last["ok"] is True
    assert last["device"]["platform"] == "cpu"
    phases = [line for line in out if line.startswith("phase ")]
    if chips == 1:
        assert [p.split(":")[0] for p in phases] == [
            "phase a",
            "phase b brca1 device-vs-host",
            "phase c whole-genome",
            "phase d served",
        ]
    else:
        assert [p.split(":")[0] for p in phases] == [
            "phase a",
            "phase sharded 1,4",
            "phase sharded 2,2",
            "phase dense reference",
        ]


def test_refuses_cpu_without_result_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr
