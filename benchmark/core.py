"""Shared pieces of the benchmark: the manifest and the files it names, the
compile clock, the device check, and the comparison arithmetic.

Everything that belongs to one configuration, traffic mix, per-layer metric
or cell lives in a file of its own that is found here by its name:
``configs/<file named in BENCHMARK.json>``, ``traffic/<traffic>.json``,
``metrics/<metric>.py`` (a ``read(run)`` function) and
``limits/<cell>.json``.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import sys
import time
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class BenchFailure(Exception):
    """A run that cannot produce a result: exit non-zero, print no line."""


def load_json(path: str):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def manifest() -> dict:
    path = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.exists(path):
        raise BenchFailure(f"no BENCHMARK.json at {ROOT}")
    return load_json(path)


def load_traffic(name: str) -> dict:
    return load_json(os.path.join(BENCH, "traffic", f"{name}.json"))


def load_limits(cell: str) -> dict:
    return load_json(os.path.join(BENCH, "limits", f"{cell}.json"))


def load_reader(metric: str) -> Callable:
    """The ``read(run)`` function of ``metrics/<metric>.py``."""
    path = os.path.join(BENCH, "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def cell(name: str, doc: Optional[dict] = None) -> dict:
    """Everything one cell needs, found by the names in the manifest."""
    doc = doc if doc is not None else manifest()
    workloads = {w["name"]: w for w in doc["workloads"]}
    if name not in workloads:
        raise BenchFailure(f"no workload {name!r} in BENCHMARK.json")
    work = workloads[name]
    entry = {c["name"]: c for c in doc["configs"]}[work["config"]]
    return {
        "name": name,
        "chips": int(work["chips"]),
        "config": load_json(os.path.join(ROOT, entry["file"])),
        "traffic": load_traffic(work["traffic"]),
        "limits": load_limits(name),
        "end_to_end": [m for m in doc["end_to_end"] if name in m.get("workloads", [name])],
        "per_layer": [m for m in doc["per_layer"] if name in m.get("workloads", [name])],
    }


def dry_overrides(cell_doc: dict) -> dict:
    """Tests only: the ``dry`` sizes of the config and traffic files."""
    out = dict(cell_doc)
    out["config"] = {**cell_doc["config"], **cell_doc["config"].get("dry", {})}
    out["traffic"] = {**cell_doc["traffic"], **cell_doc["traffic"].get("dry", {})}
    return out


# ------------------------------------------------------------ clocks


def process_age_s() -> float:
    """Seconds since this process was created (Linux; 10 ms resolution)."""
    with open("/proc/self/stat", encoding="ascii") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    start_ticks = int(fields[19])
    return time.clock_gettime(time.CLOCK_BOOTTIME) - start_ticks / os.sysconf("SC_CLK_TCK")


def resident_bytes() -> int:
    """This process's resident host memory now (Linux)."""
    with open("/proc/self/statm", encoding="ascii") as f:
        return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")


def peak_resident_bytes() -> int:
    """The most host memory this process has held resident (Linux)."""
    import resource

    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


class CompileClock:
    """Tracing, lowering and compiling seconds, read from JAX's own
    monitoring events (copied from ``chip_smoke.py``)."""

    EVENTS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self) -> None:
        import jax.monitoring

        self.seconds = 0.0
        self.events = 0
        self.compiles = 0
        self.names: List[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, fun_name: str = "?", **_kw) -> None:
        if event in self.EVENTS:
            self.seconds += duration
            self.events += 1
            self.names.append(str(fun_name))
            if event == self.EVENTS[-1]:
                self.compiles += 1

    def snapshot(self) -> tuple:
        return (self.seconds, self.events, self.compiles)

    def since(self, before: tuple) -> str:
        """What was traced, lowered or compiled since ``before``."""
        seconds, events, compiles = (a - b for a, b in zip(self.snapshot(), before))
        counts: Dict[str, int] = {}
        for name in self.names[len(self.names) - events :] if events else []:
            counts[name] = counts.get(name, 0) + 1
        return (
            f"compile {seconds:.3f} s, {events} trace/lower/compile events, "
            f"{compiles} backend compiles {dict(sorted(counts.items()))}"
        )


# ------------------------------------------------------------ device


def require_chips(chips: int, dry: bool) -> list:
    """The devices of the cell, or a failure: no TPU (unless ``dry``), or
    fewer chips than the cell asks for."""
    import jax

    devices = jax.devices()
    if not dry and devices[0].platform != "tpu":
        raise BenchFailure(f"no TPU: JAX backend is {devices[0].platform!r}")
    if len(devices) < chips:
        raise BenchFailure(f"cell needs {chips} chips, JAX has {len(devices)}")
    return devices[:chips]


def device_block(devices) -> dict:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": max(peaks),
    }


# ------------------------------------------------------------ comparison


def nearest_rank(values: List[float], q: float) -> float:
    """The q-quantile by nearest rank over all values."""
    ordered = sorted(values)
    return float(ordered[max(0, math.ceil(q * len(ordered)) - 1)])


def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Every compared number at or under its limit; each printed beside it
    as the last lines of standard error."""
    ok = True
    for name, value in numbers.items():
        limit = limits[name]
        passed = value is not None and np.isfinite(value) and value <= limit
        ok = ok and passed
        say(f"compare {name} {value!r} limit {limit!r} {'ok' if passed else 'FAIL'}")
    return ok
