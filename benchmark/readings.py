"""The readings the correctness limits are set from (``PERF.md``), in one
process per cell, on the chip:

    python benchmark/readings.py --workload <cell> --seeds 1,2,... --control-seeds 7,8,9 --seconds 8

Set-up once; then for each seed a short window of the cell's own traffic
and the comparison a run makes (the program's readings, the lower ones);
then, for each control seed, the control: the plain reference put in the
program's place, one rung down the precision ladder (``reference.py``),
compared on the same jobs by the same numbers (the upper readings). The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if sys.path[0] != ROOT:
    sys.path.insert(0, ROOT)

from benchmark import batch, core, reference, served, traffic  # noqa: E402


def control_numbers(cell: dict, seed: int, devices=None) -> dict:
    """The control's numbers on the jobs a run with ``seed`` compares, with
    the Gramians in row tiles over the cell's devices."""
    import jax

    cfg, trf = cell["config"], cell["traffic"]
    spacing = int(trf["spacing"])
    devices = devices or jax.devices()[: cell["chips"]]
    if trf["loop"] == "closed":
        parts = traffic.closed_job(trf, seed, 0).split(",")
        windows = [[reference.grid_range(int(p.split(":")[1]), int(p.split(":")[2]), spacing) for p in parts]]
    else:
        schedule = traffic.open_schedule(trf, seed, 8.0)[: int(trf["sampled_jobs"])]
        windows = [
            [reference.grid_range(int(r.split(":")[1]), int(r.split(":")[2]), spacing)]
            for _, r in schedule
        ]
    layout = reference.row_layout(int(cfg["num_samples"]), devices)
    gap_g = gap_pc = 0.0
    for ranges in windows:
        G = reference.gramian_tiles(cfg, ranges, spacing, layout)
        G_c = reference.gramian_tiles(cfg, ranges, spacing, layout, precision="control")
        gap_g = max(gap_g, reference.tiles_max_abs_diff(G_c, G))
        vals, vecs = reference.reference_eigen(cfg, G)
        del G
        gap_pc = max(gap_pc, reference.eigenspace_gap(reference.control_pcs(cfg, G_c), vals, vecs))
    numbers = {"pc_eigenspace_gap": gap_pc}
    if "gramian_max_abs_diff" in cell["limits"]:
        numbers["gramian_max_abs_diff"] = gap_g
    if "unanswered_requests" in cell["limits"]:
        numbers["unanswered_requests"] = 0.0
    return numbers


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--control-seeds", required=True)
    parser.add_argument("--seconds", type=float, default=8.0)
    parser.add_argument("--dry", action="store_true")
    args = parser.parse_args(argv)
    cell = core.cell(args.workload)
    if args.dry:
        cell = core.dry_overrides(cell)
    from spark_examples_tpu.utils.cache import enable_persistent_compile_cache

    enable_persistent_compile_cache(persist_all=True)
    devices = core.require_chips(cell["chips"], args.dry)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    out = {"workload": args.workload, "program": {}, "control": {}}
    if cell["traffic"]["loop"] == "closed":
        with batch.set_up(cell, devices, False) as (kind, job, _):
            for seed in seeds:
                result = batch.window(job, seed, args.seconds)
                out["program"][seed] = batch.compare(kind, cell, result["kept"])
                core.say(f"program seed {seed}: {out['program'][seed]}")
    else:
        svc = served.Service(cell)
        try:
            svc.warm_up(seeds[0])
            for seed in seeds:
                result = served.window(svc, seed, args.seconds, cell["traffic"]["poll_s"])
                out["program"][seed] = served.check(cell, result, seed)
                core.say(f"program seed {seed}: {out['program'][seed]}")
        finally:
            svc.close()
    for seed in [int(s) for s in args.control_seeds.split(",") if s]:
        out["control"][seed] = control_numbers(cell, seed, devices)
        core.say(f"control seed {seed}: {out['control'][seed]}")
    for name in cell["limits"]:
        low = max(r[name] for r in out["program"].values())
        high = min(r[name] for r in out["control"].values())
        core.say(f"{name}: lower reading {low!r}, upper reading {high!r}, limit {cell['limits'][name]!r}")
    print(json.dumps(out), file=sys.__stdout__, flush=True)
    return 0


if __name__ == "__main__":
    sys.stdout = sys.stderr
    try:
        code = main()
    except core.BenchFailure as e:
        print(f"readings FAILED: {e}", file=sys.stderr, flush=True)
        code = 1
    sys.exit(code)
