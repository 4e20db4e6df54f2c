"""What the per-layer readers take from the program itself, in the run's
own process, after the window: its closed stage spans
(``obs/spans.py:recent_spans``) and the scope map of its Gramian update
programs (``ops/devicegen.py:update_op_scopes``). A checkout of the program
from before either existed reads ``None``, as does a run whose trace has no
device plane."""

from __future__ import annotations

from typing import Dict, List, Optional


def window_runs(run, paths) -> Optional[List[Dict[str, dict]]]:
    """``[{span path: record}]``, one per job of the window, oldest first:
    the spans of the last ``len(run.jobs)`` run ids the program recorded
    (the warm-up job runs before the window, and the check after it runs no
    program code). ``None`` unless the trace has a device plane and each of
    those runs holds every one of ``paths``."""
    if run.trace is None or not run.trace.busy or not run.jobs:
        return None
    try:
        from spark_examples_tpu.obs.spans import recent_spans
    except ImportError:
        return None
    by_run: Dict[str, Dict[str, dict]] = {}
    for record in recent_spans():
        if record["run_id"] is not None and record["seconds"] is not None:
            by_run.setdefault(record["run_id"], {})[record["path"]] = record
    runs = list(by_run.values())[-len(run.jobs):]
    if len(runs) != len(run.jobs) or not all(all(p in spans for p in paths) for spans in runs):
        return None
    return runs


def scoped_device_ms(run, scope: str) -> Optional[float]:
    """Device milliseconds per job of the operations the program scoped
    ``scope`` inside its update programs (``jit_devicegen_update``,
    ``_tail``, ``jit_devicegen_ring_update``), inside the window, averaged
    over the chips. An operation belongs to the update program whose
    module event contains its start on the same chip; its HLO instruction
    name is looked up in that program's scope map."""
    trace = run.trace
    if trace is None or not trace.busy or not trace.modules or not run.jobs:
        return None
    try:
        from spark_examples_tpu.ops.devicegen import update_op_scopes
    except ImportError:
        return None
    maps = update_op_scopes()
    if not maps:
        return None
    start, end = trace.window
    total = 0.0
    for chip, modules in trace.modules.items():
        programs = sorted(
            (s, s + d, maps[name.split("(", 1)[0]])
            for name, s, d in modules
            if name.split("(", 1)[0] in maps
        )
        if not programs:
            continue
        index = 0
        for name, s, d in sorted(trace.ops.get(chip, []), key=lambda op: op[1]):
            while index < len(programs) and programs[index][1] < s:
                index += 1
            if index == len(programs):
                break
            first, _, scopes = programs[index]
            if s < first:
                continue
            if scopes.get(name.split(" = ", 1)[0].lstrip("%")) == scope:
                total += max(0.0, min(s + d, end) - max(s, start))
    if total <= 0:
        return None
    return total * 1e-6 / len(trace.modules) / len(run.jobs)
