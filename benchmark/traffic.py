"""The one traffic generator: a mix's parameter file and ``--seed`` in,
the jobs of a run out. Every seed gives the same set of sizes and the same
multiset of arrival gaps, in another order, so seeds change the order of
the work and not its amount.

Two loops exist:

- ``closed``: jobs back to back, one in flight. Each job scans the mix's
  contigs (``"references": "autosomes"`` or a list), in an order drawn
  from the seed per job.
- ``open``: requests due on a schedule whatever the system does. Gaps are
  the quantiles of an exponential distribution at ``rate_per_s`` (a Poisson
  stream's gaps, spread evenly) in one fixed shuffled order: every seed sees
  the same arrivals, since the order of the gaps decides where bursts fall
  and the tail follows the bursts. Each request asks for a window of
  ``window_bases`` on ``contig``, starting ``anchor`` plus a seed-drawn
  multiple of the grid spacing, so every window holds the same number of
  grid sites and the seed changes only which data each request reads.
"""

from __future__ import annotations

from typing import List, Tuple

import numpy as np

#: GRCh37 autosome lengths, the grid the whole-genome mixes scan.
AUTOSOMES = {
    "1": 249250621, "2": 243199373, "3": 198022430, "4": 191154276,
    "5": 180915260, "6": 171115067, "7": 159138663, "8": 146364022,
    "9": 141213431, "10": 135534747, "11": 135006516, "12": 133851895,
    "13": 115169878, "14": 107349540, "15": 102531392, "16": 90354753,
    "17": 81195210, "18": 78077248, "19": 59128983, "20": 63025520,
    "21": 48129895, "22": 51304566,
}


#: The one order of the arrival gaps (see the module docstring).
ARRIVAL_ORDER = 20261015


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed & ((1 << 64) - 1), stream])


def contigs(traffic: dict) -> List[Tuple[str, int, int]]:
    refs = traffic["references"]
    if refs == "autosomes":
        return [(name, 0, end) for name, end in AUTOSOMES.items()]
    out = []
    for spec in refs.split(","):
        name, start, end = spec.split(":")
        out.append((name, int(start), int(end)))
    return out


def closed_job(traffic: dict, seed: int, index: int) -> str:
    """Job ``index``'s ``--references``: the mix's contigs in a seed-drawn
    order."""
    parts = contigs(traffic)
    order = _rng(seed, index).permutation(len(parts))
    return ",".join(f"{parts[i][0]}:{parts[i][1]}:{parts[i][2]}" for i in order)


def warmup_references(traffic: dict, blocks_per_dispatch: int) -> str:
    """The ``bench.py`` warm-up rule: one contig covering one dispatch group
    of the resolved length plus the ~K/8 tail group, so every program of a
    whole-genome job compiles before the window."""
    block = int(traffic["block_size"])
    bases = int(traffic["spacing"]) * (
        block * blocks_per_dispatch + block * max(1, blocks_per_dispatch // 8)
    )
    return f"1:0:{bases}"


def open_schedule(traffic: dict, seed: int, seconds: float) -> List[Tuple[float, str]]:
    """``[(due seconds, --references)]`` of the requests due in the window."""
    rate = float(traffic["rate_per_s"])
    count = max(1, int(round(rate * seconds)))
    quantiles = (np.arange(count) + 0.5) / count
    gaps = -np.log1p(-quantiles) / rate
    gaps = gaps[_rng(ARRIVAL_ORDER, 0).permutation(count)]
    due = np.cumsum(gaps) - gaps[0]
    spacing = int(traffic["spacing"])
    width = int(traffic["window_bases"])
    anchor = int(traffic["anchor"])
    lo, hi = traffic["shift_steps"]
    shifts = _rng(seed, 1).integers(lo, hi, size=count)
    out = []
    for t, s in zip(due, shifts):
        start = anchor + int(s) * spacing
        out.append((float(t), f"{traffic['contig']}:{start}:{start + width}"))
    return out
