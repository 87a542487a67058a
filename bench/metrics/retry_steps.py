"""Share of the window's cell-scan-steps spent in exhausted-retry passes.

Layer: exhausted-retry loop (``simulate_grid`` re-runs the cells whose
request budget ran out at twice the budget, until none does).  Source:
the program's ``steps`` output, each cell's final budget, which the
retry loop sets: per call, the first pass runs every cell for
ceil(lo / B) scan steps, lo the smallest budget and B the burst, and
retry pass p runs the cells whose budget reached lo * 2^p for
ceil(lo * 2^p / B) steps.  A count that is the same on every run of a
seed.
Moves: sim_req_per_s.
"""
import math

from bench.metrics._steps import burst

LAYER = "exhausted-retry loop"
UNIT = "%"
MOVES = "sim_req_per_s"


def passes(spec: dict, rec: dict) -> list:
    """(cells, scan steps) of each pass of one call, first pass first."""
    b = burst(spec, rec)
    steps = [int(s) for s in rec["steps"]]
    lo, hi = min(steps), max(steps)
    out, s = [], lo
    while s <= hi:
        out.append((sum(v >= s for v in steps), math.ceil(s / b)))
        s *= 2
    return out


def read(ctx: dict):
    total = retry = 0
    for rec in ctx["calls"]:
        cell_steps = [c * n for c, n in passes(ctx["spec"], rec)]
        total += sum(cell_steps)
        retry += sum(cell_steps[1:])
    if total == 0:
        return None
    return 100.0 * retry / total
