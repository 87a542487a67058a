"""Share of the scan's request slots that completed a measured request.

Layer: step budget (``simulate_grid``'s rate-bound budget and its
exhausted-retry loop).  Source: the program's outputs, a count that is
the same on every run of a seed: the completed requests inside the
simulated window (``count``) over cells x burst-sized scan steps.
Moves: sim_req_per_s.
"""
from bench.metrics._steps import slots

LAYER = "step budget"
UNIT = "%"
MOVES = "sim_req_per_s"


def read(ctx: dict):
    total = sum(slots(ctx["spec"], rec) for rec in ctx["calls"])
    done = sum(int(rec["count"].sum()) for rec in ctx["calls"])
    if total == 0:
        return None
    return 100.0 * done / total
