"""Share of the traced window in which no operation ran on the device.

Layer: device.  Source: profiler trace of the window's first calls
(1 - busy / traced window, busy = union of the operation intervals,
averaged over the chips used).
Moves: sim_req_per_s.
"""
LAYER = "device"
UNIT = "%"
MOVES = "sim_req_per_s"


def read(ctx: dict):
    tr = ctx["trace"]
    if tr is None or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
