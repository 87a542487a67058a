"""Device time of one scan step of the grid program.

Layer: batch device path (the scan of ``vectorsim._run_cells``).
Source: profiler trace: the device time of the program with the
most device time in the window (the grid program), divided by the scan
steps the traced calls ran, per chip, averaged over the chips used.
Moves: sim_req_per_s.
"""
from bench.metrics._steps import scan_steps

LAYER = "batch device path"
UNIT = "us"
MOVES = "sim_req_per_s"


def read(ctx: dict):
    tr = ctx["trace"]
    if tr is None:
        return None
    steps = sum(scan_steps(ctx["spec"], rec) for rec in ctx["traced"])
    per_dev = [max(d["modules_s"].values()) for d in tr["devices"]
               if d["modules_s"]]
    if not per_dev or len(per_dev) != len(tr["devices"]) or steps == 0:
        return None
    return 1e6 * sum(per_dev) / len(per_dev) / steps
