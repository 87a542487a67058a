"""Device time of the Pallas fan-in kernel per scan step.

Layer: fan-in kernel (``kernels/segfanin.py`` via
``kernels/ops.seg_fanin``).  Source: profiler trace: the summed device
time of the operations whose name holds ``NAME``, divided by the scan
steps the traced calls ran, averaged over the chips used.  The grid
program names the kernel's custom call (``tpu_custom_call``) after the
function that wraps it, ``seg_fanin_bf.<n>``; the name of an operation
that only reads its output does not hold it.  None where no such
operation ran (the EPaxos kernel, or a fan-in on the lax path).
Moves: sim_req_per_s.
"""
from bench.metrics._steps import scan_steps

LAYER = "fan-in kernel"
UNIT = "us"
MOVES = "sim_req_per_s"
NAME = "fanin"


def read(ctx: dict):
    tr = ctx["trace"]
    if tr is None:
        return None
    steps = sum(scan_steps(ctx["spec"], rec) for rec in ctx["traced"])
    per_dev = [sum(v for k, v in d["ops_s"].items() if NAME in k)
               for d in tr["devices"]]
    if not any(per_dev) or steps == 0:
        return None
    return 1e6 * sum(per_dev) / len(per_dev) / steps
