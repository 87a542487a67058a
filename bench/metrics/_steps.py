"""Scan steps and request slots the batch backend ran, from its outputs.

A cell's ``steps`` output is its request budget; each scan step pops a
burst of requests (the configuration's model says how many: its plain
reference's ``BURST``, at most the call's largest client count).  A call
runs its first pass at the smallest budget and each exhausted-retry pass
at twice the one before, up to the largest.
"""
import math

from bench import harness


def burst(spec: dict, rec: dict) -> int:
    ref = harness.load_module("references", spec["config"]["reference"])
    return min(ref.BURST, max(g[1] for g in rec["grid"]))


def scan_steps(spec: dict, rec: dict) -> int:
    b = burst(spec, rec)
    lo, hi = int(rec["steps"].min()), int(rec["steps"].max())
    total, s = 0, lo
    while s <= hi:
        total += math.ceil(s / b)
        s *= 2
    return total


def slots(spec: dict, rec: dict) -> int:
    b = burst(spec, rec)
    return sum(math.ceil(int(s) / b) * b for s in rec["steps"])
