#!/usr/bin/env python3
"""Readings that the limits of a cell's check are set from.

    python bench/calibrate.py --workload fig8-grid --seeds 12 --control 3

For each of ``--seeds`` seeds, one call of the program's entry on the
cell's grid (the grid of a run's first call) is compared with the float64
reference on the run's sample: the program's readings, whose largest is
the lower reading of each number.  For the first ``--control`` seeds the
control (the same reference computed in bfloat16, in the program's place)
is compared with the float64 reference too: its smallest reading is the
upper one.  One JSON line per reading, then a summary line.  Benchmark
runs never run this.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONTROL_STEPS = 2     # the control may run twice the reference's steps


def control_numbers(spec: dict, calls: list, picks: list, ref: dict) -> dict:
    """The control's numbers: the bfloat16 reference against float64."""
    import ml_dtypes

    from bench import check

    cap = {ci: CONTROL_STEPS * s for ci, s in ref["steps"].items()}
    low = check.reference(spec, calls, picks, dt=ml_dtypes.bfloat16,
                          max_steps=cap)
    if low["dtype"] != "bfloat16":
        raise RuntimeError(f"the control ran in {low['dtype']}")
    return check.numbers(low, ref, [])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--first", type=int, default=1_000_003)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    from bench import check, harness
    from repro import compile_cache

    compile_cache.enable()
    spec = harness.cell_spec(args.workload)
    devs = jax.devices()
    sims = harness.build_sims(spec)
    prog_max, ctrl_min = {}, {}
    for i in range(args.seeds):
        seed = args.first + 7919 * i
        grid = harness.call_grid(spec, seed, 0)
        t0 = time.perf_counter()
        rec = harness.keep(harness.run_entry(spec, sims, grid), grid)
        t_call = time.perf_counter() - t0
        calls = [rec]
        picks = check.sample(spec, calls, seed)
        t0 = time.perf_counter()
        ref = check.reference(spec, calls, picks)
        t_ref = time.perf_counter() - t0
        nums = check.numbers(check.program(calls, picks), ref, calls)
        for k, v in nums.items():
            prog_max[k] = max(prog_max.get(k, 0.0), v)
        print(json.dumps({"seed": seed, "side": "program", "numbers": nums,
                          "call_s": t_call, "reference_s": t_ref,
                          "sampled": len(picks)}), flush=True)
        if i < args.control:
            t0 = time.perf_counter()
            cn = control_numbers(spec, calls, picks, ref)
            for k, v in cn.items():
                if k != "bad_cells":
                    ctrl_min[k] = min(ctrl_min.get(k, np.inf), v)
            print(json.dumps({"seed": seed, "side": "control", "numbers": cn,
                              "control_s": time.perf_counter() - t0}),
                  flush=True)
    print(json.dumps({"workload": args.workload, "device":
                      devs[0].device_kind, "lower": prog_max,
                      "upper": ctrl_min}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
