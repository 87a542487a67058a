"""Batch-backend benchmark: a fig8-style sweep grid (protocol x R x
clients x seeds) in ONE jitted call, versus the DES process pool.

Three measurements, written to BENCH_vectorsim.json at the repo root:

* ``grid``    — the full protocol x R x clients x 32-seed grid (one
  ``vectorsim.simulate_grid`` call: one XLA compile + one device dispatch),
  cold and warm wall clock.
* ``sharded`` — the same grid through ``vectorsim.simulate_grid_sharded``
  (device-sharded chunked dispatch, bit-identical results): per-chunk
  walls, cells/s, device count, the fan-in taken.
* ``des``     — the same grid on ``Cluster(engine="fast")``: a stratified
  sample of units (every (config, clients) point, subset of seeds) is
  measured serially AND through a real ``multiprocessing`` pool at
  ``run.py --parallel`` concurrency, then extrapolated to the full grid
  using the *measured* pool speedup (pools on small boxes scale ~1.6x on
  2 cores, not 2x — assuming ideal scaling would overstate the DES).
  The sampled units double as the DES<->batch cross-check points (max
  throughput / median deviation recorded).
* ``sweep1025`` — an N=1025 PigPaxos (R=32) multi-seed sweep, a grid no
  DES run can touch interactively (~10^3 x the paper's 25-node testbed
  state space), with its wall clock.
"""
import json
import os
import time

import numpy as np

from repro.core import Cluster, PigConfig
from repro.core import vectorsim as vs

from .common import row

BENCH_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_vectorsim.json")

DUR, WARM = 0.4, 0.2
CLIENTS = (20, 60, 120)


def _grid_configs():
    """The fig8-style axes: classic Paxos plus rotating PigPaxos R sweep.
    R=8 at 120 clients crosses the leader-timeout retry boundary (the DES
    re-proposes, the timeout-free batch model doesn't — see
    ``vectorsim.simulate_scenario``), so the cross-checked grid stops at
    R=5; R=8+ still runs fine via the ``scale`` catalog family."""
    cfgs = [("paxos", "paxos", None)]
    for r in (2, 3, 5):
        cfgs.append((f"pig_R{r}", "pigpaxos", PigConfig(n_groups=r, prc=1)))
    return cfgs


def _des_unit(proto, pig, k, seed):
    t0 = time.perf_counter()
    c = Cluster(proto, 25, pig=pig, seed=seed, engine="fast")
    st = c.measure(duration=DUR, warmup=WARM, clients=k)
    return st.throughput, st.median_ms, time.perf_counter() - t0


def _pool_speedup(unit_args, workers: int, serial_wall: float) -> float:
    """Measured speedup of a real worker pool over the serial walk of the
    SAME units (run.py --parallel scales sublinearly on small boxes)."""
    import multiprocessing

    t0 = time.perf_counter()
    # forkserver: workers never inherit this process's JAX backend (a
    # forked child of a process holding the chip would share its client)
    with multiprocessing.get_context("forkserver").Pool(workers) as pool:
        pool.starmap(_des_unit, unit_args, chunksize=1)
    pool_wall = time.perf_counter() - t0
    return max(serial_wall / max(pool_wall, 1e-9), 1.0)


def run(quick: bool = True):
    out = []
    seeds = list(range(32))
    cfgs = _grid_configs()
    sims = [vs.build_config(proto, 25, pig=pig, label=label)
            for label, proto, pig in cfgs]
    grid = [(ci, k, s) for ci in range(len(cfgs))
            for k in CLIENTS for s in seeds]

    t0 = time.perf_counter()
    res = vs.simulate_grid(sims, grid, DUR, WARM)
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = vs.simulate_grid(sims, grid, DUR, WARM)
    warm = time.perf_counter() - t0
    assert not res["exhausted"].any()
    out.append(row("vectorsim/grid", cold, len(grid),
                   f"{len(cfgs)}cfgs x {len(CLIENTS)}clients x "
                   f"{len(seeds)}seeds = {len(grid)} cells in ONE call: "
                   f"cold={cold:.1f}s warm={warm:.1f}s "
                   f"steps={int(res['steps'][0])}"))

    # ---- the same grid through the device-sharded chunked dispatcher
    # (bit-identical results; on this CPU container device_count is 1 —
    # multi-device walls come from the forced-host-device CI smoke and
    # GPU/TPU runs)
    import jax
    t0 = time.perf_counter()
    sres = vs.simulate_grid_sharded(sims, grid, DUR, WARM, chunk=128)
    sh_wall = time.perf_counter() - t0
    np.testing.assert_array_equal(np.asarray(res["throughput"]),
                                  sres["throughput"])
    shard = sres["sharding"]
    out.append(row("vectorsim/sharded", sh_wall, len(grid),
                   f"devices={shard['devices']} "
                   f"kernel={shard['kernel']} chunk={shard['chunk']} "
                   f"{len(shard['chunks'])}chunks "
                   f"{len(grid)/max(sh_wall, 1e-9):.0f}cells/s "
                   f"wall={sh_wall:.1f}s (== unsharded grid bit-for-bit)"))

    # ---- DES reference: stratified sample, extrapolated to the full grid
    n_sample_seeds = 1 if quick else 2
    workers = os.cpu_count() or 1
    des_wall = 0.0
    errs_t, errs_m = [], []
    sample_args = []
    by_cell = {g: i for i, g in enumerate(grid)}
    for ci, (label, proto, pig) in enumerate(cfgs):
        for k in CLIENTS:
            d_t, d_m, d_w = [], [], 0.0
            for s in range(n_sample_seeds):
                sample_args.append((proto, pig, k, seeds[s]))
                tput, med, w = _des_unit(proto, pig, k, seeds[s])
                d_t.append(tput)
                d_m.append(med)
                d_w += w
            des_wall += d_w
            b_t = float(np.mean([res["throughput"][by_cell[(ci, k, s)]]
                                 for s in seeds]))
            b_m = float(np.mean([res["median_s"][by_cell[(ci, k, s)]]
                                 for s in seeds])) * 1e3
            errs_t.append(b_t / max(np.mean(d_t), 1e-9) - 1)
            errs_m.append(b_m / max(np.mean(d_m), 1e-9) - 1)
    sampled = len(sample_args)
    pool_speedup = _pool_speedup(sample_args, workers, des_wall)
    des_est_total = des_wall / sampled * len(grid)
    des_est_parallel = des_est_total / pool_speedup
    speedup = des_est_parallel / max(cold, 1e-9)
    speedup_serial = des_est_total / max(cold, 1e-9)
    out.append(row("vectorsim/speedup", des_wall, sampled,
                   f"batch={cold:.1f}s vs run.py --parallel est="
                   f"{des_est_parallel:.0f}s ({workers} workers, measured "
                   f"pool speedup {pool_speedup:.2f}x) -> {speedup:.0f}x "
                   f"({speedup_serial:.0f}x vs serial DES est "
                   f"{des_est_total:.0f}s)  "
                   f"[{sampled} DES units measured, {des_wall:.0f}s]"))
    max_t = max(abs(e) for e in errs_t)
    max_m = max(abs(e) for e in errs_m)
    out.append(row("vectorsim/xcheck", 0, 1,
                   f"DES overlap ({len(errs_t)} points): max |tput err|="
                   f"{max_t:.1%} max |median err|={max_m:.1%} "
                   f"(acceptance: <10%)"))

    # ---- the N=1025 sweep the DES cannot touch
    n_big_seeds = 4 if quick else 8
    big = vs.build_config("pigpaxos", 1025,
                          pig=PigConfig(n_groups=32, prc=1), label="N1025")
    big_grid = [(0, 60, s) for s in range(n_big_seeds)]
    t0 = time.perf_counter()
    bres = vs.simulate_grid([big], big_grid, DUR, WARM)
    big_wall = time.perf_counter() - t0
    bt = float(np.mean(bres["throughput"]))
    bm = float(np.mean(bres["median_s"])) * 1e3
    out.append(row("vectorsim/N=1025", big_wall, n_big_seeds,
                   f"PigPaxos N=1025 R=32 x {n_big_seeds} seeds: "
                   f"tput={bt:.0f}req/s median={bm:.2f}ms "
                   f"wall={big_wall:.1f}s (acceptance: <60s)"))

    payload = {
        "bench": "vectorsim",
        "grid": {"configs": [c[0] for c in cfgs], "clients": list(CLIENTS),
                 "seeds": len(seeds), "cells": len(grid),
                 "duration_s": DUR, "warmup_s": WARM,
                 "steps": int(res["steps"][0])},
        "batch": {"wall_cold_s": round(cold, 2),
                  "wall_warm_s": round(warm, 2)},
        "sharded": {"wall_s": round(sh_wall, 2),
                    "cells_per_s": round(len(grid) / max(sh_wall, 1e-9), 1),
                    "device_count": shard["devices"],
                    "kernel": shard["kernel"],
                    "chunk": shard["chunk"],
                    "chunks": [{"cells": m["cells"],
                                "wall_s": round(m["wall_s"], 3),
                                "steps": m["steps"]}
                               for m in shard["chunks"]]},
        "des_sample": {"units": sampled, "wall_s": round(des_wall, 1),
                       "est_total_s": round(des_est_total, 1),
                       "est_parallel_s": round(des_est_parallel, 1),
                       "workers": workers,
                       "pool_speedup_measured": round(pool_speedup, 2)},
        "speedup_vs_parallel_est": round(speedup, 1),
        "speedup_vs_serial_est": round(speedup_serial, 1),
        "xcheck": {"points": len(errs_t),
                   "max_abs_tput_err": round(max_t, 4),
                   "max_abs_median_err": round(max_m, 4)},
        "sweep1025": {"seeds": n_big_seeds, "wall_s": round(big_wall, 2),
                      "throughput": round(bt), "median_ms": round(bm, 3)},
    }
    with open(BENCH_PATH, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    out.append(row("vectorsim/json", 0, 1, f"wrote {BENCH_PATH}"))
    return out
