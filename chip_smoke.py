#!/usr/bin/env python3
"""Smoke run of the batch backend on a TPU, through the entry points a
user calls.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the sharded path only, four chips

One chip, in order:

1. the Pallas fan-in kernel (``kernels.ops.seg_fanin``) against its
   oracle at B in {4, 8} and F in {24, 1024}, compiled natively;
2. the ``vectorsim_bench`` fig8 grid (N=25, Paxos + rotating PigPaxos
   R in {2, 3, 5} at PRC=1, clients {20, 60, 120} x 32 seeds = 384
   cells) through ``simulate_grid``: the platform rule must pick the
   native Pallas fan-in (its compiled step holds a ``tpu_custom_call``),
   which must agree with the lax path, forced through the grid entries'
   ``_fanin`` hook;
3. ``experiments.run_scenarios`` on the gated DES <-> batch twins and
   the N=1025 scale cell, judged by the regression gate's fidelity
   windows and audit check (the DES is the independent reference).

Four chips: one 4096-cell chunk of the fig8 grid (seeds tiled) through
``simulate_grid_sharded`` over the four devices, bit-equal to
``simulate_grid`` on device 0.

Without a TPU the script exits 1: there is no CPU fallback.  A failed
phase raises, so the exit code is non-zero; the last line of stdout is
the JSON verdict only when every phase passed.  Compiled programs are
cached under ``$JAX_COMPILATION_CACHE_DIR`` when set, otherwise under
``<repo>/.jax_cache``.  All work runs in this one process.
"""
import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# fan-in shapes: (segments, segment size) -> F = 24 (N=25, R=3) and
# F = 1024 (N=1025, R=32); B = 4 is a 4-client burst, 8 the full burst
KERNEL_BURSTS = (4, 8)
KERNEL_SEGMENTS = ((3, 8), (32, 32))
GRID_SEEDS = 32
FIDELITY = ("wan/N=25", "conflict/N=25/c=0.1", "avail/leader/N=25",
            "reads/paxos/lease/r=0.9", "batching/paxos/m=8")
SCALE = "scale/batch/N=1025/R=32"
SHARD_CELLS = 4096


def log(msg: str) -> None:
    print(msg, flush=True)


def require(ok, what) -> None:
    """A check that holds under ``python -O`` too (unlike ``assert``)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def device_gate(chips: int):
    """The TPU devices, or exit 1 naming what JAX found instead."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found platform "
                 f"{devs[0].platform!r}")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} devices; JAX "
                 f"found {len(devs)}")
    log(f"[device] platform={devs[0].platform} kind={devs[0].device_kind} "
        f"count={len(devs)} jax={jax.__version__}")
    return devs


def fig8_grid(seeds: int):
    """The vectorsim_bench fig8 configs and a clients x seeds cell grid."""
    from benchmarks.vectorsim_bench import CLIENTS, _grid_configs
    from repro.core import vectorsim as vs

    cfgs = _grid_configs()
    sims = [vs.build_config(proto, 25, pig=pig, label=label)
            for label, proto, pig in cfgs]
    grid = [(ci, k, s) for ci in range(len(cfgs)) for k in CLIENTS
            for s in range(seeds)]
    return sims, grid


def _fanin_case(rng, B: int, G: int, gsize: int):
    """A vectorsim-shaped burst: contiguous segments, segment-constant
    coef/kcap, one +inf-masked slot per segment."""
    import jax.numpy as jnp
    import numpy as np

    F = G * gsize
    vals = rng.uniform(1.0, 2.0, (B, F)).astype(np.float32)
    vals[:, rng.integers(0, gsize, G) + np.arange(G) * gsize] = np.inf
    coef = np.repeat(rng.uniform(0.0, 1e-3, (B, G)), gsize, axis=1)
    kcap = np.repeat(rng.integers(0, gsize - 1, G), gsize)
    return (jnp.asarray(vals), jnp.asarray(coef, jnp.float32),
            jnp.asarray(np.repeat(np.arange(G), gsize)),
            jnp.asarray(kcap, jnp.float32), -0.5, 3e-4, 2e-5,
            jnp.ones((B,), jnp.float32))


def check_kernel(tag: str) -> None:
    import numpy as np
    from repro.kernels import ops, ref

    require(not ops._interpret(), "Pallas would run in interpret mode")
    rng = np.random.default_rng(0)
    for B in KERNEL_BURSTS:
        for G, gsize in KERNEL_SEGMENTS:
            args = _fanin_case(rng, B, G, gsize)
            got = np.asarray(ops.seg_fanin(*args))
            want = np.asarray(ref.seg_fanin_ref(*args))
            np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
            require(np.isfinite(got).all(), "a consumed segment max is inf")
            log(f"[kernel] {tag} seg_fanin B={B} F={G * gsize}: matches "
                f"the sort+segscan oracle (rtol=1e-6, atol=1e-6)")


def assert_native_step(sims, grid, out, dur: float, warm: float) -> None:
    """The compiled group step that ``out`` came from lowers the fan-in
    to a native TPU kernel (same static signature as ``simulate_grid``;
    the persistent cache serves the compile)."""
    from repro.core import vectorsim as vs

    batch, kind, kmax = vs._stack_cells(sims, grid, dur, warm)
    breq = min(8, kmax)
    steps = int(out["steps"].max())
    text = vs._run_cells.lower(batch, -(-steps // breq), kmax, kind, breq,
                               False, 0, "pallas", False,
                               False).compile().as_text()
    require("tpu_custom_call" in text, "group step has no native kernel")


def check_grid(tag: str) -> None:
    import numpy as np
    from benchmarks.vectorsim_bench import DUR, WARM
    from repro.core import vectorsim as vs

    kernel = vs._fanin("group")
    require(kernel == "pallas", f"the platform rule picked {kernel!r}")
    sims, grid = fig8_grid(GRID_SEEDS)
    walls = {}
    outs = {}
    platform = vs._fanin
    for name, fanin in (("platform", platform), ("lax", lambda kind: "lax")):
        vs._fanin = fanin
        try:
            t0 = time.perf_counter()
            outs[name] = vs.simulate_grid(sims, grid, DUR, WARM)
            walls[name + "_cold"] = time.perf_counter() - t0
            t0 = time.perf_counter()
            outs[name] = vs.simulate_grid(sims, grid, DUR, WARM)
            walls[name + "_warm"] = time.perf_counter() - t0
        finally:
            vs._fanin = platform
    for name, out in outs.items():
        require(not out["exhausted"].any(), f"{name}: exhausted cells")
        tput = out["throughput"]
        require(np.isfinite(tput).all() and (tput > 0).all(),
                f"{name}: throughput not finite and positive")
    pal, lax = outs["platform"], outs["lax"]
    np.testing.assert_allclose(pal["throughput"], lax["throughput"],
                               rtol=1e-5)
    for key in ("median_s", "p99_s"):
        np.testing.assert_allclose(pal[key], lax[key], rtol=1e-4)
    assert_native_step(sims, grid, pal, DUR, WARM)
    exact = int(np.all([pal[k] == lax[k] for k in ("throughput", "median_s",
                                                   "p99_s")], axis=0).sum())
    budget = int(pal["steps"].max())
    log(f"[grid] {tag} {len(grid)} cells, request budget {budget} per cell: "
        f"platform->pallas cold={walls['platform_cold']}s "
        f"warm={walls['platform_warm']}s; lax cold={walls['lax_cold']}s "
        f"warm={walls['lax_warm']}s (host wall, compile in cold)")
    log(f"[grid] {tag} pallas == lax: throughput rtol=1e-5, median/p99 "
        f"rtol=1e-4 ({exact}/{len(grid)} cells bit-equal); mean throughput "
        f"{float(np.mean(pal['throughput']))} req/s; compiled step holds "
        f"tpu_custom_call")


def check_fidelity(tag: str) -> None:
    from benchmarks import regression_gate as gate
    from repro import experiments

    names = [n for base in FIDELITY for n in (base, base + "/batch")]
    names.append(SCALE)
    t0 = time.perf_counter()
    art = experiments.run_scenarios([experiments.get(n) for n in names],
                                    quick=True, processes=0,
                                    ignore_quick_skip=True)
    wall = time.perf_counter() - t0
    seen = {sa["name"]: sa for sa in art["scenarios"]}
    require(sorted(seen) == sorted(names), f"scenarios run: {sorted(seen)}")
    for sa in art["scenarios"]:
        for u in sa["units"]:
            t = u["throughput"]
            require(t is not None and math.isfinite(t) and t > 0,
                    f"{sa['name']}: throughput {t}")
            require(not u.get("exhausted"), f"{sa['name']}: exhausted")
        log(f"[fidelity] {tag} {sa['name']}: backend={sa['backend']} "
            f"units={len(sa['units'])} "
            f"throughput={sa['summary']['throughput']['mean']} "
            f"consistency={sa['consistency']}")
    with open(gate.DEFAULT_BOUNDS) as f:
        ref = json.load(f)
    failures, lines = gate.evaluate(seen, {
        "bounds": {n: ref["bounds"][n] for n in names if n in ref["bounds"]},
        "fidelity": {b: ref["fidelity"][b] for b in FIDELITY}})
    for line in lines:
        log(f"[fidelity] {line}")
    require(not failures, failures)
    log(f"[fidelity] {tag} {len(names)} scenarios through run_scenarios in "
        f"{wall}s (host wall, DES included); gate passed")


def check_sharded(tag: str, devs) -> None:
    import jax
    import numpy as np
    from benchmarks.vectorsim_bench import DUR, WARM
    from repro.core import vectorsim as vs

    per_seed = len(fig8_grid(1)[1])
    sims, grid = fig8_grid(-(-SHARD_CELLS // per_seed))
    grid = grid[:SHARD_CELLS]
    t0 = time.perf_counter()
    got = vs.simulate_grid_sharded(sims, grid, DUR, WARM, chunk=SHARD_CELLS,
                                   devices=devs[:4])
    sh_wall = time.perf_counter() - t0
    sh = got["sharding"]
    require(sh["devices"] == 4 and len(sh["chunks"]) == 1, sh)
    t0 = time.perf_counter()
    with jax.default_device(devs[0]):
        want = vs.simulate_grid(sims, grid, DUR, WARM)
    one_wall = time.perf_counter() - t0
    require(not want["exhausted"].any() and not got["exhausted"].any(),
            "exhausted cells")
    for key in ("throughput", "median_s", "p99_s", "committed"):
        np.testing.assert_array_equal(want[key], got[key], err_msg=key)
    log(f"[sharded] {tag} {len(grid)} cells: simulate_grid_sharded over "
        f"{sh['devices']} devices (kernel={sh['kernel']}) == simulate_grid "
        f"on device 0, bit for bit (throughput, median_s, p99_s, "
        f"committed); wall sharded={sh_wall}s one-device={one_wall}s "
        f"(host wall, compile included)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded path, on four chips")
    args = ap.parse_args(argv)
    devs = device_gate(args.chips)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro import compile_cache

    log(f"[cache] {compile_cache.enable()}")
    tag = f"tpu/{devs[0].device_kind}"
    if args.chips == 4:
        check_sharded(tag, devs)
    else:
        check_kernel(tag)
        check_grid(tag)
        check_fidelity(tag)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
