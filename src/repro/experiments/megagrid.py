"""The ``megagrid`` study: the full N x R x PRC x conflict x WAN
cross-product as one million-cell batch-backend run.

The paper's analytical claim — throughput is maximized at one rotating
relay and the bottleneck shifts predictably with N, R and PRC (§6,
Eq. 1-3) — is only fully testable over the cross-product of all those
axes.  This module enumerates it:

* **group kernel** — Paxos plus rotating PigPaxos at every valid
  (N, R, PRC) combination of ``GROUP_N`` x ``R_AXIS`` x ``PRC_AXIS``;
* **epaxos kernel** — the conflict axis (``CONFLICT_AXIS`` hot-key rates)
  at ``EPAXOS_N``;
* **WAN** — every point twice: LAN and the fig10 three-region topology
  scaled to N (``wan3``);
* **clients x seeds** — the cell grid within each point (seeds are the
  replicate axis and the knob that scales the run to a target cell count).

Cells are executed by ``vectorsim.simulate_grid_sharded``: points are
bucketed by compiled signature (kernel kind, follower-axis size class,
client class, topology class) so the whole study compiles once per bucket,
then each bucket streams through the device-sharded runner chunk by chunk
(donated inputs, bounded device memory).  Results aggregate into ONE
``repro-experiments/v1`` artifact — per-point curve entries under the
``megagrid`` family plus a ``megagrid`` section with per-chunk walls,
cells/s, device count and kernel flag.

CLI:  ``python -m repro.experiments.megagrid --cells 1000000 --out FILE``
(``--preset smoke`` is the CI slice).  On GPU/TPU hosts the same command
shards across all visible devices; on CPU, multi-device execution is
forced with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List, Optional

import numpy as np

from .. import compile_cache
from ..core import PigConfig, WorkloadConfig
from ..core import vectorsim as vs
from .runner import ARTIFACT_SCHEMA, _agg
from .scenario import build_topology

# the committed 384-cell fig8-grid baseline this PR's acceptance is
# measured against (BENCH_vectorsim.json, PR 3): 31.3 s cold / 384 cells
BASELINE_PER_CELL_MS = 31.3e3 / 384

_WAN3_MS = [[0.15, 31, 35], [31, 0.15, 11], [35, 11, 0.15]]   # fig10

FULL_AXES = {
    "group_n": (5, 9, 17, 25),
    "r": (1, 2, 4, 8),
    "prc": (0, 1, 2),
    "epaxos_n": (5, 9, 17),
    "conflict": (0.0, 0.1, 0.5),
    "wan": ("lan", "wan3"),
    "clients": (2, 4, 8, 16),
}

# the CI slice: same code path (both kernels, both topology classes,
# sharded dispatch) at ~1/500 the cell count and 3 compiles
SMOKE_AXES = {
    "group_n": (5, 9),
    "r": (1, 2),
    "prc": (0, 1),
    "epaxos_n": (5,),
    "conflict": (0.0, 0.5),
    "wan": ("lan", "wan3"),
    "clients": (4,),
}

_TIMEOUT = {"lan": 50e-3, "wan3": 400e-3}   # retry_risk classification


def _topo_spec(wan: str, n: int) -> Optional[dict]:
    if wan == "lan":
        return None
    per = [n - 2 * (n // 3), n // 3, n // 3]
    return {"kind": "wan", "nodes_per_region": per, "oneway_ms": _WAN3_MS}


def build_points(axes: Dict = FULL_AXES) -> List[dict]:
    """One entry per config point of the cross-product: {name, kind, axes,
    cfg, weight} — clients x seeds fill the cell grid within each point.
    ``weight`` down-scales the seed allocation of expensive kinds."""
    pts = []
    for wan in axes["wan"]:
        for n in axes["group_n"]:
            topo = build_topology(_topo_spec(wan, n))
            pts.append(dict(
                name=f"paxos/N={n}/{wan}", kind="group", weight=1.0,
                axes=dict(protocol="paxos", n=n, wan=wan),
                cfg=vs.build_config("paxos", n, topo=topo,
                                    label=f"paxos/N={n}/{wan}")))
            for r in axes["r"]:
                if r > n - 1:
                    continue
                for prc in axes["prc"]:
                    pts.append(dict(
                        name=f"pig/N={n}/R={r}/PRC={prc}/{wan}",
                        kind="group", weight=1.0,
                        axes=dict(protocol="pigpaxos", n=n, r=r, prc=prc,
                                  wan=wan),
                        cfg=vs.build_config(
                            "pigpaxos", n, pig=PigConfig(n_groups=r, prc=prc),
                            topo=topo,
                            label=f"pig/N={n}/R={r}/PRC={prc}/{wan}")))
        for n in axes["epaxos_n"]:
            topo = build_topology(_topo_spec(wan, n))
            for c in axes["conflict"]:
                wl = (WorkloadConfig(key_dist="conflict", conflict_rate=c)
                      if c > 0 else WorkloadConfig())
                # epaxos pops one request per scan step (no burst batching)
                # -> ~8x the per-cell cost; give it 1/8 the seed budget
                pts.append(dict(
                    name=f"epaxos/N={n}/c={c}/{wan}", kind="epaxos",
                    weight=0.125,
                    axes=dict(protocol="epaxos", n=n, conflict=c, wan=wan),
                    cfg=vs.build_config(
                        "epaxos", n, topo=topo, workload=wl,
                        label=f"epaxos/N={n}/c={c}/{wan}")))
    return pts


def _bucket_key(pt: dict, k: int) -> tuple:
    """Compiled-signature bucket: kind + follower-axis size class + client
    class + topology class.  Everything inside one bucket shares padded
    shapes and a step budget, so it compiles exactly once."""
    n = pt["cfg"].n
    wan = pt["axes"]["wan"]
    kcls = 4 if k <= 4 else 16
    if pt["kind"] == "epaxos":
        return ("epaxos", n, kcls, wan)
    fcls = 8 if n <= 9 else 16 if n <= 17 else 24
    return ("group", fcls, kcls, wan)


# ------------------------------------------------------------------ the run
def run_megagrid(cells: int = 1_000_000, *, axes: Dict = FULL_AXES,
                 chunk: int = 4096, kernel: str = "auto",
                 duration: float = 0.1,
                 warmup: float = 0.05, progress=print) -> dict:
    """Run the cross-product study at >= ``cells`` total grid cells and
    return the ``repro-experiments/v1`` artifact (see module docstring).

    Memory is bounded by ``chunk`` (sharded dispatch donates each chunk's
    buffers); compile cost is one trace per bucket.  ``kernel`` passes
    through to ``simulate_grid_sharded``.
    """
    import jax

    t_start = time.perf_counter()
    pts = build_points(axes)
    kaxis = list(axes["clients"])
    wsum = sum(p["weight"] for p in pts) * len(kaxis)
    seeds = max(1, int(np.ceil(cells / wsum)))
    for p in pts:
        p["seeds"] = max(1, int(round(seeds * p["weight"])))

    buckets: Dict[tuple, List] = {}
    for pi, p in enumerate(pts):
        for k in kaxis:
            buckets.setdefault(_bucket_key(p, k), []).append((pi, k))

    acc: Dict[int, Dict[int, dict]] = {pi: {} for pi in range(len(pts))}
    bmeta, all_chunks = [], []
    total_cells = 0
    for bkey in sorted(buckets, key=str):
        pairs = buckets[bkey]
        pis = sorted({pi for pi, _ in pairs})
        cfgs = [pts[pi]["cfg"] for pi in pis]
        grid, spans = [], []
        for pi, k in pairs:
            s0 = len(grid)
            grid += [(pis.index(pi), k, s) for s in range(pts[pi]["seeds"])]
            spans.append((pi, k, s0, len(grid)))
        t0 = time.perf_counter()
        out = vs.simulate_grid_sharded(cfgs, grid, duration, warmup,
                                       chunk=chunk, kernel=kernel)
        wall = time.perf_counter() - t0
        for pi, k, lo, hi in spans:
            tput = out["throughput"][lo:hi]
            med = out["median_s"][lo:hi] * 1e3
            p99 = out["p99_s"][lo:hi] * 1e3
            to = _TIMEOUT[pts[pi]["axes"]["wan"]]
            acc[pi][k] = {
                "throughput": _agg([float(v) for v in tput]),
                "median_ms": _agg([float(v) for v in med]),
                "p99_ms": _agg([float(v) for v in p99]),
                "committed": int(out["committed"][lo:hi].sum()),
                "retry_risk_frac": float(
                    (out["p99_s"][lo:hi] >= to).mean()),
                "exhausted": int(out["exhausted"][lo:hi].sum()),
            }
        ncell = len(grid)
        total_cells += ncell
        steps = float(np.mean([m["steps"] for m in
                               out["sharding"]["chunks"]]))
        bmeta.append({"bucket": list(map(str, bkey)), "cells": ncell,
                      "wall_s": round(wall, 2), "steps": int(steps),
                      "chunks": len(out["sharding"]["chunks"])})
        all_chunks += [{"bucket": str(bkey), **m}
                       for m in out["sharding"]["chunks"]]
        if progress:
            progress(f"[megagrid] {bkey}: {ncell} cells in {wall:.1f}s "
                     f"({ncell / max(wall, 1e-9):.0f} cells/s)")

    wall_total = time.perf_counter() - t_start
    per_cell_ms = wall_total / max(total_cells, 1) * 1e3
    scenarios = []
    for pi, p in enumerate(pts):
        per_k = acc[pi]
        alln = [per_k[k]["throughput"] for k in per_k]
        scenarios.append({
            "name": f"megagrid/{p['name']}", "family": "megagrid",
            "grid_mode": "curve", "backend": "batch", "quick": False,
            "consistency": "model",
            "spec": {**p["axes"], "clients": kaxis, "seeds": p["seeds"],
                     "duration": duration, "warmup": warmup},
            "units": [],          # 10^6 raw units stay out of the artifact
            "replicates": [],
            "points": [{"clients": k, **per_k[k]}
                       for k in sorted(per_k)],
            "summary": {
                "throughput": _agg([a["mean"] for a in alln
                                    if a["mean"] is not None]),
                "median_ms": _agg(
                    [per_k[k]["median_ms"]["mean"] for k in per_k
                     if per_k[k]["median_ms"]["mean"] is not None]),
                "p99_ms": _agg(
                    [per_k[k]["p99_ms"]["mean"] for k in per_k
                     if per_k[k]["p99_ms"]["mean"] is not None]),
                "committed": sum(per_k[k]["committed"] for k in per_k),
                "cells": sum(a["n"] for a in alln),
            },
        })
    return {
        "schema": ARTIFACT_SCHEMA, "quick": False, "processes": 1,
        "scenarios": scenarios,
        "megagrid": {
            "cells": total_cells,
            "points": len(pts),
            "wall_s": round(wall_total, 1),
            "cells_per_s": round(total_cells / max(wall_total, 1e-9), 1),
            "per_cell_ms": round(per_cell_ms, 4),
            "baseline_per_cell_ms": round(BASELINE_PER_CELL_MS, 2),
            "speedup_per_cell": round(BASELINE_PER_CELL_MS / per_cell_ms, 1),
            "device_count": int(jax.device_count()),
            "backend": jax.default_backend(),
            "device_kind": jax.devices()[0].device_kind,
            "kernel": vs._resolve_kernel(kernel, "group"),
            "chunk": chunk,
            "duration_s": duration, "warmup_s": warmup,
            "buckets": bmeta,
            "chunk_walls": all_chunks,
        },
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cells", type=int, default=1_000_000)
    ap.add_argument("--preset", choices=("full", "smoke"), default="full")
    ap.add_argument("--chunk", type=int, default=4096)
    ap.add_argument("--kernel", default="auto",
                    choices=("auto", "lax", "pallas"))
    ap.add_argument("--duration", type=float, default=0.1)
    ap.add_argument("--warmup", type=float, default=0.05)
    ap.add_argument("--out", default="megagrid.json")
    args = ap.parse_args(argv)
    compile_cache.enable()
    axes = SMOKE_AXES if args.preset == "smoke" else FULL_AXES
    art = run_megagrid(args.cells, axes=axes, chunk=args.chunk,
                       kernel=args.kernel, duration=args.duration,
                       warmup=args.warmup)
    with open(args.out, "w") as f:
        json.dump(art, f, indent=1, sort_keys=True)
    mg = art["megagrid"]
    print(f"[megagrid] {mg['cells']} cells in {mg['wall_s']}s "
          f"({mg['cells_per_s']} cells/s, {mg['per_cell_ms']} ms/cell; "
          f"{mg['speedup_per_cell']}x the committed 384-cell baseline) "
          f"-> {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
