"""One run of one benchmark cell: build, warm up, measure, check.

Everything that belongs to one cell is data found by name:
``BENCHMARK.json`` names the cell's configuration and traffic,
``bench/configs/<config>.json`` holds the deployment, ``bench/workloads/
<traffic>.json`` the grid each call submits, ``bench/references/
<reference>.py`` the plain reference of the configuration's model and
``bench/metrics/<metric>.py`` each per-layer reader.  The program is
reached only through its public entry points: ``build_config`` and the
grid entry the traffic names (``simulate_grid``).
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
# per-cell outputs the window keeps for the check and the metrics
KEEP = ("count", "committed", "median_s", "p99_s", "m_leader", "m_follower",
        "throughput", "exhausted", "steps")
SEED_MASK = 0x7FFFFFFF        # grid seeds the program turns into PRNG keys
TRACE_S = 0.5                 # a traced run traces the calls of its first
                              # half second (at least one whole call)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold '-' or '.')."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_spec(name: str, benchmark: dict | None = None) -> dict:
    """The cell's BENCHMARK.json entry with its configuration, traffic and
    the per-layer metrics it reports, all loaded by name."""
    bm = benchmark or load_json(ROOT, "BENCHMARK.json")
    entries = {w["name"]: w for w in bm["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(entries)})")
    entry = entries[name]
    cfg = load_json(BENCH_DIR, "configs", entry["config"] + ".json")
    traffic = load_json(BENCH_DIR, "workloads", entry["traffic"] + ".json")
    if traffic["config"] != entry["config"]:
        raise ValueError(f"traffic {entry['traffic']!r} is written for "
                         f"{traffic['config']!r}, not {entry['config']!r}")
    per_layer = [m for m in bm["per_layer"]
                 if name in m.get("workloads", [name])]
    return {"name": name, "chips": int(entry["chips"]), "config": cfg,
            "traffic": traffic, "per_layer": per_layer,
            "end_to_end": [m for m in bm["end_to_end"]
                           if name in m.get("workloads", [name])]}


def points(spec: dict) -> list:
    by_label = {p["label"]: p for p in spec["config"]["points"]}
    return [by_label[label] for label in spec["traffic"]["points"]]


def build_sims(spec: dict) -> list:
    """The traffic's protocol points lowered by the program's own
    ``build_config``, from the configuration file's numbers."""
    from repro.core import (CostModel, PigConfig, Topology, WorkloadConfig,
                            vectorsim)

    cfg = spec["config"]
    cost = CostModel(**cfg["cost_model"])
    topo = Topology(n=cfg["n"], **cfg["topology"])
    sims = []
    for p in points(spec):
        wl = WorkloadConfig(**dict(cfg["workload"], **p.get("workload", {})))
        pig = PigConfig(**p["pig"]) if "pig" in p else None
        sims.append(vectorsim.build_config(p["protocol"], cfg["n"], pig=pig,
                                           topo=topo, workload=wl, cost=cost,
                                           label=p["label"]))
    return sims


def call_seeds(seed: int, call: int, count: int) -> list:
    """Grid seeds of call ``call`` (-1 = the warm-up call) of a run."""
    ss = np.random.SeedSequence([seed % 2 ** 63, call + 1])
    seeds = [int(s) & SEED_MASK for s in ss.generate_state(2 * count)]
    return list(dict.fromkeys(seeds))[:count]


def call_grid(spec: dict, seed: int, call: int) -> list:
    """(point index, clients, grid seed) cells of one call: points x
    clients x seeds."""
    t = spec["traffic"]
    seeds = call_seeds(seed, call, int(t["seeds_per_call"]))
    return [(ci, int(k), s) for ci in range(len(t["points"]))
            for k in t["clients"] for s in seeds]


def run_entry(spec: dict, sims: list, grid: list) -> dict:
    """One call of the program's entry that the traffic names on the
    cell's grid."""
    from repro.core import vectorsim

    cfg = spec["config"]
    entry = getattr(vectorsim, spec["traffic"]["entry"])
    return entry(sims, grid, cfg["duration_s"], cfg["warmup_s"])


def keep(out: dict, grid: list) -> dict:
    rec = {k: np.array(out[k]) for k in KEEP}
    rec["grid"] = grid
    return rec


class CompileCounter:
    """Counts JAX traces and backend compiles (cache hits included) while
    it is entered."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.count = 0

    def _on(self, event: str, duration: float, **kwargs) -> None:
        if event in self.EVENTS:
            self.count += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)


def window(spec: dict, sims: list, seed: int, seconds: float,
           trace_dir: str | None = None) -> tuple:
    """Calls the entry back to back; a call that starts before
    ``seconds`` have passed is finished and counted.  Returns the kept
    outputs of every call and the window's start and end (host clock).

    With ``trace_dir`` the profiler records the window's first calls, up
    to the first that ends ``TRACE_S`` seconds into it, and each kept
    call says whether it was traced: the device's trace of a whole
    window of short calls outgrows the profiler's buffer, which then
    drops the later operations without a word."""
    import jax

    calls = []
    tracing = trace_dir is not None
    if tracing:
        jax.profiler.start_trace(trace_dir)
    t0 = time.perf_counter()
    try:
        while not calls or time.perf_counter() - t0 < seconds:
            grid = call_grid(spec, seed, len(calls))
            t_call = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.call"):
                out = run_entry(spec, sims, grid)
            with jax.profiler.TraceAnnotation("bench.readback"):
                calls.append(keep(out, grid))
            t_end = time.perf_counter()
            calls[-1]["wall_s"] = t_end - t_call
            calls[-1]["traced"] = tracing
            del out
            if tracing and t_end - t0 >= TRACE_S:
                tracing = False
                jax.profiler.stop_trace()
        t1 = time.perf_counter()
    finally:
        if tracing:
            jax.profiler.stop_trace()
    return calls, t0, t1


def memory_peak(devices: list) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def _metric(value: float, unit: str) -> dict:
    return {"value": value, "unit": unit}


def run_cell(name: str, seed: int, seconds: float, trace: bool, devices: list,
             t_origin: float, spec: dict | None = None,
             log=None) -> dict:
    """Everything of one run after the device gate: the result line."""
    import jax

    from bench import check
    from bench import trace as trace_mod

    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    spec = spec or cell_spec(name)
    used = devices[:spec["chips"]]
    sims = build_sims(spec)
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    with CompileCounter() as compiles:
        # warm-up: one whole call on the cell's own shapes, own seeds
        t_w = time.perf_counter()
        run_entry(spec, sims, call_grid(spec, seed, -1))
        log(f"[setup] warm-up call {time.perf_counter() - t_w}s, "
            f"{compiles.count} traces+compiles")
        before = compiles.count
        calls, w0, w1 = window(spec, sims, seed, seconds, trace_dir=tdir)
        in_window = compiles.count - before
    setup_s = w0 - t_origin
    peak = memory_peak(used)
    cells = sum(len(c["grid"]) for c in calls)
    done = float(sum(int(c["count"].sum()) for c in calls))
    log(f"[window] {len(calls)} calls, {cells} cells, {int(done)} simulated "
        f"requests in {w1 - w0}s; traces+compiles in the window: "
        f"{in_window}; exhausted-retry budgets: "
        f"{sum(int((c['steps'] != c['steps'].min()).sum()) for c in calls)}")
    walls = np.sort([c["wall_s"] for c in calls])
    log(f"[window] call walls: min {walls[0]}s, median "
        f"{float(np.median(walls))}s, max {walls[-1]}s")

    reduced = None
    if trace:
        with jax.profiler.TraceAnnotation("bench.reduce"):
            reduced = trace_mod.reduce_dir(tdir, n_devices=len(used))
        shutil.rmtree(tdir, ignore_errors=True)

    with jax.profiler.TraceAnnotation("bench.check"):
        t_c = time.perf_counter()
        verdict = check.judge(spec, calls, seed)
        log(f"[check] reference over {verdict['sampled']} sampled cells in "
            f"{time.perf_counter() - t_c}s")

    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": verdict["correct"], "attempted": cells,
              "failed": verdict["failed"]}
    if trace:
        ctx = {"spec": spec, "calls": calls, "trace": reduced,
               "traced": [c for c in calls if c["traced"]]}
        metrics = {}
        for m in spec["per_layer"]:
            value = load_module("metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = _metric(value, m["unit"])
            elif reduced is not None and name in m.get("workloads", ()):
                # listed for this cell by name: a device trace that holds
                # nothing for it is a fault, not a metric left out
                raise RuntimeError(f"per-layer metric {m['name']!r} is "
                                   f"listed for {name!r}, but its reader "
                                   f"found nothing in the device trace")
        result["metrics"] = metrics
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
    else:
        units = {m["name"]: m["unit"] for m in spec["end_to_end"]}
        result["metrics"] = {
            "sim_req_per_s": _metric(done / (w1 - w0), units["sim_req_per_s"]),
            "setup_s": _metric(setup_s, units["setup_s"])}
    result["device"] = device
    if trace and reduced is not None:
        result["breakdown"] = reduced["breakdown"]
    for line in verdict["lines"]:
        log(line)
    result["check"] = verdict["numbers"]
    return result
