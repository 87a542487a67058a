"""Benchmark driver: scenario families run through the experiment registry
(``repro.experiments``); framework benches stay one module each.

Prints ``name,us_per_call,derived`` CSV rows (the perf-trajectory contract).

- ``--full``          paper-length measurement windows
- ``--only M1,M2``    restrict to specific modules (legacy entry points)
- ``--filter GLOBS``  comma-separated fnmatch globs over *scenario* names
                      (e.g. ``'fig8/rotating/*,fig9/paxos'``; a bare family
                      name matches the whole family).  Skips the
                      non-scenario modules entirely.
- ``--parallel [N]``  run scenario units ((scenario, clients, seed) triples)
                      in an N-process pool (no N: one per CPU).  The DES is
                      single-threaded, so scenarios x seeds scale ~linearly
                      with cores.
- ``--list-scenarios``  print every registry entry and exit
- ``--json PATH``     persist all rows + the full experiments artifact
                      (per-seed replicates, summary stats) + the engine
                      events/sec numbers from BENCH_sim.json
"""
import argparse
import importlib
import json
import os
import sys
import time

MODULES = [
    "table1_message_load",
    "table2_message_load_small",
    "fig8_relay_groups",
    "fig9_latency_throughput",
    "fig10_wan",
    "fig11_small5",
    "fig12_cluster9",
    "fig13_payload",
    "fig14_prc",
    "fig15_graylist",
    "fig16_group_failure",
    "fig17_heatmap",
    "fault_scenarios",
    "extra_scenarios",
    "overload_scenarios",
    "obs_scenarios",
    "read_scenarios",
    "serialization_cost",
    "analytical_sweep",
    "sim_engine_bench",
    "vectorsim_bench",
    "collective_schedules",
    "kernel_bench",
    "roofline",
]

# A module that declares FAMILIES = [...] is a scenario-registry shim: its
# families' units all run in ONE suite pass (shared --parallel pool), then
# each module slot formats its families' legacy rows.  The mapping lives in
# the modules themselves — this driver just reads it.


def _scenario_families(module_name: str):
    try:
        mod = importlib.import_module(f"benchmarks.{module_name}")
    except Exception:   # noqa: BLE001  (unknown module: reported at run time)
        return None
    return getattr(mod, "FAMILIES", None)


def _parse_row(line: str) -> dict:
    name, us, derived = line.split(",", 2)
    try:
        us_val = float(us)
    except ValueError:
        us_val = None
    return {"name": name, "us_per_call": us_val, "derived": derived}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--filter", default=None, metavar="GLOBS",
                    help="comma-separated scenario-name globs; scenario "
                         "families only (framework benches are skipped)")
    ap.add_argument("--parallel", nargs="?", const=0, default=None, type=int,
                    metavar="N", help="pool size for scenario units "
                                      "(no value: one per CPU)")
    ap.add_argument("--list-scenarios", action="store_true")
    ap.add_argument("--backend", default=None, choices=("des", "batch"),
                    help="override the simulation backend: 'batch' runs "
                         "every batch-eligible scenario's whole grid as one "
                         "jitted vectorsim call; 'des' forces the DES")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write all rows (+ artifact + engine stats) to a "
                         "BENCH json")
    ap.add_argument("--plot", default=None, metavar="DIR",
                    help="render throughput-vs-load / latency-CDF SVGs for "
                         "every family that ran (dependency-free)")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="write the Perfetto trace-event JSON collected "
                         "from every traced scenario unit that ran (open "
                         "at https://ui.perfetto.dev)")
    args = ap.parse_args()

    from repro import compile_cache, experiments
    compile_cache.enable()

    if args.list_scenarios:
        for name in experiments.names():
            sc = experiments.get(name)
            print(f"{name}  [{sc.protocol} n={sc.n} grid={sc.grid_mode} "
                  f"engine={sc.engine}]")
        return

    processes = args.parallel
    if processes == 0:
        processes = os.cpu_count() or 1
    processes = processes or 0

    mods = MODULES if not args.only else args.only.split(",")
    mod_families = {m: _scenario_families(m) for m in mods}
    if args.filter:
        mods = [m for m in mods if mod_families[m]]
    quick = not args.full

    print("name,us_per_call,derived")
    t00 = time.time()
    failures = 0
    rows = []
    artifact = None

    # one suite pass over every selected scenario unit (shared pool)
    fams = [f for m in mods for f in (mod_families[m] or [])]
    if fams:
        t0 = time.time()
        try:
            artifact = experiments.run_families(
                fams, quick=quick, processes=processes,
                filter_expr=args.filter, backend_override=args.backend)
            n_units = sum(len(sa["units"]) for sa in artifact["scenarios"])
            print(f"# scenario suite: {len(artifact['scenarios'])} scenarios"
                  f", {n_units} units, processes={processes}, "
                  f"{time.time()-t0:.1f}s wall", flush=True)
        except Exception as e:   # noqa: BLE001
            failures += 1
            line = f"scenario_suite,0,ERROR: {type(e).__name__}: {e}"
            rows.append(_parse_row(line))
            print(line, flush=True)

    for m in mods:
        t0 = time.time()
        try:
            if mod_families[m]:
                if artifact is None:
                    continue   # suite itself failed; already reported
                lines = experiments.report.rows_for_artifact(
                    artifact, mod_families[m])
            else:
                mod = importlib.import_module(f"benchmarks.{m}")
                lines = mod.run(quick=quick)
            for line in lines:
                rows.append(_parse_row(line))
                print(line, flush=True)
        except Exception as e:   # noqa: BLE001
            failures += 1
            line = f"{m},0,ERROR: {type(e).__name__}: {e}"
            rows.append(_parse_row(line))
            print(line, flush=True)
        print(f"# {m} done in {time.time()-t0:.1f}s", flush=True)
    total = time.time() - t00
    print(f"# total {total:.1f}s, failures={failures}")
    if args.plot and artifact is not None:
        from repro.experiments import plot
        written = plot.render_artifact(artifact, args.plot)
        print(f"# wrote {len(written)} plots to {args.plot}")
    if args.trace and artifact is not None:
        # merge the per-unit Perfetto events the traced scenarios embedded
        # in their obs extras into one ui.perfetto.dev-openable file
        evs, traced_units = [], 0
        for sa in artifact["scenarios"]:
            for u in sa["units"]:
                pf = ((u.get("extras") or {}).get("obs") or {}) \
                    .get("perfetto")
                if pf and pf.get("events"):
                    evs.extend(pf["events"])
                    traced_units += 1
        with open(args.trace, "w") as f:
            json.dump({"traceEvents": evs, "displayTimeUnit": "ms",
                       "otherData": {"traced_units": traced_units}}, f)
        print(f"# wrote {len(evs)} trace events from {traced_units} "
              f"traced units to {args.trace}")
    if args.json:
        payload = {"rows": rows, "total_s": round(total, 1),
                   "failures": failures, "full": bool(args.full)}
        if artifact is not None:
            payload["experiments"] = artifact
        # fold in the engine events/sec trajectory if the engine bench ran
        try:
            from benchmarks.sim_engine_bench import BENCH_PATH
            if os.path.exists(BENCH_PATH):
                with open(BENCH_PATH) as f:
                    payload["sim_engine"] = json.load(f)
        except Exception:   # noqa: BLE001
            pass
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"# wrote {args.json}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
