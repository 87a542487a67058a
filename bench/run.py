#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python bench/run.py --workload fig8-grid --seed 7 --seconds 30 --trace 0

Steps: refuse anything but a TPU with the chips the cell asks for; keep
JAX's compilation cache in the checkout (``repro.compile_cache``); build
the cell's configurations from its data files; warm up with one call on
the cell's own shapes; call the program's entry back to back for
``--seconds`` (a call that starts inside the window is finished and
counted); check a sample of the answers against the plain reference.

The last line of stdout is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device`` (and, with
``--trace 1``, ``breakdown``), then ``check``: every compared number
beside its limit.  The same numbers are the last lines of stderr.
``--trace 0`` reports the end-to-end metrics, ``--trace 1`` the
per-layer ones, read from a profiler trace of the window's first calls
(``harness.TRACE_S``).  There is no CPU fallback: without a TPU the run
exits 2 and prints no result.
"""
import time

T_ORIGIN = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def device_gate(chips: int) -> list:
    """The TPU devices, or exit 2 naming what JAX found instead."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        sys.exit(f"bench: JAX found no usable backend: {e}")
    found = (f"platform {devs[0].platform!r}, kind "
             f"{devs[0].device_kind!r}, {len(devs)} device(s)")
    if devs[0].platform != "tpu":
        print(f"bench: needs a TPU; JAX found {found}", file=sys.stderr)
        sys.exit(2)
    if len(devs) < chips:
        print(f"bench: the cell needs {chips} chips; JAX found {found}",
              file=sys.stderr)
        sys.exit(2)
    return devs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from bench import harness

    spec = harness.cell_spec(args.workload)
    devs = device_gate(spec["chips"])
    from repro import compile_cache

    print(f"[cache] {compile_cache.enable()}", file=sys.stderr, flush=True)
    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), devs, T_ORIGIN, spec=spec)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
