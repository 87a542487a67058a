#!/usr/bin/env python3
"""The program's own spans in a profiler trace, the device's idle time
split by them, and the device's time split by the scan's stages.

The batch backend writes host spans named ``vectorsim.*`` with
``jax.profiler.TraceAnnotation``: ``grid`` around a call, ``stack``
around grid assembly, ``budget`` around the rate budget, ``run`` and
``readback`` around each pass of the scan, ``retry`` around an
exhausted-retry pass, ``chunk`` around a chunk of the sharded path, and
``trace`` around a retrace of the scan program.  Three carry counters as
span metadata: ``run`` its ``scan_steps``, ``readback`` the cells left
``exhausted`` by its pass, ``grid`` its ``passes``.  They land in the
same ``.xplane.pb`` as the device planes, on one clock.  Over the window
that ``trace.reduce`` measures (first ``bench.call`` to the end of the
last ``bench.readback``) this module reads:

* ``idle_in``: each device's idle time, every idle instant given to the
  innermost span the host was in (self time), so that nested spans count
  it once; what no span covers is ``host:between-spans``.  The parts sum
  to ``window_s - busy_s``.
* ``stages_s``: each device's operation self time by the scan stage
  (``jax.named_scope`` in ``_group_cell`` / ``_epaxos_cell``) in the
  operation's HLO ``op_name``, which the profiler keeps in the HLO proto
  of each program on its ``/host:metadata`` plane.
* ``metrics``: per traced call, ``stack_ms``, ``stack_idle``,
  ``readback_idle``, ``scan_steps``, ``passes`` and ``exhausted``, and
  the ``retraces`` in the window.

On the chip, it traces the first calls of a cell's window as
``bench/run.py --trace 1`` does and prints one JSON line:

    python3 bench/spans.py --workload fig8-point --seed 7
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import shutil
import sys
import tempfile

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __package__ in (None, ""):
    sys.path.insert(0, ROOT)

from bench import trace  # noqa: E402

PREFIX = "vectorsim."
BETWEEN = "host:between-spans"
# the scan's stage scopes; an operation outside all of them is "other"
STAGES = ("ingress", "relay_pick", "relay_fanout", "relay_acks", "commit",
          "state", "summary", "keys", "preaccept", "conflict", "exec_gate")
OTHER = "other"
_WRAPPED = re.compile(r"[\w.]+\((.*)\)")


def program_spans(pd) -> list:
    """(name, start_ns, end_ns, stats) of the program's spans, by start."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    out.append((ev.name, ev.start_ns, ev.end_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda s: s[1])


def idle_in(gaps, spans) -> dict:
    """Idle time (in the gaps' unit) per innermost span around it.

    ``gaps`` are disjoint (start, end) pairs in ascending order, ``spans``
    (name, start, end, ...) tuples; an instant inside several spans goes
    to the shortest, as ``trace.label`` labels a whole gap."""
    if not gaps:
        return {}
    g = np.asarray(gaps, dtype=np.float64).reshape(-1, 2)
    pts = [g.ravel()]
    if spans:
        pts.append(np.asarray([s[1:3] for s in spans], np.float64).ravel())
    pts = np.unique(np.concatenate(pts))
    pts = pts[(pts >= g[0, 0]) & (pts <= g[-1, 1])]
    a, b = pts[:-1], pts[1:]
    mid = 0.5 * (a + b)
    k = np.searchsorted(g[:, 0], mid, "right") - 1
    idle = (k >= 0) & (mid < g[np.maximum(k, 0), 1])
    names = [BETWEEN] + [s[0] for s in spans]
    owner = np.zeros(len(mid), np.int64)
    # paint outermost first, so that the shortest span around an instant
    # is the one left on it
    for i in sorted(range(len(spans)), key=lambda i: spans[i][1] -
                    spans[i][2]):
        owner[(mid >= spans[i][1]) & (mid <= spans[i][2])] = i + 1
    sums = np.bincount(owner[idle], weights=(b - a)[idle],
                       minlength=len(names))
    out: dict = {}
    for name, v in zip(names, sums):
        if v > 0:
            out[name] = out.get(name, 0.0) + float(v)
    return out


# --------------------------------------------------------------- HLO scopes
def _varint(buf, i: int) -> tuple:
    v = shift = 0
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << shift
        if b < 0x80:
            return v, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int, or a
    memoryview of a length-delimited value."""
    buf = memoryview(buf)
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _first(fields, number: int, default=b""):
    return next((v for f, v in fields if f == number), default)


def _ids(values) -> list:
    """The ints of a repeated int64 field, packed or not."""
    out = []
    for v in values:
        if isinstance(v, int):
            out.append(v)
            continue
        i = 0
        while i < len(v):
            n, i = _varint(v, i)
            out.append(n)
    return out


def _instructions(hlo_proto) -> dict:
    """{instruction name: op_name} of every computation of an
    ``xla.HloProto`` (hlo_module 1 > computations 3 > instructions 2 >
    name 1, opcode 2, metadata 7 > op_name 2).  A fusion without an
    op_name of its own takes its root's, or else the one of the
    instruction nearest its root that has one: XLA's scatter rewrite
    leaves some scatter roots without metadata (called computations 38,
    computation id 5 and root 6, instruction id 35, operands 36)."""
    module = _first(list(_fields(hlo_proto)), 1)
    roots, by_id = {}, {}
    for f, comp in _fields(module):
        if f != 3:
            continue
        cf = list(_fields(comp))
        roots[_first(cf, 5, 0)] = _first(cf, 6, 0)
        for g, ins in cf:
            if g != 2:
                continue
            fi = list(_fields(ins))
            by_id[_first(fi, 35, 0)] = (
                bytes(_first(fi, 1)).decode(), bytes(_first(fi, 2)).decode(),
                bytes(_first(list(_fields(_first(fi, 7))), 2)).decode(),
                _ids(v for h, v in fi if h == 36),
                _ids(v for h, v in fi if h == 38))
    out = {}
    for name, opcode, op_name, _, called in by_id.values():
        if not op_name and opcode == "fusion" and called:
            op_name = _nearest_op_name(by_id, roots.get(called[0]))
        out[name] = op_name
    return out


def _nearest_op_name(by_id: dict, root) -> str:
    """The op_name of ``root`` or of its nearest operand, breadth first,
    that has one."""
    queue, seen = [root], {root}
    for i in queue:
        ins = by_id.get(i)
        if ins is None:
            continue
        if ins[2]:
            return ins[2]
        for j in ins[3]:
            if j not in seen:
                seen.add(j)
                queue.append(j)
    return ""


def hlo_scopes(xspace: bytes) -> dict:
    """{program name: {instruction name: op_name}} from the HLO
    protos on the trace's ``/host:metadata`` plane (XSpace planes 1 >
    name 2, event_metadata 4, stat_metadata 5; an event metadata's name
    2 and stats 5; a stat's metadata_id 1 and bytes_value 6)."""
    for f, plane in _fields(xspace):
        if f != 1:
            continue
        pf = list(_fields(plane))
        if bytes(_first(pf, 2)).decode() != "/host:metadata":
            continue
        stat_names = {}
        for g, entry in pf:
            if g == 5:
                sm = list(_fields(_first(list(_fields(entry)), 2)))
                stat_names[_first(sm, 1, 0)] = bytes(_first(sm, 2)).decode()
        out = {}
        for g, entry in pf:
            if g != 4:
                continue
            em = list(_fields(_first(list(_fields(entry)), 2)))
            for h, stat in em:
                sf = list(_fields(stat)) if h == 5 else ()
                if sf and stat_names.get(_first(sf, 1, 0)) == "Hlo Proto":
                    out[bytes(_first(em, 2)).decode()] = _instructions(
                        _first(sf, 6))
        return out
    return {}


def stage(op_name: str) -> str:
    """The first stage scope in an ``op_name`` path, else ``other``; a
    transform keeps the scope inside its name (``vmap(summary)``)."""
    for part in op_name.split("/"):
        while (m := _WRAPPED.fullmatch(part)):
            part = m.group(1)
        if part in STAGES:
            return part
    return OTHER


def self_times(ops) -> list:
    """(name, start, self time) of nested (name, start, end) intervals:
    each one's length less its direct children's (a ``while`` operation
    holds the operations of its body)."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    out = [[name, s, e - s] for name, s, e in ops]
    stack: list = []
    for i, (_, s, e) in enumerate(ops):
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            out[stack[-1]][2] -= e - s
        stack.append(i)
    return out


def stages_s(plane, scopes: dict, lo: float, hi: float,
             op_line: str = trace.OP_LINE,
             module_line: str = trace.MODULE_LINE) -> dict:
    """Device self time (ns) by stage of the operations that start in
    [lo, hi], each looked up in the program whose ``module_line`` event
    holds it."""
    mods = sorted(trace.line_events(plane, module_line), key=lambda m: m[1])
    starts = np.asarray([m[1] for m in mods], np.float64)
    out: dict = {}
    for name, s, dt in self_times(trace.line_events(plane, op_line)):
        if not lo <= s <= hi:
            continue
        k = int(np.searchsorted(starts, s, "right")) - 1
        prog = scopes.get(mods[k][0], {}) if k >= 0 and s <= mods[k][2] \
            else {}
        key = stage(prog.get(trace.short(name), ""))
        out[key] = out.get(key, 0.0) + dt
    return out


# ---------------------------------------------------------------- reduction
def attribute(pd, n_devices: int, scopes: dict | None = None,
              plane_re: str = trace.DEVICE_PLANE,
              op_line: str = trace.OP_LINE) -> dict:
    """The window's idle time split by span, the device time split by
    stage (with ``scopes`` from ``hlo_scopes``) and the program's metrics,
    in seconds (``*_ms`` in ms, shares in %).  None where the trace holds
    no harness span or no device plane."""
    harness_spans = trace.host_spans(pd)
    calls = [s for s in harness_spans if s[0] == "bench.call"]
    planes = trace.device_planes(pd, n_devices, plane_re)
    if not calls or not planes:
        return None
    # trace.reduce's window
    lo = calls[0][1]
    hi = max(s[2] for s in harness_spans
             if s[0] in ("bench.call", "bench.readback"))
    prog = [s for s in program_spans(pd) if lo <= s[1] <= hi]
    spans = harness_spans + [s[:3] for s in prog]
    devices = []
    for plane in planes:
        ops = trace.line_events(plane, op_line)
        busy, gaps = trace.union([(s, e) for _, s, e in ops], lo, hi)
        devices.append({"busy_s": busy * 1e-9, "gaps": gaps,
                        "idle_in": idle_in(gaps, spans),
                        "stages": stages_s(plane, scopes, lo, hi, op_line)
                        if scopes else {}})
    n = len(devices)

    def mean(key):
        names = {k for d in devices for k in d[key]}
        avg = {k: sum(d[key].get(k, 0.0) for d in devices) * 1e-9 / n
               for k in names}
        return dict(sorted(avg.items(), key=lambda kv: -kv[1]))

    idle = mean("idle_in")
    window_s = (hi - lo) * 1e-9
    top_gaps = sorted(devices[0]["gaps"],
                      key=lambda g: g[0] - g[1])[:trace.TOP]
    return {
        "window_s": window_s,
        "busy_s": sum(d["busy_s"] for d in devices) / n,
        "calls": len(calls),
        "idle_in": idle,
        "idle_gaps": [[trace.label(g, spans), (g[1] - g[0]) * 1e-9]
                      for g in top_gaps],
        "stages_s": mean("stages"),
        "spans": sorted({s[0] for s in prog}),
        "metrics": metrics(prog, idle, window_s, len(calls)),
    }


def metrics(prog, idle: dict, window_s: float, calls: int) -> dict:
    """The program's numbers over ``calls`` traced calls; empty where the
    program wrote no ``vectorsim.stack`` span (a program without spans)."""
    def named(name):
        return [s for s in prog if s[0] == PREFIX + name]

    def per_call(name, stat):
        return sum(int(s[3][stat]) for s in named(name)) / calls

    stack = named("stack")
    if not stack:
        return {}
    return {
        "stack_ms": 1e-6 * sum(s[2] - s[1] for s in stack) / calls,
        "stack_idle": 100 * idle.get(PREFIX + "stack", 0.0) / window_s,
        "readback_idle": 100 * idle.get(PREFIX + "readback", 0.0)
        / window_s,
        "scan_steps": per_call("run", "scan_steps"),
        "passes": per_call("grid", "passes"),
        "exhausted": per_call("readback", "exhausted"),
        "retraces": len(named("trace"))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from bench import harness
    from bench.run import device_gate
    from repro import compile_cache

    spec = harness.cell_spec(args.workload)
    chips = spec["chips"]
    device = device_gate(chips)[0].device_kind
    compile_cache.enable()
    sims = harness.build_sims(spec)
    # the warm-up and the traced calls of bench/run.py --trace 1
    harness.run_entry(spec, sims, harness.call_grid(spec, args.seed, -1))
    tdir = tempfile.mkdtemp(prefix="bench_spans_")
    try:
        harness.window(spec, sims, args.seed, harness.TRACE_S,
                       trace_dir=tdir)
        pd = trace.load(tdir)
        (path,) = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                            recursive=True)
        with open(path, "rb") as f:
            scopes = hlo_scopes(f.read())
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    red = trace.reduce(pd, chips)
    att = attribute(pd, chips, scopes)
    print(json.dumps({
        "workload": args.workload, "seed": args.seed, "device": device,
        "device_idle": 100 * (1 - red["busy_s"] / red["window_s"]),
        "breakdown": red["breakdown"], **att}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
