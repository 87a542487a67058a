"""Reduction of a profiler trace of the measured window's first calls.

The traced window is the span from the first ``bench.call`` to the end
of the last ``bench.readback``, both host spans the harness writes with
``jax.profiler.TraceAnnotation``.  On each device plane used, busy time is
the union of the operation intervals (line ``XLA Ops``) inside the
window, and the idle gaps are its complement; a traced call in which no
operation starts means that the profiler dropped events, and is an
error.  Per device the reduction
also sums each operation's and each program's (line ``XLA Modules``)
device time.  Each idle gap is labelled with the innermost harness span
the host was in at its midpoint.
"""
from __future__ import annotations

import glob
import os
import re

import numpy as np

DEVICE_PLANE = r"^/device:TPU:(\d+)$"
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
HOST_SPANS = ("bench.call", "bench.readback", "bench.check")
TOP = 10


def load(tdir: str):
    """The one ``.xplane.pb`` the profiler wrote under ``tdir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace file under {tdir}, "
                           f"found {paths}")
    return ProfileData.from_file(paths[0])


def host_spans(pd) -> list:
    """(name, start_ns, end_ns) of the harness's spans, by start."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in HOST_SPANS:
                    out.append((ev.name, ev.start_ns, ev.end_ns))
    return sorted(out, key=lambda s: s[1])


def device_planes(pd, n_devices: int, plane_re: str = DEVICE_PLANE) -> list:
    """The first ``n_devices`` device planes by device index."""
    found = []
    for plane in pd.planes:
        m = re.match(plane_re, plane.name)
        if m:
            found.append((int(m.group(1)) if m.groups() else 0, plane))
    found.sort(key=lambda t: t[0])
    return [p for _, p in found[:n_devices]]


def line_events(plane, name: str) -> list:
    """(name, start_ns, end_ns) of every event on lines called ``name``
    (a prefix match, so that one selector covers numbered lines)."""
    out = []
    for line in plane.lines:
        if not line.name.startswith(name):
            continue
        for ev in line.events:
            out.append((ev.name, ev.start_ns, ev.end_ns))
    return out


def union(intervals, lo: float, hi: float) -> tuple:
    """Total length of the union of ``intervals`` clipped to [lo, hi], and
    the gaps of [lo, hi] that no interval covers, as (start, end) pairs."""
    if not len(intervals):
        return 0.0, [(lo, hi)] if hi > lo else []
    iv = np.asarray(intervals, dtype=np.float64).reshape(-1, 2)
    iv = np.clip(iv, lo, hi)
    iv = iv[iv[:, 1] > iv[:, 0]]
    if not len(iv):
        return 0.0, [(lo, hi)] if hi > lo else []
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    reach = np.maximum.accumulate(iv[:, 1])
    # a new run starts where an interval begins past all earlier ends
    new = np.concatenate([[True], iv[1:, 0] > reach[:-1]])
    starts = iv[new, 0]
    ends = np.concatenate([reach[:-1][new[1:]], [reach[-1]]])
    busy = float(np.sum(ends - starts))
    edges = np.concatenate([[lo], ends]), np.concatenate([starts, [hi]])
    gaps = [(float(a), float(b)) for a, b in zip(*edges) if b > a]
    return busy, gaps


def short(name: str) -> str:
    """An operation's name without its HLO text: ``%seg_fanin_bf.11 =
    f32[...] custom-call(...)`` is ``seg_fanin_bf.11``."""
    return name.split(" = ", 1)[0].lstrip("%")


def totals(events, lo: float, hi: float) -> dict:
    """Device time per short event name, of the events that start in
    [lo, hi]."""
    out: dict = {}
    for name, s, e in events:
        if lo <= s <= hi:
            out[short(name)] = out.get(short(name), 0.0) + (e - s)
    return out


def check_coverage(ops, calls, plane: str) -> None:
    """Raise where a traced call saw no device operation start: the
    profiler's buffer was full and it dropped the rest of the trace."""
    starts = np.sort(np.asarray([s for _, s, _ in ops], dtype=np.float64))
    for i, (_, lo, hi) in enumerate(calls):
        if np.searchsorted(starts, hi, "right") == \
                np.searchsorted(starts, lo, "left"):
            raise RuntimeError(
                f"{plane}: no device operation in traced call {i + 1} of "
                f"{len(calls)}; the profiler dropped events, so fewer "
                f"calls have to be traced")


def label(gap, spans) -> str:
    """The innermost harness span around the gap's midpoint."""
    mid = 0.5 * (gap[0] + gap[1])
    inside = [s for s in spans if s[1] <= mid <= s[2]]
    if not inside:
        return "host:between-spans"
    return min(inside, key=lambda s: s[2] - s[1])[0]


def reduce(pd, n_devices: int, plane_re: str = DEVICE_PLANE,
           op_line: str = OP_LINE, module_line: str = MODULE_LINE) -> dict:
    """Busy and idle time, per-operation and per-program device time, and
    the breakdown of the measured window, in seconds.  None where the
    trace holds no harness span or no device plane."""
    spans = host_spans(pd)
    calls = [s for s in spans if s[0] == "bench.call"]
    planes = device_planes(pd, n_devices, plane_re)
    if not calls or not planes:
        return None
    lo = calls[0][1]
    hi = max(s[2] for s in spans if s[0] in ("bench.call", "bench.readback"))
    devices, op_sum = [], {}
    gaps0 = []
    for i, plane in enumerate(planes):
        ops = line_events(plane, op_line)
        check_coverage(ops, calls, plane.name)
        busy, gaps = union([(s, e) for _, s, e in ops], lo, hi)
        per_op = totals(ops, lo, hi)
        for k, v in per_op.items():
            op_sum[k] = op_sum.get(k, 0.0) + v
        devices.append({
            "plane": plane.name, "busy_s": busy * 1e-9,
            "ops_s": {k: v * 1e-9 for k, v in per_op.items()},
            "modules_s": {k: v * 1e-9 for k, v in
                          totals(line_events(plane, module_line),
                                 lo, hi).items()}})
        if i == 0:
            gaps0 = gaps
    n = len(devices)
    top_ops = sorted(op_sum.items(), key=lambda kv: -kv[1])[:TOP]
    top_gaps = sorted(gaps0, key=lambda g: g[0] - g[1])[:TOP]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(d["busy_s"] for d in devices) / n,
        "devices": devices,
        "breakdown": {
            "device_ops": [[k, v * 1e-9 / n] for k, v in top_ops],
            "idle_gaps": [[label(g, spans), (g[1] - g[0]) * 1e-9]
                          for g in top_gaps]}}


def reduce_dir(tdir: str, n_devices: int, **kw) -> dict:
    return reduce(load(tdir), n_devices, **kw)
