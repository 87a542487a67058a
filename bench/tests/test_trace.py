"""The trace reduction's arithmetic, on a trace recorded here on the CPU
and on made-up intervals, and the per-layer readers on made-up inputs."""
import numpy as np
import pytest

from bench import harness, trace

CPU_PLANE = r"^/host:CPU$"
CPU_OPS = "tf_XLAPjRtCpuClient"


@pytest.mark.parametrize("intervals,lo,hi,busy,gaps", [
    ([], 0, 10, 0, [(0, 10)]),
    ([(2, 4)], 0, 10, 2, [(0, 2), (4, 10)]),
    ([(2, 4), (3, 6), (8, 9)], 0, 10, 5, [(0, 2), (6, 8), (9, 10)]),
    ([(1, 9), (2, 3), (4, 5)], 0, 10, 8, [(0, 1), (9, 10)]),
    ([(-5, 2), (9, 20)], 0, 10, 3, [(2, 9)]),
    ([(0, 10)], 0, 10, 10, []),
    ([(5, 6), (1, 2)], 0, 10, 2, [(0, 1), (2, 5), (6, 10)]),
])
def test_union(intervals, lo, hi, busy, gaps):
    b, g = trace.union(intervals, lo, hi)
    assert b == busy and g == [tuple(map(float, x)) for x in gaps]


def test_label_picks_innermost_span():
    spans = [("bench.call", 0, 100), ("bench.readback", 40, 60)]
    assert trace.label((45, 55), spans) == "bench.readback"
    assert trace.label((10, 20), spans) == "bench.call"
    assert trace.label((200, 300), spans) == "host:between-spans"


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A trace of three annotated calls of a jitted program on the CPU."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sort(jnp.sin(x) @ x.T, axis=1))
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    tdir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(tdir)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.call"):
            f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.readback"):
            np.asarray(f(x))
    jax.profiler.stop_trace()
    return trace.load(tdir)


def test_reduction_of_a_recorded_trace(recorded):
    red = trace.reduce(recorded, 1, plane_re=CPU_PLANE, op_line=CPU_OPS)
    spans = trace.host_spans(recorded)
    assert [s[0] for s in spans].count("bench.call") == 3
    lo = spans[0][1]
    hi = max(s[2] for s in spans)
    assert red["window_s"] == pytest.approx((hi - lo) * 1e-9)
    # busy time by brute force: a 1 ns grid over the window
    plane = trace.device_planes(recorded, 1, CPU_PLANE)[0]
    ops = [(s, e) for _, s, e in trace.line_events(plane, CPU_OPS)]
    grid = np.zeros(int(hi - lo) + 1, bool)
    for s, e in ops:
        a, b = max(s, lo), min(e, hi)
        if b > a:
            grid[int(a - lo):int(b - lo)] = True
    assert red["busy_s"] == pytest.approx(grid.sum() * 1e-9,
                                          abs=2e-9 * len(ops))
    assert 0 < red["busy_s"] <= red["window_s"]
    gaps = red["breakdown"]["idle_gaps"]
    assert len(gaps) <= trace.TOP
    assert all(name in trace.HOST_SPANS or name == "host:between-spans"
               for name, _ in gaps)
    secs = [s for _, s in gaps]
    assert secs == sorted(secs, reverse=True)
    top = red["breakdown"]["device_ops"]
    assert top and [s for _, s in top] == sorted([s for _, s in top],
                                                  reverse=True)
    assert sum(red["devices"][0]["ops_s"].values()) >= top[0][1]


def test_short_names_drop_the_hlo_text():
    long = ("%seg_fanin_bf.11 = f32[8,8,128]{2,1,0} custom-call(f32[8,8,128]"
            " %pad.89), custom_call_target=\"tpu_custom_call\"")
    assert trace.short(long) == "seg_fanin_bf.11"
    assert trace.short("%fusion.3 = f32[4] fusion(%seg_fanin_bf.11)") == \
        "fusion.3"
    assert trace.short("jit__run_cells") == "jit__run_cells"


class _Ev:
    def __init__(self, name, start, end):
        self.name, self.start_ns, self.end_ns = name, start, end


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class _Profile:
    def __init__(self, planes):
        self.planes = planes


def _profile(op_times):
    """Three traced calls on the host; device operations at op_times."""
    host = _Plane("/host:CPU", [_Line("python", [
        _Ev("bench.call", 0, 100), _Ev("bench.readback", 100, 110),
        _Ev("bench.call", 110, 200), _Ev("bench.readback", 200, 210),
        _Ev("bench.call", 210, 300), _Ev("bench.readback", 300, 310)])])
    ops = [_Ev(f"%fusion.{i} = f32[8] fusion()", t, t + 50)
           for i, t in enumerate(op_times)]
    tpu = _Plane("/device:TPU:0", [_Line("XLA Ops", ops),
                                   _Line("XLA Modules", [])])
    return _Profile([host, tpu])


def test_reduction_of_a_complete_device_trace():
    red = trace.reduce(_profile([10, 120, 220]), 1)
    assert red["window_s"] == pytest.approx(310e-9)
    assert red["busy_s"] == pytest.approx(150e-9)
    assert set(red["devices"][0]["ops_s"]) == {"fusion.0", "fusion.1",
                                               "fusion.2"}


def test_a_trace_cut_short_is_an_error():
    # the profiler's buffer filled during the second call: the third call
    # holds no device operation, and the busy share would read too low
    with pytest.raises(RuntimeError, match="traced call 3 of 3"):
        trace.reduce(_profile([10, 120]), 1)


def test_no_device_plane_gives_nothing(recorded):
    assert trace.reduce(recorded, 1) is None       # no TPU plane here


def _ctx(name, modules, ops, steps, counts, busy=0.9, window=1.0):
    spec = harness.cell_spec(name)
    grid = [(0, 20, s) for s in range(len(steps))]
    rec = {"steps": np.asarray(steps), "count": np.asarray(counts),
           "grid": grid}
    devices = [{"modules_s": m, "ops_s": o} for m, o in zip(modules, ops)]
    return {"spec": spec, "calls": [rec], "traced": [rec],
            "trace": {"busy_s": busy, "window_s": window,
                      "devices": devices}}


def test_readers_arithmetic():
    ctx = _ctx("fig8-grid", [{"jit__run_cells": 0.8, "small": 0.1}],
               [{"seg_fanin_bf.11": 0.2, "fusion.1": 0.5}],
               steps=[800, 800], counts=[300, 500])
    read = {n: harness.load_module("metrics", n).read
            for n in ("device_idle", "step_us", "fanin_us", "slot_yield")}
    assert read["device_idle"](ctx) == pytest.approx(10.0)
    assert read["step_us"](ctx) == pytest.approx(0.8 / 100 * 1e6)
    assert read["fanin_us"](ctx) == pytest.approx(0.2 / 100 * 1e6)
    assert read["slot_yield"](ctx) == pytest.approx(100 * 800 / 1600)


def test_readers_with_retries_and_chips():
    # one exhausted cell retried at twice the budget: 100 + 200 steps
    ctx = _ctx("fig8-grid", [{"p": 3.0}, {"p": 1.5}],
               [{"seg_fanin_bf.3": 0.3}, {"seg_fanin_bf.3": 0.6}],
               steps=[800, 1600], counts=[10, 20])
    read = {n: harness.load_module("metrics", n).read
            for n in ("step_us", "fanin_us", "slot_yield")}
    assert read["step_us"](ctx) == pytest.approx(2.25 / 300 * 1e6)
    assert read["fanin_us"](ctx) == pytest.approx(0.45 / 300 * 1e6)
    assert read["slot_yield"](ctx) == pytest.approx(100 * 30 / 2400)


def test_readers_without_a_trace_or_kernel():
    ctx = _ctx("epaxos-conflict", [{"p": 1.0}], [{"fusion": 1.0}],
               steps=[100], counts=[40])
    assert harness.load_module("metrics", "fanin_us").read(ctx) is None
    # EPaxos pops one request per step
    assert harness.load_module("metrics", "step_us").read(ctx) == \
        pytest.approx(1e6 / 100)
    ctx["trace"] = None
    for n in ("device_idle", "step_us", "fanin_us"):
        assert harness.load_module("metrics", n).read(ctx) is None
