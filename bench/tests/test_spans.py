"""The batch backend's own spans and counters (``vectorsim.*``) in a
profiler trace recorded here on the CPU, and ``bench/spans.py``'s reduction
of them: idle time split by span, device time split by the scan's stage
scopes (read from the HLO protos the trace keeps), and the program's
numbers.  The stage scopes are also checked where they land, in the
program compiled for a TPU (tests/test_tpu_compile.py)."""
import glob
import os
import shutil
import tempfile

import jax
import numpy as np
import pytest

from bench import harness, spans, trace
from bench.metrics import _steps
from bench.tests._tiny import tiny_spec
from repro.core import vectorsim

CPU_PLANE = r"^/host:CPU$"
CPU_OPS = "tf_XLAPjRtCpuClient"


def _record(fn, raw: bool = False):
    """fn() under the profiler: its result and the trace (and, with
    ``raw``, the trace file's bytes)."""
    tdir = tempfile.mkdtemp(prefix="spans_test_")
    try:
        jax.profiler.start_trace(tdir)
        try:
            out = fn()
        finally:
            jax.profiler.stop_trace()
        if not raw:
            return out, trace.load(tdir)
        (path,) = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                            recursive=True)
        with open(path, "rb") as f:
            return out, trace.load(tdir), f.read()
    finally:
        shutil.rmtree(tdir, ignore_errors=True)


def _named(pd, name):
    return [s for s in spans.program_spans(pd) if s[0] == "vectorsim." + name]


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.fixture(scope="module", params=["fig8-point", "epaxos-conflict"])
def cell(request):
    """A tiny cell, its configurations and one grid, called once with the
    profiler off (which compiles) and once with it on."""
    spec = tiny_spec(request.param)
    sims = harness.build_sims(spec)
    grid = harness.call_grid(spec, 2 ** 31 + 5, 0)
    plain = harness.run_entry(spec, sims, grid)
    out, pd = _record(lambda: harness.run_entry(spec, sims, grid))
    return {"spec": spec, "sims": sims, "grid": grid, "plain": plain,
            "out": out, "trace": pd}


def test_a_call_nests_stack_run_and_readback_in_its_grid_span(cell):
    sims, grid, pd = cell["sims"], cell["grid"], cell["trace"]
    (g,) = _named(pd, "grid")
    (st,) = _named(pd, "stack")
    (bu,) = _named(pd, "budget")
    (run,) = _named(pd, "run")
    (rb,) = _named(pd, "readback")
    assert not _named(pd, "retry") and not _named(pd, "trace")
    for s in (st, bu, run, rb):
        assert _inside(s, g)
    assert st[2] <= bu[1] and bu[2] <= run[1] and run[2] <= rb[1]
    breq = min(8, max(k for _, k, _ in grid)) \
        if sims[0].kind == "group" else 1
    assert g[3] == {"passes": 1}
    assert st[3] == bu[3] == {}
    assert run[3] == {"scan_steps": -(-int(cell["out"]["steps"][0]) // breq)}
    assert rb[3] == {"exhausted": 0}


def test_outputs_are_the_same_with_the_profiler_on(cell):
    assert cell["plain"].keys() == cell["out"].keys()
    for k in cell["plain"]:
        np.testing.assert_array_equal(cell["plain"][k], cell["out"][k],
                                      err_msg=k)


def test_a_trace_of_the_program_shows_inside_its_run_span(cell):
    # one burst more than the call's own budget: a static step count no
    # other call uses, so the pass traces afresh
    cfg = cell["spec"]["config"]
    _, pd = _record(lambda: vectorsim.simulate_grid(
        cell["sims"], cell["grid"], cfg["duration_s"], cfg["warmup_s"],
        steps=int(cell["out"]["steps"][0]) + 8))
    (run,) = _named(pd, "run")
    (tr,) = _named(pd, "trace")
    assert _inside(tr, run) and tr[3] == {}
    assert spans.metrics(spans.program_spans(pd), {}, 1.0, 1)["retraces"] == 1


def _retried(spec, sims, grid, steps):
    cfg = spec["config"]
    out, pd = _record(lambda: vectorsim.simulate_grid(
        sims, grid, cfg["duration_s"], cfg["warmup_s"], steps=steps))
    return harness.keep(out, grid), pd


@pytest.fixture(scope="module")
def point():
    spec = tiny_spec("fig8-point")
    return spec, harness.build_sims(spec)


def test_a_retry_pass_has_its_own_run_and_readback(point):
    spec, sims = point
    # 20 clients finish in the first pass's budget, 60 need one retry
    grid = [(0, 20, 11), (0, 60, 11)]
    rec, pd = _retried(spec, sims, grid, 768)
    assert list(rec["steps"]) == [768, 1536]
    (retry,) = _named(pd, "retry")
    runs, rbs = _named(pd, "run"), _named(pd, "readback")
    assert len(runs) == len(rbs) == 2
    (g,) = _named(pd, "grid")
    assert g[3] == {"passes": 2} and retry[3] == {}
    assert [_inside(s, retry) for s in runs] == [False, True]
    assert [_inside(s, retry) for s in rbs] == [False, True]
    assert [s[3]["exhausted"] for s in rbs] == [1, 0]
    # the program's scan steps per pass are those bench/metrics/_steps.py
    # rebuilds from the outputs
    assert [s[3]["scan_steps"] for s in runs] == [96, 192]
    assert sum(s[3]["scan_steps"] for s in runs) == \
        _steps.scan_steps(spec, rec)


def test_scan_steps_of_a_call_whose_every_cell_retried(point):
    spec, sims = point
    grid = [(0, 60, 11), (0, 60, 12)]
    rec, pd = _retried(spec, sims, grid, 512)
    runs = _named(pd, "run")
    assert list(rec["steps"]) == [1024, 1024]
    assert [s[3]["scan_steps"] for s in runs] == [64, 128]
    assert len(_named(pd, "retry")) == 1
    # the outputs keep only the last budget, so the rebuild from them
    # counts the last pass alone: the program's counter is the exact one
    assert _steps.scan_steps(spec, rec) == 128


def test_a_sharded_grid_shows_one_chunk_span_per_chunk():
    spec = tiny_spec("fig8-point", seeds=3)
    sims = harness.build_sims(spec)
    grid = harness.call_grid(spec, 3, 0)
    cfg = spec["config"]
    out, pd = _record(lambda: vectorsim.simulate_grid_sharded(
        sims, grid, cfg["duration_s"], cfg["warmup_s"], chunk=2,
        devices=jax.devices()[:1]))
    chunks = _named(pd, "chunk")
    assert [c[3] for c in chunks] == [{}, {}]
    (g,) = _named(pd, "grid")
    assert g[3] == {"passes": 2}
    (bu,) = _named(pd, "budget")
    assert _inside(bu, g) and bu[2] <= chunks[0][1]
    for c in chunks:
        assert _inside(c, g)
        for name in ("stack", "run", "readback"):
            assert len([s for s in _named(pd, name) if _inside(s, c)]) == 1
    assert len(out["sharding"]["chunks"]) == 2


@pytest.mark.parametrize("gaps,spans_,want", [
    ([], [("bench.call", 0, 9)], {}),
    ([(0, 10)], [], {"host:between-spans": 10}),
    # nested spans: each idle instant goes to the innermost span only
    ([(0, 10), (20, 30), (50, 60)],
     [("bench.call", 0, 40), ("vectorsim.grid", 5, 40),
      ("vectorsim.stack", 5, 25)],
     {"bench.call": 5, "vectorsim.stack": 10, "vectorsim.grid": 5,
      "host:between-spans": 10}),
    # a gap that runs across two sibling spans is split between them
    ([(0, 100)], [("vectorsim.run", 10, 40), ("vectorsim.readback", 40, 90)],
     {"host:between-spans": 20, "vectorsim.run": 30,
      "vectorsim.readback": 50}),
])
def test_idle_in(gaps, spans_, want):
    got = spans.idle_in(gaps, spans_)
    assert got == pytest.approx(want)
    assert sum(got.values()) == pytest.approx(sum(b - a for a, b in gaps))




@pytest.mark.parametrize("op_name,want", [
    ("jit(_run_cells)/vmap()/while/body/closed_call/relay_acks/sort",
     "relay_acks"),
    # a transform keeps the scope inside its name
    ("jit(_run_cells)/vmap(summary)/reduce_sum", "summary"),
    ("jit(_run_cells)/vmap(jit(preaccept))/add", "preaccept"),
    # the first scope in the path names the stage
    ("a/conflict/keys/b", "conflict"),
    ("jit(_run_cells)/while/body/add", "other"),
    ("lt_to", "other"),
    ("", "other"),
])
def test_stage(op_name, want):
    assert spans.stage(op_name) == want


def _varint(n):
    out = b""
    while True:
        out += bytes([n & 0x7F | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _pb(*fields):
    """A protobuf message of (field number, int | str | bytes) pairs."""
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return out


def test_self_times():
    ops = [("while.1", 0, 100), ("a", 0, 30), ("b", 40, 100),
           ("c", 50, 60), ("d", 120, 130)]
    assert spans.self_times(ops) == [["while.1", 0, 10], ["a", 0, 30],
                                     ["b", 40, 50], ["c", 50, 10],
                                     ["d", 120, 10]]


def test_hlo_scopes_of_a_made_up_trace():
    # computation 1 (add.1, id 11; root scatter.2, id 12, with no
    # metadata) is fused into fusion.3 and 2 (root add.4, id 21) into
    # fusion.5: each fusion, with no metadata of its own, takes that of
    # its root or, failing that, of the root's nearest operand
    hlo = _pb((1, _pb(
        (1, "jit_f"),
        (3, _pb((1, "fused"), (5, 1), (6, 12),
                (2, _pb((1, "add.1"), (2, "add"), (35, 11),
                        (7, _pb((1, "add"), (2, "f/relay_acks/add"))))),
                (2, _pb((1, "scatter.2"), (2, "scatter"), (35, 12),
                        (36, _varint(11)))))),   # packed operand ids
        (3, _pb((1, "fused.1"), (5, 2), (6, 21),
                (2, _pb((1, "add.4"), (2, "add"), (35, 21),
                        (7, _pb((2, "f/commit/add"))))))),
        (3, _pb((1, "main"), (5, 3), (6, 32),
                (2, _pb((1, "fusion.3"), (2, "fusion"), (35, 31),
                        (38, _varint(1)))),
                (2, _pb((1, "fusion.5"), (2, "fusion"), (35, 32),
                        (38, 2))))))))        # one unpacked id
    xspace = _pb(
        (1, _pb((2, "/host:CPU"), (4, _pb((1, 3), (2, _pb((2, "x"))))))),
        (1, _pb((1, 9), (2, "/host:metadata"),
                (5, _pb((1, 7), (2, _pb((1, 7), (2, "Hlo Proto"))))),
                (4, _pb((1, 3), (2, _pb(
                    (1, 3), (2, "jit_f(3)"),
                    (5, _pb((1, 7), (6, hlo))))))))))
    assert spans.hlo_scopes(xspace) == {"jit_f(3)": {
        "add.1": "f/relay_acks/add", "scatter.2": "",
        "fusion.3": "f/relay_acks/add", "add.4": "f/commit/add",
        "fusion.5": "f/commit/add"}}
    assert spans.hlo_scopes(b"") == {}


class _Ev:
    def __init__(self, name, start, end, **stats):
        self.name, self.start_ns, self.end_ns = name, start, end
        self.duration_ns = end - start
        self.stats = list(stats.items())


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class _Profile:
    def __init__(self, planes):
        self.planes = planes


def _op(name, start, end):
    return _Ev(f"%{name} = f32[8] {name.split('.')[0]}()", start, end)


PROGRAM = "jit__run_cells(7)"
SCOPES = {PROGRAM: {
    "while.1": "jit(_run_cells)/while",
    "fusion.1": "jit(_run_cells)/while/body/relay_acks/sort",
    "fusion.2": "jit(_run_cells)/while/body/commit/add"}}


def _fake_profile():
    """Two traced calls; the device idles in stack, run and readback."""
    host = _Plane("/host:CPU", [_Line("python", [
        _Ev("bench.call", 0, 100), _Ev("bench.readback", 100, 110),
        _Ev("vectorsim.grid", 0, 100, passes=1),
        _Ev("vectorsim.stack", 0, 20),
        _Ev("vectorsim.run", 20, 30, scan_steps=50),
        _Ev("vectorsim.readback", 30, 100, exhausted=0),
        _Ev("bench.call", 110, 200), _Ev("bench.readback", 200, 210),
        _Ev("vectorsim.grid", 110, 200, passes=2),
        _Ev("vectorsim.stack", 110, 140),
        _Ev("vectorsim.run", 140, 145, scan_steps=50),
        _Ev("vectorsim.readback", 145, 147, exhausted=1),
        _Ev("vectorsim.retry", 147, 200),
        _Ev("vectorsim.run", 147, 150, scan_steps=100),
        _Ev("vectorsim.readback", 150, 200, exhausted=0)])])
    # the while loop holds the operations it runs, and its own 5 ns
    ops = [_op("while.1", 25, 90), _op("fusion.1", 25, 60),
           _op("fusion.2", 60, 90), _op("while.1", 145, 195),
           _op("fusion.1", 145, 165), _op("fusion.2", 170, 195)]
    modules = [_Ev(PROGRAM, 25, 90), _Ev(PROGRAM, 145, 195)]
    return _Profile([host, _Plane("/device:TPU:0", [
        _Line("XLA Modules", modules), _Line("XLA Ops", ops)])])


def test_attribute_on_a_made_up_trace():
    att = spans.attribute(_fake_profile(), 1, SCOPES)
    assert att["window_s"] == pytest.approx(210e-9)
    assert att["busy_s"] == pytest.approx(115e-9)
    assert att["idle_in"] == pytest.approx({
        "vectorsim.stack": 50e-9, "vectorsim.run": 10e-9,
        "vectorsim.readback": 15e-9, "bench.readback": 20e-9})
    assert sum(att["idle_in"].values()) == \
        pytest.approx(att["window_s"] - att["busy_s"])
    assert att["idle_gaps"][0] == ["vectorsim.stack",
                                   pytest.approx(55e-9)]
    # self times: they sum to the busy time
    assert att["stages_s"] == pytest.approx({"relay_acks": 55e-9,
                                             "commit": 55e-9,
                                             "other": 5e-9})
    assert att["metrics"] == pytest.approx({
        "stack_ms": 25e-6, "stack_idle": 100 * 50 / 210,
        "readback_idle": 100 * 15 / 210, "scan_steps": 100,
        "passes": 1.5, "exhausted": 0.5, "retraces": 0})


def test_stages_without_the_program_are_other():
    att = spans.attribute(_fake_profile(), 1, {"jit_g(1)": {}})
    assert att["stages_s"] == pytest.approx({"other": 115e-9})
    assert spans.attribute(_fake_profile(), 1)["stages_s"] == {}


def test_attribute_without_program_spans_reads_nothing():
    # the trace of a program that writes no vectorsim.* span
    pd = _fake_profile()
    host = pd.planes[0].lines[0]
    host.events = [e for e in host.events if e.name.startswith("bench.")]
    att = spans.attribute(pd, 1)
    assert att["spans"] == [] and att["metrics"] == {}
    assert sum(att["idle_in"].values()) == pytest.approx(95e-9)
    assert spans.attribute(_Profile([]), 1) is None


def test_attributed_idle_sums_on_a_recorded_trace(cell):
    spec, sims, grid = cell["spec"], cell["sims"], cell["grid"]
    recs = []

    def calls():
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.call"):
                out = harness.run_entry(spec, sims, grid)
            with jax.profiler.TraceAnnotation("bench.readback"):
                recs.append(harness.keep(out, grid))

    _, pd = _record(calls)
    att = spans.attribute(pd, 1, plane_re=CPU_PLANE, op_line=CPU_OPS)
    red = trace.reduce(pd, 1, plane_re=CPU_PLANE, op_line=CPU_OPS)
    assert att["window_s"] == pytest.approx(red["window_s"])
    assert att["busy_s"] == pytest.approx(red["busy_s"])
    assert sum(att["idle_in"].values()) == pytest.approx(
        att["window_s"] - att["busy_s"], rel=1e-9, abs=1e-12)
    assert {"vectorsim.grid", "vectorsim.stack", "vectorsim.run",
            "vectorsim.readback"} <= set(att["spans"])
    names = set(att["spans"]) | set(trace.HOST_SPANS) | {spans.BETWEEN}
    assert {n for n, _ in att["idle_gaps"]} <= names
    m = att["metrics"]
    assert m["stack_ms"] > 0
    assert 0 <= m["stack_idle"] + m["readback_idle"] <= \
        100 * (1 - att["busy_s"] / att["window_s"]) + 1e-9
    # the program's counters against what the outputs say
    assert m["scan_steps"] == _steps.scan_steps(spec, recs[0])
    assert (m["passes"], m["exhausted"], m["retraces"]) == (1, 0, 0)


KERNEL_STAGES = {
    "group": {"ingress", "relay_pick", "relay_fanout", "relay_acks",
              "commit", "state", "summary"},
    "epaxos": {"keys", "preaccept", "conflict", "exec_gate", "state",
               "summary"}}


def test_each_stage_scope_is_in_the_traced_program(cell):
    # the HLO protos the profiler keeps name every stage of the kernel
    spec, sims, grid = cell["spec"], cell["sims"], cell["grid"]
    _, _, raw = _record(lambda: harness.run_entry(spec, sims, grid),
                        raw=True)
    # (it keeps every program this process compiled)
    found = [{spans.stage(op) for op in ins.values()} - {spans.OTHER}
             for p, ins in spans.hlo_scopes(raw).items()
             if p.startswith("jit__run_cells(")]
    assert KERNEL_STAGES[sims[0].kind] in found
