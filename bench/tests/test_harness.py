"""One run of a cell through the harness on the CPU: the result line's
schema, the counts behind the metrics, and the device gate."""
import json
import math
import os
import subprocess
import sys

import pytest

from bench import check, harness, trace
from bench.tests._tiny import run, tiny_spec

ROOT = harness.ROOT


@pytest.fixture(scope="module")
def plain():
    return run(tiny_spec("fig8-grid"))


@pytest.fixture(scope="module")
def traced():
    return run(tiny_spec("fig8-point"), trace=True)


def test_result_line_schema(plain):
    assert list(plain) == ["correct", "attempted", "failed", "metrics",
                           "device", "check"]
    assert plain["correct"] is True and plain["failed"] == 0
    assert plain["attempted"] == len(harness.call_grid(
        tiny_spec("fig8-grid"), 0, 0))
    assert set(plain["metrics"]) == {"sim_req_per_s", "setup_s"}
    for name, m in plain["metrics"].items():
        assert set(m) == {"value", "unit"} and m["value"] > 0, name
    assert plain["metrics"]["sim_req_per_s"]["unit"] == "req/s"
    dev = plain["device"]
    assert set(dev) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert list(plain["check"]) == list(check.NAMES)
    for v in plain["check"].values():
        assert set(v) == {"value", "limit"} and v["value"] <= v["limit"]
    json.dumps(plain)


def test_traced_line_reports_per_layer_metrics(traced):
    assert traced["correct"] is True
    # no TPU plane on the CPU: only the count-based metric is read
    assert set(traced["metrics"]) == {"slot_yield"}
    assert traced["metrics"]["slot_yield"]["unit"] == "%"
    assert "busy_s" not in traced["device"]


def test_slot_yield_counts_the_window():
    spec = tiny_spec("fig8-point")
    sims = harness.build_sims(spec)
    calls, _, _ = harness.window(spec, sims, 5, 0.0)
    (rec,) = calls
    slots = sum(math.ceil(int(s) / 8) * 8 for s in rec["steps"])
    want = 100.0 * int(rec["count"].sum()) / slots
    got = harness.load_module("metrics", "slot_yield").read(
        {"spec": spec, "calls": calls, "trace": None})
    assert got == pytest.approx(want)
    assert 0 < got < 100


def test_window_counts_every_started_call():
    spec = tiny_spec("fig8-point")
    sims = harness.build_sims(spec)
    calls, t0, t1 = harness.window(spec, sims, 9, 0.3)
    assert len(calls) >= 2 and t1 - t0 >= 0.3
    seeds = [g[2] for rec in calls for g in rec["grid"]]
    assert len(seeds) == len(set(seeds))


def test_window_traces_only_its_first_calls(tmp_path, monkeypatch):
    spec = tiny_spec("fig8-point")
    sims = harness.build_sims(spec)
    harness.run_entry(spec, sims, harness.call_grid(spec, 11, -1))
    monkeypatch.setattr(harness, "TRACE_S", 0.0)
    calls, _, _ = harness.window(spec, sims, 11, 3.0,
                                 trace_dir=str(tmp_path))
    assert len(calls) >= 2
    assert [c["traced"] for c in calls] == [True] + [False] * (len(calls) - 1)
    spans = trace.host_spans(trace.load(str(tmp_path)))
    assert [s[0] for s in spans] == ["bench.call", "bench.readback"]


def test_run_refuses_a_machine_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "fig8-point", "--seed", "1", "--seconds", "1"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "needs a TPU" in p.stderr and "'cpu'" in p.stderr


def _fake_reduce(ops_s):
    def reduce_dir(tdir, n_devices):
        dev = {"plane": "/device:TPU:0", "busy_s": 0.5, "ops_s": ops_s,
               "modules_s": {"jit__run_cells(1)": 0.5}}
        return {"window_s": 1.0, "busy_s": 0.5, "devices": [dev],
                "breakdown": {"device_ops": [[k, v] for k, v in
                                             ops_s.items()],
                              "idle_gaps": [["bench.readback", 0.5]]}}
    return reduce_dir


def test_traced_line_with_a_device_trace(monkeypatch):
    from bench import trace

    monkeypatch.setattr(trace, "reduce_dir",
                        _fake_reduce({"seg_fanin_bf.11": 0.1,
                                      "fusion.2": 0.3}))
    res = run(tiny_spec("fig8-point"), trace=True)
    assert set(res["metrics"]) == {"device_idle", "step_us", "fanin_us",
                                   "slot_yield"}
    assert res["metrics"]["device_idle"]["value"] == pytest.approx(50.0)
    assert res["device"]["busy_s"] == 0.5 and res["device"]["window_s"] == 1
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "breakdown", "check"]


def test_listed_metric_missing_from_a_device_trace_is_an_error(monkeypatch):
    """fanin_us lists fig8-point by name: a device trace without the
    kernel stops the run instead of leaving the metric out."""
    from bench import trace

    monkeypatch.setattr(trace, "reduce_dir", _fake_reduce({"fusion.2": 0.3}))
    with pytest.raises(RuntimeError, match="fanin_us"):
        run(tiny_spec("fig8-point"), trace=True)
