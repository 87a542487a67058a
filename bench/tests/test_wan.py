"""The three-region WAN cell (``wan15-fig10``) at a size a CPU test run
can hold: the program against its plain reference (``group_wan``) and
the control, the planted faults of ``test_faults``, ``group_wan`` against
``group_lan`` on one region, and ``retry_steps`` against the program's
own span counters.

The window is 0.15 + 0.3 simulated seconds: ``_tiny.WINDOW`` is shorter
than one 63 ms round trip across the regions."""
import re

import ml_dtypes
import numpy as np
import pytest

from bench import calibrate, check, harness, spans
from bench.references import group_lan, group_wan
from bench.tests._tiny import run
from bench.tests.test_faults import FAULTS, PLANT
from bench.tests.test_spans import _named, _record
from repro.core import vectorsim

CELL = "wan15-fig10"
WINDOW = {"warmup_s": 0.15, "duration_s": 0.3}
SEED = 2 ** 31 + 29


def wan_spec(seeds: int = 2) -> dict:
    """The cell cut to the CPU's size, every cell of a call sampled."""
    spec = harness.cell_spec(CELL)
    spec["config"] = dict(spec["config"], **WINDOW)
    spec["traffic"] = dict(spec["traffic"], seeds_per_call=seeds)
    n = len(harness.call_grid(spec, 0, 0))
    spec["traffic"] = dict(spec["traffic"], sample={"per_point": n})
    return spec


@pytest.fixture(scope="module")
def case():
    spec = wan_spec()
    sims = harness.build_sims(spec)
    grid = harness.call_grid(spec, SEED, 0)
    calls = [harness.keep(harness.run_entry(spec, sims, grid), grid)]
    picks = check.sample(spec, calls, SEED)
    return spec, calls, picks, check.reference(spec, calls, picks)


def test_the_cell_runs_both_points_across_regions(case):
    spec, calls, picks, _ = case
    assert spec["config"]["reference"] == "group_wan"
    assert len(picks) == len(calls[0]["grid"]) == 16
    sims = harness.build_sims(spec)
    assert [s.region_latency.shape for s in sims] == [(3, 3), (3, 3)]
    assert list(sims[1].sizes) == [4, 5, 5]
    assert list(sims[1].thresh) == [3, 4, 4]
    # a commit waits for a remote region: every median is past 62 ms
    assert (calls[0]["median_s"] > 0.062).all()


def test_program_within_limits(case):
    spec, calls, picks, ref = case
    nums = check.numbers(check.program(calls, picks), ref, calls)
    limits = spec["traffic"]["limits"]
    for k in check.NAMES:
        assert nums[k] <= limits[k], (k, nums[k], limits[k])


def test_control_fails_a_limit(case):
    """The reference computed in bfloat16, in the program's place."""
    spec, calls, picks, ref = case
    nums = calibrate.control_numbers(spec, calls, picks, ref)
    limits = spec["traffic"]["limits"]
    assert any(nums[k] > limits[k] for k in check.NAMES), nums


def test_reference_repeats_exactly(case):
    spec, calls, picks, ref = case
    again = check.reference(spec, calls, picks)
    for key in ("count", "committed", "median_s", "p99_s", "m_leader",
                "m_follower"):
        np.testing.assert_array_equal(again[key], ref[key], err_msg=key)


def test_sound_run_is_correct():
    res = run(wan_spec(), seed=SEED)
    assert res["correct"] is True, res["check"]
    assert res["failed"] == 0


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_is_not_correct(fault, monkeypatch):
    spec = wan_spec()
    name = spec["traffic"]["entry"]
    real = getattr(vectorsim, name)
    monkeypatch.setattr(vectorsim, name, PLANT[fault](real, spec))
    res = run(spec, seed=SEED)
    assert res["correct"] is False, (fault, res["check"])


@pytest.mark.parametrize("name,label", [
    ("fig8-point", "pig_R3"), ("fig8-grid", "paxos"),
    ("fig8-grid", "pig_R2"), ("fig8-grid", "pig_R5")])
def test_one_region_gives_group_lans_answers(name, label):
    spec = harness.cell_spec(name)
    cfg = dict(spec["config"], warmup_s=0.03, duration_s=0.06)
    one = dict(cfg, topology=dict(cfg["topology"], region_of=[0] * cfg["n"],
                                  region_latency=[[cfg["topology"]
                                                   ["base_latency"]]]))
    pts = harness.points(spec)
    (point,) = [p for p in pts if p["label"] == label]
    cells = [(k, 1_000_003 * s + 1) for k in (20, 60) for s in (3, 4)]
    for dt in (np.float64, ml_dtypes.bfloat16):
        # clients past a cell's own count never issue (+inf - +inf)
        with np.errstate(invalid="ignore"):
            want = group_lan.simulate(cfg, point, cells, pts, 60, dt=dt,
                                      max_steps=60)
            got = group_wan.simulate(one, point, cells, pts, 60, dt=dt,
                                     max_steps=60)
        assert got["steps"] == want["steps"]
        for key in ("count", "committed", "median_s", "p99_s", "m_leader",
                    "m_follower"):
            np.testing.assert_array_equal(got[key], want[key], err_msg=key)


def test_explicit_groups_and_regions():
    spec = harness.cell_spec(CELL)
    cfg = spec["config"]
    paxos, pig = (group_wan.point_params(cfg, p)
                  for p in harness.points(spec))
    assert paxos["groups"] == [[f] for f in range(1, 15)]
    assert pig["groups"] == [[1, 2, 3, 4], [5, 6, 7, 8, 9],
                             [10, 11, 12, 13, 14]]
    assert (pig["thresh"], pig["majority"]) == ([3, 4, 4], 8)
    assert group_wan.pad_dims(cfg, harness.points(spec)) == {
        "groups": 14, "followers": 14}


def test_retry_steps_counts_the_program_retry_passes():
    """A budget the 120- and 200-client cells outgrow twice: the metric,
    from the outputs, equals the share the program's span counters give
    (first pass: every cell; each retry pass: its run span's ``cells``
    times its ``scan_steps``)."""
    spec = wan_spec()
    sims = harness.build_sims(spec)
    grid = [(ci, k, 7) for ci in range(2) for k in (10, 120, 200)]
    cfg = spec["config"]
    out, pd, raw = _record(lambda: vectorsim.simulate_grid(
        sims, grid, cfg["duration_s"], cfg["warmup_s"], steps=400), raw=True)
    rec = harness.keep(out, grid)
    assert list(rec["steps"]) == [400, 1600, 1600] * 2
    (g,) = _named(pd, "grid")
    assert g[3] == {"passes": 3, "regions": 3}
    runs = _named(pd, "run")
    assert [r[3] for r in runs] == [
        {"scan_steps": 50}, {"scan_steps": 100, "cells": 4},
        {"scan_steps": 200, "cells": 4}]
    retry = sum(r[3]["cells"] * r[3]["scan_steps"] for r in runs[1:])
    total = len(grid) * runs[0][3]["scan_steps"] + retry
    got = harness.load_module("metrics", "retry_steps").read(
        {"spec": spec, "calls": [rec]})
    assert got == pytest.approx(100.0 * retry / total)
    # the region lookups carry their own scope inside the stages
    ops = [op for p, ins in spans.hlo_scopes(raw).items()
           if p.startswith("jit__run_cells(") for op in ins.values()]
    regional = [op for op in ops if "regions" in op]
    assert regional
    assert {spans.stage(op) for op in regional} <= {"relay_fanout",
                                                    "relay_acks"}


def _op_names(sims, grid) -> list:
    """The op_name of every operation of the grid's scan program, as
    compiled here."""
    batch, kind, kmax = vectorsim._stack_cells(sims, grid, 0.06, 0.03)
    text = vectorsim._run_cells.lower(batch, steps=16, kmax=kmax, kind=kind,
                                      breq=min(8, kmax)).compile().as_text()
    return re.findall(r'op_name="([^"]*)"', text)


def test_a_lan_grid_has_no_region_counter_or_scope():
    spec = harness.cell_spec("fig8-point")
    sims = harness.build_sims(spec)
    grid = [(0, 20, 3)]
    _, pd = _record(lambda: vectorsim.simulate_grid(sims, grid, 0.06, 0.03))
    (g,) = _named(pd, "grid")
    assert g[3] == {"passes": 1}
    ops = _op_names(sims, grid)
    assert ops and not any("regions" in op for op in ops)
    wan = harness.build_sims(harness.cell_spec(CELL))
    assert any("regions" in op for op in _op_names(wan, [(1, 20, 3)]))
