"""A run whose timed path is broken underneath comes out not correct.

Each fault wraps the program's public entry, so the harness drives it as
it drives the program: a step that returns its state unchanged (no
request ever completes), half of the batch left out with the mean of
the rest in its place, one answer altered where it is produced, and the
control: the float64 reference's model computed in bfloat16 in the
program's place.  Every cell of the call is sampled.  (The fault of an
exchange between chips left out belongs to a cell on four chips, and
no cell runs on more than one.)
"""
import ml_dtypes
import numpy as np
import pytest

from bench import check, harness
from bench.tests._tiny import run, tiny_spec
from repro.core import vectorsim

CELLS = {"fig8-grid": 4, "epaxos-conflict": 4, "fig8-point": 1}
FAULTS = ("state_unchanged", "half_batch", "answer_altered", "control")


def spec_for(name: str) -> dict:
    spec = tiny_spec(name, seeds=4 if CELLS[name] == 1 else 2)
    n = len(harness.call_grid(spec, 0, 0))
    spec["traffic"] = dict(spec["traffic"], sample={"per_point": n})
    return spec


def entry_name(spec: dict) -> str:
    return spec["traffic"]["entry"]


def state_unchanged(real, spec):
    def broken(configs, grid, duration, warmup, **kw):
        out = real(configs, grid, duration, warmup, **kw)
        n = len(grid)
        out = dict(out, count=np.zeros(n, np.int32),
                   committed=np.zeros(n, np.int32),
                   throughput=np.zeros(n, np.float32),
                   exhausted=np.ones(n, bool))
        for k in ("median_s", "p99_s", "m_leader", "m_follower"):
            out[k] = np.full(n, np.nan, np.float32)
        return out
    return broken


def half_batch(real, spec):
    def broken(configs, grid, duration, warmup, **kw):
        half = max(len(grid) // 2, 1)
        out = real(configs, grid[:half], duration, warmup, **kw)
        full = {}
        for k in harness.KEEP:
            v = np.asarray(out[k])
            fill = v.mean(axis=0).astype(v.dtype)
            full[k] = np.concatenate(
                [v, np.repeat(fill[None], len(grid) - half, axis=0)])
        return full
    return broken


def answer_altered(real, spec):
    def broken(configs, grid, duration, warmup, **kw):
        out = {k: np.array(v) for k, v in
               real(configs, grid, duration, warmup, **kw).items()}
        out["median_s"][len(grid) // 2] *= 1.5
        return out
    return broken


def control(real, spec):
    """Every cell answered by the reference computed in bfloat16."""
    def broken(configs, grid, duration, warmup, **kw):
        calls = [{"grid": grid}]
        picks = [(0, j) for j in range(len(grid))]
        ref = check.reference(spec, calls, picks)
        caps = {ci: 2 * s for ci, s in ref["steps"].items()}
        low = check.reference(spec, calls, picks, dt=ml_dtypes.bfloat16,
                              max_steps=caps)
        out = {k: np.asarray(low[k]) for k in ("count", "committed",
                                                "median_s", "p99_s",
                                                "m_leader", "m_follower")}
        out["throughput"] = out["count"] / duration
        out["exhausted"] = np.zeros(len(grid), bool)
        out["steps"] = np.full(len(grid), 8 * max(ref["steps"].values()))
        return out
    return broken


PLANT = {"state_unchanged": state_unchanged, "half_batch": half_batch,
         "answer_altered": answer_altered, "control": control}
CASES = [(c, f) for c in CELLS for f in FAULTS]


@pytest.mark.parametrize("cell", list(CELLS))
def test_sound_run_is_correct(cell):
    res = run(spec_for(cell))
    assert res["correct"] is True, res["check"]
    assert res["failed"] == 0


@pytest.mark.parametrize("cell,fault", CASES)
def test_fault_is_not_correct(cell, fault, monkeypatch):
    spec = spec_for(cell)
    name = entry_name(spec)
    real = getattr(vectorsim, name)
    monkeypatch.setattr(vectorsim, name, PLANT[fault](real, spec))
    res = run(spec)
    assert res["correct"] is False, (fault, res["check"])
