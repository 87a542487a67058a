"""The plain references against the program, and the control against the
reference, at a size a CPU test run can hold."""
import ml_dtypes
import numpy as np
import pytest

from bench import calibrate, check, harness
from bench.tests._tiny import tiny_spec

CELLS = ("fig8-grid", "epaxos-conflict")


def one_call(name: str, seed: int):
    spec = tiny_spec(name)
    sims = harness.build_sims(spec)
    grid = harness.call_grid(spec, seed, 0)
    import jax

    out = harness.run_entry(spec, sims, grid)
    calls = [harness.keep(out, grid)]
    spec["traffic"] = dict(spec["traffic"], sample={"per_point": len(grid)})
    return spec, calls, check.sample(spec, calls, seed)


@pytest.fixture(scope="module", params=CELLS)
def case(request):
    spec, calls, picks = one_call(request.param, 2 ** 31 + 3)
    return spec, calls, picks, check.reference(spec, calls, picks)


def test_sample_covers_each_point_and_the_largest_cell(case):
    spec, calls, picks, _ = case
    pts = {calls[c]["grid"][j][0] for c, j in picks}
    assert pts == set(range(len(spec["traffic"]["points"])))
    assert len(set(picks)) == len(picks)


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
    for key in ("count", "median_s", "p99_s", "m_leader"):
        np.testing.assert_array_equal(again[key], ref[key])


@pytest.mark.parametrize("name", CELLS)
def test_control_stays_in_bfloat16(name):
    spec, calls, picks = one_call(name, 11)
    low = check.reference(spec, calls, picks[:1], dt=ml_dtypes.bfloat16,
                          max_steps={ci: 40 for ci in range(8)})
    assert low["dtype"] == "bfloat16"
