"""Cells of the benchmark cut to a size a CPU test run can hold: the same
configurations, points and limits, a short simulated window and few
seeds, one call per run (``seconds=0``)."""
import time

import jax

from bench import harness

WINDOW = {"warmup_s": 0.03, "duration_s": 0.06}


def tiny_spec(name: str, seeds: int = 2) -> dict:
    spec = harness.cell_spec(name)
    spec["config"] = dict(spec["config"], **WINDOW)
    spec["traffic"] = dict(spec["traffic"], seeds_per_call=seeds)
    return spec


def run(spec: dict, seed: int = 2 ** 31 + 17, trace: bool = False,
        seconds: float = 0.0) -> dict:
    """One run of the cell through the harness on the CPU device."""
    return harness.run_cell(spec["name"], seed, seconds, trace,
                            jax.devices(), time.perf_counter(), spec=spec,
                            log=lambda msg: None)
