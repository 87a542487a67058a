"""Whether the timed calls answered correctly.

After the window, a sample of the cells the window simulated, drawn from
the run's seed, is simulated again by the configuration's plain reference
(``bench/references/<reference>.py``, float64) from the same seeds, and
the program's answers are compared with the reference's.  Every cell of
every call is also checked for an exhausted step budget and for answers
that are not finite and positive.  Each compared number has a limit in
the traffic file; the run is correct when every number is within it.

Numbers (over the sampled cells unless said otherwise):

- ``count_gap``, ``committed_gap``, ``median_gap``, ``p99_gap``: relative
  gap between the program's and the reference's sums of completed
  requests, committed requests, median and p99 latency;
- ``cell_gap``: the widest relative gap of one cell's completed requests
  or median latency;
- ``load_gap``: the widest relative gap of one cell's messages per
  committed request at the leader or at a follower;
- ``bad_cells``: cells of any call that are exhausted or whose
  throughput, median or p99 is not finite and positive;
- ``dup_cells``: cells of any call whose completed and committed counts,
  median and p99 repeat those of another cell of the call bit for bit
  (every cell of a call has a seed of its own, so a sound run repeats
  none: an answer copied or made up for another cell does).
"""
from __future__ import annotations

import math

import numpy as np

from bench import harness

NAMES = ("count_gap", "committed_gap", "median_gap", "p99_gap", "cell_gap",
         "load_gap", "bad_cells", "dup_cells")


def sample(spec: dict, calls: list, seed: int) -> list:
    """(call, cell) pairs to check: per protocol point, one of the cells
    with the most clients and ``per_point - 1`` others, drawn from the
    seed over every call of the window."""
    rng = np.random.default_rng(np.random.SeedSequence([seed % 2 ** 63,
                                                        0x5EED]))
    per_point = int(spec["traffic"]["sample"]["per_point"])
    picks = []
    for ci in range(len(spec["traffic"]["points"])):
        cand = [(c, j) for c, rec in enumerate(calls)
                for j, g in enumerate(rec["grid"]) if g[0] == ci]
        if not cand:
            continue
        cand = [cand[i] for i in rng.permutation(len(cand))]
        kmax = max(calls[c]["grid"][j][1] for c, j in cand)
        first = next(p for p in cand if calls[p[0]]["grid"][p[1]][1] == kmax)
        picks.append(first)
        picks.extend([p for p in cand if p != first][:per_point - 1])
    return picks


def reference(spec: dict, calls: list, picks: list, dt=np.float64,
              max_steps=None) -> dict:
    """The reference's answers for the picked cells, in pick order;
    ``max_steps`` (per protocol point) caps a run that would not end."""
    cfg = spec["config"]
    ref = harness.load_module("references", cfg["reference"])
    pts = harness.points(spec)
    kmax = max(g[1] for rec in calls for g in rec["grid"])
    out = {}
    for ci, point in enumerate(pts):
        idx = [i for i, (c, j) in enumerate(picks)
               if calls[c]["grid"][j][0] == ci]
        if not idx:
            continue
        cells = []
        for i in idx:
            c, j = picks[i]
            _, k, s = calls[c]["grid"][j]
            cells.append((k, s * 1_000_003 + ci))
        # clients past a cell's own count never issue (+inf - +inf)
        with np.errstate(invalid="ignore"):
            res = ref.simulate(cfg, point, cells, pts, kmax, dt=dt,
                               max_steps=(max_steps or {}).get(ci, 0))
        out.setdefault("steps", {})[ci] = res["steps"]
        for key in ("count", "committed", "median_s", "p99_s", "m_leader",
                    "m_follower"):
            out.setdefault(key, np.full(len(picks), np.nan))[idx] = res[key]
        out["dtype"] = res["dtype"]
    return out


def program(calls: list, picks: list) -> dict:
    return {key: np.asarray([calls[c][key][j] for c, j in picks], float)
            for key in ("count", "committed", "median_s", "p99_s",
                        "m_leader", "m_follower")}


def _rel(a, b):
    return np.abs(np.asarray(a, float) - np.asarray(b, float)) \
        / np.maximum(np.abs(np.asarray(b, float)), 1e-300)


def numbers(prog: dict, ref: dict, calls: list) -> dict:
    """Every compared number (see the module docstring)."""
    nums = {}
    for key, name in (("count", "count_gap"), ("committed", "committed_gap"),
                      ("median_s", "median_gap"), ("p99_s", "p99_gap")):
        nums[name] = float(_rel(np.sum(prog[key]), np.sum(ref[key])))
    nums["cell_gap"] = float(np.max(np.maximum(
        _rel(prog["count"], ref["count"]),
        _rel(prog["median_s"], ref["median_s"]))))
    nums["load_gap"] = float(np.max(np.maximum(
        _rel(prog["m_leader"], ref["m_leader"]),
        _rel(prog["m_follower"], ref["m_follower"]))))
    nums["bad_cells"] = float(sum(bad_cells(calls)))
    nums["dup_cells"] = float(sum(dup_cells(calls)))
    # a NaN (no answer) fails every limit
    return {k: (v if math.isfinite(v) else math.inf) for k, v in nums.items()}


def bad_cells(calls: list) -> list:
    out = []
    for rec in calls:
        ok = ~rec["exhausted"].astype(bool)
        for key in ("throughput", "median_s", "p99_s"):
            v = rec[key].astype(float)
            ok &= np.isfinite(v) & (v > 0)
        out.append(int((~ok).sum()))
    return out


def dup_cells(calls: list) -> list:
    out = []
    for rec in calls:
        rows = zip(*(rec[k].tolist() for k in ("count", "committed",
                                               "median_s", "p99_s")))
        out.append(len(rec["grid"]) - len(set(rows)))
    return out


def judge(spec: dict, calls: list, seed: int) -> dict:
    limits = spec["traffic"]["limits"]
    picks = sample(spec, calls, seed)
    prog = program(calls, picks)
    ref = reference(spec, calls, picks)
    nums = numbers(prog, ref, calls)
    per_cell = np.maximum(_rel(prog["count"], ref["count"]),
                          _rel(prog["median_s"], ref["median_s"]))
    wrong = int(np.sum(~(per_cell <= limits["cell_gap"])))
    report = {k: {"value": nums[k], "limit": limits[k]} for k in NAMES}
    lines = [f"check {k} {nums[k]!r} limit {limits[k]!r}" for k in NAMES]
    return {"correct": all(nums[k] <= limits[k] for k in NAMES),
            "failed": int(nums["bad_cells"] + nums["dup_cells"]) + wrong,
            "sampled": len(picks),
            "numbers": report, "lines": lines}
