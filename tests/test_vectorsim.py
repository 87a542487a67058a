"""Batch-backend tests: DES<->batch tolerance on overlapping grid points,
message loads vs Eq. 1-3, bit-determinism under a fixed PRNGKey, and the
single-compilation guarantee across a grid (no per-cell retrace)."""
import numpy as np
import pytest

pytest.importorskip("jax")

from repro.core import Cluster, PigConfig, analytical, wan_topology
from repro.core import vectorsim as vs
from repro.core.pig import PigComm
from repro.experiments import runner
from repro.experiments.scenario import Scenario

DUR, WARM = 0.4, 0.2
SEEDS = (1, 2)


def _des_mean(protocol, n, pig, clients, topo=None, engine="fast",
              duration=DUR, warmup=WARM, **kw):
    t, m = [], []
    for s in SEEDS:
        c = Cluster(protocol, n, pig=pig, seed=s, engine=engine, topo=topo,
                    **kw)
        st = c.measure(duration=duration, warmup=warmup, clients=clients)
        t.append(st.throughput)
        m.append(st.median_ms)
    return float(np.mean(t)), float(np.mean(m))


def _batch_mean(units, clients):
    us = [u for u in units if u["clients"] == clients]
    return (float(np.mean([u["throughput"] for u in us])),
            float(np.mean([u["median_ms"] for u in us])))


# ------------------------------------------------------- DES <-> batch
def test_pigpaxos_matches_fast_engine_within_tolerance():
    pig = PigConfig(n_groups=3, prc=1)
    units = vs.simulate_scenario("pigpaxos", 25, pig=pig, clients=(20, 60),
                                 seeds=SEEDS, duration=DUR, warmup=WARM)
    for k in (20, 60):
        dt, dm = _des_mean("pigpaxos", 25, pig, k)
        bt, bm = _batch_mean(units, k)
        assert bt == pytest.approx(dt, rel=0.10), (k, dt, bt)
        assert bm == pytest.approx(dm, rel=0.10), (k, dm, bm)


def test_paxos_matches_fast_engine_within_tolerance():
    units = vs.simulate_scenario("paxos", 25, clients=(40,), seeds=SEEDS,
                                 duration=DUR, warmup=WARM)
    dt, dm = _des_mean("paxos", 25, None, 40)
    bt, bm = _batch_mean(units, 40)
    assert bt == pytest.approx(dt, rel=0.10)
    assert bm == pytest.approx(dm, rel=0.10)


def test_epaxos_matches_fast_engine():
    # the symmetric random-leader kernel is a coarser fit (conflict-free
    # fast path only): hold it to 12% throughput / 15% median
    units = vs.simulate_scenario("epaxos", 25, clients=(40,), seeds=SEEDS,
                                 duration=DUR, warmup=WARM)
    dt, dm = _des_mean("epaxos", 25, None, 40)
    bt, bm = _batch_mean(units, 40)
    assert bt == pytest.approx(dt, rel=0.12)
    assert bm == pytest.approx(dm, rel=0.15)


def test_wan_region_matrix_latency():
    """Three-region WAN: commit needs a remote region, so the latency floor
    is ~2x the 31ms one-way — and the batch backend matches the DES."""
    topo = {"npr": [5, 5, 5],
            "ms": [[0.15, 31, 35], [31, 0.15, 11], [35, 11, 0.15]]}
    groups = [[1, 2, 3, 4], [5, 6, 7, 8, 9], [10, 11, 12, 13, 14]]
    pig = PigConfig(n_groups=3, groups=groups, prc=1)
    units = vs.simulate_scenario(
        "pigpaxos", 15, pig=pig,
        topo=wan_topology(topo["npr"], topo["ms"]),
        clients=(20,), seeds=SEEDS, duration=DUR, warmup=WARM,
        leader_timeout=400e-3)
    bt, bm = _batch_mean(units, 20)
    assert 60.0 < bm < 70.0
    assert bt > 0


FIG10_GROUPS = [[1, 2, 3, 4], [5, 6, 7, 8, 9], [10, 11, 12, 13, 14]]


@pytest.mark.parametrize("protocol,pig", [
    ("paxos", None),
    ("pigpaxos", PigConfig(n_groups=3, groups=FIG10_GROUPS, prc=1))])
def test_fig10_wan_matches_fast_engine_within_tolerance(protocol, pig):
    """The paper's Fig. 10 deployment (15 nodes, 5 per region, the
    leader's clients in its region), 0.5 + 1.0 s: the batch twin within
    the gated [0.90, 1.10] window of the DES at 40 and 200 clients."""
    topo = wan_topology([5, 5, 5], [[0.15, 31, 35], [31, 0.15, 11],
                                    [35, 11, 0.15]])
    window = {"duration": 1.0, "warmup": 0.5, "leader_timeout": 0.4}
    units = vs.simulate_scenario(protocol, 15, pig=pig, topo=topo,
                                 clients=(40, 200), seeds=SEEDS, **window)
    for k in (40, 200):
        dt, dm = _des_mean(protocol, 15, pig, k, topo=topo, **window)
        bt, bm = _batch_mean(units, k)
        assert bt == pytest.approx(dt, rel=0.10), (k, dt, bt)
        assert bm == pytest.approx(dm, rel=0.10), (k, dm, bm)


# ------------------------------------------------------------ Eq. 1-3
def test_message_loads_match_analytical():
    for r in (1, 3, 5):
        units = vs.simulate_scenario(
            "pigpaxos", 25, pig=PigConfig(n_groups=r), clients=(20,),
            seeds=(7,), duration=0.3, warmup=0.15)
        u = units[0]
        assert u["leader_msgs_per_op"] == pytest.approx(
            analytical.leader_messages(r), abs=0.25)
        assert u["follower_msgs_per_op"] == pytest.approx(
            analytical.follower_messages(25, r), abs=0.25)
    u = vs.simulate_scenario("paxos", 25, clients=(20,), seeds=(7,),
                             duration=0.3, warmup=0.15)[0]
    assert u["leader_msgs_per_op"] == pytest.approx(2 * 24 + 2, abs=0.25)
    assert u["follower_msgs_per_op"] == pytest.approx(2.0, abs=0.25)


def test_required_per_group_shared_with_pigcomm():
    """The batch backend and the DES comm layer consume the SAME §4.1
    threshold implementation (pig.required_per_group) — and PigComm's
    delegating method agrees with it."""
    from repro.core.pig import partition_followers, required_per_group
    assert vs.required_per_group is required_per_group
    assert vs.partition_followers is partition_followers
    for n, r, prc, sgm in ((25, 3, 1, False), (25, 8, 3, False),
                           (25, 1, 0, True), (9, 2, 1, False)):
        cfg = PigConfig(n_groups=r, prc=prc, single_group_majority=sgm)
        pc = PigComm.__new__(PigComm)
        pc.cfg = cfg
        pc.all_nodes = list(range(n))
        groups = partition_followers([i for i in range(1, n)], r)
        assert PigComm._partition([i for i in range(1, n)], r) == groups
        assert (required_per_group(groups, n, prc, sgm)
                == pc._required_per_group(groups))


# ------------------------------------------------------- determinism
def test_bit_determinism_under_fixed_key():
    kw = dict(pig=PigConfig(n_groups=3, prc=1), clients=(10, 20),
              seeds=(0, 1), duration=0.15, warmup=0.05)
    a = vs.simulate_scenario("pigpaxos", 25, **kw)
    b = vs.simulate_scenario("pigpaxos", 25, **kw)
    assert a == b  # bit-identical, not approx


def test_seeds_differ():
    units = vs.simulate_scenario("pigpaxos", 25,
                                 pig=PigConfig(n_groups=3, prc=1),
                                 clients=(20,), seeds=(0, 1),
                                 duration=0.15, warmup=0.05)
    # two seeds can commit the same count in a short window (equal
    # throughput); their latency distributions still differ
    a, b = units[0], units[1]
    assert (a["median_ms"], a["p99_ms"]) != (b["median_ms"], b["p99_ms"])


# ------------------------------------------------ compilation contract
def test_single_compilation_across_grid():
    """A whole multi-config grid is ONE trace, and re-running the same
    shapes hits the jit cache (no per-cell retrace)."""
    cfgs = [vs.build_config("pigpaxos", 9, pig=PigConfig(n_groups=2)),
            vs.build_config("pigpaxos", 9, pig=PigConfig(n_groups=4))]
    grid = [(ci, k, s) for ci in range(2) for k in (4, 8) for s in (0, 1, 2)]
    before = vs.trace_counts()
    out = vs.simulate_grid(cfgs, grid, 0.1, 0.05)
    after = vs.trace_counts()
    new = {k: v - before.get(k, 0) for k, v in after.items()
           if v != before.get(k, 0)}
    assert sum(new.values()) == 1, new          # one compile for 12 cells
    assert not out["exhausted"].any()
    out2 = vs.simulate_grid(cfgs, grid, 0.1, 0.05)
    assert vs.trace_counts() == after           # cache hit on re-run
    assert np.array_equal(out["throughput"], out2["throughput"])


def test_exhausted_grid_retries_with_larger_budget():
    cfg = vs.build_config("pigpaxos", 9, pig=PigConfig(n_groups=2))
    out = vs.simulate_grid([cfg], [(0, 8, 0)], 0.2, 0.05, steps=32)
    assert not out["exhausted"].any()
    assert out["steps"][0] > 32                 # budget was doubled


# ------------------------------------------------- sharded dispatch
def _small_grid():
    cfgs = [vs.build_config("pigpaxos", 9, pig=PigConfig(n_groups=2, prc=1)),
            vs.build_config("paxos", 9)]
    grid = [(ci, k, s) for ci in range(2) for k in (4, 8) for s in range(6)]
    return cfgs, grid


def test_sharded_equals_unsharded_single_device():
    """chunked sharded dispatch == the one-call grid, bit for bit (this
    process sees one device; the 4-device check is the subprocess test)."""
    cfgs, grid = _small_grid()
    want = vs.simulate_grid(cfgs, grid, 0.1, 0.05)
    for chunk in (64, 7):                       # one chunk / ragged chunks
        got = vs.simulate_grid_sharded(cfgs, grid, 0.1, 0.05, chunk=chunk)
        for key in ("throughput", "median_s", "p99_s", "committed"):
            np.testing.assert_array_equal(np.asarray(want[key]), got[key],
                                          err_msg=f"chunk={chunk} {key}")
        sh = got["sharding"]
        assert sh["devices"] >= 1
        assert sum(m["cells"] for m in sh["chunks"]) == len(grid)
        assert all(m["wall_s"] > 0 for m in sh["chunks"])


def test_sharded_exhausted_cells_retry():
    cfgs, _ = _small_grid()
    out = vs.simulate_grid_sharded(cfgs, [(0, 8, 0), (1, 8, 1)], 0.2, 0.05,
                                   steps=32, chunk=2)
    assert not out["exhausted"].any()
    assert (out["steps"] > 32).all()


def test_sharded_grid_multidevice_subprocess():
    """shard_map over 4 forced host devices == single device,
    bit for bit, chunked and unchunked (subprocess keeps pytest's own
    jax single-device)."""
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run(
        [sys.executable, "tests/shard_worker.py"],
        capture_output=True, text=True, env=env, timeout=600,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "OK all" in r.stdout


# ------------------------------------------------- pallas fan-in kernel
def _use_fanin(monkeypatch, kernel):
    """Force the grid entries' fan-in through their hook."""
    monkeypatch.setattr(vs, "_fanin", lambda kind: kernel)


def test_kernel_pallas_matches_lax_path(monkeypatch):
    """The Pallas segmented fan-in kernel is a drop-in for the sort-based
    lax path: same grid, same tolerances as the DES cross-check."""
    pig = PigConfig(n_groups=3, prc=1)
    kw = dict(pig=pig, clients=(10, 20), seeds=(0, 1),
              duration=0.15, warmup=0.05)
    _use_fanin(monkeypatch, "lax")
    lax_u = vs.simulate_scenario("pigpaxos", 25, **kw)
    _use_fanin(monkeypatch, "pallas")
    pal_u = vs.simulate_scenario("pigpaxos", 25, **kw)
    for a, b in zip(lax_u, pal_u):
        assert b["throughput"] == pytest.approx(a["throughput"], rel=1e-5)
        assert b["median_ms"] == pytest.approx(a["median_ms"], rel=1e-4)
        assert b["p99_ms"] == pytest.approx(a["p99_ms"], rel=1e-4)


def test_kernel_pallas_multigroup_and_faulty(monkeypatch):
    """Kernel parity holds across R (segment shapes) and under fault masks
    (down followers = +inf arrivals, the kernel's masked-slot path)."""
    from repro.faults import crash_window

    def both(cfgs, grid, dur, warm):
        outs = []
        for kernel in ("lax", "pallas"):
            _use_fanin(monkeypatch, kernel)
            outs.append(vs.simulate_grid(cfgs, grid, dur, warm))
        return outs

    for r in (1, 4):
        cfgs = [vs.build_config("pigpaxos", 13, pig=PigConfig(n_groups=r))]
        grid = [(0, 8, s) for s in range(4)]
        a, b = both(cfgs, grid, 0.1, 0.05)
        np.testing.assert_allclose(np.asarray(a["throughput"]),
                                   np.asarray(b["throughput"]), rtol=1e-5)
    masks = crash_window(5, 0.02, 0.08).to_masks(13, 0.2)
    cfgs = [vs.build_config("pigpaxos", 13, pig=PigConfig(n_groups=3),
                            masks=masks)]
    grid = [(0, 8, s) for s in range(4)]
    a, b = both(cfgs, grid, 0.2, 0.0)
    np.testing.assert_allclose(np.asarray(a["throughput"]),
                               np.asarray(b["throughput"]), rtol=1e-5)


def test_resolve_kernel(monkeypatch):
    """The platform picks the fan-in: the Pallas kernel for the group
    kernel on a TPU, the lax path otherwise; the epaxos kernel, which has
    no grouped fan-in, always gets lax.  Both grid entries read the rule
    through the ``_fanin`` hook, which their shared plan carries."""
    assert vs._fanin is vs._resolve_kernel
    for backend, want in (("tpu", "pallas"), ("cpu", "lax"), ("gpu", "lax")):
        monkeypatch.setattr(vs.jax, "default_backend", lambda: backend)
        assert vs._resolve_kernel("group") == want
        assert vs._resolve_kernel("epaxos") == "lax"
    monkeypatch.undo()
    cfgs = [vs.build_config("pigpaxos", 9, pig=PigConfig(n_groups=2))]
    grid = [(0, 8, 0)]
    assert vs._plan(cfgs, grid, 0.1, 0.05, 64, 8)[1].kernel == \
        vs._resolve_kernel("group")
    _use_fanin(monkeypatch, "pallas")
    assert vs._plan(cfgs, grid, 0.1, 0.05, 64, 8)[1].kernel == "pallas"


# ------------------------------------------- one-hot relay lookups
def _bits(x):
    x = np.asarray(x)
    return x.view(f"u{x.dtype.itemsize}")


def _specials(rng, shape, dtype):
    """Random values of ``dtype`` with the extremes a select must keep:
    ±0 and ±inf for floats, the int32 bounds for ints."""
    if dtype == np.int32:
        x = rng.integers(-1000, 1000, shape, dtype=np.int32)
        pool = np.array([np.iinfo(np.int32).min, np.iinfo(np.int32).max, 0],
                        np.int32)
    else:
        x = rng.standard_normal(shape).astype(np.float32)
        pool = np.array([0.0, -0.0, np.inf, -np.inf], np.float32)
    hit = rng.random(shape) < 0.3
    x[hit] = rng.choice(pool, int(hit.sum()))
    return x


LAYOUTS = {
    "paxos24": [("paxos", 25, None)],
    "pig-r2": [("pigpaxos", 25, PigConfig(n_groups=2, prc=1))],
    "pig-r3": [("pigpaxos", 25, PigConfig(n_groups=3, prc=1))],
    "pig-r5": [("pigpaxos", 25, PigConfig(n_groups=5, prc=1))],
    "wan-4-5-5": [("pigpaxos", 15, PigConfig(n_groups=3, groups=FIG10_GROUPS,
                                             prc=1))],
    # padded to the batch's fmax = rmax = 24: gstart = F on R=3's padded
    # groups, padded slots (grp = rmax - 1) behind N=9's 8 real ones
    "padded": [("pigpaxos", 25, PigConfig(n_groups=3, prc=1)),
               ("pigpaxos", 9, PigConfig(n_groups=2, prc=1)),
               ("paxos", 25, None)],
}


@pytest.mark.parametrize("dtype", [np.float32, np.int32])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_onehot_lookups_match_gathers_bit_for_bit(layout, dtype):
    """``_take`` (group -> slot broadcasts, slot -> group picks) and
    ``_lut`` (the region table) give the gathers' bits on each cell's
    layout, under ``vmap`` over cells as the scan runs them."""
    import jax
    import jax.numpy as jnp

    cfgs = [vs.build_config(p, n, pig=pig) for p, n, pig in LAYOUTS[layout]]
    batch, _, _ = vs._stack_cells(cfgs, [(i, 4, 0) for i in range(len(cfgs))],
                                  0.1, 0.05)
    grp, gstart, thresh = batch["grp"], batch["gstart"], batch["thresh"]
    C, F = grp.shape
    G = gstart.shape[1]
    B, W = 8, 2
    if layout == "padded":
        assert (gstart == F).any() and (grp[1, 8:] == G - 1).all()
    rng = np.random.default_rng(len(layout))
    j = rng.integers(0, np.maximum(batch["sizes"], 1)[:, None], (C, B, G))
    rel_idx = np.clip(gstart[:, None] + j, 0, F - 1)           # (C, B, G)
    g0 = np.clip(gstart, 0, F - 1)
    t_idx = np.clip(gstart + thresh - 2, 0, F - 1)
    xg = _specials(rng, (C, B, G), dtype)
    xf = _specials(rng, (C, F), dtype)
    xbf = _specials(rng, (C, B, F), dtype)
    xw = _specials(rng, (C, F, W, 2), dtype)
    xgw = _specials(rng, (C, B, G, W, 2), dtype)

    def onehot(i, n):
        return i[..., None] == jnp.arange(n)

    cases = {
        "group->slot": (jax.vmap(lambda x, g: vs._take(x, onehot(g, G), 1))(
            xg, grp), np.take_along_axis(xg, np.broadcast_to(
                grp[:, None], (C, B, F)), axis=2)),
        "group->slot, trailing": (
            jax.vmap(lambda x, g: vs._take(x, onehot(g, G), 1))(xgw, grp),
            np.stack([xgw[c][:, grp[c]] for c in range(C)])),
        "relay slot": (
            jax.vmap(lambda x, r: vs._take(x, onehot(r, F)))(xf, rel_idx),
            np.stack([xf[c][rel_idx[c]] for c in range(C)])),
        "relay slot, trailing": (
            jax.vmap(lambda x, r: vs._take(x, onehot(r, F)))(xw, rel_idx),
            np.stack([xw[c][rel_idx[c]] for c in range(C)])),
    }
    for name, idx in (("segment start", g0), ("flush slot", t_idx)):
        cases[name] = (
            jax.vmap(lambda x, i: vs._take(x, onehot(i, F), 1))(xbf, idx),
            np.take_along_axis(xbf, np.broadcast_to(idx[:, None], (C, B, G)),
                               axis=2))
    for name, (got, want) in cases.items():
        assert got.shape == want.shape, name
        assert np.array_equal(_bits(got), _bits(want)), name

    tab = _specials(rng, (3, 3), dtype)
    a = rng.integers(0, 3, (B, F))
    b = rng.integers(0, 3, (1, F))
    for aa in (a, np.int32(2)):
        got = vs._lut(jnp.asarray(tab), jnp.asarray(aa), jnp.asarray(b))
        assert np.array_equal(_bits(got), _bits(tab[aa, b]))


def _lookup_grids():
    from repro.core import WorkloadConfig
    from repro.faults import crash_window, slow_window

    topo = wan_topology([5, 5, 5], [[0.15, 31, 35], [31, 0.15, 11],
                                    [35, 11, 0.15]])
    fig8 = [vs.build_config("pigpaxos", 13, pig=PigConfig(n_groups=3, prc=1)),
            vs.build_config("paxos", 13)]
    wan = [vs.build_config("paxos", 15, topo=topo),
           vs.build_config("pigpaxos", 15, topo=topo, pig=PigConfig(
               n_groups=3, groups=FIG10_GROUPS, prc=1))]
    masks = (crash_window(5, 0.02, 0.08)
             + slow_window(3, extra_latency=2e-3)).to_masks(13, 0.2)
    faults = [vs.build_config("pigpaxos", 13, pig=PigConfig(n_groups=3),
                              masks=masks)]
    reads = [vs.build_config("pigpaxos", 13, pig=PigConfig(n_groups=3, prc=1),
                             workload=WorkloadConfig(read_ratio=0.5,
                                                     read_path="lease"))]
    two = [(ci, k, s) for ci in range(2) for k in (4, 8) for s in (0, 1)]
    one = [(0, 8, s) for s in range(3)]
    return {"fig8-lax": (fig8, two, 0.1, 0.05, "lax"),
            "fig8-pallas": (fig8, two, 0.1, 0.05, "pallas"),
            "wan": (wan, [(ci, k, s) for ci in range(2) for k in (10, 40)
                          for s in (0, 1)], 0.3, 0.1, "lax"),
            "faults": (faults, one, 0.2, 0.0, "lax"),
            "reads": (reads, one, 0.1, 0.05, "lax")}


@pytest.mark.parametrize("case", ["fig8-lax", "fig8-pallas", "wan",
                                  "faults", "reads"])
def test_onehot_grid_equals_gather_grid(case, monkeypatch):
    """Every output of a grid on the one-hot lookups equals the gathers'
    (the path wide grids take, forced here by a zero width boundary), bit
    for bit, and the trace-time counter names the path each program
    took."""
    import jax

    cfgs, grid, dur, warm, kernel = _lookup_grids()[case]
    _use_fanin(monkeypatch, kernel)

    def run():
        before = vs.index_counts()
        out = vs.simulate_grid(cfgs, grid, dur, warm)
        after = vs.index_counts()
        return out, {k: v - before.get(k, 0) for k, v in after.items()
                     if v != before.get(k, 0)}

    jax.clear_caches()
    try:
        onehot, traced = run()
        assert set(traced) == {"onehot"}, traced
        monkeypatch.setattr(vs, "_ONEHOT_MAX_F", 0)
        jax.clear_caches()
        gather, traced = run()
        assert set(traced) == {"gather"}, traced
    finally:
        jax.clear_caches()
    assert sorted(onehot) == sorted(gather)
    for key in onehot:
        assert np.array_equal(_bits(onehot[key]), _bits(gather[key])), key


# ------------------------------------------------------ runner / spec
def test_runner_batch_backend_artifact():
    sc = Scenario(name="t/batch", protocol="pigpaxos", n=9,
                  pig=PigConfig(n_groups=2), backend="batch",
                  clients=(4, 8), seeds=(1, 2), duration=0.15, warmup=0.05)
    art = runner.run_scenarios([sc], quick=False)
    sa = art["scenarios"][0]
    assert sa["backend"] == "batch"
    assert len(sa["units"]) == 4
    assert len(sa["replicates"]) == 2
    for u in sa["units"]:
        assert u["backend"] == "batch"
        assert u["throughput"] > 0
        assert "retry_risk" in u
    assert sa["summary"]["throughput"]["mean"] > 0


def test_backend_override_switches_batch_ok_scenarios():
    des = Scenario(name="t/ovr", protocol="pigpaxos", n=9,
                   pig=PigConfig(n_groups=2), batch_ok=True,
                   clients=(4,), seeds=(1,), duration=0.15, warmup=0.05)
    art = runner.run_scenarios([des], quick=False, backend_override="batch")
    assert art["scenarios"][0]["backend"] == "batch"
    # not batch_ok -> stays on the DES
    des2 = Scenario(name="t/ovr2", protocol="pigpaxos", n=9,
                    pig=PigConfig(n_groups=2),
                    clients=(4,), seeds=(1,), duration=0.15, warmup=0.05)
    art2 = runner.run_scenarios([des2], quick=False,
                                backend_override="batch")
    assert art2["scenarios"][0]["backend"] == "des"


def test_batch_backend_rejects_unsupported_specs():
    # crash/recover windows ARE mask-expressible since the fault subsystem
    # (see tests/test_faults.py) — partitions and friends still are not
    with pytest.raises(ValueError):
        Scenario(name="t/bad1", protocol="pigpaxos", n=9, backend="batch",
                 failures=(("partition", 1, 2, 0.1),))
    Scenario(name="t/ok1", protocol="pigpaxos", n=9, backend="batch",
             failures=(("crash", 3, 0.1), ("recover", 3, 0.2)))
    # timeline collection needs a fault plan on the batch backend
    with pytest.raises(ValueError):
        Scenario(name="t/bad2", protocol="pigpaxos", n=9, backend="batch",
                 collect=("timeline",))
    with pytest.raises(ValueError):
        Scenario(name="t/bad3", protocol="pigpaxos", n=9, backend="nope")
    from repro.core import WorkloadConfig
    with pytest.raises(ValueError):
        vs.build_config("pigpaxos", 9, pig=PigConfig(n_groups=2),
                        workload=WorkloadConfig(arrival="poisson"))
