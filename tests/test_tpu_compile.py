"""The batch backend's device path, compiled for a described TPU v5e.

Nothing runs here: each test lowers and compiles for a ``v5e:2x2``
topology that is described, not attached.  Interpret mode (every other
kernel test) cannot show what the chip's compiler refuses — block shapes
off the (8, 128) tiling, kernels past the scoped fast-memory limit — so
these compiles guard the fan-in kernel at the widths the model uses
(F = 24 -> 128 at N=25, F = 1024 at N=1025) and the whole scan step
around it.  Code that asks ``jax.default_backend()`` still sees the CPU,
so the group step is steered onto the native kernel by patching
``kernels.ops._interpret`` inside the fixture.  The compiled steps also
show that the scan's stage scopes reach each operation's ``op_name``
metadata and lend no operation the fan-in kernel's name.
"""
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from repro.core import PigConfig, WorkloadConfig, wan_topology  # noqa: E402
from repro.core import vectorsim as vs  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.kernels.segfanin import seg_fanin_bf  # noqa: E402


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with JAX's persistent cache off
    (a compile for an absent chip is written but can never be read)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs in /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001  (no libtpu / no topology)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _structs(batch, sharding):
    return {k: jax.ShapeDtypeStruct(np.shape(v), np.asarray(v).dtype,
                                    sharding=sharding)
            for k, v in batch.items()}


def _compile_step(one_chip, cfgs, grid, kernel):
    batch, kind, kmax = vs._stack_cells(cfgs, grid, 0.2, 0.1)
    breq = min(8, kmax) if kind == "group" else 1
    lowered = vs._run_cells.lower(_structs(batch, one_chip), steps=64,
                                  kmax=kmax, kind=kind, breq=breq,
                                  kernel=kernel)
    return lowered.compile().as_text()


@pytest.mark.parametrize("B,F", [(4, 128), (8, 128), (8, 1024), (8, 24)])
def test_seg_fanin_compiles_native(one_chip, B, F):
    """F real slots padded to whole lanes: (8, 24) is the benchmark's
    N=25 burst, (8, 1024) an N=1025 one, whose shifts run in a loop
    bounded by the longest segment.  One tile, and a grid of two as the
    scan's ``vmap`` over cells makes it."""
    Fp = -(-F // 128) * 128
    fn = jax.jit(lambda v, u, s, k, c, n: seg_fanin_bf(
        v, u, s, k, c, n, nslots=F, interpret=False))
    for cells in ((), (2,)):
        def struct(*shape, dtype=jnp.float32):
            return jax.ShapeDtypeStruct(cells + shape, dtype,
                                        sharding=one_chip)
        f = jax.vmap(fn) if cells else fn
        text = jax.jit(f).lower(
            struct(B, Fp), struct(B, Fp), struct(1, Fp), struct(1, Fp),
            struct(B, 4), struct(1, 1, dtype=jnp.int32)).compile().as_text()
        assert "tpu_custom_call" in text


@pytest.fixture(scope="module")
def group_step(one_chip):
    """One whole PigPaxos N=25 scan step program with the Pallas fan-in
    lowered natively (the fan-in the platform picks on a TPU)."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "_interpret", lambda: False)
        cfgs = [vs.build_config("pigpaxos", 25, pig=PigConfig(n_groups=3,
                                                               prc=1))]
        grid = [(0, 24, s) for s in range(3)]
        return _compile_step(one_chip, cfgs, grid, "pallas")


@pytest.fixture(scope="module")
def epaxos_step(one_chip):
    cfgs = [vs.build_config("epaxos", 25, workload=WorkloadConfig(
        key_dist="conflict", conflict_rate=0.1))]
    grid = [(0, 24, s) for s in range(3)]
    return _compile_step(one_chip, cfgs, grid, "lax")


@pytest.fixture(scope="module")
def wan_step(one_chip):
    """The Fig. 10 deployment's step: Paxos and PigPaxos with one relay
    group per region (4/5/5) over three regions, the native fan-in."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "_interpret", lambda: False)
        topo = wan_topology([5, 5, 5], [[0.15, 31, 35], [31, 0.15, 11],
                                        [35, 11, 0.15]])
        groups = [[1, 2, 3, 4], [5, 6, 7, 8, 9], [10, 11, 12, 13, 14]]
        cfgs = [vs.build_config("paxos", 15, topo=topo),
                vs.build_config("pigpaxos", 15, topo=topo, pig=PigConfig(
                    n_groups=3, groups=groups, prc=1))]
        grid = [(0, 24, 1), (1, 24, 2), (1, 24, 3)]
        return _compile_step(one_chip, cfgs, grid, "pallas")


def test_group_step_compiles_with_native_fanin(group_step):
    assert "tpu_custom_call" in group_step


def test_epaxos_step_compiles(epaxos_step):
    assert "while" in epaxos_step


# the scan's stage scopes (jax.named_scope in _group_cell / _epaxos_cell)
STAGES = {"group_step": ("ingress", "relay_pick", "relay_fanout",
                         "relay_acks", "commit", "state", "summary"),
          "epaxos_step": ("keys", "preaccept", "conflict", "exec_gate",
                          "state", "summary")}


@pytest.mark.parametrize("step", sorted(STAGES))
def test_stage_scopes_reach_the_op_name_metadata(request, step):
    text = request.getfixturevalue(step)
    scopes = {part.rsplit("(", 1)[-1].rstrip(")")
              for path in re.findall(r'op_name="([^"]*)"', text)
              for part in path.split("/")}
    assert set(STAGES[step]) <= scopes


@pytest.mark.parametrize("step", sorted(STAGES) + ["wan_step"])
def test_only_the_wan_step_has_a_regions_scope(request, step):
    """The region lookups of the WAN branch carry ``regions`` inside the
    stage that uses them, so a stage split still gives them to it; a
    one-region (LAN) program and the EPaxos program carry none.  They
    are one-hot selects (``relay_index``), not gathers."""
    text = request.getfixturevalue(step)
    paths = re.findall(r'op_name="([^"]*)"', text)
    regional = {p for p in paths if "/regions/" in p}
    if step != "wan_step":
        assert not regional
        return
    scopes = {part.rsplit("(", 1)[-1].rstrip(")")
              for path in paths for part in path.split("/")}
    assert set(STAGES["group_step"]) <= scopes
    for stage in ("relay_fanout", "relay_acks"):
        assert any(f"/{stage}/regions/relay_index/" in p
                   for p in regional), stage
    # a reduction's own computation names its path from the stage on
    assert all(re.search(r"(^|/)(relay_fanout|relay_acks)/regions/", p)
               for p in regional)


@pytest.mark.parametrize("step", ["group_step", "wan_step"])
def test_relay_stages_hold_no_gather(request, step):
    """At F <= 128 the relay stages look up the cell's slot<->group layout
    and the region table by one-hot selects: no batched gather is left in
    ``relay_pick``, ``relay_fanout`` or ``relay_acks``."""
    text = request.getfixturevalue(step)
    ops = re.findall(r'= \S+ (\w[\w-]*)\(.*op_name="([^"]*)"', text)
    relay = [(op, p) for op, p in ops
             if re.search(r"(^|/)(relay_pick|relay_fanout|relay_acks)/", p)]
    assert any("/relay_index/" in p for _, p in relay)
    assert not [p for op, p in relay if op == "gather"]


@pytest.mark.parametrize("step", sorted(STAGES))
def test_only_the_pallas_call_is_named_fanin(request, step):
    """``fanin_us`` sums the operations whose name holds "fanin": the
    scopes must not lend that word to any other operation."""
    text = request.getfixturevalue(step)
    named = re.findall(r"^\s*(?:ROOT )?%(\S*fanin\S*) = (.*)$", text, re.M)
    want = ["seg_fanin_bf"] if step == "group_step" else []
    assert [n.split(".")[0] for n, _ in named] == want
    assert all('custom_call_target="tpu_custom_call"' in rest
               for _, rest in named)
    assert not any("fanin" in s for s in STAGES[step])
