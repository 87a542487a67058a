"""Worker script for multi-device collective tests.

Run in a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8
so the main pytest process keeps its single-device view.
"""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax                                    # noqa: E402
import jax.numpy as jnp                       # noqa: E402
import numpy as np                            # noqa: E402
from jax.sharding import PartitionSpec as P   # noqa: E402

from repro.collectives import (direct_allreduce, pig_allreduce,  # noqa: E402
                               pig_allreduce_quantized)
from repro.collectives.schedules import dcn_bytes_per_chip  # noqa: E402
from repro.roofline import collective_stats  # noqa: E402


def main() -> None:
    assert jax.device_count() == 8, jax.device_count()
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
    key = jax.random.PRNGKey(0)
    # per-device distinct values along (pod, data); replicated over model
    x = jax.random.normal(key, (4, 1031), jnp.float32)    # odd size: pad path

    def run(fn):
        m = jax.shard_map(fn, mesh=mesh, in_specs=P(("pod", "data")),
                          out_specs=P(("pod", "data")), check_vma=False)
        return jax.jit(m)

    def direct(xs):
        return direct_allreduce(xs, ("pod", "data"))

    def pig(xs):
        return pig_allreduce(xs, group_axis="data", pod_axis="pod")

    def pig_rot(xs):
        return pig_allreduce(xs, group_axis="data", pod_axis="pod", rotation=3)

    want = np.asarray(jax.jit(run(direct))(x))
    got = np.asarray(jax.jit(run(pig))(x))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    got_rot = np.asarray(jax.jit(run(pig_rot))(x))
    np.testing.assert_allclose(got_rot, want, rtol=1e-5, atol=1e-5)
    print("OK equivalence")

    # quantized path: error bounded by quant step and EF residual is exact
    def pigq(xs):
        y, r = pig_allreduce_quantized(xs, None, group_axis="data",
                                       pod_axis="pod", block=256)
        return y, r

    y, r = jax.jit(jax.shard_map(
        pigq, mesh=mesh, in_specs=P(("pod", "data")),
        out_specs=(P(("pod", "data")), P(("pod", "data"))),
        check_vma=False))(x)
    y = np.asarray(y)
    err = np.abs(y - want)
    step = np.abs(x).max() / 127.0
    assert err.max() <= 2 * 2 * step + 1e-5, (err.max(), step)   # 2 pods
    print("OK quantized")

    # HLO accounting: the pig schedule must move fewer bytes over the pod
    # (DCN) boundary than the direct schedule (the whole point)
    from repro.roofline import collective_stats

    def stats_of(fn, out_specs=P(("pod", "data"))):
        m = jax.shard_map(fn, mesh=mesh, in_specs=P(("pod", "data")),
                          out_specs=out_specs, check_vma=False)
        txt = jax.jit(m).lower(x).compile().as_text()
        return collective_stats(txt, pod_size=4)   # 8 devices / 2 pods

    s_direct = stats_of(direct)
    s_pig = stats_of(pig)
    print("direct:", s_direct)
    print("pig:", s_pig)
    assert s_direct["cross_pod"] > 0
    # group size 2 => the DCN hop carries ~1/2 of the direct bytes
    assert s_pig["cross_pod"] <= 0.55 * s_direct["cross_pod"], (
        s_pig["cross_pod"], s_direct["cross_pod"])

    # closed-form model sanity
    assert dcn_bytes_per_chip(100.0, 4, 2, "pig") == dcn_bytes_per_chip(
        100.0, 1, 2, "direct") / 4
    print("OK all")


if __name__ == "__main__":
    main()
