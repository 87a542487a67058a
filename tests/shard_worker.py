"""Worker script for multi-device batch-backend tests.

Run in a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=4
so the main pytest process keeps its single-device view.  Asserts the
sharded grid runner (``jax.shard_map``, chunked and not) is bit-identical
to the single-call ``simulate_grid`` on the same cells, on the platform's
fan-in and on the Pallas kernel (interpret mode here), forced through the
grid entries' ``_fanin`` hook.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax                                    # noqa: E402
import numpy as np                            # noqa: E402

from repro.core import PigConfig              # noqa: E402
from repro.core import vectorsim as vs        # noqa: E402


def main() -> None:
    assert jax.device_count() == 4, jax.device_count()
    cfgs = [vs.build_config("pigpaxos", 9, pig=PigConfig(n_groups=2, prc=1)),
            vs.build_config("paxos", 9),
            vs.build_config("pigpaxos", 9, pig=PigConfig(n_groups=4))]
    grid = [(ci, k, s) for ci in range(3) for k in (4, 8)
            for s in range(10)]                      # 60 cells, not % 4 == 0
    want = vs.simulate_grid(cfgs, grid, 0.1, 0.05)

    for chunk in (len(grid) + 4, 16):                # one chunk / many
        got = vs.simulate_grid_sharded(cfgs, grid, 0.1, 0.05, chunk=chunk)
        sh = got["sharding"]
        assert sh["devices"] == 4, sh
        for key in ("throughput", "median_s", "p99_s", "committed"):
            np.testing.assert_array_equal(
                np.asarray(want[key]), got[key],
                err_msg=f"chunk={chunk} key={key}")
        print(f"OK chunk={chunk} ({len(sh['chunks'])} chunks, 4 devices)")

    # epaxos kind through the same path
    ecfg = vs.build_config("epaxos", 5)
    egrid = [(0, k, s) for k in (2, 4) for s in range(6)]
    ewant = vs.simulate_grid([ecfg], egrid, 0.1, 0.05)
    egot = vs.simulate_grid_sharded([ecfg], egrid, 0.1, 0.05, chunk=8)
    np.testing.assert_array_equal(np.asarray(ewant["throughput"]),
                                  egot["throughput"])
    print("OK epaxos")

    # the Pallas fan-in, forced through the hook: ragged chunks with
    # padding, sharded == one device
    vs._fanin = lambda kind: "pallas"
    pgrid = grid[:20]
    pwant = vs.simulate_grid(cfgs, pgrid, 0.1, 0.05)
    pgot = vs.simulate_grid_sharded(cfgs, pgrid, 0.1, 0.05, chunk=8)
    assert pgot["sharding"]["kernel"] == "pallas", pgot["sharding"]
    for key in ("throughput", "median_s", "p99_s", "committed"):
        np.testing.assert_array_equal(np.asarray(pwant[key]), pgot[key],
                                      err_msg=f"pallas key={key}")
    print(f"OK pallas ({len(pgot['sharding']['chunks'])} chunks)")
    print("OK all")


if __name__ == "__main__":
    main()
