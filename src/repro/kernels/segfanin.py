"""Segmented quorum fan-in kernel (TPU Pallas).

The batch backend's hot spot: every scan step, every relay FIFOs its
group's reply fan-in and flushes at the k-th completion — per-group order
statistics over a flat group-contiguous slot axis.  The ``lax`` path pays a
lexicographic two-key sort plus a segmented cumulative max per burst
(``core.vectorsim``); sorts lower to O(F log^2 F) sorting networks on TPU
and leave the VPU idle between compare-exchange passes.

This kernel replaces the sort with *rank-by-comparison-counting*: the rank
of slot i among its segment equals the number of segment peers that sort
before it (value ascending, index tie-break — exactly ``lax.sort``'s stable
order).  That is valid because the downstream per-slot transform

    y_j = v_j + max(coef_j + vcoef * (v_j - anchor), 0) + md1 - rank_j * c

has a segment-CONSTANT coefficient ``coef`` (the relay's backlog at the
leader's pacing point), so sorting never permutes it, and the FIFO position
offset equals the rank.  Only the order statistic at the per-segment
threshold ``kcap`` is consumed, so the kernel emits each slot's *capped
segment max* directly:

    m_i = max over {j in seg(i) : rank_j <= kcap_i, v_j finite} of y_j

(-inf when the admissible set is empty).

Method: the whole (B, Fp) tile at once, by lane rolls.  ``pltpu.roll``
follows ``jnp.roll``: rolled by d, lane i holds slot (i - d) mod Fp, which
has the lower index exactly where i >= d.  A segment is a contiguous run
of at most L slots, so every same-segment peer of slot i sits at a lane
shift d in {1 .. L-1} or {Fp-L+1 .. Fp-1}.  The shifts are taken in the
order 1, Fp-1, 2, Fp-2, ...: its first min(2(L-1), Fp-1) terms are that
set, each once, and later terms reach no peer until the order runs out
at Fp - 1 terms.  Pass one counts, over those shifts, the peers
``roll(v, d)`` that sort before ``v`` where ``roll(sid, d) == sid``; the
tie-break ``i >= d`` stays exact for a segment that wraps the tile.  Pass
two takes the max of ``roll(z, d)`` under the same segment test, where z
is y on admissible slots and -inf elsewhere.  The rank is an integer
count, y keeps the lax path's summation order and max does not depend on
order, so the result equals the sort-based path bit for bit.

Cost: O(B * Fp * L) — per pass, one roll and a few compares per shift on
a (B, Fp) array; no (Fp, Fp) intermediate, no transpose, no row loop.
The static width picks how the shifts run:

* Fp = 128 (one vreg of lanes at B <= 8): L is taken as the real slot
  count F, static, and every shift is a static roll, unrolled (46 at
  F = 24); on a v5e a loop of one shift per iteration costs 16x as much.
* wider tiles (Fp = 1024 at N = 1025): ``ops.seg_fanin`` passes the
  data's longest segment L, and a loop of ``_UNROLL`` shifts an iteration
  runs until 2(L-1) are done (4 iterations at R = 32, not the 64 that
  all 1023 shifts take); pass one stops counting past that many, and
  pass two may see a shift twice, which changes no max.

Preconditions (hold by construction in ``vectorsim._group_cell``):
segments occupy contiguous runs of at most F slots within the first F
lanes, and padded lanes carry a segment id no real slot has;
``coef``/``kcap`` are constant within each segment; every segment consumed
downstream has at least ``kcap + 1`` finite entries; masked slots carry
``+inf``.  ``vcoef`` must be non-zero when any slot is +inf (vectorsim's
utilization coefficient is <= -0.05).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_UNROLL = 16        # shifts per loop iteration on a tile wider than 128


def _shift(k, width):
    """The k-th lane shift of the order 1, width-1, 2, width-2, ...; k a
    Python int or a traced one."""
    return k // 2 + 1 + (k % 2) * (width - 2 * (k // 2) - 2)


def _over_shifts(width: int, step, init, nshift, nslots: int):
    """Fold ``step(d, live, acc)`` over the first ``nshift`` lane shifts d
    of the order 1, width-1, 2, width-2, ...; ``live`` is 1.0 on those
    and 0.0 on the loop's overshoot.  At width 128 ``nshift`` is static
    (``nslots``, the real slot count, bounds the segments) and every shift
    is a static roll; wider, ``nshift`` is traced and the shifts run in a
    loop of ``_UNROLL`` an iteration."""
    if width == 128:
        for k in range(min(2 * (nslots - 1), width - 1)):
            init = step(_shift(k, width), 1.0, init)
        return init

    def body(g, acc):
        for u in range(_UNROLL):
            k = g * _UNROLL + u
            acc = step(_shift(k, width), jnp.where(k < nshift, 1.0, 0.0),
                       acc)
        return acc

    return lax.fori_loop(0, (nshift + _UNROLL - 1) // _UNROLL, body, init)


def _fanin_kernel(v_ref, u_ref, s_ref, k_ref, c_ref, *rest, nslots):
    """One whole (B, Fp) burst tile per grid program.  ``s_ref``/``k_ref``
    are (1, Fp) segment ids and caps, ``c_ref`` the (B, 4) per-row table
    [vcoef, md1, c, anchor]; on a tile wider than 128 lanes ``rest`` leads
    with the (1, 1) longest segment in SMEM."""
    *n_ref, o_ref = rest
    f32 = jnp.float32
    B, Fp = v_ref.shape
    nshift = jnp.minimum(2 * (n_ref[0][0, 0] - 1), Fp - 1) if n_ref else None
    v = v_ref[...]                          # arrivals, +inf masked
    sid = jnp.broadcast_to(s_ref[...], (B, Fp))
    lane = lax.broadcasted_iota(jnp.int32, (B, Fp), 1)
    cs = c_ref[...]
    vcoef, md1, c, anchor = (cs[:, k:k + 1] for k in range(4))

    def count(d, live, rank):
        rv = pltpu.roll(v, d, 1)        # lane i holds v_j, j = (i - d) % Fp
        # j sorts before i: stable (value, index) order == lax.sort's order;
        # j < i exactly where the roll did not wrap, i >= d
        before = (rv < v) | ((rv == v) & (lane >= d))
        same = pltpu.roll(sid, d, 1) == sid
        return rank + jnp.where(same & before, f32(live), f32(0.0))

    rank = _over_shifts(Fp, count, jnp.zeros((B, Fp), f32), nshift, nslots)
    # summed in the lax path's order (wait + md1 first), so both paths
    # round alike and commit the same requests
    y = v + (jnp.maximum(u_ref[...] + vcoef * (v - anchor), 0.0) + md1) \
        - rank * c
    z = jnp.where((rank <= k_ref[...]) & (v < jnp.inf), y, -jnp.inf)

    def cap(d, live, m):                # a shift seen twice changes no max
        same = pltpu.roll(sid, d, 1) == sid
        return jnp.maximum(m, jnp.where(same, pltpu.roll(z, d, 1), -jnp.inf))

    o_ref[...] = _over_shifts(Fp, cap, z, nshift, nslots)


@functools.partial(jax.jit, static_argnames=("nslots", "interpret"))
def seg_fanin_bf(vals: jax.Array, coef: jax.Array, segid: jax.Array,
                 kcap: jax.Array, scal: jax.Array,
                 seglen: jax.Array | None = None, *, nslots: int,
                 interpret: bool = False) -> jax.Array:
    """vals/coef: (B, Fp) f32, Fp a multiple of 128; segid/kcap: (1, Fp)
    f32; scal: (B, 4) f32 rows of [vcoef, md1, c, anchor]; ``nslots``: the
    real slot count, the longest a segment can be; ``seglen``: (1, 1) int32,
    the longest segment in the data, required where Fp > 128 and not read
    at 128.  Returns (B, Fp) f32 capped segment maxes.  Under ``vmap`` the
    mapped axis becomes the grid."""
    B, Fp = vals.shape
    tile = pl.BlockSpec((B, Fp), lambda: (0, 0))
    row = pl.BlockSpec((1, Fp), lambda: (0, 0))
    specs = [tile, tile, row, row, pl.BlockSpec((B, 4), lambda: (0, 0))]
    args = [vals, coef, segid, kcap, scal]
    if Fp > 128:
        specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(seglen)
    return pl.pallas_call(
        functools.partial(_fanin_kernel, nslots=nslots),
        in_specs=specs,
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((B, Fp), jnp.float32),
        interpret=interpret,
    )(*args)
