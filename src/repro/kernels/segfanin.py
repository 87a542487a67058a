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
order), computed as one dense masked (F, F) comparison reduction.  That is
valid because the downstream per-slot transform

    y_j = v_j + max(coef_j + vcoef * (v_j - anchor), 0) + md1 - rank_j * c

has a segment-CONSTANT coefficient ``coef`` (the relay's backlog at the
leader's pacing point), so sorting never permutes it, and the FIFO position
offset equals the rank.  Only the order statistic at the per-segment
threshold ``kcap`` is consumed, so the kernel emits each slot's *capped
segment max* directly:

    m_i = max over {j in seg(i) : rank_j <= kcap_i, v_j finite} of y_j

(-inf when the admissible set is empty).  Dense compares + reductions are
pure VPU work — no scatter, no sort — at O(F^2) per burst row, a win for
the model's group sizes (F = N - 1, segments of ~N/R slots).

Preconditions (hold by construction in ``vectorsim._group_cell``):
segments occupy contiguous slot runs; ``coef``/``kcap`` are constant within
each segment; every segment consumed downstream has at least ``kcap + 1``
finite entries; masked slots carry ``+inf``.  ``vcoef`` must be non-zero
when any slot is +inf (vectorsim's utilization coefficient is <= -0.05).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _fanin_kernel(v_ref, u_ref, s_ref, k_ref, c_ref, o_ref):
    """One whole (B, F) burst tile per grid program (the block equals the
    array's last two dims, the shape Mosaic accepts for any B), looping
    over its B rows.  ``c_ref`` is the (B, 4) scalar table in SMEM."""
    f32 = jnp.float32
    B, F = v_ref.shape
    j_idx = lax.broadcasted_iota(jnp.int32, (F, F), 1)
    i_idx = lax.broadcasted_iota(jnp.int32, (F, F), 0)

    def row(r, carry):
        rows = pl.ds(r, 1)
        v = v_ref[rows, :]                   # (1, F) arrivals, +inf masked
        u = u_ref[rows, :]                   # (1, F) segment-constant coef
        sid = s_ref[rows, :]                 # (1, F) segment id (exact f32)
        kcap = k_ref[rows, :]                # (1, F) per-segment cap
        vcoef, md1 = c_ref[r, 0], c_ref[r, 1]
        c, anchor = c_ref[r, 2], c_ref[r, 3]
        vt = jnp.transpose(v, (1, 0))        # (F, 1): slot i down the rows
        st = jnp.transpose(sid, (1, 0))
        same = sid == st                     # (F, F): j in segment(i)
        # j sorts before i: stable (value, index) order == lax.sort's order
        before = (v < vt) | ((v == vt) & (j_idx < i_idx))
        rank_i = jnp.sum(jnp.where(same & before, f32(1.0), f32(0.0)),
                         axis=1, keepdims=True)        # (F, 1) rank of i
        rank = jnp.transpose(rank_i, (1, 0))           # (1, F) rank of j
        # summed in the lax path's order (wait + md1 first), so both paths
        # round alike and commit the same requests
        y = v + (jnp.maximum(u + vcoef * (v - anchor), 0.0) + md1) - rank * c
        ok = same & (rank <= kcap) & (v < jnp.inf)
        contrib = jnp.where(ok, jnp.broadcast_to(y, (F, F)), -jnp.inf)
        o_ref[rows, :] = jnp.transpose(
            jnp.max(contrib, axis=1, keepdims=True), (1, 0))
        return carry

    lax.fori_loop(0, B, row, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def seg_fanin_bf(vals: jax.Array, coef: jax.Array, segid: jax.Array,
                 kcap: jax.Array, scal: jax.Array,
                 interpret: bool = False) -> jax.Array:
    """vals/coef/segid/kcap: (B, F) f32, F a multiple of 128; scal: (B, 4)
    f32 rows of [vcoef, md1, c, anchor].  Returns (B, F) f32 capped
    segment maxes.  Under ``vmap`` the mapped axis becomes the grid."""
    B, F = vals.shape
    spec = pl.BlockSpec((B, F), lambda: (0, 0))
    return pl.pallas_call(
        _fanin_kernel,
        in_specs=[spec, spec, spec, spec,
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((B, F), jnp.float32),
        interpret=interpret,
    )(vals, coef, segid, kcap, scal)
