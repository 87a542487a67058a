"""Public jit'd wrappers around the Pallas kernels.

Handles layout conversion, padding to hardware-aligned block shapes, and
backend selection: on CPU (this container) kernels run in interpret mode;
on TPU they compile natively.  Model code calls these, never pallas_call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .flash_attention import flash_attention_bhsd
from .pig_aggregate import pig_aggregate as _pig_aggregate_kernel
from .pig_aggregate import quantize_blockwise  # noqa: F401 (re-export)
from .segfanin import seg_fanin_bf
from .ssm_scan import ssm_scan_bhtd


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to(x: jax.Array, axis: int, mult: int):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 128) -> jax.Array:
    """Model-layout entry point: q (B,S,Hq,Dh), k/v (B,S,Hkv,Dh)."""
    B, S, Hq, Dh = q.shape
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    bq = min(block_q, S)
    bk = min(block_k, S)
    qt, pq = _pad_to(qt, 2, bq)
    kt, pk = _pad_to(kt, 2, bk)
    vt, _ = _pad_to(vt, 2, bk)
    qt, pd = _pad_to(qt, 3, 128)
    kt, _ = _pad_to(kt, 3, 128)
    vt, _ = _pad_to(vt, 3, 128)
    # padded k rows must never win the softmax: they are masked by causality
    # only when pq == pk pads align; mask explicitly via huge negative keys
    out = flash_attention_bhsd(qt, kt, vt, causal=causal, block_q=bq,
                               block_k=bk, interpret=_interpret(),
                               sm_scale=1.0 / (Dh ** 0.5))
    out = out[:, :, :S, :Dh]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def ssm_scan(q: jax.Array, k: jax.Array, v: jax.Array, log_a: jax.Array,
             u: jax.Array | None = None, chunk: int = 64) -> jax.Array:
    """Model-layout entry point: q/k/log_a (B,T,H,Dk), v (B,T,H,Dv),
    u (H,Dk) or None.  Returns (B,T,H,Dv)."""
    B, T, H, Dk = q.shape
    Dv = v.shape[-1]
    fold = lambda a: a.transpose(0, 2, 1, 3).reshape(B * H, T, a.shape[-1])
    qf, kf, vf, lf = fold(q), fold(k), fold(v), fold(log_a)
    qf, pt = _pad_to(qf, 1, chunk)
    kf, _ = _pad_to(kf, 1, chunk)
    vf, _ = _pad_to(vf, 1, chunk)
    lf, _ = _pad_to(lf, 1, chunk)       # log_a = 0 pad => decay 1, harmless
    uf = None if u is None else jnp.tile(u, (B, 1))
    out = ssm_scan_bhtd(qf, kf, vf, lf, uf, chunk=chunk,
                        interpret=_interpret())
    out = out[:, :T]
    return out.reshape(B, H, T, Dv).transpose(0, 2, 1, 3)


def pig_aggregate(shards: jax.Array, scales: jax.Array,
                  block: int = 1024) -> jax.Array:
    """shards (G, N) int8 + scales (G, N//block) f32 -> (N,) f32 sum."""
    return _pig_aggregate_kernel(shards, scales, block=block,
                                 interpret=_interpret())


def seg_fanin(vals: jax.Array, coef: jax.Array, segid: jax.Array,
              kcap: jax.Array, vcoef, md1, c, anchor) -> jax.Array:
    """Segmented quorum fan-in (see ``segfanin`` for the model and its
    preconditions).  vals/coef: (B, F) f32 (+inf = masked slot); segid /
    kcap: (F,) per-slot segment id and order-statistic cap (both
    segment-constant); vcoef/md1/c: scalars; anchor: (B,).  Returns (B, F):
    each slot's capped segment max m, -inf where the admissible set is
    empty.  Values can be traced scalars (called per scan step)."""
    B, F = vals.shape
    f32 = jnp.float32
    # pad the slot axis to the TPU lane width; padded slots form their own
    # segment (id -1) so they never contribute to a real segment's max
    pad = (-F) % 128
    vals = jnp.pad(vals, ((0, 0), (0, pad)), constant_values=jnp.inf)
    coef = jnp.pad(coef.astype(f32), ((0, 0), (0, pad)))
    sid = jnp.pad(segid.astype(f32), (0, pad), constant_values=-1.0)
    kc = jnp.pad(kcap.astype(f32), (0, pad))
    ones = jnp.ones((B,), f32)
    scal = jnp.stack([vcoef * ones, md1 * ones, c * ones,
                      jnp.broadcast_to(jnp.asarray(anchor, f32), (B,))],
                     axis=1)
    seglen = None
    if F + pad > 128:
        # the longest segment bounds the kernel's lane shifts on a wide tile
        idx = jnp.arange(F)
        first = jnp.concatenate([jnp.ones((1,), bool),
                                 segid[1:] != segid[:-1]])
        start = lax.cummax(jnp.where(first, idx, 0), axis=0)
        seglen = (jnp.max(idx - start) + 1).astype(jnp.int32).reshape(1, 1)
    out = seg_fanin_bf(vals, coef, sid[None, :], kc[None, :], scal, seglen,
                       nslots=F, interpret=_interpret())
    return out[:, :F]
