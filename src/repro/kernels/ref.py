"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..models.layers import attention_ref
from ..models.ssm import chunked_linear_scan


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True) -> jax.Array:
    """q: (B,Hq,Sq,Dh); k/v: (B,Hkv,Sk,Dh) -> (B,Hq,Sq,Dh)."""
    B, Hq, Sq, Dh = q.shape
    Sk = k.shape[2]
    qs = q.transpose(0, 2, 1, 3)      # (B,S,H,D) layout of attention_ref
    ks = k.transpose(0, 2, 1, 3)
    vs = v.transpose(0, 2, 1, 3)
    q_pos = jnp.broadcast_to(jnp.arange(Sq)[None], (B, Sq))
    if not causal:
        q_pos = jnp.full((B, Sq), Sk - 1, jnp.int32)
    k_pos = jnp.broadcast_to(jnp.arange(Sk)[None], (B, Sk))
    out = attention_ref(qs, ks, vs, q_pos, k_pos)
    return out.transpose(0, 2, 1, 3)


def ssm_scan_ref(q: jax.Array, k: jax.Array, v: jax.Array, log_a: jax.Array,
                 u: jax.Array | None = None, chunk: int = 64) -> jax.Array:
    """Same signature as kernels.ssm_scan.ssm_scan_bhtd (BH-major layout)."""
    BH, T, Dk = q.shape
    pad = (-T) % chunk
    if pad:
        zp = lambda a: jnp.pad(a, [(0, 0), (0, pad), (0, 0)])
        out = ssm_scan_ref(zp(q), zp(k), zp(v), zp(log_a), u=u, chunk=chunk)
        return out[:, :T]
    add = lambda a: a[:, :, None]     # (BH,T,D) -> (B=BH, T, H=1, D)
    if u is not None:
        # chunked_linear_scan wants bonus (H, Dk); fold BH into batch, H=1:
        # handle per-row bonus by vmapping over BH
        def one(qr, kr, vr, lr, ur):
            return chunked_linear_scan(qr[None, :, None], kr[None, :, None],
                                       vr[None, :, None], lr[None, :, None],
                                       chunk=chunk, bonus=ur[None])[0, :, 0]
        return jax.vmap(one)(q, k, v, log_a, u)
    out = chunked_linear_scan(add(q), add(k), add(v), add(log_a), chunk=chunk)
    return out[:, :, 0]


def pig_aggregate_ref(shards: jax.Array, scales: jax.Array,
                      block: int = 1024) -> jax.Array:
    G, N = shards.shape
    nb = N // block
    x = shards.reshape(G, nb, block).astype(jnp.float32) * scales[:, :, None]
    return x.sum(axis=0).reshape(N)


def seg_fanin_ref(vals: jax.Array, coef: jax.Array, segid: jax.Array,
                  kcap: jax.Array, vcoef, md1, c, anchor) -> jax.Array:
    """The production ``lax`` fan-in path (lexicographic sort + segmented
    cumulative max, ``core.segscan``) with ``kernels.ops.seg_fanin``'s
    signature: vals/coef (B, F), segid/kcap (F,), anchor (B,), scalars
    vcoef/md1/c.  Same preconditions as the kernel: contiguous segments,
    segment-constant coef/kcap, >= kcap+1 finite entries per consumed
    segment.  Returns each slot's capped segment max (B, F)."""
    from ..core.segscan import seg_cummax, seg_start_index

    B, F = vals.shape
    segid = segid.astype(jnp.int32)
    sid_b = jnp.broadcast_to(segid[None, :], (B, F))
    # two-key stable sort: segment blocks stay in place, values ascend
    _, arr_s = jax.lax.sort((sid_b, vals), num_keys=2)
    first = segid != jnp.concatenate([segid[:1] - 1, segid[:-1]])
    first_b = jnp.broadcast_to(first[None, :], (B, F))
    gsl = seg_start_index(first, axis=0)                   # (F,)
    posf = (jnp.arange(F) - gsl).astype(jnp.float32)
    anchor = jnp.asarray(anchor, jnp.float32).reshape(B, 1)
    y = arr_s + (jnp.maximum(coef + vcoef * (arr_s - anchor), 0.0) + md1) \
        - posf[None, :] * c
    pref = seg_cummax(y, first_b, axis=1)
    idx = jnp.clip(gsl + kcap.astype(jnp.int32), 0, F - 1)
    return jnp.take_along_axis(pref, jnp.broadcast_to(idx[None, :], (B, F)),
                               axis=1)
