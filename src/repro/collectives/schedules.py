"""Pig collective schedules: the paper's primitive, adapted to a TPU mesh.

Paper -> TPU mapping (DESIGN.md §3): the leader's fan-out/fan-in over a
cluster becomes cross-pod gradient synchronization over DCN; a relay group
becomes a pod; the rotating relay becomes the shard owner after an in-group
reduce-scatter (every chip relays 1/G of the payload, and the shard->chip
assignment can additionally rotate per step); aggregated piggybacked acks
become int8-compressed cross-pod payloads with error feedback.

All functions here run *inside* a shard_map manual context over the named
axes (see ``sync_grads`` for the entry point used by the training runtime).

Cross-DCN byte accounting per chip for payload P bytes, G chips per group,
npods pods:
  direct  : flat all-reduce over ('pod','group') ~ 2 P (pods-1)/pods  over DCN
  pig     : RS(group) -> AR(pod) -> AG(group)    ~ 2 (P/G) (pods-1)/pods
  pig+q8  : int8 payload + f32 block scales      ~ direct / G / 2 (vs bf16)
i.e. the paper's "shift the hot resource's work into the group" effect: the
expensive link sees 1/G (or 1/2G) of the traffic.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..kernels.ops import pig_aggregate as pig_aggregate_op
from ..kernels.pig_aggregate import quantize_blockwise


def _flatten(x: jax.Array, mult: int):
    """Flatten to 1-D and pad to a multiple of ``mult``."""
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % mult
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat, pad


def direct_allreduce(x: jax.Array, axes) -> jax.Array:
    """Baseline: flat psum over all sync axes (GSPMD default behaviour)."""
    return jax.lax.psum(x, axes)


def pig_allreduce(x: jax.Array, group_axis: str = "data",
                  pod_axis: str = "pod", rotation: int = 0) -> jax.Array:
    """Hierarchical grouped all-reduce (bf16/f32 path).

    1. reduce-scatter within the group: each chip becomes the *relay* for a
       1/G shard (rotation built in: relay duty is spread uniformly, the
       paper's amortization argument);
    2. psum across pods on the scattered shard only (the DCN hop carries
       1/G of the bytes — the aggregated, deduplicated "ack");
    3. all-gather within the group.

    ``rotation`` (e.g. the step counter) additionally rotates which chip
    owns which shard across steps for uniform sustained link wear.
    """
    G = jax.lax.axis_size(group_axis)
    flat, pad = _flatten(x, G)
    if rotation:
        flat = jnp.roll(flat, (rotation % G) * (flat.shape[0] // G))
    shard = jax.lax.psum_scatter(flat.reshape(G, -1), group_axis,
                                 scatter_dimension=0, tiled=False)
    shard = jax.lax.psum(shard, pod_axis)
    out = jax.lax.all_gather(shard, group_axis, axis=0, tiled=False)
    out = out.reshape(-1)
    if rotation:
        out = jnp.roll(out, -(rotation % G) * (out.shape[0] // G))
    if pad:
        out = out[:-pad]
    return out.reshape(x.shape)


def pig_allreduce_quantized(x: jax.Array, residual: Optional[jax.Array],
                            group_axis: str = "data", pod_axis: str = "pod",
                            block: int = 1024, rotation: int = 0):
    """Pig schedule with int8-compressed cross-pod hop + error feedback.

    The relay's deduplicated aggregate (§6.4) maps to block-quantized int8:
    the DCN hop carries ~1/4 the f32 bytes (1/2 of bf16).  Quantization error
    is fed back into the next step's gradient (residual), so the *average*
    update is unbiased — the PRC analogue: accept an approximate aggregate
    now, repay later.

    Returns (synced, new_residual); both shaped like x.
    """
    G = jax.lax.axis_size(group_axis)
    npods = jax.lax.axis_size(pod_axis)
    flat, pad = _flatten(x, G * block)
    if residual is not None:
        flat = flat + residual.reshape(-1)
    # 1) in-group reduce-scatter (full precision inside the pod: ICI is cheap)
    shard = jax.lax.psum_scatter(flat.reshape(G, -1), group_axis,
                                 scatter_dimension=0, tiled=False)   # (P/G,)
    # 2) quantize the shard, exchange across pods, fused dequant-accumulate
    q, scales = quantize_blockwise(shard.astype(jnp.float32), block)
    q_all = jax.lax.all_gather(q, pod_axis, axis=0)                  # (pods, P/G) int8
    s_all = jax.lax.all_gather(scales, pod_axis, axis=0)             # (pods, nb) f32
    agg = pig_aggregate_op(q_all, s_all, block=block)                # (P/G,) f32
    # error feedback: what the other pods saw vs what we contributed
    my_deq = (q.reshape(-1, block).astype(jnp.float32)
              * scales[:, None]).reshape(-1)
    local_err = shard.astype(jnp.float32) - my_deq
    # 3) in-group all-gather of the aggregated shard
    out = jax.lax.all_gather(agg.astype(x.dtype), group_axis, axis=0,
                             tiled=False).reshape(-1)
    err_full = jax.lax.all_gather(local_err.astype(x.dtype), group_axis,
                                  axis=0, tiled=False).reshape(-1)
    if pad:
        out = out[:-pad]
        err_full = err_full[:-pad]
    return out.reshape(x.shape), err_full.reshape(x.shape)


def sync_grads(grads, schedule: str = "pig", group_axis: str = "data",
               pod_axis: str = "pod", residuals=None, rotation: int = 0,
               block: int = 1024):
    """Synchronize a gradient pytree across ``(pod_axis, group_axis)``.

    schedule: 'direct' | 'pig' | 'pig_q8'.  Returns (grads, residuals)."""
    if schedule == "direct":
        return jax.tree.map(lambda g: direct_allreduce(g, (pod_axis, group_axis)),
                            grads), residuals
    if schedule == "pig":
        return jax.tree.map(
            lambda g: pig_allreduce(g, group_axis, pod_axis, rotation), grads), residuals
    if schedule == "pig_q8":
        if residuals is None:
            residuals = jax.tree.map(jnp.zeros_like, grads)
        pairs = jax.tree.map(
            lambda g, r: pig_allreduce_quantized(g, r, group_axis, pod_axis,
                                                 block, rotation), grads, residuals)
        synced = jax.tree.map(lambda p: p[0], pairs,
                              is_leaf=lambda p: isinstance(p, tuple))
        res = jax.tree.map(lambda p: p[1], pairs,
                           is_leaf=lambda p: isinstance(p, tuple))
        return synced, res
    raise ValueError(schedule)


def dcn_bytes_per_chip(param_bytes: int, group_size: int, npods: int,
                       schedule: str) -> float:
    """Closed-form DCN traffic model (the byte analogue of Eq. 1-3)."""
    f = 2.0 * (npods - 1) / npods
    if schedule == "direct":
        return f * param_bytes
    if schedule == "pig":
        return f * param_bytes / group_size
    if schedule == "pig_q8":
        # int8 payload + f32 scale per 1024 block, vs bf16 wire dtype
        return f * (param_bytes / group_size) * (1.0 + 4.0 / 1024) / 2.0
    raise ValueError(schedule)
