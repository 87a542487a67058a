"""Per-kernel validation: Pallas (interpret=True on CPU) vs pure-jnp oracle,
swept over shapes and dtypes, plus hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

# hypothesis is an optional dev dep (requirements-dev.txt): only the
# property tests skip without it, the deterministic sweeps always run
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ModuleNotFoundError:
    def _needs_hypothesis(*_a, **_k):
        def deco(fn):
            return pytest.mark.skip(reason="hypothesis not installed")(fn)
        return deco

    given = settings = _needs_hypothesis

    class _St:
        def __getattr__(self, name):
            return lambda *a, **k: None
    st = _St()

from repro.kernels import ops, ref
from repro.kernels.pig_aggregate import quantize_blockwise


# ------------------------------------------------------------- flash attention
@pytest.mark.parametrize("B,S,Hq,Hkv,Dh", [
    (1, 128, 4, 4, 64),       # MHA, aligned
    (2, 256, 8, 2, 64),       # GQA 4:1
    (1, 200, 4, 1, 64),       # MQA, unaligned seq (padding path)
    (1, 128, 4, 4, 112),      # zamba2 head_dim 112 (pad to 128)
    (2, 96, 8, 8, 256),       # gemma head_dim 256
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_vs_ref(B, S, Hq, Hkv, Dh, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, Hq, Dh), dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, Dh), dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, Dh), dtype)
    got = ops.flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    qb, kb, vb = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
    want = ref.flash_attention_ref(qb, kb, vb, causal=True).transpose(0, 2, 1, 3)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_matches_model_attention():
    """flash path == attention_ref used inside the models (causal, GQA)."""
    from repro.models.layers import attention_ref
    B, S, Hq, Hkv, Dh = 2, 128, 8, 4, 64
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, S, Hq, Dh), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, Dh), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, Dh), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    want = attention_ref(q, k, v, pos, pos)
    got = ops.flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ------------------------------------------------------------------- ssm scan
@pytest.mark.parametrize("B,T,H,Dk,Dv,chunk", [
    (1, 128, 2, 64, 64, 32),
    (2, 96, 4, 64, 64, 32),     # pad path (96 % 32 == 0, but use 64 below)
    (1, 100, 1, 32, 64, 32),    # unaligned T
    (2, 64, 2, 16, 64, 16),     # rwkv-style chunk 16
])
@pytest.mark.parametrize("scalar_decay", [True, False])
def test_ssm_scan_vs_ref(B, T, H, Dk, Dv, chunk, scalar_decay):
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q = jax.random.normal(ks[0], (B, T, H, Dk), jnp.float32) * 0.3
    k = jax.random.normal(ks[1], (B, T, H, Dk), jnp.float32) * 0.3
    v = jax.random.normal(ks[2], (B, T, H, Dv), jnp.float32) * 0.3
    la = -jnp.abs(jax.random.normal(ks[3], (B, T, H, Dk))) * 0.5 - 0.01
    if scalar_decay:
        la = jnp.broadcast_to(la[..., :1], la.shape)
    got = ops.ssm_scan(q, k, v, la, chunk=chunk)
    fold = lambda a: a.transpose(0, 2, 1, 3).reshape(B * H, T, a.shape[-1])
    want = ref.ssm_scan_ref(fold(q), fold(k), fold(v), fold(la), chunk=chunk)
    want = want.reshape(B, H, T, Dv).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_ssm_scan_bonus_rwkv_mode():
    B, T, H, D = 1, 64, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    q = jax.random.normal(ks[0], (B, T, H, D)) * 0.3
    k = jax.random.normal(ks[1], (B, T, H, D)) * 0.3
    v = jax.random.normal(ks[2], (B, T, H, D)) * 0.3
    la = -jnp.abs(jax.random.normal(ks[3], (B, T, H, D))) * 0.5 - 0.01
    u = jax.random.normal(ks[4], (H, D)) * 0.1
    got = ops.ssm_scan(q, k, v, la, u=u, chunk=16)
    fold = lambda a: a.transpose(0, 2, 1, 3).reshape(B * H, T, a.shape[-1])
    want = ref.ssm_scan_ref(fold(q), fold(k), fold(v), fold(la),
                            u=jnp.tile(u, (B, 1)), chunk=16)
    want = want.reshape(B, H, T, D).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_ssm_scan_equals_sequential_recurrence():
    """Chunked kernel == naive sequential recurrence (independent oracle)."""
    B, T, H, D = 1, 48, 1, 8
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    q = jax.random.normal(ks[0], (B, T, H, D)) * 0.5
    k = jax.random.normal(ks[1], (B, T, H, D)) * 0.5
    v = jax.random.normal(ks[2], (B, T, H, D)) * 0.5
    la = -jnp.abs(jax.random.normal(ks[3], (B, T, H, D))) * 0.3 - 0.01
    got = np.asarray(ops.ssm_scan(q, k, v, la, chunk=16))
    S = np.zeros((D, D))
    qn, kn, vn, ln = (np.asarray(a[0, :, 0], np.float64) for a in (q, k, v, la))
    for t in range(T):
        S = S * np.exp(ln[t])[:, None] + np.outer(kn[t], vn[t])
        np.testing.assert_allclose(got[0, t, 0], qn[t] @ S, rtol=1e-3, atol=1e-3)


# -------------------------------------------------------------- pig aggregate
@pytest.mark.parametrize("G,N,block", [(2, 2048, 1024), (5, 8192, 512),
                                       (16, 4096, 256)])
def test_pig_aggregate_vs_ref(G, N, block):
    key = jax.random.PRNGKey(5)
    x = jax.random.normal(key, (G, N), jnp.float32)
    qs, ss = [], []
    for g in range(G):
        q, s = quantize_blockwise(x[g], block)
        qs.append(q)
        ss.append(s)
    shards = jnp.stack(qs)
    scales = jnp.stack(ss)
    got = ops.pig_aggregate(shards, scales, block=block)
    want = ref.pig_aggregate_ref(shards, scales, block=block)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    # dequantized sum approximates the true sum to int8 precision
    true = np.asarray(x.sum(0))
    err = np.abs(np.asarray(got) - true).max()
    amax = np.abs(np.asarray(x)).max()
    assert err <= G * amax / 127.0 * 0.6


# ---------------------------------------------------------- seg fan-in
# the sort + segmented-scan oracle, compiled once per shape rather than op
# by op (same operations, same results; most of these tests' CPU time)
_seg_fanin_ref = jax.jit(ref.seg_fanin_ref)


def _fanin_case(key, B, sizes, mask_per_seg=0):
    """A vectorsim-shaped burst: contiguous segments of the given sizes,
    segment-constant coef/kcap, optionally one +inf-masked slot per
    segment of two or more slots (masking a one-slot segment would leave
    it no admissible entry)."""
    sizes = np.asarray(sizes)
    G, F = len(sizes), int(sizes.sum())
    ks = jax.random.split(key, 4)
    vals = jax.random.uniform(ks[0], (B, F), jnp.float32, 1.0, 2.0)
    segid = jnp.asarray(np.repeat(np.arange(G), sizes))
    coef = jnp.asarray(np.repeat(np.asarray(jax.random.uniform(
        ks[1], (B, G), jnp.float32, 0.0, 1e-3)), sizes, axis=1))
    masked = mask_per_seg * (sizes >= 2)
    kcap = jnp.asarray(np.repeat(np.asarray(jax.random.randint(
        ks[2], (G,), 0, np.maximum(sizes - masked, 1))), sizes),
        jnp.float32)
    if masked.any():
        start = np.cumsum(sizes) - sizes
        drop = start + np.asarray(jax.random.randint(ks[3], (G,), 0, sizes))
        vals = vals.at[:, drop[masked > 0]].set(jnp.inf)
    anchor = jnp.full((B,), 1.0, jnp.float32)
    return (vals, coef, segid, kcap, -0.5, 3e-4, 2e-5, anchor)


@pytest.mark.parametrize("B,G,gsize", [
    (1, 1, 4),        # single segment
    (4, 3, 8),        # a 4-client burst (megagrid k=4 bucket), N=25 R=3
    (8, 4, 6),        # N=25, R=4
    (8, 8, 16),       # wide, pads 128 -> 128 exactly
    (3, 5, 7),        # odd everything (padding path, 35 -> 128)
    # the benchmark's N=25 bursts: B=8, F=24 padded to 128
    (8, 24, 1),       # MultiPaxos: 24 one-slot segments
    (8, 2, 12),       # PigPaxos R=2
    (8, 3, 8),        # PigPaxos R=3
    (8, 5, (5, 5, 5, 5, 4)),   # PigPaxos R=5: the ragged partition of 24
    (8, 1, 24),       # one group of all 24 followers
    (8, 1, 128),      # one 128-slot segment: every lane shift, wraps the tile
    # wider than 128 lanes: the shifts run in a loop bounded by the data's
    # longest segment, 16 an iteration
    (2, 10, 26),      # 260 -> 384: 50 shifts, the last iteration partly past
    (2, 5, (5, 40, 3, 130, 82)),   # ragged, the longest segment not first
    (1, 1, 256),      # one 256-slot segment: every lane shift, wraps the tile
])
@pytest.mark.parametrize("mask", [0, 1])
def test_seg_fanin_vs_ref(B, G, gsize, mask):
    """Kernel == the sort + segmented-scan oracle, bit for bit."""
    sizes = (gsize,) * G if isinstance(gsize, int) else gsize
    args = _fanin_case(jax.random.PRNGKey(B * 100 + G * 10 + max(sizes)),
                       B, sizes, mask_per_seg=mask)
    got = np.asarray(ops.seg_fanin(*args))
    want = np.asarray(_seg_fanin_ref(*args))
    np.testing.assert_array_equal(got, want)


def test_seg_fanin_ties_match_stable_sort():
    """Duplicate values: the kernel's (value, index) tie-break must equal
    lax.sort's stable order, so rank-dependent outputs agree exactly."""
    B, G, gsize = 4, 3, 5
    vals = jnp.tile(jnp.array([1.5, 1.25, 1.5, 1.25, 1.5], jnp.float32),
                    (B, G))
    segid = jnp.repeat(jnp.arange(G), gsize)
    coef = jnp.zeros((B, G * gsize), jnp.float32)
    kcap = jnp.full((G * gsize,), 2.0, jnp.float32)
    anchor = jnp.ones((B,), jnp.float32)
    args = (vals, coef, segid, kcap, -0.5, 3e-4, 2e-5, anchor)
    np.testing.assert_array_equal(np.asarray(ops.seg_fanin(*args)),
                                  np.asarray(_seg_fanin_ref(*args)))


def test_seg_fanin_empty_admissible_set_is_neg_inf():
    """A fully-masked segment (all followers down) yields -inf, never NaN
    (the vcoef * inf hazard the kernel's precondition rules out)."""
    B, F = 2, 6
    vals = jnp.where(jnp.arange(F)[None, :] < 3, jnp.inf,
                     jnp.ones((B, F), jnp.float32))
    segid = jnp.repeat(jnp.arange(2), 3)
    coef = jnp.zeros((B, F), jnp.float32)
    kcap = jnp.ones((F,), jnp.float32)
    out = np.asarray(ops.seg_fanin(vals, coef, segid, kcap, -0.5, 0.0,
                                   1e-5, jnp.ones((B,), jnp.float32)))
    assert np.all(np.isneginf(out[:, :3]))
    assert np.all(np.isfinite(out[:, 3:]))


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 4), st.lists(st.integers(2, 9), min_size=1,
                                   max_size=5), st.integers(0, 10 ** 6))
def test_seg_fanin_property(B, sizes, salt):
    """Random ragged segment layouts: kernel == lax oracle bit for bit
    (both paths are f32 with the same operation order per slot)."""
    ks = jax.random.split(jax.random.PRNGKey(salt), 3)
    F = sum(sizes)
    segid = jnp.asarray(np.repeat(np.arange(len(sizes)), sizes))
    vals = jax.random.uniform(ks[0], (B, F), jnp.float32, 0.5, 1.5)
    coef = jnp.asarray(np.repeat(
        np.asarray(jax.random.uniform(ks[1], (B, len(sizes)), jnp.float32,
                                      0.0, 1e-3)), sizes, axis=1))
    kcap = jnp.asarray(np.repeat(
        np.asarray(jax.random.randint(ks[2], (len(sizes),), 0, 3)),
        sizes)).astype(jnp.float32)
    kcap = jnp.minimum(kcap, jnp.asarray(np.repeat(sizes, sizes) - 1,
                                         jnp.float32))
    args = (vals, coef, segid, kcap, -0.3, 1e-4, 3e-5,
            jnp.full((B,), 0.5, jnp.float32))
    got = np.asarray(ops.seg_fanin(*args))
    want = np.asarray(_seg_fanin_ref(*args))
    np.testing.assert_array_equal(got, want)


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 8), st.integers(1, 6))
def test_pig_aggregate_property(G, nb):
    """Quantize->aggregate error is bounded by the per-block quant step."""
    block = 256
    N = nb * block
    x = jax.random.normal(jax.random.PRNGKey(G * 31 + nb), (G, N), jnp.float32)
    shards, scales = [], []
    for g in range(G):
        q, s = quantize_blockwise(x[g], block)
        shards.append(q)
        scales.append(s)
    got = np.asarray(ops.pig_aggregate(jnp.stack(shards), jnp.stack(scales),
                                       block=block))
    true = np.asarray(x.sum(0))
    step = np.asarray(jnp.stack(scales)).max()
    assert np.abs(got - true).max() <= G * step * 0.51 + 1e-6
