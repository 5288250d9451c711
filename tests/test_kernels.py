"""Pallas kernel correctness: shape/dtype sweeps against the pure-jnp
oracles, in interpret mode (assignment requirement)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.mlstm import mlstm_chunkwise, mlstm_ref
from repro.kernels.ssm_scan import ssm_scan, ssm_scan_ref

KEY = jax.random.key(0)


def tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else dict(atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # B, S, H, KVH, hd, causal, window, dtype
    (2, 256, 4, 4, 64, True, 0, jnp.float32),
    (1, 256, 8, 2, 64, True, 0, jnp.float32),     # GQA 4:1
    (2, 128, 4, 1, 32, True, 64, jnp.float32),    # MQA + sliding window
    (1, 384, 4, 4, 128, True, 0, jnp.float32),    # ragged (pad path)
    (1, 256, 4, 2, 64, True, 0, jnp.bfloat16),
    (2, 128, 2, 2, 128, True, 32, jnp.bfloat16),
]


@pytest.mark.parametrize("B,S,H,KVH,hd,causal,window,dtype", FLASH_CASES)
def test_flash_attention_matches_oracle(B, S, H, KVH, hd, causal, window, dtype):
    ks = jax.random.split(jax.random.fold_in(KEY, S * H + hd + window), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, KVH, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, KVH, hd), dtype)
    out = flash_attention(q, k, v, causal, window, 0, 128, 128, True)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **tol(dtype)
    )


FLASH_BWD_CASES = [
    # B, S, H, KVH, hd, window — backward PALLAS kernels vs jax.grad(oracle)
    (1, 128, 2, 2, 32, 0),
    (1, 128, 4, 2, 32, 0),      # GQA: dk/dv accumulate over the group dim
    (1, 128, 4, 1, 64, 32),     # MQA + sliding window
    (1, 192, 2, 2, 32, 0),      # ragged (pad path): inert pad rows
]


@pytest.mark.parametrize("B,S,H,KVH,hd,window", FLASH_BWD_CASES)
def test_flash_attention_bwd_kernels_match_oracle_grad(B, S, H, KVH, hd, window):
    ks = jax.random.split(jax.random.fold_in(KEY, 77 + S + H + window), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KVH, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KVH, hd), jnp.float32)

    def f_kernel(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, window, 0, 64, 64, True) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(
            attention_ref(q, k, v, causal=True, window=window).astype(jnp.float32) ** 2
        )

    gk = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), gk, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4, err_msg=name
        )


# ---------------------------------------------------------------------------
# selective scan
# ---------------------------------------------------------------------------

SSM_CASES = [
    (2, 128, 256, 16, 32, jnp.float32),
    (1, 96, 128, 8, 64, jnp.float32),    # ragged seq (pad path)
    (2, 64, 512, 16, 16, jnp.float32),
    (1, 128, 256, 16, 64, jnp.bfloat16),
]


@pytest.mark.parametrize("B,S,inner,N,chunk,dtype", SSM_CASES)
def test_ssm_scan_matches_oracle(B, S, inner, N, chunk, dtype):
    ks = jax.random.split(jax.random.fold_in(KEY, S * inner + N), 6)
    u = jax.random.normal(ks[0], (B, S, inner), dtype)
    dt = (jax.nn.softplus(jax.random.normal(ks[1], (B, S, inner))) * 0.1).astype(dtype)
    B_ = jax.random.normal(ks[2], (B, S, N), dtype)
    C_ = jax.random.normal(ks[3], (B, S, N), dtype)
    A = -jnp.exp(jax.random.normal(ks[4], (inner, N)) * 0.5)
    D = jax.random.normal(ks[5], (inner,))
    h0 = jax.random.normal(jax.random.fold_in(KEY, 9), (B, inner, N))
    y, h = ssm_scan(u, dt, B_, C_, A, D, h0, chunk=chunk, interpret=True)
    yr, hr = ssm_scan_ref(u, dt, B_, C_, A, D, h0)
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(yr, np.float32), **tol(dtype)
    )
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# mLSTM
# ---------------------------------------------------------------------------

MLSTM_CASES = [
    (2, 2, 128, 64, 32, jnp.float32),
    (1, 4, 64, 32, 64, jnp.float32),     # single chunk
    (2, 1, 96, 128, 16, jnp.float32),    # hd 128, odd chunk count
    (1, 2, 128, 64, 64, jnp.bfloat16),
]


@pytest.mark.parametrize("B,H,S,hd,chunk,dtype", MLSTM_CASES)
def test_mlstm_matches_oracle(B, H, S, hd, chunk, dtype):
    ks = jax.random.split(jax.random.fold_in(KEY, S * hd + chunk), 4)
    q = jax.random.normal(ks[0], (B, H, S, hd), dtype)
    k = jax.random.normal(ks[1], (B, H, S, hd), dtype)
    v = jax.random.normal(ks[2], (B, H, S, hd), dtype)
    g = (jax.random.normal(ks[3], (B, H, S, 2)) * 2.0).astype(dtype)
    h, (C, n, m) = mlstm_chunkwise(q, k, v, g, chunk=chunk, interpret=True)
    hr, (Cr, nr, mr) = mlstm_ref(q, k, v, g)
    np.testing.assert_allclose(
        np.asarray(h, np.float32), np.asarray(hr, np.float32), **tol(dtype)
    )
    np.testing.assert_allclose(np.asarray(m), np.asarray(mr), atol=1e-3, rtol=1e-3)


def test_mlstm_state_carry_composes():
    """Running two chunks separately == running them jointly (state carry)."""
    B, H, S, hd = 1, 2, 64, 32
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, H, S, hd))
    k = jax.random.normal(ks[1], (B, H, S, hd))
    v = jax.random.normal(ks[2], (B, H, S, hd))
    g = jax.random.normal(ks[3], (B, H, S, 2))
    _, joint = mlstm_ref(q, k, v, g)
    _, st = mlstm_ref(q[:, :, :32], k[:, :, :32], v[:, :, :32], g[:, :, :32])
    _, split = mlstm_ref(q[:, :, 32:], k[:, :, 32:], v[:, :, 32:], g[:, :, 32:], state=st)
    for a, b in zip(joint, split):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# paged decode attention
# ---------------------------------------------------------------------------

from repro.kernels.paged_attention import paged_attention_ref, paged_decode_attention  # noqa: E402
from repro.kernels.paged_attention.kernel import pages_per_block  # noqa: E402


def _paged_case(B, H, KVH, hd, page_size, max_blocks, lens, dtype, seed=0):
    """Random pool + a block table that scatters each sequence's pages
    non-contiguously (the pool is shared — physical page order must not
    matter), with unassigned tail entries left at -1."""
    rng = np.random.RandomState(seed)
    num_pages = B * max_blocks + 1  # +1: a never-referenced spare page
    ks = jax.random.split(jax.random.fold_in(KEY, seed + B * hd), 3)
    q = jax.random.normal(ks[0], (B, H, hd), dtype)
    k_pages = jax.random.normal(ks[1], (num_pages, page_size, KVH, hd), dtype)
    v_pages = jax.random.normal(ks[2], (num_pages, page_size, KVH, hd), dtype)
    perm = rng.permutation(B * max_blocks)
    table = np.full((B, max_blocks), -1, np.int32)
    for b, n in enumerate(lens):
        used = -(-n // page_size)  # ceil
        table[b, :used] = perm[b * max_blocks: b * max_blocks + used]
    return q, k_pages, v_pages, jnp.asarray(table), jnp.asarray(np.asarray(lens, np.int32))


# lane lengths at the kernel's edges: 0, 1, one page, one compute block,
# one past it, the whole block table
EDGES = "edges"

PAGED_CASES = [
    # B, H, KVH, hd, page_size, max_blocks, lens, dtype
    (2, 4, 4, 64, 16, 4, [64, 33], jnp.float32),
    (3, 8, 2, 64, 16, 4, [1, 50, 64], jnp.float32),   # GQA 4:1, len-1 lane
    (2, 4, 1, 32, 8, 6, [41, 17], jnp.float32),       # MQA, ragged pages
    (2, 4, 2, 64, 16, 4, [64, 7], jnp.bfloat16),
    # qwen3-4b's heads over a 2,048-position table
    (6, 32, 8, 128, 16, 128, EDGES, jnp.bfloat16),
    (6, 32, 8, 128, 16, 128, EDGES, jnp.float32),
    (4, 32, 8, 128, 16, 128, [2048, 3, 700, 129], jnp.bfloat16),
    # GQA 8:1: smaller pages, so a block of 32 of them
    (6, 16, 2, 128, 16, 40, EDGES, jnp.bfloat16),
]


@pytest.mark.parametrize("B,H,KVH,hd,ps,mb,lens,dtype", PAGED_CASES)
def test_paged_attention_kernel_matches_ref(B, H, KVH, hd, ps, mb, lens, dtype):
    if lens == EDGES:
        blk = ps * pages_per_block(ps, KVH, hd, dtype, mb)
        lens = [0, 1, ps, blk, blk + 1, ps * mb]
        assert 1 < blk < ps * mb
    q, kp, vp, table, sl = _paged_case(B, H, KVH, hd, ps, mb, lens, dtype)
    out = paged_decode_attention(q, kp, vp, table, sl, interpret=True)
    ref = paged_attention_ref(q, kp, vp, table, sl)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **tol(dtype)
    )


def test_paged_attention_ref_matches_dense_sdpa():
    """The paged oracle itself against plain masked attention on the
    gathered, densified cache — the ref is only a layout change."""
    B, H, KVH, hd, ps, mb = 2, 4, 2, 64, 16, 4
    lens = [37, 64]
    q, kp, vp, table, sl = _paged_case(B, H, KVH, hd, ps, mb, lens, jnp.float32)
    out = paged_attention_ref(q, kp, vp, table, sl)

    G = H // KVH
    k = jnp.take(kp, jnp.maximum(table, 0), axis=0).reshape(B, mb * ps, KVH, hd)
    v = jnp.take(vp, jnp.maximum(table, 0), axis=0).reshape(B, mb * ps, KVH, hd)
    qg = q.reshape(B, KVH, G, hd)
    s = jnp.einsum("bkgd,btkd->bkgt", qg, k) / np.sqrt(hd)
    mask = jnp.arange(mb * ps)[None, None, None, :] < sl[:, None, None, None]
    s = jnp.where(mask, s, -1e30)
    ref = jnp.einsum("bkgt,btkd->bkgd", jax.nn.softmax(s, axis=-1), v)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.reshape(B, H, hd)), atol=1e-5, rtol=1e-5
    )


def test_paged_attention_dead_lane_is_zero_and_isolated():
    """seq_len 0 lanes finalize to exactly zero and never perturb live
    lanes — the engine parks evicted lanes on the trash page and relies on
    this."""
    B, H, KVH, hd, ps, mb = 3, 4, 2, 32, 16, 3
    q, kp, vp, table, sl = _paged_case(B, H, KVH, hd, ps, mb, [40, 17, 25], jnp.float32)
    dead_sl = sl.at[1].set(0)
    out = paged_decode_attention(q, kp, vp, table, dead_sl, interpret=True)
    ref = paged_attention_ref(q, kp, vp, table, dead_sl)
    assert np.all(np.asarray(out[1]) == 0.0)
    assert np.all(np.asarray(ref[1]) == 0.0)
    # live lanes unchanged vs the all-live run
    full = paged_decode_attention(q, kp, vp, table, sl, interpret=True)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(full[0]), atol=0, rtol=0)
    np.testing.assert_allclose(np.asarray(out[2]), np.asarray(full[2]), atol=0, rtol=0)
