"""Paged decode attention as a Pallas TPU kernel.

One decode token per sequence attends over that sequence's pages of a
shared KV block pool (vLLM-style paged KV cache). The physical page for
grid step (b, j) is read from the *scalar-prefetched* block table inside
the k/v BlockSpec index maps — ``pltpu.PrefetchScalarGridSpec`` makes
``block_table``/``seq_lens`` available before the kernel body runs, so
the DMA engine fetches exactly the pages the sequence occupies and the
HBM traffic is O(seq_len), not O(max_context) like the dense-cache decode
path.

Grid: (B, max_blocks) with the page axis innermost. One block is one
whole pool page, ``(1, page_size, KVH, hd)``: its last two dims are the
pool's own, which the TPU's block tiling rule requires (a one-head block
``(1, page_size, 1, hd)`` is refused by the compiler). The page's
``page_size × KVH`` key rows are scored against all ``H`` query heads in
one matmul, and the pairs whose key head is not the query's own GQA head
are masked out: KVH times the score, exp and PV work a per-head block
would do, at a cost not measured yet (a ``(P, KVH, page_size, hd)`` pool
would give a legal per-head block with none of it). A TPU Pallas grid
executes sequentially per core, so the online-softmax state (m, l, acc)
for the H query heads lives in VMEM scratch and is carried across pages, exactly like the prefill
flash kernel.

Pages past ``seq_lens[b]`` are skipped with ``pl.when``, and their index
map repeats the lane's last live page, so the pipeline issues no DMA for
them (unassigned table entries are clamped to page 0). A dead lane
(seq_len 0) runs no page and finalizes to a zero vector — deterministic,
and never read by the engine.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


def _paged_kernel(
    bt_ref, sl_ref,                 # scalar-prefetch: block table, seq lens
    q_ref, k_ref, v_ref,            # VMEM tiles
    o_ref,                          # output tile
    m_scr, l_scr, acc_scr,          # VMEM scratch carried over the page axis
    *,
    sm_scale: float,
    page_size: int,
    n_blocks: int,
    group: int,
):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    seq_len = sl_ref[b]
    base = j * page_size

    @pl.when(base < seq_len)
    def _body():
        _, ps, kvh, hd = k_ref.shape
        q = q_ref[0].astype(jnp.float32)                  # (H, hd)
        # (ps, KVH, hd) -> (ps*KVH, hd): key row c is position c // KVH of
        # the page, kv head c % KVH
        k = k_ref[0].astype(jnp.float32).reshape(ps * kvh, hd)
        v = v_ref[0].astype(jnp.float32).reshape(ps * kvh, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale                                      # (H, ps*KVH)
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        own_head = (row // group) == (col % kvh)
        in_seq = (base + col // kvh) < seq_len
        s = jnp.where(own_head & in_seq, s, NEG_INF)

        m_prev = m_scr[...]                               # (H, 1)
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                            # (H, ps*KVH)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )                                                 # (H, hd)
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(j == n_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-37)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _page_index(b, j, bt, sl, *, page_size):
    """Pool page for grid step (b, j): steps past the lane's last live page
    repeat that page, so the pipeline skips their copy."""
    last = jnp.maximum(sl[b] - 1, 0) // page_size
    return (jnp.maximum(bt[b, jnp.minimum(j, last)], 0), 0, 0, 0)


def paged_attention(
    q: jax.Array,            # (B, H, hd)
    k_pages: jax.Array,      # (P, page_size, KVH, hd)
    v_pages: jax.Array,      # (P, page_size, KVH, hd)
    block_table: jax.Array,  # (B, max_blocks) int32
    seq_lens: jax.Array,     # (B,) int32
    *,
    sm_scale: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    """Paged decode attention over a shared block pool. Returns (B, H, hd)."""
    B, H, hd = q.shape
    page_size, KVH = k_pages.shape[1], k_pages.shape[2]
    n_blocks = block_table.shape[1]
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(hd)

    kernel = functools.partial(
        _paged_kernel,
        sm_scale=scale,
        page_size=page_size,
        n_blocks=n_blocks,
        group=H // KVH,
    )
    page_map = functools.partial(_page_index, page_size=page_size)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_blocks),
        in_specs=[
            pl.BlockSpec((1, H, hd), lambda b, j, bt, sl: (b, 0, 0)),
            pl.BlockSpec((1, page_size, KVH, hd), page_map),
            pl.BlockSpec((1, page_size, KVH, hd), page_map),
        ],
        out_specs=pl.BlockSpec((1, H, hd), lambda b, j, bt, sl: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        interpret=interpret,
    )(block_table.astype(jnp.int32), seq_lens.astype(jnp.int32),
      q, k_pages, v_pages)
