"""Paged decode attention as a Pallas TPU kernel.

One decode token per sequence attends over that sequence's pages of a
shared KV block pool (vLLM-style paged KV cache). The pools stay in HBM
(``memory_space=pl.ANY``); the kernel copies only the pages a lane holds,
so its HBM traffic and its work follow the live positions, not the width
of the block table.

Grid: one step per lane. A step walks its lane's live pages in compute
blocks of ``pages_per_block`` pages. Each page is one DMA of the pool's
own ``(page_size, KVH, hd)`` slab (viewed as ``(page_size * KVH, hd)``,
the same bytes), started from the scalar-prefetched block table into one
of two VMEM slots: the copies of the next block — the lane's next one, or
the next lane's first — start before the current block's compute, so the
DMA engine runs one block ahead across the whole grid. Pages past
``seq_lens[b]`` are never copied; the lane's last, partial block masks the
positions past its length. A dead lane (seq_len 0) copies nothing and
finalizes to a zero vector — deterministic, and never read by the engine.

Each KV head's rows are read out of the slot with a sublane stride of KVH
and scored against its own query group only; the online softmax (m, l,
acc) stays in float32 per head, as in the prefill flash kernel.
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

# VMEM bytes of one K (or V) compute block: pages_per_block follows from
# the page's bytes, page_size x KVH x head_dim x itemsize. 256 KiB is 8 of
# qwen3-4b's bf16 pages (128 positions), the best of 4, 8 and 16 pages on a
# TPU v5e at the serving benchmark's decode shapes.
BLOCK_BYTES = 256 * 1024


def pages_per_block(page_size: int, kv_heads: int, head_dim: int,
                    dtype, max_blocks: int) -> int:
    page_bytes = page_size * kv_heads * head_dim * jnp.dtype(dtype).itemsize
    return max(1, min(BLOCK_BYTES // page_bytes, max_blocks))


def _paged_kernel(
    bt_ref, sl_ref,                 # scalar prefetch: flat block table, seq lens
    q_ref,                          # (1, KVH, G, hd) VMEM
    k_hbm, v_hbm,                   # (P, page_size * KVH, hd) in HBM
    o_ref,                          # (1, KVH, G, hd) VMEM
    k_buf, v_buf,                   # (2, pages_per_block * page_size * KVH, hd)
    sems,                           # DMA semaphores, (2 [k, v], 2 [slot])
    slot_ref,                       # SMEM (1,): slot of the next block to compute
    k_f32, v_f32,                   # (pages_per_block * page_size * KVH, hd) f32
    m_scr, l_scr, acc_scr,          # (KVH, G, 1), (KVH, G, 1), (KVH, G, hd) f32
    *,
    sm_scale: float,
    page_size: int,
    pages_per_block: int,
    n_blocks: int,
    n_lanes: int,
):
    b = pl.program_id(0)
    kvh = m_scr.shape[0]
    rows = page_size * kvh                     # pool rows per page
    block_t = pages_per_block * page_size      # positions per compute block

    def page_copies(lane, blk, slot, go):
        """Start (``go``) or wait for the copies of block ``blk`` of
        ``lane``: one per live page, none past the lane's length."""
        length = sl_ref[lane]
        for i in range(pages_per_block):
            j = blk * pages_per_block + i

            @pl.when(j * page_size < length)
            def _():
                page = jnp.maximum(bt_ref[lane * n_blocks + j], 0)
                dst = pl.ds(i * rows, rows)
                for cp in (
                    pltpu.make_async_copy(k_hbm.at[page], k_buf.at[slot, dst], sems.at[0, slot]),
                    pltpu.make_async_copy(v_hbm.at[page], v_buf.at[slot, dst], sems.at[1, slot]),
                ):
                    if go:
                        cp.start()
                    else:
                        cp.wait()

    @pl.when(b == 0)
    def _first():
        # stale rows of a partial block are masked to p == 0; zeroing the
        # slots once keeps them finite, so p * v adds nothing
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        slot_ref[0] = 0
        page_copies(0, 0, 0, go=True)

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    length = sl_ref[b]
    n_live = (length + block_t - 1) // block_t

    def prefetch_next(blk, slot):
        @pl.when(blk + 1 < n_live)
        def _():
            page_copies(b, blk + 1, slot, go=True)

        @pl.when((blk + 1 >= n_live) & (b + 1 < n_lanes))
        def _():
            page_copies(b + 1, 0, slot, go=True)

    def block(blk, carry):
        slot = slot_ref[0]
        prefetch_next(blk, 1 - slot)
        page_copies(b, blk, slot, go=False)
        pos = blk * block_t + jax.lax.broadcasted_iota(jnp.int32, (1, block_t), 1)
        valid = pos < length
        # strided loads take 32-bit rows: stage the block in float32
        k_f32[...] = k_buf[slot].astype(jnp.float32)
        v_f32[...] = v_buf[slot].astype(jnp.float32)
        for h in range(kvh):
            own = pl.ds(h, block_t, stride=kvh)   # head h's row of each position
            k = k_f32[own, :].astype(k_buf.dtype)                  # (T, hd)
            s = jax.lax.dot_general(
                q_ref[0, h], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * sm_scale                                           # (G, T)
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_scr[h] = l_scr[h] * corr + jnp.sum(p, axis=1, keepdims=True)
            v = v_f32[own, :].astype(v_buf.dtype)
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                                      # (G, hd)
            acc_scr[h] = acc_scr[h] * corr + pv
            m_scr[h] = m_new
        slot_ref[0] = 1 - slot
        return carry

    jax.lax.fori_loop(0, n_live, block, 0)

    @pl.when(n_live == 0)
    def _dead():
        prefetch_next(0, slot_ref[0])

    l = jnp.maximum(l_scr[...], 1e-37)
    o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


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
    P, page_size, KVH, _ = k_pages.shape
    G = H // KVH
    n_blocks = block_table.shape[1]
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(hd)
    ppb = pages_per_block(page_size, KVH, hd, k_pages.dtype, n_blocks)

    kernel = functools.partial(
        _paged_kernel,
        sm_scale=scale,
        page_size=page_size,
        pages_per_block=ppb,
        n_blocks=n_blocks,
        n_lanes=B,
    )
    lane = pl.BlockSpec((1, KVH, G, hd), lambda b, bt, sl: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            lane,
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=lane,
        scratch_shapes=[
            pltpu.VMEM((2, ppb * page_size * KVH, hd), k_pages.dtype),
            pltpu.VMEM((2, ppb * page_size * KVH, hd), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((ppb * page_size * KVH, hd), jnp.float32),
            pltpu.VMEM((ppb * page_size * KVH, hd), jnp.float32),
            pltpu.VMEM((KVH, G, 1), jnp.float32),
            pltpu.VMEM((KVH, G, 1), jnp.float32),
            pltpu.VMEM((KVH, G, hd), jnp.float32),
        ],
    )
    # (page_size, KVH, hd) -> (page_size * KVH, hd) merges a page's positions
    # with its heads: the same bytes, so XLA makes it a bitcast of the pool
    rows = (P, page_size * KVH, hd)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, G, hd), q.dtype),
        # the copies run one block ahead across lanes: the grid is sequential
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        # the TPU interpreter runs the DMAs and their semaphores on the CPU
        interpret=pltpu.InterpretParams() if interpret else False,
        name="paged_attention",
    )(block_table.astype(jnp.int32).reshape(-1), seq_lens.astype(jnp.int32),
      q.reshape(B, KVH, G, hd), k_pages.reshape(rows), v_pages.reshape(rows))
    return out.reshape(B, H, hd)
