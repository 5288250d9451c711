"""Public wrapper around the paged decode-attention Pallas kernel.

Decode attention is inference-only — no custom_vjp, no padding gymnastics:
the pool/page layout is already block-aligned by construction (the engine
allocates whole pages), so the wrapper only validates the layout contract
and dispatches to the kernel. ``interpret=True`` runs the same kernel
through the Pallas interpreter on CPU (the tests' path). Where the kernel
does not fit (``models/layers.py`` ``paged_kernel_fits``: not one TPU
device, an int8 pool, a head dim other than 128) the model layer runs
:func:`paged_attention_ref`, which ``tests/test_kernels.py`` compares
against the kernel.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.kernels.paged_attention.kernel import paged_attention as _kernel


def paged_decode_attention(
    q: jax.Array,            # (B, H, hd)
    k_pages: jax.Array,      # (P, page_size, KVH, hd)
    v_pages: jax.Array,      # (P, page_size, KVH, hd)
    block_table: jax.Array,  # (B, max_blocks) int32
    seq_lens: jax.Array,     # (B,) int32
    *,
    sm_scale: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    B, H, hd = q.shape
    P, page_size, KVH, hd_k = k_pages.shape
    assert hd == hd_k, (hd, hd_k)
    assert H % KVH == 0, (H, KVH)
    assert v_pages.shape == k_pages.shape, (v_pages.shape, k_pages.shape)
    assert block_table.shape[0] == B and seq_lens.shape == (B,), (
        block_table.shape, seq_lens.shape, B,
    )
    return _kernel(
        q, k_pages, v_pages, block_table, seq_lens,
        sm_scale=sm_scale, interpret=interpret,
    )
