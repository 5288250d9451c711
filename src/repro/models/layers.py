"""Shared neural-net layers: norms, RoPE, blockwise attention, MLPs.

Attention is implemented *blockwise* (online-softmax over KV chunks, scan
over Q chunks) in pure jnp so that 32k-token prefill never materializes an
S×S score matrix — this is the XLA-side analogue of the Pallas
``flash_attention`` kernel in ``repro.kernels`` (which is the TPU-native
version of the same algorithm, validated against ``ref.py``).

Two causal implementations are selectable (``impl=``):

* ``masked``      — scan over all KV chunks with a causal mask. Simple,
                    uniform, but ~2× the useful FLOPs (upper triangle wasted).
* ``triangular``  — static unrolled loop over Q chunks; Q chunk i only visits
                    KV chunks 0..i. No wasted FLOPs; slightly larger HLO.

Sliding-window attention slices a static ``window + q_chunk`` KV band per Q
chunk (sub-quadratic — this is what makes ``long_500k`` decoding viable).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.base import ModelConfig
from repro.models import common
from repro.models.common import ParamSpec

NEG_INF = -1e30  # large-negative for masking in f32 accumulation


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm_spec(dim: int, axis: str = "embed") -> Dict[str, ParamSpec]:
    return {"scale": ParamSpec((dim,), (axis,), init="ones")}


def layernorm_spec(dim: int, axis: str = "embed") -> Dict[str, ParamSpec]:
    return {
        "scale": ParamSpec((dim,), (axis,), init="ones"),
        "bias": ParamSpec((dim,), (axis,), init="zeros"),
    }


def rmsnorm(params: Dict, x: jax.Array, eps: float) -> jax.Array:
    """RMSNorm in f32 (numerics), output cast back to input dtype."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps) * params["scale"].astype(jnp.float32)
    return y.astype(dtype)


def layernorm(params: Dict, x: jax.Array, eps: float) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    return y.astype(dtype)


def norm(params: Dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if "bias" in params:
        return layernorm(params, x, cfg.norm_eps)
    return rmsnorm(params, x, cfg.norm_eps)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding. x: (..., S, H, hd); positions: (..., S)."""
    hd = x.shape[-1]
    dtype = x.dtype
    freq = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    angles = positions[..., None].astype(jnp.float32) * freq  # (..., S, hd/2)
    cos = jnp.cos(angles)[..., None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    y = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return y.astype(dtype)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def mlp_spec(cfg: ModelConfig, d_in: int = 0, d_ff: int = 0) -> Dict[str, ParamSpec]:
    d = d_in or cfg.d_model
    f = d_ff or cfg.d_ff
    if cfg.gated_mlp:
        return {
            "wi_gate": ParamSpec((d, f), ("embed", "ffn")),
            "wi_up": ParamSpec((d, f), ("embed", "ffn")),
            "wo": ParamSpec((f, d), ("ffn", "embed")),
        }
    return {
        "wi": ParamSpec((d, f), ("embed", "ffn")),
        "bi": ParamSpec((f,), ("ffn",), init="zeros"),
        "wo": ParamSpec((f, d), ("ffn", "embed")),
        "bo": ParamSpec((d,), ("embed",), init="zeros"),
    }


def _act(x: jax.Array, kind: str) -> jax.Array:
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "gelu":
        return jax.nn.gelu(x)
    raise ValueError(kind)


def mlp(params: Dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    ct = cfg.dtype
    if cfg.gated_mlp:
        g = common.dense(x, params["wi_gate"], ct)
        u = common.dense(x, params["wi_up"], ct)
        return common.dense(_act(g, cfg.mlp_activation) * u, params["wo"], ct)
    h = common.dense(x, params["wi"], ct) + params["bi"].astype(jnp.dtype(ct))
    h = _act(h, cfg.mlp_activation)
    return common.dense(h, params["wo"], ct) + params["bo"].astype(jnp.dtype(ct))


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def attention_spec(cfg: ModelConfig, cross: bool = False) -> Dict[str, ParamSpec]:
    d = cfg.d_model
    qd, kd = cfg.q_dim, cfg.kv_dim
    spec: Dict[str, ParamSpec] = {
        "wq": ParamSpec((d, qd), ("embed", "q_dim")),
        "wk": ParamSpec((d, kd), ("embed", "kv_dim")),
        "wv": ParamSpec((d, kd), ("embed", "kv_dim")),
        "wo": ParamSpec((qd, d), ("q_dim", "embed")),
    }
    if cfg.qkv_bias:
        spec["bq"] = ParamSpec((qd,), ("q_dim",), init="zeros")
        spec["bk"] = ParamSpec((kd,), ("kv_dim",), init="zeros")
        spec["bv"] = ParamSpec((kd,), ("kv_dim",), init="zeros")
    if cfg.qk_norm and not cross:
        spec["q_norm"] = ParamSpec((cfg.resolved_head_dim,), ("head_dim",), init="ones")
        spec["k_norm"] = ParamSpec((cfg.resolved_head_dim,), ("head_dim",), init="ones")
    return spec


def _project_qkv(
    params: Dict, xq: jax.Array, xkv: jax.Array, cfg: ModelConfig
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(B,S,d) -> q (B,S,H,hd), k/v (B,T,KVH,hd)."""
    ct = cfg.dtype
    hd = cfg.resolved_head_dim
    q = common.dense(xq, params["wq"], ct)
    k = common.dense(xkv, params["wk"], ct)
    v = common.dense(xkv, params["wv"], ct)
    if "bq" in params:
        q = q + params["bq"].astype(q.dtype)
        k = k + params["bk"].astype(k.dtype)
        v = v + params["bv"].astype(v.dtype)
    q = q.reshape(*q.shape[:-1], cfg.num_heads, hd)
    k = k.reshape(*k.shape[:-1], cfg.num_kv_heads, hd)
    v = v.reshape(*v.shape[:-1], cfg.num_kv_heads, hd)
    if "q_norm" in params:
        q = rmsnorm({"scale": params["q_norm"]}, q, cfg.norm_eps)
        k = rmsnorm({"scale": params["k_norm"]}, k, cfg.norm_eps)
    return q, k, v


def _sdpa(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array],
    scale: float,
) -> jax.Array:
    """Plain softmax attention over one (q-block × kv-block) pair.

    q: (B, Sq, KVH, G, hd)  k/v: (B, T, KVH, hd)  mask: (B, Sq, T) or None.
    Grouped-query attention without materializing repeated KV heads.
    """
    # preferred_element_type: bf16 inputs accumulate into f32 WITHOUT HLO
    # convert ops on the operands (matches MXU semantics; also prevents
    # XLA-CPU from hoisting a full-f32 copy of the KV cache out of the
    # layer loop — measured 2× cache memory without it)
    s = jnp.einsum(
        "bqhgd,bthd->bhgqt", q, k, preferred_element_type=jnp.float32
    ) * scale
    if mask is not None:
        s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhgqt,bthd->bqhgd", p, v)


def _online_block(
    carry: Tuple[jax.Array, jax.Array, jax.Array],
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array],
    scale: float,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One online-softmax accumulation step (flash-attention recurrence).

    carry: acc (B,Sq,KVH,G,hd) f32, m (B,KVH,G,Sq) f32, l (B,KVH,G,Sq) f32.
    """
    acc, m, l = carry
    s = jnp.einsum(
        "bqhgd,bthd->bhgqt", q, k, preferred_element_type=jnp.float32
    ) * scale
    if mask is not None:
        s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhgqt,bthd->bqhgd", p.astype(q.dtype), v).astype(jnp.float32)
    acc_new = acc * jnp.moveaxis(corr, -1, 1)[..., None] + pv
    return acc_new, m_new, l_new


def _finish_online(acc: jax.Array, l: jax.Array, dtype) -> jax.Array:
    out = acc / jnp.maximum(jnp.moveaxis(l, -1, 1)[..., None], 1e-37)
    return out.astype(dtype)


def blockwise_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool,
    window: int = 0,
    q_chunk: int = 512,
    kv_chunk: int = 1024,
    impl: str = "masked",
    q_offset: int = 0,
    kv_valid: Optional[int] = None,
) -> jax.Array:
    """Blockwise (flash-style) attention in pure jnp.

    q: (B, Sq, H, hd); k/v: (B, T, KVH, hd). Returns (B, Sq, H, hd).
    ``q_offset``: absolute position of q[0] relative to k[0] (prefill = 0).
    ``kv_valid``: KV rows ≥ this index are padding and masked out.
    """
    B, Sq, H, hd = q.shape
    T = k.shape[1]
    KVH = k.shape[2]
    G = H // KVH
    scale = 1.0 / np.sqrt(hd)
    qg = q.reshape(B, Sq, KVH, G, hd)

    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, T)
    if Sq <= q_chunk and T <= kv_chunk:
        # tiny: single fused block
        q_pos = q_offset + jnp.arange(Sq)
        kv_pos = jnp.arange(T)
        mask = jnp.ones((B, Sq, T), bool)
        if causal:
            mask &= q_pos[None, :, None] >= kv_pos[None, None, :]
        if window:
            mask &= q_pos[None, :, None] - kv_pos[None, None, :] < window
        if kv_valid is not None and kv_valid < T:
            mask &= (kv_pos < kv_valid)[None, None, :]
        out = _sdpa(qg, k, v, mask, scale)
        return out.reshape(B, Sq, H, hd)

    # Ragged sequence lengths (e.g. a VLM's 1025-patch prefix + 4096 text
    # tokens): pad to the chunk grid instead of falling back to an O(S²)
    # fused block; padded KV rows are masked via kv_valid, padded Q rows are
    # sliced off.
    pad_q = (-Sq) % q_chunk
    pad_kv = (-T) % kv_chunk if window == 0 else 0
    if pad_q or pad_kv:
        q_p = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        k_p = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v_p = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        out = blockwise_attention(
            q_p, k_p, v_p,
            causal=causal, window=window, q_chunk=q_chunk, kv_chunk=kv_chunk,
            impl=impl, q_offset=q_offset, kv_valid=T,
        )
        return out[:, :Sq]

    n_q = Sq // q_chunk

    if window:
        # Sliding window: per q-chunk slice a static (window + q_chunk) KV band.
        band = min(window + q_chunk, T)

        @jax.checkpoint
        def q_step(_, qi):
            qc, i = qi
            qs = q_offset + i * q_chunk
            start = jnp.clip(qs + q_chunk - band, 0, T - band)
            kb = jax.lax.dynamic_slice_in_dim(k, start, band, axis=1)
            vb = jax.lax.dynamic_slice_in_dim(v, start, band, axis=1)
            q_pos = qs + jnp.arange(q_chunk)
            kv_pos = start + jnp.arange(band)
            mask = jnp.ones((B, q_chunk, band), bool)
            if causal:
                mask &= q_pos[None, :, None] >= kv_pos[None, None, :]
            mask &= q_pos[None, :, None] - kv_pos[None, None, :] < window
            if kv_valid is not None and kv_valid < T:
                mask &= (kv_pos < kv_valid)[None, None, :]
            return None, _sdpa(qc, kb, vb, mask, scale)

        qs_stacked = qg.reshape(B, n_q, q_chunk, KVH, G, hd).swapaxes(0, 1)
        _, outs = jax.lax.scan(q_step, None, (qs_stacked, jnp.arange(n_q)))
        out = outs.swapaxes(0, 1).reshape(B, Sq, KVH, G, hd)
        return out.reshape(B, Sq, H, hd)

    n_kv = T // kv_chunk
    k_blocks = k.reshape(B, n_kv, kv_chunk, KVH, hd).swapaxes(0, 1)
    v_blocks = v.reshape(B, n_kv, kv_chunk, KVH, hd).swapaxes(0, 1)

    def attend_q_chunk(qc: jax.Array, qi: int, n_vis: int) -> jax.Array:
        """Online softmax of one q chunk over KV chunks [0, n_vis)."""
        qs = q_offset + qi * q_chunk
        q_pos = qs + jnp.arange(q_chunk)

        # checkpoint each KV block: backward recomputes the (q_chunk×kv_chunk)
        # scores instead of saving them — the flash-attention memory win.
        @jax.checkpoint
        def kv_step(carry, blk):
            kb, vb, j = blk
            kv_pos = j * kv_chunk + jnp.arange(kv_chunk)
            mask = None
            if causal:
                mask = q_pos[None, :, None] >= kv_pos[None, None, :]
            if kv_valid is not None and kv_valid < T:
                bound = (kv_pos < kv_valid)[None, None, :]
                mask = bound if mask is None else mask & bound
            if mask is not None:
                mask = mask & jnp.ones((B, 1, 1), bool)
            return _online_block(carry, qc, kb, vb, mask, scale), None

        acc0 = jnp.zeros((B, q_chunk, KVH, G, hd), jnp.float32)
        m0 = jnp.full((B, KVH, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KVH, G, q_chunk), jnp.float32)
        (acc, _, l), _ = jax.lax.scan(
            kv_step,
            (acc0, m0, l0),
            (k_blocks[:n_vis], v_blocks[:n_vis], jnp.arange(n_vis)),
        )
        return _finish_online(acc, l, q.dtype)

    if impl == "triangular" and causal:
        # Static unroll: q chunk i sees exactly KV chunks 0..i — no masked-out
        # FLOPs above the diagonal (the ~2x win recorded in §Perf).
        outs = []
        for i in range(n_q):
            qc = jax.lax.slice_in_dim(qg, i * q_chunk, (i + 1) * q_chunk, axis=1)
            n_vis = min(-(-((i + 1) * q_chunk + q_offset) // kv_chunk), n_kv)
            outs.append(jax.checkpoint(
                lambda qc_, i_=i, n_=n_vis: attend_q_chunk(qc_, i_, n_)
            )(qc))
        out = jnp.concatenate(outs, axis=1)
    else:
        qs_stacked = qg.reshape(B, n_q, q_chunk, KVH, G, hd).swapaxes(0, 1)

        @jax.checkpoint
        def q_step(_, qi):
            qc, i = qi
            return None, attend_q_chunk(qc, i, n_kv)

        _, outs = jax.lax.scan(q_step, None, (qs_stacked, jnp.arange(n_q)))
        out = outs.swapaxes(0, 1).reshape(B, Sq, KVH, G, hd)
    return out.reshape(B, Sq, H, hd)


# ---------------------------------------------------------------------------
# KV cache (decode)
# ---------------------------------------------------------------------------

def make_cache_specs(
    cfg: ModelConfig, batch: int, cache_len: int, int8: bool = False
) -> Dict:
    """Abstract KV-cache entry for ONE layer (stacked over layers by caller).

    ``pos_ids`` stores the absolute position held in each slot (-1 = empty),
    which uniformly supports full caches and ring-buffer window caches.

    ``int8``: quantized cache with a per-(batch, slot, kv_head) dynamic
    scale — halves HBM for the decode-dominant cache reads (the production
    fix for MHA archs like qwen1.5-32b whose 40-head 32k cache cannot fit
    at bf16).
    """
    hd = cfg.resolved_head_dim
    kv_dtype = "int8" if int8 else cfg.dtype
    spec = {
        "k": ParamSpec((batch, cache_len, cfg.num_kv_heads, hd),
                       ("batch", "seq", "kv_heads", "head_dim"),
                       init="zeros", dtype=kv_dtype),
        "v": ParamSpec((batch, cache_len, cfg.num_kv_heads, hd),
                       ("batch", "seq", "kv_heads", "head_dim"),
                       init="zeros", dtype=kv_dtype),
        "pos_ids": ParamSpec((cache_len,), (None,), init="zeros", dtype="int32"),
    }
    if int8:
        spec["k_scale"] = ParamSpec((batch, cache_len, cfg.num_kv_heads, 1),
                                    ("batch", "seq", "kv_heads", None),
                                    init="zeros", dtype=cfg.dtype)
        spec["v_scale"] = ParamSpec((batch, cache_len, cfg.num_kv_heads, 1),
                                    ("batch", "seq", "kv_heads", None),
                                    init="zeros", dtype=cfg.dtype)
    return spec


def _quantize_kv(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-(b, slot, head) int8 quantization. x: (B, T, KVH, hd)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dequantize_kv(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (q.astype(jnp.float32) * scale.astype(jnp.float32)).astype(dtype)


def decode_attention(
    params: Dict,
    cache: Dict,
    x: jax.Array,
    pos: jax.Array,
    cfg: ModelConfig,
) -> Tuple[jax.Array, Dict]:
    """One-token attention against a (possibly ring-buffer) KV cache.

    x: (B, 1, d); pos: scalar int32 absolute position of this token.
    """
    B = x.shape[0]
    hd = cfg.resolved_head_dim
    q, k_new, v_new = _project_qkv(params, x, x, cfg)
    q = rope(q, pos[None].astype(jnp.float32) * jnp.ones((B, 1)), cfg.rope_theta)
    k_new = rope(k_new, pos[None].astype(jnp.float32) * jnp.ones((B, 1)), cfg.rope_theta)

    T = cache["k"].shape[1]
    slot = (pos % T).astype(jnp.int32)
    int8 = cache["k"].dtype == jnp.int8
    if int8:
        kq, ks = _quantize_kv(k_new)
        vq, vs = _quantize_kv(v_new)
        k = jax.lax.dynamic_update_slice_in_dim(cache["k"], kq, slot, axis=1)
        v = jax.lax.dynamic_update_slice_in_dim(cache["v"], vq, slot, axis=1)
        k_scale = jax.lax.dynamic_update_slice_in_dim(
            cache["k_scale"], ks.astype(cache["k_scale"].dtype), slot, axis=1
        )
        v_scale = jax.lax.dynamic_update_slice_in_dim(
            cache["v_scale"], vs.astype(cache["v_scale"].dtype), slot, axis=1
        )
        k_use = _dequantize_kv(k, k_scale, q.dtype)
        v_use = _dequantize_kv(v, v_scale, q.dtype)
    else:
        k = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k_new.astype(cache["k"].dtype), slot, axis=1
        )
        v = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v_new.astype(cache["v"].dtype), slot, axis=1
        )
        k_use, v_use = k.astype(q.dtype), v.astype(q.dtype)
    pos_ids = jax.lax.dynamic_update_slice_in_dim(
        cache["pos_ids"], pos[None].astype(jnp.int32), slot, axis=0
    )

    valid = pos_ids >= 0
    if cfg.window:
        valid &= pos - pos_ids < cfg.window
    valid &= pos_ids <= pos

    KVH = cfg.num_kv_heads
    G = cfg.num_heads // KVH
    qg = q.reshape(B, 1, KVH, G, hd)
    mask = jnp.broadcast_to(valid[None, None, :], (B, 1, T))
    out = _sdpa(qg, k_use, v_use, mask, 1.0 / np.sqrt(hd))
    out = out.reshape(B, 1, cfg.num_heads * hd)
    y = common.dense(out, params["wo"], cfg.dtype)
    new_cache = {"k": k, "v": v, "pos_ids": pos_ids}
    if int8:
        new_cache["k_scale"] = k_scale
        new_cache["v_scale"] = v_scale
    return y, new_cache


# ---------------------------------------------------------------------------
# Paged KV cache (serving decode)
# ---------------------------------------------------------------------------

PAGE_SIZE = 16  # token positions per pool page; matches cache_len_for's ×16


def make_paged_cache_specs(
    cfg: ModelConfig, num_pages: int, page_size: int = PAGE_SIZE,
    int8: bool = False,
) -> Dict:
    """Abstract paged-KV pool entry for ONE layer (stacked by caller).

    The pool is shared across all sequences: ``num_pages`` fixed-size
    blocks of ``page_size`` consecutive token positions each. Host-side
    per-sequence block tables (int32, -1 = unassigned) map logical
    position ranges to pool pages, replacing the dense
    ``(B, cache_len, KVH, hd)`` max-context over-allocation — HBM scales
    with *occupied* tokens, and the continuous-batching engine admits new
    sequences against pool occupancy instead of a static batch ceiling.
    The last pool page is reserved as a trash page: dead decode lanes
    write there and it is never allocated or attended to.
    """
    hd = cfg.resolved_head_dim
    kv_dtype = "int8" if int8 else cfg.dtype
    spec = {
        "k_pages": ParamSpec((num_pages, page_size, cfg.num_kv_heads, hd),
                             (None, None, "kv_heads", "head_dim"),
                             init="zeros", dtype=kv_dtype),
        "v_pages": ParamSpec((num_pages, page_size, cfg.num_kv_heads, hd),
                             (None, None, "kv_heads", "head_dim"),
                             init="zeros", dtype=kv_dtype),
    }
    if int8:
        spec["k_scale"] = ParamSpec((num_pages, page_size, cfg.num_kv_heads, 1),
                                    (None, None, "kv_heads", None),
                                    init="zeros", dtype=cfg.dtype)
        spec["v_scale"] = ParamSpec((num_pages, page_size, cfg.num_kv_heads, 1),
                                    (None, None, "kv_heads", None),
                                    init="zeros", dtype=cfg.dtype)
    return spec


def _paged_write(pages: jax.Array, new: jax.Array, rows: jax.Array) -> jax.Array:
    """Scatter one token per sequence into the flattened pool.

    pages: (P, ps, ...); new: (B, ...); rows: (B,) flattened pool rows.
    Live rows are unique by construction (one page owner per range); only
    trash-page rows may collide, and those are never read back.
    """
    P, ps = pages.shape[:2]
    flat = pages.reshape(P * ps, *pages.shape[2:])
    flat = flat.at[rows].set(new.astype(pages.dtype))
    return flat.reshape(pages.shape)


def _paged_attend_gathered(
    q: jax.Array, k: jax.Array, v: jax.Array, lens: jax.Array
) -> jax.Array:
    """Exact masked attention of one decode token over gathered pages.

    q: (B, H, hd); k/v: (B, T, KVH, hd) already gathered (and dequantized
    if int8) through the block table; lens: (B,) valid positions.
    """
    B, H, hd = q.shape
    KVH = k.shape[2]
    qg = q.reshape(B, 1, KVH, H // KVH, hd)
    T = k.shape[1]
    kv_pos = jnp.arange(T, dtype=jnp.int32)
    mask = (kv_pos[None, :] < lens[:, None])[:, None, :]  # (B, 1, T)
    out = _sdpa(qg, k, v, mask, 1.0 / np.sqrt(hd))
    return out.reshape(B, H, hd)


def paged_kernel_fits(backend: str, pool_dtype, head_dim: int, mesh=None) -> bool:
    """Whether decode attention over the paged pool runs the Pallas kernel.

    It does on a TPU, over a bf16 or f32 pool with 128-lane rows
    (``head_dim`` 128) that one device holds whole (``mesh`` None is the
    default device). Everywhere else the gather (``paged_attention_ref``)
    serves: on the CPU; for an int8 pool, whose pages the gather
    dequantizes; for other head dims, whose rows the kernel's per-head
    strided reads cannot take (the TPU compiler refuses them); and on a mesh
    of more than one device, which splits the pool over its model axis or
    copies it, and where a bare ``pallas_call`` (GSPMD does not partition
    it) would gather it whole.
    """
    float_pool = jnp.dtype(pool_dtype) in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32))
    one_device = mesh is None or mesh.size == 1
    return backend == "tpu" and float_pool and head_dim == 128 and one_device


def decode_attention_paged(
    params: Dict,
    cache: Dict,
    x: jax.Array,
    seq_lens: jax.Array,     # (B,) int32: tokens already cached per lane
    block_table: jax.Array,  # (B, max_blocks) int32; -1 = unassigned
    cfg: ModelConfig,
    *,
    use_kernel: bool = False,
    interpret: bool = False,
) -> Tuple[jax.Array, Dict]:
    """One-token attention against the shared paged KV pool.

    x: (B, 1, d). ``seq_lens[b]`` is both the number of cached tokens and
    the absolute position of this token for lane b (continuous batching:
    lanes advance independently, so position is a vector, not a scalar).
    A dead lane (unassigned page at its write index) redirects its write
    to the reserved trash page and attends over zero positions, producing
    a deterministic output the engine never reads.

    ``use_kernel`` dispatches to the Pallas kernel, which reads only each
    lane's live pages and takes bf16/f32 pools only: an int8 pool with
    ``use_kernel=True`` raises. ``build_paged_decode_step`` sets it where
    :func:`paged_kernel_fits` says the kernel fits; tests force it, with
    ``interpret``, on the CPU. Otherwise the gather path runs, which reads
    every page of the block table; int8 pools take it with dequantization
    scoped to the gathered pages — O(max_context) dequant per token, unlike
    the dense ``decode_attention`` path which dequantizes the whole cache
    each step.
    """
    from repro.kernels.paged_attention import (
        paged_attention_ref, paged_decode_attention,
    )

    B = x.shape[0]
    hd = cfg.resolved_head_dim
    KVH = cfg.num_kv_heads
    k_pages = cache["k_pages"]
    P, ps = k_pages.shape[:2]
    int8 = k_pages.dtype == jnp.int8
    if int8 and use_kernel:
        raise NotImplementedError(
            "the paged-attention kernel takes bf16/f32 pools; an int8 pool "
            "needs use_kernel=False (the gather path dequantizes its pages)"
        )

    pos = seq_lens.astype(jnp.int32)
    q, k_new, v_new = _project_qkv(params, x, x, cfg)
    q = rope(q, pos[:, None].astype(jnp.float32), cfg.rope_theta)
    k_new = rope(k_new, pos[:, None].astype(jnp.float32), cfg.rope_theta)

    pidx = jnp.clip(pos // ps, 0, block_table.shape[1] - 1)
    page = jnp.take_along_axis(block_table, pidx[:, None], axis=1)[:, 0]
    live = page >= 0
    dest = jnp.where(live, page, P - 1)  # trash page for dead lanes
    rows = dest * ps + pos % ps

    new_cache = dict(cache)
    if int8:
        kq, ks = _quantize_kv(k_new)
        vq, vs = _quantize_kv(v_new)
        new_cache["k_pages"] = _paged_write(cache["k_pages"], kq[:, 0], rows)
        new_cache["v_pages"] = _paged_write(cache["v_pages"], vq[:, 0], rows)
        new_cache["k_scale"] = _paged_write(cache["k_scale"], ks[:, 0], rows)
        new_cache["v_scale"] = _paged_write(cache["v_scale"], vs[:, 0], rows)
    else:
        new_cache["k_pages"] = _paged_write(cache["k_pages"], k_new[:, 0], rows)
        new_cache["v_pages"] = _paged_write(cache["v_pages"], v_new[:, 0], rows)

    lens_att = jnp.where(live, pos + 1, 0).astype(jnp.int32)
    q3 = q[:, 0]  # (B, H, hd)
    if int8:
        tbl = jnp.maximum(block_table, 0)
        T = tbl.shape[1] * ps
        kg = jnp.take(new_cache["k_pages"], tbl, axis=0)
        vg = jnp.take(new_cache["v_pages"], tbl, axis=0)
        ksg = jnp.take(new_cache["k_scale"], tbl, axis=0)
        vsg = jnp.take(new_cache["v_scale"], tbl, axis=0)
        k_use = _dequantize_kv(kg, ksg, q.dtype).reshape(B, T, KVH, hd)
        v_use = _dequantize_kv(vg, vsg, q.dtype).reshape(B, T, KVH, hd)
        out = _paged_attend_gathered(q3, k_use, v_use, lens_att)
    elif use_kernel:
        out = paged_decode_attention(
            q3, new_cache["k_pages"], new_cache["v_pages"],
            block_table, lens_att, interpret=interpret,
        )
    else:
        out = paged_attention_ref(
            q3, new_cache["k_pages"], new_cache["v_pages"],
            block_table, lens_att,
        )
    out = out.reshape(B, 1, cfg.num_heads * hd)
    y = common.dense(out, params["wo"], cfg.dtype)
    return y, new_cache


def _constrain_qkv(q, k, v, opts):
    # gather ONLY K and V (once per layer); Q keeps its sequence sharding so
    # the attention FLOPs still partition over the model axis by q rows
    k = opts.constrain(k, "attn_qkv")
    v = opts.constrain(v, "attn_qkv")
    return q, k, v


def full_attention_layer(
    params: Dict,
    x: jax.Array,
    positions: jax.Array,
    cfg: ModelConfig,
    *,
    q_chunk: int,
    kv_chunk: int,
    impl: str,
) -> jax.Array:
    """Self-attention over a full sequence (train / prefill). x: (B,S,d)."""
    q, k, v = _project_qkv(params, x, x, cfg)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    out = blockwise_attention(
        q, k, v,
        causal=True,
        window=cfg.window if cfg.attention.value == "sliding" else 0,
        q_chunk=q_chunk,
        kv_chunk=kv_chunk,
        impl=impl,
    )
    B, S = x.shape[:2]
    out = out.reshape(B, S, cfg.q_dim)
    return common.dense(out, params["wo"], cfg.dtype)


def cross_attention_layer(
    params: Dict,
    x: jax.Array,
    memory: jax.Array,
    cfg: ModelConfig,
) -> jax.Array:
    """Encoder-decoder cross attention (no RoPE, no mask). memory: (B,T,d)."""
    q, k, v = _project_qkv(params, x, memory, cfg)
    out = blockwise_attention(q, k, v, causal=False, q_chunk=512, kv_chunk=512)
    B, S = x.shape[:2]
    return common.dense(out.reshape(B, S, cfg.q_dim), params["wo"], cfg.dtype)
