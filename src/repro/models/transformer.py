"""Unified decoder/enc-dec model covering all 10 assigned architectures.

One parameter tree + three drivers:

* ``forward_train``  — full-sequence forward -> logits (training).
* ``prefill``        — full-sequence forward that also *builds* the KV /
                       SSM-state cache -> (last-position logits, cache).
* ``decode_step``    — one token against the cache -> (logits, cache).

Layers are stacked and driven by ``jax.lax.scan`` (configurable remat
policy), so the HLO stays O(1) in depth — essential for 64-layer archs in
the 512-device dry-run. Heterogeneous stacks (xLSTM's mLSTM/sLSTM pattern)
scan over *super-blocks* (groups).

Positional encoding is RoPE everywhere, including the Whisper backbone
(deviation from learned/sinusoidal embeddings, noted in DESIGN.md: the
assigned decode_32k shape exceeds Whisper's 448-token learned table).
Modality frontends (Whisper conv, InternViT) are stubs per the assignment:
``batch["frames"]`` / ``batch["patches"]`` carry precomputed embeddings.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.base import AttentionKind, BlockKind, ModelConfig
from repro.models import common, layers, moe, ssm, xlstm
from repro.models.common import ParamSpec


@dataclasses.dataclass(frozen=True)
class RunOpts:
    """Execution knobs (from ShardingLayout) that change HLO, not semantics."""

    attn_impl: str = "masked"      # masked | triangular
    q_chunk: int = 512
    kv_chunk: int = 1024
    remat: str = "full"            # none | full | dots
    scan_layers: bool = True
    # decode unrolls the layer loop: a scanned decode carries the whole
    # stacked KV cache through the while loop, and XLA-CPU float
    # normalization then keeps a second f32 copy of it (2x cache memory).
    # Unrolled, each layer's slice converts transiently. On TPU either works;
    # unrolled also lets the scheduler overlap per-layer collectives.
    decode_unroll: bool = True
    int8_kv_cache: bool = False
    constrain: Callable[[jax.Array, str], jax.Array] = staticmethod(
        lambda x, name: x
    )


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------

def _norm_spec(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    if cfg.family == "audio":  # whisper uses LayerNorm
        return layers.layernorm_spec(cfg.d_model)
    return layers.rmsnorm_spec(cfg.d_model)


def block_spec(cfg: ModelConfig) -> Dict[str, Any]:
    """Spec for ONE decoder block of this config's kind (unstacked)."""
    b = cfg.block
    spec: Dict[str, Any] = {"ln1": _norm_spec(cfg)}
    if b in (BlockKind.DENSE, BlockKind.ENCDEC):
        spec["attn"] = layers.attention_spec(cfg)
        spec["ln2"] = _norm_spec(cfg)
        spec["mlp"] = layers.mlp_spec(cfg)
        if b == BlockKind.ENCDEC:
            spec["ln_cross"] = _norm_spec(cfg)
            spec["cross"] = layers.attention_spec(cfg, cross=True)
    elif b == BlockKind.MOE:
        spec["attn"] = layers.attention_spec(cfg)
        spec["ln2"] = _norm_spec(cfg)
        spec["moe"] = moe.moe_spec(cfg)
    elif b == BlockKind.HYBRID_PARALLEL:
        spec["attn"] = layers.attention_spec(cfg)
        spec["mamba"] = ssm.mamba_spec(cfg)
        spec["fuse_attn"] = layers.rmsnorm_spec(cfg.d_model)
        spec["fuse_ssm"] = layers.rmsnorm_spec(cfg.d_model)
        spec["ln2"] = _norm_spec(cfg)
        spec["mlp"] = layers.mlp_spec(cfg)
    else:
        raise ValueError(b)
    return spec


def _xlstm_group_layout(cfg: ModelConfig) -> Tuple[int, int, int]:
    """(n_groups, mlstm_per_group, has_slstm)."""
    if cfg.slstm_every:
        per = cfg.slstm_every
        assert cfg.num_layers % per == 0
        return cfg.num_layers // per, per - 1, 1
    return 1, cfg.num_layers, 0


def model_specs(cfg: ModelConfig) -> Dict[str, Any]:
    d = cfg.d_model
    spec: Dict[str, Any] = {
        "embed": ParamSpec((cfg.vocab_size, d), ("vocab", "embed"), init="embed"),
        "final_norm": _norm_spec(cfg),
    }
    if not cfg.tie_embeddings:
        spec["lm_head"] = ParamSpec((d, cfg.vocab_size), ("embed", "vocab"))

    if cfg.block in (BlockKind.MLSTM, BlockKind.SLSTM):
        groups, m_per, has_s = _xlstm_group_layout(cfg)
        g: Dict[str, Any] = {
            "mlstm": common.stacked(
                {"block": xlstm.mlstm_spec(cfg), "ln": layers.rmsnorm_spec(d)}, m_per
            )
        }
        if has_s:
            g["slstm"] = {"block": xlstm.slstm_spec(cfg), "ln": layers.rmsnorm_spec(d)}
        spec["groups"] = common.stacked(g, groups, axis_name="groups")
    else:
        spec["blocks"] = common.stacked(block_spec(cfg), cfg.num_layers)

    if cfg.encoder_layers:  # whisper encoder (self-attn only, non-causal)
        enc_block = {
            "ln1": _norm_spec(cfg),
            "attn": layers.attention_spec(cfg),
            "ln2": _norm_spec(cfg),
            "mlp": layers.mlp_spec(cfg),
        }
        spec["encoder"] = {
            "blocks": common.stacked(enc_block, cfg.encoder_layers),
            "final_norm": _norm_spec(cfg),
        }
    if cfg.vision_tokens:  # internvl stub projector
        spec["vision_proj"] = ParamSpec((cfg.vision_width, d), ("vit_embed", "embed"))
    # storage dtype: float32 master weights for training; a server stores
    # the compute dtype (every use casts to it, so the outputs are the same)
    return jax.tree_util.tree_map(
        lambda s: dataclasses.replace(s, dtype=cfg.param_dtype),
        spec,
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )


# ---------------------------------------------------------------------------
# Cache specs
# ---------------------------------------------------------------------------

def cache_len_for(cfg: ModelConfig, seq_len: int) -> int:
    """Ring-buffer caches for SWA archs; +prefix for VLM prefixes. Rounded
    up to a multiple of 16 so the cache seq dim always shards over the
    model mesh axis (an unshardable 33793-slot VLM cache is 16× the HBM)."""
    n = seq_len + (cfg.vision_tokens if cfg.vision_tokens else 0)
    if cfg.attention == AttentionKind.SLIDING and cfg.window:
        n = min(n, cfg.window)
    return -(-n // 16) * 16


def cache_specs(
    cfg: ModelConfig, batch: int, seq_len: int, int8: bool = False
) -> Dict[str, Any]:
    T = cache_len_for(cfg, seq_len)
    if cfg.block in (BlockKind.MLSTM, BlockKind.SLSTM):
        groups, m_per, has_s = _xlstm_group_layout(cfg)
        g: Dict[str, Any] = {
            "mlstm": common.stacked(xlstm.mlstm_state_spec(cfg, batch), m_per)
        }
        if has_s:
            g["slstm"] = xlstm.slstm_state_spec(cfg, batch)
        return {"groups": common.stacked(g, groups, axis_name="groups")}

    one: Dict[str, Any] = {}
    if cfg.attention != AttentionKind.NONE:
        one.update(layers.make_cache_specs(cfg, batch, T, int8=int8))
    if cfg.block == BlockKind.HYBRID_PARALLEL:
        one["ssm"] = ssm.init_state(cfg, batch)
    if cfg.block == BlockKind.MOE:
        one["moe_load"] = moe.moe_load_spec(cfg, batch)
    out: Dict[str, Any] = {"blocks": common.stacked(one, cfg.num_layers)}
    if cfg.encoder_layers:
        out["memory"] = ParamSpec(
            (batch, cfg.encoder_seq_len, cfg.d_model),
            ("batch", "seq", "embed"),
            init="zeros",
            dtype=cfg.dtype,
        )
    return out


def init_cache(cfg: ModelConfig, batch: int, seq_len: int) -> Any:
    specs = cache_specs(cfg, batch, seq_len)
    zeros = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, jnp.dtype(s.dtype)),
        specs,
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )
    # empty cache slots are marked pos_id = -1
    def fix(path, x):
        if path and path[-1] == "pos_ids":
            return jnp.full_like(x, -1)
        return x

    return _tree_map_with_path(fix, zeros)


def paged_cache_specs(
    cfg: ModelConfig, num_pages: int,
    page_size: int = layers.PAGE_SIZE, int8: bool = False,
) -> Dict[str, Any]:
    """Paged KV pool specs, stacked over layers (serving decode engine).

    Only DENSE blocks page their cache; recurrent-state archs (ssm/xlstm)
    and MOE's load counters keep dense per-lane state — the fallback
    matrix is documented in docs/kernels.md.
    """
    if cfg.block != BlockKind.DENSE:
        raise NotImplementedError(
            f"paged KV cache supports DENSE blocks only, got {cfg.block}"
        )
    one = layers.make_paged_cache_specs(cfg, num_pages, page_size, int8=int8)
    return {"blocks": common.stacked(one, cfg.num_layers)}


def init_paged_cache(
    cfg: ModelConfig, num_pages: int,
    page_size: int = layers.PAGE_SIZE, int8: bool = False,
) -> Any:
    specs = paged_cache_specs(cfg, num_pages, page_size, int8=int8)
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, jnp.dtype(s.dtype)),
        specs,
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )


def _tree_map_with_path(fn, tree, path=()):
    if isinstance(tree, dict):
        return {k: _tree_map_with_path(fn, v, path + (k,)) for k, v in tree.items()}
    return fn(path, tree)


# ---------------------------------------------------------------------------
# Block application (full sequence)
# ---------------------------------------------------------------------------

def _apply_block_full(
    params: Dict,
    x: jax.Array,
    positions: jax.Array,
    cfg: ModelConfig,
    opts: RunOpts,
    memory: Optional[jax.Array] = None,
    want_cache: bool = False,
    cache_len: int = 0,
) -> Tuple[jax.Array, jax.Array, Optional[Dict]]:
    """One block over a full sequence. Returns (x, aux_loss, cache | None)."""
    b = cfg.block
    aux = jnp.zeros((), jnp.float32)
    cache_out: Optional[Dict] = None
    x = opts.constrain(x, "activation")

    if b in (BlockKind.DENSE, BlockKind.MOE, BlockKind.ENCDEC):
        h = layers.norm(params["ln1"], x, cfg)
        attn_out, kv = _attn_full(params["attn"], h, positions, cfg, opts)
        x = x + attn_out
        if b == BlockKind.ENCDEC:
            h = layers.norm(params["ln_cross"], x, cfg)
            x = x + layers.cross_attention_layer(params["cross"], h, memory, cfg)
        h = layers.norm(params["ln2"], x, cfg)
        moe_load = None
        if b == BlockKind.MOE:
            m_out, aux, moe_load = moe.moe_block(params["moe"], h, cfg, opts.constrain)
            x = x + m_out
        else:
            x = x + layers.mlp(params["mlp"], h, cfg)
        if want_cache:
            cache_out = _kv_to_cache(kv, positions, cfg, cache_len, opts.int8_kv_cache)
            if moe_load is not None:
                cache_out["moe_load"] = moe_load

    elif b == BlockKind.HYBRID_PARALLEL:
        h = layers.norm(params["ln1"], x, cfg)
        attn_out, kv = _attn_full(params["attn"], h, positions, cfg, opts)
        ssm_out, ssm_state = ssm.mamba_block(params["mamba"], h, cfg)
        fused = 0.5 * (
            layers.rmsnorm(params["fuse_attn"], attn_out, cfg.norm_eps)
            + layers.rmsnorm(params["fuse_ssm"], ssm_out, cfg.norm_eps)
        )
        x = x + fused
        h = layers.norm(params["ln2"], x, cfg)
        x = x + layers.mlp(params["mlp"], h, cfg)
        if want_cache:
            cache_out = _kv_to_cache(kv, positions, cfg, cache_len, opts.int8_kv_cache)
            cache_out["ssm"] = ssm_state
    else:
        raise ValueError(b)
    return x, aux, cache_out


def _attn_full(params, h, positions, cfg, opts):
    """Self-attention returning output and the roped (k, v) for caching."""
    q, k, v = layers._project_qkv(params, h, h, cfg)
    q = layers.rope(q, positions, cfg.rope_theta)
    k = layers.rope(k, positions, cfg.rope_theta)
    q, k, v = layers._constrain_qkv(q, k, v, opts)
    window = cfg.window if cfg.attention == AttentionKind.SLIDING else 0
    if opts.attn_impl == "flash":
        # Pallas flash-attention prefill. Same math as the jnp blockwise
        # path (allclose-swept in tests/test_kernels.py). It compiles for
        # the TPU; there is no interpret-mode fallback on other backends.
        from repro.kernels.flash_attention import flash_attention

        out = flash_attention(q, k, v, True, window, 0, 128, 128)
    else:
        out = layers.blockwise_attention(
            q, k, v,
            causal=True,
            window=window,
            q_chunk=opts.q_chunk,
            kv_chunk=opts.kv_chunk,
            impl=opts.attn_impl,
        )
    B, S = h.shape[:2]
    out = out.reshape(B, S, cfg.q_dim)
    return common.dense(out, params["wo"], cfg.dtype), (k, v)


def _kv_to_cache(kv, positions, cfg, cache_len: int, int8: bool = False) -> Dict:
    """Write the last ``cache_len`` positions of (k, v) into a fresh cache."""
    k, v = kv
    B, S = k.shape[:2]
    T = cache_len
    if S >= T:
        kc, vc = k[:, S - T :], v[:, S - T :]
        pos_ids = positions[0, S - T :].astype(jnp.int32)
        # ring-buffer layout: slot = pos % T
        slots = pos_ids % T
        kc = jnp.take(kc, jnp.argsort(slots), axis=1)
        vc = jnp.take(vc, jnp.argsort(slots), axis=1)
        pos_sorted = jnp.take(pos_ids, jnp.argsort(slots), axis=0)
        out = {"k": kc, "v": vc, "pos_ids": pos_sorted}
    else:
        pad = T - S
        kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        pos_ids = jnp.concatenate(
            [positions[0].astype(jnp.int32), jnp.full((pad,), -1, jnp.int32)]
        )
        out = {"k": kc, "v": vc, "pos_ids": pos_ids}
    if int8:
        kq, ks = layers._quantize_kv(out["k"])
        vq, vs = layers._quantize_kv(out["v"])
        ct = jnp.dtype(cfg.dtype)
        out = {"k": kq, "v": vq, "pos_ids": out["pos_ids"],
               "k_scale": ks.astype(ct), "v_scale": vs.astype(ct)}
    return out


def _xlstm_group_full(params, x, cfg, opts, states=None, want_cache=False):
    """One xLSTM super-block (m_per mLSTM + optional sLSTM) over a sequence."""
    new_state: Dict[str, Any] = {}

    def m_body(xx, pl):
        p, st = pl
        xx = opts.constrain(xx, "activation")
        h, s = xlstm.mlstm_block(
            p["block"], layers.rmsnorm(p["ln"], xx, cfg.norm_eps), cfg, state=st
        )
        return xx + h, s

    m_params = params["mlstm"]
    m_states = states["mlstm"] if states is not None else None
    if m_states is None:
        n_m = jax.tree_util.tree_leaves(m_params)[0].shape[0]
        B = x.shape[0]
        m_states = common.stacked(xlstm.mlstm_state_spec(cfg, B), n_m)
        m_states = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, jnp.dtype(s.dtype)),
            m_states,
            is_leaf=lambda z: isinstance(z, ParamSpec),
        )

    def scan_body(xx, pl):
        xx, s = m_body(xx, pl)
        return xx, s

    x, m_state_out = jax.lax.scan(scan_body, x, (m_params, m_states))
    new_state["mlstm"] = m_state_out

    if "slstm" in params:
        p = params["slstm"]
        st = states["slstm"] if states is not None else None
        x = opts.constrain(x, "activation")
        h, s_state = xlstm.slstm_block(
            p["block"], layers.rmsnorm(p["ln"], x, cfg.norm_eps), cfg, state=st
        )
        x = x + h
        new_state["slstm"] = s_state
    return x, new_state if want_cache else None


# ---------------------------------------------------------------------------
# Drivers
# ---------------------------------------------------------------------------

def _embed_inputs(params, batch, cfg: ModelConfig):
    """tokens (+ stub modality embeddings) -> (x, positions, memory, n_prefix)."""
    tokens = batch["tokens"]
    ct = jnp.dtype(cfg.dtype)
    x = jnp.take(params["embed"], tokens, axis=0).astype(ct)
    if cfg.embed_scale:
        x = x * np.sqrt(cfg.d_model).astype(np.float32)
    n_prefix = 0
    if cfg.vision_tokens:
        patches = batch["patches"].astype(ct)  # (B, P, vit_width)
        prefix = common.dense(patches, params["vision_proj"], cfg.dtype)
        x = jnp.concatenate([prefix, x], axis=1)
        n_prefix = prefix.shape[1]
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    memory = None
    if cfg.encoder_layers:
        memory = _run_encoder(params["encoder"], batch["frames"].astype(ct), cfg)
    return x, positions, memory, n_prefix


def _run_encoder(enc_params, frames, cfg: ModelConfig):
    B, T, _ = frames.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))

    def body(x, p):
        h = layers.norm(p["ln1"], x, cfg)
        q, k, v = layers._project_qkv(p["attn"], h, h, cfg)
        q = layers.rope(q, positions, cfg.rope_theta)
        k = layers.rope(k, positions, cfg.rope_theta)
        out = layers.blockwise_attention(q, k, v, causal=False, q_chunk=512, kv_chunk=512)
        out = out.reshape(B, T, cfg.q_dim)
        x = x + common.dense(out, p["attn"]["wo"], cfg.dtype)
        h = layers.norm(p["ln2"], x, cfg)
        x = x + layers.mlp(p["mlp"], h, cfg)
        return x, None

    x, _ = jax.lax.scan(body, frames, enc_params["blocks"])
    return layers.norm(enc_params["final_norm"], x, cfg)


def _maybe_remat(fn, opts: RunOpts):
    if opts.remat == "none":
        return fn
    if opts.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        )
    return jax.checkpoint(fn)


def _unembed(params, x, cfg: ModelConfig):
    x = layers.norm(params["final_norm"], x, cfg)
    if cfg.tie_embeddings:
        return common.dense(x, params["embed"].T, cfg.dtype)
    return common.dense(x, params["lm_head"], cfg.dtype)


def forward_hidden(params, batch, cfg: ModelConfig, opts: RunOpts):
    """Full-sequence forward up to (but excluding) the LM head.

    Returns (normed hidden states over TEXT positions, aux_loss) — the fused
    cross-entropy in train/steps.py consumes this and never materializes the
    full (B, S, vocab) logits.
    """
    x, positions, memory, n_prefix = _embed_inputs(params, batch, cfg)

    if cfg.block in (BlockKind.MLSTM, BlockKind.SLSTM):
        def body(xx, p):
            y, _ = _xlstm_group_full(p, xx, cfg, opts)
            return y, jnp.zeros((), jnp.float32)

        body = _maybe_remat(body, opts)
        x, auxes = jax.lax.scan(body, x, params["groups"])
    else:
        def body(xx, p):
            y, aux, _ = _apply_block_full(p, xx, positions, cfg, opts, memory=memory)
            return y, aux

        body = _maybe_remat(body, opts)
        if opts.scan_layers:
            x, auxes = jax.lax.scan(body, x, params["blocks"])
        else:
            auxes = []
            n = jax.tree_util.tree_leaves(params["blocks"])[0].shape[0]
            for i in range(n):
                p_i = jax.tree_util.tree_map(lambda q: q[i], params["blocks"])
                x, a = body(x, p_i)
                auxes.append(a)
            auxes = jnp.stack(auxes)

    x = layers.norm(params["final_norm"], x[:, n_prefix:], cfg)
    return x, jnp.sum(auxes)


def unembed_weight(params, cfg: ModelConfig):
    """(d, vocab) projection — the tied-embedding transpose when tied."""
    if cfg.tie_embeddings:
        return params["embed"].T
    return params["lm_head"]


def forward_train(params, batch, cfg: ModelConfig, opts: RunOpts):
    """Full-sequence forward. Returns (logits over TEXT positions, aux_loss)."""
    x, aux = forward_hidden(params, batch, cfg, opts)
    logits = common.dense(x, unembed_weight(params, cfg), cfg.dtype)
    return logits, aux


def prefill(params, batch, cfg: ModelConfig, opts: RunOpts, cache_seq_len: int):
    """Forward + cache build. Returns (last-position logits, cache)."""
    x, positions, memory, n_prefix = _embed_inputs(params, batch, cfg)
    T = cache_len_for(cfg, cache_seq_len)

    if cfg.block in (BlockKind.MLSTM, BlockKind.SLSTM):
        def body(xx, p):
            y, st = _xlstm_group_full(p, xx, cfg, opts, want_cache=True)
            return y, st

        x, group_states = jax.lax.scan(body, x, params["groups"])
        cache = {"groups": group_states}
    else:
        def body(xx, p):
            y, aux, c = _apply_block_full(
                p, xx, positions, cfg, opts, memory=memory,
                want_cache=True, cache_len=T,
            )
            return y, c

        x, cache_blocks = jax.lax.scan(body, x, params["blocks"])
        cache = {"blocks": cache_blocks}
        if memory is not None:
            cache["memory"] = memory

    logits = _unembed(params, x[:, -1:, :], cfg)
    return logits, cache


def decode_step(params, cache, tokens, pos, cfg: ModelConfig, opts: RunOpts):
    """One decode step. tokens: (B, 1) int32; pos: scalar int32 — the TEXT
    position of the new token (callers count generated text tokens).

    Returns (logits (B, 1, V), new cache).
    """
    ct = jnp.dtype(cfg.dtype)
    x = jnp.take(params["embed"], tokens, axis=0).astype(ct)
    if cfg.embed_scale:
        x = x * np.sqrt(cfg.d_model).astype(np.float32)
    if cfg.vision_tokens:
        # prefill ran over [vision prefix | text], so cache slots and RoPE
        # angles are prefix-absolute; without this offset the new token
        # overwrites a live slot and masks out every later prefill position
        pos = pos + cfg.vision_tokens

    if cfg.block in (BlockKind.MLSTM, BlockKind.SLSTM):
        def body(xx, pc):
            p, st = pc
            y, new_st = _xlstm_group_full(p, xx, cfg, opts, states=st, want_cache=True)
            return y, new_st

        x, new_groups = jax.lax.scan(body, x, (params["groups"], cache["groups"]))
        new_cache = {"groups": new_groups}
    else:
        memory = cache.get("memory")

        def body(xx, pc):
            p, c = pc
            # barrier: stop XLA-CPU from hoisting the dot's f32 operand
            # convert across the scan slice (it would keep a full f32 copy
            # of the stacked KV cache alive — 2x cache memory)
            c = jax.lax.optimization_barrier(c)
            xx = opts.constrain(xx, "activation")
            h = layers.norm(p["ln1"], xx, cfg)
            if cfg.block == BlockKind.HYBRID_PARALLEL:
                attn_out, kv_cache = layers.decode_attention(
                    p["attn"], {k: v_ for k, v_ in c.items() if k != "ssm"}, h, pos, cfg
                )
                ssm_out, ssm_state = ssm.mamba_decode_step(p["mamba"], h, c["ssm"], cfg)
                fused = 0.5 * (
                    layers.rmsnorm(p["fuse_attn"], attn_out, cfg.norm_eps)
                    + layers.rmsnorm(p["fuse_ssm"], ssm_out, cfg.norm_eps)
                )
                xx = xx + fused
                new_c = dict(kv_cache, ssm=ssm_state)
            else:
                attn_out, new_c = layers.decode_attention(
                    p["attn"],
                    {k: v_ for k, v_ in c.items() if k not in ("ssm", "moe_load")},
                    h, pos, cfg,
                )
                xx = xx + attn_out
                if cfg.block == BlockKind.ENCDEC:
                    h = layers.norm(p["ln_cross"], xx, cfg)
                    xx = xx + layers.cross_attention_layer(p["cross"], h, memory, cfg)
            h = layers.norm(p["ln2"], xx, cfg)
            if cfg.block == BlockKind.MOE:
                m_out, new_load = moe.moe_decode_block(
                    p["moe"], h, c["moe_load"], pos, cfg, opts.constrain
                )
                xx = xx + m_out
                new_c = dict(new_c, moe_load=new_load)
            else:
                xx = xx + layers.mlp(p["mlp"], h, cfg)
            return xx, new_c

        if opts.decode_unroll:
            n = jax.tree_util.tree_leaves(params["blocks"])[0].shape[0]
            new_blocks = cache["blocks"]
            for i in range(n):
                p_i = jax.tree_util.tree_map(lambda t: t[i], params["blocks"])
                c_i = jax.tree_util.tree_map(lambda t: t[i], new_blocks)
                x, c_new = body(x, (p_i, c_i))
                # write the updated layer slice back in place: the stacked
                # cache stays ONE buffer end-to-end (donation-friendly)
                new_blocks = jax.tree_util.tree_map(
                    lambda stack, sl: jax.lax.dynamic_update_index_in_dim(
                        stack, sl.astype(stack.dtype), i, 0
                    ),
                    new_blocks, c_new,
                )
        else:
            x, new_blocks = jax.lax.scan(body, x, (params["blocks"], cache["blocks"]))
        new_cache = {"blocks": new_blocks}
        if memory is not None:
            new_cache["memory"] = memory

    logits = _unembed(params, x, cfg)
    return logits, new_cache


def decode_step_paged(
    params, cache, tokens, seq_lens, block_table,
    cfg: ModelConfig, opts: RunOpts,
    *, use_kernel: bool,
):
    """One continuous-batching decode step against the paged KV pool.

    tokens: (B, 1) int32; seq_lens: (B,) int32 per-lane cached-token counts
    (each lane's write position — lanes advance independently, unlike
    ``decode_step``'s single scalar ``pos``); block_table: (B, max_blocks)
    int32 with -1 for unassigned ranges (a fully dead lane produces
    deterministic garbage logits the engine never samples).

    ``use_kernel`` picks the attention path (``build_paged_decode_step``
    decides it with ``layers.paged_kernel_fits`` from where the step runs).

    Returns (logits (B, 1, V), new cache). DENSE blocks only — see
    ``paged_cache_specs``.
    """
    if cfg.block != BlockKind.DENSE:
        raise NotImplementedError(
            f"paged decode supports DENSE blocks only, got {cfg.block}"
        )
    ct = jnp.dtype(cfg.dtype)
    x = jnp.take(params["embed"], tokens, axis=0).astype(ct)
    if cfg.embed_scale:
        x = x * np.sqrt(cfg.d_model).astype(np.float32)

    def body(xx, pc):
        p, c = pc
        c = jax.lax.optimization_barrier(c)
        xx = opts.constrain(xx, "activation")
        h = layers.norm(p["ln1"], xx, cfg)
        attn_out, new_c = layers.decode_attention_paged(
            p["attn"], c, h, seq_lens, block_table, cfg, use_kernel=use_kernel,
        )
        xx = xx + attn_out
        h = layers.norm(p["ln2"], xx, cfg)
        xx = xx + layers.mlp(p["mlp"], h, cfg)
        return xx, new_c

    if opts.decode_unroll:
        n = jax.tree_util.tree_leaves(params["blocks"])[0].shape[0]
        new_blocks = cache["blocks"]
        for i in range(n):
            p_i = jax.tree_util.tree_map(lambda t: t[i], params["blocks"])
            c_i = jax.tree_util.tree_map(lambda t: t[i], new_blocks)
            x, c_new = body(x, (p_i, c_i))
            new_blocks = jax.tree_util.tree_map(
                lambda stack, sl: jax.lax.dynamic_update_index_in_dim(
                    stack, sl.astype(stack.dtype), i, 0
                ),
                new_blocks, c_new,
            )
    else:
        x, new_blocks = jax.lax.scan(body, x, (params["blocks"], cache["blocks"]))

    logits = _unembed(params, x, cfg)
    return logits, {"blocks": new_blocks}
