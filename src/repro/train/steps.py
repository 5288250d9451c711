"""train_step / serve_step builders — the functions the dry-run lowers.

``build_train_step`` returns a jit-able ``(state, batch) -> (state, metrics)``
with:

* vocab-sharded cross-entropy (logits never gathered to a full-vocab array:
  the logsumexp reduction runs on the sharded dim and GSPMD inserts a small
  all-reduce instead of an all-gather),
* microbatch gradient accumulation (``lax.scan`` over microbatches),
* optional bf16 gradient all-reduce compression (params are cast once at the
  top of the loss so backward — and hence the cross-data-shard gradient
  reduction — runs in bf16, halving collective bytes),
* remat + scan-over-layers via RunOpts,
* AdamW with global-norm clip and warmup-cosine schedule.

``build_prefill_step`` / ``build_decode_step`` are the serving pair; decode
updates the KV cache in place (donated) via dynamic_update_slice.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.config.base import ShardingLayout, TrainConfig
from repro.models import zoo
from repro.models.layers import paged_kernel_fits
from repro.models.transformer import RunOpts
from repro.optim import (
    OptState,
    adamw_update,
    clip_by_global_norm,
    init_opt_state,
)
from repro.optim.schedule import warmup_cosine


class TrainState(NamedTuple):
    params: Any
    opt: OptState
    step: jax.Array


def init_train_state(model: zoo.Model, key: jax.Array) -> TrainState:
    params = model.init(key)
    return TrainState(params=params, opt=init_opt_state(params), step=jnp.zeros((), jnp.int32))


def abstract_train_state(model: zoo.Model) -> TrainState:
    params = model.abstract_params()
    zeros_like = lambda t: jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), t
    )
    return TrainState(
        params=params,
        opt=OptState(
            m=zeros_like(params),
            v=zeros_like(params),
            count=jax.ShapeDtypeStruct((), jnp.int32),
        ),
        step=jax.ShapeDtypeStruct((), jnp.int32),
    )


def run_opts_from_layout(layout: ShardingLayout, constrain=None) -> RunOpts:
    kw = dict(
        attn_impl=layout.attn_impl,
        q_chunk=layout.q_chunk,
        kv_chunk=layout.kv_chunk,
        remat=layout.remat,
        scan_layers=layout.scan_layers,
        decode_unroll=layout.decode_unroll,
        int8_kv_cache=layout.int8_kv_cache,
    )
    if constrain is not None:
        kw["constrain"] = constrain
    return RunOpts(**kw)


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------

def cross_entropy(
    logits: jax.Array, labels: jax.Array, label_smoothing: float = 0.0
) -> jax.Array:
    """Token-mean CE. logits (B,S,V) may be vocab-sharded — no full gather:
    logsumexp reduces the sharded axis; the gold logit comes via a 1-element
    take_along_axis (a tiny cross-shard gather)."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)                       # (B, S)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if label_smoothing:
        smooth = lse - jnp.mean(logits, axis=-1)
        nll = (1.0 - label_smoothing) * nll + label_smoothing * smooth
    return jnp.mean(nll)


def chunked_cross_entropy(
    x: jax.Array,
    w: jax.Array,
    labels: jax.Array,
    chunk: int = 256,
    label_smoothing: float = 0.0,
) -> jax.Array:
    """Fused unembed+CE: scan over sequence chunks, jax.checkpoint per chunk.

    Never materializes (B, S, V) logits — forward holds one (B, chunk, V)
    slab, backward recomputes it per chunk. This is the memory-decisive
    optimization for 150k-vocab archs (qwen/gemma) at 4k×256 batches.
    """
    B, S, d = x.shape
    chunk = min(chunk, S)
    if S % chunk:
        chunk = S  # ragged fallback: single slab
    n = S // chunk
    xc = x.reshape(B, n, chunk, d).swapaxes(0, 1)      # (n, B, c, d)
    lc = labels.reshape(B, n, chunk).swapaxes(0, 1)    # (n, B, c)

    @jax.checkpoint
    def body(total, xs):
        xi, li = xs
        logits = jnp.einsum("bcd,dv->bcv", xi, w.astype(xi.dtype)).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, li[..., None], axis=-1)[..., 0]
        nll = lse - gold
        if label_smoothing:
            smooth = lse - jnp.mean(logits, axis=-1)
            nll = (1.0 - label_smoothing) * nll + label_smoothing * smooth
        return total + jnp.sum(nll), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xc, lc))
    return total / (B * S)


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------

def build_train_step(
    model: zoo.Model,
    tc: TrainConfig,
    layout: ShardingLayout,
    constrain=None,
) -> Callable[[TrainState, Dict[str, jax.Array]], Tuple[TrainState, Dict[str, jax.Array]]]:
    opts = run_opts_from_layout(layout, constrain)
    compress = layout.gradient_allreduce_dtype == "bfloat16"

    def loss_fn(params, batch):
        if compress:
            params = jax.tree_util.tree_map(
                lambda p: p.astype(jnp.bfloat16)
                if jnp.issubdtype(p.dtype, jnp.floating) else p,
                params,
            )
        if layout.fused_ce:
            x, aux = model.forward_hidden(params, batch, opts)
            x = opts.constrain(x, "loss_input")
            loss = chunked_cross_entropy(
                x, model.unembed_weight(params), batch["labels"],
                layout.ce_chunk, tc.label_smoothing,
            )
        else:
            logits, aux = model.forward(params, batch, opts)
            loss = cross_entropy(logits, batch["labels"], tc.label_smoothing)
        return loss + aux, (loss, aux)

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def split_microbatches(batch):
        def split(x):
            b = x.shape[0]
            assert b % tc.microbatches == 0, (b, tc.microbatches)
            return x.reshape(tc.microbatches, b // tc.microbatches, *x.shape[1:])

        return jax.tree_util.tree_map(split, batch)

    def train_step(state: TrainState, batch: Dict[str, jax.Array]):
        if tc.microbatches > 1:
            mb = split_microbatches(batch)

            def acc_step(carry, mb_i):
                g_acc, l_acc, a_acc = carry
                (_, (loss, aux)), grads = grad_fn(state.params, mb_i)
                g_acc = jax.tree_util.tree_map(
                    lambda a, g: a + g.astype(a.dtype), g_acc, grads
                )
                return (g_acc, l_acc + loss, a_acc + aux), None

            g0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params
            )
            (grads, loss, aux), _ = jax.lax.scan(
                acc_step, (g0, jnp.zeros(()), jnp.zeros(())), mb
            )
            scale = 1.0 / tc.microbatches
            grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
            loss, aux = loss * scale, aux * scale
        else:
            (_, (loss, aux)), grads = grad_fn(state.params, batch)

        grads, grad_norm = clip_by_global_norm(grads, tc.grad_clip)
        lr = warmup_cosine(state.step, tc)
        new_params, new_opt = adamw_update(grads, state.opt, state.params, lr, tc)
        metrics = {
            "loss": loss.astype(jnp.float32),
            "aux_loss": aux.astype(jnp.float32),
            "grad_norm": grad_norm,
            "lr": lr,
        }
        return TrainState(new_params, new_opt, state.step + 1), metrics

    return train_step


# ---------------------------------------------------------------------------
# Serve steps
# ---------------------------------------------------------------------------

def build_prefill_step(model: zoo.Model, layout: ShardingLayout, cache_seq_len: int,
                       constrain=None):
    opts = run_opts_from_layout(layout, constrain)

    def prefill_step(params, batch):
        logits, cache = model.prefill(params, batch, cache_seq_len, opts)
        return logits, cache

    return prefill_step


def build_decode_step(model: zoo.Model, layout: ShardingLayout, constrain=None):
    opts = run_opts_from_layout(layout, constrain)

    def decode_step(params, cache, tokens, pos):
        logits, new_cache = model.decode_step(params, cache, tokens, pos, opts)
        return logits, new_cache

    return decode_step


def build_paged_decode_step(
    model: zoo.Model, layout: ShardingLayout, constrain=None, *, mesh=None,
):
    """Continuous-batching decode step against the paged KV pool.

    Signature: (params, cache, tokens (B,1), seq_lens (B,), block_table
    (B,nb)) -> (logits, cache). The block table and per-lane lengths are
    small host-side int32 arrays re-fed each step (not donated); the pool
    itself is donation-friendly like the dense cache.

    Attention runs the Pallas paged kernel where ``paged_kernel_fits`` the
    mesh the step is jitted for (``None``: the default device), its
    platform, and the pool's dtype and head dim; the gather otherwise.
    """
    opts = run_opts_from_layout(layout, constrain)
    backend = jax.default_backend() if mesh is None else mesh.devices.flat[0].platform
    pool_dtype = jnp.int8 if layout.int8_kv_cache else model.cfg.dtype
    use_kernel = paged_kernel_fits(backend, pool_dtype, model.cfg.resolved_head_dim, mesh)

    def paged_decode_step(params, cache, tokens, seq_lens, block_table):
        logits, new_cache = model.decode_step_paged(
            params, cache, tokens, seq_lens, block_table, opts,
            use_kernel=use_kernel,
        )
        return logits, new_cache

    return paged_decode_step
