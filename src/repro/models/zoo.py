"""Public model API: build any assigned architecture + its input specs.

``input_specs(cfg, shape)`` returns ShapeDtypeStructs for every model input
of an assigned (arch × input-shape) cell — weak-type-correct, shardable,
zero allocation — exactly what ``jax.jit(...).lower()`` consumes in the
multi-pod dry-run. Modality frontends are stubs: whisper gets precomputed
frame embeddings, internvl precomputed patch embeddings.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.base import InputShape, ModelConfig
from repro.models import common, transformer
from repro.models.common import ParamSpec
from repro.models.transformer import RunOpts


@dataclasses.dataclass(frozen=True)
class Model:
    """A built architecture: specs + the three pure driver functions."""

    cfg: ModelConfig
    specs: Dict[str, Any]

    def init(self, key: jax.Array) -> Any:
        return common.init_params(self.specs, key)

    def abstract_params(self) -> Any:
        return common.abstract_params(self.specs)

    def forward(self, params, batch, opts: Optional[RunOpts] = None):
        return transformer.forward_train(params, batch, self.cfg, opts or RunOpts())

    def forward_hidden(self, params, batch, opts: Optional[RunOpts] = None):
        return transformer.forward_hidden(params, batch, self.cfg, opts or RunOpts())

    def unembed_weight(self, params):
        return transformer.unembed_weight(params, self.cfg)

    def prefill(self, params, batch, cache_seq_len: int, opts: Optional[RunOpts] = None):
        return transformer.prefill(
            params, batch, self.cfg, opts or RunOpts(), cache_seq_len
        )

    def decode_step(self, params, cache, tokens, pos, opts: Optional[RunOpts] = None):
        return transformer.decode_step(
            params, cache, tokens, pos, self.cfg, opts or RunOpts()
        )

    def cache_specs(self, batch: int, seq_len: int, int8: bool = False):
        return transformer.cache_specs(self.cfg, batch, seq_len, int8=int8)

    def init_cache(self, batch: int, seq_len: int):
        return transformer.init_cache(self.cfg, batch, seq_len)

    def decode_step_paged(
        self, params, cache, tokens, seq_lens, block_table,
        opts: Optional[RunOpts] = None,
        *, use_kernel: bool,
    ):
        return transformer.decode_step_paged(
            params, cache, tokens, seq_lens, block_table,
            self.cfg, opts or RunOpts(), use_kernel=use_kernel,
        )

    def paged_cache_specs(self, num_pages: int, page_size: int = 16,
                          int8: bool = False):
        return transformer.paged_cache_specs(
            self.cfg, num_pages, page_size, int8=int8
        )

    def init_paged_cache(self, num_pages: int, page_size: int = 16,
                         int8: bool = False):
        return transformer.init_paged_cache(
            self.cfg, num_pages, page_size, int8=int8
        )

    def param_count(self) -> int:
        return sum(
            int(np.prod(s.shape))
            for s in jax.tree_util.tree_leaves(
                self.specs, is_leaf=lambda x: isinstance(x, ParamSpec)
            )
        )


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg=cfg, specs=transformer.model_specs(cfg))


# ---------------------------------------------------------------------------
# Input specs per (arch × shape) cell
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape: InputShape) -> Dict[str, jax.ShapeDtypeStruct]:
    """Model inputs for one assigned cell, as ShapeDtypeStructs."""
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    f = jnp.dtype(cfg.dtype)
    out: Dict[str, jax.ShapeDtypeStruct] = {}
    if shape.mode == "train":
        out["tokens"] = jax.ShapeDtypeStruct((B, S), i32)
        out["labels"] = jax.ShapeDtypeStruct((B, S), i32)
    elif shape.mode == "prefill":
        out["tokens"] = jax.ShapeDtypeStruct((B, S), i32)
    else:  # decode: one new token against a cache of S
        out["tokens"] = jax.ShapeDtypeStruct((B, 1), i32)
    if cfg.encoder_layers:
        out["frames"] = jax.ShapeDtypeStruct(
            (B, cfg.encoder_seq_len, cfg.d_model), f
        )
    if cfg.vision_tokens and shape.mode != "decode":
        out["patches"] = jax.ShapeDtypeStruct((B, cfg.vision_tokens, cfg.vision_width), f)
    return out


def concrete_inputs(
    cfg: ModelConfig, shape: InputShape, key: jax.Array
) -> Dict[str, jax.Array]:
    """Random concrete inputs matching input_specs (smoke tests / examples)."""
    specs = input_specs(cfg, shape)
    out = {}
    for name, s in specs.items():
        key, sub = jax.random.split(key)
        if jnp.issubdtype(s.dtype, jnp.integer):
            out[name] = jax.random.randint(sub, s.shape, 0, cfg.vocab_size, s.dtype)
        else:
            out[name] = jax.random.normal(sub, s.shape, s.dtype)
    return out
