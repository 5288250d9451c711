"""The Qwen3 family: its keys, its parameter tree, and the plain float32
Qwen3 (decoder only, tied or untied head), in jax.numpy.

Follows Qwen3's published modeling: token embedding; per layer RMSNorm,
q/k/v projections without bias, RMSNorm of q and k over head_dim (qk-norm),
rotary embedding (rotate-half, base ``rope_theta``), causal grouped-query
attention (query head h reads key/value head h // (H / KVH)), output
projection, residual; RMSNorm, SwiGLU MLP, residual; final RMSNorm and the
LM head (the embedding's transpose when tied). No cache, no paging, no
batching: one sequence, all positions at once.

Every matmul runs at ``Precision.HIGHEST`` in float32; the weights are the
benchmark's seeded weights as served (bfloat16 values), read in float32.
The layers run in a scan, each layer's weights widened to float32 only
while it runs, so the reference fits one chip beside nothing else.

``quant="fp8"`` is the control: the same computation with both operands of
every matmul rounded to float8 e4m3 at a per-tensor scale (the step below
the bfloat16 the configuration serves in).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0

# source key -> the program's ModelConfig field
KEYS = {
    "hidden_size": "d_model", "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim", "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}


def program_fields(conf: Dict[str, Any], arch) -> Dict[str, Any]:
    """The program's config fields besides ``KEYS``: it computes in the
    dtype it serves in."""
    return {"dtype": conf["serve_dtype"]}


def describe(c: Dict[str, Any]) -> Dict[str, Any]:
    """The parameter tree as the program stores it: (shape, kind, scale)."""
    d, L = c["hidden_size"], c["num_hidden_layers"]
    H, KVH, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    f, V = c["intermediate_size"], c["vocab_size"]
    qd, kd = H * hd, KVH * hd
    tree: Dict[str, Any] = {
        "embed": ((V, d), "embed", 1.0),
        "final_norm": {"scale": ((d,), "norm", 1.0)},
        "blocks": {
            "ln1": {"scale": ((L, d), "norm", 1.0)},
            "attn": {
                "wq": ((L, d, qd), "matrix", 1.0), "wk": ((L, d, kd), "matrix", 1.0),
                "wv": ((L, d, kd), "matrix", 1.0), "wo": ((L, qd, d), "matrix", 1.0),
                "q_norm": ((L, hd), "norm", 1.0), "k_norm": ((L, hd), "norm", 1.0),
            },
            "ln2": {"scale": ((L, d), "norm", 1.0)},
            "mlp": {"wi_gate": ((L, d, f), "matrix", 1.0), "wi_up": ((L, d, f), "matrix", 1.0),
                    "wo": ((L, f, d), "matrix", 1.0)},
        },
    }
    if not c["tie_word_embeddings"]:
        tree["lm_head"] = ((d, V), "matrix", 1.0)
    return tree


def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(spec: str, a, b, quant: Optional[str]):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if quant == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _layer(c: Dict[str, Any], quant, x, w):
    T = x.shape[0]
    H, KVH, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    eps = c["rms_norm_eps"]
    pos = jnp.arange(T)
    a = w["attn"]
    h = _rms(x, w["ln1"]["scale"], eps)
    q = _mm("td,de->te", h, a["wq"], quant).reshape(T, H, hd)
    k = _mm("td,de->te", h, a["wk"], quant).reshape(T, KVH, hd)
    v = _mm("td,de->te", h, a["wv"], quant).reshape(T, KVH, hd)
    q = _rope(_rms(q, a["q_norm"], eps), pos, c["rope_theta"])
    k = _rope(_rms(k, a["k_norm"], eps), pos, c["rope_theta"])
    rep = H // KVH
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    s = _mm("thd,shd->hts", q, k, quant) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = _mm("hts,shd->thd", p, v, quant).reshape(T, H * hd)
    x = x + _mm("te,ed->td", o, a["wo"], quant)
    m = w["mlp"]
    h = _rms(x, w["ln2"]["scale"], eps)
    g = _mm("td,df->tf", h, m["wi_gate"], quant)
    u = _mm("td,df->tf", h, m["wi_up"], quant)
    return x + _mm("tf,fd->td", jax.nn.silu(g) * u, m["wo"], quant)


def logits(c: Dict[str, Any], w: Dict[str, Any], tokens, quant: Optional[str] = None):
    """(T, vocab) float32 logits of one sequence ``tokens`` (T,)."""
    x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
    x, _ = jax.lax.scan(lambda xx, wl: (_layer(c, quant, xx, wl), None), x, w["blocks"])
    x = _rms(x, w["final_norm"]["scale"], c["rms_norm_eps"])
    head = w["embed"].T if c["tie_word_embeddings"] else w["lm_head"]
    return _mm("td,dv->tv", x, head, quant)


def readings(c: Dict[str, Any], w: Dict[str, Any], tokens, targets, quant=None):
    """Per position: the best logit, the logit of ``targets`` and the
    top-1 token, all of this computation."""
    lg = logits(c, w, tokens, quant)
    tgt = jnp.take_along_axis(lg, targets[:, None], axis=1)[:, 0]
    return jnp.max(lg, axis=1), tgt, jnp.argmax(lg, axis=1).astype(jnp.int32)


def compiled_readings(c: Dict[str, Any], quant=None):
    return jax.jit(lambda w, t, g: readings(c, w, t, g, quant))
