"""The xLSTM family: its keys, its parameter tree, its FLOPs beyond the
matrices, and plain float32 xLSTM training steps (loss, gradients, AdamW),
in jax.numpy.

The model is the repo's xLSTM stack as its docstrings state it
(arXiv:2405.04517 with the repo's departures, listed in the configuration
file under ``assumed``): token embedding; ``num_blocks / slstm_every``
groups of ``slstm_every - 1`` mLSTM blocks and one sLSTM block, each
pre-normed (RMSNorm) and residual; final RMSNorm; an untied LM head; the
mean next-token cross entropy over every position.

* mLSTM block, in the paper's parallel form (not the program's chunkwise
  one): up-projection to ``u, z``; ``q, k, v`` from ``u``, ``k`` scaled by
  ``1/sqrt(hd)``; input and forget gate pre-activations ``i~, f~`` from
  ``u``; with ``F`` the running sum of ``f~`` (the forget gate is
  ``exp(f~)``), the weight of source ``s`` at ``t >= s`` is
  ``exp(i~_s + F_t - F_s - m_t)``, ``m_t = max(F_t, max_s(i~_s + F_t -
  F_s))`` (the zero initial state counts as a source of weight ``F_t``);
  ``h_t = sum_s w (q_t.k_s) v_s / max(|sum_s w (q_t.k_s)|, 1)``; out
  ``(h * silu(z)) @ down``.
* sLSTM block: the exponential-gated recurrence with stabilizer ``m``,
  ``h = o * c / max(n, 1)``, then ``(gelu(a) * b) @ down`` of an
  up-projection (tanh-approximate GELU, as the program's ``jax.nn.gelu``).
* AdamW with global-norm clipping, decoupled weight decay on every leaf,
  bias correction, and the warmup-cosine schedule of the job's file.

Every matmul runs at ``Precision.HIGHEST`` in float32. ``quant="fp8"``
is the control: both operands of every matmul rounded to float8 e4m3 at a
per-tensor scale, the step below the bfloat16 the program computes in.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0

# source key -> the program's ModelConfig field
KEYS = {
    "embedding_dim": "d_model", "num_blocks": "num_layers", "num_heads": "num_heads",
    "vocab_size": "vocab_size", "slstm_every": "slstm_every", "norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}


def program_fields(conf: Dict[str, Any], arch) -> Dict[str, Any]:
    """The program's config fields besides ``KEYS``: one key/value head per
    head, the mLSTM's chunk, float32 master weights and the compute dtype."""
    return {"num_kv_heads": conf["num_heads"],
            "ssm": dataclasses.replace(arch.ssm, chunk=conf["mlstm_chunk"]),
            "param_dtype": conf["param_dtype"], "dtype": conf["compute_dtype"]}


def describe(c: Dict[str, Any]) -> Dict[str, Any]:
    """The parameter tree as the program stores it: (shape, kind, scale)."""
    d, V, H = c["embedding_dim"], c["vocab_size"], c["num_heads"]
    per = c["slstm_every"]
    G, M = c["num_blocks"] // per, per - 1
    inner = c["mlstm_proj_factor"] * d
    ff = c["slstm_ffn_factor"] * d
    return {
        "embed": ((V, d), "embed", 1.0),
        "final_norm": {"scale": ((d,), "norm", 1.0)},
        "lm_head": ((d, V), "matrix", 1.0),
        "groups": {
            "mlstm": {
                "block": {
                    "up_proj": ((G, M, d, 2 * inner), "matrix", 1.0),
                    "wq": ((G, M, inner, inner), "matrix", 1.0),
                    "wk": ((G, M, inner, inner), "matrix", 1.0),
                    "wv": ((G, M, inner, inner), "matrix", 1.0),
                    "w_if": ((G, M, inner, 2 * H), "matrix", 1.0),
                    "b_if": ((G, M, 2 * H), "zeros", 0.0),
                    "down_proj": ((G, M, inner, d), "matrix", 1.0),
                },
                "ln": {"scale": ((G, M, d), "norm", 1.0)},
            },
            "slstm": {
                "block": {
                    "w_gates": ((G, d, 4 * d), "matrix", 1.0),
                    "r_gates": ((G, d, 4 * d), "matrix", 0.5),
                    "b_gates": ((G, 4 * d), "zeros", 0.0),
                    "up_proj": ((G, d, ff), "matrix", 1.0),
                    "down_proj": ((G, ff // 2, d), "matrix", 1.0),
                },
                "ln": {"scale": ((G, d), "norm", 1.0)},
            },
        },
    }


def flops_per_token_beyond_matrices(c: Dict[str, Any]) -> int:
    """The forward's work per token that no weight matrix counts: the
    mLSTM's matrix-memory update and readout, 4 * heads * head_dim**2 per
    mLSTM block."""
    d, H, per = c["embedding_dim"], c["num_heads"], c["slstm_every"]
    hd = c["mlstm_proj_factor"] * d // H
    return 4 * H * hd * hd * (c["num_blocks"] // per * (per - 1))


def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(spec: str, a, b, quant: Optional[str]):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if quant == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _mlstm(c, w, x, quant):
    B, S, _ = x.shape
    H = c["num_heads"]
    inner = c["mlstm_proj_factor"] * c["embedding_dim"]
    hd = inner // H
    up = _mm("bsd,de->bse", x, w["up_proj"], quant)
    u, z = up[..., :inner], up[..., inner:]
    q = _mm("bse,ef->bsf", u, w["wq"], quant).reshape(B, S, H, hd)
    k = _mm("bse,ef->bsf", u, w["wk"], quant).reshape(B, S, H, hd) / math.sqrt(hd)
    v = _mm("bse,ef->bsf", u, w["wv"], quant).reshape(B, S, H, hd)
    gates = _mm("bse,eg->bsg", u, w["w_if"], quant) + w["b_if"]
    ig, fg = gates[..., :H], gates[..., H:]                      # (B,S,H)
    F = jnp.cumsum(fg, axis=1)
    logw = ig[:, None, :, :] + F[:, :, None, :] - F[:, None, :, :]   # (B,t,s,H)
    causal = jnp.tril(jnp.ones((S, S), bool))[None, :, :, None]
    logw = jnp.where(causal, logw, -jnp.inf)
    m = jnp.maximum(F, jnp.max(logw, axis=2))                     # (B,t,H)
    wts = jnp.exp(logw - m[:, :, None, :])
    qk = _mm("bthd,bshd->btsh", q, k, quant) * wts
    num = _mm("btsh,bshd->bthd", qk, v, quant)
    den = jnp.maximum(jnp.abs(jnp.sum(qk, axis=2)), 1.0)         # (B,t,H)
    h = (num / den[..., None]).reshape(B, S, inner)
    return _mm("bse,ed->bsd", h * jax.nn.silu(z), w["down_proj"], quant)


def _slstm(c, w, x, quant):
    B, S, d = x.shape
    wx = _mm("bsd,dg->bsg", x, w["w_gates"], quant) + w["b_gates"]

    def step(carry, wx_t):
        cc, n, h, m = carry
        pre = wx_t + _mm("bd,dg->bg", h, w["r_gates"], quant)
        zt, it, ft, ot = jnp.split(pre, 4, axis=-1)
        zt, ot = jnp.tanh(zt), jax.nn.sigmoid(ot)
        m_new = jnp.maximum(ft + m, it)
        i_ = jnp.exp(it - m_new)
        f_ = jnp.exp(ft + m - m_new)
        cc = f_ * cc + i_ * zt
        n = f_ * n + i_
        h = ot * cc / jnp.maximum(n, 1.0)
        return (cc, n, h, m_new), h

    zero = jnp.zeros((B, d), jnp.float32)
    _, hs = jax.lax.scan(step, (zero, zero, zero, zero), wx.swapaxes(0, 1))
    y = hs.swapaxes(0, 1)
    u = _mm("bsd,df->bsf", y, w["up_proj"], quant)
    a, b = jnp.split(u, 2, axis=-1)
    return _mm("bsf,fd->bsd", jax.nn.gelu(a, approximate=True) * b, w["down_proj"], quant)


def loss(c: Dict[str, Any], params, tokens, labels, quant: Optional[str] = None):
    eps = c["norm_eps"]
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)

    @jax.checkpoint
    def m_block(xx, w):
        return xx + _mlstm(c, w["block"], _rms(xx, w["ln"]["scale"], eps), quant), None

    @jax.checkpoint
    def s_block(xx, w):
        return xx + _slstm(c, w["block"], _rms(xx, w["ln"]["scale"], eps), quant)

    def group(xx, g):
        xx, _ = jax.lax.scan(m_block, xx, g["mlstm"])
        return s_block(xx, g["slstm"]), None

    x, _ = jax.lax.scan(group, x, params["groups"])
    x = _rms(x, params["final_norm"]["scale"], eps)
    lg = _mm("bsd,dv->bsv", x, params["lm_head"], quant)
    lse = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


def learning_rate(opt: Dict[str, Any], step: int) -> float:
    """Linear warmup to ``learning_rate``, then cosine down to a tenth."""
    lr, warm, total = opt["learning_rate"], opt["warmup_steps"], opt["total_steps"]
    if step < warm:
        return lr * min(1.0, step / max(warm, 1))
    frac = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return lr * (0.1 + 0.9 * 0.5 * (1.0 + math.cos(math.pi * frac)))


def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x)))


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def _adamw_leaf(p, m, v, g, scale, lr, t, hp):
    """One leaf's AdamW update, its buffers donated: the reference holds
    params, moments and one gradient, and nothing twice."""
    b1, b2, eps, wd = hp
    g = g * scale
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    p = p - lr * ((m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps) + wd * p)
    return p, m, v, _norm(g)


def train_readings(c: Dict[str, Any], opt: Dict[str, Any], make_params, batches,
                   quant: Optional[str] = None) -> Dict[str, Any]:
    """Three AdamW steps from ``make_params()`` over ``batches``: each step's
    loss, the norm of each leaf of the first (clipped) gradient and of the
    unclipped one, and of each leaf's change over the three steps (the
    start weights are made again at the end, not kept)."""
    vg = jax.jit(jax.value_and_grad(lambda p, t, l: loss(c, p, t, l, quant)))
    norms = jax.jit(lambda leaves: [_norm(x) for x in leaves])
    hp = (opt["beta1"], opt["beta2"], opt["eps"], opt["weight_decay"])
    params = make_params()
    leaves, treedef = jax.tree_util.tree_flatten(params)
    del params
    m = [jnp.zeros_like(x) for x in leaves]
    v = [jnp.zeros_like(x) for x in leaves]
    losses, first_grad, raw_grad = [], None, None
    for i, b in enumerate(batches):
        lval, g = vg(jax.tree_util.tree_unflatten(treedef, leaves),
                     jnp.asarray(b["tokens"]), jnp.asarray(b["labels"]))
        g = jax.tree_util.tree_leaves(g)
        raw = [float(x) for x in norms(g)]
        total = math.sqrt(sum(x * x for x in raw))
        scale = min(1.0, opt["grad_clip"] / max(total, 1e-9))
        clipped = []
        for k in range(len(leaves)):
            leaves[k], m[k], v[k], n = _adamw_leaf(leaves[k], m[k], v[k], g[k], scale,
                                                   learning_rate(opt, i), float(i + 1), hp)
            g[k] = None
            clipped.append(float(n))
        if i == 0:
            raw_grad, first_grad = raw, clipped
        losses.append(float(lval))
    del m, v, g
    start = jax.tree_util.tree_leaves(make_params())
    change = [float(x) for x in jax.jit(lambda a, b: [_norm(x - y) for x, y in zip(a, b)])(
        leaves, start)]
    return {"losses": losses, "grad": first_grad, "raw_grad": raw_grad, "change": change}


def compare(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    """The three numbers compared, each the worst over steps or leaves.

    * ``loss_gap``: relative gap of each step's loss;
    * ``grad_gap``: per leaf, the gap of the first gradient's norm over the
      larger of that leaf's reference norm and the median leaf's;
    * ``update_gap``: the same for each leaf's change over three steps,
      leaving out leaves whose reference gradient is under a thousandth of
      the median leaf's (they move by round-off alone).
    """
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(prog["losses"], ref["losses"]))

    def worst(a, b, keep):
        med = float(np.median([x for x, k in zip(b, keep) if k]))
        return max(abs(x - y) / max(y, med) for x, y, k in zip(a, b, keep) if k)

    n = len(ref["grad"])
    med_raw = float(np.median(ref["raw_grad"]))
    moving = [g >= 1e-3 * med_raw for g in ref["raw_grad"]]
    return {"loss_gap": loss_gap,
            "grad_gap": worst(prog["grad"], ref["grad"], [True] * n),
            "update_gap": worst(prog["change"], ref["change"], moving)}
