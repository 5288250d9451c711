"""Seeded random weights, made by the benchmark on the device.

Both the program and the plain reference are given these weights: they are
an input, like the traffic, and the reference takes nothing that the
program made. The tree is laid out as the program stores its parameters
(``describe``); ``make`` draws every leaf in one jitted call, in the dtype
the configuration serves or trains in, under the shardings it is given.

Each leaf draws from its own stream, folded from the seed and its path, so
a leaf's values do not depend on the other leaves.
"""
from __future__ import annotations

import zlib
from typing import Any, Dict, Tuple

Leaf = Tuple[Tuple[int, ...], str, float]   # shape, kind, scale


def _qwen3(c: Dict[str, Any]) -> Dict[str, Any]:
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


def _xlstm(c: Dict[str, Any]) -> Dict[str, Any]:
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


FAMILIES = {"qwen3": _qwen3, "xlstm": _xlstm}


def describe(conf: Dict[str, Any]) -> Dict[str, Any]:
    """The parameter tree of a configuration: (shape, kind, scale) leaves."""
    return FAMILIES[conf["reference"]](conf)


def is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], tuple)


def _draw(key, path: str, leaf: Leaf, width: int, dtype):
    import jax
    import jax.numpy as jnp

    shape, kind, scale = leaf
    if kind == "zeros":
        return jnp.zeros(shape, dtype)
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    z = jax.random.normal(k, shape, jnp.float32)
    if kind == "norm":
        return (1.0 + 0.1 * z).astype(dtype)
    if kind == "embed":
        return (z * (1.0 / width) ** 0.5).astype(dtype)
    fan_in = shape[-2]
    return (z * (scale / fan_in ** 0.5)).astype(dtype)


def make(conf: Dict[str, Any], key, dtype: str, shardings=None):
    """Draw the whole tree in one jitted call on the device."""
    import jax
    import jax.numpy as jnp

    desc = describe(conf)
    width = conf.get("hidden_size", conf.get("embedding_dim"))
    dt = jnp.dtype(dtype)

    def build(k):
        def walk(node, path):
            if is_leaf(node):
                return _draw(k, path, node, width, dt)
            return {name: walk(sub, f"{path}/{name}") for name, sub in node.items()}

        return walk(desc, "")

    return jax.jit(build, out_shardings=shardings)(key)


def leaves(desc):
    """Every (shape, kind, scale) leaf of a described tree."""
    if is_leaf(desc):
        yield desc
        return
    for sub in desc.values():
        yield from leaves(sub)


def shapes_match(tree_a, tree_b) -> bool:
    """Same structure, shapes and dtypes (arrays or ShapeDtypeStructs)."""
    import jax

    sa, sb = jax.tree_util.tree_structure(tree_a), jax.tree_util.tree_structure(tree_b)
    if sa != sb:
        return False
    return all(a.shape == b.shape and a.dtype == b.dtype
               for a, b in zip(jax.tree_util.tree_leaves(tree_a), jax.tree_util.tree_leaves(tree_b)))
