"""Seeded random weights, made by the benchmark on the device.

Both the program and the plain reference are given these weights: they are
an input, like the traffic, and the reference takes nothing that the
program made. The tree is laid out as the program stores its parameters
(``describe``, from the configuration's family file); ``make`` draws every
leaf in one jitted call, in the dtype the configuration serves or trains
in, under the shardings it is given.

Each leaf draws from its own stream, folded from the seed and its path, so
a leaf's values do not depend on the other leaves.
"""
from __future__ import annotations

import pathlib
import zlib
from typing import Any, Dict, Tuple

import bench

Leaf = Tuple   # (shape, kind, scale) or (shape, kind, scale, share)


def describe(conf: Dict[str, Any], base: pathlib.Path = bench.HERE) -> Dict[str, Any]:
    """The parameter tree of a configuration, as its family
    (``reference/<family>.py`` under ``base``) describes it. A leaf is
    (shape, kind, scale), with kind ``matrix``, ``embed``, ``norm`` or
    ``zeros``; a matrix may add the share of it that one token uses
    (``share``)."""
    return bench.family(conf, base).describe(conf)


def share(leaf: Leaf):
    """The part of a leaf that one token uses: 1 for every matrix, except
    where the family says less. For a routed-expert matrix that holds this
    chip's experts, it is experts per token over the published experts: a
    token does that share of the held experts' work, on average, on each
    chip of a deployment whose chips hold equal shares of the experts."""
    return leaf[3] if len(leaf) > 3 else 1


def is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) in (3, 4) and isinstance(x[0], tuple)


def _draw(key, path: str, leaf: Leaf, dtype):
    import jax
    import jax.numpy as jnp

    shape, kind, scale = leaf[:3]
    if kind == "zeros":
        return jnp.zeros(shape, dtype)
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    z = jax.random.normal(k, shape, jnp.float32)
    if kind == "norm":
        return (1.0 + 0.1 * z).astype(dtype)
    if kind == "embed":         # (vocab, width)
        return (z * (1.0 / shape[-1]) ** 0.5).astype(dtype)
    fan_in = shape[-2]
    return (z * (scale / fan_in ** 0.5)).astype(dtype)


def make(conf: Dict[str, Any], key, dtype: str, shardings=None,
         base: pathlib.Path = bench.HERE):
    """Draw the whole tree in one jitted call on the device."""
    import jax
    import jax.numpy as jnp

    desc = describe(conf, base)
    dt = jnp.dtype(dtype)

    def build(k):
        def walk(node, path):
            if is_leaf(node):
                return _draw(k, path, node, dt)
            return {name: walk(sub, f"{path}/{name}") for name, sub in node.items()}

        return walk(desc, "")

    return jax.jit(build, out_shardings=shardings)(key)


def leaves(desc):
    """Every leaf of a described tree."""
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
