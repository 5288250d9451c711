"""Elastic resharding: move live training state between device meshes.

This is the system-level analogue of the paper's claim that spot
revocations need no fault-tolerance machinery: when the provisioner loses
(or gains) instances, the job's params/opt-state are re-laid-out onto a
mesh over the surviving device pool via :func:`reshard_params` and training
continues — nothing is checkpointed, the state never leaves device/host
memory.

``jax.device_put(x, sharding)`` performs the actual cross-mesh transfer;
it resolves source and destination shardings and issues the minimal
copies device to device. A transfer it cannot make raises: a reshard
never stages through host memory, which would change what a live
reshard costs.
"""
from __future__ import annotations

from typing import Any

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.config.base import ShardingLayout
from repro.dist.sharding import param_shardings


def reshard_tree(tree: Any, shardings: Any) -> Any:
    """device_put every leaf of ``tree`` onto the matching sharding leaf."""
    return jax.tree_util.tree_map(jax.device_put, tree, shardings)


def replicate(tree: Any, mesh) -> Any:
    """Fully replicate a pytree across every device of ``mesh``."""
    repl = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, repl), tree)


def reshard_params(params: Any, specs: Any, mesh, layout: ShardingLayout) -> Any:
    """Re-resolve the param shardings on a NEW mesh and move the live params.

    The elastic shrink/grow path: ``specs`` (the model's ParamSpec tree)
    re-resolves against the new mesh's axis sizes — the divisibility
    fallbacks may pick different specs than on the old mesh (e.g. a dim
    that sharded 4-way no longer divides and replicates) — and the params
    are transferred leaf-by-leaf.
    """
    return reshard_tree(params, param_shardings(specs, mesh, layout))
