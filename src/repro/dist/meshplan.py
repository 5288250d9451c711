"""Mesh planning for market-menu elastic provisioning.

The provisioner's instance menu (``repro.core.market.InstanceShape``)
describes each market as ``device_count`` accelerators behind an
interconnect; this module turns that description into something the
training stack can run on and *price*:

* :func:`mesh_shape_for` — deterministic (data, model) factorization of a
  device count (model axis = largest power of two ≤ √n that divides n, so
  1→(1,1), 2→(2,1), 4→(2,2), 8→(4,2)),
* :class:`MeshPlan` / :class:`ElasticMeshManager` — build and cache one
  concrete ``jax.sharding.Mesh`` per honored device count from the local
  device pool (menu shapes larger than the pool are capped — the local
  pool *simulates* the market's instance), and resolve the old-vs-new
  sharding trees for a migration,
* :func:`reshard_bytes` — the byte-level cost model of a live cross-mesh
  reshard: for every leaf, every destination device pays only for the
  slice elements it does not already hold under the source sharding
  (exact slice-overlap arithmetic over ``devices_indices_map``). Identical
  shardings therefore cost 0 bytes; any migration costs at most
  :func:`tree_bytes` — the full state size a checkpoint restore would pull
  through remote storage. That inequality, in bytes, is the paper's
  "no-FT is cheaper" claim made quantitative.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np


def mesh_shape_for(n_devices: int) -> Tuple[int, int]:
    """Deterministic (data, model) factorization of ``n_devices``."""
    n = max(int(n_devices), 1)
    # model axis: largest power of two m with m*m <= n and n % m == 0
    m = 1
    while (m * 2) * (m * 2) <= n and n % (m * 2) == 0:
        m *= 2
    return (n // m, m)


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    """One menu shape — or one multi-leg allocation — made concrete on the
    local device pool. ``leg_spans`` maps each allocation leg to its
    contiguous range of (honored) device indices in
    ``mesh.devices.flatten()``; single-market plans have one span covering
    the whole mesh."""

    requested_devices: int          # the menu's device_count
    device_count: int               # honored (capped to the local pool)
    mesh_shape: Tuple[int, int]     # (data, model)
    axes: Tuple[str, str]
    mesh: Any                       # jax.sharding.Mesh
    leg_spans: Tuple[Tuple[int, int], ...] = ()

    def __post_init__(self):
        if not self.leg_spans:
            object.__setattr__(self, "leg_spans", ((0, self.device_count),))

    @property
    def key(self) -> Tuple[int, Tuple[int, int]]:
        """Identity of the *execution* substrate (honored count + shape).

        Deliberately leg-blind: a 4+4 split and a single 8-device market
        compile to the SAME mesh, so re-provisioning between them reuses
        the jitted step and moves zero bytes of layout — only the
        DCN-crossing leg bytes (``leg_state_bytes``) differ, and those are
        billed by the orchestrator, not the compiler."""
        return (self.device_count, self.mesh_shape)


class ElasticMeshManager:
    """Builds and caches one mesh per honored device count.

    The pool is the local accelerator set (tests/benches: host CPUs forced
    via ``XLA_FLAGS``); a menu shape asking for more devices than the pool
    holds is capped — two menu shapes that cap to the same count share one
    mesh, so re-provisioning between them is a zero-byte reshard.
    """

    def __init__(self, devices: Optional[Sequence[Any]] = None):
        self.devices: List[Any] = list(devices if devices is not None else jax.devices())
        self._plans: Dict[int, MeshPlan] = {}
        self._alloc_plans: Dict[Tuple[int, ...], MeshPlan] = {}

    @classmethod
    def from_mesh(cls, mesh) -> "ElasticMeshManager":
        return cls(devices=list(np.asarray(mesh.devices).flatten()))

    def plan_for(self, device_count: int) -> MeshPlan:
        n = max(1, min(int(device_count), len(self.devices)))
        plan = self._plans.get(n)
        if plan is None:
            shape = mesh_shape_for(n)
            devs = np.asarray(self.devices[:n], dtype=object).reshape(shape)
            mesh = jax.sharding.Mesh(devs, ("data", "model"))
            plan = MeshPlan(
                requested_devices=int(device_count),
                device_count=n,
                mesh_shape=shape,
                axes=("data", "model"),
                mesh=mesh,
            )
            self._plans[n] = plan
        return plan

    def plan_for_allocation(self, device_counts: Sequence[int]) -> MeshPlan:
        """One mesh spanning every leg of a multi-leg allocation.

        The union mesh is built over the summed device count (capped to the
        local pool — the pool *simulates* the federated instances) with
        contiguous per-leg device spans recorded in ``leg_spans``; honored
        leg sizes are the proportional split of the capped total, so an
        (8, 8) allocation on an 8-device pool simulates as (4, 4). A
        single-leg allocation delegates to :meth:`plan_for` — the identical
        cached plan object the pre-allocation orchestrator used. When the
        pool has fewer devices than the allocation has legs, trailing legs
        collapse to empty spans (a 1-device pool cannot represent a split;
        byte accounting then degenerates to zero for those legs)."""
        counts = [max(int(c), 1) for c in device_counts]
        if len(counts) == 1:
            return self.plan_for(counts[0])
        total = sum(counts)
        honored_total = max(1, min(total, len(self.devices)))
        # proportional, deterministic rounding: floor shares, then hand the
        # remainder to the widest legs first (ties: leg order)
        shares = [honored_total * c // total for c in counts]
        rest = honored_total - sum(shares)
        order = sorted(range(len(counts)), key=lambda i: (-counts[i], i))
        for i in order:
            if rest <= 0:
                break
            shares[i] += 1
            rest -= 1
        key = tuple(shares)
        plan = self._alloc_plans.get(key)
        if plan is None:
            shape = mesh_shape_for(honored_total)
            devs = np.asarray(
                self.devices[:honored_total], dtype=object
            ).reshape(shape)
            mesh = jax.sharding.Mesh(devs, ("data", "model"))
            spans, lo = [], 0
            for s in shares:
                spans.append((lo, lo + s))
                lo += s
            plan = MeshPlan(
                requested_devices=int(total),
                device_count=honored_total,
                mesh_shape=shape,
                axes=("data", "model"),
                mesh=mesh,
                leg_spans=tuple(spans),
            )
            self._alloc_plans[key] = plan
        return plan


# ---------------------------------------------------------------------------
# Measured throughput per mesh shape
# ---------------------------------------------------------------------------

class ThroughputTracker:
    """EMA of measured steps/sec per :attr:`MeshPlan.key`.

    The provisioner's menu predicts each shape's relative speed analytically
    (``repro.core.market.shape_throughput``); the orchestrator records what
    ``run_segment`` actually delivered per mesh shape here and uses
    :meth:`correction` to scale the analytic prediction by the measured
    deviation — so a shape that scales worse than the model's efficiency
    exponent stops looking cheap-per-step after one segment on it.
    """

    def __init__(self, ema: float = 0.5):
        self.ema = ema
        self._sps: Dict[Any, float] = {}

    def observe(self, key, steps: int, seconds: float) -> None:
        if steps <= 0 or seconds <= 0:
            return
        sps = steps / seconds
        prev = self._sps.get(key)
        self._sps[key] = sps if prev is None else self.ema * sps + (1 - self.ema) * prev

    def steps_per_sec(self, key) -> Optional[float]:
        return self._sps.get(key)

    @property
    def measured(self) -> Dict[Any, float]:
        return dict(self._sps)

    def correction(self, key, analytic: Dict[Any, float]) -> float:
        """Measured-vs-analytic speed ratio for ``key``, relative to the
        slowest-predicted observed shape (which anchors the scale).

        ``analytic`` maps plan keys to the model's predicted relative
        throughput. Returns 1.0 until two distinct shapes have been
        measured — a single observation fixes the anchor, not a ratio."""
        if key not in self._sps or len(self._sps) < 2:
            return 1.0
        ref = min(self._sps, key=lambda k: analytic.get(k, 1.0))
        if ref == key:
            return 1.0
        predicted = analytic.get(key, 1.0) / max(analytic.get(ref, 1.0), 1e-9)
        observed = self._sps[key] / max(self._sps[ref], 1e-9)
        return observed / max(predicted, 1e-9)


# ---------------------------------------------------------------------------
# Byte-level reshard cost
# ---------------------------------------------------------------------------

def _norm_index(idx: Tuple, shape: Tuple[int, ...]) -> Tuple[Tuple[int, int], ...]:
    """Normalize a devices_indices_map entry to ((start, stop), ...) pairs."""
    out = []
    for sl, dim in zip(idx, shape):
        start, stop, step = sl.indices(dim)
        assert step == 1, "strided shards unsupported"
        out.append((start, stop))
    return tuple(out)


def _volume(norm: Tuple[Tuple[int, int], ...]) -> int:
    v = 1
    for start, stop in norm:
        v *= max(stop - start, 0)
    return v


def _overlap(a, b) -> int:
    v = 1
    for (a0, a1), (b0, b1) in zip(a, b):
        v *= max(min(a1, b1) - max(a0, b0), 0)
    return v


def _leaf_moved_bytes(leaf, old_sharding, new_sharding) -> Dict[Any, int]:
    """Bytes a migration must move for one leaf, per destination device:
    every destination device pays for the part of its new slice it does
    not already hold locally."""
    shape = tuple(leaf.shape)
    itemsize = np.dtype(leaf.dtype).itemsize
    if old_sharding == new_sharding:
        return {}
    old_map = {
        d: _norm_index(idx, shape)
        for d, idx in old_sharding.devices_indices_map(shape).items()
    }
    new_map = new_sharding.devices_indices_map(shape)
    moved = {}
    for dev, idx in new_map.items():
        need = _norm_index(idx, shape)
        have = old_map.get(dev)
        vol = _volume(need)
        if have is not None:
            vol -= _overlap(need, have)
        moved[dev] = max(vol, 0) * itemsize
    return moved


def reshard_bytes_per_device(
    tree: Any, old_shardings: Any, new_shardings: Any
) -> Dict[Any, int]:
    """:func:`reshard_bytes`, broken down by destination device."""
    leaves, _ = jax.tree_util.tree_flatten(tree)
    old_leaves = jax.tree_util.tree_leaves(old_shardings)
    new_leaves = jax.tree_util.tree_leaves(new_shardings)
    assert len(leaves) == len(old_leaves) == len(new_leaves)
    per_device: Dict[Any, int] = {}
    for leaf, old, new in zip(leaves, old_leaves, new_leaves):
        for dev, b in _leaf_moved_bytes(leaf, old, new).items():
            per_device[dev] = per_device.get(dev, 0) + int(b)
    return per_device


def reshard_bytes(tree: Any, old_shardings: Any, new_shardings: Any) -> int:
    """Bytes actually moved by resharding ``tree`` from ``old_shardings``
    to ``new_shardings`` — leaf-by-leaf slice-overlap accounting.

    Leaves of ``tree`` only need ``.shape``/``.dtype`` (live arrays,
    ShapeDtypeStructs, or ParamSpecs via ``abstract_params`` all work), so
    the cost is computable *before* committing to a migration. Compare with
    :func:`tree_bytes` — what a checkpoint restore moves through storage.
    """
    return sum(reshard_bytes_per_device(tree, old_shardings, new_shardings).values())


def leg_state_bytes(tree: Any, shardings: Any, plan: MeshPlan, leg_index: int) -> int:
    """Bytes that must cross the DCN to rebuild ONE lost allocation leg.

    When a leg of a multi-leg allocation is revoked, the surviving legs
    still hold their shards; only the replacement leg starts empty. What
    crosses the DCN is the set of DISTINCT array slices the new leg's
    devices hold under ``shardings`` — each distinct slice is sent once
    and fanned out over the leg's own interconnect, so intra-leg replicas
    don't re-cross the wide-area link. Compare: a full checkpoint restore
    pulls :func:`tree_bytes` (every leaf in full) through remote storage,
    and a full cross-mesh reshard re-materializes every device. For any
    layout that shards state across the data axis (FSDP/ZeRO), a leg's
    distinct-slice volume is a strict fraction of the full state — the
    byte-level sense in which a one-leg revocation is cheaper than losing
    the whole allocation.
    """
    lo, hi = plan.leg_spans[leg_index]
    flat = np.asarray(plan.mesh.devices, dtype=object).flatten()
    leg_devices = {id(d): d for d in flat[lo:hi]}
    total = 0
    leaves = jax.tree_util.tree_leaves(tree)
    sh_leaves = jax.tree_util.tree_leaves(shardings)
    assert len(leaves) == len(sh_leaves)
    for leaf, sh in zip(leaves, sh_leaves):
        shape = tuple(leaf.shape)
        itemsize = np.dtype(leaf.dtype).itemsize
        seen = set()
        for dev, idx in sh.devices_indices_map(shape).items():
            if id(dev) not in leg_devices:
                continue
            norm = _norm_index(idx, shape)
            if norm not in seen:
                seen.add(norm)
                total += _volume(norm) * itemsize
    return int(total)


def live_shardings(tree: Any) -> Any:
    """The shardings a live pytree is currently laid out with."""
    return jax.tree_util.tree_map(lambda x: x.sharding, tree)


def tree_bytes(tree: Any) -> int:
    """Full byte size of a pytree — what a checkpoint restore transfers."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        total += int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
    return int(total)


def train_state_bytes(model) -> int:
    """Param + Adam moment footprint of a model's TrainState, in bytes.

    ``3 ×`` the fp32 param bytes: the fp32 master params plus the two Adam
    moments (m, v) mirror the param tree; scalars are negligible. Counted
    in fp32 whatever dtype ``model`` stores its weights in (a serving
    model stores bf16). This is the number the orchestrator matches
    against an instance shape's ``memory_gb × device_count`` — replacing
    the seed's hard-coded 16 GB.
    """
    return 3 * np.dtype(np.float32).itemsize * model.param_count()


def serve_state_bytes(
    model, batch: int, seq_len: int, *, int8_cache: bool = False
) -> int:
    """Footprint of one INFERENCE replica, in bytes: params once plus the
    KV/decode cache at the configured batch and context length.

    No optimizer state — a serving replica never holds Adam moments, which
    is why it is strictly smaller than :func:`train_state_bytes` for the
    same model and why a replica migration is params-only. This is the
    number the fleet provisioner (``repro.serve.fleet``) matches against
    an instance shape's total memory.
    """
    from repro.models.common import param_bytes

    cache = model.cache_specs(batch, seq_len, int8=int8_cache)
    return param_bytes(model.specs) + param_bytes(cache)
