"""Mesh builders for the launchers, benches and tests.

Every axis is ``AxisType.Auto``: GSPMD propagates shardings and
``with_sharding_constraint`` may name any axis. ``jax.make_mesh`` would
otherwise build Explicit axes, on which the activation constrainer and
the embedding gather refuse to trace. (``dist.meshplan`` builds its plans
with ``jax.sharding.Mesh``, whose axes are Auto already.)

Functions, not module-level constants — importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    """Auto-axes mesh over the first ``prod(shape)`` local devices."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """TPU v5e production mesh: 16×16 = 256 chips/pod; 2 pods = 512 chips.

    The ``pod`` axis is pure data parallelism (one gradient all-reduce per
    step crosses the DCN); ``data`` is within-pod DP/FSDP; ``model`` is
    tensor/expert parallelism over ICI.
    """
    if multi_pod:
        return make_mesh((2, 16, 16), ("pod", "data", "model"))
    return make_mesh((16, 16), ("data", "model"))


def make_host_mesh(model_parallel: int = 1) -> Mesh:
    """Mesh over whatever devices exist locally (smoke tests: 1 CPU)."""
    n = len(jax.devices())
    assert n % model_parallel == 0
    return make_mesh((n // model_parallel, model_parallel), ("data", "model"))


# TPU v5e hardware constants (per chip) — used by the roofline analysis.
V5E_PEAK_BF16_FLOPS = 197e12     # 197 TFLOP/s bf16
V5E_HBM_BANDWIDTH = 819e9        # 819 GB/s
V5E_ICI_LINK_BW = 50e9           # ~50 GB/s per ICI link
V5E_HBM_BYTES = 16 * 1024**3     # 16 GiB HBM per chip
