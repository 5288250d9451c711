"""The serving path's Pallas kernels and the train step compile for a TPU v5e.

Every chip compile of the test suite lives in this one file. The chip is
described (``v5e:2x2``), not attached: the TPU compiler refuses here what
it would refuse on the chip — block shapes off the tiling, too much VMEM
— which interpret-mode runs never see. Nothing runs, so these tests say
nothing about results or times. Kernel shapes are qwen3-4b's: 32 query
heads, 8 KV heads, head_dim 128 (the paged kernel also at the other head
shapes it serves); the paged pool is 8 lanes at 2048 context in pages of
16, the flash prefill 2048 tokens. The engine's whole paged decode step
compiles at the serving benchmark's shapes. The train step is the
orchestrator's, on each mesh a 4-chip pool is planned into.

The topology is described inside a fixture, never at import, and the
persistent compilation cache is off around these compiles (an entry
compiled for a described chip cannot be read back without one).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import paged_decode_attention

H, KVH, HD = 32, 8, 128
LANES, CONTEXT, PAGE = 8, 2048, 16
PREFILL = 2048


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


@pytest.mark.parametrize("heads,kv_heads,dtype", [
    (H, KVH, jnp.bfloat16),
    (H, KVH, jnp.float32),
    (20, 20, jnp.bfloat16),     # qwen1.5-4b: no GQA
    (48, 8, jnp.bfloat16),      # internvl2-26b: GQA 6:1
])
def test_paged_kernel_compiles_at_qwen3_4b_widths(one_chip, heads, kv_heads, dtype):
    """Every head shape of the zoo's DENSE configurations whose pool the
    kernel serves (head_dim 128)."""
    blocks = CONTEXT // PAGE
    pool = _spec(one_chip, (LANES * blocks + 1, PAGE, kv_heads, HD), dtype)
    compiled = jax.jit(paged_decode_attention).lower(
        _spec(one_chip, (LANES, heads, HD), dtype), pool, pool,
        _spec(one_chip, (LANES, blocks), jnp.int32),
        _spec(one_chip, (LANES,), jnp.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_engine_decode_step_runs_the_kernel_at_bench_shapes(topo):
    """The engine's whole paged decode step, jitted as ``DecodeEngine`` jits
    it, at the serving benchmark's shapes: qwen3-4b tied in bf16, 16 lanes,
    1,025 pages of 16, a 2,048-position block table, on one chip. The
    layers' attention is the Pallas kernel; the gather of the whole block
    table (``bf16[2048,16,8,128]``, 16 lanes x 128 pages) is gone."""
    import dataclasses

    from repro.config import ShardingLayout, get_arch
    from repro.dist import (
        ElasticMeshManager, cache_shardings, make_activation_constrainer, param_shardings,
    )
    from repro.models import build_model
    from repro.models.common import ParamSpec
    from repro.train.steps import build_paged_decode_step

    lanes, pages = 16, 1025
    cfg = dataclasses.replace(
        get_arch("qwen3-4b"), tie_embeddings=True, param_dtype="bfloat16", dtype="bfloat16",
    )
    model, layout = build_model(cfg), ShardingLayout()
    mesh = ElasticMeshManager(devices=topo.devices).plan_for(1).mesh
    param_sh = param_shardings(model.specs, mesh, layout)
    cache_specs = model.paged_cache_specs(pages, PAGE)
    cache_sh = cache_shardings(cache_specs, mesh, layout)
    repl = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    step = jax.jit(
        build_paged_decode_step(
            model, layout, make_activation_constrainer(mesh, layout, cfg), mesh=mesh,
        ),
        in_shardings=(param_sh, cache_sh, repl, repl, repl),
        out_shardings=(None, cache_sh),
        donate_argnums=(1,),
    )
    shaped = lambda tree, sh: jax.tree_util.tree_map(
        lambda s, d: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=d), tree, sh,
    )
    cache = jax.tree_util.tree_map(
        lambda s, d: jax.ShapeDtypeStruct(s.shape, jnp.dtype(s.dtype), sharding=d),
        cache_specs, cache_sh, is_leaf=lambda x: isinstance(x, ParamSpec),
    )
    lane_spec = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=repl)
    text = step.lower(
        shaped(model.abstract_params(), param_sh), cache, lane_spec((lanes, 1)),
        lane_spec((lanes,)), lane_spec((lanes, CONTEXT // PAGE)),
    ).compile().as_text()
    assert "tpu_custom_call" in text
    assert "bf16[2048,16,8,128]" not in text


def _flash_args(one_chip):
    q = _spec(one_chip, (1, PREFILL, H, HD), jnp.bfloat16)
    kv = _spec(one_chip, (1, PREFILL, KVH, HD), jnp.bfloat16)
    return q, kv, kv


def test_flash_forward_compiles_at_prefill_2048(one_chip):
    fwd = lambda q, k, v: flash_attention(q, k, v, True, 0, 0, 128, 128)
    compiled = jax.jit(fwd).lower(*_flash_args(one_chip)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1


def test_flash_backward_compiles_at_prefill_2048(one_chip):
    def loss(q, k, v):
        o = flash_attention(q, k, v, True, 0, 0, 128, 128)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    compiled = grad.lower(*_flash_args(one_chip)).compile()
    # the forward plus the two backward kernels (dk/dv and dq)
    assert compiled.as_text().count("tpu_custom_call") == 3


@pytest.mark.parametrize("chips", [1, 2, 4])
def test_orchestrator_train_step_compiles_on_every_plan(topo, chips):
    """The xLSTM train step the orchestrator jits, at the default layout
    (full remat, FSDP over the data axis), on each mesh ``plan_for`` gives
    a 4-chip pool. Reduced widths hit the same compiler fault on (2, 1)
    as the published ones when the step leaves the TPU fusion mode on."""
    from repro.config import ShardingLayout, TrainConfig, get_arch
    from repro.dist import ElasticMeshManager
    from repro.models import build_model
    from repro.train.loop import make_jitted_step
    from repro.train.steps import abstract_train_state

    plan = ElasticMeshManager(devices=topo.devices).plan_for(chips)
    assert plan.device_count == chips
    model = build_model(get_arch("xlstm-350m").reduced())
    jitted, state_sh = make_jitted_step(model, TrainConfig(), ShardingLayout(), plan.mesh)
    state = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        abstract_train_state(model), state_sh,
    )
    repl = jax.sharding.NamedSharding(plan.mesh, jax.sharding.PartitionSpec())
    batch = {k: jax.ShapeDtypeStruct((4, 512), jnp.int32, sharding=repl)
             for k in ("tokens", "labels")}
    jitted.lower(state, batch).compile()
