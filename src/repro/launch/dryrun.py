import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)
"""Multi-pod dry-run: lower + compile every (arch × shape) cell on the
production mesh with 512 placeholder host devices, and extract the roofline
inputs (FLOPs, HBM bytes, per-device memory, collective traffic) from the
compiled artifact. No arrays are ever allocated — inputs are
ShapeDtypeStructs.

Usage:
    python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod] [--layout baseline]
    python -m repro.launch.dryrun --cell qwen3-4b:train_4k --layout seqpar

Results land in results/dryrun/<arch>__<shape>__<mesh>__<layout>.json and
feed EXPERIMENTS.md §Dry-run / §Roofline.
"""
import argparse
import json
import pathlib
import time
import traceback
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.config import (
    ShardingLayout,
    TrainConfig,
    get_arch,
    get_shape,
    runnable_cells,
)
from repro.dist import (
    batch_shardings,
    cache_shardings,
    make_activation_constrainer,
    opt_state_shardings,
    param_shardings,
)
from repro.launch import hlo_cost
from repro.launch.mesh import make_production_mesh
from repro.models import build_model, input_specs
from repro.obs import get_logger

log = get_logger("launch.dryrun")
from repro.models.common import abstract_params
from repro.train.steps import (
    abstract_train_state,
    build_decode_step,
    build_prefill_step,
    build_train_step,
)

RESULTS_DIR = pathlib.Path(__file__).resolve().parents[3] / "results" / "dryrun"


LAYOUTS: Dict[str, ShardingLayout] = {
    "baseline": ShardingLayout(),
    "triangular": ShardingLayout(name="triangular", attn_impl="triangular"),
    "seqpar": ShardingLayout(
        name="seqpar", sequence_shard_activations=True, attn_impl="triangular"
    ),
    "tp_only": ShardingLayout(name="tp_only", param_rules="tp_only"),
    "bf16_grads": ShardingLayout(
        name="bf16_grads", gradient_allreduce_dtype="bfloat16", attn_impl="triangular"
    ),
    "remat_dots": ShardingLayout(name="remat_dots", remat="dots", attn_impl="triangular"),
    "fsdp_heavy": ShardingLayout(name="fsdp_heavy", param_rules="fsdp_heavy"),
    "int8_cache": ShardingLayout(name="int8_cache", int8_kv_cache=True),
    "decode_unroll": ShardingLayout(name="decode_unroll", decode_unroll=True),
    "naive": ShardingLayout(
        name="naive", sequence_shard_activations=False, fused_ce=False
    ),
    # --- §Perf hillclimb variants ---
    "attn_gather": ShardingLayout(name="attn_gather", attn_gather_kv=True),
    "tri_gather": ShardingLayout(
        name="tri_gather", attn_impl="triangular", attn_gather_kv=True
    ),
    "tri_gather_bf16g": ShardingLayout(
        name="tri_gather_bf16g", attn_impl="triangular", attn_gather_kv=True,
        gradient_allreduce_dtype="bfloat16",
    ),
    "bigchunk": ShardingLayout(
        name="bigchunk", attn_impl="triangular", q_chunk=2048, kv_chunk=4096
    ),
    "tri_gather_bigchunk": ShardingLayout(
        name="tri_gather_bigchunk", attn_impl="triangular", attn_gather_kv=True,
        q_chunk=2048, kv_chunk=4096,
    ),
    "tri_bigchunk": ShardingLayout(
        name="tri_bigchunk", attn_impl="triangular", q_chunk=2048, kv_chunk=4096
    ),
    "tri_bigchunk_dots": ShardingLayout(
        name="tri_bigchunk_dots", attn_impl="triangular",
        q_chunk=2048, kv_chunk=4096, remat="dots",
    ),
    "moe_tp": ShardingLayout(name="moe_tp", param_rules="moe_tp"),
    "tri_zero1": ShardingLayout(
        name="tri_zero1", attn_impl="triangular",
        param_rules="tp_only", opt_rules="baseline",
    ),
    "tri_zero1_bigchunk": ShardingLayout(
        name="tri_zero1_bigchunk", attn_impl="triangular",
        param_rules="tp_only", opt_rules="baseline",
        q_chunk=2048, kv_chunk=4096,
    ),
}


def _tree_shardings_like(tree: Any, leaf_sharding) -> Any:
    return jax.tree_util.tree_map(lambda _: leaf_sharding, tree)


# Per-arch gradient-accumulation defaults for train_4k: big models need
# microbatching to fit the 16 GiB/chip activation budget at global batch 256
# over 16 data shards (production config, not a hack — every framework does
# this). 1 = no accumulation.
TRAIN_MICROBATCHES: Dict[str, int] = {
    "qwen1.5-32b": 2,
    "mixtral-8x7b": 2,
    "phi3.5-moe-42b-a6.6b": 4,
    "internvl2-26b": 4,
    "gemma-7b": 2,
}

# Per-cell production-config overrides applied when --layout baseline:
# qwen1.5-32b is MHA (40 KV heads) — its bf16 32k cache is 21.5 GiB/chip and
# cannot fit 16 GiB at the assigned batch; int8 KV cache is the config a
# real deployment would run.
CELL_LAYOUT_OVERRIDES: Dict[tuple, str] = {
    ("qwen1.5-32b", "decode_32k"): "int8_cache",
}


def lower_cell(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    layout: ShardingLayout = ShardingLayout(),
    microbatches: int = 1,
):
    """Lower + compile one cell. Returns (compiled, lowered, meta)."""
    cfg = get_arch(arch)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    model = build_model(cfg)
    constrain = make_activation_constrainer(mesh, layout, cfg)
    p_sh = param_shardings(model.specs, mesh, layout)
    inputs = input_specs(cfg, shape)
    in_sh = batch_shardings(inputs, mesh)
    repl = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())

    with mesh:
        if shape.mode == "train":
            tc = TrainConfig(microbatches=microbatches)
            step = build_train_step(model, tc, layout, constrain)
            state = abstract_train_state(model)
            o_sh = opt_state_shardings(model.specs, mesh, layout)
            state_sh = type(state)(
                params=p_sh,
                opt=type(state.opt)(
                    m=o_sh, v=o_sh, count=repl
                ),
                step=repl,
            )
            jitted = jax.jit(
                step,
                in_shardings=(state_sh, in_sh),
                out_shardings=(state_sh, None),
                donate_argnums=(0,),
            )
            lowered = jitted.lower(state, inputs)
        elif shape.mode == "prefill":
            step = build_prefill_step(model, layout, shape.seq_len, constrain)
            params = abstract_params(model.specs)
            jitted = jax.jit(step, in_shardings=(p_sh, in_sh))
            lowered = jitted.lower(params, inputs)
        else:  # decode
            step = build_decode_step(model, layout, constrain)
            params = abstract_params(model.specs)
            c_specs = model.cache_specs(
                shape.global_batch, shape.seq_len, int8=layout.int8_kv_cache
            )
            cache = abstract_params(c_specs)
            c_sh = cache_shardings(c_specs, mesh, layout)
            tok_sh = batch_shardings(inputs, mesh)["tokens"]
            jitted = jax.jit(
                step,
                in_shardings=(p_sh, c_sh, tok_sh, repl),
                donate_argnums=(1,),
            )
            lowered = jitted.lower(
                params, cache, inputs["tokens"], jax.ShapeDtypeStruct((), jnp.int32)
            )
        compiled = lowered.compile()
    meta = {
        "arch": arch,
        "shape": shape_name,
        "mode": shape.mode,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "layout": layout.name,
        "params": model.param_count(),
    }
    if shape.mode == "decode":
        # per-device bytes of the donated cache: on TPU the output cache
        # aliases the input (donation); the CPU backend ignores donation and
        # double-counts it — analyze() reports a TPU-adjusted peak.
        import numpy as _np

        from repro.models.common import ParamSpec as _PS

        total = 0
        flat_specs = jax.tree_util.tree_leaves(
            c_specs, is_leaf=lambda x: isinstance(x, _PS)
        )
        flat_sh = jax.tree_util.tree_leaves(c_sh)
        for s, sh in zip(flat_specs, flat_sh):
            local = sh.shard_shape(s.shape)
            total += int(_np.prod(local)) * jnp.dtype(s.dtype).itemsize
        meta["cache_bytes_per_device"] = total
    return compiled, lowered, meta


def analyze(compiled, meta: Dict[str, Any]) -> Dict[str, Any]:
    out = dict(meta)
    # raw XLA numbers (loop bodies counted ONCE — kept for reference only)
    ca = compiled.cost_analysis() or {}
    out["xla_flops_loop_once"] = float(ca.get("flops", 0.0))
    out["xla_bytes_loop_once"] = float(
        ca.get("bytes accessed", ca.get("bytes accessed0{}", 0.0))
    )
    mem = None
    try:
        mem = compiled.memory_analysis()
    except Exception:
        pass
    if mem is not None:
        for k in (
            "argument_size_in_bytes",
            "output_size_in_bytes",
            "temp_size_in_bytes",
            "generated_code_size_in_bytes",
        ):
            out[k] = int(getattr(mem, k, 0))
        out["peak_bytes_per_device"] = int(
            getattr(mem, "argument_size_in_bytes", 0)
            + getattr(mem, "temp_size_in_bytes", 0)
        )
    hlo = compiled.as_text()
    # trip-count-aware walker (per-device FLOPs / HBM bytes / collectives)
    walk = hlo_cost.analyze_hlo(hlo)
    out["flops"] = walk["flops"]
    out["hbm_bytes"] = walk["hbm_bytes"]
    out["collectives"] = {
        k.replace("coll_", ""): v for k, v in walk.items() if k.startswith("coll_")
    }
    out["collectives"]["count"] = int(walk["collective_count"])
    out["collective_wire_bytes"] = walk["collective_wire_bytes"]
    out["hlo_instructions"] = hlo.count("\n")

    # XLA-CPU measurement artifact: CPU float-normalization rewrites the
    # decode while-loop so the carried KV-cache stack is kept in f32 (TPU
    # has native bf16/int8 dots — no such copy exists there). Detect the
    # hoisted f32 stack(s) in the HLO and report a TPU-adjusted peak.
    if meta.get("mode") == "decode" and "peak_bytes_per_device" in out:
        import re as _re

        # (a) hoisted f32 copies of the bf16 cache stack (CPU float
        # normalization rewrites the while carry; TPU has native bf16 dots)
        artifact = 0
        seen = set()
        for m in _re.finditer(
            r"%([\w\.\-]+)\s*=\s*f32\[(\d+(?:,\d+){3,5})\]\S*\s+(?:convert|dynamic-update-slice)\(",
            hlo,
        ):
            name, dim_s = m.groups()
            dims = tuple(int(d) for d in dim_s.split(","))
            n = 1
            for d in dims:
                n *= d
            if n * 4 >= (1 << 30) and name not in seen:  # cache-stack sized
                seen.add(name)
                artifact += n * 4
        # one live f32 stack per (k, v), not every textual occurrence:
        artifact = min(artifact, 2 * 4 * max(
            (int(_np_prod(d)) for d in (tuple(int(x) for x in m2.split(","))
             for m2 in _re.findall(r"f32\[(\d+(?:,\d+){3,5})\]", hlo))), default=0,
        )) if artifact else 0
        # (b) donation is a no-op on CPU: the donated cache is double-counted
        donated = meta.get("cache_bytes_per_device", 0)
        out["cpu_f32_cache_artifact_bytes"] = int(artifact)
        out["cpu_no_donation_artifact_bytes"] = int(donated)
        out["peak_tpu_adjusted"] = int(
            out["peak_bytes_per_device"] - artifact - donated
        )
    return out


def _np_prod(t):
    n = 1
    for x in t:
        n *= x
    return n


def run_cell(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    layout_name: str = "baseline",
    save: bool = True,
) -> Dict[str, Any]:
    if layout_name == "baseline":
        layout_name = CELL_LAYOUT_OVERRIDES.get((arch, shape_name), layout_name)
    layout = LAYOUTS[layout_name]
    t0 = time.time()
    mb = TRAIN_MICROBATCHES.get(arch, 1) if get_shape(shape_name).mode == "train" else 1
    compiled, lowered, meta = lower_cell(
        arch, shape_name, multi_pod=multi_pod, layout=layout, microbatches=mb
    )
    meta["microbatches"] = mb
    result = analyze(compiled, meta)
    result["compile_seconds"] = round(time.time() - t0, 1)
    if save:
        RESULTS_DIR.mkdir(parents=True, exist_ok=True)
        fname = f"{arch.replace('/', '_')}__{shape_name}__{result['mesh']}__{layout_name}.json"
        (RESULTS_DIR / fname).write_text(json.dumps(result, indent=2))
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--cell", help="arch:shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--layout", default="baseline", choices=sorted(LAYOUTS))
    args = ap.parse_args()

    if args.cell:
        args.arch, args.shape = args.cell.split(":")

    cells = runnable_cells() if args.all else [(args.arch, args.shape)]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    failures = []
    for arch, shape in cells:
        for mp in meshes:
            tag = f"{arch}:{shape} mesh={'2x16x16' if mp else '16x16'} layout={args.layout}"
            try:
                r = run_cell(arch, shape, multi_pod=mp, layout_name=args.layout)
                log.info(f"OK {tag}",
                         flops=f"{r['flops']:.3e}",
                         hbm=f"{r['hbm_bytes']:.3e}",
                         coll=f"{r['collective_wire_bytes']:.3e}",
                         peak_gib=r.get("peak_bytes_per_device", 0) / 2**30,
                         compile_s=r["compile_seconds"])
            except Exception as e:  # noqa: BLE001 — report and continue the sweep
                failures.append((tag, repr(e)))
                log.warn(f"FAIL {tag}", error=repr(e))
                traceback.print_exc()
    if failures:
        log.warn("dry-run sweep had failures", count=len(failures))
        for t, e in failures:
            log.warn(f"failed cell {t}", error=e)
        raise SystemExit(1)
    log.info("all cells compiled", count=len(cells) * len(meshes))


if __name__ == "__main__":
    main()
