"""Serving launcher: batched prefill + greedy decode on the host mesh,
with the same sharded step construction train/dryrun use.

Params, KV cache, and input batch all get NamedShardings resolved from the
layout's rule tables (``param_shardings`` / ``cache_shardings`` /
``batch_shardings``), the activation constrainer is threaded through the
steps, and the decode cache is donated — on a 1-device host mesh this
degenerates to the unsharded path, on a multi-device pool it serves
sharded with zero code change. Weights are stored in the compute dtype
(``serving_config``) and made on the device (``init_params``). The
reduced smoke config is the default; ``--no-reduced`` serves the
published widths.

    python -m repro.launch.serve --arch <id> [--batch 4] [--prompt-len 64]
        [--new-tokens 16] [--int8-cache] [--model-parallel 1] [--no-reduced]

``--plan`` mode (the serving-fleet subsystem, ``repro.serve``): serve on
an :class:`ElasticMeshManager` plan instead of the host mesh, so a
serving replica can migrate between instance shapes like training does.
``--plan 8,4 --revoke-after 3`` decodes 3 tokens on the 8-device plan,
then simulates a spot revocation: the params move to the 4-device plan as
a PARAMS-ONLY cross-mesh reshard (asserted strictly smaller than the
training path's restore — no optimizer state exists to move) and the KV
cache either rides along over the DCN (``--cache-policy migrate``) or is
dropped and re-prefilled from the tokens generated so far
(``--cache-policy drop``, the default). Decode then continues on the new
mesh. A ``PLAN_JSON`` line reports the byte accounting and the decoded
rows for the subprocess round-trip test. Without ``--plan`` the legacy
host-mesh path below runs unchanged (bit-exact with pre-plan serve.py).

    python -m repro.launch.serve --arch <id> --plan 8,4 --revoke-after 3
        [--cache-policy drop|migrate]
"""
import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig, ShardingLayout, get_arch, list_archs
from repro.dist import (
    batch_shardings,
    cache_shardings,
    make_activation_constrainer,
    param_shardings,
)
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import build_model
from repro.obs import get_logger
from repro.train.steps import build_decode_step, build_prefill_step

log = get_logger("launch.serve")


def serving_config(arch: str, reduced: bool = True) -> ModelConfig:
    """The served configuration: weights stored in the compute dtype.

    A server keeps no float32 master copy — every use casts the weights to
    the compute dtype anyway, so storing them there halves the HBM they
    take and changes no output. ``reduced=False`` serves the published
    widths."""
    cfg = get_arch(arch)
    if reduced:
        cfg = cfg.reduced()
    return dataclasses.replace(cfg, param_dtype=cfg.dtype)


def init_params(model, shardings, seed: int = 0):
    """Seeded random weights, made on the device directly under
    ``shardings`` (no host copy, no full-size float32 staging)."""
    return jax.jit(model.init, out_shardings=shardings)(jax.random.key(seed))


def _serve_batch(cfg, B, S):
    """The (seeded, deterministic) serving inputs both paths share."""
    batch = {"tokens": jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size, jnp.int32)}
    if cfg.encoder_layers:
        batch["frames"] = jax.random.normal(
            jax.random.key(2), (B, cfg.encoder_seq_len, cfg.d_model), jnp.bfloat16
        )
    if cfg.vision_tokens:
        batch["patches"] = jax.random.normal(
            jax.random.key(3), (B, cfg.vision_tokens, cfg.vision_width), jnp.bfloat16
        )
    return batch


def _serve_steps(model, cfg, layout, mesh, batch, total, int8):
    """Sharded prefill/decode jits for one mesh — identical construction to
    the legacy host-mesh path (same shardings, same donation, same
    constrainer), parameterized by the plan's mesh."""
    constrain = make_activation_constrainer(mesh, layout, cfg)
    p_sh = param_shardings(model.specs, mesh, layout)
    in_sh = batch_shardings(batch, mesh)
    c_specs = model.cache_specs(batch["tokens"].shape[0], total, int8=int8)
    c_sh = cache_shardings(c_specs, mesh, layout)
    repl = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    prefill = jax.jit(
        build_prefill_step(model, layout, total, constrain),
        in_shardings=(p_sh, in_sh),
        out_shardings=(None, c_sh),
    )
    decode = jax.jit(
        build_decode_step(model, layout, constrain),
        in_shardings=(p_sh, c_sh, in_sh["tokens"], repl),
        out_shardings=(None, c_sh),
        donate_argnums=(1,),
    )
    return p_sh, c_sh, in_sh, prefill, decode


def serve_engine_plans(model, layout, counts, prompts, new_tokens, *,
                       revoke_after: int = 0, man=None, seed: int = 0):
    """Serve ``prompts`` through the continuous-batching decode engine
    (paged KV pool) on ElasticMeshManager plans, one lane per prompt.

    With a second entry in ``counts``, a revocation after
    ``revoke_after`` steps sheds every in-flight request from the dying
    engine and resumes it — committed tokens included — on a fresh engine
    over the replacement plan, after a params-only cross-mesh reshard (the
    paged pool always follows drop-and-reprefill semantics: pages die with
    the instance). Returns ``(report, plans, params)``: the report holds
    the token rows and the byte accounting, ``plans`` the plans served on
    in order, ``params`` the weights as the last plan holds them.
    """
    from repro.dist import ElasticMeshManager, reshard_tree
    from repro.dist.meshplan import (
        ThroughputTracker,
        live_shardings,
        reshard_bytes_per_device,
    )
    from repro.models.layers import PAGE_SIZE
    from repro.serve.autoscale import drain_replica
    from repro.serve.engine import DecodeEngine, Request
    from repro.serve.migrate import assert_params_only

    man = man or ElasticMeshManager()
    tracker = ThroughputTracker()
    B = len(prompts)
    total = max(len(p) for p in prompts) + new_tokens
    num_pages = B * (-(-total // PAGE_SIZE)) + 1

    def engine_on(plan):
        return DecodeEngine(
            model, layout, plan.mesh, lanes=B, num_pages=num_pages,
            max_context=total, tracker=tracker, tracker_key=plan.key,
        )

    plans = [man.plan_for(counts[0])]
    engines = [engine_on(plans[0])]
    params = init_params(model, engines[0].param_sh, seed)
    for b, prompt in enumerate(prompts):
        engines[0].submit(Request(rid=b, prompt=prompt, max_new_tokens=new_tokens))
    log.info("engine plan up", devices=plans[0].device_count,
             mesh=str(plans[0].mesh_shape), lanes=B, pages=num_pages)

    report = {"params_bytes": 0, "params_bytes_per_device": {}, "cache_bytes": 0,
              "train_path_bytes": 0, "migrated_at": None, "cache_policy": "drop"}
    revoke_after = revoke_after if len(counts) > 1 else 0
    i = 0
    while engines[-1].in_flight:
        if revoke_after and i == revoke_after:
            # the revocation is the same move a scale-down makes: drain
            # the dying engine's streams onto the replacement replica
            plans.append(man.plan_for(counts[1]))
            engines.append(engine_on(plans[-1]))
            dst = engines[-1].param_sh
            moved = reshard_bytes_per_device(params, live_shardings(params), dst)
            params = reshard_tree(params, dst)
            report["params_bytes"] = sum(moved.values())
            report["params_bytes_per_device"] = {str(d.id): b for d, b in moved.items()}
            report["train_path_bytes"] = assert_params_only(report["params_bytes"], model)
            report["migrated_at"] = i
            n_drained = drain_replica(engines[-2], engines[-1])
            log.info("revoked: streams drained to replacement", step=i,
                     shed=n_drained, devices=plans[-1].device_count,
                     mesh=str(plans[-1].mesh_shape),
                     params_bytes=report["params_bytes"],
                     train_path_bytes=report["train_path_bytes"])
        engines[-1].step(params)
        i += 1

    done = {c.rid: c.tokens for e in engines for c in e.completions}
    report["tokens"] = [done[b] for b in range(B)]
    report["measured_steps_per_sec"] = {
        f"{k[1][0]}x{k[1][1]}": round(v, 3) for k, v in tracker.measured.items()
    }
    report["engine_tokens_per_sec"] = round(engines[-1].measured_tokens_per_sec, 3)
    return report, plans, params


def engine_plan_main(args) -> None:
    """``--plan ... --engine``: :func:`serve_engine_plans` on the seeded
    serving batch, reported as a ``PLAN_JSON`` line."""
    if args.cache_policy != "drop":
        raise SystemExit("--engine supports --cache-policy drop only "
                         "(pool pages die with the instance)")

    cfg = serving_config(args.arch, args.reduced)
    counts = [int(x) for x in args.plan.split(",")]
    prompts = np.asarray(_serve_batch(cfg, args.batch, args.prompt_len)["tokens"])
    report, _, _ = serve_engine_plans(
        build_model(cfg), ShardingLayout(int8_kv_cache=args.int8_cache),
        counts, list(prompts), args.new_tokens, revoke_after=args.revoke_after,
    )
    print("first row:", report["tokens"][0])
    print("PLAN_JSON " + json.dumps({"plans": counts, "engine": True, **report}))


def plan_main(args) -> None:
    """Serve on ElasticMeshManager plans with a live shape migration."""
    from repro.dist import ElasticMeshManager, reshard_tree
    from repro.dist.meshplan import (
        ThroughputTracker,
        live_shardings,
        reshard_bytes,
    )
    from repro.serve.migrate import (
        assert_params_only,
        replica_param_bytes_moved,
    )

    cfg = serving_config(args.arch, args.reduced)
    model = build_model(cfg)
    layout = ShardingLayout(int8_kv_cache=args.int8_cache)
    man = ElasticMeshManager()
    counts = [int(x) for x in args.plan.split(",")]
    tracker = ThroughputTracker()

    B, S = args.batch, args.prompt_len
    total = S + args.new_tokens
    batch = _serve_batch(cfg, B, S)

    plan = man.plan_for(counts[0])
    p_sh, c_sh, in_sh, prefill, decode = _serve_steps(
        model, cfg, layout, plan.mesh, batch, total, args.int8_cache
    )
    params = init_params(model, p_sh)
    batch = jax.device_put(batch, in_sh)

    migrated = {"params_bytes": 0, "cache_bytes": 0, "train_path_bytes": 0,
                "migrated_at": None, "cache_policy": args.cache_policy}
    revoke_after = args.revoke_after if len(counts) > 1 else 0
    toks = []
    with plan.mesh:
        logits, cache = prefill(params, batch)
        jax.block_until_ready(logits)
        tok = jax.device_put(
            jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None],
            in_sh["tokens"],
        )
        toks.append(np.asarray(tok))
    log.info("plan up", devices=plan.device_count, mesh=str(plan.mesh_shape))

    i = 0
    while i < args.new_tokens - 1:
        if revoke_after and i == revoke_after:
            # --- spot revocation: live shape migration -----------------
            gen = np.concatenate(toks, axis=1)
            plan = man.plan_for(counts[1])
            p_sh, c_sh, in_sh, prefill, decode = _serve_steps(
                model, cfg, layout, plan.mesh, batch, total, args.int8_cache
            )
            moved = replica_param_bytes_moved(params, p_sh)
            params = reshard_tree(params, p_sh)
            migrated["params_bytes"] = moved
            migrated["train_path_bytes"] = assert_params_only(moved, model)
            migrated["migrated_at"] = i
            if args.cache_policy == "migrate":
                migrated["cache_bytes"] = reshard_bytes(
                    cache, live_shardings(cache), c_sh
                )
                cache = reshard_tree(cache, c_sh)
                batch = jax.device_put(batch, in_sh)
            else:
                # drop: the cache died with the instance; re-prefill the
                # prompt + every token already fed to the old cache (the
                # newest token rides the next decode call), billed as
                # recompute on the replacement
                batch = jax.device_put(batch, in_sh)
                refill = dict(batch)
                refill["tokens"] = jax.device_put(
                    jnp.asarray(
                        np.concatenate(
                            [np.asarray(batch["tokens"]), gen[:, :i]], axis=1
                        )
                    ),
                    in_sh["tokens"],
                )
                with plan.mesh:
                    _, cache = prefill(params, refill)
            tok = jax.device_put(tok, in_sh["tokens"])
            log.info("revoked: migrated to replacement plan", token=i,
                     devices=plan.device_count, mesh=str(plan.mesh_shape),
                     params_bytes=migrated["params_bytes"],
                     train_path_bytes=migrated["train_path_bytes"],
                     cache_policy=args.cache_policy)
        with plan.mesh:
            t0 = time.perf_counter()
            logits, cache = decode(params, cache, tok, jnp.int32(S + i))
            tok = jax.device_put(
                jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None],
                in_sh["tokens"],
            )
            jax.block_until_ready(tok)
            tracker.observe(plan.key, 1, time.perf_counter() - t0)
        toks.append(np.asarray(tok))
        i += 1

    rows = np.concatenate(toks, axis=1)
    sps = {f"{k[1][0]}x{k[1][1]}": round(v, 3) for k, v in tracker.measured.items()}
    print("first row:", rows[0].tolist())
    print("PLAN_JSON " + json.dumps({
        "plans": counts,
        "tokens": rows.tolist(),
        "measured_steps_per_sec": sps,
        **migrated,
    }))


def host_main(args) -> None:
    """The legacy host-mesh path: lock-step batched prefill + decode."""
    cfg = serving_config(args.arch, args.reduced)
    model = build_model(cfg)
    layout = ShardingLayout(int8_kv_cache=args.int8_cache)
    mesh = make_host_mesh(model_parallel=args.model_parallel)
    constrain = make_activation_constrainer(mesh, layout, cfg)

    p_sh = param_shardings(model.specs, mesh, layout)
    params = init_params(model, p_sh)

    B, S = args.batch, args.prompt_len
    batch = _serve_batch(cfg, B, S)

    total = S + args.new_tokens
    in_sh = batch_shardings(batch, mesh)
    c_specs = model.cache_specs(B, total, int8=args.int8_cache)
    c_sh = cache_shardings(c_specs, mesh, layout)
    repl = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())

    prefill = jax.jit(
        build_prefill_step(model, layout, total, constrain),
        in_shardings=(p_sh, in_sh),
        # commit the produced cache to the same shardings decode declares,
        # or the decode jit rejects the GSPMD-chosen layout on >1 device
        out_shardings=(None, c_sh),
    )
    decode = jax.jit(
        build_decode_step(model, layout, constrain),
        in_shardings=(p_sh, c_sh, in_sh["tokens"], repl),
        # the returned cache feeds the next decode call: pin it to the same
        # shardings or GSPMD drifts the layout and the next call rejects it
        out_shardings=(None, c_sh),
        donate_argnums=(1,),
    )

    with mesh:
        t0 = time.perf_counter()
        logits, cache = prefill(params, batch)
        jax.block_until_ready(logits)
        log.info("prefill done", tokens=S, batch=B,
                 ms=round((time.perf_counter() - t0) * 1e3),
                 mesh=str(dict(mesh.shape)))

        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        t0 = time.perf_counter()
        toks = [tok]
        for i in range(args.new_tokens - 1):
            logits, cache = decode(params, cache, tok, jnp.int32(S + i))
            tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
            toks.append(tok)
        jax.block_until_ready(tok)
    dt = (time.perf_counter() - t0) / max(args.new_tokens - 1, 1)
    log.info("decode done", ms_per_token=dt * 1e3, int8_cache=args.int8_cache)
    print("first row:", jnp.concatenate(toks, axis=1)[0].tolist())


def _dispatch(args) -> None:
    if args.plan and args.engine:
        return engine_plan_main(args)
    if args.plan:
        return plan_main(args)
    if args.engine:
        raise SystemExit("--engine requires --plan")
    return host_main(args)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="serve the reduced smoke config (default); "
                         "--no-reduced serves the published widths")
    ap.add_argument("--int8-cache", action="store_true")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--plan", default="",
                    help="serve on ElasticMeshManager plans: comma-separated "
                         "device counts; the second entry is the migration "
                         "target (e.g. 8,4)")
    ap.add_argument("--revoke-after", type=int, default=0,
                    help="decode this many tokens, then revoke + migrate to "
                         "the second --plan entry")
    ap.add_argument("--cache-policy", choices=("drop", "migrate"),
                    default="drop",
                    help="on migration: drop the KV cache and re-prefill, "
                         "or reshard it over the DCN")
    ap.add_argument("--engine", action="store_true",
                    help="with --plan: serve through the continuous-batching "
                         "decode engine (paged KV pool) instead of the "
                         "lock-step dense-cache loop")
    ap.add_argument("--trace", default="",
                    help="record the structured event timeline to this JSONL "
                         "path (replay with python -m repro.obs.replay, "
                         "render with python -m repro.obs.export)")
    args = ap.parse_args()
    use_compile_cache()
    if args.trace:
        from repro.obs.export import write_jsonl
        from repro.obs.recorder import recording

        with recording() as rec:
            _dispatch(args)
        log.info("trace written", path=args.trace,
                 events=write_jsonl(args.trace, rec.events))
        return
    _dispatch(args)


if __name__ == "__main__":
    main()
