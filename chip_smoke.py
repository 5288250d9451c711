#!/usr/bin/env python3
"""Bring-up check: the serving main path, end to end, on a TPU.

    python chip_smoke.py                # one chip: device, serve, kernel, train
    python chip_smoke.py --four-chips   # the live 4 -> 2 chip reshard only

One process drives every chip it uses, through the package's own entry
points. Weights and data are random, made from ``--seed``.

* device — JAX must report a TPU; there is no CPU fallback. The plan from
  ``ElasticMeshManager.plan_for`` must hold as many chips as requested.
* serve — qwen3-4b at its published widths (36 layers, bf16 weights made
  on the chip) through ``DecodeEngine``: 8 lanes, 2048 context, seeded
  requests at three prompt lengths, each run to completion. Token ids
  must be in range and logits finite, and lane 0's first tokens must
  match a teacher-forced ``model.forward`` on the same chip: the same
  top-1 (up to a tie within two bf16 ulps of the top logit) and logit
  correlation above 0.99.
* kernel — the Pallas paged-attention kernel on the served pool's shapes,
  against ``paged_attention_ref`` on the chip.
* train — the orchestrator in siwoft mode runs single-step segments of
  xlstm-350m at its published widths (float32 Adam state, seq 512,
  batch 4) through ``run_segment``; every loss must be finite.
* ``--four-chips`` — qwen3-4b served on the 4-chip plan, revoked after a
  few steps and drained to the 2-chip plan (``drain_replica``), against
  the same requests served on one chip: every drained token must be the
  one-chip teacher-forced top-1 of its stream (up to the same tie). Then
  the xlstm-350m training state, at the orchestrator's layout, resharded
  4 -> 2 (bit for bit) with one step after, against the unrevoked run
  (losses within 0.2%).

Each phase prints one ``chip_smoke <phase> {json}`` line. The last line
is ``{"ok": true, "device": {...}}`` and is printed only when every phase
passed; any failure exits non-zero. Compiles go to the persistent cache
(``JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache/`` here).
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
SRC = HERE / "src"

SERVE_ARCH = "qwen3-4b"
TRAIN_ARCH = "xlstm-350m"
LANES, MAX_CONTEXT = 8, 2048
PROMPT_LENS = (128, 256, 512)
REQUESTS, NEW_TOKENS, CHECKED_TOKENS = 12, 32, 8
TRAIN_SEQ, TRAIN_BATCH, TRAIN_STEPS = 512, 4, 4
CORR_MIN = 0.99
TIE_ULPS = 2
LOSS_RTOL = 2e-3


def emit(phase: str, info: dict) -> None:
    print(f"chip_smoke {phase} " + json.dumps(info, sort_keys=True), flush=True)


class CompileMeter:
    """Programs compiled, programs read back from the persistent cache,
    and the seconds spent on both (JAX times a cache read as a compile)."""

    def __init__(self, monitoring):
        self.count, self.seconds, self.cache_hits = 0, 0.0, 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"compiles": self.count - self.cache_hits, "compile_seconds": self.seconds,
                "cache_hits": self.cache_hits}


def device_phase(jax, want: int) -> dict:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU found (JAX platform {devices[0].platform!r}); "
            "this check runs on the chip only"
        )
    if len(devices) < want:
        raise SystemExit(f"chip_smoke: {want} chips needed, JAX found {len(devices)}")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def checked_plan(man, n: int):
    """The mesh plan for ``n`` chips, checked to span ``n`` distinct chips."""
    plan = man.plan_for(n)
    ids = {d.id for d in plan.mesh.devices.flat}
    if plan.device_count != n or len(ids) != n:
        raise AssertionError(f"plan for {n} chips holds {plan.device_count} ({ids})")
    return plan


def assert_spans(tree, plan) -> None:
    """Every leaf lives on exactly the plan's chips."""
    import jax

    want = set(plan.mesh.devices.flat)
    for leaf in jax.tree_util.tree_leaves(tree):
        if set(leaf.sharding.device_set) != want:
            raise AssertionError(
                f"leaf on {sorted(d.id for d in leaf.sharding.device_set)}, "
                f"plan holds {sorted(d.id for d in want)}"
            )


def top1_agrees(ref_logits, token: int) -> bool:
    """``token`` is the reference's top-1, or ties it within ``TIE_ULPS``
    bf16 ulps of the top logit (logits are bf16: closer is no order)."""
    import numpy as np

    top = float(ref_logits.max())
    ulp = 2.0 ** (np.floor(np.log2(max(abs(top), 1e-30))) - 7)
    return int(ref_logits.argmax()) == token or float(ref_logits[token]) >= top - TIE_ULPS * ulp


def teacher_forced(model, params, mesh, rows):
    """Logits of ``model.forward`` over int32 token ``rows`` (B, L), float32."""
    import jax
    import numpy as np

    fwd = jax.jit(lambda p, t: model.forward(p, {"tokens": t})[0])
    with mesh:
        return np.asarray(fwd(params, jax.numpy.asarray(rows)), np.float32)


def seeded_prompts(seed: int, vocab: int, lens):
    import numpy as np

    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, n, dtype=np.int32) for n in lens]


def weights_bytes(params) -> int:
    import jax

    return sum(x.nbytes for x in jax.tree_util.tree_leaves(params))


def peak_bytes(jax) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


def serve_phase(man, seed: int, *, reduced: bool = False) -> dict:
    import jax
    import numpy as np

    from repro.config import ShardingLayout
    from repro.launch.serve import init_params, serving_config
    from repro.models import build_model
    from repro.models.layers import PAGE_SIZE
    from repro.serve import DecodeEngine, Request

    cfg = serving_config(SERVE_ARCH, reduced=reduced)
    model = build_model(cfg)
    plan = checked_plan(man, 1)
    engine = DecodeEngine(
        model, ShardingLayout(), plan.mesh, lanes=LANES,
        num_pages=LANES * -(-MAX_CONTEXT // PAGE_SIZE) + 1, max_context=MAX_CONTEXT,
    )
    params = init_params(model, engine.param_sh, seed)
    assert_spans(params, plan)
    lens = [PROMPT_LENS[i % len(PROMPT_LENS)] for i in range(REQUESTS)]
    prompts = seeded_prompts(seed, cfg.vocab_size, lens)
    for rid, prompt in enumerate(prompts):
        engine.submit(Request(rid=rid, prompt=prompt, max_new_tokens=NEW_TOKENS))

    # request 0 is admitted first, into lane 0; decode step k produces its
    # token k (token 0 comes from its prefill)
    lane0 = []
    t0 = time.perf_counter()
    while engine.in_flight:
        engine.step(params)
        if len(lane0) < CHECKED_TOKENS:
            lane0.append(np.asarray(engine.last_logits[0], np.float32))
    serve_seconds = time.perf_counter() - t0

    done = {c.rid: c for c in engine.completions}
    assert sorted(done) == list(range(REQUESTS)), sorted(done)
    for c in done.values():
        assert c.reason == "length" and len(c.tokens) == NEW_TOKENS, (c.rid, c.reason)
        assert all(0 <= t < cfg.vocab_size for t in c.tokens), c.rid
    assert all(np.isfinite(x).all() for x in lane0), "non-finite decode logits"

    tokens0 = done[0].tokens
    p = len(prompts[0])
    row = np.concatenate([prompts[0], np.asarray(tokens0[:CHECKED_TOKENS], np.int32)])
    ref = teacher_forced(model, params, plan.mesh, row[None])[0]
    assert np.isfinite(ref).all(), "non-finite reference logits"
    corr, exact = [], 0
    for k in range(CHECKED_TOKENS + 1):
        pos = ref[p - 1 + k]
        assert top1_agrees(pos, tokens0[k]), (k, tokens0[k], int(pos.argmax()))
        exact += int(pos.argmax()) == tokens0[k]
        if k:  # token 0 came from prefill; decode step k gave token k
            corr.append(float(np.corrcoef(pos, lane0[k - 1])[0, 1]))
    assert min(corr) > CORR_MIN, corr

    info = {
        "arch": cfg.name, "params": model.param_count(),
        "weights_bytes": weights_bytes(params),
        "weights_dtype": str(jax.tree_util.tree_leaves(params)[0].dtype),
        "lanes": LANES, "max_context": MAX_CONTEXT, "pool_pages": engine.num_pages,
        "requests": REQUESTS, "prompt_lens": sorted(set(lens)),
        "tokens_generated": sum(len(c.tokens) for c in done.values()),
        "tokens_prefilled": engine.prefilled_tokens, "decode_steps": engine.steps,
        "serve_wall_seconds_incl_compile": serve_seconds,
        "lane0_top1_exact": exact, "lane0_checked": CHECKED_TOKENS + 1,
        "lane0_min_corr": min(corr),
    }
    del engine, params
    gc.collect()
    return info


def kernel_phase(seed: int, *, reduced: bool = False, interpret: bool = False) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.paged_attention import paged_attention_ref, paged_decode_attention
    from repro.launch.serve import serving_config
    from repro.models.layers import PAGE_SIZE

    cfg = serving_config(SERVE_ARCH, reduced=reduced)
    H, KVH, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    blocks = -(-MAX_CONTEXT // PAGE_SIZE)
    pages = LANES * blocks + 1
    k1, k2, k3 = jax.random.split(jax.random.key(seed), 3)
    dt = jnp.dtype(cfg.dtype)
    q = jax.random.normal(k1, (LANES, H, hd), dt)
    k_pages = jax.random.normal(k2, (pages, PAGE_SIZE, KVH, hd), dt)
    v_pages = jax.random.normal(k3, (pages, PAGE_SIZE, KVH, hd), dt)
    # ragged lanes over scattered pages: full context, one token, a dead
    # lane (0), and lengths that end mid-page
    lens = np.asarray([MAX_CONTEXT, 1, 17, 1000, 0, 512, 33, MAX_CONTEXT - 49][:LANES],
                      np.int32)
    perm = np.random.default_rng(seed).permutation(pages - 1)
    table = np.full((LANES, blocks), -1, np.int32)
    for b, n in enumerate(lens):
        used = -(-int(n) // PAGE_SIZE)
        table[b, :used] = perm[b * blocks: b * blocks + used]
    args = (q, k_pages, v_pages, jnp.asarray(table), jnp.asarray(lens))
    out = jax.jit(lambda *a: paged_decode_attention(*a, interpret=interpret))(*args)
    ref = jax.jit(paged_attention_ref)(*args)
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    np.testing.assert_allclose(out, ref, atol=2e-2, rtol=2e-2)
    assert np.all(out[lens == 0] == 0.0), "dead lane is not zero"
    return {"q": list(q.shape), "pool": list(k_pages.shape), "dtype": str(dt),
            "lens": lens.tolist(), "max_abs_err": float(np.abs(out - ref).max())}


def train_phase(man, seed: int, *, reduced: bool = False) -> dict:
    import numpy as np

    from repro.config import TrainConfig, get_arch
    from repro.core import generate_markets, split_history_future
    from repro.core.orchestrator import SpotTrainingOrchestrator
    from repro.data import SyntheticLM
    from repro.dist.meshplan import train_state_bytes
    from repro.models import build_model

    cfg = get_arch(TRAIN_ARCH)
    if reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    plan = checked_plan(man, 1)
    ds = SyntheticLM(cfg.vocab_size, TRAIN_SEQ, TRAIN_BATCH, seed=seed)
    ms = generate_markets(seed=3, n_hours=24 * 90 + 24 * 30)
    hist, fut = split_history_future(ms, 24 * 90)
    # single-step segments: the orchestrator keeps each segment's start
    # state for a live handoff, so a longer segment would hold a third
    # copy of the float32 train state
    orch = SpotTrainingOrchestrator(
        model, ds, plan.mesh, hist, fut, mode="siwoft",
        tc=TrainConfig(total_steps=TRAIN_STEPS, warmup_steps=1, seed=seed),
        segment_steps=1, steps_per_trace_hour=200, mesh_manager=man, seed=seed,
    )
    rep = orch.run(TRAIN_STEPS)
    assert rep.useful_steps == TRAIN_STEPS, rep.useful_steps
    assert rep.losses and np.isfinite(rep.losses).all(), rep.losses
    return {"arch": cfg.name, "params": model.param_count(),
            "train_state_bytes": train_state_bytes(model),
            "seq": TRAIN_SEQ, "batch": TRAIN_BATCH, "useful_steps": rep.useful_steps,
            "wasted_steps": rep.wasted_steps, "revocations": rep.revocations,
            "segments": len(rep.allocations_used), "markets_used": rep.markets_used,
            "reshard_bytes": rep.reshard_bytes, "losses": rep.losses}


def four_chip_serve(man, seed: int, *, reduced: bool = False) -> dict:
    import numpy as np

    from repro.config import ShardingLayout
    from repro.launch.serve import serve_engine_plans, serving_config
    from repro.models import build_model

    cfg = serving_config(SERVE_ARCH, reduced=reduced)
    model = build_model(cfg)
    layout = ShardingLayout()
    p4, p2, p1 = checked_plan(man, 4), checked_plan(man, 2), checked_plan(man, 1)
    prompts = seeded_prompts(seed, cfg.vocab_size, [PROMPT_LENS[0]] * LANES)
    new, revoke_after = 16, 6

    moved, plans, params = serve_engine_plans(
        model, layout, (4, 2), prompts, new, revoke_after=revoke_after, man=man, seed=seed
    )
    assert [p.key for p in plans] == [p4.key, p2.key], [p.key for p in plans]
    assert_spans(params, p2)
    assert moved["migrated_at"] == revoke_after and moved["params_bytes"] > 0, moved
    del params
    gc.collect()

    solo, _, params = serve_engine_plans(model, layout, (1,), prompts, new, man=man, seed=seed)
    assert_spans(params, p1)
    # every token of every drained stream, before the drain and after it,
    # must be the one-chip model's greedy choice given the stream so far:
    # its teacher-forced top-1, up to a bf16 tie
    rows = np.asarray([np.concatenate([p, t[:-1]]) for p, t in zip(prompts, moved["tokens"])])
    ref = teacher_forced(model, params, p1.mesh, rows)
    for b, toks in enumerate(moved["tokens"]):
        assert len(toks) == new, (b, len(toks))
        for k, tok in enumerate(toks):
            assert top1_agrees(ref[b, len(prompts[b]) - 1 + k], tok), (b, k, tok)
    first_diff = [next((i for i, (x, y) in enumerate(zip(a, s)) if x != y), None)
                  for a, s in zip(moved["tokens"], solo["tokens"])]
    del params
    gc.collect()
    return {"arch": cfg.name, "plans": ["x".join(map(str, p.mesh_shape)) for p in plans],
            "lanes": LANES, "prompt_len": PROMPT_LENS[0], "new_tokens": new,
            "migrated_at": moved["migrated_at"], "params_bytes": moved["params_bytes"],
            "params_bytes_per_device": moved["params_bytes_per_device"],
            "train_path_bytes": moved["train_path_bytes"],
            "identical_streams": sum(d is None for d in first_diff),
            "first_divergence": first_diff}


def four_chip_train(man, seed: int, *, reduced: bool = False) -> dict:
    import jax
    import numpy as np

    from repro.config import ShardingLayout, TrainConfig, get_arch
    from repro.data import SyntheticLM
    from repro.dist import reshard_tree
    from repro.dist.meshplan import live_shardings, reshard_bytes_per_device
    from repro.models import build_model
    from repro.train.loop import make_jitted_step, run_segment
    from repro.train.steps import init_train_state

    cfg = get_arch(TRAIN_ARCH)
    if reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    layout = ShardingLayout()  # the orchestrator's, as in train_phase
    tc = TrainConfig(total_steps=4, warmup_steps=1, seed=seed)
    ds = SyntheticLM(cfg.vocab_size, TRAIN_SEQ, TRAIN_BATCH, seed=seed)
    p4, p2 = checked_plan(man, 4), checked_plan(man, 2)
    step4, sh4 = make_jitted_step(model, tc, layout, p4.mesh)
    step2, sh2 = make_jitted_step(model, tc, layout, p2.mesh)

    def segment(state, plan, jitted, start):
        return run_segment(model, state, ds, plan.mesh, tc, layout, num_steps=1,
                           start_step=start, jitted=jitted)

    # the steps donate nothing: each copy of the float32 state no longer
    # needed is dropped, or the 2-chip step does not fit beside them
    first = segment(reshard_tree(init_train_state(model, jax.random.key(seed)), sh4),
                    p4, step4, 0)
    loss0 = first.losses[0]
    b = segment(first.state, p4, step4, 1).losses[0]
    moved = reshard_bytes_per_device(first.state, live_shardings(first.state), sh2)
    state2 = reshard_tree(first.state, sh2)
    assert_spans(state2, p2)
    # a reshard only copies: the 2-chip state is the stepped 4-chip state, bit for bit
    for old, new in zip(jax.tree_util.tree_leaves(first.state), jax.tree_util.tree_leaves(state2)):
        assert np.array_equal(np.asarray(old), np.asarray(new)), "reshard changed the state"
    del first  # the revoked mesh's copy
    a = segment(state2, p2, step2, 1).losses[0]
    assert np.isfinite([loss0, a, b]).all(), (loss0, a, b)
    # the (2, 1) mesh sums in another order; the step before moved the loss
    # 0.45% (seed 0), so a stale or un-stepped state would fail this
    assert abs(a - b) <= LOSS_RTOL * abs(b), (a, b)
    return {"arch": cfg.name, "plans": ["x".join(map(str, p.mesh_shape)) for p in (p4, p2)],
            "reshard_bytes": sum(moved.values()),
            "reshard_bytes_per_device": {str(d.id): n for d, n in moved.items()},
            "loss_step0": loss0, "loss_step1_revoked": a, "loss_step1_unrevoked": b}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4 -> 2 chip reshard and what it is compared with")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        raise SystemExit(f"chip_smoke: the package is missing ({SRC / 'repro'})")
    sys.path.insert(0, str(SRC))
    from repro.launch.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    import jax

    from repro.dist import ElasticMeshManager

    meter = CompileMeter(jax.monitoring)
    want = 4 if args.four_chips else 1
    device = device_phase(jax, want)
    device["cache_dir"] = cache_dir
    emit("device", device)
    man = ElasticMeshManager()

    phases = (
        [("serve_4to2", four_chip_serve), ("train_4to2", four_chip_train)]
        if args.four_chips else
        [("serve", serve_phase), ("kernel", lambda man, seed: kernel_phase(seed)),
         ("train", train_phase)]
    )
    for name, phase in phases:
        info = phase(man, args.seed)
        info.update(meter.snapshot(), peak_bytes_in_use=peak_bytes(jax))
        emit(name, info)

    last = {"ok": True, "device": {"platform": device["platform"], "kind": device["kind"],
                                   "count": device["count"]}}
    print(json.dumps(last), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
