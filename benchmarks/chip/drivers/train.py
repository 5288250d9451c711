"""Training driver: the orchestrator's train step through ``run_segment``.

Set-up builds the job once: the jitted step of ``make_jitted_step`` for
each mesh plan the traffic file names (``plans``: ``[1]``, or ``[4, 2]``
for a job revoked between the elastic mesh manager's 4- and 2-chip plans),
and the train state, its weights made on the chip from the seed in one
jitted call. It then drives that same state through its first three steps
with ``run_segment``, the window's own call and feed (with two plans: step
0 on the first, a live reshard, step 1 on the second, a reshard back, step
2 on the first, so both plans' programs are compiled), and keeps what the
check compares: each step's loss, the norms of the first gradient as AdamW
holds it after one step, and of each leaf's change over the three steps.

The window goes on from step 3 with the same object:

* one plan: segments of ``segment_steps`` steps until ``--seconds`` have
  passed; ``tok_s`` is tokens trained over the window's whole length;
* two plans: every ``revoke_every`` steps the mesh is revoked and the live
  state moved with ``reshard_tree`` onto the other plan (4 -> 2, then
  2 -> 4 as the next market comes), as the orchestrator does; ``resume_s``
  is the mean time from a revocation to the end of the first step on the
  new mesh.

Then the check: peak memory is read, the state is dropped, and the plain
float32 reference (``reference/<family>.py``) runs the same three steps from
the same weights on the same rows.
"""
from __future__ import annotations

import gc
import sys
import time
from typing import Any, Dict, List

import bench
import programs
import traffic as gen
import weights


def _train_config(tr: Dict[str, Any], seed: int):
    from repro.config import TrainConfig

    o = tr["optimizer"]
    return TrainConfig(learning_rate=o["learning_rate"], weight_decay=o["weight_decay"],
                       beta1=o["beta1"], beta2=o["beta2"], eps=o["eps"],
                       grad_clip=o["grad_clip"], warmup_steps=o["warmup_steps"],
                       total_steps=o["total_steps"], seed=seed % (2 ** 31))


def run(ctx: bench.Context) -> bench.Run:
    import jax
    import jax.numpy as jnp

    from repro.config import ShardingLayout
    from repro.dist import ElasticMeshManager, reshard_tree
    from repro.dist.meshplan import reshard_bytes_per_device
    from repro.models import build_model
    from repro.optim import init_opt_state
    from repro.train.loop import make_jitted_step, run_segment
    from repro.train.steps import TrainState

    conf, tr = ctx.cell.config, ctx.cell.traffic
    cfg = programs.model_config(conf, ctx.cell.base)
    model = build_model(cfg)
    layout = ShardingLayout()          # the orchestrator's layout
    tc = _train_config(tr, ctx.seed)
    man = ElasticMeshManager(ctx.devices)
    plans = [man.plan_for(n) for n in tr["plans"]]
    for p, n in zip(plans, tr["plans"]):
        if p.device_count != n:
            raise SystemExit(f"bench: the plan for {n} chips holds {p.device_count}")
    steps = [make_jitted_step(model, tc, layout, p.mesh) for p in plans]
    rows = gen.TrainRows(ctx.seed, cfg.vocab_size, tr["seq_len"], tr["batch"])
    key = bench.jax_key(ctx.seed)

    sh0 = steps[0][1]
    params = weights.make(conf, key, cfg.param_dtype, sh0.params, ctx.cell.base)
    if not weights.shapes_match(params, model.abstract_params()):
        raise SystemExit("bench: the program's parameter tree is not the one the "
                         "configuration describes")
    opt = jax.jit(init_opt_state, out_shardings=sh0.opt)(params)
    state = TrainState(params, opt, jax.device_put(jnp.zeros((), jnp.int32), sh0.step))
    del params, opt
    def leaf_norms(t):
        return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                for x in jax.tree_util.tree_leaves(t)]

    norms = jax.jit(leaf_norms)
    change_norms = jax.jit(lambda a, b: leaf_norms(jax.tree_util.tree_map(jnp.subtract, a, b)))

    def segment(seg_state, i, start, n):
        # as ``core/orchestrator.py`` calls it: the caller keeps the
        # segment's start state (``seg_state``) while the segment runs
        plan, (jitted, _) = plans[i], steps[i]
        return run_segment(model, seg_state, rows, plan.mesh, tc, layout, num_steps=n,
                           start_step=start, jitted=jitted)

    def move(st, i):
        new = reshard_tree(st, steps[i][1])
        jax.block_until_ready(new)
        return new

    # the first three steps, kept for the check
    order = [0, 1, 0] if len(plans) > 1 else [0, 0, 0]
    losses, cur = [], 0
    for s, i in enumerate(order):
        if i != cur:
            state, cur = move(state, i), i
        res = segment(state, i, s, 1)
        state = res.state
        losses.extend(res.losses)
        if s == 0:
            b1 = tc.beta1
            first_grad = [float(x) / (1 - b1) for x in norms(state.opt.m)]
    p0 = weights.make(conf, key, cfg.param_dtype, steps[cur][1].params, ctx.cell.base)
    change = [float(x) for x in change_norms(state.params, p0)]
    del p0
    prog = {"losses": losses, "grad": first_grad, "change": change}
    jax.block_until_ready(state)

    compiles_before = ctx.meter.count
    setup_s = time.perf_counter() - ctx.t_process
    step = len(order)
    seg = tr["segment_steps"]
    revoke_every = tr.get("revoke_every")
    pair_bytes = {(i, j): sum(reshard_bytes_per_device(state, steps[i][1], steps[j][1]).values())
                  for i in range(len(plans)) for j in range(len(plans)) if i != j}
    resumes: List[float] = []
    reshards: List[float] = []
    moved_bytes: List[int] = []
    with ctx.window():
        t0 = time.perf_counter()
        t_end = t0 + ctx.seconds
        while time.perf_counter() < t_end:
            if revoke_every:
                with bench.span("train.segment"):
                    state = segment(state, cur, step, revoke_every - 1).state
                step += revoke_every - 1
                nxt = (cur + 1) % len(plans)
                t_rev = time.perf_counter()
                with bench.span("reshard"):
                    state = move(state, nxt)
                t_moved = time.perf_counter()
                with bench.span("train.first_step"):
                    state = segment(state, nxt, step, 1).state
                t_first = time.perf_counter()
                moved_bytes.append(pair_bytes[(cur, nxt)])
                step += 1
                cur = nxt
                resumes.append(t_first - t_rev)
                reshards.append(t_moved - t_rev)
            else:
                with bench.span("train.segment"):
                    state = segment(state, cur, step, seg).state
                step += seg
        t_stop = time.perf_counter()
    compiles_in_window = ctx.meter.count - compiles_before
    window_s = t_stop - t0
    trained = step - len(order)
    tokens = trained * tr["batch"] * tr["seq_len"]
    end_to_end = {"setup_s": setup_s}
    if tokens:
        end_to_end["tok_s"] = tokens / window_s
    if resumes:
        end_to_end["resume_s"] = sum(resumes) / len(resumes)
    counts: Dict[str, Any] = {
        "window_s": window_s, "steps": trained, "tokens": tokens, "chips": ctx.cell.chips, "resume_s": resumes, "reshard_s": reshards,
        "reshard_bytes": moved_bytes,
    }
    print(f"bench window steps {trained} window_s {window_s!r} revocations {len(resumes)}",
          file=sys.stderr, flush=True)

    peak = bench.peak_bytes(ctx.devices)
    del state
    gc.collect()
    checks = _check(conf, tr, key, rows, prog, ctx)
    control = checks.pop("_control", None)
    counts["control"] = control
    return bench.Run(
        end_to_end=end_to_end, attempted=trained + len(order), failed=0,
        checks={k: (v, ctx.limit(k)) for k, v in checks.items()},
        counts=counts, memory_peak_bytes=peak, compiles_in_window=compiles_in_window,
        control_checks=({k: (v, ctx.limit(k)) for k, v in control["fp8"].items()}
                        if control else None),
    )


def _check(conf, tr, key, rows, prog, ctx) -> Dict[str, Any]:
    """The reference's three steps on one chip, compared with the program's."""
    import jax

    ref = bench.family(conf, ctx.cell.base)
    dev = ctx.devices[0]
    batches = [rows.batch(s) for s in range(3)]
    with jax.default_device(dev), jax.default_matmul_precision("highest"):
        def p():
            return weights.make(conf, key, "float32", base=ctx.cell.base)

        want = ref.train_readings(conf, tr["optimizer"], p, batches)
        out = ref.compare(prog, want)
        if ctx.control:
            low = ref.train_readings(conf, tr["optimizer"], p, batches, quant="fp8")
            half = [{k: v[: len(v) // 2] for k, v in b.items()} for b in batches]
            halved = ref.train_readings(conf, tr["optimizer"], p, half)
            out["_control"] = {"fp8": ref.compare(low, want), "half_batch": ref.compare(halved, want)}
    print("bench check losses program " + repr(prog["losses"]) + " reference "
          + repr(want["losses"]), file=sys.stderr, flush=True)
    return out
