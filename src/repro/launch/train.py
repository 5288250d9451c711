"""Training launcher.

    python -m repro.launch.train --arch <id> [--steps N] [--no-reduced]
        [--spot-mode siwoft|checkpoint|hybrid|none]

It trains on the host mesh: the reduced config by default, the published
widths with ``--no-reduced``. With
``--spot-mode`` the run goes through the P-SIWOFT orchestrator (the paper's
provisioning layer); with ``none`` it is a plain training loop.
"""
import argparse
import tempfile

import jax

from repro.ckpt import CheckpointManager
from repro.config import ShardingLayout, TrainConfig, get_arch, list_archs
from repro.core import generate_markets, split_history_future
from repro.core.orchestrator import SpotTrainingOrchestrator
from repro.data import SyntheticLM
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import build_model
from repro.obs import get_logger
from repro.train.loop import run_segment
from repro.train.steps import init_train_state

log = get_logger("launch.train")


def _run(args) -> None:
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    mesh = make_host_mesh()
    ds = SyntheticLM(cfg.vocab_size, args.seq, args.batch, seed=args.seed)
    tc = TrainConfig(total_steps=args.steps, warmup_steps=min(20, args.steps // 10 + 1))
    log.info("launching", arch=cfg.name,
             params_m=model.param_count() / 1e6, mode=args.spot_mode)

    if args.spot_mode == "none":
        ckpt = CheckpointManager(args.ckpt_dir, keep=3) if args.ckpt_dir else None
        state = init_train_state(model, jax.random.key(args.seed))
        res = run_segment(
            model, state, ds, mesh, tc, ShardingLayout(),
            num_steps=args.steps, ckpt=ckpt, ckpt_every=50,
        )
        if ckpt:
            ckpt.close()
        log.info("training done",
                 loss_first=res.losses[0], loss_last=res.losses[-1],
                 mean_step_ms=sum(res.step_seconds) / len(res.step_seconds) * 1e3)
        return

    ms = generate_markets(seed=3, n_hours=24 * 90 + 24 * 30)
    hist, fut = split_history_future(ms, 24 * 90)
    with tempfile.TemporaryDirectory() as d:
        orch = SpotTrainingOrchestrator(
            model, ds, mesh, hist, fut, mode=args.spot_mode, tc=tc,
            segment_steps=max(args.steps // 5, 1), steps_per_trace_hour=200,
            ckpt_dir=args.ckpt_dir or d, ckpt_every=10, seed=args.seed,
        )
        rep = orch.run(args.steps)
    log.info("spot training done", useful=rep.useful_steps,
             wasted=rep.wasted_steps, revocations=rep.revocations,
             goodput=rep.goodput, cost_dollars=rep.cost_dollars,
             loss_first=rep.losses[0], loss_last=rep.losses[-1])


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="train the reduced smoke config (default); "
                         "--no-reduced trains the published widths")
    ap.add_argument("--spot-mode", default="none",
                    choices=["none", "siwoft", "checkpoint", "hybrid"])
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default="",
                    help="record the structured event timeline to this JSONL "
                         "path (replay with python -m repro.obs.replay)")
    args = ap.parse_args()
    use_compile_cache()
    if args.trace:
        from repro.obs.export import write_jsonl
        from repro.obs.recorder import recording

        with recording() as rec:
            _run(args)
        log.info("trace written", path=args.trace,
                 events=write_jsonl(args.trace, rec.events))
        return
    _run(args)


if __name__ == "__main__":
    main()
