"""Orchestrated spot-training goodput: P-SIWOFT vs checkpoint-FT vs hybrid
driving a REAL (reduced) JAX training run under market revocations.

Byte-level thesis check (paper: "no FT mechanism needed"): the CSV carries
``reshard_bytes`` (bytes a live cross-mesh reshard actually moved on
revocation, siwoft/hybrid) next to ``restore_bytes`` (bytes the checkpoint
baseline pulled through remote storage) — siwoft must move strictly fewer
bytes than checkpoint restores, and the run aborts if it doesn't.

Throughput check (beyond the paper): the CSV carries ``steps_per_hour``
(measured per-mesh-shape step rates, ``DxM:steps/h`` joined by ``;``) and
``cost_to_complete`` (the expected $ for the whole job on the first
provisioned market — price integrated over the shape's wall time,
risk-adjusted). The run asserts siwoft's first pick demonstrates
price-vs-speed provisioning: the chosen shape is NOT the cheapest $/h
suitable market, but has the lowest expected cost-to-complete among the
top-lifetime candidates Algorithm 1 admits.

Allocation check (beyond the paper, ISSUE 4): a separate split scenario —
run in a subprocess with 8 forced host devices — provisions a job whose
footprint fits NO single menu shape as a 2-leg allocation over DCN, loses
one leg to a trace revocation mid-run, repairs only that leg (the lost
leg's distinct state slices cross DCN; the surviving leg keeps its
shards), and completes. Asserted: per-leg costs sum to the total bill and
the one-leg rebuild moves strictly fewer bytes than a full restore.

Besides the CSV on stdout, the bench writes machine-readable results to
``BENCH_orchestrator.json`` at the repo root (cost, completion time,
reshard/restore bytes per policy + the split scenario) so the perf
trajectory is tracked across PRs.

CSV: mode,useful_steps,wasted_steps,revocations,goodput,cost_usd,
    reshard_bytes,restore_bytes,reshard_usd,recovery_usd,
    steps_per_hour,cost_to_complete,final_loss

    python benchmarks/orchestrator_bench.py [--quick] [--steps N]
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys
import tempfile

if "--split-only" in sys.argv:
    # the split scenario needs a multi-device pool to mean anything; force
    # it BEFORE jax initializes (the parent process re-execs us this way).
    # Appended AFTER any inherited XLA_FLAGS: duplicate flags resolve
    # last-wins, so an environment-set device count cannot override ours.
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()


from repro.config import TrainConfig, get_arch
from repro.core import SiwoftPolicy, generate_markets, split_history_future
from repro.core import provisioner as alg
from repro.core.orchestrator import SpotTrainingOrchestrator
from repro.data import SyntheticLM
from repro.launch.mesh import make_host_mesh
from repro.models import build_model

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH_JSON = REPO_ROOT / "BENCH_orchestrator.json"

CSV_HEADER = (
    "mode,useful_steps,wasted_steps,revocations,goodput,cost_usd,"
    "reshard_bytes,restore_bytes,reshard_usd,recovery_usd,"
    "steps_per_hour,cost_to_complete,final_loss"
)


def check_price_vs_speed(orch: SpotTrainingOrchestrator, rep, total_steps: int) -> str:
    """Assert the siwoft run provisions by cost-to-complete, not raw $/h:
    its first market must be pricier per hour than the cheapest suitable
    market yet the cheapest per unit of work among the admitted
    top-lifetime candidates."""
    job = orch._segment_job(total_steps)
    feats = orch.feats
    chosen = rep.markets_used[0]
    suitable = alg.find_suitable_servers(job, feats)
    assert chosen in suitable
    cheapest = min(suitable, key=lambda i: float(feats.avg_price[i]))
    lifetimes = alg.compute_lifetime(feats, suitable)
    S = alg.server_based_lifetime(job, lifetimes, SiwoftPolicy(), feats)
    top = [i for i in S if lifetimes[i] == lifetimes[S[0]]]
    ecc = {i: alg.expected_cost_to_complete(job.length_hours, feats, i) for i in top}
    assert chosen != cheapest, (
        "expected the chosen shape to beat the cheapest $/h market on "
        "cost-to-complete, but siwoft picked the cheapest market itself"
    )
    assert ecc[chosen] == min(ecc.values()), (chosen, ecc)
    ch, cc = orch.future.markets[chosen], orch.future.markets[cheapest]
    return (
        f"# price-vs-speed: chose {ch.instance_type} ({ch.device_count} dev, "
        f"${feats.avg_price[chosen]:.3f}/h, ecc ${ecc[chosen]:.4f}) over cheapest "
        f"{cc.instance_type} ({cc.device_count} dev, ${feats.avg_price[cheapest]:.3f}/h, "
        f"ecc ${alg.expected_cost_to_complete(job.length_hours, feats, cheapest):.4f})"
    )


def split_scenario(quick: bool = False) -> dict:
    """A job too big for every menu shape completes as a 2-leg allocation.

    Hand-built market set (8 forced host devices simulate the instances):
    three 8-device/40 GB markets in distinct regions — A and B calm over
    the whole history (so the (A, B) pair has the max min-MTTR and wins
    the split ranking), C with a mildly revoking history — plus a small
    1-device market that can never fit the job. The planner footprint
    (``job_memory_gb``) is 400 GB: more than any single 320 GB shape,
    within any 8+8 pair. In the future window B revokes at hour 2 (the
    trace-driven surprise history could not predict). The run must (1)
    provision the 2-leg (A, B) allocation, (2) lose leg B to the trace
    revocation, (3) repair ONLY that leg with C — billing the lost leg's
    distinct state slices over DCN, strictly fewer bytes than the
    full-state restore a checkpoint baseline would pull — and (4) finish,
    with the per-leg cost split summing to the total bill.
    """
    import numpy as np

    from repro.core.market import Market, MarketSet
    from repro.dist.meshplan import train_state_bytes

    cfg = get_arch("qwen3-4b").reduced()
    model = build_model(cfg)
    markets = [
        Market(0, "big8.a", "us-east-1", "us-east-1a", 40, 1.2,
               device_count=8, interconnect_gbps=60.0),
        Market(1, "big8.b", "eu-west-1", "eu-west-1a", 40, 1.2,
               device_count=8, interconnect_gbps=60.0),
        Market(2, "big8.c", "ap-southeast-1", "ap-southeast-1a", 40, 1.2,
               device_count=8, interconnect_gbps=60.0),
        Market(3, "small1", "us-east-1", "us-east-1b", 64, 0.4,
               device_count=1, interconnect_gbps=10.0),
    ]
    H = 90
    hp = np.full((4, H), 0.35)
    hp[2, ::45] = 1.5   # C: MTTR 45 h (admits, but ranks below calm A/B)
    hp[3, ::5] = 0.6    # small market: volatile (0.6 > its 0.4 on-demand ->
    #                     revokes every 5 h); irrelevant either way — one
    #                     device can never fit the 400 GB job
    hist = MarketSet(markets, hp)
    F = 24
    fp = np.full((4, F), 0.35)
    fp[1, 2:4] = 1.5    # B revokes at future hour 2 — mid-run
    fut = MarketSet(markets, fp, start_hour=H)

    ds = SyntheticLM(cfg.vocab_size, seq_len=32, global_batch=4, seed=0)
    steps = 20 if quick else 40
    tc = TrainConfig(total_steps=steps * 2, warmup_steps=2)
    orch = SpotTrainingOrchestrator(
        model, ds, make_host_mesh(), hist, fut, mode="siwoft", tc=tc,
        segment_steps=10, steps_per_trace_hour=1, seed=0,
        job_memory_gb=400.0,
    )
    rep = orch.run(steps)

    full_restore_bytes = train_state_bytes(model)
    leg_cost_sum = sum(rep.leg_costs.values())
    assert len(rep.allocations_used[0]) == 2, rep.allocations_used
    assert rep.useful_steps == steps, (rep.useful_steps, steps)
    assert rep.revocations >= 1 and rep.leg_repairs >= 1, (
        rep.revocations, rep.leg_repairs)
    assert 1 in [m for a in rep.allocations_used for m in a]  # B was used
    assert 0 < rep.reshard_bytes < full_restore_bytes, (
        rep.reshard_bytes, full_restore_bytes)
    assert abs(leg_cost_sum - rep.cost_dollars) < 1e-6 * max(rep.cost_dollars, 1.0)
    assert len(rep.leg_costs) >= 3  # A, B and the replacement leg all billed
    return {
        "steps": steps,
        "allocations_used": [list(a) for a in rep.allocations_used],
        "revocations": rep.revocations,
        "leg_repairs": rep.leg_repairs,
        "reshard_bytes": rep.reshard_bytes,
        "full_restore_bytes": full_restore_bytes,
        "cost_usd": rep.cost_dollars,
        "leg_costs": {str(k): v for k, v in sorted(rep.leg_costs.items())},
        "completion_trace_hours": rep.breakdown.total_time,
        "final_loss": rep.losses[-1],
    }


def run_split_subprocess(quick: bool) -> dict:
    """Re-exec this script with 8 forced host devices for the split
    scenario (the parent process is pinned to the real 1-CPU pool, which
    cannot represent a 2-leg mesh). The child is a CPU simulation: it is
    held to the CPU so that it never reaches for a chip the parent holds."""
    cmd = [sys.executable, __file__, "--split-only"]
    if quick:
        cmd.append("--quick")
    pythonpath = os.pathsep.join(
        p for p in (str(REPO_ROOT / "src"), os.environ.get("PYTHONPATH")) if p
    )
    env = {**os.environ, "PYTHONPATH": pythonpath, "JAX_PLATFORMS": "cpu"}
    res = subprocess.run(
        cmd, capture_output=True, text=True, timeout=1200, env=env,
        cwd=str(REPO_ROOT),
    )
    for line in res.stdout.splitlines():
        if line.startswith("SPLIT_JSON "):
            return json.loads(line[len("SPLIT_JSON "):])
    raise RuntimeError(
        f"split scenario failed (exit {res.returncode}):\n{res.stdout}\n{res.stderr}"
    )


def main(quick: bool = False, steps: int = 0, trace: str = "") -> None:
    if trace:
        from repro.obs.export import write_jsonl
        from repro.obs.recorder import recording

        # NOTE: the 2-leg split scenario runs in a re-exec'd subprocess
        # (8 forced host devices), so its events are not in this trace.
        with recording() as rec:
            _main(quick, steps)
        print(f"# trace: {trace} ({write_jsonl(trace, rec.events)} events)")
        return
    _main(quick, steps)


def _main(quick: bool = False, steps: int = 0) -> None:
    cfg = get_arch("qwen3-4b").reduced()
    model = build_model(cfg)
    ds = SyntheticLM(cfg.vocab_size, seq_len=32, global_batch=4, seed=0)
    mesh = make_host_mesh()
    # seed 4: a market set where the lowest cost-to-complete suitable market
    # is a 4-device g5.12xlarge at ~2.9x the $/h of the cheapest m5.xlarge —
    # the price-vs-speed flip this bench asserts on
    ms = generate_markets(seed=4, n_hours=24 * 90 + 24 * 30)
    hist, fut = split_history_future(ms, 24 * 90)
    custom_steps = bool(steps)
    steps = steps or (30 if quick else 60)
    tc = TrainConfig(total_steps=steps * 2, warmup_steps=5)

    print(CSV_HEADER)
    reports = {}
    orchs = {}
    rows = {}
    for mode in ("siwoft", "checkpoint", "hybrid"):
        with tempfile.TemporaryDirectory() as d:
            orch = SpotTrainingOrchestrator(
                model, ds, mesh, hist, fut, mode=mode, tc=tc,
                segment_steps=10, steps_per_trace_hour=200, ckpt_dir=d,
                ckpt_every=5, ft_revocations=2, seed=0,
            )
            rep = orch.run(steps)
        reports[mode] = rep
        orchs[mode] = orch
        sph = ";".join(
            f"{shape}:{rate:.1f}" for shape, rate in sorted(rep.shape_steps_per_hour.items())
        )
        rows[mode] = (
            f"{mode},{rep.useful_steps},{rep.wasted_steps},{rep.revocations},"
            f"{rep.goodput:.3f},{rep.cost_dollars:.4f},"
            f"{rep.reshard_bytes},{rep.restore_bytes},"
            f"{rep.breakdown.cost['reshard']:.6f},"
            f"{rep.breakdown.cost['recovery']:.6f},"
            f"{sph},{rep.cost_to_complete:.4f},"
            f"{rep.losses[-1]:.4f}"
        )
        print(rows[mode])

    # the report must carry the throughput columns, populated: a measured
    # steps/hour entry per mesh shape used, and a positive expected
    # cost-to-complete for the first provisioned market
    for mode, row in rows.items():
        cells = row.split(",")
        assert len(cells) == len(CSV_HEADER.split(",")), (mode, row)
        assert ":" in cells[10], f"{mode}: no measured per-shape steps_per_hour"
        assert float(cells[11]) > 0, f"{mode}: missing cost_to_complete"
    # the flip is tuned to the default/quick job length on market seed 4; a
    # custom --steps changes the admission set, so report instead of abort
    if custom_steps:
        try:
            print(check_price_vs_speed(orchs["siwoft"], reports["siwoft"], steps))
        except AssertionError as e:
            print(f"# note: price-vs-speed flip not exhibited at --steps {steps}: {e}")
    else:
        print(check_price_vs_speed(orchs["siwoft"], reports["siwoft"], steps))

    # the paper's thesis, in bytes: a live reshard moves less than a
    # checkpoint restore pulls through storage. A custom --steps can be so
    # short that the injected revocations precede the first checkpoint
    # (nothing to restore) — skip the degenerate comparison with a note
    # instead of asserting; default/quick runs always enforce it.
    if not custom_steps or reports["checkpoint"].restore_bytes > 0:
        assert reports["siwoft"].reshard_bytes < reports["checkpoint"].restore_bytes, (
            reports["siwoft"].reshard_bytes,
            reports["checkpoint"].restore_bytes,
        )
        assert reports["checkpoint"].restore_bytes > 0
    else:
        print("# note: no checkpoint restore at this step count; "
              "byte comparison skipped")

    # multi-leg allocation check: a job that fits no single shape completes
    # as a 2-leg split with one-leg repair (subprocess: 8 forced devices)
    split = run_split_subprocess(quick)
    print(
        f"# split: allocs={split['allocations_used']} "
        f"leg_repairs={split['leg_repairs']} "
        f"reshard={split['reshard_bytes']}B < restore={split['full_restore_bytes']}B"
    )

    # machine-readable perf trajectory, tracked across PRs
    BENCH_JSON.write_text(json.dumps({
        "steps": steps,
        "quick": quick,
        "modes": {
            mode: {
                "useful_steps": rep.useful_steps,
                "wasted_steps": rep.wasted_steps,
                "revocations": rep.revocations,
                "goodput": round(rep.goodput, 4),
                "cost_usd": round(rep.cost_dollars, 6),
                "completion_trace_hours": round(rep.breakdown.total_time, 6),
                "reshard_bytes": rep.reshard_bytes,
                "restore_bytes": rep.restore_bytes,
                "reshard_usd": round(rep.breakdown.cost["reshard"], 8),
                "recovery_usd": round(rep.breakdown.cost["recovery"], 8),
                "cost_to_complete": round(rep.cost_to_complete, 6),
                "final_loss": round(rep.losses[-1], 6),
                "leg_costs": {
                    str(k): round(v, 6) for k, v in sorted(rep.leg_costs.items())
                },
            }
            for mode, rep in reports.items()
        },
        "split_scenario": split,
    }, indent=1) + "\n")
    print(f"# wrote {BENCH_JSON.relative_to(REPO_ROOT)}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="30-step smoke run")
    ap.add_argument("--steps", type=int, default=0, help="override step count")
    ap.add_argument("--split-only", action="store_true",
                    help="internal: run just the 2-leg split scenario "
                         "(re-execed with 8 forced host devices)")
    ap.add_argument("--trace", default="",
                    help="record the structured event timeline to this JSONL "
                         "path (validate with python -m repro.obs.replay)")
    args = ap.parse_args()
    if args.split_only:
        print("SPLIT_JSON " + json.dumps(split_scenario(quick=args.quick)))
    else:
        main(quick=args.quick, steps=args.steps, trace=args.trace)
