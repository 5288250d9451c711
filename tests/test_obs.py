"""Structured event telemetry (``repro.obs``): the frozen registry's
lossless JSONL round trip, the replay oracle — an event log re-billed
through the REAL accounting entry points reconstructs the run's
Breakdown bit-exactly — null-recorder byte-identity (telemetry off
changes nothing), cross-engine log identity (reference and vectorized
simulators emit the same timeline), and the replay/export CLIs."""
import dataclasses
import json
import pathlib
import tempfile

from hypothesis import given, settings, strategies as st
import numpy as np
import pytest

from repro.core import (
    CheckpointPolicy,
    Job,
    MigrationPolicy,
    OnDemandPolicy,
    ReplicationPolicy,
    Simulator,
    SiwoftPolicy,
    generate_markets,
    legacy_menu,
    split_history_future,
)
from repro.core import provisioner as alg
from repro.core.accounting import (
    TIME_COMPONENTS,
    Breakdown,
    PriceTable,
    Session,
    bill_session,
)
from repro.core.market import Market, MarketSet
from repro.obs import events as E
from repro.obs import replay as rp
from repro.obs.export import read_jsonl, to_chrome_trace, write_jsonl
from repro.obs.recorder import NullRecorder, current, recording
from repro.serve import (
    FleetSimulator,
    ServePolicy,
    ServingWorkload,
    on_demand_reference,
)

# --- shared hand-built serving scenario (mirrors test_serve_fleet) ----------


def _hand_markets():
    mk = [
        Market(0, "g4.a", "us-east-1", "us-east-1a", 10, 1.0,
               device_count=4, interconnect_gbps=25.0),
        Market(1, "g4.b", "us-east-1", "us-east-1b", 10, 1.0,
               device_count=4, interconnect_gbps=25.0),
        Market(2, "g4.c", "us-west-2", "us-west-2a", 10, 1.0,
               device_count=4, interconnect_gbps=25.0),
        Market(3, "g4.d", "eu-central-1", "eu-central-1a", 10, 1.0,
               device_count=4, interconnect_gbps=25.0),
    ]
    H = 24 * 90
    hp = np.full((4, H), 0.35)
    hp[2, ::45] = 1.5
    F = 48
    fp = np.full((4, F), 0.35)
    fp[1, 6:8] = 1.5
    return MarketSet(mk, hp), MarketSet(mk, fp, start_hour=H)


def _hand_workload():
    return ServingWorkload(
        target_tokens_per_sec=500.0,
        replica_tokens_per_sec=100.0,
        state_gb=30.0,
        param_bytes=120_000_000,
        cache_bytes=30_000_000,
        inflight_context_tokens=2048.0,
    )


def _rate(hours=48):
    rate = np.full(hours, 400.0)
    rate[0] = 0.0
    return rate


def _bd_fields(bd: Breakdown) -> tuple:
    return (
        dict(bd.time), dict(bd.cost), dict(bd.leg_cost), bd.revocations,
        bd.sessions, bd.wall_time, bd.served_tokens, bd.shed_tokens,
        bd.queued_token_seconds,
    )


def _replay_single(events):
    runs, problems = rp.verify_events(events)
    assert problems == [], problems
    assert len(runs) == 1
    run = runs[0]
    assert run.pin is not None
    assert rp.mismatches(run.breakdown, run.pin) == []
    return run


# --- registry + round trip --------------------------------------------------


def test_default_recorder_is_null_and_disabled():
    rec = current()
    assert isinstance(rec, NullRecorder)
    assert rec.enabled is False


def test_wire_names_are_unique_and_snake_case():
    assert len(E.EVENT_TYPES) == 20
    for name, cls in E.EVENT_TYPES.items():
        assert name == E.wire_name(cls)
        assert name == name.lower() and " " not in name


def test_every_event_type_round_trips_through_json():
    samples = [
        E.RunStart(t=0.0, subsystem="fleet", label="fleet/static",
                   horizon_hours=48.0),
        E.PriceTrace(t=0.0, prices=((0.35, 1.5), (0.4, 0.4))),
        E.RunEnd(t=48.0, wall_hours=48.0),
        E.Provision(t=1.0, market_id=3, legs=(3, 1), replica_id=2,
                    rate_tokens_per_sec=325.0),
        E.Revoke(t=6.0, market_id=1, replica_id=0),
        E.ReshardStart(t=6.0, bytes_moved=120_000_000, gbps=25.0),
        E.ReshardDone(t=6.01, hours=0.01),
        E.ScaleDecision(t=7.0, kind="up", offered_tokens_per_sec=400.0,
                        forecast_tokens_per_sec=480.0,
                        capacity_tokens_per_sec=650.0,
                        target_tokens_per_sec=600.0),
        E.ScaleUp(t=7.0, added=1, target_tokens_per_sec=600.0),
        E.ScaleDown(t=30.0, retired=1, target_tokens_per_sec=400.0),
        E.Admit(t=3.0, request_id=7, lane=1, pages_reserved=4),
        E.Evict(t=9.0, request_id=7, lane=1, reason="length"),
        E.Shed(t=5.0, request_id=7, lane=1, prompt_tokens=17,
               resume_tokens=4),
        E.Drain(t=5.0, moved_requests=2),
        E.GaugeSample(t=5.0, name="engine.occupancy", value=0.5),
        E.SessionBilled(t=8.0, market_id=1, start_wall=0.0,
                        intervals=(("startup", 0.2), ("execution", 5.8)),
                        legs=(1,), leg_anchors=None, leg_releases=None,
                        price_const=None),
        E.SessionBilled(t=8.0, market_id=0, start_wall=0.0,
                        intervals=(("execution", 8.0),), legs=(0, 2),
                        leg_anchors=(0.0, 0.0), leg_releases=(True, False),
                        price_const=0.9),
        E.LegSettled(t=12.0, market_id=2, anchor=3.0, end_wall=12.0),
        E.RouterInterval(t=0.0, t0=0.0, t1=1.0, offered_tokens=1e5,
                         served_tokens=9e4, shed_tokens=1e4,
                         queued_token_seconds=50.0,
                         slo_violation_seconds=2.5, q_end=10.0,
                         delay_segments=((1.0, 0.0, 0.5),)),
        E.SloViolation(t=0.0, seconds=2.5),
        E.BreakdownPin(t=48.0, time=(("execution", 48.0),),
                       cost=(("execution", 16.8),), leg_cost=((0, 16.8),),
                       revocations=1, sessions=2, wall_time=48.0,
                       served_tokens=1e6, shed_tokens=0.0,
                       queued_token_seconds=12.5),
    ]
    assert {type(s) for s in samples} == set(E.EVENT_TYPES.values())
    for ev in samples:
        wire = json.loads(json.dumps(E.as_dict(ev)))
        back = E.from_dict(wire)
        assert back == ev, ev


def test_jsonl_file_round_trip(tmp_path):
    events = [
        E.RunStart(t=0.0, subsystem="x", label="y", horizon_hours=1.0),
        E.Revoke(t=0.5, market_id=3),
        E.RunEnd(t=1.0, wall_hours=1.0),
    ]
    path = tmp_path / "trace.jsonl"
    assert write_jsonl(path, events) == 3
    assert read_jsonl(path) == events


# --- the replay oracle on the serving fleet ---------------------------------


def test_fleet_static_sizing_replay_bit_exact():
    hist, fut = _hand_markets()
    wl = _hand_workload()
    policy = ServePolicy(slo_horizon_hours=12.0, capacity_headroom=1.4)
    with recording() as rec:
        rep = FleetSimulator(hist, fut, wl, policy).run(48.0, _rate())
    run = _replay_single(rec.events)
    assert run.subsystem == "fleet" and run.label == "fleet/static"
    assert _bd_fields(run.breakdown) == _bd_fields(rep.breakdown)
    # the scenario actually exercises the interesting paths
    assert rep.revocations == 1 and rep.breakdown.served_tokens > 0


def test_fleet_static_mode_replay_bit_exact():
    hist, fut = _hand_markets()
    wl = _hand_workload()
    with recording() as rec:
        rep = FleetSimulator(
            hist, fut, wl,
            ServePolicy(slo_horizon_hours=12.0, capacity_headroom=1.5),
            mode="static",
        ).run(48.0, _rate())
    run = _replay_single(rec.events)
    assert run.label == "static/static"
    assert _bd_fields(run.breakdown) == _bd_fields(rep.breakdown)
    assert rep.breakdown.time["recovery"] > 0  # full restores replayed too


def test_fleet_autoscale_replay_bit_exact():
    hist, fut = _hand_markets()
    wl = _hand_workload()
    policy = ServePolicy(slo_horizon_hours=12.0, capacity_headroom=1.4)
    hours = 48
    rate = 250.0 - 150.0 * np.cos(2 * np.pi * np.arange(hours) / 24.0)
    rate[0] = 0.0
    with recording() as rec:
        rep = FleetSimulator(
            hist, fut, wl, policy, sizing="auto"
        ).run(float(hours), rate)
    run = _replay_single(rec.events)
    assert run.label == "fleet/auto"
    assert _bd_fields(run.breakdown) == _bd_fields(rep.breakdown)
    # the diurnal rate must have driven real scaler traffic
    kinds = [e.kind for e in rec.events if isinstance(e, E.ScaleDecision)]
    assert "up" in kinds or "down" in kinds
    assert rep.scale_downs > 0 or rep.scale_ups > 0


def test_on_demand_reference_replay_bit_exact():
    hist, fut = _hand_markets()
    wl = _hand_workload()
    policy = ServePolicy(slo_horizon_hours=12.0, capacity_headroom=1.4)
    feats = alg.MarketFeatures.from_history(hist)
    with recording() as rec:
        rep = on_demand_reference(wl, feats, fut, 48.0, _rate(), policy)
    run = _replay_single(rec.events)
    assert run.label == "on_demand"
    assert _bd_fields(run.breakdown) == _bd_fields(rep.breakdown)


def test_fleet_breakdown_literal_pin():
    """The hand-built 48 h scenario's totals, pinned as literals: the
    replay oracle guarantees log == run, this pins run == history (the
    numbers current at instrumentation time — a drift here is a billing
    change, not a telemetry change)."""
    hist, fut = _hand_markets()
    wl = _hand_workload()
    policy = ServePolicy(slo_horizon_hours=12.0, capacity_headroom=1.4)
    with recording() as rec:
        rep = FleetSimulator(hist, fut, wl, policy).run(48.0, _rate())
    run = _replay_single(rec.events)
    assert run.breakdown.total_cost == rep.breakdown.total_cost
    bd = rep.breakdown
    assert bd.total_cost == 50.40000000000013
    assert bd.time["execution"] == 143.83310112988207
    assert (bd.wall_time, bd.revocations, bd.sessions) == (48.0, 1, 4)
    assert bd.served_tokens == 67_680_000.0 and bd.shed_tokens == 0.0


def test_null_recorder_keeps_run_byte_identical():
    """Telemetry OFF is the default; ON must not perturb one bit of the
    arithmetic. Run the same fleet twice — under the null recorder and
    under a live one — and compare every Breakdown field with ==."""
    hist, fut = _hand_markets()
    wl = _hand_workload()
    policy = ServePolicy(slo_horizon_hours=12.0, capacity_headroom=1.4)
    assert current().enabled is False  # default: null
    plain = FleetSimulator(hist, fut, wl, policy).run(48.0, _rate())
    with recording() as rec:
        traced = FleetSimulator(hist, fut, wl, policy).run(48.0, _rate())
    assert rec.events  # the live run DID emit
    assert _bd_fields(plain.breakdown) == _bd_fields(traced.breakdown)
    assert plain.cost_dollars == traced.cost_dollars


# --- the replay oracle on the training simulator ----------------------------


SIM_POLICIES = (
    SiwoftPolicy(),
    CheckpointPolicy(),
    MigrationPolicy(),
    ReplicationPolicy(),
    OnDemandPolicy(),
)


@pytest.fixture(scope="module")
def sim_markets():
    ms = generate_markets(seed=0, n_hours=24 * 90 + 24 * 45,
                          menu=legacy_menu())
    return split_history_future(ms, 24 * 90)


def test_simulator_replay_bit_exact_both_engines(sim_markets):
    hist, fut = sim_markets
    job = Job(length_hours=24, memory_gb=16)
    for engine in ("vectorized", "reference"):
        sim = Simulator(hist, fut, seed=0, engine=engine)
        for policy in SIM_POLICIES:
            with recording() as rec:
                bd = sim.run_job(job, policy, n_revocations=2)
            run = _replay_single(rec.events)
            assert run.subsystem == "simulator"
            assert _bd_fields(run.breakdown) == _bd_fields(bd), (
                engine, type(policy).__name__)


def test_simulator_engines_emit_identical_logs(sim_markets):
    """The vectorized core bills through PriceTable and the scalar oracle
    through a closure — but the TIMELINE is engine-invariant: both must
    emit byte-identical event logs (the cross-engine form of the
    bit-exactness pin in test_vectorized_core)."""
    hist, fut = sim_markets
    job = Job(length_hours=24, memory_gb=16)
    for policy in SIM_POLICIES:
        logs = []
        for engine in ("vectorized", "reference"):
            with recording() as rec:
                Simulator(hist, fut, seed=0, engine=engine).run_job(
                    job, policy, n_revocations=2
                )
            logs.append(json.dumps([E.as_dict(e) for e in rec.events]))
        assert logs[0] == logs[1], type(policy).__name__


# --- the replay oracle on the orchestrator (real JAX training) --------------


def test_orchestrator_replay_bit_exact(host_mesh):
    """The orchestrator drives REAL training, yet its billed timeline
    replays like any other: checkpoint mode with forced revocations
    exercises sessions, recovery billing, and the revocation counter."""
    import tempfile

    from repro.config import TrainConfig, get_arch
    from repro.core.orchestrator import SpotTrainingOrchestrator
    from repro.data import SyntheticLM
    from repro.models import build_model

    cfg = get_arch("qwen3-4b").reduced()
    model = build_model(cfg)
    ds = SyntheticLM(cfg.vocab_size, seq_len=32, global_batch=4, seed=0)
    ms = generate_markets(seed=3, n_hours=24 * 90 + 24 * 30)
    hist, fut = split_history_future(ms, 24 * 90)
    tc = TrainConfig(total_steps=60, warmup_steps=5)
    with tempfile.TemporaryDirectory() as d, recording() as rec:
        rep = SpotTrainingOrchestrator(
            model, ds, host_mesh, hist, fut, mode="checkpoint", tc=tc,
            segment_steps=10, steps_per_trace_hour=200, ckpt_dir=d,
            ckpt_every=5, seed=0, ft_revocations=2,
        ).run(30)
    run = _replay_single(rec.events)
    assert run.subsystem == "orchestrator"
    assert _bd_fields(run.breakdown) == _bd_fields(rep.breakdown)
    assert run.breakdown.revocations == rep.revocations >= 1


# --- property test: random sessions through emit -> JSONL -> replay ---------


@given(
    n_sessions=st.integers(1, 6),
    seed=st.integers(0, 10_000),
    price_lo=st.floats(0.05, 0.5),
    price_hi=st.floats(0.6, 3.0),
)
@settings(max_examples=40, deadline=None)
def test_random_sessions_replay_bit_exact(n_sessions, seed, price_lo, price_hi):
    """Any run assembled from random sessions survives emit -> JSONL ->
    replay with its Breakdown reconstructed bit-exactly: Python's json
    floats round-trip shortest-repr exact, and replay bills through the
    same bill_session the run used."""
    rng = np.random.default_rng(seed)
    n_markets, horizon = 4, 48  # roomy: max 6 sessions x ~6 h each
    prices = rng.uniform(price_lo, price_hi, size=(n_markets, horizon))
    table = PriceTable(prices)

    bd = Breakdown()
    events = [
        E.RunStart(t=0.0, subsystem="simulator", label="random",
                   horizon_hours=float(horizon)),
        E.price_trace(0.0, prices),
    ]
    wall = 0.0
    for _ in range(n_sessions):
        market = int(rng.integers(0, n_markets))
        session = Session(market_id=market, start_wall=wall)
        for comp in rng.choice(TIME_COMPONENTS[:6], size=2, replace=False):
            session.add(str(comp), float(rng.uniform(0.1, 3.0)))
        events.append(E.session_billed(wall, session))
        wall += bill_session(session, table, bd)
    bd.wall_time = wall
    events.append(E.breakdown_pin(wall, bd))
    events.append(E.RunEnd(t=wall, wall_hours=wall))

    # a directory of its own per example: a function-scoped ``tmp_path``
    # would be shared by every example Hypothesis draws
    with tempfile.TemporaryDirectory() as tmp:
        path = pathlib.Path(tmp) / "random.jsonl"
        write_jsonl(path, events)
        run = _replay_single(read_jsonl(path))
    assert _bd_fields(run.breakdown) == _bd_fields(bd)


# --- CLIs -------------------------------------------------------------------


def _fleet_trace(tmp_path, name="fleet.jsonl"):
    hist, fut = _hand_markets()
    wl = _hand_workload()
    policy = ServePolicy(slo_horizon_hours=12.0, capacity_headroom=1.4)
    with recording() as rec:
        FleetSimulator(hist, fut, wl, policy).run(48.0, _rate())
    path = tmp_path / name
    write_jsonl(path, rec.events)
    return path, rec.events


def test_replay_cli_accepts_and_rejects(tmp_path, capsys):
    path, events = _fleet_trace(tmp_path)
    assert rp.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "1 run(s)" in out and "0 mismatch(es)" in out

    # corrupt the pin: the CLI must exit nonzero and name the field
    bad = []
    for ev in events:
        if isinstance(ev, E.BreakdownPin):
            ev = dataclasses.replace(ev, revocations=ev.revocations + 1)
        bad.append(ev)
    bad_path = tmp_path / "bad.jsonl"
    write_jsonl(bad_path, bad)
    assert rp.main([str(bad_path)]) == 1
    err = capsys.readouterr().err
    assert "revocations" in err


def test_chrome_trace_export(tmp_path, capsys):
    path, events = _fleet_trace(tmp_path)
    trace = to_chrome_trace(events)
    assert trace["traceEvents"]
    phases = {ev["ph"] for ev in trace["traceEvents"]}
    assert "X" in phases and "M" in phases  # slices + track names
    # every event JSON-serializable (Perfetto loads the file as-is)
    blob = json.dumps(trace)
    assert "fleet" in blob

    from repro.obs.export import main as export_main

    out = tmp_path / "trace.json"
    assert export_main([str(path), "-o", str(out)]) == 0
    assert "CHROME_TRACE" in capsys.readouterr().out
    assert json.loads(out.read_text())["traceEvents"]
