"""Continuous-batching decode engine: admission under block-pool
pressure, lane-isolation (batched ≡ solo greedy streams), shed→resume
token identity, throughput-tracker feeding, and the int8 paged-path
dequant-scoping bugfix pinned bitwise."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ShardingLayout, get_arch
from repro.launch.mesh import make_host_mesh
from repro.models import build_model
from repro.serve import DecodeEngine, Request

PROMPT_LENS = (5, 17, 9, 30)
NEW = 6


@pytest.fixture(scope="module")
def served():
    """One batched run under page pressure, plus everything needed to
    re-serve the same requests solo."""
    cfg = get_arch("qwen3-4b").reduced()
    model = build_model(cfg)
    layout = ShardingLayout()
    mesh = make_host_mesh(model_parallel=1)
    params = jax.device_put(model.init(jax.random.key(0)))
    rng = np.random.RandomState(0)
    prompts = [
        rng.randint(0, cfg.vocab_size, n).astype(np.int32) for n in PROMPT_LENS
    ]
    reqs = [
        Request(rid=i, prompt=p, max_new_tokens=NEW)
        for i, p in enumerate(prompts)
    ]
    # pool holds ~2 requests at a time: admission must stagger
    eng = DecodeEngine(model, layout, mesh, lanes=2, num_pages=7, max_context=48)
    for r in reqs:
        eng.submit(r)
    done = eng.run(params)
    return cfg, model, layout, mesh, params, reqs, eng, done


def test_engine_serves_all_requests_under_page_pressure(served):
    *_, reqs, eng, done = served
    assert sorted(c.rid for c in done) == [r.rid for r in reqs]
    assert all(len(c.tokens) == NEW for c in done)
    assert all(c.reason == "length" for c in done)
    # every reserved page came back to the pool at drain
    assert eng.in_flight == 0
    assert eng.free_pages == 7 - 1  # all but the reserved trash page
    assert eng.measured_tokens_per_sec > 0


def test_engine_batched_matches_solo_streams(served):
    """Continuous batching must not leak state across lanes: each request
    decoded alone produces the same greedy stream as the contended run."""
    cfg, model, layout, mesh, params, reqs, _, done = served
    by_rid = {c.rid: c for c in done}
    for r in reqs[:2]:
        solo = DecodeEngine(
            model, layout, mesh, lanes=1, num_pages=4, max_context=48
        )
        solo.submit(Request(rid=r.rid, prompt=r.prompt, max_new_tokens=NEW))
        (sd,) = solo.run(params)
        assert sd.tokens == by_rid[r.rid].tokens, r.rid


def test_engine_shed_resume_token_identical(served):
    """Evicting mid-stream (spot revocation) and resuming on a fresh
    engine replays to the exact uninterrupted stream — the engine-level
    form of the --plan round-trip guarantee."""
    cfg, model, layout, mesh, params, reqs, _, done = served
    by_rid = {c.rid: c for c in done}
    eng1 = DecodeEngine(model, layout, mesh, lanes=2, num_pages=9, max_context=48)
    for r in reqs[:2]:
        eng1.submit(r)
    for _ in range(3):
        eng1.step(params)
    resumed = eng1.shed()
    assert {q.rid for q in resumed} == {0, 1}
    assert all(len(q.resume_tokens) > 0 for q in resumed)
    assert not eng1.completions
    eng2 = DecodeEngine(model, layout, mesh, lanes=2, num_pages=9, max_context=48)
    for q in resumed:
        eng2.submit(q)
    for c in eng2.run(params):
        assert c.tokens == by_rid[c.rid].tokens, c.rid


def test_engine_scale_down_drain_token_identical(served):
    """The autoscaler's scale-down path: ``drain_replica`` sheds every
    in-flight stream from the retiring engine and resubmits on a
    survivor that is already serving its own traffic — every stream,
    moved or resident, completes token-identically to uninterrupted
    serving. A scale-down is as invisible as a revocation."""
    from repro.serve import drain_replica

    cfg, model, layout, mesh, params, reqs, _, done = served
    by_rid = {c.rid: c for c in done}
    retiring = DecodeEngine(model, layout, mesh, lanes=2, num_pages=9, max_context=48)
    survivor = DecodeEngine(model, layout, mesh, lanes=2, num_pages=9, max_context=48)
    for r in reqs[:2]:
        retiring.submit(r)
    survivor.submit(reqs[2])
    for _ in range(3):
        retiring.step(params)
    n = drain_replica(retiring, survivor)
    assert n == 2
    assert not retiring.completions and retiring.occupancy == 0.0
    for c in survivor.run(params):
        assert c.tokens == by_rid[c.rid].tokens, c.rid
    assert {c.rid for c in survivor.completions} == {0, 1, 2}


def test_engine_occupancy_and_page_pool_under_drain(served):
    """The drain telemetry triple: before a scale-down the retiring engine
    holds lanes and pages, during the drain every shed event carries
    enough to re-prefill the stream elsewhere, and after it both gauges
    read exactly zero — with the recorded gauge series agreeing with the
    engine properties at every sample."""
    from repro.obs import events as E
    from repro.obs.recorder import recording
    from repro.serve import drain_replica

    cfg, model, layout, mesh, params, reqs, *_ = served
    with recording() as rec:
        retiring = DecodeEngine(
            model, layout, mesh, lanes=2, num_pages=9, max_context=48
        )
        survivor = DecodeEngine(
            model, layout, mesh, lanes=2, num_pages=9, max_context=48
        )
        for r in reqs[:2]:
            retiring.submit(r)
        for _ in range(3):
            retiring.step(params)

        # before: both lanes live, pages reserved up front for both streams
        assert retiring.occupancy == 1.0
        assert retiring.page_pool_used_frac > 0.0
        occ_before = retiring.occupancy
        pool_before = retiring.page_pool_used_frac

        moved = drain_replica(retiring, survivor)
        assert moved == 2

        # after: the retiring engine is empty on BOTH axes — every lane
        # free and every reserved page back in the pool
        assert retiring.occupancy == 0.0
        assert retiring.page_pool_used_frac == 0.0

    sheds = [e for e in rec.events if isinstance(e, E.Shed)]
    evicts = [e for e in rec.events if isinstance(e, E.Evict)]
    drains = [e for e in rec.events if isinstance(e, E.Drain)]
    assert len(sheds) == 2 and len(drains) == 1
    assert drains[0].moved_requests == 2
    assert all(e.reason == "shed" for e in evicts)
    # during: each shed event carries what re-prefilling needs — the
    # prompt length and the committed tokens (prompt + resume[:-1] is the
    # re-prefill; resume[-1] rides the next decode step)
    by_rid = {r.rid: r for r in reqs}
    for s in sheds:
        assert s.prompt_tokens == len(by_rid[s.request_id].prompt)
        # prefill's argmax token + one per decode step
        assert s.resume_tokens == 4
        total = s.prompt_tokens + s.resume_tokens + by_rid[s.request_id].max_new_tokens
        assert total <= 48  # re-prefill still fits the survivor's context

    # the gauge series brackets the drain: a sample at admission matching
    # the pre-drain properties, and a final sample at zero/zero
    occ = rec.gauge_series["engine.occupancy"]
    pool = rec.gauge_series["engine.page_pool_used_frac"]
    assert occ[0][1] == 0.5 and occ[-1][1] == 0.0
    # second sample: both streams admitted — matches the pre-drain state
    assert (occ[1][1], pool[1][1]) == (occ_before, pool_before)
    assert pool[-1][1] == 0.0
    assert rec.gauge_values["engine.occupancy"] == 0.0
    assert rec.gauge_values["engine.page_pool_used_frac"] == 0.0


def test_engine_occupancy_tracks_live_lanes(served):
    cfg, model, layout, mesh, params, reqs, *_ = served
    eng = DecodeEngine(model, layout, mesh, lanes=2, num_pages=9, max_context=48)
    assert eng.occupancy == 0.0
    eng.submit(reqs[0])
    eng.step(params)
    assert eng.occupancy == 0.5
    eng.run(params)
    assert eng.occupancy == 0.0


def test_engine_feeds_throughput_tracker(served):
    cfg, model, layout, mesh, params, reqs, *_ = served
    from repro.dist.meshplan import ThroughputTracker

    tracker = ThroughputTracker()
    eng = DecodeEngine(
        model, layout, mesh, lanes=2, num_pages=9, max_context=48,
        tracker=tracker, tracker_key="1x1",
    )
    for r in reqs[:2]:
        eng.submit(r)
    eng.run(params)
    # one observation per decode batch step, real wall-clock rates; the
    # measured steps/sec for this shape anchors fleet rate corrections
    assert tracker._sps.get("1x1", 0.0) > 0.0
    assert eng.measured_tokens_per_sec > 0.0


def test_paged_int8_scoped_dequant_pins_dense_fallback_bitwise():
    """The bugfix: the paged int8 path dequantizes ONLY the gathered
    pages. That scoping must be invisible — byte-identical attention
    output to the dense fallback that dequantizes the entire pool before
    the same gather."""
    import dataclasses

    from repro.models import layers
    from repro.models.common import init_params

    cfg = dataclasses.replace(get_arch("qwen3-4b").reduced(), num_layers=1)
    params = init_params(layers.attention_spec(cfg), jax.random.key(0))
    B, nb, ps = 2, 3, layers.PAGE_SIZE
    P = B * nb + 1
    KVH, hd = cfg.num_kv_heads, cfg.resolved_head_dim

    key = jax.random.key(7)
    kq, ks = layers._quantize_kv(
        jax.random.normal(key, (P, ps, KVH, hd), jnp.bfloat16)
    )
    vq, vs = layers._quantize_kv(
        jax.random.normal(jax.random.fold_in(key, 1), (P, ps, KVH, hd), jnp.bfloat16)
    )
    cache = {"k_pages": kq, "v_pages": vq, "k_scale": ks, "v_scale": vs}
    table = jnp.asarray([[0, 1, 2], [3, 4, -1]], jnp.int32)
    lens = jnp.asarray([40, 21], jnp.int32)
    x = jax.random.normal(jax.random.fold_in(key, 2), (B, 1, cfg.d_model), jnp.bfloat16)

    y_scoped, nc = layers.decode_attention_paged(params, cache, x, lens, table, cfg)
    assert nc["k_pages"].dtype == jnp.int8

    # dense fallback: dequantize the WHOLE pool, then the identical
    # gather + masked attention the shipped path runs
    q, _, _ = layers._project_qkv(params, x, x, cfg)
    q = layers.rope(q, lens[:, None].astype(jnp.float32), cfg.rope_theta)
    full_k = layers._dequantize_kv(nc["k_pages"], nc["k_scale"], x.dtype)
    full_v = layers._dequantize_kv(nc["v_pages"], nc["v_scale"], x.dtype)
    tbl = jnp.maximum(table, 0)
    kg = jnp.take(full_k, tbl, axis=0).reshape(B, nb * ps, KVH, hd)
    vg = jnp.take(full_v, tbl, axis=0).reshape(B, nb * ps, KVH, hd)
    from repro.models import common

    att = layers._paged_attend_gathered(q[:, 0], kg, vg, lens + 1)
    att = att.reshape(B, 1, cfg.num_heads * hd)
    y_full = common.dense(att, params["wo"], cfg.dtype)

    a = np.asarray(y_scoped, np.float32)
    b = np.asarray(y_full, np.float32)
    assert np.array_equal(a, b), np.abs(a - b).max()


def test_paged_int8_pool_refuses_the_kernel():
    """An int8 pool never enters the Pallas kernel, and asking for it is
    an error rather than a silent switch to the gather path."""
    import dataclasses

    from repro.models import layers
    from repro.models.common import init_params

    cfg = dataclasses.replace(get_arch("qwen3-4b").reduced(), num_layers=1)
    params = init_params(layers.attention_spec(cfg), jax.random.key(0))
    P, ps, KVH, hd = 3, layers.PAGE_SIZE, cfg.num_kv_heads, cfg.resolved_head_dim
    kq, ks = layers._quantize_kv(jnp.zeros((P, ps, KVH, hd), jnp.bfloat16))
    cache = {"k_pages": kq, "v_pages": kq, "k_scale": ks, "v_scale": ks}
    x = jnp.zeros((1, 1, cfg.d_model), jnp.bfloat16)
    table, lens = jnp.zeros((1, 2), jnp.int32), jnp.ones((1,), jnp.int32)
    with pytest.raises(NotImplementedError, match="int8"):
        layers.decode_attention_paged(
            params, cache, x, lens, table, cfg, use_kernel=True, interpret=True
        )


@pytest.mark.parametrize("backend,dtype,head_dim,mesh_shape,kernel", [
    ("cpu", jnp.bfloat16, 128, (1, 1), False),    # the CPU gathers
    ("tpu", jnp.int8, 128, (1, 1), False),        # the gather dequantizes int8 pages
    ("tpu", jnp.bfloat16, 128, (2, 1), False),    # a pool on two devices: copied,
    ("tpu", jnp.bfloat16, 128, (1, 2), False),    # or split over the model axis
    ("tpu", jnp.bfloat16, 256, (1, 1), False),    # rows the kernel cannot read
    ("tpu", jnp.bfloat16, 32, (1, 1), False),
    ("tpu", jnp.bfloat16, 128, (1, 1), True),     # a float pool on one TPU device
    ("tpu", jnp.float32, 128, None, True),        # ... or on the default device
])
def test_paged_attention_path_follows_backend_dtype_and_placement(
    backend, dtype, head_dim, mesh_shape, kernel
):
    from repro.models.layers import paged_kernel_fits

    mesh = None if mesh_shape is None else jax.sharding.AbstractMesh(
        mesh_shape, ("data", "model")
    )
    assert paged_kernel_fits(backend, dtype, head_dim, mesh) is kernel


def test_engine_resumes_a_stream_at_the_context_limit(served):
    """A stream whose prompt + max_new_tokens fills the whole context is
    shed mid-way and resumes on an engine of the same context: its resumed
    tokens count toward max_new_tokens, so it needs no more pages than it
    did at first admission, and it completes token-identically."""
    cfg, model, layout, mesh, params, *_ = served
    ctx = 48
    prompt = np.random.RandomState(3).randint(0, cfg.vocab_size, ctx - NEW).astype(np.int32)
    req = Request(rid=9, prompt=prompt, max_new_tokens=NEW)

    def engine():
        return DecodeEngine(model, layout, mesh, lanes=1, num_pages=4, max_context=ctx)

    whole = engine()
    whole.submit(req)
    (ref,) = whole.run(params)
    first = engine()
    first.submit(req)
    for _ in range(3):
        first.step(params)
    second = engine()
    for q in first.shed():
        second.submit(q)
    (done,) = second.run(params)
    assert done.tokens == ref.tokens
