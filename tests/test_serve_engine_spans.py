"""The decode engine's host spans in the JAX profiler's own trace: each
step emits ``serve.batch`` -> ``serve.decode`` -> ``serve.readback`` ->
``serve.commit`` inside its caller's span; each admission emits one
``serve.admit`` carrying the request's ``rid`` around ``serve.prefill``,
``serve.pack`` and ``serve.first_token``; garbage collections show as
``serve.gc``, hooked once per process; and tracing changes no token."""
import gc
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import obs
from repro.config import ShardingLayout, get_arch
from repro.launch.mesh import make_host_mesh
from repro.models import build_model
from repro.serve import DecodeEngine, Request
from repro.serve import engine as engine_mod

STEP_SPANS = ["serve.batch", "serve.decode", "serve.readback", "serve.commit"]
ADMIT_SPANS = ["serve.prefill", "serve.pack", "serve.first_token"]
PROMPT_LENS = (5, 11, 7)


@pytest.fixture(scope="module")
def served():
    cfg = get_arch("qwen3-4b").reduced()
    model = build_model(cfg)
    mesh = make_host_mesh(model_parallel=1)
    params = jax.device_put(model.init(jax.random.key(0)))
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, cfg.vocab_size, n).astype(np.int32) for n in PROMPT_LENS]
    return model, mesh, params, prompts


def _serve(model, mesh, params, prompts):
    """Three requests on two lanes, each step inside the caller's span."""
    eng = DecodeEngine(model, ShardingLayout(), mesh, lanes=2, num_pages=9, max_context=32)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=10 + i, prompt=p, max_new_tokens=4))
    while eng.in_flight:
        with obs.span("caller.step"):
            eng.step(params)
    return {c.rid: c.tokens for c in eng.completions}


def _host_spans(trace_dir):
    """(start ns, end ns, name, args) of every ``serve.``/``caller.`` span."""
    path = sorted(glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("serve.", "caller.")):
                    out.append((e.start_ns, e.start_ns + e.duration_ns, e.name,
                                {k: v for k, v in e.stats}))
    return sorted(out, key=lambda s: (s[0], -s[1]))


def _inside(outer, spans_):
    """Names of the spans nested in ``outer``, in order, collections aside."""
    return [s for s in spans_ if outer[0] <= s[0] and s[1] <= outer[1]
            and s is not outer and s[2] != "serve.gc"]


@pytest.fixture(scope="module")
def traced(served, tmp_path_factory):
    model, mesh, params, prompts = served
    plain = _serve(model, mesh, params, prompts)
    trace_dir = tmp_path_factory.mktemp("engine_trace")
    with jax.profiler.trace(str(trace_dir)):
        tokens = _serve(model, mesh, params, prompts)
        gc.collect()
    return plain, tokens, _host_spans(trace_dir)


def test_each_step_emits_its_spans_in_order_inside_the_callers(traced):
    _, _, found = traced
    steps = [s for s in found if s[2] == "caller.step"]
    assert steps
    for step in steps:
        inner = [s for s in _inside(step, found) if s[2] in STEP_SPANS]
        assert [s[2] for s in inner] == STEP_SPANS
        assert all(a[1] <= b[0] for a, b in zip(inner, inner[1:]))
    batch = [s for s in found if s[2] == "serve.batch"]
    assert len(batch) == len(steps)
    assert all(1 <= s[3]["lanes"] <= 2 for s in batch)


def test_each_admission_spans_prefill_pack_and_first_token(traced):
    _, _, found = traced
    admits = [s for s in found if s[2] == "serve.admit"]
    assert sorted(s[3]["rid"] for s in admits) == [10, 11, 12]
    assert sorted(s[3]["prompt_len"] for s in admits) == sorted(PROMPT_LENS)
    steps = [s for s in found if s[2] == "caller.step"]
    for admit in admits:
        assert [s[2] for s in _inside(admit, found)] == ADMIT_SPANS
        assert any(st[0] <= admit[0] and admit[1] <= st[1] for st in steps)


def test_tracing_changes_no_token(traced):
    plain, tokens, _ = traced
    assert tokens == plain
    assert sorted(tokens) == [10, 11, 12]


def test_collections_are_spanned_and_hooked_once(traced):
    _, _, found = traced
    collections = [s for s in found if s[2] == "serve.gc"]
    assert any(s[3]["generation"] == 2 for s in collections)
    engine_mod._trace_gc()
    engine_mod._trace_gc()
    assert sum(cb is engine_mod._gc_span for cb in gc.callbacks) == 1
