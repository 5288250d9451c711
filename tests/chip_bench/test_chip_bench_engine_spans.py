"""The readers of the engine's own host spans, on a hand-made trace: two
pairs of decode steps, one idle gap labelled by an engine span and one by
none, two admissions; and ``None`` from each where the spans are absent,
as in a trace of a program without them."""
import pytest

import chip_bench_support as sup

trace = sup.bench.load_module(sup.BENCH / "trace.py")
MS = 1_000_000  # ns
READERS = ("engine.gap_in_engine_ms", "engine.batch_ms", "engine.commit_ms",
           "engine.admit_idle_ms")


def _reader(name):
    return sup.bench.load_module(sup.BENCH / "metrics" / f"{name}.py")


def _made(host=None, chips=(0,)):
    """Decode steps at 0-10, 15-25 and 30-40 ms, an argmax after the first
    two, a prefill at 42-45 ms. Between the first pair the chip idles
    11-15 ms, its middle inside ``serve.batch``; between the second 26-30
    ms, its middle under no span."""
    ops = [(0, 10 * MS, "%a = f32[] fusion(f32[] %x)"), (10 * MS, 11 * MS, "%m = s32[] reduce()"),
           (15 * MS, 25 * MS, "%a = f32[] fusion(f32[] %x)"), (25 * MS, 26 * MS, "%m = s32[] reduce()"),
           (30 * MS, 40 * MS, "%a = f32[] fusion(f32[] %x)"), (42 * MS, 45 * MS, "%p = f32[] fusion()")]
    modules = [(0, 10 * MS, "jit_paged_decode_step(1)"), (10 * MS, 11 * MS, "jit_argmax(2)"),
               (15 * MS, 25 * MS, "jit_paged_decode_step(1)"), (25 * MS, 26 * MS, "jit_argmax(2)"),
               (30 * MS, 40 * MS, "jit_paged_decode_step(1)"), (42 * MS, 45 * MS, "jit_prefill(3)")]
    if host is None:
        host = [(9 * MS, 11.5 * MS, "serve.readback"), (11.5 * MS, 12.5 * MS, "serve.commit"),
                (13 * MS, 14.5 * MS, "serve.batch"), (14.5 * MS, 15.5 * MS, "serve.decode"),
                (24 * MS, 26.2 * MS, "serve.readback"), (26.2 * MS, 27 * MS, "serve.commit"),
                (29 * MS, 29.5 * MS, "serve.batch"), (29.5 * MS, 30.5 * MS, "serve.decode"),
                (41 * MS, 47 * MS, "serve.admit"), (41.5 * MS, 45.5 * MS, "serve.prefill"),
                (50 * MS, 52 * MS, "serve.admit")]
    return trace.Trace(window=(0, 60 * MS), ops={c: list(ops) for c in chips},
                       modules={c: list(modules) for c in chips}, host=host, chips=list(chips))


def test_gap_in_engine_counts_the_gaps_an_engine_span_labels():
    t = _made()
    assert [g[2] for g in t.idle_gaps()[:2]] == ["serve.batch", "host"]
    value = _reader("engine.gap_in_engine_ms").read(t, {}, None)
    assert value == pytest.approx(2.0)             # 4 ms in the first pair, 0 in the second
    assert value <= _reader("engine.host_gap_ms").read(t, {}, None) == pytest.approx(4.0)


def test_gap_in_engine_counts_a_collection_as_the_engines():
    t = _made()
    t.host.append((27.5 * MS, 28.5 * MS, "serve.gc"))
    assert _reader("engine.gap_in_engine_ms").read(t, {}, None) == pytest.approx(4.0)


def test_gap_in_engine_counts_a_span_it_does_not_list():
    """A serve.* span the engine might add later counts as the engine's; the
    harness's serve.step around the call does not."""
    t = _made()
    t.host[2] = (13 * MS, 14.5 * MS, "serve.x")
    t.host.append((27.5 * MS, 28.5 * MS, "serve.step"))
    assert [g[2] for g in t.idle_gaps()[:2]] == ["serve.x", "serve.step"]
    assert _reader("engine.gap_in_engine_ms").read(t, {}, None) == pytest.approx(2.0)


def test_span_means_and_idle_inside_admissions():
    t = _made()
    assert _reader("engine.batch_ms").read(t, {}, None) == pytest.approx(1.0)
    assert _reader("engine.commit_ms").read(t, {}, None) == pytest.approx(0.9)
    # 6 ms with 3 of prefill, then 2 ms with nothing on the chip
    assert _reader("engine.admit_idle_ms").read(t, {}, None) == pytest.approx(2.5)


@pytest.mark.parametrize("name", READERS)
def test_reader_is_silent_without_the_engines_spans(name):
    harness_only = [(9 * MS, 16 * MS, "serve.step"), (24 * MS, 31 * MS, "serve.step"),
                    (47 * MS, 50 * MS, "serve.idle")]
    assert _reader(name).read(_made(host=harness_only), {}, None) is None
    assert _reader(name).read(_made(host=[]), {}, None) is None


@pytest.mark.parametrize("name", ["engine.gap_in_engine_ms", "engine.admit_idle_ms"])
def test_device_readers_are_silent_without_a_chip(name):
    assert _reader(name).read(_made(chips=()), {}, None) is None


def test_every_listed_metric_has_a_reader():
    for m in sup.spec()["per_layer"]:
        assert (sup.BENCH / "metrics" / f"{m['name']}.py").is_file(), m["name"]
