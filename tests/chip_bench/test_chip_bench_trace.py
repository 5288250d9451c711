"""The trace reduction: busy time as a union, idle gaps labelled by the
harness's host spans, program time, and the breakdown; on hand-made
intervals and on a small trace recorded on a TPU v5e."""
import pathlib

import pytest

import chip_bench_support as sup

trace = sup.bench.load_module(sup.BENCH / "trace.py")
MS = 1_000_000  # ns


def _made():
    ops = {0: [(0, 2 * MS, "%a = f32[] fusion(f32[] %x)"), (2 * MS, 3 * MS, "%b = f32[] copy(f32[] %y)"),
           (5 * MS, 6 * MS, "%a = f32[] fusion(f32[] %x)")]}
    modules = {0: [(0, 3 * MS, "jit_paged_decode_step(1)"), (5 * MS, 6 * MS, "jit_prefill_step(2)")]}
    host = [(2 * MS, 4 * MS, "serve.step"), (3.5 * MS, 5.5 * MS, "serve.idle")]
    return trace.Trace(window=(0, 10 * MS), ops=ops, modules=modules, host=host, chips=[0])


def test_busy_is_the_union_of_operations():
    t = _made()
    assert t.busy_intervals(0) == [(0, 3 * MS), (5 * MS, 6 * MS)]
    assert t.busy_s == pytest.approx(4e-3)
    assert t.window_s == pytest.approx(10e-3)
    assert t.idle_share == pytest.approx(0.6)


def test_idle_gaps_take_the_innermost_host_span():
    gaps = _made().idle_gaps()
    assert [(a / MS, b / MS, n) for a, b, n in gaps] == [(3, 5, "serve.step"), (6, 10, "host")]
    t = _made()
    t.host.append((3.8 * MS, 4.2 * MS, "serve.idle"))
    assert t.idle_gaps()[0][2] == "serve.idle"


def test_programs_and_breakdown():
    t = _made()
    assert t.program_seconds("prefill") == pytest.approx(1e-3)
    runs = t.program_runs("decode")
    assert len(runs) == 1
    assert t.idle_between(3 * MS, 5 * MS) == pytest.approx(2e-3)
    bd = t.breakdown()
    assert bd["device_ops"] == [["jit_paged_decode_step/%a fusion", pytest.approx(2e-3)],
                                ["jit_paged_decode_step/%b copy", pytest.approx(1e-3)],
                                ["jit_prefill_step/%a fusion", pytest.approx(1e-3)]]
    assert dict(bd["idle_gaps"]) == pytest.approx({"host": 4e-3, "serve.step": 2e-3})


def test_small_trace_recorded_on_a_v5e():
    """Three runs each of two small programs, with ``serve.step`` and
    ``serve.idle`` host spans inside ``bench.window`` (testdata/)."""
    t = trace.reduce_trace(sup.BENCH / "testdata", 1)
    assert t.chips == [0]
    assert t.window_s == pytest.approx(0.013093889)
    assert 0 < t.busy_s < t.window_s
    assert [trace.program_label(m[2]) for m in t.modules[0]] == ["jit__lambda"] * 5
    assert {n for _, _, n in t.idle_gaps()} <= {"serve.step", "serve.idle", "host"}
    bd = t.breakdown()
    ops = dict(bd["device_ops"])
    assert "jit__lambda/%fusion fusion" in ops
    assert sum(ops.values()) == pytest.approx(t.busy_s, rel=0.05)
    assert sum(v for _, v in bd["idle_gaps"]) == pytest.approx(t.window_s - t.busy_s, rel=1e-6)


def test_operation_labels():
    line = ("%while.7 = (s32[]{:T(128)}, bf16[16,1,2560]{2,0,1:T(8,128)(2,1)S(1)}) "
            "while((s32[]{:T(128)}) %tuple.72), condition=%c, body=%b")
    assert trace.op_label(line) == "%while.7 while"
    assert trace.program_label("jit_paged_decode_step(1234)") == "jit_paged_decode_step"


def test_nested_operations_count_once():
    ops = [(0, 10, "%while.1 = (s32[]) while(s32[] %t)"), (1, 4, "%a = f32[] fusion(f32[] %x)"),
           (5, 9, "%b = f32[] copy(f32[] %y)")]
    own = trace.self_times(ops, [(0, 10, "jit_step(9)")])
    assert own == pytest.approx({"jit_step/%while.1 while": 3e-9, "jit_step/%a fusion": 3e-9,
                                 "jit_step/%b copy": 4e-9})


@pytest.mark.parametrize("seed", [1, 2])
def test_idle_time_and_gap_labels_match_a_direct_count(seed):
    """On many overlapping operations and nested spans, the indexed idle time
    and the sweep that labels gaps agree with counting each directly."""
    import numpy as np

    rng = np.random.default_rng(seed)
    starts = np.sort(rng.uniform(0, 1000 * MS, 400))
    ops = {0: [(float(s), float(s + rng.uniform(0.1, 6) * MS), "%a = f32[] fusion(f32[] %x)")
               for s in starts]}
    host = []
    for s in np.sort(rng.uniform(0, 1000 * MS, 60)):
        host.append((float(s), float(s + rng.uniform(1, 40) * MS), "serve.step"))
        host.append((float(s + MS), float(s + 2 * MS), "serve.idle"))
    t = trace.Trace(window=(0, 1000 * MS), ops=ops, modules={0: []}, host=host, chips=[0])
    busy = t.busy_intervals(0)
    for a, b in rng.uniform(0, 1000 * MS, (50, 2)):
        a, b = min(a, b), max(a, b)
        direct = sum(max(0.0, min(e, b) - max(s, a)) for s, e in busy)
        assert t.idle_between(a, b) == pytest.approx((b - a - direct) * 1e-9, abs=1e-12)
    for a, b, label in t.idle_gaps():
        mid = 0.5 * (a + b)
        covering = [sp for sp in host if sp[0] <= mid <= sp[1]]
        want = min(covering, key=lambda sp: sp[1] - sp[0])[2] if covering else "host"
        assert label == want


def test_paged_attention_roofline_reads_the_kernel_inside_the_decode_step():
    """Two decode steps, each a layer loop that runs the kernel twice (1 ms
    each) beside a fusion; a ``paged_attention`` call in another program
    does not count. The least time is the live keys and values over the
    HBM bandwidth (the FLOPs take 1/60 of it)."""
    import json
    import types

    reader = sup.bench.load_module(sup.BENCH / "metrics" / "paged_attention_roofline.py")
    kernel = "%paged_attention.11 = bf16[16,32,128]{2,1,0} custom-call(bf16[16,32,128] %q)"
    ops, modules = [], []
    for k in range(2):
        t = k * 10 * MS
        modules.append((t, t + 5 * MS, "jit_paged_decode_step(1)"))
        ops += [(t, t + 5 * MS, "%while.3 = (s32[]) while(s32[] %t)"),
                (t + 0.5 * MS, t + 1.5 * MS, kernel),
                (t + 1.5 * MS, t + 2 * MS, "%f = f32[] fusion()"),
                (t + 2.5 * MS, t + 3.5 * MS, kernel)]
    modules.append((30 * MS, 31 * MS, "jit_prefill(2)"))
    ops.append((30 * MS, 31 * MS, kernel))
    t = trace.Trace(window=(0, 40 * MS), ops={0: ops}, modules={0: modules}, host=[], chips=[0])
    assert t.op_seconds("paged_decode_step", "paged_attention.*custom-call") == pytest.approx(4e-3)
    conf = json.loads((sup.BENCH / "configs" / "qwen3-4b.json").read_text())
    cell = types.SimpleNamespace(config=conf, base=sup.BENCH)
    counts = {"steps": [(16, 6800), (16, 7000)], "device_kind": "TPU v5 lite"}
    kv = 2 * 36 * 8 * 128 * 2
    want = 100.0 * kv * (6800 + 7000) / 819e9 / 4e-3
    assert reader.read(t, counts, cell) == pytest.approx(want)

    gather = trace.Trace(window=(0, 40 * MS), ops={0: ops[:1] + ops[2:3]},
                         modules={0: modules[:1]}, host=[], chips=[0])
    assert reader.read(gather, counts, cell) is None
