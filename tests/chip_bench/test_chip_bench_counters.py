"""FLOP and byte counters against sums worked out by hand from the
published shapes, and the table of peaks."""
import json

import pytest

import chip_bench_support as sup
import counters


def _conf(name):
    return json.loads((sup.BENCH / "configs" / f"{name}.json").read_text())


def test_qwen3_4b_matmul_weights_by_hand():
    d, qd, kd, f, L, V = 2560, 32 * 128, 8 * 128, 9728, 36, 151936
    per_layer = d * qd + 2 * d * kd + qd * d + 3 * d * f
    assert counters.matmul_params(_conf("qwen3-4b")) == L * per_layer + d * V == 4_022_272_000


def test_qwen3_4b_weight_and_kv_bytes_by_hand():
    conf = _conf("qwen3-4b")
    norms = 36 * (2 * 2560 + 2 * 128) + 2560
    assert counters.weight_bytes(conf, 2) == 2 * (4_022_272_000 + norms)
    assert counters.kv_bytes_per_token(conf) == 2 * 36 * 8 * 128 * 2 == 147_456


def test_decode_step_needs_weights_and_live_kv():
    conf = _conf("qwen3-4b")
    need = counters.decode_step(conf, decoded=16, context=16 * 1000)
    kv = 147_456
    assert need["bytes"] == counters.weight_bytes(conf, 2) + kv * 16_000 + kv * 16
    attn = 4 * 36 * 32 * 128 * 16_000
    assert need["flops"] == 2 * 4_022_272_000 * 16 + attn


def test_prefill_counts_the_causal_half_and_one_logit_row():
    conf = _conf("qwen3-4b")
    d, V, P = 2560, 151936, 512
    body = 4_022_272_000 - d * V
    attn = 4 * 36 * 32 * 128 * P * (P + 1) // 2
    assert counters.prefill_flops(conf, P) == 2 * body * P + 2 * d * V + attn


def test_xlstm_train_flops_per_token_by_hand():
    conf = _conf("xlstm-350m")
    d, inner, H, V = 1024, 2048, 4, 50304
    mlstm = d * 2 * inner + 3 * inner * inner + inner * 2 * H + inner * d
    slstm = 2 * d * 4 * d + d * 2 * d + d * d
    matrices = 20 * mlstm + 4 * slstm + d * V
    memory = 4 * H * (inner // H) ** 2 * 20
    assert counters.matmul_params(conf) == matrices
    assert counters.train_flops_per_token(conf) == 3.0 * (2 * matrices + memory)


def test_peaks_of_the_v5e_and_an_unknown_chip():
    pk = counters.peaks("TPU v5 lite")
    assert pk["bf16_flop_per_s"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    assert pk["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        counters.peaks("TPU v9 imaginary")
