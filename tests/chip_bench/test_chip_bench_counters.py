"""FLOP and byte counters against sums worked out by hand from the
published shapes, routed experts at their active share, and the table of
peaks."""
import json
import textwrap

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


TOY_MOE = textwrap.dedent('''
    """A family that only describes: attention and a router in every layer,
    and this chip's experts, a token using the published share of them."""


    def describe(c):
        d, L, V, hd = c["hidden_size"], c["num_hidden_layers"], c["vocab_size"], c["head_dim"]
        qd, kd = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
        E, f = c["experts_held"], c["moe_intermediate_size"]
        share = c["num_experts_per_tok"] / c["num_experts"]
        return {
            "embed": ((V, d), "embed", 1.0),
            "blocks": {
                "ln1": {"scale": ((L, d), "norm", 1.0)},
                "attn": {"wq": ((L, d, qd), "matrix", 1.0), "wk": ((L, d, kd), "matrix", 1.0),
                         "wv": ((L, d, kd), "matrix", 1.0), "wo": ((L, qd, d), "matrix", 1.0)},
                "router": ((L, d, c["num_experts"]), "matrix", 1.0),
                "experts": {"wi_gate": ((L, E, d, f), "matrix", 1.0, share),
                            "wi_up": ((L, E, d, f), "matrix", 1.0, share),
                            "wo": ((L, E, f, d), "matrix", 1.0, share)},
            },
            "lm_head": ((d, V), "matrix", 1.0),
        }
''')


def test_routed_experts_count_at_their_active_share(tmp_path):
    """2 layers, hidden 64, 8 experts held of 32 published, 4 per token,
    expert width 48: a token uses 4/32 of the held experts' matrices."""
    (tmp_path / "reference").mkdir()
    (tmp_path / "reference" / "toymoe.py").write_text(TOY_MOE)
    conf = {"name": "toy-moe", "reference": "toymoe", "hidden_size": 64,
            "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 16, "vocab_size": 256, "num_experts": 32, "experts_held": 8,
            "num_experts_per_tok": 4, "moe_intermediate_size": 48,
            "tie_word_embeddings": False}
    d, L, V, E, f = 64, 2, 256, 8, 48
    dense = L * (d * 64 + 2 * d * 32 + 64 * d + d * 32) + d * V
    experts = L * E * 3 * d * f
    active = dense + experts * 4 / 32
    assert counters.matmul_params(conf, tmp_path) == active == 45_056 + 18_432
    need = counters.decode_step(conf, decoded=16, context=16 * 100, base=tmp_path)
    assert need["flops"] == 2 * active * 16 + 4 * L * 4 * 16 * 1600
    assert counters.prefill_flops(conf, 40, tmp_path) == (
        2 * (active - d * V) * 40 + 2 * d * V + 4 * L * 4 * 16 * 40 * 41 // 2)
    every = dense + experts + V * d + L * d
    assert counters.weight_bytes(conf, 2, tmp_path) == 2 * every
    assert need["bytes"] == 2 * every + 2 * L * 2 * 16 * 2 * (1600 + 16)


def test_peaks_of_the_v5e_and_an_unknown_chip():
    pk = counters.peaks("TPU v5 lite")
    assert pk["bf16_flop_per_s"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    assert pk["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        counters.peaks("TPU v9 imaginary")
