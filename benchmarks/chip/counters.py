"""Operations and bytes a step needs, computed from shapes.

Model FLOPs count the multiply-adds the algorithm requires (two operations
each): every weight matrix once per token (the embedding lookup is free,
the LM head is not), and attention or recurrent state work per token.
Recomputation (remat) and masked-out work are not counted. Bytes are the
least a decode step must move: every weight once, the live KV it reads,
the KV it writes.
"""
from __future__ import annotations

import json
import pathlib
from typing import Any, Dict

import weights

HERE = pathlib.Path(__file__).resolve().parent


def peaks(device_kind: str) -> Dict[str, Any]:
    """The chip's published peaks; an unknown chip is an error."""
    with open(HERE / "peaks.json") as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table[device_kind]


def _size(shape) -> int:
    n = 1
    for s in shape:
        n *= s
    return n


def matmul_params(conf: Dict[str, Any]) -> int:
    """Weights that take part in a matmul for every token: every matrix,
    and the embedding where it is also the (tied) LM head."""
    n = sum(_size(shape) for shape, kind, _ in weights.leaves(weights.describe(conf))
            if kind == "matrix")
    if conf.get("tie_word_embeddings"):
        n += conf["hidden_size"] * conf["vocab_size"]
    return n


def weight_bytes(conf: Dict[str, Any], itemsize: int) -> int:
    return itemsize * sum(_size(shape) for shape, _, _ in weights.leaves(weights.describe(conf)))


def kv_bytes_per_token(conf: Dict[str, Any], itemsize: int = 2) -> int:
    """Key and value of one position in every layer."""
    return (2 * conf["num_hidden_layers"] * conf["num_key_value_heads"] * conf["head_dim"]
            * itemsize)


def attention_flops(conf: Dict[str, Any], context: int) -> int:
    """Scores and weighted values of one query over ``context`` positions."""
    return (4 * conf["num_hidden_layers"] * conf["num_attention_heads"] * conf["head_dim"]
            * context)


def decode_step(conf: Dict[str, Any], decoded: int, context: int) -> Dict[str, float]:
    """One paged decode step of ``decoded`` live lanes that attend over
    ``context`` positions in all: the least FLOPs and bytes it needs."""
    flops = 2 * matmul_params(conf) * decoded + attention_flops(conf, context)
    moved = (weight_bytes(conf, 2) + kv_bytes_per_token(conf) * context
             + kv_bytes_per_token(conf) * decoded)
    return {"flops": float(flops), "bytes": float(moved)}


def prefill_flops(conf: Dict[str, Any], prompt: int) -> float:
    """A causal prefill of ``prompt`` tokens, logits at its last position."""
    d, V = conf["hidden_size"], conf["vocab_size"]
    body = matmul_params(conf) - d * V
    causal = attention_flops(conf, 1) * prompt * (prompt + 1) // 2
    return float(2 * body * prompt + 2 * d * V + causal)


def train_flops_per_token(conf: Dict[str, Any]) -> float:
    """Forward and backward (three times the forward) per trained token:
    every matrix, and the mLSTM's matrix-memory update and readout
    (4 * heads * head_dim**2 per token and mLSTM block)."""
    d, H, per = conf["embedding_dim"], conf["num_heads"], conf["slstm_every"]
    inner = conf["mlstm_proj_factor"] * d
    hd = inner // H
    n_mlstm = conf["num_blocks"] // per * (per - 1)
    memory = 4 * H * hd * hd * n_mlstm
    return 3.0 * (2 * matmul_params(conf) + memory)
