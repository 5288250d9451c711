"""Operations and bytes a step needs, computed from shapes.

Model FLOPs count the multiply-adds the algorithm requires (two operations
each): each weight matrix per token at the share of it one token uses (1
for a dense matrix, experts per token over the published experts for a
routed expert's; ``weights.share``), the embedding lookup free and the LM
head not, and attention or recurrent state work per token. Recomputation
(remat) and masked-out work are not counted. Bytes are the least a decode
step must move: every weight once, the live KV it reads, the KV it writes.

Every count reads the parameter tree from the configuration's family file
(``reference/<family>.py`` under ``base``).
"""
from __future__ import annotations

import json
import pathlib
from typing import Any, Dict

import bench
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


def matmul_params(conf: Dict[str, Any], base: pathlib.Path = HERE):
    """Weights that take part in a matmul for one token: every matrix at
    the share of it one token uses, and the embedding where it is also the
    (tied) LM head. A whole number where every share is 1."""
    n = sum(_size(leaf[0]) * weights.share(leaf)
            for leaf in weights.leaves(weights.describe(conf, base)) if leaf[1] == "matrix")
    if conf.get("tie_word_embeddings"):
        n += conf["hidden_size"] * conf["vocab_size"]
    return n


def weight_bytes(conf: Dict[str, Any], itemsize: int, base: pathlib.Path = HERE) -> int:
    """Every weight once, whatever share of it a token uses. For routed
    experts that is the least a decode step moves only where every held
    expert gets a token in the step, so a MoE cell is sized for that: its
    lanes times experts per token over the published experts, the tokens
    each expert sees a step, well above one."""
    return itemsize * sum(_size(leaf[0]) for leaf in weights.leaves(weights.describe(conf, base)))


def kv_bytes_per_token(conf: Dict[str, Any], itemsize: int = 2) -> int:
    """Key and value of one position in every layer."""
    return (2 * conf["num_hidden_layers"] * conf["num_key_value_heads"] * conf["head_dim"]
            * itemsize)


def attention_flops(conf: Dict[str, Any], context: int) -> int:
    """Scores and weighted values of one query over ``context`` positions."""
    return (4 * conf["num_hidden_layers"] * conf["num_attention_heads"] * conf["head_dim"]
            * context)


def decode_step(conf: Dict[str, Any], decoded: int, context: int,
                base: pathlib.Path = HERE) -> Dict[str, float]:
    """One paged decode step of ``decoded`` live lanes that attend over
    ``context`` positions in all: the least FLOPs and bytes it needs (the
    bytes with every weight once, as ``weight_bytes`` says)."""
    flops = 2 * matmul_params(conf, base) * decoded + attention_flops(conf, context)
    moved = (weight_bytes(conf, 2, base) + kv_bytes_per_token(conf) * context
             + kv_bytes_per_token(conf) * decoded)
    return {"flops": float(flops), "bytes": float(moved)}


def prefill_flops(conf: Dict[str, Any], prompt: int, base: pathlib.Path = HERE) -> float:
    """A causal prefill of ``prompt`` tokens, logits at its last position."""
    d, V = conf["hidden_size"], conf["vocab_size"]
    body = matmul_params(conf, base) - d * V
    causal = attention_flops(conf, 1) * prompt * (prompt + 1) // 2
    return float(2 * body * prompt + 2 * d * V + causal)


def train_flops_per_token(conf: Dict[str, Any], base: pathlib.Path = HERE) -> float:
    """Forward and backward (three times the forward) per trained token:
    every matrix, and the family's work beyond its matrices
    (``flops_per_token_beyond_matrices``, such as the mLSTM's memory)."""
    beyond = bench.family(conf, base).flops_per_token_beyond_matrices(conf)
    return 3.0 * (2 * matmul_params(conf, base) + beyond)
