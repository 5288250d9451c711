"""The program's configuration, built from a configuration file.

The file holds the published sizes under the source's own keys; the
program's ``ModelConfig`` is the registered architecture (``program_arch``)
with those sizes and ``program_overrides`` put in. Each size of the file
is checked against the config the program will run, so a file and the
program cannot drift apart unseen.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

# source key -> ModelConfig field, per reference family
QWEN3_KEYS = {
    "hidden_size": "d_model", "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim", "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}
XLSTM_KEYS = {
    "embedding_dim": "d_model", "num_blocks": "num_layers", "num_heads": "num_heads",
    "vocab_size": "vocab_size", "slstm_every": "slstm_every", "norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}
KEYS = {"qwen3": QWEN3_KEYS, "xlstm": XLSTM_KEYS}


def model_config(conf: Dict[str, Any]):
    """The program's ModelConfig for a configuration file."""
    from repro.config import get_arch

    keys = KEYS[conf["reference"]]
    base = get_arch(conf["program_arch"])
    kw = {field: conf[k] for k, field in keys.items() if k in conf}
    if conf["reference"] == "xlstm":
        kw["num_kv_heads"] = conf["num_heads"]
        kw["ssm"] = dataclasses.replace(base.ssm, chunk=conf["mlstm_chunk"])
        kw["param_dtype"] = conf["param_dtype"]
        kw["dtype"] = conf["compute_dtype"]
    else:
        kw["dtype"] = conf["serve_dtype"]
    kw.update(conf.get("program_overrides", {}))
    cfg = dataclasses.replace(base, **kw)
    for k, field in keys.items():
        if k in conf and getattr(cfg, field) != conf[k]:
            raise SystemExit(f"bench: {conf['name']}: {k}={conf[k]} but the program "
                             f"runs {field}={getattr(cfg, field)}")
    return cfg
