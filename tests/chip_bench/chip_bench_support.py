"""Shared helpers of the chip benchmark's CPU tests.

``reduced_copy`` copies ``benchmarks/chip`` into a temporary directory and
shrinks the copy's configuration and traffic files (widths, depth, lanes,
lengths) so that a cell runs on the CPU in seconds; the harness and the
drivers run unchanged on those files, with the look for a chip skipped.
Its limits are those of the reduced sizes, read on the CPU: the bfloat16
program against the float32 reference gives a ``logit_gap`` up to about
0.02 where the float8 control gives 0.23 to 0.50; the training numbers
read up to 6e-4, 0.11 and 0.004 where the control and the faults read
4.7e-3 (loss), 0.17 to 4.2 (gradient), 0.16 to 1 (change).

``spec`` is what the harness reads: ``BENCHMARK.json`` with the cells
that ``pending.json`` holds but ``BENCHMARK.json`` does not list yet, so
that every driver is rehearsed.
"""
from __future__ import annotations

import json
import pathlib
import shutil
import sys

REPO = pathlib.Path(__file__).resolve().parents[2]
BENCH = REPO / "benchmarks" / "chip"
if str(BENCH) not in sys.path:
    sys.path.append(str(BENCH))

import bench  # noqa: E402

REDUCED_CONFIGS = {
    "qwen3-4b": dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
                     num_key_value_heads=2, head_dim=16, intermediate_size=128, vocab_size=256),
    "xlstm-350m": dict(embedding_dim=64, num_blocks=4, num_heads=4, vocab_size=256,
                       slstm_every=2, mlstm_chunk=8),
}
REDUCED_TRAFFIC = {
    "chat": dict(rate_per_s=16, lanes=4, max_context=96, pool_pages=25,
                 prompt={"dist": "lognormal", "median": 12, "sigma": 0.8, "ladder": [8, 32]},
                 output={"dist": "lognormal", "median": 8, "sigma": 0.7, "min": 2, "max": 40}),
    "decode": dict(lanes=4, clients=4, per_client=2, max_context=96, pool_pages=25,
                   prompt={"dist": "fixed", "value": 16},
                   output={"dist": "uniform", "min": 20, "max": 40}),
    "train": dict(seq_len=32, batch=4, segment_steps=2),
    "revoke-4to2": dict(seq_len=32, batch=4, segment_steps=2, revoke_every=2),
}


REDUCED_LIMITS = {
    "qwen3-4b.chat": {"logit_gap": 0.1},
    "qwen3-4b.decode": {"logit_gap": 0.1},
    "xlstm-350m.train": {"loss_gap": 2e-3, "grad_gap": 0.2, "update_gap": 0.03},
    "xlstm-350m.revoke-4to2": {"loss_gap": 2e-3, "grad_gap": 0.2, "update_gap": 0.03},
}


def spec() -> dict:
    """``BENCHMARK.json`` with the cells of ``benchmarks/chip/pending.json``,
    as the harness reads it."""
    return bench.load_spec(REPO)


def reduced_copy(tmp: pathlib.Path) -> pathlib.Path:
    base = pathlib.Path(tmp) / "chip"
    shutil.copytree(BENCH, base, ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    for kind, table in (("configs", REDUCED_CONFIGS), ("traffic", REDUCED_TRAFFIC)):
        for name, changes in table.items():
            path = base / kind / f"{name}.json"
            data = json.loads(path.read_text())
            data.update(changes)
            path.write_text(json.dumps(data))
    for cell, limits in REDUCED_LIMITS.items():
        (base / "limits" / f"{cell}.json").write_text(json.dumps(limits))
    return base


def run(base: pathlib.Path, cell: str, *, seed: int = 2**33 + 5, seconds: float = 0.5,
        trace: bool = False, spec_: dict = None, control: bool = False) -> dict:
    """One run of ``cell`` on the CPU from the files under ``base``."""
    return bench.run_cell(cell, seed, seconds, trace, chip=False,
                          spec=spec() if spec_ is None else spec_, base=base,
                          control=control, keep_counts=control)
