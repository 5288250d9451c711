"""The one traffic generator: a traffic file's parameters -> the run's inputs.

Every size is drawn at the quantiles ``(i + 0.5) / n`` of the stated
distribution and put in an order drawn from the seed. So every seed offers
the same set of prompt lengths, output lengths and arrival gaps, in another
order, with other token ids: the seed changes which requests come when, not
how much work the window holds.

With ``"strata": k`` in the traffic file the order is stratified: the sorted
values are cut into ``k`` strata, and each run of ``k`` consecutive requests
takes one value from each stratum, in an order drawn from the seed. Every
stretch of the window then carries about the same load (the same share of
long outputs, long prompts and short gaps), so a seed cannot pile the long
requests or the bursts into one part of it; within a run of ``k`` the order
is still random.

Distributions (``{"dist": ...}``):

* ``fixed``: ``value``;
* ``uniform``: integers ``min`` .. ``max``;
* ``lognormal``: ``median``, ``sigma``, clipped to ``min`` .. ``max``, and
  binned to the nearest (in log) entry of ``ladder`` where one is given;
* ``exponential``: ``mean`` (arrival gaps).
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np


def quantile_draws(dist: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` values of ``dist`` at evenly spaced quantiles, sorted."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "fixed":
        return np.full(n, dist["value"], dtype=np.float64)
    if kind == "uniform":
        lo, hi = dist["min"], dist["max"]
        return np.floor(lo + u * (hi - lo + 1))
    if kind == "exponential":
        return -np.log1p(-u) * dist["mean"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        v = dist["median"] * np.exp(dist["sigma"] * z)
        v = np.clip(v, dist.get("min", 1), dist.get("max", math.inf))
        ladder = dist.get("ladder")
        if ladder:
            lad = np.log(np.asarray(ladder, np.float64))
            v = np.asarray(ladder)[np.abs(np.log(v)[:, None] - lad[None, :]).argmin(axis=1)]
        return np.round(v)
    raise ValueError(f"unknown distribution {kind!r}")


def drawn(dist: Dict[str, Any], n: int, rng: np.random.Generator,
          strata: int = 0) -> np.ndarray:
    """``quantile_draws`` in an order drawn from ``rng``: a permutation, or
    with ``strata`` > 1 a stratified one (see the module's docstring)."""
    values = quantile_draws(dist, n)
    if strata <= 1:
        return rng.permutation(values)
    stratum = (np.arange(n) * strata) // n
    run = np.empty(n)
    for j in range(strata):
        members = np.flatnonzero(stratum == j)
        run[members] = rng.permutation(len(members))
    return values[np.lexsort((rng.random(n), run))]


@dataclasses.dataclass
class Request:
    rid: int
    due_s: float          # seconds after the window opens (open loop); 0 closed
    client: int           # closed loop: which client sends it; -1 open
    prompt: np.ndarray    # int32 token ids
    max_new_tokens: int


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(int(seed), spawn_key=(stream,)))


def requests(traffic: Dict[str, Any], seed: int, seconds: float, vocab: int) -> List[Request]:
    """The window's requests. Open loop: ``rate_per_s * seconds`` requests at
    Poisson arrival gaps. Closed loop: ``per_client`` requests for each of
    ``clients`` clients, sent one after another."""
    rng = _rng(seed, 1)
    k = int(traffic.get("strata", 0))
    if traffic["loop"] == "open":
        n = max(1, int(round(traffic["rate_per_s"] * seconds)))
        gaps = drawn({"dist": "exponential", "mean": 1.0 / traffic["rate_per_s"]}, n, rng, k)
        due = np.cumsum(gaps) - gaps[0]
        clients = [-1] * n
    else:
        per, nc = traffic["per_client"], traffic["clients"]
        n = per * nc
        due = np.zeros(n)
        clients = [i % nc for i in range(n)]
    prompt_lens = drawn(traffic["prompt"], n, rng, k).astype(int)
    outputs = drawn(traffic["output"], n, rng, k).astype(int)
    tok_rng = _rng(seed, 2)
    return [
        Request(rid=i, due_s=float(due[i]), client=clients[i],
                prompt=tok_rng.integers(0, vocab, int(prompt_lens[i]), dtype=np.int32),
                max_new_tokens=int(outputs[i]))
        for i in range(n)
    ]


def warmup_requests(reqs: List[Request], vocab: int, base_rid: int) -> List[Request]:
    """One short request for each prompt length the window sends: every
    prefill and page-pack shape it will use, and the decode step."""
    lengths = sorted({len(r.prompt) for r in reqs})
    rng = _rng(0, 3)
    return [Request(rid=base_rid + i, due_s=0.0, client=-1,
                    prompt=rng.integers(0, vocab, n, dtype=np.int32), max_new_tokens=2)
            for i, n in enumerate(lengths)]


class TrainRows:
    """A training job's batches: ``batch(step)`` is a pure function of the
    seed and the step, so a resumed or resharded job sees the same rows.
    Tokens are uniform over the vocabulary, every row different; labels
    are the next token (the last wraps to the first)."""

    def __init__(self, seed: int, vocab: int, seq_len: int, batch: int):
        self.seed, self.vocab, self.seq_len, self.global_batch = seed, vocab, seq_len, batch

    def batch(self, step: int) -> Dict[str, np.ndarray]:
        rng = np.random.default_rng(np.random.SeedSequence(int(self.seed), spawn_key=(4, step)))
        tokens = rng.integers(0, self.vocab, (self.global_batch, self.seq_len), dtype=np.int32)
        return {"tokens": tokens, "labels": np.roll(tokens, -1, axis=1)}
