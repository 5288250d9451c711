"""Serving driver: the program's ``DecodeEngine`` under seeded traffic.

Set-up makes the weights on the chip (one jitted call), builds the engine
at the traffic's lanes, pool and context, and serves one short request for
each prompt length the window will send, so that every prefill, page pack
and the decode step are compiled before the window opens.

The window offers the traffic's requests (open loop: each at its due time,
whatever the engine is doing; closed loop: each client sends its next
request when its last one completes) and calls ``engine.step`` while
anything is in flight. The host clock is read when each step returns:
every token a step delivers is delivered then. The engine's own ``Admit``
and ``Evict`` events say which request a step admitted or finished.

* ``tok_s``: tokens delivered in the window over the window's length;
* ``ttft_p90_ms``: over every request due in the window, from its due time
  to the return of the step that delivered its first token (after the
  window, steps go on until each has one, for at most a minute; one that
  never gets it counts as missing);
* ``itl_p99_ms``: over every gap between consecutive tokens of a request,
  both delivered in the window.

Then the check: the chip's peak memory is read, the engine and its weights
are dropped, and the plain reference (``reference/<family>.py``) runs over
a sample of the finished requests, drawn from the seed with the longest
among them: at each position where a token was served, how far that
token's logit lies below the reference's best (``logit_gap``).
"""
from __future__ import annotations

import collections
import gc
import sys
import time
from typing import Any, Dict, List

import numpy as np

import bench
import programs
import traffic as gen
import weights

DRAIN_LIMIT_S = 60.0
NO_ROWS = 1e9    # the gap read where no request finished: never correct


class _Book:
    """Per-request delivery times, kept by the harness."""

    def __init__(self):
        self.due: Dict[int, float] = {}
        self.times: Dict[int, List[float]] = collections.defaultdict(list)
        self.prompt_len: Dict[int, int] = {}
        self.live: set = set()

    def submit(self, r: gen.Request, due: float) -> None:
        self.due[r.rid] = due
        self.prompt_len[r.rid] = len(r.prompt)


def _serve_one_step(engine, params, rec, book: _Book, done_tokens: Dict[int, int],
                    counts: Dict[str, Any], counting: bool) -> tuple:
    """One engine step; returns (time it returned, rids finished)."""
    n_ev = len(rec.events)
    with bench.span("serve.step"):
        finished = engine.step(params)
    t = time.perf_counter()
    admitted, evicted = set(), set()
    for ev in rec.events[n_ev:]:
        kind = type(ev).__name__
        if kind == "Admit":
            admitted.add(ev.request_id)
        elif kind == "Evict":
            evicted.add(ev.request_id)
    for c in finished:
        done_tokens[c.rid] = len(c.tokens)
    ctx_tokens = 0
    decoded = 0
    for rid in book.live:
        book.times[rid].append(t)
        decoded += 1
        ctx_tokens += book.prompt_len[rid] + len(book.times[rid]) - 1
    prefills = []
    for rid in admitted:
        prefills.append(book.prompt_len[rid])
        book.times[rid].append(t)  # the prefill's token
        if not (rid in evicted and done_tokens.get(rid) == 1):
            book.times[rid].append(t)
            decoded += 1
            ctx_tokens += book.prompt_len[rid] + 1
    book.live = (book.live | admitted) - evicted
    if counting:
        counts["steps"].append((decoded, ctx_tokens))
        counts["prefill_lens"].extend(prefills)
        counts["step_admits"].append(len(prefills))
    return t, [c.rid for c in finished]


def run(ctx: bench.Context) -> bench.Run:
    import jax

    from repro.config import ShardingLayout
    from repro.dist import ElasticMeshManager
    from repro.models import build_model
    from repro.obs import recording
    from repro.serve import DecodeEngine, Request

    conf, tr = ctx.cell.config, ctx.cell.traffic
    cfg = programs.model_config(conf, ctx.cell.base)
    model = build_model(cfg)
    plan = ElasticMeshManager(ctx.devices).plan_for(ctx.cell.chips)
    engine = DecodeEngine(model, ShardingLayout(), plan.mesh, lanes=tr["lanes"],
                          num_pages=tr["pool_pages"], max_context=tr["max_context"])
    params = weights.make(conf, bench.jax_key(ctx.seed), cfg.param_dtype, engine.param_sh,
                          ctx.cell.base)
    if not weights.shapes_match(params, model.abstract_params()):
        raise SystemExit("bench: the program's parameter tree is not the one the "
                         "configuration describes")
    vocab = cfg.vocab_size
    reqs = gen.requests(tr, ctx.seed, ctx.seconds, vocab)
    by_rid = {r.rid: r for r in reqs}

    def to_engine(r: gen.Request):
        return Request(rid=r.rid, prompt=r.prompt, max_new_tokens=r.max_new_tokens)

    counts: Dict[str, Any] = {"steps": [], "prefill_lens": [], "step_admits": []}
    book = _Book()
    done_tokens: Dict[int, int] = {}
    with recording() as rec:
        for w in gen.warmup_requests(reqs, vocab, base_rid=-len(reqs) - 64):
            engine.submit(to_engine(w))
        engine.run(params)
        jax.block_until_ready(engine.cache)
        rec.clear()

        compiles_before = ctx.meter.count
        setup_s = time.perf_counter() - ctx.t_process
        closed = tr["loop"] == "closed"
        queue = collections.deque(sorted(reqs, key=lambda r: (r.due_s, r.rid)))
        per_client: Dict[int, collections.deque] = collections.defaultdict(collections.deque)
        if closed:
            for r in queue:
                per_client[r.client].append(r)
            queue.clear()
        due_in_window: List[int] = []

        with ctx.window():
            t0 = time.perf_counter()
            t_end = t0 + ctx.seconds
            if closed:
                for c in sorted(per_client):
                    r = per_client[c].popleft()
                    book.submit(r, t0)
                    due_in_window.append(r.rid)
                    engine.submit(to_engine(r))
            while True:
                now = time.perf_counter()
                if now >= t_end:
                    break
                while queue and t0 + queue[0].due_s <= now:
                    r = queue.popleft()
                    book.submit(r, t0 + r.due_s)
                    due_in_window.append(r.rid)
                    engine.submit(to_engine(r))
                if engine.in_flight == 0:
                    nxt = t0 + queue[0].due_s if queue else t_end
                    with bench.span("serve.idle"):
                        time.sleep(max(0.0, min(nxt, t_end) - now))
                    continue
                t, finished = _serve_one_step(engine, params, rec, book, done_tokens,
                                              counts, counting=True)
                if closed:
                    for rid in finished:
                        c = by_rid[rid].client
                        if per_client[c] and t < t_end:
                            r = per_client[c].popleft()
                            book.submit(r, t)
                            due_in_window.append(r.rid)
                            engine.submit(to_engine(r))
            t_stop = time.perf_counter()
        compiles_in_window = ctx.meter.count - compiles_before
        while queue and queue[0].due_s < ctx.seconds:   # due in the window, not yet sent
            r = queue.popleft()
            book.submit(r, t0 + r.due_s)
            due_in_window.append(r.rid)
            engine.submit(to_engine(r))

        window_s = t_stop - t0
        tokens_in_window = sum(1 for rid in due_in_window for x in book.times[rid] if x <= t_stop)
        gaps = [b - a for rid in due_in_window
                for a, b in zip(book.times[rid], book.times[rid][1:]) if b <= t_stop]
        # after the window: no new requests; step on until every request
        # due in the window has its first token, for at most a minute
        t_drain = time.perf_counter() + DRAIN_LIMIT_S
        while (any(not book.times[rid] for rid in due_in_window)
               and engine.in_flight and time.perf_counter() < t_drain):
            _serve_one_step(engine, params, rec, book, done_tokens, counts, counting=False)

    # a request that never got its first token counts as waiting until the
    # drain gave up: longer than any that did
    gave_up = time.perf_counter()
    ttft = [(book.times[rid][0] if book.times[rid] else gave_up) - book.due[rid]
            for rid in due_in_window]
    failed = sum(1 for rid in due_in_window if not book.times[rid])
    ms = 1000.0
    end_to_end = {
        "setup_s": setup_s,
        "tok_s": tokens_in_window / window_s,
        "ttft_p90_ms": bench.percentile(ttft, 90) * ms,
        "itl_p99_ms": bench.percentile(gaps, 99) * ms,
    }
    counts.update(window_s=window_s, tokens=tokens_in_window, requests=len(due_in_window),
                  itl_p99_ms=end_to_end["itl_p99_ms"],
                  ttft_p50_ms=bench.percentile(ttft, 50) * ms,
                  itl_p50_ms=bench.percentile(gaps, 50) * ms)
    print(f"bench window requests {len(due_in_window)} tokens {tokens_in_window} "
          f"steps {len(counts['steps'])} window_s {window_s!r} "
          f"ttft_p50_ms {counts['ttft_p50_ms']!r} itl_p50_ms {counts['itl_p50_ms']!r}",
          file=sys.stderr, flush=True)
    _print_spread(ttft, gaps, counts["step_admits"])

    peak = bench.peak_bytes(ctx.devices)
    served = {c.rid: list(c.tokens) for c in engine.completions if c.rid >= 0}
    finished_rids = [rid for rid in due_in_window if rid in served]
    extra_ok = all(0 <= t < vocab for toks in served.values() for t in toks)
    del engine, params
    gc.collect()

    sample = _sample(finished_rids, served, ctx.seed, tr["check"])
    rows = [(by_rid[rid].prompt, served[rid]) for rid in sample]
    gap, ctrl = _compare(conf, ctx.cell.base, ctx.seed, rows, tr["max_context"], ctx.control)
    counts.update(checked_rows=len(rows), checked_tokens=sum(len(s) for _, s in rows))
    print(f"bench check rows {len(rows)} tokens {counts['checked_tokens']}",
          file=sys.stderr, flush=True)
    limit = ctx.limit("logit_gap")
    return bench.Run(
        end_to_end=end_to_end, attempted=len(due_in_window), failed=failed,
        checks={"logit_gap": (gap, limit)},
        counts=counts, memory_peak_bytes=peak, compiles_in_window=compiles_in_window,
        extra_ok=extra_ok,
        control_checks={"logit_gap": (ctrl, limit)} if ctx.control else None,
    )


def _print_spread(ttft: List[float], gaps: List[float], admits: List[int]) -> None:
    """Where in their distributions the tails lie: a few percentiles of
    TTFT and of the inter-token gaps, and how many steps admitted more
    than one request (each admission is a batch-1 prefill in that step)."""
    def pct(values, qs):
        return " ".join(f"p{q}={bench.percentile(values, q) * 1000.0:.1f}" for q in qs) \
            if values else "none"
    multi = sum(1 for a in admits if a > 1)
    print(f"bench spread ttft_ms {pct(ttft, (50, 75, 90, 95, 100))} | itl_ms "
          f"{pct(gaps, (50, 90, 95, 98, 99, 99.5, 100))} | gaps {len(gaps)} "
          f"steps_admitting_2+ {multi}", file=sys.stderr, flush=True)


def _sample(rids: List[int], served: Dict[int, List[int]], seed: int,
            check: Dict[str, Any]) -> List[int]:
    """The longest finished request, and others drawn from the seed."""
    if not rids:
        return []
    longest = max(rids, key=lambda r: (len(served[r]), -r))
    rest = [r for r in rids if r != longest]
    rng = bench.numpy_rng(seed, 5)
    extra = list(rng.permutation(rest)[: check["rows"] - 1]) if rest else []
    return [longest] + [int(r) for r in extra]


def _compare(conf, base, seed: int, rows, width: int, control: bool):
    """Widest gap of a served token's logit below the reference's best;
    with ``control``, the same for the token that the control (the
    reference with float8 matmuls, in the program's place) puts first at
    each of those positions."""
    import jax
    import jax.numpy as jnp

    ref = bench.family(conf, base)
    if not rows:
        return NO_ROWS, NO_ROWS
    w = weights.make(conf, bench.jax_key(seed), conf.get("serve_dtype", "bfloat16"), base=base)
    with jax.default_matmul_precision("highest"):
        f = ref.compiled_readings(conf)
        fq = ref.compiled_readings(conf, "fp8") if control else None
        gap, ctrl = 0.0, 0.0
        for prompt, toks in rows:
            seq = np.concatenate([prompt, np.asarray(toks, np.int32)])[:width]
            p, n = len(prompt), len(seq) - len(prompt)
            tokens = np.zeros(width, np.int32)
            tokens[: len(seq)] = seq
            targets = np.roll(tokens, -1)
            sl = slice(p - 1, p - 1 + n)
            best, tgt, _ = (np.asarray(a) for a in f(w, jnp.asarray(tokens), jnp.asarray(targets)))
            gap = max(gap, float(np.max(best[sl] - tgt[sl])))
            if fq is not None:
                _, _, top_q = fq(w, jnp.asarray(tokens), jnp.asarray(targets))
                best_r, at_q, _ = (np.asarray(a) for a in f(w, jnp.asarray(tokens), top_q))
                ctrl = max(ctrl, float(np.max(best_r[sl] - at_q[sl])))
    return gap, (ctrl if control else None)
