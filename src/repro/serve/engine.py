"""Continuous-batching decode engine over the paged KV block pool.

The serving hot path: a fixed set of decode *lanes* (the batch dimension of
the compiled decode step) advances every active sequence one token per
step, while a host-side free-page list admits pending requests into lanes
as pool pages free up — insertion at prefill completion, eviction at
EOS / length / shed. Unlike the legacy lock-step path (``launch/serve.py``
without ``--engine``), lanes hold sequences of DIFFERENT lengths: each
lane's write position and attention extent come from its own ``seq_lens``
entry, and its pages from its row of the block table.

Admission rule (documented in docs/serving.md): requests are admitted
FIFO, and a request is admitted only when a free lane exists AND the pool
has enough free pages for its whole lifetime — ``ceil((prompt + max_new)
/ page_size)`` pages are reserved up front. Reserving up front means an
admitted request can never stall mid-stream on pool exhaustion, so the
engine needs no preemption machinery; the cost is earlier admission
back-pressure, which the fleet layer sees as queue depth.

Page accounting: the pool's LAST page is the trash page — dead lanes
(no active sequence) redirect their decode writes there and it is never
allocated, so a fully static-shape decode step serves a ragged, changing
set of sequences.

Prefill runs dense (the existing blockwise/flash path, one request at a
time at its exact prompt length), then a donating jit scatters the dense
cache pages into the request's reserved pool pages. Per-batch decode wall
times feed a ``ThroughputTracker`` so the fleet simulator can consume
MEASURED tokens/sec (``FleetSimulator`` ``throughput_mode="engine"``)
instead of the closed-form analytic table.

Each phase of an admission (``serve.admit``: ``serve.prefill``,
``serve.pack``, ``serve.first_token``) and of a step (``serve.batch``,
``serve.decode``, ``serve.readback``, ``serve.commit``) runs inside a
profiler span (``repro.obs.span``), and so does each garbage collection
(``serve.gc``): a profiler trace then names what the host did while the
chip idled. Without a running trace the spans record nothing.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import time
from collections import deque
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import PAGE_SIZE
from repro.obs import events as obs_ev
from repro.obs.recorder import current as obs_current
from repro.obs.spans import span

GC_SPAN = "serve.gc"
_gc_open = None         # the span of the collection under way


def _gc_span(phase: str, info: dict) -> None:
    """A ``gc.callbacks`` hook: every collection in the process, not only
    those the engine sets off, runs inside a ``serve.gc`` span."""
    global _gc_open
    if phase == "start":
        _gc_open = span(GC_SPAN, generation=info["generation"])
        _gc_open.__enter__()
    elif _gc_open is not None:
        _gc_open.__exit__(None, None, None)
        _gc_open = None


def _trace_gc() -> None:
    """Hook ``_gc_span`` once per process; a later call does nothing."""
    if _gc_span not in gc.callbacks:
        gc.callbacks.append(_gc_span)


@dataclasses.dataclass
class Request:
    """One generation request. ``resume_tokens`` carries tokens already
    generated (and committed) before a migration; the engine re-prefills
    prompt + resume_tokens[:-1] and continues from resume_tokens[-1]."""

    rid: int
    prompt: np.ndarray                      # (S,) int32
    max_new_tokens: int
    resume_tokens: Optional[np.ndarray] = None


@dataclasses.dataclass
class Completion:
    rid: int
    tokens: List[int]                       # all generated tokens, in order
    reason: str                             # "eos" | "length" | "shed"


@dataclasses.dataclass
class _Lane:
    rid: int
    prompt: np.ndarray
    max_new_tokens: int
    pages: List[int]                        # reserved pool pages, in order
    seq_len: int                            # tokens written to the pool
    current: int                            # last generated, not yet fed
    generated: List[int]


class DecodeEngine:
    """Continuous-batching greedy decode over a paged KV pool.

    One engine per (model, mesh, lane count): the decode step compiles
    once for the static (lanes, max_blocks) shape and every step serves
    whatever mix of sequences currently occupies the lanes. Its attention
    reads only the live pages (the Pallas paged kernel) on one TPU chip
    over a float pool, and gathers the whole block table elsewhere
    (``build_paged_decode_step``).
    """

    def __init__(
        self,
        model,
        layout,
        mesh,
        *,
        lanes: int,
        num_pages: int,
        max_context: int,
        page_size: int = PAGE_SIZE,
        eos_id: Optional[int] = None,
        tracker=None,                       # Optional[ThroughputTracker]
        tracker_key: Any = None,
    ):
        from repro.dist import (
            cache_shardings,
            make_activation_constrainer,
            param_shardings,
        )
        from repro.train.steps import (
            build_paged_decode_step,
            build_prefill_step,
        )

        assert num_pages >= 2, "pool needs at least one real page + trash"
        _trace_gc()
        self.model = model
        self.layout = layout
        self.mesh = mesh
        self.lanes = lanes
        self.page_size = page_size
        self.num_pages = num_pages
        self.max_blocks = -(-max_context // page_size)
        self.eos_id = eos_id
        self.tracker = tracker
        self.tracker_key = tracker_key
        self.decoded_tokens = 0
        self.decode_seconds = 0.0
        self.prefilled_tokens = 0
        self.steps = 0                      # lane-event trace clock
        self.last_logits = None             # (lanes, vocab) of the last step

        self._int8 = layout.int8_kv_cache
        self._free_pages = deque(range(num_pages - 1))  # last page = trash
        self._pending: deque = deque()
        self._lanes: List[Optional[_Lane]] = [None] * lanes
        self._done: List[Completion] = []

        constrain = make_activation_constrainer(mesh, layout, model.cfg)
        self.param_sh = param_shardings(model.specs, mesh, layout)
        pc_specs = model.paged_cache_specs(num_pages, page_size, int8=self._int8)
        self._c_sh = cache_shardings(pc_specs, mesh, layout)
        repl = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
        self._repl = repl
        self._decode = jax.jit(
            build_paged_decode_step(model, layout, constrain, mesh=mesh),
            in_shardings=(self.param_sh, self._c_sh, repl, repl, repl),
            out_shardings=(None, self._c_sh),
            donate_argnums=(1,),
        )
        self._build_prefill = functools.partial(
            build_prefill_step, model, layout, constrain=constrain
        )
        self._prefills: Dict[int, Any] = {}   # prompt len -> jitted prefill
        self._packs: Dict[int, Any] = {}      # n dense pages -> jitted pack
        with mesh:
            self.cache = jax.device_put(
                model.init_paged_cache(num_pages, page_size, int8=self._int8),
                self._c_sh,
            )

    # -- submission ---------------------------------------------------------

    def submit(self, req: Request) -> None:
        self._pending.append(req)

    @property
    def in_flight(self) -> int:
        return len(self._pending) + sum(l is not None for l in self._lanes)

    @property
    def free_pages(self) -> int:
        return len(self._free_pages)

    @property
    def completions(self) -> List[Completion]:
        return list(self._done)

    @property
    def occupancy(self) -> float:
        """Fraction of decode lanes currently holding a live stream — the
        utilization signal the demand-driven autoscaler's low-water mark
        reads (pending-but-unadmitted requests do not count: they hold no
        lane, so they are demand pressure, not occupancy)."""
        if not self._lanes:
            return 0.0
        return sum(l is not None for l in self._lanes) / len(self._lanes)

    @property
    def page_pool_used_frac(self) -> float:
        """Fraction of *allocatable* pool pages currently reserved by live
        lanes. The trash page is excluded from the denominator: it is never
        allocated, so a fully drained engine reads exactly 0.0."""
        allocatable = self.num_pages - 1
        return 1.0 - len(self._free_pages) / allocatable

    def _sample_gauges(self, rec) -> None:
        t = float(self.steps)
        rec.gauge("engine.occupancy", t, self.occupancy)
        rec.gauge("engine.page_pool_used_frac", t, self.page_pool_used_frac)

    @property
    def measured_tokens_per_sec(self) -> float:
        if self.decode_seconds <= 0:
            return 0.0
        return self.decoded_tokens / self.decode_seconds

    # -- admission ----------------------------------------------------------

    def _pages_needed(self, req: Request) -> int:
        # resumed tokens count toward max_new_tokens: a stream never holds
        # more than prompt + max_new_tokens positions, resumed or not
        total = len(req.prompt) + req.max_new_tokens
        return -(-total // self.page_size)

    def _prefill_for(self, length: int):
        if length not in self._prefills:
            self._prefills[length] = jax.jit(self._build_prefill(length))
        return self._prefills[length]

    def _pack_for(self, n_dense_pages: int):
        if n_dense_pages not in self._packs:
            ps = self.page_size
            key_map = {"k": "k_pages", "v": "v_pages",
                       "k_scale": "k_scale", "v_scale": "v_scale"}

            def pack(pool, dense_blocks, pages):
                out = dict(pool["blocks"])
                for dk, pk in key_map.items():
                    if dk not in dense_blocks:
                        continue
                    src = dense_blocks[dk][:, 0]      # (L, T, ...)
                    L, T = src.shape[:2]
                    src = src.reshape(L, T // ps, ps, *src.shape[2:])
                    out[pk] = out[pk].at[:, pages].set(src.astype(out[pk].dtype))
                return {"blocks": out}

            self._packs[n_dense_pages] = jax.jit(
                pack, donate_argnums=(0,), out_shardings=self._c_sh
            )
        return self._packs[n_dense_pages]

    def _admit(self) -> None:
        while self._pending and None in self._lanes:
            req = self._pending[0]
            needed = self._pages_needed(req)
            assert needed <= self.max_blocks, (
                f"request {req.rid} needs {needed} pages > "
                f"max_blocks {self.max_blocks}"
            )
            if needed > len(self._free_pages):
                return  # FIFO back-pressure: head-of-line waits for pages
            self._pending.popleft()
            pages = [self._free_pages.popleft() for _ in range(needed)]
            with span("serve.admit", rid=int(req.rid), prompt_len=len(req.prompt)):
                self._insert(req, pages)

    def _insert(self, req: Request, pages: List[int]) -> None:
        resume = (np.asarray(req.resume_tokens, np.int32)
                  if req.resume_tokens is not None else np.zeros(0, np.int32))
        # cache must hold prompt + all resumed tokens except the newest,
        # which rides the next decode step
        cached = np.concatenate([req.prompt.astype(np.int32), resume[:-1]])
        length = len(cached)
        with self.mesh:
            with span("serve.prefill"):
                prefill = self._prefill_for(length)
                tokens = jax.device_put(jnp.asarray(cached[None, :]), self._repl)
                logits, dense = prefill(self._params, {"tokens": tokens})
            with span("serve.pack"):
                n_dense = dense["blocks"]["k"].shape[2] // self.page_size
                pack = self._pack_for(n_dense)
                self.cache = pack(
                    self.cache, dense["blocks"],
                    jnp.asarray(pages[:n_dense], jnp.int32),
                )
            if len(resume):
                current = int(resume[-1])
            else:
                with span("serve.first_token"):
                    current = int(jnp.argmax(logits[0, -1]))
        self.prefilled_tokens += length
        lane = self._lanes.index(None)
        generated = [int(t) for t in resume] if len(resume) else [current]
        self._lanes[lane] = _Lane(
            rid=req.rid, prompt=req.prompt, max_new_tokens=req.max_new_tokens,
            pages=pages, seq_len=length, current=current, generated=generated,
        )
        rec = obs_current()
        if rec.enabled:
            rec.emit(obs_ev.Admit(
                t=float(self.steps), request_id=int(req.rid),
                lane=lane, pages_reserved=len(pages),
            ))
            self._sample_gauges(rec)
        self._maybe_finish(lane)

    # -- stepping -----------------------------------------------------------

    def _maybe_finish(self, lane_idx: int) -> None:
        lane = self._lanes[lane_idx]
        reason = None
        if len(lane.generated) >= lane.max_new_tokens:
            reason = "length"
        elif self.eos_id is not None and lane.generated[-1] == self.eos_id:
            reason = "eos"
        if reason is not None:
            self._evict(lane_idx, reason)

    def _evict(self, lane_idx: int, reason: str) -> None:
        lane = self._lanes[lane_idx]
        self._free_pages.extend(lane.pages)
        self._done.append(Completion(lane.rid, lane.generated, reason))
        self._lanes[lane_idx] = None
        rec = obs_current()
        if rec.enabled:
            rec.emit(obs_ev.Evict(
                t=float(self.steps), request_id=int(lane.rid),
                lane=lane_idx, reason=reason,
            ))
            self._sample_gauges(rec)

    def shed(self) -> List[Request]:
        """Evict every active lane and drain the queue (spot revocation):
        returns the resumable requests, committed tokens included."""
        rec = obs_current()
        out: List[Request] = []
        for i, lane in enumerate(self._lanes):
            if lane is None:
                continue
            if rec.enabled:
                rec.emit(obs_ev.Shed(
                    t=float(self.steps), request_id=int(lane.rid), lane=i,
                    prompt_tokens=len(lane.prompt),
                    resume_tokens=len(lane.generated),
                ))
            out.append(Request(
                rid=lane.rid, prompt=lane.prompt,
                max_new_tokens=lane.max_new_tokens,
                resume_tokens=np.asarray(lane.generated, np.int32),
            ))
            self._evict(i, "shed")
            self._done.pop()  # shed lanes resume elsewhere, not completions
        while self._pending:
            out.append(self._pending.popleft())
        return out

    def step(self, params) -> List[Completion]:
        """Admit what fits, advance every active lane one token. Returns
        completions finished by this call."""
        self._params = params
        self.steps += 1
        done_before = len(self._done)
        self._admit()
        active = [i for i, l in enumerate(self._lanes) if l is not None]
        if not active:
            return self._done[done_before:]

        # decode_seconds: the whole step as a caller of it sees it, from
        # the lane arrays to the last lane's bookkeeping
        t0 = time.perf_counter()  # repro-lint: disable=D001
        with span("serve.batch", lanes=len(active)):
            tokens = np.zeros((self.lanes, 1), np.int32)
            seq_lens = np.zeros(self.lanes, np.int32)
            table = np.full((self.lanes, self.max_blocks), -1, np.int32)
            for i in active:
                lane = self._lanes[i]
                tokens[i, 0] = lane.current
                seq_lens[i] = lane.seq_len
                table[i, : len(lane.pages)] = lane.pages
            with self.mesh:
                tok_d = jax.device_put(jnp.asarray(tokens), self._repl)
                sl_d = jax.device_put(jnp.asarray(seq_lens), self._repl)
                bt_d = jax.device_put(jnp.asarray(table), self._repl)
        with self.mesh, span("serve.decode"):
            logits, self.cache = self._decode(
                params, self.cache, tok_d, sl_d, bt_d
            )
            self.last_logits = logits[:, -1]
            nxt = jnp.argmax(self.last_logits, axis=-1).astype(jnp.int32)
        with span("serve.readback"):
            jax.block_until_ready(nxt)
            nxt = np.asarray(nxt)
        with span("serve.commit"):
            for i in active:
                lane = self._lanes[i]
                lane.seq_len += 1
                lane.current = int(nxt[i])
                lane.generated.append(lane.current)
                self._maybe_finish(i)
        dt = time.perf_counter() - t0  # repro-lint: disable=D001
        self.decode_seconds += dt
        self.decoded_tokens += len(active)
        if self.tracker is not None:
            self.tracker.observe(self.tracker_key, 1, dt)
        return self._done[done_before:]

    def run(self, params, max_steps: int = 100_000) -> List[Completion]:
        """Drive until every submitted request completes."""
        for _ in range(max_steps):
            if self.in_flight == 0:
                break
            self.step(params)
        assert self.in_flight == 0, "engine did not drain (pool too small?)"
        return list(self._done)
