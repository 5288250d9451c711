"""Reduction of a JAX profiler trace to what the per-layer readers read.

``reduce_trace(dir, chips)`` reads the ``.xplane.pb`` that
``jax.profiler.start_trace(dir)`` wrote, with nothing but JAX's own
``ProfileData``:

* the window: the host span ``bench.window`` that the harness opens around
  the measured window (the whole trace where it is missing);
* per chip (planes ``/device:TPU:<n>``): the operations that ran (line
  ``XLA Ops``) and the programs (line ``XLA Modules``), clipped to the
  window;
* busy time: the union of the operations' intervals, averaged over the
  chips the cell uses; idle share is one minus busy over the window;
* an operation's own time (``op_seconds``): its device time net of the
  operations nested in it, found by program and operation label;
* idle gaps of the first chip, each labelled by the innermost host span
  (``serve.step``, ``serve.idle``, ``train.segment``, ``reshard``, ...)
  that covers its middle, ``host`` where none does;
* ``breakdown()``: the ten operations (by self time, named by program and
  HLO instruction) and the ten gap labels that took the most time, in
  seconds.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

NS = 1e-9
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_SPAN_PREFIXES = ("bench.", "serve.", "train.", "reshard")

Interval = Tuple[float, float, str]   # start ns, end ns, name


def find_xplane(trace_dir) -> str:
    files = sorted(glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """The union of intervals, as sorted disjoint intervals."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


OPCODE = re.compile(r" = .*?\b([a-z][a-z0-9_-]*)\(")


def op_label(name: str) -> str:
    """``%fusion.180 fusion`` for an HLO line ``%fusion.180 = bf16[...] fusion(...)``."""
    head = name.split(" = ", 1)[0]
    m = OPCODE.search(name)
    return f"{head} {m.group(1)}" if m else head


def program_label(name: str) -> str:
    """``jit_pack`` for ``jit_pack(15571563011137774678)``."""
    return name.split("(", 1)[0]


def self_times(ops: List[Interval], modules: List[Interval]) -> Dict[str, float]:
    """Seconds of each operation net of the operations nested in it (a
    loop's own line holds its body's operations too), keyed by program
    and operation."""
    out: Dict[str, float] = collections.defaultdict(float)
    mods = sorted(modules)
    starts = [m[0] for m in mods]
    stack: List[list] = []     # [end, key, self ns]

    def close(upto: float):
        while stack and stack[-1][0] <= upto:
            end, key, own = stack.pop()
            out[key] += own * NS

    for s, e, n in sorted(ops, key=lambda iv: (iv[0], -iv[1])):
        close(s)
        k = bisect.bisect_right(starts, s) - 1
        prog = program_label(mods[k][2]) if k >= 0 and mods[k][1] >= s else "?"
        if stack:
            stack[-1][2] -= e - s
        stack.append([e, f"{prog}/{op_label(n)}", e - s])
    close(float("inf"))
    return out


def clip(iv: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi), n) for s, e, n in iv if e > lo and s < hi]


@dataclasses.dataclass
class Trace:
    window: Tuple[float, float]
    ops: Dict[int, List[Interval]]          # chip -> operations in the window
    modules: Dict[int, List[Interval]]      # chip -> programs in the window
    host: List[Interval]                    # host spans in the window
    chips: List[int]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * NS

    def __post_init__(self):
        self._busy: Dict[int, tuple] = {}
        self._self: Dict[int, Dict[str, float]] = {}

    def _self_times(self, chip: int) -> Dict[str, float]:
        """``self_times`` of the chip's operations, computed once."""
        if chip not in self._self:
            self._self[chip] = self_times(self.ops.get(chip, []), self.modules.get(chip, []))
        return self._self[chip]

    def _busy_index(self, chip: int) -> tuple:
        """The chip's busy intervals, their starts and ends, and the busy
        time before each (computed once: a window holds about a million
        operations)."""
        if chip not in self._busy:
            merged = merge([(s, e) for s, e, _ in self.ops.get(chip, [])])
            before = [0.0]
            for s, e in merged:
                before.append(before[-1] + (e - s))
            self._busy[chip] = (merged, [s for s, _ in merged], [e for _, e in merged], before)
        return self._busy[chip]

    def busy_intervals(self, chip: int) -> List[Tuple[float, float]]:
        return self._busy_index(chip)[0]

    def _busy_until(self, chip: int, t: float) -> float:
        """Busy ns of the chip before time ``t``."""
        _, starts, ends, before = self._busy_index(chip)
        k = bisect.bisect_right(starts, t)
        if k == 0:
            return 0.0
        return before[k] - max(0.0, ends[k - 1] - t)

    @property
    def busy_s(self) -> float:
        per = [sum(e - s for s, e in self.busy_intervals(c)) * NS for c in self.chips]
        return sum(per) / len(per) if per else 0.0

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def program_seconds(self, pattern: str) -> float:
        """Device seconds of programs whose name matches, averaged over chips."""
        rx = re.compile(pattern)
        per = [sum(e - s for s, e, n in self.modules.get(c, []) if rx.search(n)) * NS
               for c in self.chips]
        return sum(per) / len(per) if per else 0.0

    def op_seconds(self, program: str, op: str) -> float:
        """Device seconds (self time) of the operations whose label matches
        ``op`` inside programs whose name matches ``program``, averaged
        over chips."""
        rp, ro = re.compile(program), re.compile(op)
        per = []
        for c in self.chips:
            per.append(sum(v for k, v in self._self_times(c).items()
                           if rp.search(k.split("/", 1)[0]) and ro.search(k.split("/", 1)[1])))
        return sum(per) / len(per) if per else 0.0

    def program_runs(self, pattern: str, chip: Optional[int] = None) -> List[Interval]:
        rx = re.compile(pattern)
        c = self.chips[0] if chip is None else chip
        return sorted(iv for iv in self.modules.get(c, []) if rx.search(iv[2]))

    def idle_between(self, a: float, b: float, chip: Optional[int] = None) -> float:
        """Seconds of [a, b] in which no operation ran on the chip."""
        c = self.chips[0] if chip is None else chip
        busy = self._busy_until(c, b) - self._busy_until(c, a)
        return (b - a - busy) * NS

    def idle_gaps(self) -> List[Tuple[float, float, str]]:
        """Idle gaps of the first chip in the window, labelled by host span."""
        busy = self.busy_intervals(self.chips[0])
        lo, hi = self.window
        edges, prev = [], lo
        for s, e in busy:
            if s > prev:
                edges.append((prev, s))
            prev = max(prev, e)
        if hi > prev:
            edges.append((prev, hi))
        # the gaps' middles rise, so one sweep over the spans (by start)
        # keeps those that have begun and not yet ended
        spans = sorted(self.host)
        nxt, active, out = 0, [], []
        for a, b in edges:
            mid = 0.5 * (a + b)
            while nxt < len(spans) and spans[nxt][0] <= mid:
                active.append(spans[nxt])
                nxt += 1
            active = [sp for sp in active if sp[1] >= mid]
            best = min(active, key=lambda sp: sp[1] - sp[0])[2] if active else "host"
            out.append((a, b, best))
        return out

    def breakdown(self) -> Dict[str, List[List]]:
        if not self.chips:
            return {"device_ops": [], "idle_gaps": []}
        ops: Dict[str, float] = collections.defaultdict(float)
        for c in self.chips:
            for k, v in self._self_times(c).items():
                ops[k] += v / len(self.chips)
        gaps: Dict[str, float] = collections.defaultdict(float)
        for a, b, label in self.idle_gaps():
            gaps[label] += (b - a) * NS
        top = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]
        return {"device_ops": top(ops), "idle_gaps": top(gaps)}


def reduce_trace(trace_dir, chips: int) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(find_xplane(trace_dir))
    ops: Dict[int, List[Interval]] = {}
    modules: Dict[int, List[Interval]] = {}
    host: List[Interval] = []
    window: Optional[Tuple[float, float]] = None
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name not in ("XLA Ops", "XLA Modules"):
                    continue
                ivs = [(e.start_ns, e.start_ns + e.duration_ns, e.name) for e in line.events]
                (ops if line.name == "XLA Ops" else modules)[chip] = ivs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == "bench.window" and window is None:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif e.name.startswith(HOST_SPAN_PREFIXES):
                        host.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
    used = sorted(ops)[:chips]
    if window is None:
        every = [iv for c in used for iv in ops[c]]
        window = (min(s for s, _, _ in every), max(e for _, e, _ in every))
    lo, hi = window
    return Trace(window=window,
                 ops={c: clip(ops[c], lo, hi) for c in used},
                 modules={c: clip(modules.get(c, []), lo, hi) for c in used},
                 host=clip(host, lo, hi), chips=used)
