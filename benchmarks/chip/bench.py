"""The chip benchmark's harness: one run of one cell, found by name.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix. Each
is a data file of its own (``configs/<config>.json``, ``traffic/<traffic>
.json``), the comparison's limits are ``limits/<cell>.json``, and each
per-layer metric is a reader of its own (``metrics/<metric>.py``). The
traffic file names the general driver that runs it (``drivers/<driver>
.py``). A cell added later brings only such files; nothing here changes.

A configuration names its model family (``"reference"``), and the family
is one file, ``reference/<family>.py``, found like the others. A new
architecture brings that file; it defines:

* ``KEYS``: each of the configuration's published sizes, by its source
  key, mapped to the field of the program's ``ModelConfig`` that holds it
  (``programs.py`` checks the two against each other);
* ``program_fields(conf, arch)``: the rest of the program's config, from
  the file and the registered architecture ``arch`` (nested settings, the
  dtypes);
* ``describe(conf)``: the parameter tree as the program stores it, each
  leaf ``(shape, kind, scale)`` or ``(shape, kind, scale, share)``, where
  ``share`` is the part of a matrix one token uses (``weights.py``);
* the plain reference that decides ``correct``: for a served family
  ``compiled_readings``, for a trained one ``train_readings``, ``compare``
  and ``flops_per_token_beyond_matrices`` (``counters.py``).

A run:

1. refuses unless JAX reports a TPU and at least the chips the cell asks
   for (``NoChip``; the command exits non-zero and prints no result);
2. keeps JAX's persistent compile cache at ``.bench_cache/jax`` inside the
   checkout (a fixed path: the path is part of the cache key);
3. hands the cell to its driver, which makes weights and traffic from
   ``--seed``, warms up the cell's own shapes, measures for ``--seconds``
   and then checks what the timed path produced against the plain
   reference (``reference/``);
4. with ``--trace 1`` traces the window with the JAX profiler and reduces
   the trace (``trace.py``) for the per-layer readers.

The result is one JSON line: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, (``breakdown``), and last ``checks``: each number
compared, beside its limit.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import importlib.util
import json
import math
import pathlib
import sys
import time
from typing import Any, Dict, List, Optional

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
SRC = ROOT / "src"
CACHE_DIR = ROOT / ".bench_cache" / "jax"
OUT_DIR = ROOT / ".bench_cache" / "runs"


class NoChip(SystemExit):
    """No TPU, or fewer chips than the cell asks for: no result."""


# ---------------------------------------------------------------------------
# finding a cell's files by name
# ---------------------------------------------------------------------------

def load_spec(root: pathlib.Path = ROOT, pending: bool = True) -> Dict[str, Any]:
    """``BENCHMARK.json``, and with ``pending`` the cells of ``pending.json``
    beside this file: cells whose files the benchmark holds but which
    ``BENCHMARK.json`` does not list yet (not proven on the chip). Their
    entries have ``BENCHMARK.json``'s form; a metric already listed gains
    the pending cells of its ``workloads``, and nothing else changes."""
    with open(root / "BENCHMARK.json") as f:
        spec = json.load(f)
    path = HERE / "pending.json"
    if not pending or not path.is_file():
        return spec
    with open(path) as f:
        more = json.load(f)
    cells = {w["name"] for w in more.get("workloads", [])}
    for key, entries in more.items():
        have = {e["name"]: e for e in spec[key]}
        for e in entries:
            if e["name"] not in have:
                spec[key].append(e)
            elif "workloads" in have[e["name"]]:
                have[e["name"]]["workloads"] += [w for w in e.get("workloads", [])
                                                 if w in cells]
    return spec


def find(entries: List[Dict[str, Any]], name: str, what: str) -> Dict[str, Any]:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"bench: no {what} named {name!r} in BENCHMARK.json")


def load_json(kind: str, name: str, base: pathlib.Path = HERE,
              missing: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    path = base / kind / f"{name}.json"
    if not path.is_file():
        if missing is not None:
            return missing
        raise SystemExit(f"bench: {path} is missing")
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path):
    """Import one reader or driver by its file path (names may hold dots)."""
    name = f"bench_{path.parent.name}_{path.stem}".replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def _family_at(path: pathlib.Path):
    return load_module(path)


def family(conf: Dict[str, Any], base: pathlib.Path = HERE):
    """The module of the configuration's model family,
    ``<base>/reference/<family>.py``, imported once per file."""
    path = pathlib.Path(base).resolve() / "reference" / f"{conf['reference']}.py"
    if not path.is_file():
        raise SystemExit(f"bench: {path} is missing")
    return _family_at(path)


@dataclasses.dataclass
class Cell:
    """Everything a driver needs about one cell, read from files."""

    name: str
    chips: int
    base: pathlib.Path              # the directory the cell's files are read from
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve_cell(name: str, spec: Dict[str, Any], base: pathlib.Path = HERE) -> Cell:
    w = find(spec["workloads"], name, "workload")
    c = find(spec["configs"], w["config"], "config")
    config = load_json("configs", c["name"], base)
    return Cell(
        name=name,
        chips=int(w["chips"]),
        base=base,
        config=config,
        traffic=load_json("traffic", w["traffic"], base),
        # a cell with no limits yet (pending, never calibrated) compares
        # every number against NaN: it is never correct
        limits=load_json("limits", name, base, missing={}),
        end_to_end=[m for m in spec["end_to_end"] if applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if applies(m, name)],
    )


# ---------------------------------------------------------------------------
# device, compile cache, compile counting
# ---------------------------------------------------------------------------

def use_bench_cache(jax) -> str:
    """The persistent compile cache, at a fixed path inside the checkout.
    Set in code, so it wins over ``JAX_COMPILATION_CACHE_DIR``, which may
    point outside the checkout. Every program is cached, however quick."""
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)


def require_chip(jax, want: int) -> Dict[str, Any]:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"bench: no TPU found (JAX platform {devices[0].platform!r})")
    if len(devices) < want:
        raise NoChip(f"bench: the cell needs {want} chips, JAX found {len(devices)}")
    return device_info(jax)


def device_info(jax) -> Dict[str, Any]:
    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


class CompileMeter:
    """Programs compiled and read back from the persistent cache (JAX
    reports a cache read as a compile, so ``count`` holds both)."""

    def __init__(self, monitoring):
        self.count, self.seconds, self.cache_hits = 0, 0.0, 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


_METER: Optional[CompileMeter] = None


def compile_meter(jax) -> CompileMeter:
    """One meter per process: JAX's listeners cannot be taken back."""
    global _METER
    if _METER is None:
        _METER = CompileMeter(jax.monitoring)
    return _METER


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)


# ---------------------------------------------------------------------------
# statistics
# ---------------------------------------------------------------------------

def percentile(values: List[float], q: float) -> float:
    """The ``q``-th percentile (nearest rank) of all values."""
    s = sorted(values)
    k = max(0, math.ceil(q / 100.0 * len(s)) - 1)
    return s[k]


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """What a driver hands back. ``end_to_end`` holds the cell's end-to-end
    values; ``counts`` holds what the per-layer readers read besides the
    trace; ``checks`` maps each compared number to (value, limit)."""

    end_to_end: Dict[str, float]
    attempted: int
    failed: int
    checks: Dict[str, tuple]
    counts: Dict[str, Any]
    memory_peak_bytes: int
    compiles_in_window: int
    extra_ok: bool = True          # e.g. every token id in range
    control_checks: Optional[Dict[str, tuple]] = None   # the control's, as ``checks``


@dataclasses.dataclass
class Context:
    """What a driver is given."""

    cell: Cell
    seed: int
    seconds: float
    trace_dir: Optional[pathlib.Path]
    meter: Any
    t_process: float
    devices: List[Any]
    control: bool = False          # also read the control (calibration only)

    def limit(self, name: str) -> float:
        return float(self.cell.limits.get(name, math.nan))

    @contextlib.contextmanager
    def window(self):
        """Around the measured window: the profiler when tracing, and a
        host span that the trace reduction finds."""
        import jax

        if self.trace_dir is not None:
            self.trace_dir.mkdir(parents=True, exist_ok=True)
            jax.profiler.start_trace(str(self.trace_dir))
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                yield
        finally:
            if self.trace_dir is not None:
                t = time.perf_counter()
                jax.profiler.stop_trace()
                print(f"bench trace stop_s {time.perf_counter() - t:.2f}", file=sys.stderr,
                      flush=True)


def span(name: str):
    """A host span in the profiler's trace, read to label idle gaps."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def seed_words(seed: int) -> List[int]:
    """Two 32-bit words from a seed of any size: ``jax.random.key`` keeps
    only the low 32 bits of a large integer, so two seeds 2**32 apart
    would otherwise make the same weights."""
    import numpy as np

    return [int(x) for x in np.random.SeedSequence(int(seed)).generate_state(2)]


def jax_key(seed: int, stream: int = 0):
    import jax

    a, b = seed_words(seed)
    return jax.random.fold_in(jax.random.fold_in(jax.random.key(a), b), stream)


def numpy_rng(seed: int, stream: int):
    import numpy as np

    return np.random.default_rng(np.random.SeedSequence(int(seed), spawn_key=(stream,)))


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_process: Optional[float] = None, chip: bool = True,
             spec: Optional[Dict[str, Any]] = None, base: pathlib.Path = HERE,
             control: bool = False, keep_counts: bool = False,
             traffic_changes: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """One run of one cell; returns the result object. ``chip=False`` skips
    the look for a TPU and the persistent cache (tests on the CPU, at the
    sizes their files state). ``control``, ``keep_counts`` and
    ``traffic_changes`` serve ``calibrate.py`` and ``sweep.py`` only: with
    ``control`` the control is judged in the program's place (``checks``
    and ``correct`` are the control's) and the program's own checks are
    kept under ``program_checks``."""
    t_process = time.perf_counter() if t_process is None else t_process
    spec = load_spec() if spec is None else spec
    cell = resolve_cell(workload, spec, base)
    cell.traffic.update(traffic_changes or {})
    if not (SRC / "repro").is_dir():
        raise SystemExit(f"bench: the program is missing ({SRC / 'repro'})")
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    import jax

    device = require_chip(jax, cell.chips) if chip else device_info(jax)
    if chip:
        use_bench_cache(jax)
    devices = jax.devices()[: cell.chips]
    meter = compile_meter(jax)
    trace_dir = None
    if trace:
        trace_dir = OUT_DIR / f"{workload}-{seed}-{time.time_ns()}"
    ctx = Context(cell=cell, seed=int(seed), seconds=float(seconds), trace_dir=trace_dir,
                  meter=meter, t_process=t_process, devices=devices, control=control)
    driver = load_module(base / "drivers" / f"{cell.traffic['driver']}.py")
    run: Run = driver.run(ctx)
    run.counts["device_kind"] = device["kind"]

    print(f"bench compiles_in_window {run.compiles_in_window}", flush=True)
    checks = run.checks
    if control:
        if not run.control_checks:
            raise SystemExit(f"bench: the cell {workload!r} reads no control")
        checks = run.control_checks
    result: Dict[str, Any] = {
        "correct": judge(checks) and run.extra_ok,
        "attempted": run.attempted,
        "failed": run.failed,
        "metrics": {},
        "device": dict(device, memory_peak_bytes=run.memory_peak_bytes),
    }
    if trace:
        t = time.perf_counter()
        tr = load_module(HERE / "trace.py").reduce_trace(trace_dir, len(devices))
        result["device"].update(busy_s=tr.busy_s, window_s=tr.window_s)
        for m in cell.per_layer:
            reader = load_module(base / "metrics" / f"{m['name']}.py")
            value = reader.read(tr, run.counts, cell)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = tr.breakdown()
        print(f"bench trace reduce_s {time.perf_counter() - t:.2f}", file=sys.stderr, flush=True)
    else:
        for m in cell.end_to_end:
            if m["name"] in run.end_to_end:
                result["metrics"][m["name"]] = {"value": run.end_to_end[m["name"]],
                                                "unit": m["unit"]}
    if keep_counts:
        result["counts"] = {k: v for k, v in run.counts.items()
                            if k not in ("steps", "prefill_lens")}
    if control:
        result["program_checks"] = as_checks(run.checks)
        result["program_correct"] = judge(run.checks) and run.extra_ok
    result["checks"] = as_checks(checks)
    return result


def judge(checks: Dict[str, tuple]) -> bool:
    """Correct: there is something to compare, and every number compared
    is at or under its limit (a NaN reading or limit is never under)."""
    return bool(checks) and all(v <= lim for v, lim in checks.values())


def as_checks(checks: Dict[str, tuple]) -> Dict[str, Dict[str, float]]:
    return {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}


def print_result(result: Dict[str, Any]) -> None:
    """The checks as the last lines on standard error; the result as the
    last line on standard output."""
    for k, c in result["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)

