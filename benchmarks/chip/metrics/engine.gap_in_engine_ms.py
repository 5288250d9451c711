"""Device-idle time between consecutive paged decode-step programs that
lies in gaps labelled with one of the engine's own host spans (ms, per
pair of steps): the part of engine.host_gap_ms spent inside DecodeEngine.
A gap takes the label that Trace.idle_gaps gives it, the innermost host
span over its middle. Every serve.* label counts as the engine's except
the two the harness owns (serve.step around the call, serve.idle between
arrivals), so a span the engine adds or renames later still counts.
serve.gc spans every collection in the process, those the harness sets
off between steps too, so a collection counts here wherever it began;
engine.host_gap_ms less this is what the caller and the runtime add,
collections aside. None where the trace holds none of the engine's spans.
Reads the trace; moves itl_p99_ms."""
import bisect

HARNESS_SPANS = {"serve.step", "serve.idle"}
MS_PER_NS = 1e-6


def _engines(name):
    return name.startswith("serve.") and name not in HARNESS_SPANS


def read(trace, counts, cell):
    if not trace.chips or not any(_engines(n) for _, _, n in trace.host):
        return None
    runs = trace.program_runs(r"paged_decode_step")
    if len(runs) < 2:
        return None
    between = [(a[1], b[0]) for a, b in zip(runs, runs[1:])]
    starts = [s for s, _ in between]
    total_ns = 0.0
    for a, b, label in trace.idle_gaps():
        if not _engines(label):
            continue
        k = bisect.bisect_left(starts, b) - 1
        while k >= 0 and between[k][1] > a:
            total_ns += max(0.0, min(b, between[k][1]) - max(a, between[k][0]))
            k -= 1
    return total_ns * MS_PER_NS / len(between)
