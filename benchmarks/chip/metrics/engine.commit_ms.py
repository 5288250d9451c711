"""Mean duration of the engine's serve.commit spans in the window (ms):
each step's per-lane bookkeeping, finishing and evicting requests
included, host work after each decode step. None where the trace holds no
such span. Reads the trace; moves itl_p99_ms."""
MS_PER_NS = 1e-6


def read(trace, counts, cell):
    spans = [e - s for s, e, n in trace.host if n == "serve.commit"]
    if not spans:
        return None
    return sum(spans) / len(spans) * MS_PER_NS
