"""Mean duration of the engine's serve.batch spans in the window (ms): the
lane arrays and their three device_puts, host work before each decode
step. None where the trace holds no such span. Reads the trace; moves
itl_p99_ms."""
MS_PER_NS = 1e-6


def read(trace, counts, cell):
    spans = [e - s for s, e, n in trace.host if n == "serve.batch"]
    if not spans:
        return None
    return sum(spans) / len(spans) * MS_PER_NS
