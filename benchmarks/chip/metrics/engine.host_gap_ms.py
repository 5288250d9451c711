"""Mean device-idle time between consecutive paged decode-step programs
(ms): the host's work between two steps. Reads the trace; moves
itl_p99_ms."""


def read(trace, counts, cell):
    runs = trace.program_runs(r"paged_decode_step")
    if len(runs) < 2:
        return None
    gaps = [trace.idle_between(a[1], b[0]) for a, b in zip(runs, runs[1:])]
    return 1000.0 * sum(gaps) / len(gaps)
