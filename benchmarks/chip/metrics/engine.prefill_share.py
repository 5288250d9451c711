"""Device time of the engine's prefill and page-pack programs, over the
window (%). Reads the trace; moves ttft_p90_ms."""


def read(trace, counts, cell):
    t = trace.program_seconds(r"prefill|pack")
    if t <= 0.0:
        return None
    return 100.0 * t / trace.window_s
