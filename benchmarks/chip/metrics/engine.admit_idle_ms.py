"""Device-idle time inside the engine's serve.admit spans, per admitted
request (ms): the host's part of an admission (dispatching the batch-1
prefill and the page pack, and reading the first token back) while the
chip waits. None where the trace holds no such span. Reads the trace;
moves ttft_p90_ms."""


def read(trace, counts, cell):
    admits = [(s, e) for s, e, n in trace.host if n == "serve.admit"]
    if not trace.chips or not admits:
        return None
    return 1000.0 * sum(trace.idle_between(s, e) for s, e in admits) / len(admits)
