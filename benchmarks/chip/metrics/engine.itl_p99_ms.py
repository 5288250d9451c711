"""The chat cell's 99th-percentile gap between consecutive tokens of a
request (ms), by the harness's host clock: the tail that batch-1 prefill
stalls set. Reported per layer, beside the end-to-end metrics, because it
jumps between the gaps stalled by a 1024-token prefill (about 118 ms) and
by a 1536-token one (about 150 ms) as a few steps admit two requests;
moves ttft_p90_ms, which the same stalls delay."""


def read(trace, counts, cell):
    return counts.get("itl_p99_ms")
