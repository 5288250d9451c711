"""Roofline share of the paged decode step (%): the least time its steps
in the window could take on this chip (the larger of their FLOPs over the
bf16 peak and their bytes, weights and live KV, over the HBM bandwidth),
over the device time of those steps. Reads the trace and the harness's
per-step lane and context counts; moves tok_s."""
import counters


def read(trace, counts, cell):
    runs = trace.program_runs(r"paged_decode_step")
    if not runs or not counts.get("steps"):
        return None
    pk = counters.peaks(counts["device_kind"])
    t_min = 0.0
    for decoded, context in counts["steps"]:
        need = counters.decode_step(cell.config, decoded, context, cell.base)
        t_min += max(need["flops"] / pk["bf16_flop_per_s"], need["bytes"] / pk["hbm_bytes_per_s"])
    return 100.0 * t_min / trace.program_seconds(r"paged_decode_step")
