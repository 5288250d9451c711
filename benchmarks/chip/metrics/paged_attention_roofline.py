"""Roofline share of the paged decode attention kernel (%): the least time
the window's decode steps could spend in it on this chip (per step, the
larger of its FLOPs over the bf16 peak and the live keys and values it
reads, every layer's of each position a lane attends over, over the HBM
bandwidth), over the device time of the ``paged_attention`` custom calls
inside the paged decode step. Reads the trace and the harness's per-step
context counts; nothing where the kernel is not on the path (the gather
runs instead); moves tok_s."""
import counters


def read(trace, counts, cell):
    t = trace.op_seconds(r"paged_decode_step", r"paged_attention.*custom-call")
    if t <= 0.0 or not counts.get("steps"):
        return None
    pk = counters.peaks(counts["device_kind"])
    kv = counters.kv_bytes_per_token(cell.config)
    t_min = sum(max(counters.attention_flops(cell.config, context) / pk["bf16_flop_per_s"],
                    kv * context / pk["hbm_bytes_per_s"]) for _, context in counts["steps"])
    return 100.0 * t_min / t
