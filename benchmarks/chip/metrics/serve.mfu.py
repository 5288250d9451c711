"""Model FLOPs the engine did in the window (2 per weight and token of
every decode step and prefill, plus attention over the live context), over
the window's length times the chip's bf16 peak (%). Reads the harness's
counts and the trace's window; moves tok_s."""
import counters


def read(trace, counts, cell):
    if not counts.get("steps"):
        return None
    pk = counters.peaks(counts["device_kind"])
    flops = sum(counters.decode_step(cell.config, d, c, cell.base)["flops"]
                for d, c in counts["steps"])
    flops += sum(counters.prefill_flops(cell.config, p, cell.base) for p in counts["prefill_lens"])
    return 100.0 * flops / (counts["window_s"] * pk["bf16_flop_per_s"])
