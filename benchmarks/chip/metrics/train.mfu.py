"""Model FLOPs per second of training (6 per matrix weight and token, and
the mLSTM's memory work; recomputation not counted), over the bf16 peak of
the chips used (%). Reads the harness's counts; moves tok_s."""
import counters


def read(trace, counts, cell):
    if not counts.get("tokens"):
        return None
    pk = counters.peaks(counts["device_kind"])
    per_token = counters.train_flops_per_token(cell.config, cell.base)
    rate = per_token * counts["tokens"] / counts["window_s"]
    return 100.0 * rate / (pk["bf16_flop_per_s"] * counts["chips"])
