#!/usr/bin/env python3
"""The knee of an open-loop serving cell: its traffic at several fixed
rates, one run each, in one process on the chip.

    python3 benchmarks/chip/sweep.py --workload qwen3-4b.chat \\
        --rates 0.9 1.1 1.3 --seeds 5 6 --seconds 51 --out sweep.jsonl

One JSON line per rate and seed: the offered rate, tokens/s delivered,
the TTFT and inter-token percentiles, and how many requests never got a
first token. The knee is the highest rate whose TTFT tail stays flat, without a
backlog that grows through the window; the cell's rate is set at about
four fifths of it, by hand, in its traffic file.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.append(str(pathlib.Path(__file__).resolve().parent))

import bench  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", default=[424242])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    with open(out, "a") as f:
        for rate in args.rates:
            for seed in args.seeds:
                t0 = time.perf_counter()
                result = bench.run_cell(args.workload, seed, args.seconds, False,
                                        t_process=t0, keep_counts=True,
                                        traffic_changes={"rate_per_s": rate})
                line = {"workload": args.workload, "rate_per_s": rate, "seed": seed,
                        "metrics": {k: v["value"] for k, v in result["metrics"].items()},
                        "attempted": result["attempted"], "failed": result["failed"],
                        "correct": result["correct"], "checks": result["checks"],
                        "counts": result["counts"]}
                f.write(json.dumps(line) + "\n")
                f.flush()
                print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
