#!/usr/bin/env python3
"""Readings that the limits in ``limits/<cell>.json`` are set from.

    python3 benchmarks/chip/calibrate.py --workload qwen3-4b.chat \\
        --seeds 12 --control-seeds 3 --seconds 30 --out calib.jsonl

Runs the cell on the chip, in one process, once for each of ``--seeds``
seeds, each a whole run at the cell's own sizes and load; the first
``--control-seeds`` of them also read the control (the reference computed
with float8 matmuls, put in the program's place and judged by the same
check: ``control_correct`` has to come out false) and, for a training
cell, the reference with half of each batch left out. One JSON line per
seed: the numbers compared for the program and for the control. The
benchmark's own runs never run the control.
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
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=1_000_003)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    with open(out, "a") as f:
        for i in range(args.seeds):
            seed = args.first_seed + 7919 * i
            t0 = time.perf_counter()
            result = bench.run_cell(args.workload, seed, args.seconds, False,
                                    t_process=t0, control=i < args.control_seeds,
                                    keep_counts=True)
            line = {"workload": args.workload, "seed": seed,
                    "correct": result.get("program_correct", result["correct"]),
                    "checks": result.get("program_checks", result["checks"]),
                    "control_correct": result["correct"] if "program_checks" in result else None,
                    "control_checks": result["checks"] if "program_checks" in result else None,
                    "counts": result["counts"], "metrics": result["metrics"],
                    "memory_peak_bytes": result["device"]["memory_peak_bytes"],
                    "seconds": time.perf_counter() - t0}
            f.write(json.dumps(line) + "\n")
            f.flush()
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
