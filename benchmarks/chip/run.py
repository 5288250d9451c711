#!/usr/bin/env python3
"""The chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 benchmarks/chip/run.py --workload qwen3-4b.chat --seed 7 \\
        --seconds 30 --trace 0

Run it from the root of a checkout, on a machine that holds the chips the
cell asks for. It exits non-zero and prints no result where JAX finds no
TPU, fewer chips than the cell needs, or no program beside the benchmark.
Otherwise the last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``); the last lines of standard error are
the checks, each number beside its limit. See ``bench.py``.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.append(str(pathlib.Path(__file__).resolve().parent))

import bench  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, help="a cell's name in BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True, help="length of the window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = bench.run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                            t_process=T_PROCESS)
    bench.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
