"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

Run from the root of a checkout.  Exits 2 and prints no result where JAX
finds no TPU or fewer chips than the cell asks for.  The last line of
standard output is the result object; the numbers the check compared,
each with its limit, are the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness

    try:
        result = harness.run(os.path.join(ROOT, "BENCHMARK.json"),
                             args.workload, args.seed, args.seconds,
                             bool(args.trace), t_start=T_START, log=log)
    except harness.NoChip as e:
        log(f"no result: {e}")
        return 2
    print(json.dumps(result), flush=True)
    harness.print_checks(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
