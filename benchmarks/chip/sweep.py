"""Find a cell's knee: the same open-loop cell at several ingest rates, in
one process, each with its read latencies and ingest lateness.

    python3 benchmarks/chip/sweep.py --workload <name> --seed <n> \\
        --seconds <s> --rates 100000,200000,300000

The knee is the highest rate at which the ingest lateness of the window's
last quarter is not above that of its first quarter by more than a tenth
of a second.  Prints one JSON line per rate.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    import harness

    for rate in [float(r) for r in args.rates.split(",")]:
        r = harness.run(os.path.join(ROOT, "BENCHMARK.json"), args.workload,
                        args.seed, args.seconds, False,
                        log=lambda m: print(f"[sweep] {m}", file=sys.stderr),
                        mix={"events_per_s": rate})
        late = r["lateness_s"]
        print(json.dumps({"events_per_s": rate, "correct": r["correct"],
                          "metrics": r["metrics"], "lateness_s": late,
                          "growing": bool(late and late[1] - late[0] > 0.1)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
