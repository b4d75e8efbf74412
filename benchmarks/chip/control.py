"""Readings of the check's numbers over many seeds in one process: the
program as it is, or the control.

The control is the program with every kernel contraction
(``onehot_dot`` in the scatter, query and update kernels) done as the
single bf16 pass that Mosaic uses by default for f32 operands, the
precision step below the fp32 tables the configuration states.  It has to
come out not correct.

    python3 benchmarks/chip/control.py --workload <name> --seconds <s> \\
        --seeds 11,12,13 [--control 1]

Prints one JSON line per seed with its compared numbers.
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

KERNEL_MODULES = ("repro.kernels.countsketch_scatter",
                  "repro.kernels.countsketch_query",
                  "repro.kernels.countsketch_update")


def bf16_dot(x, onehot, dimension_numbers):
    import jax
    import jax.numpy as jnp

    return jax.lax.dot_general(x.astype(jnp.bfloat16),
                               onehot.astype(jnp.bfloat16), dimension_numbers,
                               preferred_element_type=jnp.float32)


def use_control(on: bool = True) -> None:
    """Swap the kernels' contraction for ``bf16_dot`` (or back) and drop
    every compiled program, so the next call traces the swapped one."""
    import importlib

    import jax

    from repro.kernels import onehot

    for name in KERNEL_MODULES:
        mod = importlib.import_module(name)
        mod.onehot_dot = bf16_dot if on else onehot.onehot_dot
    jax.clear_caches()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness

    if args.control:
        use_control()
    for seed in [int(s) for s in args.seeds.split(",")]:
        r = harness.run(os.path.join(ROOT, "BENCHMARK.json"), args.workload,
                        seed, args.seconds, False,
                        log=lambda m: print(f"[control] {m}", file=sys.stderr))
        print(json.dumps({"seed": seed, "control": args.control,
                          "correct": r["correct"], "metrics": r["metrics"],
                          "checks": r["checks"], "info": r["info"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
