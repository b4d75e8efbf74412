"""Benchmark harness entrypoint -- one module per paper table/figure.

Usage: PYTHONPATH=src python -m benchmarks.run [--fast]
Prints ``name,us_per_call,derived`` CSV rows (paper-reference values inline
where the paper reports them).
"""
import argparse
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="fewer Monte Carlo runs")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from . import (comm_volume, engine_throughput, fig1_wor_vs_wr,
                   fig2_rankfreq, fleet_load, gradcomp_comm,
                   ingest_pipeline, psi_calibration, sketch_throughput,
                   table3_nrmse)
    from .common import emit

    rows = []
    print("== Table 3: NRMSE of frequency-moment estimates ==")
    rows += table3_nrmse.run(runs=10 if args.fast else 40, verbose=False)
    emit(rows[-5:])
    print("== Figure 1: WOR vs WR ==")
    r = fig1_wor_vs_wr.run(verbose=False); rows += r; emit(r)
    print("== Figure 2: rank-frequency estimates ==")
    r = fig2_rankfreq.run(verbose=False); rows += r; emit(r)
    print("== Appendix B.1: Psi calibration ==")
    r = psi_calibration.run(verbose=False); rows += r; emit(r)
    print("== Sketch data-plane throughput ==")
    r = sketch_throughput.run(verbose=False); rows += r; emit(r)
    print("== SketchEngine batched multi-stream throughput ==")
    r = engine_throughput.run(verbose=False, fast=args.fast)
    rows += r; emit(r)
    print("== Sharded prefetching ingestion pipeline ==")
    r = ingest_pipeline.run(verbose=False, fast=args.fast)
    rows += r; emit(r)
    print("== Multi-process serving fleet load ==")
    r = fleet_load.run(verbose=False, fast=args.fast)
    rows += r; emit(r)
    print("== Wire-codec communication volume ==")
    r = comm_volume.run(verbose=False, fast=args.fast)
    rows += r; emit(r)
    print("== WORp gradient compression (Sec. 1 application) ==")
    r = gradcomp_comm.run(verbose=False); rows += r; emit(r)
    print(f"== {len(rows)} benchmark rows done ==")


if __name__ == "__main__":
    main()
