"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  The roofline analysis
(benchmarks/roofline.py) reads the dry-run artifacts separately.
"""
from __future__ import annotations

import os
import sys
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def main() -> None:
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (
        ablations, fig5_traffic, fig6_batchsize, kernels_micro,
        table45_engines, table6_batching, table7_scaling,
    )
    print("name,us_per_call,derived")
    suites = [
        ("table4/5 engines", table45_engines.main),
        ("table6 batching", table6_batching.main),
        ("table7 scaling", table7_scaling.main),
        ("fig5 traffic", fig5_traffic.main),
        ("fig6 batch size", fig6_batchsize.main),
        ("paper-knob ablations", ablations.main),
        ("kernel micro", kernels_micro.main),
    ]
    failures = 0
    for name, fn in suites:
        try:
            for row in fn():
                print(row, flush=True)
        except Exception:
            failures += 1
            print(f"# SUITE FAILED: {name}", flush=True)
            traceback.print_exc()
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
