"""Benchmark entry point: one harness per paper table/figure.

  python -m benchmarks.run [--full] [--only lm_ppl,ablations,...]

Prints ``name,us_per_call,derived`` CSV rows (benchmarks.common.emit).
Default is the fast profile (CPU-friendly); --full runs the longer
trainings used for the EXPERIMENTS.md numbers.
"""
from __future__ import annotations

import argparse
import sys
import time

from repro.utils import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    enable_compile_cache()
    fast = not args.full
    only = set(args.only.split(",")) if args.only else None

    from benchmarks import (ablations, kernels, lm_ppl, longqa, roofline,
                            scaling, serving, translation)

    suites = {
        "scaling": lambda: scaling.main(fast=fast),          # §4.6
        "lm_ppl": lambda: lm_ppl.main(fast=fast),            # Table 1
        "translation": lambda: translation.main(fast=fast),  # Table 2
        "longqa": lambda: longqa.main(fast=fast),            # Table 3
        "ablations": lambda: ablations.main(fast=fast),      # Table 4
        "roofline": lambda: roofline.main(fast=fast),        # §Roofline
        "serving": lambda: serving.main(fast=fast),          # §Perf continuous batching
        "kernels": lambda: kernels.main(fast=fast),          # §Perf kernel layer
    }
    print("name,us_per_call,derived")
    t0 = time.time()
    for name, fn in suites.items():
        if only and name not in only:
            continue
        print(f"# --- {name} ---", flush=True)
        try:
            fn()
        except Exception as e:  # keep the harness alive; record the failure
            print(f"{name}/ERROR,0,{type(e).__name__}: {e}", flush=True)
            raise
    print(f"# total {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
