"""Readings behind the limits of the correctness check, on the chip.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 --seconds 5

Runs the cell once per seed, all in one process, and prints one JSON line
per seed with the numbers its check compared (the program's readings: the
lower ends of the limits) and the control's (the upper ends):

* serve cells: the control is the plain reference one precision step below
  the configuration's (``references.stlt_lm.control_for``) put in the
  program's place, read on the same prompts and served tokens: at each
  position, the gap of the token that the lower precision puts first.
* train cells: the control trains the same steps in that lower precision;
  the fault ``half_batch`` trains on half of each batch, the mean taken over
  the rest. (A step that returns its state unchanged reads 1 on the change
  by the check's own measure and needs no run.)

The benchmark's own runs never run this; it is how the limits in
``bench/limits/`` were read.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax

    from bench import run as harness

    cell = harness.load_cell(args.workload)
    device = harness.check_device(jax, cell.chips)
    harness.enable_cache(jax)
    cell.compiles = harness.CompileCounter(jax)
    peak = harness.peak_of(device["kind"])
    for seed in (int(s) for s in args.seeds.split(",")):
        cell.seed, cell.seconds, cell.t_start = seed, args.seconds, time.perf_counter()
        if cell.traffic["kind"] == "serve":
            from bench import serve_cell

            with harness.matmul_precision(jax, cell):
                out = serve_cell.run(cell, peak, control=True)
            row = {"program": out["readings"], "control": out.get("control"),
                   "checks": out["checks"]}
        else:
            with harness.matmul_precision(jax, cell):
                row = out = _train(cell, peak)
        row.update(seed=seed, metrics=out.get("metrics"),
                   memory_peak_bytes=out.get("device", {}).get("memory_peak_bytes"))
        print(json.dumps(row, default=float), flush=True)
    return 0


def _train(cell, peak) -> dict:
    from bench import train_cell, weights as weights_lib
    from bench.references import stlt_lm
    from bench.serve_cell import build_config

    out = train_cell.run(cell, peak)
    tr, m = cell.traffic, cell.config["model"]
    cfg = build_config(cell.config)
    feed = train_cell.make_batches(cfg.vocab, tr["batch"], tr["seq_len"],
                                   tr["zipf_exponent"], cell.seed)
    n = tr["check"]["steps"]
    batches = [feed(s) for s in range(n)]
    W = weights_lib.make(cfg, cell.seed, served=False, embed_std=tr["embed_std"])
    exact = stlt_lm.train_steps(W, m, tr["optimizer"], batches)
    low = stlt_lm.control_for(m["dtype"], cell.config["matmul_precision"])
    ctrl = stlt_lm.train_steps(W, m, tr["optimizer"], batches, prec=low)
    half = [{k: v[: v.shape[0] // 2] for k, v in b.items()} for b in batches]
    fault = stlt_lm.train_steps(W, m, tr["optimizer"], half)
    return {"program": out["checks"],
            "control": {"precision": low.name,
                        **train_cell.compare(ctrl, exact)},
            "half_batch": train_cell.compare(fault, exact),
            "metrics": out["metrics"], "device": out["device"]}


if __name__ == "__main__":
    sys.exit(main())
