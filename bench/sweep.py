"""Find the knee of an open-loop serve cell: the highest offered rate at
which the backlog does not grow over the window.

    python3 bench/sweep.py --workload <name> --rates 1,2,3 --seconds 40 --seed 5

One process: the cell's set-up once, then one window per rate, each on a
fresh run of the engine with the mix's arrivals at that rate. For each rate
it prints one JSON line: requests due and finished, the backlog (due but
not yet in a slot) at the window's end, the TTFT p50 and p95 in each half
of the window, and the output tokens per second. The benchmark's own runs
never run this; it is how the fixed rate in the mix file was found.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax
    import numpy as np

    from bench import run as harness
    from bench import serve_cell, weights
    from bench.engine_driver import EngineDriver
    from bench.traffic.generators import RequestStream
    from repro.serving import ServeEngine
    from repro.utils import cast_params_for_compute

    cell = harness.load_cell(args.workload)
    harness.check_device(jax, cell.chips)
    harness.enable_cache(jax)
    eng = cell.traffic["engine"]
    cfg = serve_cell.build_config(cell.config)
    params = cast_params_for_compute(weights.make(cfg, args.seed), cfg.act_dtype)
    engine = ServeEngine(params, cfg, max_len=eng["max_len"],
                         prefill_chunk=eng["prefill_chunk"],
                         temperature=cell.traffic["temperature"])
    serve_cell.warm_up(engine, eng["slots"], eng["prefill_chunk"], cfg.vocab)
    rates = [float(r) for r in args.rates.split(",")]
    for rate in rates:
        mix = copy.deepcopy(cell.traffic)
        mix["rate_per_s"] = rate
        stream = RequestStream(mix, args.seed)
        driver = EngineDriver(engine, eng["slots"], eng["prefill_chunk"])
        win = serve_cell._open_loop(driver, stream, cfg.vocab, args.seconds,
                                    serve_cell.Tracer(False, args.seconds))
        stats = driver.stats()
        backlog = sum(1 for rid, due in win.due.items()
                      if driver.admitted_at.get(rid, win.t1 + 1) > win.t1)
        halves = {}
        for name, lo, hi in (("first", win.t0, win.t0 + args.seconds / 2),
                             ("second", win.t0 + args.seconds / 2, win.t1)):
            t = []
            for rid, due in win.due.items():
                if lo <= due < hi:
                    walls = stats[rid]["token_walls"] if rid in stats else []
                    t.append((walls[0] if walls and walls[0] <= win.t1
                              else win.t1) - due)
            halves[name] = [float(np.percentile(t, 50)) * 1e3,
                            float(np.percentile(t, 95)) * 1e3] if t else None
        e2e = serve_cell.end_to_end(win, driver, args.seconds)
        print(json.dumps({
            "rate_per_s": rate, "due": len(win.due),
            "finished": sum(1 for r in win.due if r in stats
                            and stats[r]["finish"] is not None),
            "backlog_at_end": backlog, "ttft_p50_p95_ms": halves,
            "documents_due": sum(1 for r in win.reqs.values()
                                 if r["cls"] == "document"),
            "lateness_ms": win.lateness * 1e3, **e2e}), flush=True)
        if rate == rates[-1]:
            break
        # drain before the next rate, so each window starts empty
        t_drain = time.perf_counter()
        while driver.active() and time.perf_counter() - t_drain < 120:
            driver.tick()
        driver.finish()
    return 0


if __name__ == "__main__":
    sys.exit(main())
