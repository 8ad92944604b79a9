"""bench/engine_driver.py on a reduced stlt-base, for about a second of
wall clock: due requests are admitted, each request's token stamps are
ordered and start after its due time, and the tick records add up."""
import time

import numpy as np

from bench import weights
from bench.engine_driver import EngineDriver
from bench.serve_cell import build_config
from bench.tests.conftest import tiny_cell


def test_driver_admits_due_requests_and_stamps_in_order():
    from repro.serving import ServeEngine

    cell = tiny_cell("stlt-base.chat-saturated")
    cfg = build_config(cell.config)
    engine = ServeEngine(weights.make(cfg, 3), cfg, max_len=512, prefill_chunk=64)
    d = EngineDriver(engine, slots=3, prefill_chunk=64)
    rng = np.random.default_rng(0)
    plan = [(i, 0.1 * i, int(rng.integers(8, 150)), int(rng.integers(2, 6)))
            for i in range(8)]
    due, sent, ticks = {}, 0, []
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < 1.0 or d.active():
        now = time.perf_counter()
        while sent < len(plan) and t0 + plan[sent][1] <= now:
            rid, _, plen, out = plan[sent]
            d.submit(rid, rng.integers(3, cfg.vocab, plen), out)
            due[rid] = now
            sent += 1
        if d.active():
            ticks.append(d.tick())
        else:
            time.sleep(0.005)
    results, stats = d.finish()
    assert sent == len(plan)
    for rid, _, plen, out in plan:
        assert rid in d.admitted_at and d.admitted_at[rid] >= due[rid]
        walls = stats[rid]["token_walls"]
        assert len(walls) == out == len(results[rid])
        assert walls == sorted(walls) and walls[0] > due[rid]
        assert stats[rid]["prompt_tokens"] == plen
    assert sum(t["prefill_tokens"] for t in ticks) == sum(p[2] for p in plan)
    assert sum(t["prompts_done"] for t in ticks) == len(plan)
    assert (sum(t["decoded"] for t in ticks) + len(plan)
            == sum(p[3] for p in plan))
    assert sorted(r for t in ticks for r in t["finished"]) == list(range(8))
