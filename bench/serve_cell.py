"""Serve cells: a ``ServeEngine`` driven from the wall clock by a traffic mix.

Set-up makes the weights from the seed on the device (one jitted call, in
the type they are served in), builds the engine, and warms exactly the
shapes the window uses: a lone admission over two chunks (``[1, chunk]``,
resumed), then every slot admitted at once (``[slots, chunk]``, and the
splice, extract, reset and sampling at every slot index), each decoding
through the ``[slots]`` decode step. Then the window runs for ``seconds``:

* closed loop (``loop: closed``): ``clients`` callers each send their next
  request the moment the previous reply is complete;
* open loop (``loop: open``): requests are due on the mix's arrival
  schedule, are queued once due whatever the backlog, and the loop sleeps
  only when the engine has no work.

The check that decides ``correct`` runs once the window has closed, the
peak memory has been read and the engine is gone: a sample of finished
requests, drawn from the seed with the longest among them, goes through the
plain reference (``bench/references``) over its prompt and served tokens,
and ``served_logit_error`` (the widest shortfall of a served token below the
reference's best, and the largest departure of the logits the timed path
produced, in units of the reference row's spread) is held to the cell's
limit.
"""
from __future__ import annotations

import dataclasses
import gc
import shutil
import tempfile
import time

import numpy as np

from bench import flops as flops_lib
from bench import trace_reduce
from bench import weights as weights_lib
from bench.engine_driver import EngineDriver
from bench.run import log, memory_peak_bytes, metric_reader
from bench.traffic.generators import RequestStream

WARM_ID = 10 ** 9
CAPTURE_EVERY = 16      # keep the decode logits of every 16th decode tick


def build_config(conf: dict):
    """The ModelConfig a configuration file states."""
    from repro import configs as configs_lib

    return dataclasses.replace(
        configs_lib.get_config(conf["arch"], conf["variant"]), **conf["model"])


def warm_up(engine, slots: int, chunk: int, vocab: int) -> None:
    """Run every program and host path of the window once: a lone
    two-chunk admission, a prefill-only request, then all slots at once."""
    rng = np.random.default_rng(0)
    d = EngineDriver(engine, slots, chunk)
    d.submit(WARM_ID, rng.integers(3, vocab, chunk + 1), 3)
    while d.active():
        d.tick()
    d.submit(WARM_ID - 1, rng.integers(3, vocab, 2), 1)
    while d.active():
        d.tick()
    for i in range(slots):
        d.submit(WARM_ID + 1 + i, rng.integers(3, vocab, max(1, chunk // 2)), 2)
    while d.active():
        d.tick()


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


@dataclasses.dataclass
class Window:
    t0: float
    t1: float
    due: dict                    # id -> due time (host clock)
    reqs: dict                   # id -> request dict from the stream
    ticks: list                  # driver.tick() records
    lateness: float = 0.0        # how late the generator queued a request


def _closed_loop(driver, stream, vocab, clients, seconds, tracer) -> Window:
    t0 = time.perf_counter()
    win = Window(t0, t0 + seconds, {}, {}, [])

    def send(now):
        req = stream.next()
        driver.submit(req["id"], stream.tokens(req, vocab), req["output_len"])
        win.due[req["id"]] = now
        win.reqs[req["id"]] = req

    import jax

    with jax.profiler.TraceAnnotation("bench.client"):
        for _ in range(clients):
            send(t0)
    while True:
        now = time.perf_counter()
        if now >= win.t1:
            break
        tracer.poll(now - t0)
        with jax.profiler.TraceAnnotation("bench.tick"):
            info = driver.tick()
        win.ticks.append(info)
        if info["finished"]:
            with jax.profiler.TraceAnnotation("bench.client"):
                stats = driver.stats()
                for rid in info["finished"]:
                    send(stats[rid]["token_walls"][-1])
    tracer.close()
    return win


def _open_loop(driver, stream, vocab, seconds, tracer) -> Window:
    import jax

    schedule = stream.until(seconds)
    t0 = time.perf_counter()
    win = Window(t0, t0 + seconds, {}, {}, [])
    i = 0
    while True:
        now = time.perf_counter()
        if now >= win.t1:
            break
        tracer.poll(now - t0)
        while i < len(schedule) and t0 + schedule[i]["due_s"] <= now:
            req = schedule[i]
            due = t0 + req["due_s"]
            with jax.profiler.TraceAnnotation("bench.client"):
                driver.submit(req["id"], stream.tokens(req, vocab),
                              req["output_len"])
            win.lateness = max(win.lateness, time.perf_counter() - due)
            win.due[req["id"]] = due
            win.reqs[req["id"]] = req
            i += 1
        if driver.active():
            with jax.profiler.TraceAnnotation("bench.tick"):
                win.ticks.append(driver.tick())
        else:
            nxt = t0 + schedule[i]["due_s"] if i < len(schedule) else win.t1
            with jax.profiler.TraceAnnotation("bench.wait_arrival"):
                time.sleep(max(0.0, min(nxt, win.t1, tracer.next_event(t0))
                               - time.perf_counter()))
    tracer.close()
    return win


class Tracer:
    """Profiles ``[start, start + length)`` seconds of the window (only
    with ``--trace 1``), into a temporary directory."""

    def __init__(self, on: bool, seconds: float):
        self.on = on
        self.start = min(2.0, 0.2 * seconds)
        self.length = min(6.0, seconds - self.start)
        self.state = "before"
        self.host = (None, None)
        self.dir = tempfile.mkdtemp(prefix="bench_trace_") if on else None
        self._ann = None

    def next_event(self, t0: float) -> float:
        if not self.on or self.state == "done":
            return float("inf")
        return t0 + (self.start if self.state == "before"
                     else self.start + self.length)

    def poll(self, elapsed: float) -> None:
        import jax

        if not self.on:
            return
        if self.state == "before" and elapsed >= self.start:
            jax.profiler.start_trace(self.dir)
            self._ann = jax.profiler.TraceAnnotation("bench.window")
            self._ann.__enter__()
            self.host = (time.perf_counter(), None)
            self.state = "tracing"
        elif self.state == "tracing" and elapsed >= self.start + self.length:
            self.close()

    def close(self) -> None:
        import jax

        if self.state == "tracing":
            self.host = (self.host[0], time.perf_counter())
            self._ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.state = "done"

    def cleanup(self) -> None:
        if self.dir:
            shutil.rmtree(self.dir, ignore_errors=True)


def end_to_end(win: Window, driver, seconds: float) -> dict:
    stats = driver.stats()
    t0, t1 = win.t0, win.t1
    n_tok, gaps, ttft = 0, [], []
    for rid, due in win.due.items():
        walls = stats[rid]["token_walls"] if rid in stats else []
        n_tok += sum(t0 <= w <= t1 for w in walls)
        gaps.extend(b - a for a, b in zip(walls, walls[1:]) if t0 <= b <= t1)
        if t0 <= due <= t1:
            first = walls[0] if walls and walls[0] <= t1 else t1
            ttft.append(first - due)
    out = {"output_tokens_per_s": n_tok / seconds}
    if gaps:
        out["itl_p95_ms"] = percentile(gaps, 95) * 1e3
    if ttft:
        out["ttft_p95_ms"] = percentile(ttft, 95) * 1e3
    return out


def scheduler_counts(win: Window, driver, slots: int, span) -> dict:
    """Occupancy of the decode ticks and queue waits of the requests due in
    the host interval ``span`` (the traced part: stopping the profiler
    stalls the loop after it)."""
    lo, hi = span
    occ = [t["decoded"] / slots for t in win.ticks
           if t["decoded"] and lo <= t["t0"] < hi]
    waits = [driver.admitted_at[rid] - due for rid, due in win.due.items()
             if rid in driver.admitted_at and lo <= due < hi]
    return {"occupancy": float(np.mean(occ)) * 100 if occ else None,
            "queue_wait_ms": float(np.mean(waits)) * 1e3 if waits else None}


def traced_obs(cell, tracer, win, driver, m, peak) -> tuple[dict, dict, dict]:
    """(per-layer metrics, device busy and window, breakdown) from the
    trace of the window."""
    tr = trace_reduce.load(tracer.dir)
    within = trace_reduce.window(tr)
    h0, h1 = tracer.host
    counted = [t for t in win.ticks if h0 <= t["t0"] < h1]
    obs = {
        "trace": tr, "window": within,
        "ticks": trace_reduce.intersect(
            trace_reduce.union(tr.spans.get("bench.tick", [])), within),
        "sched": scheduler_counts(win, driver, cell.traffic["engine"]["slots"],
                                  tracer.host),
        "traced": {k: sum(t[k] for t in counted)
                   for k in ("prefill_tokens", "prompts_done", "decoded")},
        "model": m, "peak": peak, "flops": flops_lib,
    }
    metrics = {}
    for spec in cell.per_layer:
        v = metric_reader(spec["name"])(obs)
        if v is not None:
            metrics[spec["name"]] = v
    device = {"busy_s": trace_reduce.busy_s(tr, within),
              "window_s": trace_reduce.length(within)}
    breakdown = {"device_ops": trace_reduce.top_ops(tr, within),
                 "idle_gaps": trace_reduce.idle_gaps(tr, within)}
    return metrics, device, breakdown


def sample_for_check(cell, win: Window, results, stats) -> list:
    """Finished requests to check: the longest, and others drawn from the seed."""
    n = cell.traffic["check"]["requests"]
    done = [rid for rid in win.due if rid in stats
            and stats[rid]["finish"] is not None]
    if not done:
        return []
    size = {rid: win.reqs[rid]["prompt_len"] + len(results[rid]) for rid in done}
    longest = max(done, key=lambda r: (size[r], -r))
    rest = sorted(r for r in done if r != longest)
    rng = np.random.default_rng([cell.seed % 2 ** 63, 3])
    picked = rng.choice(rest, size=min(n - 1, len(rest)), replace=False) \
        if rest else []
    return [longest] + sorted(int(r) for r in picked)


def run(cell, peak: dict, control: bool = False) -> dict:
    """One run of a serve cell. ``control`` also reads the control's
    numbers (the reference one precision step lower, in the program's
    place)."""
    import jax

    from repro.serving import ServeEngine
    from repro.utils import cast_params_for_compute

    mix, eng = cell.traffic, cell.traffic["engine"]
    cfg = build_config(cell.config)
    m = cell.config["model"]
    params = cast_params_for_compute(weights_lib.make(cfg, cell.seed),
                                     cfg.act_dtype)
    jax.block_until_ready(params)
    engine = ServeEngine(params, cfg, max_len=eng["max_len"],
                         prefill_chunk=eng["prefill_chunk"],
                         temperature=mix["temperature"])
    stream = RequestStream(mix, cell.seed)
    warm_up(engine, eng["slots"], eng["prefill_chunk"], cfg.vocab)
    setup_s = time.perf_counter() - cell.t_start
    compiled = cell.compiles.total()
    log(f"set-up {setup_s:.3f} s: {cell.compiles.compiled} compiled, "
        f"{cell.compiles.cache_hits} from the cache")

    driver = EngineDriver(engine, eng["slots"], eng["prefill_chunk"],
                          rng_seed=cell.seed % 2 ** 31,
                          capture_every=CAPTURE_EVERY)
    tracer = Tracer(cell.trace, cell.seconds)
    if mix["loop"] == "closed":
        win = _closed_loop(driver, stream, cfg.vocab, mix["clients"],
                           cell.seconds, tracer)
    else:
        win = _open_loop(driver, stream, cfg.vocab, cell.seconds, tracer)
    in_window = cell.compiles.total() - compiled
    results, stats = driver.finish()
    log(f"window: {len(win.due)} requests due, {len(win.ticks)} ticks, "
        f"{in_window} programs compiled or loaded inside it; generator at "
        f"most {win.lateness * 1e3:.3f} ms late")
    out = {"metrics": end_to_end(win, driver, cell.seconds),
           "device": {"memory_peak_bytes": memory_peak_bytes(jax)}}
    out["metrics"]["setup_s"] = setup_s
    if cell.trace:
        metrics, dev, breakdown = traced_obs(cell, tracer, win, driver, m, peak)
        out["metrics"], out["breakdown"] = metrics, breakdown
        out["device"].update(dev)
        tracer.cleanup()

    bad = [rid for rid, st in stats.items() if st["finish"] is not None
           and rid in win.reqs and len(results[rid]) != win.reqs[rid]["output_len"]]
    check_ids = sample_for_check(cell, win, results, stats)
    checked = [(stream.tokens(win.reqs[r], cfg.vocab), results[r],
                driver.produced_logits(r)) for r in check_ids]
    del driver, engine, params, stats
    gc.collect()
    out.update(check_served(cell, cfg, checked, control))
    out["attempted"] = len(win.due)
    out["failed"] = len(bad)
    out["correct"] = out["correct"] and not bad
    log(f"checked {len(checked)} requests, "
        f"{sum(len(c[1]) for c in checked)} served tokens, "
        f"{sum(len(c[2]) for c in checked)} logit rows")
    return out


def check_served(cell, cfg, checked, control: bool = False) -> dict:
    """The reference's readings of the checked requests; the numbers named
    in the cell's limits file are compared, each with its limit."""
    from bench.references import stlt_lm

    conf, mix = cell.config, cell.traffic
    W = weights_lib.make(cfg, cell.seed)
    m = dict(conf["model"], prefill_chunk=mix["engine"]["prefill_chunk"])
    longest = max(c["output"]["max"] for c in mix["classes"])
    low = (stlt_lm.control_for(m["dtype"], conf["matmul_precision"])
           if control else None)
    got = stlt_lm.serve_readings(W, m, checked, -(-longest // 128) * 128,
                                 low) if checked else {}
    checks, ok = {}, bool(checked)
    for name, limit in cell.limits.items():
        v = got.get(name)
        checks[name] = {"value": v, "limit": limit}
        ok = ok and v is not None and limit is not None and v <= limit
    out = {"correct": bool(ok), "checks": checks}
    if control:
        out["readings"] = {k: v for k, v in got.items() if k != "control"}
        out["control"] = got.get("control")
    return out
