"""Where a serve cell's tick spends its host time, phase by phase.

    python3 bench/tick_phases.py --workload <serve cell> --seed <n> \
        --seconds <s> --profile <0|1> [--python-tracer <0|1>]

Runs a serve cell of ``BENCHMARK.json`` as ``bench/serve_cell.py`` does
(same weights, engine, warm-up, traffic and window, and the same traced part
of the window), without the correctness check, and prints one JSON line:

* ``engine``: the engine's ``tick_stats`` (``repro.serving.trace``) over
  the traced part of the window, per tick: host ms by phase, syncs, the
  share of prefill token slots that carried prompt tokens, live rows per
  decode dispatch; and the window's longest tick with its phase split.
* ``gc``: Python's garbage-collector pauses inside the window.
* ``window``: requests due, ticks, programs compiled or loaded inside the
  window, and the end-to-end numbers of ``serve_cell.end_to_end``.
* with ``--profile 1``, ``trace``: the device's busy and idle time in the
  traced part, the idle time put down to the innermost host span that holds
  it (``serve.*`` phases inside the harness's ``bench.*`` spans), and
  ``tick_host_ms``: the mean ``serve.tick`` span less its ``serve.sync``
  spans.

With ``--profile 0`` the same counters are read with the profiler off, which
is what the phases cost an untraced run. A program without ``tick_stats``
gives no ``engine`` numbers and no ``serve.*`` spans, and the rest as usual.

The reductions (:func:`load`, :func:`innermost`, :func:`idle_gaps`,
:func:`tick_host_ms`, :func:`engine_counts`) take no chip and are tested on
recorded traces (``bench/tests/test_tick_phases.py``).
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

if __name__ == "__main__":
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for _p in (os.path.join(_ROOT, "src"), _ROOT):
        if _p not in sys.path:
            sys.path.insert(0, _p)

from bench import trace_reduce

PHASE_PREFIX = "serve."
TICK, SYNC = "serve.tick", "serve.sync"
WINDOW = "bench.window"


# --------------------------------------------------------------- reductions
def serve_spans(path: str) -> dict:
    """{name: [(t0, t1)]} of the ``serve.*`` host spans of a trace (an
    ``.xplane.pb`` file or a profile directory), on the host's clock like
    ``trace_reduce.load``'s spans."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    spans: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PHASE_PREFIX):
                    spans.setdefault(e.name, []).append(
                        (e.start_ns * 1e-9, e.end_ns * 1e-9))
    return {k: sorted(v) for k, v in spans.items()}


def load(path: str) -> trace_reduce.Trace:
    """``trace_reduce.load`` with the engine's ``serve.*`` spans added to
    the benchmark's ``bench.*`` spans."""
    tr = trace_reduce.load(path)
    spans = dict(tr.spans)
    spans.update(serve_spans(path))
    return trace_reduce.Trace(tr.ops_by_device, tr.modules, spans)


def innermost(spans: dict, skip=(WINDOW,)) -> dict:
    """{name: disjoint sorted intervals in which a span of that name is the
    innermost span open}. Spans of one thread nest; one that starts inside
    another and outlasts it is cut at the other's end. A name whose spans
    never hold another keeps exactly the union of its spans."""
    events = sorted((t0, -t1, name) for name, ivs in spans.items()
                    if name not in skip for t0, t1 in ivs)
    pieces: dict = {name: [] for name in spans if name not in skip}
    stack: list = []                   # [name, end, start of its open piece]

    def pop():
        name, end, at = stack.pop()
        if at < end:
            pieces[name].append((at, end))
        if stack:
            stack[-1][2] = end

    for t0, neg_t1, name in events:
        t1 = -neg_t1
        while stack and stack[-1][1] <= t0:
            pop()
        if stack:
            t1 = min(t1, stack[-1][1])
            if stack[-1][2] < t0:
                pieces[stack[-1][0]].append((stack[-1][2], t0))
            stack[-1][2] = t1
        stack.append([name, t1, t0])
    while stack:
        pop()
    return {name: trace_reduce.union(ivs) for name, ivs in pieces.items()}


def idle_gaps(trace: trace_reduce.Trace, within, n: int = 16) -> list:
    """[[host span, seconds]]: the device's idle time inside ``within``,
    each interval put down to the innermost span that holds it (a
    ``serve.*`` phase inside ``bench.tick``, or a ``bench.*`` span where no
    phase is open), longest first; "none" for idle time outside every span.
    Measured on the first chip. Where no spans nest it reads what
    ``trace_reduce.idle_gaps`` reads."""
    if not trace.ops_by_device or not within:
        return []
    busy = trace_reduce.union(trace.ops_by_device[0])
    edges = [within[0][0]] + [t for iv in busy for t in iv] + [within[-1][1]]
    free = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if a < b]
    idle = trace_reduce.intersect(free, within)
    own = innermost(trace.spans)
    tot = {name: trace_reduce.length(trace_reduce.intersect(idle, ivs))
           for name, ivs in own.items()}
    tot["none"] = trace_reduce.length(idle) - sum(tot.values())
    ranked = sorted(((k, v) for k, v in tot.items() if v > 0),
                    key=lambda kv: -kv[1])[:n]
    return [[k, v] for k, v in ranked]


def tick_host_ms(trace: trace_reduce.Trace, within):
    """Mean host time of the engine's ticks that start inside ``within``:
    each ``serve.tick`` span less the time inside its ``serve.sync``
    spans, in ms (None without ticks)."""
    ticks = [iv for iv in trace.spans.get(TICK, [])
             if trace_reduce.inside(iv[0], within)]
    if not ticks:
        return None
    syncs = trace_reduce.union(trace.spans.get(SYNC, []))
    held = trace_reduce.intersect(trace_reduce.union(ticks), syncs)
    host = sum(t1 - t0 for t0, t1 in ticks) - trace_reduce.length(held)
    return host / len(ticks) * 1e3


def engine_counts(before: dict, after: dict) -> dict:
    """What the engine counted between two copies of its ``tick_stats``,
    as rates: host ms a tick by phase, syncs a tick, prefill valid share
    (%), live rows per decode dispatch."""
    from repro.serving import trace as phases

    d = {k: after[k] - before[k] for k in phases.COUNTERS + phases.PHASES}
    ticks = d["ticks"]
    out = {"ticks": ticks}
    if ticks:
        out["host_ms_by_phase"] = {p: d[p] / ticks * 1e3
                                   for p in phases.PHASES}
        out["host_ms"] = sum(d[p] for p in phases.PHASES) / ticks * 1e3
        out["host_ms_less_sync"] = (out["host_ms"]
                                    - out["host_ms_by_phase"][SYNC])
        out["host_syncs_per_tick"] = d["host_syncs"] / ticks
    if d["prefill_slot_tokens"]:
        out["prefill_valid_share"] = (100.0 * d["prefill_valid_tokens"]
                                      / d["prefill_slot_tokens"])
        out["prefill_dispatches"] = d["prefill_dispatches"]
    if d["decode_dispatches"]:
        out["live_rows_per_decode"] = (d["decode_live_rows"]
                                       / d["decode_dispatches"])
    return out


# --------------------------------------------------------------- on the chip
def phase_cost_us(n: int = 20000) -> float:
    """Host microseconds that one engine phase costs with the profiler off:
    its span entered and left and its seconds added up."""
    from repro.serving.trace import TickTrace

    tt = TickTrace()
    t0 = time.perf_counter()
    for _ in range(n):
        with tt.phase("serve.admit"):
            pass
    return (time.perf_counter() - t0) / n * 1e6


class GcPauses:
    """Pauses of Python's garbage collector while installed
    (``gc.callbacks``): count, seconds, longest, by generation."""

    def __init__(self):
        self.count, self.seconds, self.longest = 0, 0.0, 0.0
        self.by_generation: dict = {}
        self._t0 = None

    def __call__(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            dt = time.perf_counter() - self._t0
            self._t0 = None
            self.count += 1
            self.seconds += dt
            self.longest = max(self.longest, dt)
            g = str(info.get("generation"))
            self.by_generation[g] = self.by_generation.get(g, 0.0) + dt

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)
        return False

    def report(self) -> dict:
        return {"collections": self.count, "seconds": self.seconds,
                "longest_s": self.longest, "by_generation": self.by_generation}


def _copy_stats(engine):
    stats = getattr(engine, "tick_stats", None)
    return None if stats is None else {
        k: (dict(v) if isinstance(v, dict) else v) for k, v in stats.items()}


def _marking_tracer(tracer_cls, engine, python_tracer: bool):
    """``tracer_cls`` (the serve cell's tracer) that also copies the
    engine's ``tick_stats`` where the traced part of the window starts and
    where it ends, with the profiler on or off. Without ``python_tracer``
    the profiler records no Python function calls, only spans and the
    runtime's own events (the serve cell's tracer records both)."""
    import jax

    class Marking(tracer_cls):
        def __init__(self, on: bool, seconds: float):
            super().__init__(on, seconds)
            self.marks: list = []      # [(host time, tick_stats copy)]

        def poll(self, elapsed: float) -> None:
            if (self.on and not python_tracer and self.state == "before"
                    and elapsed >= self.start):
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(self.dir, profiler_options=opts)
                self._ann = jax.profiler.TraceAnnotation("bench.window")
                self._ann.__enter__()
                self.host = (time.perf_counter(), None)
                self.state = "tracing"
            else:
                super().poll(elapsed)
            due = (self.start, self.start + self.length)
            if len(self.marks) < 2 and elapsed >= due[len(self.marks)]:
                self.marks.append((time.perf_counter(), _copy_stats(engine)))

    return Marking


def measure(cell, profile: bool, python_tracer: bool = True) -> dict:
    """One window of the serve cell ``cell`` (loaded by ``bench/run.py``)."""
    import jax

    from bench import serve_cell
    from bench import weights as weights_lib
    from bench.engine_driver import EngineDriver
    from bench.run import log
    from bench.traffic.generators import RequestStream
    from repro.serving import ServeEngine
    from repro.utils import cast_params_for_compute

    mix, eng = cell.traffic, cell.traffic["engine"]
    cfg = serve_cell.build_config(cell.config)
    params = cast_params_for_compute(weights_lib.make(cfg, cell.seed),
                                     cfg.act_dtype)
    jax.block_until_ready(params)
    engine = ServeEngine(params, cfg, max_len=eng["max_len"],
                         prefill_chunk=eng["prefill_chunk"],
                         temperature=mix["temperature"])
    stream = RequestStream(mix, cell.seed)
    serve_cell.warm_up(engine, eng["slots"], eng["prefill_chunk"], cfg.vocab)
    compiled = cell.compiles.total()
    driver = EngineDriver(engine, eng["slots"], eng["prefill_chunk"],
                          rng_seed=cell.seed % 2 ** 31,
                          capture_every=serve_cell.CAPTURE_EVERY)

    tracer = _marking_tracer(serve_cell.Tracer, engine, python_tracer)(
        profile, cell.seconds)
    with GcPauses() as pauses:
        if mix["loop"] == "closed":
            win = serve_cell._closed_loop(driver, stream, cfg.vocab,
                                          mix["clients"], cell.seconds, tracer)
        else:
            win = serve_cell._open_loop(driver, stream, cfg.vocab,
                                        cell.seconds, tracer)
    if len(tracer.marks) < 2:
        tracer.marks.append((time.perf_counter(), _copy_stats(engine)))
    window_stats = _copy_stats(engine)
    in_window = cell.compiles.total() - compiled
    driver.finish()
    (h0, s0), (h1, s1) = tracer.marks
    h1 = min(h1, win.t1)      # the last mark may follow the profiler's stop
    out = {"workload": cell.name, "seed": cell.seed, "profile": int(profile),
           "python_tracer": int(python_tracer),
           "window": {"requests_due": len(win.due), "ticks": len(win.ticks),
                      "compiled_in_window": in_window,
                      "generator_late_ms": win.lateness * 1e3,
                      "part_s": [h0 - win.t0, h1 - win.t0],
                      "part_ticks": sum(h0 <= t["t0"] < h1
                                        for t in win.ticks),
                      **serve_cell.end_to_end(win, driver, cell.seconds)},
           "gc": pauses.report()}
    if s0 is not None:
        out["engine"] = engine_counts(s0, s1)
        out["engine"]["phase_cost_us"] = phase_cost_us()
        out["engine"]["window_ticks"] = window_stats["ticks"]
        out["engine"]["longest_tick_ms"] = window_stats["longest_tick_s"] * 1e3
        out["engine"]["longest_tick_ms_by_phase"] = {
            p: s * 1e3 for p, s in window_stats["longest_tick"].items()}
    if profile:
        tr = load(tracer.dir)
        within = trace_reduce.window(tr)
        busy = trace_reduce.busy_s(tr, within)
        gaps = idle_gaps(tr, within)
        out["trace"] = {
            "window_s": trace_reduce.length(within), "busy_s": busy,
            "idle_gaps": gaps, "idle_s": sum(v for _, v in gaps),
            "tick_host_ms": tick_host_ms(tr, within),
            "serve_ticks": sum(trace_reduce.inside(t0, within)
                               for t0, _ in tr.spans.get(TICK, [])),
            "bench_ticks": sum(trace_reduce.inside(t0, within)
                               for t0, _ in tr.spans.get("bench.tick", [])),
            "serve_spans": {k: sum(trace_reduce.inside(t0, within)
                                   for t0, _ in v)
                            for k, v in tr.spans.items()
                            if k.startswith(PHASE_PREFIX)},
        }
        tracer.cleanup()
    log(f"longest tick {out.get('engine', {}).get('longest_tick_ms')} ms "
        f"by phase {out.get('engine', {}).get('longest_tick_ms_by_phase')}; "
        f"GC {pauses.count} pauses, {pauses.seconds:.4f} s in the window")
    return out


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--profile", type=int, choices=(0, 1), default=1)
    ap.add_argument("--python-tracer", type=int, choices=(0, 1), default=1,
                    help="record Python calls in the trace, as the serve "
                    "cell's traced runs do (default 1)")
    args = ap.parse_args(argv)

    from bench import run

    cell = run.load_cell(args.workload)
    if cell.traffic["kind"] != "serve":
        raise SystemExit(f"{cell.name} is not a serve cell")
    cell.seed, cell.seconds, cell.t_start = args.seed, args.seconds, t_start

    import jax

    device = run.check_device(jax, cell.chips)
    run.enable_cache(jax)
    cell.compiles = run.CompileCounter(jax)
    with run.matmul_precision(jax, cell):
        out = measure(cell, bool(args.profile), bool(args.python_tracer))
    out["device"] = device
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
