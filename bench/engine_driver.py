"""The one place where the benchmark touches ``ServeEngine``'s tick phases.

``ServeEngine`` has no public submit-and-step API. ``ServeEngine.serve``
runs ``_serve_start``, then ``_serve_tick`` while the run is active, then
``_serve_finish``; the disaggregated controller drives the same phases.
This adapter drives them from the wall clock: it starts a run with no
requests, appends each request to the run's queue once it is due, and runs
whole ticks. Everything the harness reads of the scheduler (token stamps,
admissions, prefill progress) is read here, so a change to those phases
has one file to repoint.

Time stamps are ``time.perf_counter()``. The engine stamps each token after
the tick's host readback of the sampled tokens, so a stamp follows the
device's completion of that token.

For the correctness check the adapter keeps, without reading them back,
the logits the timed path produced: each admitted request's first-token
logits, and the decode step's logits every ``capture_every``-th decode tick
(up to ``capture_max`` of them), with which request and which output
position each row belongs to.
"""
from __future__ import annotations

import time

import numpy as np

from repro.serving.engine import Request, _Host


def name_programs(engine) -> None:
    """Name the engine's compiled programs after the functions they run.

    The engine jits ``functools.partial`` objects, which carry no name, so
    every program shows as ``jit__unknown`` in a device trace. Naming the
    partial before its first compile labels the program (``jit_decode_step``,
    ``jit_prefill_chunk``, ...) and changes nothing it computes."""
    for attr in vars(engine).values():
        fn = getattr(attr, "__wrapped__", None)
        if fn is not None and hasattr(fn, "func") and not hasattr(fn, "__name__"):
            fn.__name__ = fn.func.__name__


class EngineDriver:
    """A serve run on ``engine`` over ``slots`` slots, fed request by request."""

    def __init__(self, engine, slots: int, prefill_chunk: int, rng_seed: int = 0,
                 capture_every: int = 0, capture_max: int = 64):
        name_programs(engine)
        self.engine = engine
        self.slots = slots
        self.host = _Host(slots)
        self.run = engine._serve_start([self.host], [], None, None, rng_seed,
                                       prefill_chunk, True)
        # the wall clock, not the tick count, decides when a request is due
        self.run.fast_forward = False
        self.admitted_at: dict[int, float] = {}   # id -> tick start (s)
        self._progress: dict[int, int] = {}        # id -> prompt tokens done
        self._inflight: set[int] = set()
        self._first_seen: set[int] = set()
        # logits of the timed path: id -> [(output position, row array)]
        self.logits: dict[int, list] = {}
        self._decodes = 0
        if capture_every:
            self._capture(engine, capture_every, capture_max)

    def _capture(self, engine, every: int, most: int) -> None:
        """Keep logits as the engine produces them (instance-level wraps of
        the promote and decode primitives; no extra device work or sync)."""
        promote, decode = engine._promote, engine._ops_decode
        sched = self.host.sched

        def on_promote(run, h, local, ent, logits1, st1):
            self.logits.setdefault(ent["req"].id, []).append((0, logits1, 0))
            return promote(run, h, local, ent, logits1, st1)

        def on_decode(params, tok, pool, caps=None):
            logits, pool = decode(params, tok, pool, caps)
            self._decodes += 1
            if self._decodes % every == 0 and self._decodes // every <= most:
                for slot in np.flatnonzero(sched.live):
                    self.logits.setdefault(sched.req[slot].id, []).append(
                        (int(sched.emitted[slot]), logits, int(slot)))
            return logits, pool

        engine._promote, engine._ops_decode = on_promote, on_decode

    def produced_logits(self, rid: int) -> list:
        """[(output position, logits row as float32 numpy)] of request ``rid``."""
        return [(pos, np.asarray(arr[row], np.float32))
                for pos, arr, row in self.logits.get(rid, [])]

    def submit(self, rid: int, prompt: np.ndarray, max_new: int) -> None:
        """Queue a request now; it is admitted at the next tick with a free slot."""
        req = Request(prompt=np.asarray(prompt, np.int32), max_new_tokens=max_new,
                      id=rid)
        self.run.queue.extend(self.engine._queue([req], [self.run.tick]))

    def active(self) -> bool:
        return self.run.active()

    def stats(self) -> dict:
        return self.host.sched.stats

    def tick(self) -> dict:
        """Run one whole scheduler tick. Returns what it did: ``t0``/``t1``
        (wall), ``prefill_tokens`` (valid prompt tokens advanced),
        ``prompts_done`` (prefills finished), ``decoded`` (tokens from the
        decode step, one per live row) and ``finished`` (ids)."""
        run, stats = self.run, self.host.sched.stats
        emitted_before = {rid: len(run.results.get(rid, ()))
                          for rid in self._inflight}
        t0 = time.perf_counter()
        self.engine._serve_tick(run)
        t1 = time.perf_counter()
        for rid in stats.keys() - self._first_seen:
            self._first_seen.add(rid)
            self._inflight.add(rid)
            self.admitted_at[rid] = t0
            emitted_before[rid] = 0
        pending = {ent["req"].id: ent["done"] for ent in self.host.pending.values()}
        prefill = prompts_done = emitted = 0
        finished = []
        for rid in list(self._inflight):
            st = stats[rid]
            done = pending.get(rid, st["prompt_tokens"])
            before = self._progress.get(rid, 0)
            prefill += done - before
            if before < st["prompt_tokens"] <= done:
                prompts_done += 1
            self._progress[rid] = done
            emitted += len(run.results.get(rid, ())) - emitted_before[rid]
            if st["finish"] is not None:
                finished.append(rid)
                self._inflight.discard(rid)
        return {"t0": t0, "t1": t1, "prefill_tokens": prefill,
                "prompts_done": prompts_done,
                "decoded": emitted - prompts_done, "finished": finished}

    def finish(self):
        """Close the run: ({id: tokens}, {id: scheduler stats})."""
        return self.engine._serve_finish(self.run, True)
