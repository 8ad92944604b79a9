"""Spans and counters of the serve engine's tick phases.

Each phase of ``ServeEngine``'s tick runs inside :meth:`TickTrace.phase`,
which does two things at once. It enters a ``jax.profiler.TraceAnnotation``
named after the phase, so that with the profiler on the phase shows as a
host span on the trace's timeline, beside the programs the device ran. And
it adds the phase's host seconds to :attr:`TickTrace.stats`. With the
profiler off the annotation records nothing, and a phase costs about two
microseconds of host time.

The phases, by span name:

* ``serve.tick``: one scheduler tick (arg ``tick``).
* ``serve.admit``: queue pops, prefix lookups, slot holds, prefill-pool
  seeding.
* ``serve.prefill``: one prefill dispatch with its chunk buffer (args
  ``rows``, the dispatch's rows, and ``valid``, its prompt tokens).
* ``serve.promote``: a finished prefill going live: first-token sample, pool
  insert, key set (arg ``req``, the request id).
* ``serve.decode``: node caps, key split, the decode and sample dispatches
  or the verify dispatch (arg ``live``, the decoding rows).
* ``serve.sync``: the host waiting for a device value, inside
  ``serve.decode`` or ``serve.promote``.
* ``serve.release``: token stamps, slot advance, releases and resets.

A phase's seconds in ``stats`` are its own: time spent in a phase nested
inside it counts to the inner phase only, so the seconds of one tick's
phases add up to the tick, and ``serve.tick`` holds what no other phase
covers. ``stats`` also counts the work of the phases (:data:`COUNTERS`),
the prefill dispatches by their rows (``prefill_rows_by_bucket``, rows ->
dispatches: how often each row bucket of the co-pending prefill ran), and
keeps the longest tick with its phase split.
"""
from __future__ import annotations

import time

import jax

TICK = "serve.tick"
ADMIT = "serve.admit"
PREFILL = "serve.prefill"
PROMOTE = "serve.promote"
DECODE = "serve.decode"
SYNC = "serve.sync"
RELEASE = "serve.release"
PHASES = (TICK, ADMIT, PREFILL, PROMOTE, DECODE, SYNC, RELEASE)

COUNTERS = (
    "ticks",                 # serve.tick spans
    "prefill_dispatches",
    "prefill_valid_tokens",  # prompt tokens advanced
    "prefill_slot_tokens",   # rows x width dispatched, padding included
    "decode_dispatches",     # decode steps or verify rounds
    "decode_live_rows",      # rows decoding, summed over those dispatches
    "host_syncs",            # serve.sync spans
)


class TickTrace:
    """The phase spans and counters of one engine; :meth:`reset` starts a
    serve run's ``stats`` afresh."""

    def __init__(self):
        self.stats: dict = {}
        self.reset()

    def reset(self) -> dict:
        self.stats = dict.fromkeys(COUNTERS, 0)
        self.stats.update(dict.fromkeys(PHASES, 0.0))
        self.stats["prefill_rows_by_bucket"] = {}   # rows -> dispatches
        self.stats["longest_tick_s"] = 0.0
        self.stats["longest_tick"] = {}       # phase -> seconds of that tick
        self._open: list = []                 # [seconds of nested phases]
        self._tick: dict = {}                 # phase -> seconds, this tick
        return self.stats

    def phase(self, name: str, **args) -> "_Phase":
        """Context manager: the span ``name`` (with ``args``) and its host
        seconds."""
        return _Phase(self, name, args)

    def prefill(self, rows: int, width: int, valid: int) -> "_Phase":
        """One prefill dispatch of ``rows`` x ``width`` token slots, of
        which ``valid`` carry prompt tokens."""
        st = self.stats
        st["prefill_dispatches"] += 1
        st["prefill_valid_tokens"] += valid
        st["prefill_slot_tokens"] += rows * width
        by_rows = st["prefill_rows_by_bucket"]
        by_rows[rows] = by_rows.get(rows, 0) + 1
        return _Phase(self, PREFILL, {"rows": rows, "valid": valid})

    def decode(self, live: int) -> "_Phase":
        """One decode step or verify round over ``live`` decoding rows."""
        self.stats["decode_dispatches"] += 1
        self.stats["decode_live_rows"] += live
        return _Phase(self, DECODE, {"live": live})

    def sync(self) -> "_Phase":
        """The host waiting for a device value."""
        self.stats["host_syncs"] += 1
        return _Phase(self, SYNC, {})

    def _close(self, name: str, seconds: float, nested: float) -> None:
        own = seconds - nested
        self.stats[name] += own
        self._tick[name] = self._tick.get(name, 0.0) + own
        if self._open:
            self._open[-1] += seconds
        if name == TICK:
            self.stats["ticks"] += 1
            if seconds > self.stats["longest_tick_s"]:
                self.stats["longest_tick_s"] = seconds
                self.stats["longest_tick"] = self._tick
            self._tick = {}


def summary(stats: dict) -> str:
    """One line for an operator: host ms a tick by phase (``other``: the
    tick outside every phase), syncs a tick, the share of prefill token
    slots that carried prompt tokens, prefill dispatches by rows, and the
    longest tick by phase."""
    ticks = max(stats["ticks"], 1)

    def by_phase(secs: dict, per: int) -> str:
        ms = {p.split(".", 1)[1]: secs.get(p, 0.0) / per * 1e3
              for p in PHASES}
        ms["other"] = ms.pop("tick")
        return ", ".join(f"{k} {v:.3f}" for k, v in ms.items())

    host_ms = sum(stats[p] for p in PHASES) / ticks * 1e3
    slots = stats["prefill_slot_tokens"]
    valid = (f"{100 * stats['prefill_valid_tokens'] / slots:.1f}%"
             if slots else "n/a")
    return (f"{stats['ticks']} ticks, {host_ms:.3f} host ms a tick "
            f"({by_phase(stats, ticks)}); "
            f"{stats['host_syncs'] / ticks:.2f} syncs a tick; prefill valid "
            f"share {valid} of {slots} slot tokens; prefill dispatches "
            f"by rows {dict(sorted(stats['prefill_rows_by_bucket'].items()))}"
            f"; longest tick "
            f"{stats['longest_tick_s'] * 1e3:.3f} ms "
            f"({by_phase(stats['longest_tick'], 1)})")


class _Phase:
    __slots__ = ("_trace", "_name", "_ann", "_t0")

    def __init__(self, trace: TickTrace, name: str, args: dict):
        self._trace, self._name = trace, name
        self._ann = jax.profiler.TraceAnnotation(name, **args)

    def __enter__(self):
        if self._name == TICK:
            self._trace._tick = {}
        self._trace._open.append(0.0)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        seconds = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        self._trace._close(self._name, seconds, self._trace._open.pop())
        return False
