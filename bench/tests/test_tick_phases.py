"""bench/tick_phases.py: device idle time put down to the innermost host
span, the engine's tick phases read from a trace and from ``tick_stats``,
on hand-made traces, on the chip traces in bench/testdata and on a tiny
serve cell on the CPU."""
import gc
import json
import os

import pytest

from bench import tick_phases as tp
from bench import trace_reduce as tr
from bench.tests.conftest import tiny_cell

DATA = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "testdata")


def _nested():
    """Two harness ticks, each holding one engine tick with its phases."""
    return tr.Trace(
        [[(1.0, 2.0, "a"), (5.0, 5.5, "b")]],
        [(1.0, 2.0, "jit_decode_step"), (5.0, 5.5, "jit_prefill_chunk")],
        {"bench.window": [(0.0, 10.0)],
         "bench.tick": [(0.5, 3.5), (4.5, 6.5)],
         "bench.wait_arrival": [(3.5, 4.5), (6.5, 10.0)],
         "serve.tick": [(0.6, 3.4), (4.6, 6.4)],
         "serve.decode": [(1.0, 3.0)],
         "serve.sync": [(2.0, 2.5)],
         "serve.prefill": [(5.0, 6.0)]})


def test_innermost_span_on_a_nested_trace():
    own = tp.innermost(_nested().spans)
    assert "bench.window" not in own
    assert own["bench.tick"] == pytest.approx(
        [(0.5, 0.6), (3.4, 3.5), (4.5, 4.6), (6.4, 6.5)])
    assert own["serve.tick"] == pytest.approx(
        [(0.6, 1.0), (3.0, 3.4), (4.6, 5.0), (6.0, 6.4)])
    assert own["serve.decode"] == [(1.0, 2.0), (2.5, 3.0)]
    assert own["serve.sync"] == [(2.0, 2.5)]
    assert own["serve.prefill"] == [(5.0, 6.0)]
    assert own["bench.wait_arrival"] == [(3.5, 4.5), (6.5, 10.0)]


def test_idle_gaps_name_the_innermost_phase():
    t = _nested()
    w = tr.window(t)
    gaps = dict(tp.idle_gaps(t, w))
    want = {"bench.wait_arrival": 4.5, "serve.tick": 1.6, "none": 0.5,
            "serve.decode": 0.5, "serve.sync": 0.5, "serve.prefill": 0.5,
            "bench.tick": 0.4}
    assert gaps == pytest.approx(want)
    assert sum(gaps.values()) == pytest.approx(tr.length(w) - tr.busy_s(t, w))
    # the ticks less their syncs: (2.8 - 0.5 + 1.8) / 2 s
    assert tp.tick_host_ms(t, w) == pytest.approx(2050.0)
    assert tp.tick_host_ms(tr.Trace([], [], {}), w) is None


def test_without_nested_spans_idle_gaps_read_as_trace_reduce():
    """Where no span holds another, the innermost split is the harness's
    split, bit for bit: on a hand-made trace and on the chip trace."""
    flat = tr.Trace([[(1.0, 2.0, "a"), (1.5, 3.0, "b"), (5.0, 6.0, "a")]], [],
                    {"bench.window": [(0.0, 10.0)],
                     "bench.tick": [(0.5, 3.5), (4.5, 6.5)],
                     "bench.wait_arrival": [(3.5, 4.5), (6.5, 10.0)]})
    small = os.path.join(DATA, "small.xplane.pb")
    chip = tr.load(small)
    assert tp.load(small).spans == chip.spans      # no serve.* spans there
    for t in (flat, chip):
        w = tr.window(t)
        assert tp.idle_gaps(t, w) == tr.idle_gaps(t, w)


def test_recorded_serve_trace():
    """A tiny engine served on the chip (record_serve_trace.py): every phase
    span nests in the harness's tick, and the readers read it."""
    with open(os.path.join(DATA, "serve.json")) as f:
        want = json.load(f)
    t = tp.load(os.path.join(DATA, "serve.xplane.pb"))
    assert t.n_devices == 1
    w = tr.window(t)
    ticks = t.spans["bench.tick"]
    assert len(ticks) == len(t.spans["serve.tick"]) == want["ticks"]
    assert len(t.spans["serve.sync"]) == want["host_syncs"]
    assert len(t.spans["serve.prefill"]) == want["prefill_dispatches"]
    assert len(t.spans["serve.decode"]) == want["decode_dispatches"]
    for name, ivs in t.spans.items():
        if name.startswith("serve."):
            for a, b in ivs:
                assert any(t0 <= a and b <= t1 for t0, t1 in ticks), name
    gaps = dict(tp.idle_gaps(t, w))
    assert {"serve.tick", "serve.decode", "serve.sync"} <= set(gaps)
    assert sum(gaps.values()) == pytest.approx(
        tr.length(w) - tr.busy_s(t, w), rel=1e-6)
    host = tp.tick_host_ms(t, w)
    mean_tick = tr.length(t.spans["serve.tick"]) / want["ticks"] * 1e3
    assert 0 < host < mean_tick


def test_engine_counts_are_rates_over_the_part():
    from repro.serving import trace as phases

    before = phases.TickTrace().stats
    after = dict(before, ticks=4, host_syncs=6, prefill_valid_tokens=300,
                 prefill_slot_tokens=1200, prefill_dispatches=2,
                 decode_dispatches=4, decode_live_rows=10)
    after.update({phases.TICK: 0.004, phases.DECODE: 0.008,
                  phases.SYNC: 0.012})
    c = tp.engine_counts(before, after)
    assert c["ticks"] == 4 and c["host_syncs_per_tick"] == 1.5
    assert c["prefill_valid_share"] == 25.0
    assert c["live_rows_per_decode"] == 2.5
    assert c["host_ms"] == pytest.approx(6.0)
    assert c["host_ms_less_sync"] == pytest.approx(3.0)


def test_gc_pauses_are_counted_while_installed():
    with tp.GcPauses() as p:
        gc.collect()
    gc.collect()
    r = p.report()
    assert r["collections"] >= 1 and r["seconds"] >= r["longest_s"] > 0
    assert r["by_generation"]["2"] > 0


@pytest.mark.parametrize("python_tracer", [True, False])
def test_measure_a_tiny_serve_cell(python_tracer):
    """The whole run on the CPU: the engine's counters over the traced
    part agree with the serve.tick spans the profiler recorded."""
    cell = tiny_cell("stlt-base.chat-saturated", seconds=1.5)
    out = tp.measure(cell, profile=True, python_tracer=python_tracer)
    eng, trace = out["engine"], out["trace"]
    assert eng["ticks"] == trace["serve_ticks"] == trace["bench_ticks"] > 0
    assert eng["host_syncs_per_tick"] >= 1
    assert 0 < eng["prefill_valid_share"] <= 100
    assert 0 < trace["tick_host_ms"]
    assert eng["longest_tick_ms"] >= max(eng["host_ms_by_phase"].values())
    assert out["window"]["compiled_in_window"] == 0
