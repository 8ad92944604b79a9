"""The serve engine's tick phases (``repro.serving.trace``): the counters
of ``ServeEngine.tick_stats`` against what the engine dispatched, phase
seconds that add up to the tick, and programs named after their
functions."""
import jax
import numpy as np
import pytest

from repro.models import transformer as T
from repro.serving import ServeEngine
from repro.serving import trace as trace_lib
from repro.serving.engine import Request
from conftest import small_cfg

CHUNK = 16

# admission and decode paths of one tick, each counted by tick_stats
MODES = {
    "coalesced": dict(serve=dict(prefill_chunk=CHUNK)),
    "legacy": dict(serve=dict(prefill_chunk=CHUNK, coalesce=False)),
    "monolithic": dict(serve=dict(prefill_chunk=0)),
    "speculative": dict(serve=dict(prefill_chunk=CHUNK),
                        engine=dict(spec_k=2)),
}


@pytest.fixture(scope="module")
def model():
    cfg = small_cfg(mixer="stlt", stlt_nodes=4, stlt_chunk=8)
    return cfg, T.init_lm(jax.random.key(0), cfg)


def _requests(cfg, n=5):
    rng = np.random.default_rng(3)
    return [Request(rng.integers(3, cfg.vocab, int(rng.integers(5, 40)))
                    .astype(np.int32), int(2 + i % 4), id=i)
            for i in range(n)]


@pytest.fixture(scope="module")
def generated(model):
    """Each request's tokens from ``generate``, which runs no tick."""
    cfg, params = model
    eng = ServeEngine(params, cfg, max_len=128)
    return {r.id: eng.generate(r.prompt[None], r.max_new_tokens)[0]
            for r in _requests(cfg)}


@pytest.mark.parametrize("mode", sorted(MODES))
def test_tick_stats_count_what_the_engine_dispatched(model, generated, mode):
    cfg, params = model
    eng = ServeEngine(params, cfg, max_len=128,
                      **MODES[mode].get("engine", {}))
    reqs = _requests(cfg)
    # every prefill dispatch goes through _prefill_chunk, _prefill or
    # _ops_prefill_rows: record the [rows, width] of each
    shapes = []
    for name in ("_prefill_chunk", "_prefill", "_ops_prefill_rows"):
        fn = getattr(eng, name)

        def probe(*args, _fn=fn, **kw):
            toks = kw["inputs"] if "inputs" in kw else args[1]
            shapes.append(tuple(toks.shape))
            return _fn(*args, **kw)
        setattr(eng, name, probe)
    ticks = []
    serve_tick = eng._serve_tick

    def count_tick(run):
        ticks.append(run.tick)
        serve_tick(run)
    eng._serve_tick = count_tick

    res, stats = eng.serve(reqs, slots=2, return_stats=True,
                           **MODES[mode]["serve"])
    ts = eng.tick_stats
    lens = [len(r.prompt) for r in reqs]
    assert ts["ticks"] == len(ticks)
    assert ts["prefill_dispatches"] == len(shapes)
    assert ts["prefill_valid_tokens"] == sum(lens)
    assert ts["prefill_slot_tokens"] == sum(r * w for r, w in shapes)
    assert ts["prefill_rows_by_bucket"] == {
        r: sum(s[0] == r for s in shapes) for r, _ in shapes}
    if mode in ("legacy", "monolithic"):        # dispatched unpadded
        assert ts["prefill_slot_tokens"] == sum(lens)
    else:
        assert ts["prefill_slot_tokens"] > sum(lens)
    # one sync per decode dispatch and one per first token
    assert ts["host_syncs"] == ts["decode_dispatches"] + len(reqs)
    n_tok = sum(len(v) for v in res.values())
    if mode == "speculative":
        assert ts["decode_dispatches"] == eng.spec_stats["verify_calls"]
    else:   # each live row of a decode step emits one token
        assert ts["decode_live_rows"] + len(reqs) == n_tok
    for r in reqs:
        np.testing.assert_array_equal(res[r.id], generated[r.id])
        st = stats[r.id]
        assert st["admit_wall"] <= st["token_walls"][0]
    # a phase's seconds are its own, so the phases add up to the ticks
    secs = [ts[p] for p in trace_lib.PHASES]
    assert all(s >= 0 for s in secs) and ts[trace_lib.SYNC] > 0
    assert sum(ts["longest_tick"].values()) == pytest.approx(
        ts["longest_tick_s"], rel=1e-6)
    assert ts["longest_tick_s"] <= sum(secs)


def test_tick_stats_reset_per_serve(model):
    cfg, params = model
    eng = ServeEngine(params, cfg, max_len=128, prefill_chunk=CHUNK)
    reqs = _requests(cfg, 3)
    eng.serve(reqs, slots=2)
    first = dict(eng.tick_stats)
    eng.serve(reqs, slots=2)
    for k in trace_lib.COUNTERS:
        assert eng.tick_stats[k] == first[k], k


class _Clock:
    """A perf_counter that moves only when told to."""

    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_phase_seconds_are_own_time(monkeypatch):
    clock = _Clock()
    monkeypatch.setattr(trace_lib.time, "perf_counter", clock)
    tt = trace_lib.TickTrace()
    for tick_len in (1.0, 4.0, 2.0):
        with tt.phase(trace_lib.TICK, tick=0):
            clock.t += 0.25 * tick_len
            with tt.phase(trace_lib.ADMIT):
                clock.t += 0.25 * tick_len
            with tt.decode(live=3):
                clock.t += 0.25 * tick_len
                with tt.sync():
                    clock.t += 0.25 * tick_len
    st = tt.stats
    assert st["ticks"] == 3 and st["host_syncs"] == 3
    assert st["decode_dispatches"] == 3 and st["decode_live_rows"] == 9
    for name in (trace_lib.TICK, trace_lib.ADMIT, trace_lib.DECODE,
                 trace_lib.SYNC):
        assert st[name] == pytest.approx(0.25 * 7.0)
    assert st["longest_tick_s"] == pytest.approx(4.0)
    assert st["longest_tick"] == pytest.approx(
        {trace_lib.TICK: 1.0, trace_lib.ADMIT: 1.0, trace_lib.DECODE: 1.0,
         trace_lib.SYNC: 1.0})
    tt.reset()
    assert tt.stats["ticks"] == 0 and tt.stats["longest_tick"] == {}


def test_prefill_phase_counts_padding():
    tt = trace_lib.TickTrace()
    with tt.prefill(rows=4, width=16, valid=21):
        pass
    with tt.prefill(rows=1, width=16, valid=16):
        pass
    st = tt.stats
    assert st["prefill_dispatches"] == 2
    assert st["prefill_valid_tokens"] == 37
    assert st["prefill_slot_tokens"] == 4 * 16 + 16


def test_prefill_rows_by_bucket_counts_one_dispatch_a_tick(model):
    """Staged arrivals of 1, 2, 3 and 5 one-chunk prompts into 8 slots:
    each arrival tick runs one prefill dispatch, at the row bucket 1, 2, 4
    and 8 that holds its admissions, and the slot tokens are the buckets'
    rows times the chunk."""
    cfg, params = model
    eng = ServeEngine(params, cfg, max_len=128, prefill_chunk=CHUNK)
    rng = np.random.default_rng(5)
    arrivals = [0] + [3] * 2 + [6] * 3 + [9] * 5
    reqs = [Request(rng.integers(3, cfg.vocab, int(rng.integers(5, CHUNK + 1)))
                    .astype(np.int32), 2, id=i) for i in range(len(arrivals))]
    per_tick = []       # dispatches by rows, one dict a tick
    serve_tick = eng._serve_tick

    def count_tick(run):
        before = dict(eng.tick_stats["prefill_rows_by_bucket"])
        serve_tick(run)
        after = eng.tick_stats["prefill_rows_by_bucket"]
        per_tick.append({r: n - before.get(r, 0) for r, n in after.items()
                         if n != before.get(r, 0)})
    eng._serve_tick = count_tick

    eng.serve(reqs, slots=8, arrivals=arrivals)
    assert [d for d in per_tick if d] == [{1: 1}, {2: 1}, {4: 1}, {8: 1}], \
        per_tick
    ts = eng.tick_stats
    assert ts["prefill_rows_by_bucket"] == {1: 1, 2: 1, 4: 1, 8: 1}
    assert ts["prefill_dispatches"] == 4
    assert ts["prefill_slot_tokens"] == (1 + 2 + 4 + 8) * CHUNK
    assert ts["prefill_valid_tokens"] == sum(len(r.prompt) for r in reqs)
    assert "by rows {1: 1, 2: 1, 4: 1, 8: 1}" in trace_lib.summary(ts)
    # one program per bucket, compiled ahead of the first dispatch, named
    # so that a trace reader finding prefill programs by name finds it
    (progs,) = eng._rows_programs.values()
    assert sorted(progs) == [1, 2, 4, 8]
    for p in progs.values():
        assert "jit_prefill_chunk_rows" in p.as_text().split("\n", 1)[0]


def test_engine_names_its_programs(model):
    """Profiler traces show jit_<function>, not jit__unknown."""
    cfg, params = model
    eng = ServeEngine(params, cfg, max_len=64)
    names = {"_prefill": "prefill", "_prefill_chunk": "prefill_chunk",
             "_prefill_rows": "prefill_chunk_rows",
             "_step": "decode_step", "_verify": "spec_verify",
             "_insert": "insert_slot", "_extract": "extract_slot",
             "_reset": "reset_slot", "_sample": "sample_slot_tokens",
             "_split": "split_slot_keys"}
    for attr, name in names.items():
        assert getattr(eng, attr).__wrapped__.__name__ == name, attr
    logits = jax.numpy.zeros((2, cfg.vocab))
    keys = jax.random.split(jax.random.key(0), 2)
    text = eng._sample.lower(logits, keys, jax.numpy.zeros(2)).as_text()
    assert "jit_sample_slot_tokens" in text.split("\n", 1)[0]
