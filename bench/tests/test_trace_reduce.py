"""bench/trace_reduce.py on a small trace recorded on the chip
(bench/testdata/record_trace.py) and on hand-made intervals."""
import json
import os

import pytest

from bench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "testdata")


def test_union_intersect_length():
    u = tr.union([(0, 1), (0.5, 2), (3, 4), (4, 5), (6, 6.5)])
    assert u == [(0, 2), (3, 5), (6, 6.5)]
    assert tr.intersect(u, [(1, 3.5), (6.25, 10)]) == [(1, 2), (3, 3.5), (6.25, 6.5)]
    assert tr.length(u) == 4.5
    assert tr.inside(3.2, u) and not tr.inside(2.5, u) and not tr.inside(-1, u)


def test_busy_and_idle_on_hand_made_trace():
    t = tr.Trace([[(1.0, 2.0, "a"), (1.5, 3.0, "b"), (5.0, 6.0, "a")]],
                 [(1.0, 3.0, "jit_decode_step"), (5.0, 6.0, "jit_prefill_chunk")],
                 {"bench.window": [(0.0, 10.0)], "bench.tick": [(0.5, 3.5), (4.5, 6.5)],
                  "bench.wait_arrival": [(3.5, 4.5), (6.5, 10.0)]})
    w = tr.window(t)
    assert tr.busy_s(t, w) == pytest.approx(3.0)
    assert tr.programs(t, "decode_step", w) == [(1.0, 3.0)]
    assert tr.top_ops(t, w) == [["a", 2.0], ["b", 1.5]]
    gaps = dict(tr.idle_gaps(t, w))
    assert gaps["bench.wait_arrival"] == pytest.approx(1.0 + 3.5)
    assert gaps["bench.tick"] == pytest.approx(4 * 0.5)
    assert gaps["none"] == pytest.approx(0.5)
    assert sum(gaps.values()) == pytest.approx(10.0 - 3.0)


def _merged_length(ivs):
    """The busy time the slow way: walk the sorted intervals."""
    total, end = 0.0, float("-inf")
    for a, b in sorted(ivs):
        if b > end:
            total += b - max(a, end)
            end = b
    return total


def test_recorded_chip_trace():
    with open(os.path.join(DATA, "small.json")) as f:
        want = json.load(f)
    t = tr.load(os.path.join(DATA, "small.xplane.pb"))
    assert t.n_devices == 1
    w = tr.window(t)
    assert len(w) == 1 and len(t.spans["bench.tick"]) == want["ticks"]
    dec = tr.programs(t, "decode_step", w)
    pre = tr.programs(t, "prefill_chunk", w)
    assert len(dec) == want["decode_step"] and len(pre) == want["prefill_chunk"]
    busy = tr.busy_s(t, w)
    ops = [(max(a, w[0][0]), min(b, w[0][1])) for a, b, _ in t.ops_by_device[0]
           if b > w[0][0] and a < w[0][1]]
    assert busy == pytest.approx(_merged_length(ops))
    # programs run on the device: their time is busy time, and the host's
    # sleeps between ticks leave the device idle
    prog = tr.length(tr.union(dec + pre))
    assert 0 < prog <= tr.length(w)
    assert 0 < busy <= prog * 1.05
    idle = dict(tr.idle_gaps(t, w))
    assert idle["bench.wait_arrival"] >= 0.008
    assert sum(idle.values()) == pytest.approx(tr.length(w) - busy, rel=1e-6)
