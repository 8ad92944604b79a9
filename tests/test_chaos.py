"""Chaos harness: fault-tolerant disagg serving (DESIGN.md §Serving
failure model).

Locked contracts:

* DETERMINISTIC INJECTION: :class:`FaultSchedule` decisions are a pure
  function of ``(seed, frame bytes, attempt#)`` — two schedules with the
  same seed replay the identical fault sequence, and a re-send of the
  same bytes draws a FRESH decision (retries are not doomed).
* TOKEN-EXACT RECOVERY: under seeded drop/dup/delay/corrupt plus
  endpoint kills and partitions, every admitted request completes with
  the token stream of the fault-free run — greedy, spec-decode, and
  adaptive-node-mask configs alike. The PR-6 RNG carry contract makes
  re-derived work identical by construction.
* IDEMPOTENT SPLICE: duplicated handoffs never double-splice (receiver
  dedupe by ``(src, msg_id)`` + request id); corrupted blobs are
  NACKed and re-sent, never spliced.
* HONEST DETECTION: kills are discovered via heartbeat deadlines /
  retry exhaustion / peer-down events — never by peeking the schedule —
  and each detection is logged in ``fault_stats``.
* GRACEFUL DEGRADATION: losing the ENTIRE decode fleet flips the
  controller into colocated mode on the prefill engine, still
  token-exact.
"""
import numpy as np
import pytest

import jax

from repro.models import transformer as T
from repro.serving import (DisaggController, Request, FaultSchedule,
                           LoopbackTransport, Outbox)
from repro.serving.disagg.failover import _CORRUPTIONS
from repro.serving.disagg.transport import Message
from conftest import small_cfg

STLT_KW = dict(mixer="stlt", stlt_nodes=4, stlt_chunk=8)
MAX_LEN = 160


# ------------------------------------------------------ FaultSchedule unit
def test_fault_schedule_deterministic_replay():
    frames = [(f"frame-{i}".encode(), i % 2 == 0) for i in range(64)]
    def roll(fs):
        return [fs.action("handoff", fr, blob) for fr, blob in frames]
    kw = dict(drop=0.2, dup=0.2, delay=0.2, corrupt=0.2)
    a = roll(FaultSchedule(7, **kw))
    b = roll(FaultSchedule(7, **kw))
    assert a == b  # same seed -> bit-identical fault sequence
    c = roll(FaultSchedule(8, **kw))
    assert a != c  # and the seed actually matters
    acts = {act for act, _ in a}
    assert {"drop", "dup", "delay", "corrupt"} <= acts


def test_fault_schedule_retries_draw_fresh_decisions():
    fs = FaultSchedule(0, drop=0.5)
    frame = b"same bytes every attempt"
    acts = [fs.action("admit", frame, False)[0] for _ in range(32)]
    assert "drop" in acts and None in acts  # not doomed, not immune


def test_fault_schedule_validation_and_scoping():
    with pytest.raises(ValueError, match="drop"):
        FaultSchedule(0, drop=1.5)
    with pytest.raises(ValueError, match="sum"):
        FaultSchedule(0, drop=0.6, corrupt=0.6)
    fs = FaultSchedule(0, drop=1.0, kinds=("handoff",))
    assert fs.action("admit", b"x", False) == (None, 0)   # out-of-scope kind
    assert fs.action("config", b"x", False) == (None, 0)  # handshake immune
    assert fs.action("handoff", b"x", True)[0] == "drop"
    # corrupt degrades to drop when there is no blob to corrupt
    fc = FaultSchedule(0, corrupt=1.0)
    assert fc.action("admit", b"y", False)[0] == "drop"
    act, aux = fc.action("handoff", b"y", True)
    assert act == "corrupt" and FaultSchedule.corruption_variant(aux) in \
        _CORRUPTIONS
    # timed faults
    ft = FaultSchedule(0, kills={5: "decode/0"},
                       partitions=[(3, 7, "prefill/1")])
    assert ft.killed_at(5) == ["decode/0"] and ft.killed_at(4) == []
    assert ft.partitioned("prefill/1", 3) and not ft.partitioned(
        "prefill/1", 7)


def test_outbox_retry_backoff_and_exhaustion():
    ob = Outbox(retry_ticks=2.0, max_attempts=3)
    sent, dead = [], []
    m = Message("admit", "controller", "prefill/0", {"msg_id": 0})
    ob.add(0, m, now=0.0)
    ob.tick(1.0, False, sent.append, dead.append)
    assert not sent                       # not due yet
    ob.tick(3.0, False, sent.append, dead.append)
    assert len(sent) == 1 and ob.retries == 1
    # nack makes it due immediately regardless of backoff
    ob.nack(0)
    ob.tick(3.0, False, sent.append, dead.append)
    assert len(sent) == 2
    # exponential backoff grew the deadline
    assert ob.max_backoff >= 2.0 * 2 ** 2
    ob.tick(1e9, False, sent.append, dead.append)   # attempts exhausted
    assert dead == ["prefill/0"] and len(sent) == 2
    # ack removes; drop_for clears a dead peer's backlog
    ob2 = Outbox(retry_ticks=1.0)
    ob2.add(1, m, 0.0)
    ob2.add(2, Message("admit", "controller", "decode/0", {"msg_id": 2}), 0.0)
    assert ob2.ack(1) and not ob2.ack(1)
    assert [e.msg_id for e in ob2.drop_for("decode/0")] == [2] and not len(ob2)
    # wall-based entries only fire on wall ticks
    ob3 = Outbox(retry_ticks=0.1)
    ob3.add(3, m, 0.0, wall=True)
    ob3.tick(5.0, False, sent.append, dead.append)
    assert len(sent) == 2                 # tick-base pass skipped it
    ob3.tick(5.0, True, sent.append, dead.append)
    assert len(sent) == 3


# ----------------------------------------------------- chaos parity (e2e)
@pytest.fixture(scope="module")
def chaos_env():
    cfg = small_cfg(**STLT_KW)
    params = T.init_lm(jax.random.key(0), cfg)
    rng = np.random.default_rng(3)
    lens = [4, 40, 9, 70, 25, 6, 50, 12]
    arrivals = [0, 0, 1, 4, 4, 9, 9, 12]
    reqs = [Request(rng.integers(3, cfg.vocab, n).astype(np.int32),
                    5 + i % 6, id=i) for i, n in enumerate(lens)]
    return cfg, params, reqs, arrivals


def _run(env, faults=None, **kw):
    cfg, params, reqs, arrivals = env
    ctl = DisaggController(params, cfg, n_prefill=2, n_decode=2, slots=2,
                           max_len=MAX_LEN, prefill_chunk=16,
                           transport=LoopbackTransport(), faults=faults,
                           **kw)
    out = ctl.serve(reqs, arrivals=arrivals, rng_seed=7)
    return ctl, out


@pytest.fixture(scope="module")
def baseline(chaos_env):
    _, out = _run(chaos_env)
    return out


def _assert_parity(base, out, ctx):
    assert set(out) == set(base)
    for rid in base:
        np.testing.assert_array_equal(
            base[rid], out[rid], err_msg=f"{ctx}: request {rid} diverged")


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_chaos_message_faults_and_prefill_kill(chaos_env, baseline, seed):
    """The acceptance gate: message-level chaos on every faultable kind
    PLUS a mid-trace prefill-host kill; all 8 requests finish
    token-identical to the fault-free run, detection and recovery are
    accounted, and no splice ever lands twice."""
    fs = FaultSchedule(seed, drop=0.1, dup=0.05, delay=0.1, corrupt=0.1,
                       kills={6: ("prefill/1",)})
    ctl, out = _run(chaos_env, faults=fs)
    _assert_parity(baseline, out, f"seed={seed}")
    f = ctl.fault_stats()
    assert f["detected_failures"] >= 1             # the kill was noticed
    assert any(e["endpoint"] == "prefill/1" for e in f["failures"])
    assert sum(f["injected"].values()) > 0         # chaos actually ran
    assert f["heartbeats_sent"] > 0
    assert f["outbox_unacked"] == 0                # nothing left in flight


def test_chaos_decode_kill_resplices_kept_blob(chaos_env, baseline):
    """A decode-host death mid-stream: its live rows are requeued onto the
    survivor, re-spliced from the controller's kept handoff blob, and the
    re-derived tokens (RNG contract) match the fault-free streams."""
    fs = FaultSchedule(0, kills={8: ("decode/0",)})
    ctl, out = _run(chaos_env, faults=fs)
    _assert_parity(baseline, out, "decode kill")
    f = ctl.fault_stats()
    assert f["detected_failures"] >= 1
    assert f["recovered_requests"] >= 1
    assert f["requeued_tokens"] > 0                # work genuinely redone
    assert not f["degraded_colocated"]             # a survivor absorbed it


def test_chaos_full_decode_loss_degrades_colocated(chaos_env, baseline):
    """Losing the ENTIRE decode fleet degrades to colocated decode on the
    prefill engine — slower, but token-exact and nothing dropped."""
    fs = FaultSchedule(0, kills={8: ("decode/0", "decode/1")})
    ctl, out = _run(chaos_env, faults=fs)
    _assert_parity(baseline, out, "degraded")
    f = ctl.fault_stats()
    assert f["degraded_colocated"]
    assert f["detected_failures"] >= 2


def test_chaos_partition_short_vs_long(chaos_env, baseline):
    """A partition shorter than the heartbeat deadline heals silently
    (retry absorbs it, no failure declared); one longer than the deadline
    is declared down, fenced, and recovered — both token-exact."""
    short = FaultSchedule(0, partitions=[(5, 9, "decode/1")])
    ctl, out = _run(chaos_env, faults=short)
    _assert_parity(baseline, out, "short partition")
    assert ctl.fault_stats()["detected_failures"] == 0

    long_ = FaultSchedule(0, partitions=[(5, 60, "decode/1")])
    ctl2, out2 = _run(chaos_env, faults=long_)
    _assert_parity(baseline, out2, "long partition")
    f = ctl2.fault_stats()
    assert f["detected_failures"] >= 1
    assert any(e["endpoint"] == "decode/1" for e in f["failures"])
    assert f["injected"]["partition_drops"] > 0


def test_chaos_corrupt_handoffs_nacked_and_resent(chaos_env, baseline):
    """Heavy corruption aimed ONLY at handoff blobs: every corrupted blob
    is rejected at unpack (magic/version/truncate/digest), NACKed, and
    the re-send eventually lands — token-exact, with the reject counter
    matching the transport's injection counter."""
    fs = FaultSchedule(1, corrupt=0.6, kinds=("handoff",))
    # the fault decisions hash each blob's bytes, so they move with the
    # last ulp of the prefilled state; 16 attempts make a blob corrupted
    # on every one of them (0.6 ** 16 a blob) too rare to decide the test
    ctl, out = _run(chaos_env, faults=fs, retry_max_attempts=16)
    _assert_parity(baseline, out, "corrupt handoffs")
    f = ctl.fault_stats()
    assert f["corrupt_blobs_rejected"] > 0
    assert f["corrupt_blobs_rejected"] == f["injected"]["corrupted"]
    assert f["detected_failures"] == 0             # faults, not failures


def test_chaos_duplicates_never_double_splice(chaos_env, baseline):
    """At-least-once delivery + heavy duplication: receivers drop dups by
    ``(src, msg_id)`` and the splice path by request id — the streams
    carry no doubled tokens (parity proves it) and the dedupe counters
    show the machinery fired."""
    fs = FaultSchedule(2, dup=0.4)
    ctl, out = _run(chaos_env, faults=fs)
    _assert_parity(baseline, out, "duplicates")
    f = ctl.fault_stats()
    assert f["injected"]["duplicated"] > 0
    assert f["dup_msgs_ignored"] > 0
    # kill + retry + dup combined is the double-splice gauntlet
    fs2 = FaultSchedule(1, dup=0.3, drop=0.1, kills={8: ("decode/0",)})
    ctl2, out2 = _run(chaos_env, faults=fs2)
    _assert_parity(baseline, out2, "dup+drop+kill")


def test_chaos_spec_decode_parity(chaos_env):
    """Speculative decoding's draft/verify/rollback carries survive chaos:
    the spec fault-free and spec chaos runs agree stream-for-stream."""
    _, base = _run(chaos_env, spec_k=3)
    fs = FaultSchedule(0, drop=0.1, dup=0.1, delay=0.1,
                       kills={7: ("decode/0",)})
    ctl, out = _run(chaos_env, faults=fs, spec_k=3)
    _assert_parity(base, out, "spec chaos")
    assert ctl.decode.spec_stats["verify_calls"] > 0


def test_chaos_adaptive_mask_parity():
    """Adaptive node masks recompute from the shipped ``asum/acnt`` leaves;
    a re-splice after a decode kill must re-derive the same masks and
    tokens."""
    cfg = small_cfg(mixer="stlt", stlt_nodes=4, stlt_chunk=8,
                    stlt_adaptive=True)
    params = T.init_lm(jax.random.key(0), cfg)
    rng = np.random.default_rng(3)
    reqs = [Request(rng.integers(3, cfg.vocab, n).astype(np.int32),
                    6, id=i) for i, n in enumerate([9, 40, 25, 50, 12, 6])]
    env = (cfg, params, reqs, [0, 0, 1, 4, 4, 9])
    _, base = _run(env)
    fs = FaultSchedule(1, drop=0.1, corrupt=0.1, kills={7: ("decode/1",)})
    _, out = _run(env, faults=fs)
    _assert_parity(base, out, "adaptive chaos")


def test_chaos_report_surfaces_fault_stats(chaos_env):
    fs = FaultSchedule(0, drop=0.2)
    ctl, _ = _run(chaos_env, faults=fs)
    rep = ctl.report()
    assert rep["fault_stats"]["injected"]["dropped"] > 0
    assert "detected_failures" in rep["fault_stats"]
