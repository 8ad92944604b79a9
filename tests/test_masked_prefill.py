"""Batched chunked prefill: valid-length masking parity suite.

Locks the serving contract of DESIGN.md §Serving:

* MASKED-CHUNK parity: padding a tail chunk to the static ``chunk`` shape
  and passing per-row ``valid_len`` leaves logits AND every state leaf
  identical to the natural unpadded prefill — per block kind and per STLT
  engine (chunked, chunked_fused, pallas in interpret mode). Most combos
  are bit-identical (the masked update selects the same values); the two
  exceptions — the stlt carry closed form and the hann FFT length — agree
  to float32 ulp scale, and valid_len == 0 rows are bit-identical no-ops by
  construction.
* HETEROGENEOUS-BATCH parity: one masked dispatch over rows at different
  depths with different valid lengths matches each row's own batch-1
  prefill (the coalesced-admission data layout).
* BATCHED-ADMISSION parity: a serve trace admitted through the coalesced
  row-bucketed dispatch is token-exact vs the legacy one-request-per-tick
  path, tick for tick.
* ROW BUCKETS: co-pending admissions dispatch at [Kb, chunk], Kb the
  smallest power of two (or the slot count) that holds them, padded with
  distinct rows that are not pending; pad and free rows of the prefill
  pool come back bit for bit, per block kind.
* COMPILE COUNT: a serve trace over >= 8 distinct ``prompt_len % chunk``
  residues compiles exactly one prefill program per row bucket ([1, chunk],
  [2, chunk], [4, chunk] for 4 slots); adding a ``warm_prefix`` contributes
  exactly one more (its batch-1 [1, chunk]), and nothing re-traces after.
* ``warm_prefix`` at a non-chunk-boundary length still registers the
  EXACT-length entry (the remainder is masked-prefilled, not truncated to
  the last boundary).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAVE_HYPOTHESIS = False

from repro.models import transformer as T
from repro.serving import PrefixCache, ServeEngine
from repro.serving.engine import Request
from conftest import small_cfg

KINDS = {
    "stlt": dict(mixer="stlt", stlt_nodes=4, stlt_chunk=8),
    "stlt_fused": dict(mixer="stlt", stlt_nodes=4, stlt_chunk=8,
                       stlt_engine="chunked_fused"),
    "stlt_pallas": dict(mixer="stlt", stlt_nodes=4, stlt_chunk=8,
                        stlt_engine="pallas"),
    "stlt_hann": dict(mixer="stlt", stlt_window="hann", stlt_nodes=4,
                      stlt_chunk=8),
    "attn": dict(mixer="attention"),
    "local_attn": dict(layer_types=("local_attn", "local_attn"),
                       local_window=6),
    "rglru": dict(layer_types=("rglru", "rglru")),
    "xlstm": dict(family="xlstm", slstm_every=2),
    "scanned_stlt": dict(mixer="stlt", stlt_nodes=4, stlt_chunk=8,
                         scan_layers=True, num_layers=3),
}
MAX_LEN = 48
CHUNK = 8  # the static tail-chunk shape everything is padded to
# bit-identical combos: the masked state update gathers/selects the very
# values the natural path computes. The stlt exponential carry (closed form
# vs scan snapshot) and hann (FFT length W+chunk vs W+valid) differ only in
# float op order — ulp-scale.
ATOL = 1e-5


@functools.lru_cache(maxsize=None)
def _setup(kind):
    cfg = small_cfg(**KINDS[kind])
    params = T.init_lm(jax.random.key(0), cfg)
    return cfg, params


def _route_pallas_through_interpret():
    """On CPU the pallas engine silently falls back to the jnp path; force
    the actual kernel (interpret mode) so the test exercises it."""
    import repro.kernels.ops as kops

    orig = kops.stlt_scan
    kops.stlt_scan = functools.partial(orig, interpret=True, block_d=8)
    return kops, orig


def _assert_tree_close(a, b, atol, ctx):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb), ctx
    for x, y in zip(la, lb):
        np.testing.assert_allclose(
            np.asarray(x, np.float32), np.asarray(y, np.float32),
            atol=atol, err_msg=ctx)


def _check_masked_parity(kind, prefix, valid, seed):
    """prefill_chunk(chunk[:valid]) == prefill_chunk(pad(chunk), valid_len):
    logits AND every state leaf, from a depth-``prefix`` carried state."""
    cfg, params = _setup(kind)
    rng = np.random.default_rng(seed)
    toks = jnp.asarray(rng.integers(3, cfg.vocab, (1, prefix + max(valid, 1))),
                       jnp.int32)
    # pad positions carry JUNK tokens (not zeros): masking must win, not luck
    junk = jnp.asarray(rng.integers(3, cfg.vocab, (1, CHUNK)), jnp.int32)
    padded = junk.at[:, :valid].set(toks[:, prefix:prefix + valid])

    patched = None
    if kind == "stlt_pallas":
        patched = _route_pallas_through_interpret()
    try:
        state0 = T.init_decode_state(cfg, 1, MAX_LEN)
        if prefix:
            _, state0 = T.prefill_chunk(params, cfg, toks[:, :prefix], state0)
        if valid:
            ref_logits, ref_state = T.prefill_chunk(
                params, cfg, toks[:, prefix:prefix + valid], state0)
        else:
            ref_state = state0
        m_logits, m_state = T.prefill_chunk(
            params, cfg, padded, state0,
            valid_len=jnp.asarray([valid], jnp.int32))
    finally:
        if patched is not None:
            patched[0].stlt_scan = patched[1]

    ctx = f"{kind}: prefix={prefix} valid={valid}"
    if valid == 0:
        # a fully-masked row is a bit-exact no-op: state AND pos untouched
        for x, y in zip(jax.tree_util.tree_leaves(ref_state),
                        jax.tree_util.tree_leaves(m_state)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=ctx)
        return
    np.testing.assert_allclose(np.asarray(m_logits), np.asarray(ref_logits),
                               atol=ATOL, err_msg=ctx + " (logits)")
    _assert_tree_close(m_state, ref_state, ATOL, ctx + " (state leaf)")


@pytest.mark.parametrize("kind", sorted(KINDS))
@pytest.mark.parametrize("valid", [0, 1, CHUNK - 1, CHUNK])
def test_masked_tail_chunk_matches_unpadded(kind, valid):
    """Deterministic sweep: valid_len in {0, 1, chunk-1, chunk}, both fresh
    and mid-prompt carried states."""
    _check_masked_parity(kind, prefix=0, valid=valid, seed=0)
    _check_masked_parity(kind, prefix=5, valid=valid, seed=1)


if HAVE_HYPOTHESIS:

    @pytest.mark.parametrize("kind", sorted(KINDS))
    @settings(max_examples=6, deadline=None)
    @given(data=st.data())
    def test_masked_tail_chunk_matches_unpadded_fuzz(kind, data):
        """Hypothesis: arbitrary carried depth x valid length x junk pad."""
        prefix = data.draw(st.integers(0, 12), label="prefix_depth")
        valid = data.draw(st.integers(0, CHUNK), label="valid_len")
        seed = data.draw(st.integers(0, 2**16), label="seed")
        _check_masked_parity(kind, prefix, valid, seed)


@pytest.mark.parametrize("kind", ["stlt", "stlt_hann", "attn", "local_attn",
                                  "rglru", "xlstm", "scanned_stlt"])
def test_heterogeneous_batch_rows_match_batch1(kind):
    """One masked dispatch over a pool whose rows sit at different depths
    with different valid lengths == each row's own batch-1 prefill (the
    coalesced-admission layout; includes a valid=0 bystander row)."""
    cfg, params = _setup(kind)
    rng = np.random.default_rng(7)
    depths, valids = [0, 6, 3], [CHUNK, 4, 0]
    rows = [rng.integers(3, cfg.vocab, (1, d + max(v, 1))).astype(np.int32)
            for d, v in zip(depths, valids)]

    pool = T.init_decode_state(cfg, 3, MAX_LEN)
    singles = []
    for s, (toks, d) in enumerate(zip(rows, depths)):
        st1 = T.init_decode_state(cfg, 1, MAX_LEN)
        if d:
            _, st1 = T.prefill_chunk(params, cfg, jnp.asarray(toks[:, :d]), st1)
        singles.append((toks, st1))
        pool = T.insert_slot(pool, st1, s, cfg)

    chunk_tok = rng.integers(3, cfg.vocab, (3, CHUNK)).astype(np.int32)  # junk
    for s, ((toks, _), d, v) in enumerate(zip(singles, depths, valids)):
        chunk_tok[s, :v] = toks[0, d:d + v]
    logits, pool = T.prefill_chunk(
        params, cfg, jnp.asarray(chunk_tok), pool,
        valid_len=jnp.asarray(valids, jnp.int32))

    for s, ((toks, st1), d, v) in enumerate(zip(singles, depths, valids)):
        row_state = T.extract_slot(pool, s, cfg)
        if v == 0:
            _assert_tree_close(row_state, st1, 0.0, f"{kind} row {s} (no-op)")
            continue
        ref_logits, ref_state = T.prefill_chunk(
            params, cfg, jnp.asarray(toks[:, d:d + v]), st1)
        np.testing.assert_allclose(
            np.asarray(logits[s:s + 1]), np.asarray(ref_logits), atol=ATOL,
            err_msg=f"{kind} row {s} logits")
        _assert_tree_close(row_state, ref_state, ATOL, f"{kind} row {s} state")


# ---------------------------------------------------------------------------
# engine-level: coalesced admission parity, row buckets, the compile count
# ---------------------------------------------------------------------------


def _residue_requests(cfg, chunk, n, rng, budget_base=3):
    """Prompts covering >= 8 distinct ``len % chunk`` residues."""
    lengths = [chunk + 1 + i for i in range(n)]  # residues 1..0 mod chunk
    assert len({l % chunk for l in lengths}) >= min(8, n)
    return [Request(rng.integers(3, cfg.vocab, l).astype(np.int32),
                    budget_base + i % 4, id=i)
            for i, l in enumerate(lengths)]


def test_batched_admission_matches_one_per_tick():
    """N requests admitted via the coalesced row-bucketed dispatch produce
    token-exact outputs — and identical admit/live/finish ticks — vs the
    legacy sequential one-request-per-tick path, and vs per-request
    generate."""
    cfg, params = _setup("stlt")
    eng = ServeEngine(params, cfg, max_len=128, prefill_chunk=CHUNK)
    rng = np.random.default_rng(3)
    reqs = _residue_requests(cfg, CHUNK, 8, rng)
    arrivals = [0, 0, 1, 3, 3, 6, 10, 11]

    res_b, stats_b = eng.serve(reqs, slots=3, arrivals=arrivals,
                               return_stats=True)
    res_s, stats_s = eng.serve(reqs, slots=3, arrivals=arrivals,
                               return_stats=True, coalesce=False)
    for r in reqs:
        np.testing.assert_array_equal(
            res_b[r.id], res_s[r.id],
            err_msg=f"request {r.id}: coalesced vs one-per-tick")
        np.testing.assert_array_equal(
            res_b[r.id], eng.generate(r.prompt[None], r.max_new_tokens)[0],
            err_msg=f"request {r.id}: coalesced vs generate")
        for k in ("admit", "live", "finish"):
            assert stats_b[r.id][k] == stats_s[r.id][k], (r.id, k)


@pytest.mark.parametrize("kind", ["stlt", "stlt_hann", "attn", "local_attn",
                                  "rglru", "xlstm", "scanned_stlt"])
def test_row_bucket_pads_with_idle_rows(kind):
    """Three co-pending admissions in an 8-slot pool dispatch at bucket 4
    with one pad row: the served tokens are exact vs the one-per-tick path
    and vs generate, every row outside the pending ones (the pad, a live
    row's stale prefill state, the free rows) comes back bit for bit, and
    no pad index repeats a pending row."""
    cfg, params = _setup(kind)
    eng = ServeEngine(params, cfg, max_len=MAX_LEN, prefill_chunk=CHUNK)
    rng = np.random.default_rng(13)
    # tick 0: A (4 chunks) and B (1 chunk) -> bucket 2; B goes live and
    # leaves its final state in prefill row 1. tick 1: A, C, D pending ->
    # bucket 4, padded with row 1
    lengths, arrivals = [3 * CHUNK + 2, 5, 2 * CHUNK - 4, 7], [0, 0, 1, 1]
    reqs = [Request(rng.integers(3, cfg.vocab, n).astype(np.int32), 4, id=i)
            for i, n in enumerate(lengths)]
    calls = []
    dispatch = eng._ops_prefill_rows

    def record(params, toks, pool, idx, valid):
        before = jax.tree_util.tree_map(np.array, pool)  # pool is donated
        logits, pool = dispatch(params, toks, pool, idx, valid)
        calls.append((np.array(idx), np.array(valid), before,
                      jax.tree_util.tree_map(np.array, pool)))
        return logits, pool

    eng._ops_prefill_rows = record
    res_b, stats_b = eng.serve(reqs, slots=8, arrivals=arrivals,
                               return_stats=True)
    assert [(len(i), int((v == 0).sum())) for i, v, _, _ in calls] == [
        (2, 0), (4, 1), (2, 0), (1, 0)]
    for idx, valid, before, after in calls:
        assert len(set(idx.tolist())) == len(idx), idx
        advanced = set(idx[valid > 0].tolist())
        assert not advanced & set(idx[valid == 0].tolist()), idx
        for g in sorted(set(range(8)) - advanced):
            for x, y in zip(
                    jax.tree_util.tree_leaves(T.extract_slot(before, g, cfg)),
                    jax.tree_util.tree_leaves(T.extract_slot(after, g, cfg))):
                np.testing.assert_array_equal(
                    np.asarray(x), np.asarray(y),
                    err_msg=f"{kind}: row {g} moved (idx {idx}, valid {valid})")

    res_s, stats_s = eng.serve(reqs, slots=8, arrivals=arrivals,
                               return_stats=True, coalesce=False)
    for r in reqs:
        np.testing.assert_array_equal(
            res_b[r.id], res_s[r.id],
            err_msg=f"{kind}: request {r.id}: bucketed vs one-per-tick")
        np.testing.assert_array_equal(
            res_b[r.id], eng.generate(r.prompt[None], r.max_new_tokens)[0],
            err_msg=f"{kind}: request {r.id}: bucketed vs generate")
        for k in ("admit", "live", "finish"):
            assert stats_b[r.id][k] == stats_s[r.id][k], (r.id, k)


def test_bucket_compile_count(jit_trace_log):
    """A full chunked serve trace over 8 distinct tail residues compiles
    exactly one prefill program per row bucket — [1, chunk], [2, chunk]
    and [4, chunk] for 4 slots, all ahead of the first dispatch — and
    nothing else: warm_prefix adds its batch-1 [1, chunk] program, and
    prefix-cache resumes and further residues re-trace nothing. The
    monolithic ``prefill`` program is never traced."""
    cfg, params = _setup("stlt")
    rng = np.random.default_rng(5)
    eng = ServeEngine(params, cfg, max_len=128, prefill_chunk=CHUNK,
                      prefix_cache=PrefixCache(capacity=64))
    reqs = _residue_requests(cfg, CHUNK, 8, rng)
    # staggered arrivals: some ticks have one pending admission, some several
    eng.serve(reqs, slots=4, arrivals=[0, 0, 2, 2, 5, 9, 12, 12])

    def prefills():
        return sorted(e for e in jit_trace_log if e[0].startswith("prefill"))

    assert prefills() == [("prefill_chunk", (1, CHUNK)),
                          ("prefill_chunk", (2, CHUNK)),
                          ("prefill_chunk", (4, CHUNK))], prefills()

    # warming a NON-boundary-length system prompt runs batch-1 [1, chunk]
    sys_prompt = rng.integers(3, cfg.vocab, 2 * CHUNK + 3).astype(np.int32)
    assert eng.warm_prefix(sys_prompt) == len(sys_prompt)
    warmed = prefills()
    assert warmed == [("prefill_chunk", (1, CHUNK)),
                      ("prefill_chunk", (1, CHUNK)),
                      ("prefill_chunk", (2, CHUNK)),
                      ("prefill_chunk", (4, CHUNK))], warmed
    # serving more residues — including prefix-cache resumes — re-traces
    # NOTHING
    more = [Request(np.concatenate([sys_prompt,
                                    rng.integers(3, cfg.vocab, 5 + i).astype(np.int32)]),
                    3, id=100 + i) for i in range(4)]
    res = eng.serve(more, slots=4)
    assert all(len(res[100 + i]) == 3 for i in range(4))
    assert prefills() == warmed, prefills()


def test_warm_prefix_nonboundary_registers_exact_length():
    """warm_prefix at a length that is NOT a chunk multiple must register
    the exact-length entry (masked round-up, no silent truncation to the
    last boundary): a same-prompt request is a FULL-prompt cache hit and a
    re-warm is a no-op."""
    cfg, params = _setup("stlt")
    cache = PrefixCache(capacity=16)
    eng = ServeEngine(params, cfg, max_len=64, prefill_chunk=CHUNK,
                      prefix_cache=cache)
    rng = np.random.default_rng(11)
    sys_prompt = rng.integers(3, cfg.vocab, 3 * CHUNK + 5).astype(np.int32)

    assert eng.warm_prefix(sys_prompt) == len(sys_prompt)
    assert eng.warm_prefix(sys_prompt) == 0  # exact-length hit, not boundary
    hit = cache.lookup(sys_prompt)
    assert hit is not None and hit.n_tokens == len(sys_prompt)

    res, stats = eng.serve([Request(sys_prompt, 4, id=0)], slots=1,
                           return_stats=True)
    assert stats[0]["cached_tokens"] == len(sys_prompt)
    assert stats[0]["prefilled_tokens"] == 0  # nothing re-prefilled
    np.testing.assert_array_equal(
        res[0], eng.generate(sys_prompt[None], 4)[0],
        err_msg="full-prompt warm hit diverged from generate")
