"""Serving §Perf — slot-level continuous batching vs the wave engine,
chunked prefill admission, the prefix-state cache, the row-bucketed
BATCHED admission path, speculative decoding, multi-host sharded serving, and
disaggregated prefill/decode fleets.

Eight traces are replayed; the first four through the same ``ServeEngine``:

1. mixed short/long BUDGETS (Poisson arrivals): continuous vs wave — the
   wave engine drains whole admission waves, so one long generation stalls
   every short request behind it (p99 latency gap).
2. long-PROMPT trace: short decode requests co-resident with concurrent
   long-prompt (32k full / 2k fast) admissions — monolithic admission
   stalls every decode slot for the whole prompt prefill; chunked admission
   (Sarathi-style mixed steps) bounds the stall to one chunk per tick. The
   reported decode p99 is measured from going live, isolating the stall.
3. shared system prompt: every request repeats the same long prefix — the
   prefix cache serves the O(S*d) post-prefix state by hash and skips the
   prefix's prefill FLOPs (hit speedup + fraction skipped).
4. MANY CONCURRENT LONG PROMPTS with distinct ``len % chunk`` tail
   residues, replayed COLD (fresh jit caches) through both admission
   paths: the PR-2 one-request-per-tick path (one batch-1 dispatch per
   pending slot per tick, each distinct tail residue a fresh compile) vs
   the coalesced row-bucketed path (ONE [Kb, chunk] masked dispatch per
   tick, one prefill compile per row bucket). Reports prefill compile counts,
   admission throughput (prefill tokens/s), and the co-resident decode
   inter-token p99 gap — the compile stalls the legacy path takes
   mid-trace land exactly on those gaps.

5. SPECULATIVE decoding: a decode-heavy repeated-motif trace replayed
   plain vs with draft-verify rounds at k in {2, 4, 8} (n-gram draft) and
   k=4 (node-subset draft). Every round verifies the k-token window in ONE
   ``prefill_chunk``-shaped dispatch, so the metric is emitted tokens per
   verify dispatch (> 1 beats one-token-per-tick decode) alongside draft
   accept rate; the emitted streams are checked token-exact vs plain.

6. SLO-AWARE NODE DEGRADATION: a one-burst overload replayed with the
   degrade ladder off vs on (``slo_queue_depth=2``, ladder ``(8, 4)``):
   the queue-depth breach walks live rows down the node-budget ladder and
   the drain restores them stepwise — the recorded trace (degrade/restore
   steps, ticks degraded, min nodes) is deterministic; the quality cost
   per ladder level is the quality-vs-S curve in BENCH_ablations.json.

7. MULTI-HOST sharded serving (``ShardedServeEngine``): the same mixed
   trace — short shared-system-prompt decodes plus concurrent long-prompt
   admissions — replayed at 1/2/4 hosts x 2 slots (as the forced device
   count allows; the CI multi-host job forces 8). Reports per-host
   admission throughput, ADMISSION TOKENS PER TICK (the deterministic
   scaling metric: with more hosts, more rows co-advance per coalesced
   dispatch, so the same admission burst drains in fewer ticks —
   wall-clock on forced host devices just oversubscribes one CPU), decode
   p99 wall gaps, and the replicated prefix-cache residency (every shard
   must hold the warmed entries: ``replicated_pinned > 0``).

8. DISAGGREGATED prefill/decode fleets (``serving/disagg``): the same
   shape of trace — short decode-heavy requests co-resident with a
   long-prompt (16k full / 2k fast) admission burst — replayed colocated
   (one engine, one clock) vs disaggregated (prefill fleet + decode
   fleet, each on its own simulated per-fleet clock). The burst burns
   PREFILL-fleet clock only, so the decode fleet's inter-token p99 gap
   stays at its unloaded baseline while the colocated engine's decode
   slots eat every admission chunk dispatch. Also records the
   handoff-bytes probe: promote-time wire blobs are byte-IDENTICAL for a
   128-token and a 16k-token prompt (the O(S*d) flat-bytes property) and
   ~halve under ``wire_store="bf16"``. Token streams are checked exact
   vs colocated (f32 wire).

Time is measured in ticks (one mixed scheduler step == one tick), so the
comparisons are deterministic and hardware-independent; wall tokens/sec is
reported alongside. ``main`` writes the full row dict to
``BENCH_serving.json`` (uploaded as a CI artifact).
"""
from __future__ import annotations

import json
import pathlib
import time

import jax
import numpy as np

from benchmarks.common import bench_cfg, emit
from repro.models import transformer as T
from repro.serving import (
    DisaggController,
    FaultSchedule,
    PrefixCache,
    ReplicatedPrefixCache,
    ServeEngine,
    ShardedServeEngine,
)
from repro.serving.disagg import wire_codec
from repro.serving.engine import Request
from repro.utils import trace_probe


def _admission_chunk(fast: bool) -> int:
    """The prefill chunk shared by traces 4 and 5 (and the multihost-only
    CI entry point): both artifacts must report the same configuration."""
    return 64 if fast else 256


def _poisson_arrivals(n: int, rate: float, rng) -> np.ndarray:
    """Arrival ticks with exponential inter-arrival gaps."""
    return np.floor(np.cumsum(rng.exponential(1.0 / rate, n))).astype(np.int64)


def poisson_trace(n_requests: int, rate: float, long_frac: float, seed: int = 0,
                  vocab: int = 256):
    """(requests, arrival ticks): exponential inter-arrivals, mixed budgets."""
    rng = np.random.default_rng(seed)
    arrivals = _poisson_arrivals(n_requests, rate, rng)
    reqs = []
    for i in range(n_requests):
        budget = (int(rng.integers(48, 97)) if rng.random() < long_frac
                  else int(rng.integers(4, 9)))
        prompt = rng.integers(3, vocab, int(rng.integers(4, 13))).astype(np.int32)
        reqs.append(Request(prompt, budget, id=i))
    return reqs, arrivals.tolist()


def _latency_stats(stats):
    lat = np.sort([s["finish"] - s["arrival"] for s in stats.values()])
    return {
        "p50": float(np.percentile(lat, 50)),
        "p99": float(np.percentile(lat, 99)),
        "mean": float(lat.mean()),
        "max": float(lat.max()),
    }


def run_mode(eng: ServeEngine, reqs, arrivals, mode: str, slots: int):
    # untimed replay first: both modes pay their prefill/step compiles here,
    # so the timed pass compares steady-state throughput, not XLA compiles
    eng.serve(reqs, slots=slots, mode=mode, arrivals=arrivals)
    t0 = time.perf_counter()
    results, stats = eng.serve(reqs, slots=slots, mode=mode,
                               arrivals=arrivals, return_stats=True)
    wall = time.perf_counter() - t0
    n_tok = sum(len(v) for v in results.values())
    ls = _latency_stats(stats)
    makespan = max(s["finish"] for s in stats.values())
    return {"wall_s": wall, "tok_s": n_tok / max(wall, 1e-9), "n_tok": n_tok,
            "makespan": makespan, **ls}


def long_prompt_poisson_trace(n_requests: int, rate: float, long_len: int,
                              long_every: int = 5, seed: int = 1,
                              vocab: int = 256):
    """Decode-heavy short requests with concurrent long-prompt admissions:
    every ``long_every``-th request carries a ``long_len``-token prompt
    (prefill-heavy, tiny budget). Returns (reqs, arrivals, short_ids)."""
    rng = np.random.default_rng(seed)
    arrivals = _poisson_arrivals(n_requests, rate, rng)
    reqs, short_ids = [], []
    for i in range(n_requests):
        if i % long_every == long_every - 1:
            prompt = rng.integers(3, vocab, long_len).astype(np.int32)
            budget = 4
        else:
            prompt = rng.integers(3, vocab, int(rng.integers(6, 15))).astype(np.int32)
            budget = int(rng.integers(24, 49))
            short_ids.append(i)
        reqs.append(Request(prompt, budget, id=i))
    return reqs, arrivals.tolist(), short_ids


def _decode_gap_stats(stats, ids):
    """Inter-token wall gaps (streaming smoothness) over the given requests —
    a decode slot stalled behind a monolithic co-resident prefill shows up
    as one huge gap that tick accounting cannot see."""
    per_req = [np.diff(stats[i]["token_walls"]) for i in ids
               if len(stats[i]["token_walls"]) > 1]
    if not per_req:  # every tracked request emitted <= 1 token
        return {"gap_p50_ms": 0.0, "gap_p99_ms": 0.0, "gap_max_ms": 0.0}
    gaps = np.concatenate(per_req)
    return {"gap_p50_ms": float(np.percentile(gaps, 50) * 1e3),
            "gap_p99_ms": float(np.percentile(gaps, 99) * 1e3),
            "gap_max_ms": float(gaps.max() * 1e3)}


def run_admission(eng, reqs, arrivals, slots, prefill_chunk, short_ids):
    eng.serve(reqs, slots=slots, arrivals=arrivals,
              prefill_chunk=prefill_chunk)  # untimed: pay compiles
    t0 = time.perf_counter()
    results, stats = eng.serve(reqs, slots=slots, arrivals=arrivals,
                               prefill_chunk=prefill_chunk, return_stats=True)
    wall = time.perf_counter() - t0
    n_tok = sum(len(v) for v in results.values())
    return {"wall_s": wall, "tok_s": n_tok / max(wall, 1e-9),
            **_decode_gap_stats(stats, short_ids)}


def concurrent_long_prompt_trace(n_long: int, n_short: int, long_base: int,
                                 chunk: int, seed: int = 3, vocab: int = 256):
    """Many long-prompt admissions arriving close together, each with a
    DISTINCT ``len % chunk`` tail residue (the shape-explosion case for
    natural-length tails), plus short decode-heavy bystanders whose
    inter-token gaps expose admission stalls. Returns (reqs, arrivals,
    short_ids)."""
    rng = np.random.default_rng(seed)
    reqs, arrivals, short_ids = [], [], []
    for i in range(n_short):
        reqs.append(Request(
            rng.integers(3, vocab, int(rng.integers(5, 12))).astype(np.int32),
            int(rng.integers(32, 49)), id=i))
        arrivals.append(0)
        short_ids.append(i)
    for j in range(n_long):
        length = long_base + j * chunk // 4 + 1 + j  # distinct residues
        reqs.append(Request(rng.integers(3, vocab, length).astype(np.int32),
                            4, id=n_short + j))
        arrivals.append(j)  # near-simultaneous arrivals: admissions co-pend
    return reqs, arrivals, short_ids


def run_cold_admission(params, cfg, max_len, reqs, arrivals, slots, chunk,
                       short_ids, coalesce: bool):
    """Replay the trace through a FRESH engine (cold jit caches — per-residue
    recompiles are an inherent cost of natural-length tails, not an
    artifact) while counting prefill traces via ``trace_probe``."""
    log: list = []
    orig = {n: getattr(T, n) for n in ("prefill", "prefill_chunk")}
    for n, fn in orig.items():
        setattr(T, n, trace_probe(fn, log, n))
    try:
        eng = ServeEngine(params, cfg, max_len=max_len, prefill_chunk=chunk)
        t0 = time.perf_counter()
        results, stats = eng.serve(reqs, slots=slots, arrivals=arrivals,
                                   coalesce=coalesce, return_stats=True)
        wall = time.perf_counter() - t0
    finally:
        for n, fn in orig.items():
            setattr(T, n, fn)
    prefilled = sum(s["prefilled_tokens"] for s in stats.values())
    n_tok = sum(len(v) for v in results.values())
    shapes = sorted({e[1] for e in log})
    return {"wall_s": wall, "prefill_compiles": len(log),
            "prefill_shapes": [list(s) for s in shapes],
            "prefill_tokens": prefilled,
            "prefill_tok_s": prefilled / max(wall, 1e-9),
            "tok_s": n_tok / max(wall, 1e-9),
            **_decode_gap_stats(stats, short_ids)}


def run_prefix_cache(params, cfg, max_len, sys_len, chunk, n_requests,
                     seed: int = 2):
    """Shared system prompt: cold engine (no cache) vs warmed prefix cache."""
    rng = np.random.default_rng(seed)
    sys_prompt = rng.integers(3, cfg.vocab, sys_len).astype(np.int32)
    reqs = [Request(np.concatenate([
                sys_prompt,
                rng.integers(3, cfg.vocab, 24).astype(np.int32)]), 8, id=i)
            for i in range(n_requests)]
    out = {}
    for label, cache in (("cold", None), ("cached", PrefixCache(32))):
        eng = ServeEngine(params, cfg, max_len=max_len, prefill_chunk=chunk,
                          prefix_cache=cache)
        if cache is not None:
            eng.warm_prefix(sys_prompt)
        eng.serve(reqs, slots=2)  # untimed: pay compiles
        if cache is not None:
            # fresh sys-prompt-only cache: the untimed pass cached the FULL
            # prompts, which would overstate the steady-state hit rate
            eng.prefix_cache = PrefixCache(32)
            eng.warm_prefix(sys_prompt)
        t0 = time.perf_counter()
        _, stats = eng.serve(reqs, slots=2, return_stats=True)
        wall = time.perf_counter() - t0
        prefilled = sum(s["prefilled_tokens"] for s in stats.values())
        total = sum(s["prompt_tokens"] for s in stats.values())
        out[label] = {"wall_s": wall, "prefilled_tokens": prefilled,
                      "prompt_tokens": total,
                      "flops_skipped_frac": 1.0 - prefilled / max(total, 1)}
    out["hit_speedup"] = out["cold"]["wall_s"] / max(out["cached"]["wall_s"], 1e-9)
    return out


def multihost_trace(sys_prompt, n_short: int, n_long: int, long_base: int,
                    chunk: int, seed: int = 9, vocab: int = 256):
    """Short decode requests sharing a warmed system prompt (every host must
    hit its cache replica) plus near-simultaneous long-prompt admissions
    with distinct tail residues (the admission burst whose drain time the
    host count divides). Returns (reqs, arrivals, short_ids)."""
    rng = np.random.default_rng(seed)
    reqs, arrivals, short_ids = [], [], []
    for i in range(n_short):
        reqs.append(Request(np.concatenate([
            sys_prompt,
            rng.integers(3, vocab, int(rng.integers(5, 12))).astype(np.int32)]),
            int(rng.integers(16, 33)), id=i))
        arrivals.append(0)
        short_ids.append(i)
    for j in range(n_long):
        length = long_base + j * chunk // 4 + 1 + j  # distinct residues
        reqs.append(Request(rng.integers(3, vocab, length).astype(np.int32),
                            4, id=n_short + j))
        arrivals.append(j)
    return reqs, arrivals, short_ids


def run_multihost(params, cfg, max_len, chunk, fast: bool):
    """Replay the multi-host trace at every host count the device count
    allows, holding slots_per_host fixed — so host count is the ONLY thing
    that grows the fleet."""
    host_counts = [h for h in (1, 2, 4) if h <= jax.device_count()]
    K = 2
    rng = np.random.default_rng(9)
    sys_len = 2 * chunk + chunk // 2  # non-boundary length: masked warm tail
    sys_prompt = rng.integers(3, cfg.vocab, sys_len).astype(np.int32)
    reqs, arrivals, short_ids = multihost_trace(
        sys_prompt, n_short=4 if fast else 8, n_long=8,
        long_base=512 if fast else 2048, chunk=chunk, vocab=cfg.vocab)
    out = {"device_count": jax.device_count(), "slots_per_host": K,
           "hosts": {}}
    for H in host_counts:
        eng = ShardedServeEngine(
            params, cfg, n_hosts=H, slots_per_host=K, max_len=max_len,
            prefill_chunk=chunk,
            prefix_cache=ReplicatedPrefixCache(H, capacity=64))
        eng.warm_prefix(sys_prompt)
        eng.serve(reqs, arrivals=arrivals)  # untimed: pay compiles
        # fresh warmed cache: the untimed pass cached the full prompts,
        # which would overstate the steady-state hit rate
        cache = ReplicatedPrefixCache(H, capacity=64)
        eng.prefix_cache = cache
        eng.warm_prefix(sys_prompt)
        t0 = time.perf_counter()
        results, stats = eng.serve(reqs, arrivals=arrivals, return_stats=True)
        wall = time.perf_counter() - t0
        n_tok = sum(len(v) for v in results.values())
        prefilled = sum(s["prefilled_tokens"] for s in stats.values())
        # deterministic scaling metric: the admission burst's prefilled
        # tokens over the ticks it took to drain (first admit -> last live)
        admit_ticks = (max(s["live"] for s in stats.values())
                       - min(s["admit"] for s in stats.values()) + 1)
        per_host_prefill = {}
        for s in stats.values():
            per_host_prefill[s["host"]] = (
                per_host_prefill.get(s["host"], 0) + s["prefilled_tokens"])
        cstats = cache.stats()
        row = {
            "wall_s": wall, "tok_s": n_tok / max(wall, 1e-9),
            "prefill_tokens": prefilled, "admission_ticks": int(admit_ticks),
            "prefill_tok_per_tick": prefilled / max(admit_ticks, 1),
            "prefill_tok_s": prefilled / max(wall, 1e-9),
            "per_host_prefill_tokens": {str(k): int(v) for k, v
                                        in sorted(per_host_prefill.items())},
            "cached_tokens": sum(s["cached_tokens"] for s in stats.values()),
            "replicated_pinned": cstats["replicated_pinned"],
            "replication_ok": cstats["replication_ok"],
            "per_shard_hits": [s["hits"] for s in cstats["shards"]],
            **_decode_gap_stats(stats, short_ids),
        }
        out["hosts"][str(H)] = row
        emit(f"serving/multihost_h{H}", wall * 1e6,
             f"prefill_tok_per_tick={row['prefill_tok_per_tick']:.0f};"
             f"admission_ticks={row['admission_ticks']};"
             f"gap_p99_ms={row['gap_p99_ms']:.1f};"
             f"replicated_pinned={row['replicated_pinned']}")
        if not cstats["replication_ok"] or cstats["replicated_pinned"] < 1:
            print("# WARNING: prefix-cache replication did not happen")
    lo, hi = str(host_counts[0]), str(host_counts[-1])
    scale = (out["hosts"][hi]["prefill_tok_per_tick"]
             / max(out["hosts"][lo]["prefill_tok_per_tick"], 1e-9))
    out["admission_scaling"] = {"from_hosts": int(lo), "to_hosts": int(hi),
                                "tok_per_tick_ratio": scale}
    emit("serving/multihost_admission_scaling", 0.0,
         f"ratio={scale:.2f};hosts={lo}->{hi}")
    if len(host_counts) > 1 and scale < 1.2:
        print("# WARNING: admission throughput did not scale with host count")
    return out


def disagg_trace(n_short: int, n_long: int, long_len: int, seed: int = 23,
                 vocab: int = 256):
    """Short decode-heavy requests (the latency-sensitive traffic) plus a
    near-simultaneous burst of ``long_len``-prompt admissions — the
    workload disaggregation exists for. Returns (reqs, arrivals,
    short_ids)."""
    rng = np.random.default_rng(seed)
    reqs, arrivals, short_ids = [], [], []
    for i in range(n_short):
        reqs.append(Request(
            rng.integers(3, vocab, int(rng.integers(6, 15))).astype(np.int32),
            24, id=i))
        arrivals.append(0)
        short_ids.append(i)
    for j in range(n_long):
        reqs.append(Request(rng.integers(3, vocab, long_len).astype(np.int32),
                            4, id=n_short + j))
        arrivals.append(2 + j)  # burst lands while the shorts are decoding
    return reqs, arrivals, short_ids


def run_disagg(params, cfg, chunk, fast: bool):
    """Colocated vs disaggregated serving under a long-prompt admission
    burst, plus the handoff-bytes probe. Decode smoothness is measured on
    each configuration's own decode clock: the colocated engine's decode
    slots share every tick with the burst's chunk dispatches; the disagg
    decode fleet's simulated clock advances only on its OWN dispatches, so
    the burst (which burns prefill-fleet clock) cannot show up in its
    gaps."""
    long_len = 2048 if fast else 16384
    max_len = long_len + 128
    reqs, arrivals, short_ids = disagg_trace(
        n_short=4 if fast else 8, n_long=4, long_len=long_len,
        vocab=cfg.vocab)
    out = {"long_len": long_len, "chunk": chunk}

    eng = ServeEngine(params, cfg, max_len=max_len, prefill_chunk=chunk)
    eng.serve(reqs, slots=4, arrivals=arrivals)  # untimed: pay compiles
    t0 = time.perf_counter()
    base_results, stats = eng.serve(reqs, slots=4, arrivals=arrivals,
                                    return_stats=True)
    wall = time.perf_counter() - t0
    out["colocated"] = {"wall_s": wall,
                        **_decode_gap_stats(stats, short_ids)}

    ctl = DisaggController(params, cfg, n_prefill=2, n_decode=1, slots=2,
                           max_len=max_len, prefill_chunk=chunk)
    ctl.serve(reqs, arrivals=arrivals)  # untimed: pay compiles
    t0 = time.perf_counter()
    results, dstats = ctl.serve(reqs, arrivals=arrivals, return_stats=True)
    wall = time.perf_counter() - t0
    exact = all(list(results[r.id]) == list(base_results[r.id])
                for r in reqs)
    hb = sorted(set(ctl.handoff_bytes.values()))
    out["disagg"] = {"wall_s": wall, "exact": exact,
                     "handoff_bytes": hb,
                     "bytes_flat": len(hb) == 1,
                     "prefill_clock_s": ctl.prefill.clock,
                     "decode_clock_s": ctl.decode.clock,
                     **_decode_gap_stats(dstats, short_ids)}
    gap_ratio = (out["colocated"]["gap_p99_ms"]
                 / max(out["disagg"]["gap_p99_ms"], 1e-9))
    out["gap_p99_colocated_over_disagg"] = gap_ratio
    emit("serving/disagg", wall * 1e6,
         f"gap_p99_ms={out['disagg']['gap_p99_ms']:.1f};"
         f"colocated_gap_p99_ms={out['colocated']['gap_p99_ms']:.1f};"
         f"bytes_flat={out['disagg']['bytes_flat']};exact={exact}")
    if not exact:
        print("# WARNING: disagg serving diverged from colocated tokens")
    if not out["disagg"]["bytes_flat"]:
        print("# WARNING: handoff bytes were not flat across prompt lengths")
    if out["disagg"]["gap_p99_ms"] >= out["colocated"]["gap_p99_ms"]:
        print("# WARNING: disagg decode p99 gap not better than colocated "
              "under the admission burst")

    # handoff-bytes probe: one 128-token and one long_len-token prompt
    # through both wire stores — the flat-bytes / bf16-halving artifact
    rng = np.random.default_rng(29)
    probe = [Request(rng.integers(3, cfg.vocab, n).astype(np.int32), 2, id=i)
             for i, n in enumerate([128, long_len])]
    bytes_by_store = {}
    for store in ("f32", "bf16"):
        pctl = DisaggController(params, cfg, n_prefill=1, n_decode=1,
                                slots=2, max_len=max_len,
                                prefill_chunk=chunk, wire_store=store)
        pctl.serve(probe, arrivals=[0, 0])
        bytes_by_store[store] = {str(len(r.prompt)): pctl.handoff_bytes[r.id]
                                 for r in probe}
    ratio = (bytes_by_store["bf16"][str(128)]
             / max(bytes_by_store["f32"][str(128)], 1))
    out["handoff_bytes_by_prompt_len"] = bytes_by_store
    out["bf16_over_f32_bytes"] = ratio
    # blob compression stacks on bf16 storage (zstd when the module is
    # present, zlib fallback otherwise — the codec is part of the row)
    zctl = DisaggController(params, cfg, n_prefill=1, n_decode=1, slots=2,
                            max_len=max_len, prefill_chunk=chunk,
                            wire_store="bf16", wire_compress="zstd")
    zctl.serve(probe, arrivals=[0, 0])
    zbytes = {str(len(r.prompt)): zctl.handoff_bytes[r.id] for r in probe}
    zratio = zbytes["128"] / max(bytes_by_store["bf16"]["128"], 1)
    out["compressed_bytes_by_prompt_len"] = zbytes
    out["compress_codec"] = wire_codec("zstd")
    out["compressed_over_bf16_bytes"] = zratio
    emit("serving/disagg_bytes", 0.0,
         f"f32_128={bytes_by_store['f32']['128']};"
         f"f32_{long_len}={bytes_by_store['f32'][str(long_len)]};"
         f"bf16_ratio={ratio:.2f};"
         f"{out['compress_codec']}_ratio={zratio:.2f}")
    for store, by_len in bytes_by_store.items():
        if len(set(by_len.values())) != 1:
            print(f"# WARNING: {store} handoff bytes varied with prompt "
                  "length")
    return out


def run_disagg_failover(params, cfg, chunk, fast: bool):
    """Availability under failure: the disagg-trace mixed load with a
    prefill host KILLED mid-burst (seeded, deterministic). Three
    configurations on identical traffic — colocated (no fleet to lose),
    fault-free disagg, and disagg surviving the kill — reporting decode
    p99 gaps, completion latency p99, recovery accounting, and exactness
    of the failover streams against the fault-free run."""
    long_len = 2048 if fast else 16384
    max_len = long_len + 128
    reqs, arrivals, short_ids = disagg_trace(
        n_short=4 if fast else 8, n_long=4, long_len=long_len,
        vocab=cfg.vocab)
    out = {"long_len": long_len, "chunk": chunk, "kill_tick": 4,
           "killed": "prefill/1"}

    eng = ServeEngine(params, cfg, max_len=max_len, prefill_chunk=chunk)
    eng.serve(reqs, slots=4, arrivals=arrivals)  # untimed: pay compiles
    base_results, cstats = eng.serve(reqs, slots=4, arrivals=arrivals,
                                     return_stats=True)
    out["colocated"] = {**_decode_gap_stats(cstats, short_ids),
                        "latency": _latency_stats(cstats)}

    def disagg_run(faults):
        ctl = DisaggController(params, cfg, n_prefill=2, n_decode=1,
                               slots=2, max_len=max_len,
                               prefill_chunk=chunk, faults=faults)
        t0 = time.perf_counter()
        results, dstats = ctl.serve(reqs, arrivals=arrivals,
                                    return_stats=True)
        wall = time.perf_counter() - t0
        return ctl, results, {"wall_s": wall,
                              **_decode_gap_stats(dstats, short_ids),
                              "latency": _latency_stats(dstats)}

    _, ff_results, ff_row = disagg_run(None)
    out["disagg"] = ff_row
    # the kill lands while the long-prompt burst is mid-prefill: the dead
    # host's chunked work requeues onto the survivor
    fctl, f_results, f_row = disagg_run(
        FaultSchedule(0, kills={out["kill_tick"]: (out["killed"],)}))
    fs = fctl.fault_stats()
    exact = all(list(f_results[r.id]) == list(ff_results[r.id])
                for r in reqs)
    f_row.update(exact=exact,
                 detected_failures=fs["detected_failures"],
                 recovered_requests=fs["recovered_requests"],
                 requeued_tokens=fs["requeued_tokens"],
                 retries=fs["retries"])
    out["disagg_failover"] = f_row
    out["failover_over_faultfree_p99"] = (
        f_row["latency"]["p99"] / max(ff_row["latency"]["p99"], 1e-9))
    emit("serving/disagg_failover", f_row["wall_s"] * 1e6,
         f"exact={exact};detected={fs['detected_failures']};"
         f"recovered={fs['recovered_requests']};"
         f"p99_vs_faultfree={out['failover_over_faultfree_p99']:.2f};"
         f"gap_p99_ms={f_row['gap_p99_ms']:.1f}")
    if not exact:
        print("# WARNING: failover streams diverged from fault-free disagg")
    if fs["detected_failures"] < 1:
        print("# WARNING: the scheduled kill was never detected")
    return out


def speculative_trace(n_requests: int, motif_len: int, budget: int,
                      seed: int = 11, vocab: int = 256):
    """Decode-heavy requests whose prompts repeat a short token motif — the
    regime prompt-lookup drafting exploits (the model's greedy continuation
    of a repeated motif is itself locally repetitive, so suffix n-gram
    matches against the request's own context keep proposing right)."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n_requests):
        motif = rng.integers(3, vocab, motif_len).astype(np.int32)
        reps = int(rng.integers(4, 7))
        reqs.append(Request(np.tile(motif, reps), budget, id=i))
    return reqs


def run_speculative(params, cfg, max_len, fast: bool):
    """Plain greedy decode vs draft-verify rounds on the same decode-heavy
    trace: k in {2, 4, 8} with the n-gram draft plus one node-subset row.
    Spec decode is token-exact by construction (pytest-locked), so the only
    interesting numbers are dispatch economics: emitted tokens per verify
    dispatch (> 1 means the batched window beats one-token-per-tick) and
    the draft accept rate that drives it."""
    reqs = speculative_trace(n_requests=6 if fast else 12,
                             motif_len=6, budget=32 if fast else 64,
                             vocab=cfg.vocab)
    slots = 4
    out = {}

    def replay(eng):
        eng.serve(reqs, slots=slots)  # untimed: pay compiles
        t0 = time.perf_counter()
        results, stats = eng.serve(reqs, slots=slots, return_stats=True)
        wall = time.perf_counter() - t0
        n_tok = sum(len(v) for v in results.values())
        return results, stats, wall, n_tok

    eng0 = ServeEngine(params, cfg, max_len=max_len, prefill_chunk=64)
    base_results, _, wall0, n_tok0 = replay(eng0)
    out["plain"] = {"wall_s": wall0, "tok_s": n_tok0 / max(wall0, 1e-9),
                    "n_tok": n_tok0}
    emit("serving/spec_plain", wall0 * 1e6, f"tok_s={out['plain']['tok_s']:.1f}")

    for draft, ks in (("ngram", (2, 4, 8)), ("nodes", (4,))):
        for k in ks:
            eng = ServeEngine(params, cfg, max_len=max_len, prefill_chunk=64,
                              spec_k=k, spec_draft=draft, spec_draft_nodes=4)
            results, _, wall, n_tok = replay(eng)
            exact = all(list(results[r.id]) == list(base_results[r.id])
                        for r in reqs)
            ss = eng.spec_stats
            row = {
                "wall_s": wall, "tok_s": n_tok / max(wall, 1e-9),
                "exact": exact, "verify_calls": ss["verify_calls"],
                "accept_rate": ss["accepted"] / max(ss["drafted"], 1),
                "tok_per_dispatch": ss["emitted"] / max(ss["verify_calls"], 1),
                "speedup_vs_plain": wall0 / max(wall, 1e-9),
            }
            out[f"{draft}_k{k}"] = row
            emit(f"serving/spec_{draft}_k{k}", wall * 1e6,
                 f"tok_per_dispatch={row['tok_per_dispatch']:.2f};"
                 f"accept={100 * row['accept_rate']:.0f}%;"
                 f"exact={exact}")
            if not exact:
                print(f"# WARNING: spec decode ({draft}, k={k}) diverged "
                      "from plain greedy")
    if out["ngram_k4"]["tok_per_dispatch"] <= 1.0:
        print("# WARNING: spec decode did not beat one token per dispatch")
    return out


def run_slo_degradation(params, cfg, max_len, fast: bool):
    """SLO-aware node degradation on a burst trace: every request arrives at
    once, so the admission queue backs up far past ``slo_queue_depth`` and
    the engine walks the degrade ladder down (full S -> 8 -> 4 nodes),
    then restores stepwise as the tail drains. The queue-depth trigger is
    deterministic (tick accounting, not wall clock), so the recorded
    degrade/restore trace is reproducible in CI.

    By design the capped rows share the uncapped decode program (the cap is
    a data argument), so this artifact records the CONTROL trace — when the
    breach fired, how deep the ladder went, how long rows ran degraded —
    not a wall-clock speedup; the quality each ladder level costs is the
    companion quality-vs-S curve in BENCH_ablations.json."""
    rng = np.random.default_rng(17)
    n = 12 if fast else 24
    reqs = [Request(rng.integers(3, cfg.vocab, 12).astype(np.int32),
                    16 if fast else 24, id=i)
            for i in range(n)]
    arrivals = [0] * n  # one burst: the queue depth IS the overload signal
    slots = 2
    out = {}
    for label, kw in (("off", {}),
                      ("on", dict(slo_queue_depth=2, slo_degrade=(8, 4),
                                  slo_recovery_ticks=4))):
        eng = ServeEngine(params, cfg, max_len=max_len, prefill_chunk=64, **kw)
        eng.serve(reqs, slots=slots, arrivals=arrivals)  # pay compiles
        t0 = time.perf_counter()
        results, stats = eng.serve(reqs, slots=slots, arrivals=arrivals,
                                   return_stats=True)
        wall = time.perf_counter() - t0
        n_tok = sum(len(v) for v in results.values())
        row = {"wall_s": wall, "tok_s": n_tok / max(wall, 1e-9),
               **_latency_stats(stats),
               **_decode_gap_stats(stats, [r.id for r in reqs])}
        if kw:
            row["node_stats"] = dict(eng.node_stats)
        out[label] = row
        emit(f"serving/slo_{label}", wall * 1e6,
             f"tok_s={row['tok_s']:.1f};p99={row['p99']:.0f};"
             f"gap_p99_ms={row['gap_p99_ms']:.1f}")
    ns = out["on"]["node_stats"]
    emit("serving/slo_trace", 0.0,
         f"degrades={ns['degrade_steps']};restores={ns['restore_steps']};"
         f"ticks_degraded={ns['ticks_degraded']};min_nodes={ns['min_nodes']};"
         f"queue_breaches={ns['queue_breaches']}")
    if ns["degrade_steps"] == 0:
        print("# WARNING: SLO burst trace never triggered a degrade")
    if ns["restore_steps"] != ns["degrade_steps"]:
        print("# WARNING: SLO trace ended still degraded (tail never drained)")
    return out


def main(fast: bool = False):
    cfg = bench_cfg(mixer="stlt")
    params = T.init_lm(jax.random.key(0), cfg)
    eng = ServeEngine(params, cfg, max_len=256)
    n_requests = 24 if fast else 64
    slots = 4
    reqs, arrivals = poisson_trace(n_requests, rate=0.30, long_frac=0.25,
                                   vocab=cfg.vocab)

    rows = {}
    for mode in ("wave", "continuous"):
        r = run_mode(eng, reqs, arrivals, mode, slots)
        rows[mode] = r
        emit(f"serving/{mode}", r["wall_s"] * 1e6,
             f"tok_s={r['tok_s']:.1f};p50={r['p50']:.0f};p99={r['p99']:.0f};"
             f"makespan={r['makespan']}")

    speedup = rows["wave"]["p99"] / max(rows["continuous"]["p99"], 1e-9)
    emit("serving/p99_wave_over_continuous", 0.0, f"ratio={speedup:.2f}")
    if rows["continuous"]["p99"] >= rows["wave"]["p99"]:
        print("# WARNING: continuous batching did not beat wave p99")

    # --- chunked admission: decode smoothness under concurrent long prefills
    long_len = 2048 if fast else 32768
    chunk = 256 if fast else 2048
    lreqs, larrivals, short_ids = long_prompt_poisson_trace(
        12 if fast else 32, rate=0.25, long_len=long_len, vocab=cfg.vocab)
    for label, pc in (("monolithic", 0), ("chunked", chunk)):
        r = run_admission(eng, lreqs, larrivals, slots, pc, short_ids)
        rows[f"admission_{label}"] = r
        emit(f"serving/admission_{label}", r["wall_s"] * 1e6,
             f"tok_s={r['tok_s']:.1f};gap_p99_ms={r['gap_p99_ms']:.1f};"
             f"gap_max_ms={r['gap_max_ms']:.1f}")
    ratio = (rows["admission_monolithic"]["gap_p99_ms"]
             / max(rows["admission_chunked"]["gap_p99_ms"], 1e-9))
    emit("serving/decode_gap_p99_monolithic_over_chunked", 0.0,
         f"ratio={ratio:.2f};long_len={long_len};chunk={chunk}")
    if rows["admission_chunked"]["gap_p99_ms"] >= rows["admission_monolithic"]["gap_p99_ms"]:
        print("# WARNING: chunked admission did not improve decode p99 gap")

    # --- prefix cache: shared system prompt
    sys_len = 1024 if fast else 4096
    pc_rows = run_prefix_cache(params, cfg, max_len=256, sys_len=sys_len,
                               chunk=chunk, n_requests=6 if fast else 16)
    rows["prefix_cache"] = pc_rows
    emit("serving/prefix_cache", pc_rows["cached"]["wall_s"] * 1e6,
         f"hit_speedup={pc_rows['hit_speedup']:.2f};"
         f"flops_skipped={pc_rows['cached']['flops_skipped_frac']:.3f};"
         f"sys_len={sys_len}")

    # --- row-bucketed batched admission vs the PR-2 one-request-per-tick path
    bchunk = _admission_chunk(fast)
    blong = 512 if fast else 4096
    breqs, barrivals, bshort = concurrent_long_prompt_trace(
        n_long=8, n_short=4 if fast else 8, long_base=blong, chunk=bchunk,
        vocab=cfg.vocab)
    for label, coalesce in (("one_per_tick", False), ("batched", True)):
        r = run_cold_admission(params, cfg, 256, breqs, barrivals,
                               slots=4, chunk=bchunk, short_ids=bshort,
                               coalesce=coalesce)
        rows[f"admission_{label}"] = r
        emit(f"serving/admission_{label}", r["wall_s"] * 1e6,
             f"prefill_tok_s={r['prefill_tok_s']:.0f};"
             f"compiles={r['prefill_compiles']};"
             f"gap_p99_ms={r['gap_p99_ms']:.1f}")
    bspeed = (rows["admission_batched"]["prefill_tok_s"]
              / max(rows["admission_one_per_tick"]["prefill_tok_s"], 1e-9))
    emit("serving/batched_admission_prefill_speedup", 0.0,
         f"ratio={bspeed:.2f};compiles_one_per_tick="
         f"{rows['admission_one_per_tick']['prefill_compiles']};"
         f"compiles_batched={rows['admission_batched']['prefill_compiles']}")
    if bspeed < 2.0:
        print("# WARNING: batched admission below 2x prefill throughput")
    if (rows["admission_batched"]["gap_p99_ms"]
            > rows["admission_one_per_tick"]["gap_p99_ms"]):
        print("# WARNING: batched admission worsened decode p99 gap")

    # --- speculative decoding: draft-verify dispatch economics -------------
    rows["speculative"] = run_speculative(params, cfg, max_len=256, fast=fast)

    # --- SLO-aware node degradation under a burst ---------------------------
    rows["slo_degradation"] = run_slo_degradation(params, cfg, max_len=256,
                                                  fast=fast)

    # --- multi-host sharded serving (scales with forced host devices) ------
    rows["multihost"] = run_multihost(params, cfg, max_len=256, chunk=bchunk,
                                      fast=fast)

    # --- disaggregated prefill/decode fleets --------------------------------
    rows["disagg"] = run_disagg(params, cfg, chunk=bchunk, fast=fast)

    out = {"profile": "fast" if fast else "full", "rows": rows}
    path = _bench_path()
    path.write_text(json.dumps(out, indent=2) + "\n")
    print(f"# wrote {path}")
    return rows


def _bench_path() -> pathlib.Path:
    return pathlib.Path(__file__).resolve().parent.parent / "BENCH_serving.json"


def main_multihost(fast: bool = False):
    """The multi-host trace only — for the forced-device CI job, which would otherwise
    duplicate the four single-host traces the tier-1 job already ran. The
    multihost row is merged into an existing BENCH_serving.json when one is
    present (so the uploaded artifact stays complete)."""
    cfg = bench_cfg(mixer="stlt")
    params = T.init_lm(jax.random.key(0), cfg)
    mh = run_multihost(params, cfg, max_len=256, chunk=_admission_chunk(fast),
                       fast=fast)
    path = _bench_path()
    out = {"profile": "fast" if fast else "full", "rows": {}}
    if path.exists():
        out = json.loads(path.read_text())
    out.setdefault("rows", {})["multihost"] = mh
    path.write_text(json.dumps(out, indent=2) + "\n")
    print(f"# wrote {path}")
    return mh


def main_disagg(fast: bool = False):
    """The disagg trace only — for the CI disagg job; merged into an
    existing BENCH_serving.json when one is present (same pattern as
    ``main_multihost``)."""
    cfg = bench_cfg(mixer="stlt")
    params = T.init_lm(jax.random.key(0), cfg)
    dg = run_disagg(params, cfg, chunk=_admission_chunk(fast), fast=fast)
    fo = run_disagg_failover(params, cfg, chunk=_admission_chunk(fast),
                             fast=fast)
    path = _bench_path()
    out = {"profile": "fast" if fast else "full", "rows": {}}
    if path.exists():
        out = json.loads(path.read_text())
    out.setdefault("rows", {})["disagg"] = dg
    out["rows"]["disagg_failover"] = fo
    path.write_text(json.dumps(out, indent=2) + "\n")
    print(f"# wrote {path}")
    return dg


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--multihost-only", action="store_true",
                    help="run only the multi-host trace and merge it into "
                         "an existing BENCH_serving.json")
    ap.add_argument("--disagg-only", action="store_true",
                    help="run only the disaggregated-fleet trace and merge "
                         "it into an existing BENCH_serving.json")
    args = ap.parse_args()
    if args.multihost_only:
        main_multihost(fast=not args.full)
    elif args.disagg_only:
        main_disagg(fast=not args.full)
    else:
        main(fast=not args.full)
