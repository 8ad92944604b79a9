"""Serving driver: batched requests through the ServeEngine.

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --reduced \
      --requests 8 --prompt-len 32 --max-new 16 --mode continuous \
      --prefill-chunk 128 --system-prompt-len 64

``--mode continuous`` (default) is the slot-level continuous-batching
scheduler; ``--mode wave`` is the legacy admission-wave baseline.
``--prefill-chunk N`` admits long prompts incrementally (N tokens per tick,
interleaved with decode); all co-pending admissions advance in one batched
masked dispatch per tick, sized to the pending rows' power-of-two row
bucket (``--sequential-admission`` reverts to the one-request-per-tick
path with natural-length tails).
``--system-prompt-len K`` prepends a shared K-token system prompt to every
request and serves it through the prefix cache, reporting the prefill
FLOPs skipped; ``--prefix-cache-max-mb`` switches the cache to bytes-aware
eviction (attention KV entries dwarf O(S*d) STLT entries);
``--prefix-cache-ttl`` expires unpinned snapshots after that many idle
ticks. ``--spec-k K`` turns greedy decode ticks into draft-verify rounds:
K draft tokens (``--spec-draft ngram|nodes``) verified per tick in ONE
``prefill_chunk``-shaped dispatch, emitting the exact plain-greedy stream.

``--mesh-data H`` serves through the multi-host ShardedServeEngine: the
slot pool's batch axis is laid over a 1-D ("data",) mesh of H shards
(``--slots-per-host`` rows each, per-host admission queues, replicated
prefix cache). Needs H devices — force host devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=H`` on one box:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m \
      --reduced --mesh-data 4 --slots-per-host 2 --prefill-chunk 128 \
      --requests 16 --system-prompt-len 64

``--role disagg`` serves through the disaggregated prefill/decode
controller (``serving/disagg``): ``--prefill-hosts``/``--decode-hosts``
size the two fleets, promote-time states ship as O(S*d) wire blobs (flat
in prompt length; ``--wire-store bf16`` halves them), ``--steal-threshold``
enables cross-role work stealing, and the report block prints handoff
bytes/request, gossip hit rate, steal count, and the per-fleet clocks.
``--role controller --listen host:port --workers N`` drives N socket-
connected prefill workers instead of in-process hosts; start each with
``--role prefill --connect host:port`` (model config + init seed cross
the wire, weights never do).

  PYTHONPATH=src python -m repro.launch.serve --role disagg \
      --prefill-hosts 2 --decode-hosts 2 --prefill-chunk 64 \
      --requests 8 --system-prompt-len 64 --wire-store bf16
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import configs as configs_lib
from repro.launch.train import paper_small
from repro.models import transformer as T
from repro.serving import (
    PrefixCache,
    ReplicatedPrefixCache,
    ServeEngine,
    ShardedServeEngine,
)
from repro.serving.engine import Request
from repro.serving.trace import summary as tick_summary
from repro.utils import cast_params_for_compute, enable_compile_cache, tree_size


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--variant", default="native")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--mode", default="continuous", choices=["continuous", "wave"])
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked admission size (0 = monolithic prefill)")
    ap.add_argument("--sequential-admission", action="store_true",
                    help="legacy one-request-per-tick chunked admission "
                         "(natural-length tails; recompiles per residue)")
    ap.add_argument("--system-prompt-len", type=int, default=0,
                    help="shared system-prompt tokens served via the prefix cache")
    ap.add_argument("--prefix-cache-capacity", type=int, default=None,
                    help="entry-count cap (default: 32 when no byte cap is "
                         "set; combine with --prefix-cache-max-mb to co-cap)")
    ap.add_argument("--prefix-cache-max-mb", type=float, default=0,
                    help="bytes-aware prefix-cache cap in MiB (0 = entry-count LRU)")
    ap.add_argument("--prefix-cache-ttl", type=int, default=0,
                    help="expire unpinned cache snapshots idle for this many "
                         "ticks (0 = no TTL)")
    ap.add_argument("--mesh-data", type=int, default=0,
                    help="shard the slot pool over this many hosts "
                         "(ShardedServeEngine; 0 = single-host engine)")
    ap.add_argument("--slots-per-host", type=int, default=0,
                    help="decode slots per host shard (default: --slots)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: verify k draft tokens per "
                         "tick in one dispatch (0 = plain greedy decode; "
                         "requires temperature 0, continuous mode)")
    ap.add_argument("--spec-draft", default="ngram",
                    choices=["ngram", "nodes"],
                    help="draft source: prompt-lookup n-gram (host-side, "
                         "zero dispatches) or node-subset self-draft")
    ap.add_argument("--spec-draft-nodes", type=int, default=4,
                    help="top-m Laplace nodes kept per head in the "
                         "node-subset draft (--spec-draft nodes)")
    ap.add_argument("--serve-nodes", type=int, default=0,
                    help="decode every request with only the top-m Laplace "
                         "nodes per head (0 = full S; STLT archs only)")
    ap.add_argument("--slo-gap-ms", type=float, default=0.0,
                    help="SLO: degrade node budget when the wall-clock gap "
                         "between decode ticks exceeds this (0 = off)")
    ap.add_argument("--slo-queue-depth", type=int, default=0,
                    help="SLO: degrade node budget when this many requests "
                         "are still queued after admission (0 = off)")
    ap.add_argument("--slo-degrade", default="",
                    help="comma-separated node-budget ladder for SLO "
                         "degradation, e.g. '16,8,4' (requires a trigger: "
                         "--slo-gap-ms or --slo-queue-depth)")
    ap.add_argument("--spec-adaptive", action="store_true",
                    help="per-request adaptive draft window: shrink a "
                         "slot's k when its rolling accept rate drops "
                         "below --spec-accept-floor, restore stepwise "
                         "(requires --spec-k >= 2)")
    ap.add_argument("--spec-accept-floor", type=float, default=0.4)
    ap.add_argument("--spec-adapt-window", type=int, default=8)
    ap.add_argument("--spec-adapt-recovery", type=int, default=4)
    ap.add_argument("--role", default="colocated",
                    choices=["colocated", "disagg", "controller", "prefill"],
                    help="colocated: single engine (default). disagg: "
                         "prefill/decode fleets over an in-process "
                         "transport. controller: disagg with socket-"
                         "connected prefill workers (--listen, --workers). "
                         "prefill: run one worker process (--connect)")
    ap.add_argument("--prefill-hosts", type=int, default=1)
    ap.add_argument("--decode-hosts", type=int, default=1)
    ap.add_argument("--steal-threshold", type=int, default=0,
                    help="steal queued prefill work onto idle decode hosts "
                         "when the unadmitted backlog reaches this (0 = off)")
    ap.add_argument("--wire-store", default="f32", choices=["f32", "bf16"],
                    help="handoff state dtype on the wire (bf16 ~halves "
                         "bytes; logits always stay f32)")
    ap.add_argument("--wire-compress", default="", choices=["", "zstd"],
                    help="compress handoff blobs (zstd, falling back to "
                         "zlib when the zstandard module is absent)")
    ap.add_argument("--chaos-seed", type=int, default=None,
                    help="inject a seeded message-fault schedule "
                         "(drop/dup/delay/corrupt) into the disagg "
                         "transport — the failure drill; streams stay "
                         "token-exact")
    ap.add_argument("--listen", default="127.0.0.1:18631",
                    help="controller bind address (--role controller)")
    ap.add_argument("--connect", default="127.0.0.1:18631",
                    help="controller address (--role prefill)")
    ap.add_argument("--worker-name", default="prefill/0")
    ap.add_argument("--workers", type=int, default=1,
                    help="remote prefill workers to wait for "
                         "(--role controller)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.role == "prefill":
        # a prefill worker builds everything from the controller's config
        # message (params from the shared init seed) — no local model args
        from repro.serving.disagg import worker as worker_lib
        worker_lib.main(["--connect", args.connect,
                         "--name", args.worker_name])
        return

    cfg = paper_small() if args.arch is None else configs_lib.get_config(
        args.arch, args.variant)
    if args.reduced and args.arch is not None:
        cfg = cfg.reduced()
    if cfg.family == "encdec":
        raise SystemExit("enc-dec serving: see examples/translate.py")
    params = T.init_lm(jax.random.key(0), cfg)
    params = cast_params_for_compute(params, cfg.act_dtype)
    print(f"[serve] {cfg.name}: {tree_size(params)/1e6:.1f}M params")

    if args.mode == "wave" and (args.prefill_chunk or args.system_prompt_len):
        # the wave baseline prefills monolithically and never consults the
        # cache — warming it would waste a full prefill and report nonsense
        print("[serve] note: --prefill-chunk/--system-prompt-len apply to "
              "continuous mode only; ignored for --mode wave")
    if args.spec_k and args.mode == "wave":
        raise SystemExit("--spec-k applies to continuous mode only (the "
                         "wave baseline decodes one token per tick)")
    if args.spec_k and args.temperature > 0:
        raise SystemExit("--spec-k requires greedy decoding (temperature 0): "
                         "the verify rule is exact for argmax streams only")
    spec_kw = dict(spec_k=args.spec_k, spec_draft=args.spec_draft,
                   spec_draft_nodes=args.spec_draft_nodes,
                   spec_adaptive=args.spec_adaptive,
                   spec_accept_floor=args.spec_accept_floor,
                   spec_adapt_window=args.spec_adapt_window,
                   spec_adapt_recovery=args.spec_adapt_recovery)
    ladder = tuple(int(m) for m in args.slo_degrade.split(",") if m.strip())
    node_kw = dict(serve_nodes=args.serve_nodes or None,
                   slo_gap_ms=args.slo_gap_ms,
                   slo_queue_depth=args.slo_queue_depth,
                   slo_degrade=ladder)
    disagg = args.role in ("disagg", "controller")
    if disagg and args.mode == "wave":
        raise SystemExit("--role disagg serves continuous mode only")
    if disagg and args.mesh_data:
        raise SystemExit("--role disagg and --mesh-data are separate fleet "
                         "layouts; pick one")
    use_cache = args.system_prompt_len and args.mode == "continuous"
    cache = None
    cache_kw = dict(
        # with only a byte cap given, eviction is purely bytes-aware
        # (capacity=None); PrefixCache defaults to 32 entries when neither
        # cap is set, and an explicit capacity co-caps alongside max_bytes
        capacity=args.prefix_cache_capacity,
        max_bytes=int(args.prefix_cache_max_mb * 2**20) or None,
        ttl_ticks=args.prefix_cache_ttl or None,
        # content dedup digests every inserted state (a host readback of
        # the leaves) — the right trade for O(S*d) STLT entries, not for
        # KV-buffer entries (unbounded or windowed attention)
        dedup=not any(bt in ("attn", "local_attn")
                      for bt, _ in T.execution_plan(cfg)))
    ctl = None
    remote = None
    if disagg:
        from repro.serving import DisaggController, FaultSchedule
        from repro.serving.disagg.transport import Message, SocketTransport
        transport = None
        if args.role == "controller":
            import dataclasses
            if use_cache:
                raise SystemExit("--system-prompt-len with remote prefill "
                                 "workers is not supported yet (warm_prefix "
                                 "does not cross the wire)")
            host, port = args.listen.rsplit(":", 1)
            transport = SocketTransport("controller", listen=(host, int(port)))
            names: list[str] = []
            deadline = time.monotonic() + 120
            while len(names) < args.workers and time.monotonic() < deadline:
                names += [m.src for m in
                          transport.recv("controller", timeout=0.2)
                          if m.kind == "hello"]
            if len(names) < args.workers:
                raise SystemExit(f"only {len(names)}/{args.workers} prefill "
                                 f"workers connected")
            payload = {"cfg": dataclasses.asdict(cfg), "seed": 0,
                       "max_len": args.max_len,
                       "prefill_chunk": args.prefill_chunk or 64,
                       "slots": args.slots, "prompt_len": None,
                       "wire_store": args.wire_store,
                       "wire_compress": args.wire_compress or None}
            for n in names:
                transport.send(Message("config", "controller", n, payload))
            remote = names
            print(f"[serve] controller: remote prefill workers {names}")
        ctl = DisaggController(
            params, cfg, n_prefill=args.prefill_hosts,
            n_decode=args.decode_hosts, slots=args.slots,
            max_len=args.max_len, temperature=args.temperature,
            prefill_chunk=args.prefill_chunk or 64, transport=transport,
            steal_threshold=args.steal_threshold,
            wire_store=args.wire_store,
            wire_compress=args.wire_compress or None,
            faults=(None if args.chaos_seed is None else
                    FaultSchedule(args.chaos_seed, drop=0.05, dup=0.05,
                                  delay=0.05, corrupt=0.05)),
            prefix_cache_factory=((lambda: PrefixCache(**cache_kw))
                                  if use_cache and remote is None else None),
            remote_prefill=remote, **spec_kw, **node_kw)
        eng = ctl.decode
        if use_cache and remote is None:
            cache = ctl.prefill.caches[0]
        print(f"[serve] disagg: {args.prefill_hosts} prefill x "
              f"{args.decode_hosts} decode hosts ({args.slots} slots each), "
              f"wire={args.wire_store}"
              + (f"+{args.wire_compress}" if args.wire_compress else "")
              + (f", chaos seed={args.chaos_seed}"
                 if args.chaos_seed is not None else ""))
    elif args.mesh_data:
        if args.mode == "wave":
            raise SystemExit("--mesh-data shards the continuous engine only")
        if args.sequential_admission:
            raise SystemExit(
                "--sequential-admission is the single-host legacy path; "
                "sharded admission is always the coalesced two-shape dispatch")
        if not args.prefill_chunk:
            raise SystemExit(
                "--mesh-data serves through the chunked two-shape admission "
                "path only: pass --prefill-chunk N (monolithic admission "
                "does not shard)")
        if use_cache:
            cache = ReplicatedPrefixCache(args.mesh_data, **cache_kw)
        eng = ShardedServeEngine(
            params, cfg, n_hosts=args.mesh_data,
            slots_per_host=args.slots_per_host or args.slots,
            max_len=args.max_len, temperature=args.temperature,
            prefill_chunk=args.prefill_chunk, prefix_cache=cache,
            **spec_kw, **node_kw)
        print(f"[serve] sharded: {eng.n_hosts} hosts x "
              f"{eng.slots_per_host} slots over mesh {dict(eng.mesh.shape)}")
    else:
        if use_cache:
            cache = PrefixCache(**cache_kw)
        eng = ServeEngine(params, cfg, max_len=args.max_len,
                          temperature=args.temperature,
                          prefill_chunk=args.prefill_chunk, prefix_cache=cache,
                          **spec_kw, **node_kw)
    rng = np.random.default_rng(0)
    sys_len = args.system_prompt_len if use_cache else 0
    sys_prompt = rng.integers(3, cfg.vocab, sys_len).astype(np.int32)
    reqs = [
        Request(np.concatenate([
                    sys_prompt,
                    rng.integers(3, cfg.vocab, rng.integers(4, args.prompt_len)).astype(np.int32)]),
                args.max_new, id=i)
        for i in range(args.requests)
    ]
    if cache is not None:
        warmer = ctl if ctl is not None else eng
        warmed = warmer.warm_prefix(sys_prompt,
                                    chunk=args.prefill_chunk or None)
        print(f"[serve] prefix cache warmed: {warmed} tokens")
    t0 = time.time()
    if ctl is not None:
        results, stats = ctl.serve(reqs, rng_seed=0, return_stats=True)
    elif args.mesh_data:
        results, stats = eng.serve(
            reqs, prompt_len=None if use_cache else args.prompt_len,
            return_stats=True)
    else:
        results, stats = eng.serve(
            reqs, slots=args.slots,
            prompt_len=None if use_cache else args.prompt_len,
            mode=args.mode, return_stats=True,
            coalesce=not args.sequential_admission)
    dt = time.time() - t0
    n_tok = sum(len(v) for v in results.values())
    for rid in sorted(results):
        print(f"  req {rid}: {results[rid][:12]}{'...' if len(results[rid]) > 12 else ''}")
    lat = sorted(s["finish"] - s["arrival"] for s in stats.values())
    p50 = lat[len(lat) // 2]
    p99 = lat[min(len(lat) - 1, int(len(lat) * 0.99))]
    print(f"[serve] mode={args.mode}: {len(reqs)} requests, {n_tok} tokens in "
          f"{dt:.2f}s ({n_tok/max(dt,1e-9):.1f} tok/s), "
          f"latency p50={p50} p99={p99} ticks")
    if ctl is not None:
        rep = ctl.report()
        print(f"[serve] disagg role={args.role}: "
              f"{rep['handoff_requests']} handoffs, bytes/request "
              f"[{rep['handoff_bytes_min']}, {rep['handoff_bytes_max']}] "
              + ("(compressed) " if args.wire_compress
                 else "(flat in prompt length) ")
              + f"steals={rep['steal_count']}, "
              f"gossip sent={rep['gossip_sent']} "
              f"hit-rate={rep['gossip_hit_rate']}")
        print(f"[serve] fleet clocks: prefill={rep['prefill_clock_s']} "
              f"decode={rep['decode_clock_s']}; "
              f"transport msgs={rep['transport']['msgs']}")
        fstats = rep["fault_stats"]
        if (fstats["detected_failures"] or fstats["retries"]
                or any(fstats["injected"].values())):
            print(f"[serve] faults: injected={fstats['injected']} "
                  f"detected={fstats['detected_failures']} "
                  f"recovered={fstats['recovered_requests']} "
                  f"requeued-tokens={fstats['requeued_tokens']} "
                  f"corrupt-rejected={fstats['corrupt_blobs_rejected']} "
                  f"dups-ignored={fstats['dup_msgs_ignored']} "
                  f"retries={fstats['retries']} "
                  f"degraded={fstats['degraded_colocated']}")
        if remote:
            from repro.serving.disagg.transport import Message
            for n in remote:
                ctl.transport.send(Message("bye", "controller", n, {}))
            ctl.transport.close()
    if args.spec_k:
        ss = eng.spec_stats
        acc = ss["accepted"] / max(ss["drafted"], 1)
        print(f"[serve] spec k={ss['k']} ({args.spec_draft}): "
              f"{ss['verify_calls']} verify dispatches for {ss['emitted']} "
              f"tokens ({ss['emitted']/max(ss['verify_calls'],1):.2f} "
              f"tok/dispatch), draft accept rate {100*acc:.1f}%")
        if args.spec_adaptive:
            print(f"[serve] spec adapt: {ss['adapt_shrinks']} shrinks / "
                  f"{ss['adapt_restores']} restores "
                  f"(min k {ss['adapt_min_k']}, floor {ss['adapt_floor']})")
    if eng.tick_stats["ticks"]:
        print(f"[serve] tick phases: {tick_summary(eng.tick_stats)}")
    if ladder:
        ns = eng.node_stats
        print(f"[serve] slo ladder={ns['ladder']}: "
              f"{ns['degrade_steps']} degrades / {ns['restore_steps']} "
              f"restores, {ns['ticks_degraded']} ticks degraded "
              f"(min {ns['min_nodes']} nodes; breaches: "
              f"gap={ns['gap_breaches']} queue={ns['queue_breaches']})")
    if args.mesh_data:
        per_host = {h: 0 for h in range(eng.n_hosts)}
        for s in stats.values():
            per_host[s["host"]] += 1
        print(f"[serve] per-host requests: {per_host}")
    if cache is not None:
        prefilled = sum(s["prefilled_tokens"] for s in stats.values())
        total = sum(s["prompt_tokens"] for s in stats.values())
        print(f"[serve] prefix cache: {cache.stats()}; prefilled "
              f"{prefilled}/{total} prompt tokens "
              f"({100 * (1 - prefilled / max(total, 1)):.1f}% FLOPs skipped)")


if __name__ == "__main__":
    main()
