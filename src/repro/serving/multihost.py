"""Multi-host sharded serving: the slot pool laid over a ``data`` mesh axis.

PR 3 made the engine shardable by construction: coalesced admission is ONE
jitted ``[slots, chunk]`` masked ``prefill_chunk`` dispatch with per-row
``valid_len`` (rows that are not mid-prefill ride along as bit-exact
``valid == 0`` no-ops), and decode is ONE batched ``decode_step`` — both
row-independent. So splitting the slot axis across a device mesh needs no
new program shapes and no cross-row communication: :class:`ShardedServeEngine`
``shard_map``s the same two dispatches over a 1-D ``("data",)`` mesh, giving
each of the ``n_hosts`` shards a contiguous ``slots_per_host`` row range of
the global pool.

Layout (H hosts x K slots each; global slot g = h*K + local):

    decode pool   [ host0: rows 0..K-1 | host1: rows K..2K-1 | ... ]  P("data")
    prefill pool  [ same layout, second slot-shaped pool              P("data")
    params        replicated                                          P()

Host-local pieces stay host-local, mirroring a real multi-process
deployment even when the "hosts" are forced host-platform devices in one
process:

* **Admission queues** — arrivals are dealt to the least-loaded host's
  queue (deterministic: queued + occupied, lowest host id wins ties); each
  host admits from its own queue into its own row range only.
* **Scheduler bookkeeping** — one PR-1 :class:`Scheduler` per host tracks
  its K rows; per-request stats gain a ``host`` field.
* **Prefix cache** — a :class:`ReplicatedPrefixCache` keeps one cache per
  shard: pinned warmed entries (``warm_prefix``) replicate to every shard
  so any host serves a system-prompt hit locally; per-request boundary
  snapshots route to the owning host's shard only.

Slot splicing crosses the shard boundary through three more ``shard_map``'d
ops: ``insert``/``reset`` compute the owning shard from the global slot id
and select the update locally (non-owners pass their rows through
untouched — no communication), and ``extract`` masks non-owner rows to zero
and ``psum``s over ``data`` to hand every host the owner's batch-1 state.

The TWO-SHAPE invariant survives sharding (DESIGN.md §Serving): every
prefill tick is the full ``[H*K, chunk]`` masked dispatch — ``[K, chunk]``
per shard, ONE program — and ``warm_prefix`` keeps its host-local
``[1, chunk]`` shape, so a sharded serve trace over arbitrarily many
``prompt_len % chunk`` residues still compiles exactly two prefill
programs (``tests/test_multihost_serving.py`` locks this, and token-exact
parity vs the single-host engine, under forced host devices).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed.sharding import decode_state_specs
from repro.models import transformer as T
from repro.serving.engine import Scheduler, ServeEngine, _Host
from repro.serving.prefix_cache import PrefixCache, ReplicatedPrefixCache
from repro.serving.sampler import sample_slot_tokens


def make_serve_mesh(n_hosts: int):
    """The serving mesh: 1-D ``("data",)`` over ``n_hosts`` devices (the
    slot pool's batch axis lives on ``data``; params are replicated).

    The axis is ``Auto``: the shard_map'd dispatches state their own
    layouts, and the host-side glue between them (row slices for promote
    and splice, sampling) takes the pool's sharding as a layout, not as
    part of the array type as an ``Explicit`` axis (``jax.make_mesh``'s
    default) would."""
    if n_hosts < 1:
        raise ValueError(f"n_hosts must be >= 1 (got {n_hosts})")
    if n_hosts > jax.device_count():
        raise ValueError(
            f"n_hosts={n_hosts} exceeds {jax.device_count()} available "
            "devices (force host devices via XLA_FLAGS="
            "--xla_force_host_platform_device_count=N)")
    return jax.make_mesh((n_hosts,), ("data",), axis_types=(AxisType.Auto,))


class ShardedServeEngine(ServeEngine):
    """Slot-level continuous batching with the slot pool sharded over a
    ``("data",)`` mesh: per-host admission queues and Schedulers feed
    per-host row ranges of the single batched prefill/decode dispatches.

    Construction fixes the fleet shape (``n_hosts x slots_per_host`` slots);
    ``serve`` therefore takes no ``slots``/``mode``/``coalesce`` arguments —
    admission is always the coalesced two-shape path, which is what makes
    the slot axis shardable in the first place. Token outputs are exact vs
    the single-host :class:`ServeEngine` on the same trace (greedy; sampled
    requests share the same per-request ``fold_in(id)`` streams but key
    evolution depends on scheduling)."""

    def __init__(self, params, cfg: ModelConfig, *, mesh=None,
                 n_hosts: Optional[int] = None, slots_per_host: int = 4,
                 max_len: int = 4096, temperature: float = 0.0,
                 eos_id: int = -1, top_k: int = 0, prefill_chunk: int = 256,
                 prefix_cache: Optional[ReplicatedPrefixCache] = None,
                 spec_k: int = 0, spec_draft: str = "ngram",
                 spec_draft_nodes: int = 4, spec_adaptive: bool = False,
                 spec_accept_floor: float = 0.4, spec_adapt_window: int = 8,
                 spec_adapt_recovery: int = 4,
                 serve_nodes: Optional[int] = None, slo_gap_ms: float = 0.0,
                 slo_queue_depth: int = 0, slo_degrade=(),
                 slo_recovery_ticks: int = 8):
        if prefill_chunk < 1:
            raise ValueError(
                "ShardedServeEngine admits through the chunked two-shape "
                f"path only: prefill_chunk must be >= 1 (got {prefill_chunk})")
        if slots_per_host < 1:
            raise ValueError(f"slots_per_host must be >= 1 (got {slots_per_host})")
        if isinstance(prefix_cache, PrefixCache):
            raise TypeError(
                "ShardedServeEngine routes cache traffic per shard: pass a "
                "ReplicatedPrefixCache (or None), not a bare PrefixCache")
        super().__init__(params, cfg, max_len=max_len, temperature=temperature,
                         eos_id=eos_id, top_k=top_k, prefill_chunk=prefill_chunk,
                         prefix_cache=prefix_cache, spec_k=spec_k,
                         spec_draft=spec_draft,
                         spec_draft_nodes=spec_draft_nodes,
                         spec_adaptive=spec_adaptive,
                         spec_accept_floor=spec_accept_floor,
                         spec_adapt_window=spec_adapt_window,
                         spec_adapt_recovery=spec_adapt_recovery,
                         serve_nodes=serve_nodes, slo_gap_ms=slo_gap_ms,
                         slo_queue_depth=slo_queue_depth,
                         slo_degrade=slo_degrade,
                         slo_recovery_ticks=slo_recovery_ticks)
        self.mesh = mesh if mesh is not None else make_serve_mesh(
            n_hosts if n_hosts is not None else jax.device_count())
        if "data" not in self.mesh.axis_names:
            raise ValueError(
                f"serving mesh needs a 'data' axis (got {self.mesh.axis_names})")
        self.n_hosts = int(self.mesh.shape["data"])
        self.slots_per_host = slots_per_host
        self.n_slots = self.n_hosts * slots_per_host
        if prefix_cache is not None and prefix_cache.n_shards != self.n_hosts:
            raise ValueError(
                f"prefix cache has {prefix_cache.n_shards} shards for "
                f"{self.n_hosts} hosts")

        plan = T.execution_plan(cfg)
        state_abs = jax.eval_shape(
            lambda: T.init_decode_state(cfg, self.n_slots, max_len))
        spec = decode_state_specs(state_abs, plan)
        K = slots_per_host
        mesh_, rep = self.mesh, P()

        # the same two row-independent dispatches as the single-host engine,
        # shard_map'd so each host runs its own K-row range; params replicated
        # per-row node caps ride the data axis like the token rows: the
        # engine always passes a [B] caps array (full-S when nobody is
        # capped), so capped and uncapped traffic share ONE program here too
        def _step_body(params, tok, state, caps):
            return T.decode_step(params, cfg=cfg, token_t=tok, state=state,
                                 node_cap=caps)

        def _prefill_body(params, toks, state, valid):
            return T.prefill_chunk(params, cfg=cfg, inputs=toks, state=state,
                                   valid_len=valid)

        # speculative verify is row-independent like prefill_chunk (PR-3
        # masked contract + per-row accepted-length rollback), so it shards
        # the same way: each host scores its own [K, k+1] window
        def _verify_body(params, toks, state, valid, caps):
            return T.spec_verify(params, cfg=cfg, inputs=toks, state=state,
                                 valid_len=valid, node_cap=caps)

        # slot splicing by global id: the owner shard selects the update in,
        # everyone else passes their rows through — no communication
        def _owner(slot):
            local = slot - jax.lax.axis_index("data") * K
            return (local >= 0) & (local < K), jnp.clip(local, 0, K - 1)

        def _insert_body(pool, state1, slot):
            owns, idx = _owner(slot)
            upd = T.insert_slot(pool, state1, idx, cfg)
            return jax.tree_util.tree_map(
                lambda n, o: jnp.where(owns, n, o), upd, pool)

        def _extract_body(pool, slot):
            owns, idx = _owner(slot)
            row = T.extract_slot(pool, idx, cfg)
            # non-owners contribute zeros; the psum replicates the owner's row
            return jax.tree_util.tree_map(
                lambda x: jax.lax.psum(
                    jnp.where(owns, x, jnp.zeros_like(x)), "data"), row)

        self._step_sh = jax.jit(jax.shard_map(
            _step_body, mesh=mesh_, in_specs=(rep, P("data"), spec, P("data")),
            out_specs=(P("data"), spec)))
        self._prefill_sh = jax.jit(jax.shard_map(
            _prefill_body, mesh=mesh_,
            in_specs=(rep, P("data"), spec, P("data")),
            out_specs=(P("data"), spec)))
        self._verify_sh = jax.jit(jax.shard_map(
            _verify_body, mesh=mesh_,
            in_specs=(rep, P("data"), spec, P("data"), P("data")),
            out_specs=(P("data"), P("data"), spec)))
        self._insert_sh = jax.jit(jax.shard_map(
            _insert_body, mesh=mesh_, in_specs=(spec, rep, rep), out_specs=spec))
        self._extract_sh = jax.jit(jax.shard_map(
            _extract_body, mesh=mesh_, in_specs=(spec, rep), out_specs=rep))
        # each shard samples its own rows: the decode logits come out laid
        # over "data", and the per-slot keys and temperatures are sliced
        # the same way on entry, so no shard gathers another's logits
        self._sample = jax.jit(jax.shard_map(
            partial(sample_slot_tokens, top_k=top_k), mesh=mesh_,
            in_specs=(P("data"), P("data"), P("data")),
            out_specs=P("data")))
        # pristine batch-1 template: seeds fresh prefills and resets rows
        self._fresh1 = T.init_decode_state(cfg, 1, max_len)

    # -------------------------------------------- dispatch-op overrides
    # The tick body itself lives in ServeEngine._serve_ticks — the sharded
    # engine swaps in its shard_map'd dispatches, per-shard cache routing,
    # and least-loaded arrival routing, and inherits everything else.

    # never size the prefill dispatch to the pending rows: they sit on
    # different shards, so the sharded trace stays two-shape ([K, chunk]
    # serve dispatches + the host-local [1, chunk] warm_prefix shape)
    # regardless of admission patterns
    _row_buckets = False

    def _ops_insert(self, pool, st1, g):
        return self._insert_sh(pool, st1, g)

    def _ops_extract(self, pool, g):
        return self._extract_sh(pool, g)

    def _ops_reset(self, pool, g):
        return self._insert_sh(pool, self._fresh1, g)

    def _ops_prefill_pool(self, params, toks, state, valid):
        return self._prefill_sh(params, toks, state, valid)

    def _ops_decode(self, params, tok, pool, caps=None):
        if caps is None:
            caps = self._full_caps(int(tok.shape[0]))
        return self._step_sh(params, tok, pool, caps)

    def _ops_verify(self, params, toks, valid, pool, caps=None):
        if caps is None:
            caps = self._full_caps(int(toks.shape[0]))
        return self._verify_sh(params, toks, pool, valid, caps)

    def _ops_lookup(self, prompt: np.ndarray, h: int):
        if self.prefix_cache is None:
            return 0, None, None
        entry = self.prefix_cache.lookup(prompt, shard=h)
        if entry is None:
            return 0, None, None
        return entry.n_tokens, entry.state, entry.logits

    def _ops_cache_insert(self, prompt, n: int, state, logits, h: int):
        if self.prefix_cache is not None and n > 0:
            self.prefix_cache.insert(prompt[:n], state, logits, shard=h)

    def _route_arrivals(self, hosts, queue, tick):
        """Deal arrivals to the least-loaded host's queue (deterministic:
        queued + occupied, lowest host id wins ties)."""
        while queue and queue[0][0] <= tick:
            arrival, req = queue.pop(0)
            load = [len(h_.queue) + int(h_.sched.live.sum())
                    + int(h_.sched.pending.sum()) for h_ in hosts]
            hosts[int(np.argmin(load))].queue.append((arrival, req))

    # -------------------------------------------------------------- serve
    def serve(self, requests: list, arrivals=None, rng_seed: int = 0,
              return_stats: bool = False, prompt_len: Optional[int] = None):
        """Serve a request list across the sharded slot pool. Returns
        ``{request_id: tokens}`` (plus per-request stats — each carrying the
        ``host`` that served it — when ``return_stats``).

        Scheduling (the shared ``_serve_ticks`` body with this engine's
        dispatch ops): arrivals are dealt to the least-loaded host's queue;
        each host admits from its own queue into its own rows; every tick
        runs at most ONE ``[n_slots, chunk]`` masked prefill dispatch (all
        hosts' pending admissions advance together) and ONE ``[n_slots]``
        decode step — or, with ``spec_k``, one sharded draft-verify round.
        Under greedy decoding token outputs are exact vs the single-host
        engine regardless of the routing."""
        hosts = [_Host(self.slots_per_host) for _ in range(self.n_hosts)]
        return self._serve_ticks(hosts, requests, prompt_len, arrivals,
                                 rng_seed, return_stats, self.prefill_chunk,
                                 coalesce=True)
