"""Slot-level continuous-batching serve engine.

Key property being served (the paper's headline): for STLT/SSM/hybrid archs
the per-sequence decode state is O(S*d) / O(d^2) — independent of context
length — so a single engine instance sustains 512k-token contexts at the
same memory as 2k (benchmarks/scaling.py measures this).

Architecture
------------
The engine owns a fixed pool of ``n_slots`` decode slots whose layer states
(attention KV caches, STLT ``h_re``/``h_im``, hann ring buffers, rg-LRU /
xLSTM recurrences) live in ONE preallocated batched pytree built by
``transformer.init_decode_state(cfg, batch=n_slots, max_len)``. Every
per-sequence position in that tree is a [n_slots] vector, so co-resident
slots sit at different depths.

Three jitted operations drive it:

* ``insert_slot``  — splice a freshly prefilled batch-1 state into a free
  slot (the admission path; ``slot`` is a traced scalar so one compile
  covers every slot).
* ``reset_slot``   — return a released slot to its pristine init state.
* ``decode_step``  — one batched token step for the WHOLE pool.

The host-side :class:`Scheduler` tracks which slot holds which request.
Admission is per-slot: the moment a sequence finishes (budget or EOS) its
slot is released and the next queued request is prefilled and spliced in
while the other slots keep decoding — no wave barrier, so one long
generation never stalls the short requests behind it.

Long prompts are admitted INCREMENTALLY (``prefill_chunk``): the prompt is
split into fixed-size chunks folded through the resumable
``transformer.prefill_chunk``, one chunk per tick, interleaved with the
pool's batched decode steps (Sarathi-style mixed steps) — a 100k-token
admission therefore stalls co-resident decodes by at most one chunk of
prefill work per token, never by the whole prompt. Every STLT engine is
CARRY-NATIVE (DESIGN.md §3): a resumed chunk seeds the scan from the
carried ``h_re/h_im`` and emits the updated O(S*d) state in the SAME single
pass — the Pallas kernel included — so chunked admission pays exactly one
scan pass per chunk, with no linearity-folded free-response/final-state
correction passes (``benchmarks/kernels.py`` measures the gap).

Chunked admission is ROW-BUCKETED (DESIGN.md §Serving): every chunk — tail
chunks included — is padded to ``prefill_chunk`` and carries a per-row
``valid_len`` mask, and ALL co-pending admissions advance in ONE masked
dispatch per tick. Pending prefills live in a second slot-shaped pool
(``prefill pool``); the dispatch (``transformer.prefill_chunk_rows``)
gathers the k pending rows of it, padded with distinct idle rows to the
smallest row bucket Kb in 1, 2, 4, ..., ``slots`` that holds k, runs
``[Kb, prefill_chunk]`` and scatters the rows back — so a tick pays for
the rows that have prompt tokens, not for the pool, and a serve trace over
prompts of arbitrary lengths compiles one prefill program per bucket,
ever, all of them before the first dispatch. (The PR-2 engine compiled one
program per distinct ``prompt_len % chunk`` and advanced one request per
jitted call; that path is kept as ``coalesce=False`` for parity tests and
benchmarks.)

A :class:`PrefixCache` (``prefix_cache=``) snapshots the O(S*d) streaming
state at chunk boundaries keyed by prompt-prefix hash, so requests sharing
a system prompt skip the shared prefix's prefill FLOPs entirely;
``warm_prefix`` pre-populates it.

``ServeEngine.generate`` is the simple API (one batch in, tokens out).
``ServeEngine.serve`` runs the scheduler; ``mode="wave"`` keeps the legacy
admission-wave engine (a whole wave drains before the next is admitted) as a
baseline for benchmarks/serving.py. Time is measured in ticks: one batched
decode step == one tick, which is also the unit of the optional per-request
``arrivals`` trace and of the latency stats returned by
``serve(..., return_stats=True)``.

Each phase of a tick (admission, prefill dispatch, promote, decode, the
host's waits on the device, release) runs inside a profiler span named
``serve.*`` and is counted in ``ServeEngine.tick_stats``, reset per serve
run like ``spec_stats`` (``serving/trace.py``).
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import transformer as T
from repro.serving.prefix_cache import PrefixCache
from repro.serving.sampler import (
    advance_slots,
    sample_slot_tokens,
    sample_token,
    split_slot_keys,
)
from repro.serving.trace import ADMIT, PROMOTE, RELEASE, TICK, TickTrace


def _jit(fn, donate_argnames=(), **bound):
    """``jax.jit`` of ``fn`` with keyword arguments bound, named after
    ``fn``: a ``functools.partial`` has no name of its own, so its program
    would show as ``jit__unknown`` in a profiler trace, not ``jit_<fn>``."""
    p = partial(fn, **bound)
    p.__name__ = fn.__name__
    return jax.jit(p, donate_argnames=donate_argnames)


def row_buckets(slots: int) -> list[int]:
    """Row counts of the co-pending prefill dispatch over a pool of
    ``slots``: the powers of two below ``slots``, then ``slots``."""
    return sorted({min(1 << i, slots) for i in range(slots.bit_length() + 1)})


@dataclasses.dataclass
class Request:
    prompt: np.ndarray          # [L] int32
    max_new_tokens: int
    id: int = 0
    temperature: Optional[float] = None  # None -> engine default
    # per-request STLT node budget (None -> engine default -> full S):
    # latency-sensitive requests decode with only their top-serve_nodes
    # Laplace nodes per head; mixed levels ride ONE dispatch (same trick as
    # valid_len — the cap is a [B] argument, not a shape)
    serve_nodes: Optional[int] = None


class Scheduler:
    """Host-side slot bookkeeping: which request occupies which slot, how
    many tokens it has emitted, and when it arrived/was admitted.

    A slot is either free, ``pending`` (mid chunked-prefill, not yet
    decoding), or ``live`` (decoding). Per-request stats record the prefill
    accounting — ``prompt_tokens``, ``prefilled_tokens`` actually computed,
    and ``cached_tokens`` skipped via a prefix-cache hit — plus ``live``,
    the tick the first token was emitted."""

    def __init__(self, n_slots: int):
        self.n_slots = n_slots
        self.req = [None] * n_slots          # slot -> Request | None
        self.live = np.zeros(n_slots, bool)
        self.pending = np.zeros(n_slots, bool)
        self.emitted = np.zeros(n_slots, np.int64)
        self.budgets = np.zeros(n_slots, np.int64)
        self.stats: dict[int, dict] = {}

    def free_slots(self):
        return [s for s in range(self.n_slots)
                if not (self.live[s] or self.pending[s])]

    def hold(self, slot: int, req: Request, arrival: int, tick: int,
             prompt_tokens: int = 0, cached_tokens: int = 0):
        """Assign the slot for chunked prefill (occupied but not decoding)."""
        self.req[slot] = req
        self.pending[slot] = True
        self.emitted[slot] = 0
        self.budgets[slot] = req.max_new_tokens
        self.stats[req.id] = {
            "arrival": arrival, "admit": tick, "live": None, "finish": None,
            "n_tokens": 0, "prompt_tokens": prompt_tokens,
            "prefilled_tokens": prompt_tokens - cached_tokens,
            "cached_tokens": cached_tokens,
            # wall-clock stamp of every emitted token: inter-token gaps
            # expose decode stalls that tick accounting cannot (a monolithic
            # prefill burns arbitrary wall time inside one tick)
            "token_walls": [],
        }

    def activate(self, slot: int, tick: int):
        """Chunked prefill finished: the slot starts decoding."""
        self.pending[slot] = False
        self.live[slot] = True
        self.stats[self.req[slot].id]["live"] = tick

    def bind(self, slot: int, req: Request, arrival: int, tick: int,
             prompt_tokens: int = 0, cached_tokens: int = 0):
        """Single-shot admission: prefill completed within this tick."""
        self.hold(slot, req, arrival, tick, prompt_tokens, cached_tokens)
        self.activate(slot, tick)

    def release(self, slot: int, tick: int):
        req = self.req[slot]
        self.stats[req.id]["finish"] = tick
        self.stats[req.id]["n_tokens"] = int(self.emitted[slot])
        self.req[slot] = None
        self.live[slot] = False
        self.pending[slot] = False


class _Host:
    """One host's local serving state: its admission queue, its Scheduler
    over the K local rows, and its in-flight chunked prefills. The unified
    tick body (``ServeEngine._serve_ticks``) works over a list of these —
    the single-host engine is the one-element case."""

    def __init__(self, n_slots: int):
        self.sched = Scheduler(n_slots)
        self.queue: list = []            # (arrival, Request), FIFO
        self.pending: dict[int, dict] = {}  # local slot -> in-flight prefill


class _ServeRun:
    """Mutable state of one serve run, threaded through the tick phase
    methods (``_serve_start`` -> ``_serve_tick``* -> ``_serve_finish``).
    Factoring the loop body's locals into an object lets the disagg
    controller drive a fleet's admission and decode phases tick-by-tick
    from outside, interleaved with transport I/O, without forking the tick
    body."""

    def __init__(self, hosts, queue, chunk_size, coalesce, prompt_len,
                 base_key, B):
        self.hosts = hosts
        self.K = hosts[0].sched.n_slots
        self.B = B
        self.queue = queue               # (arrival, Request), arrival-sorted
        self.results: dict[int, list[int]] = {}
        self.spec = None
        self.spec_adapt = None
        # decode pool is built lazily at first promote (prefill-role hosts
        # never pay it); prefill pool lazily at first chunked admission
        self.pool = None
        self.prefill_pool = None
        self.tok = np.zeros(B, np.int32)
        self.temps = np.full(B, 0.0, np.float32)
        self.keys = None
        self.base_key = base_key
        self.tick = 0
        self.chunk_size = chunk_size
        self.coalesce = coalesce
        self.prompt_len = prompt_len
        # standalone runs fast-forward idle gaps to the next arrival; the
        # disagg controller owns the global clock and disables this
        self.fast_forward = True

    def any_live(self):
        return any(h.sched.live.any() for h in self.hosts)

    def any_pending(self):
        return any(h.pending for h in self.hosts)

    def any_queued(self):
        return any(h.queue for h in self.hosts)

    def active(self):
        return (bool(self.queue) or self.any_queued() or self.any_pending()
                or self.any_live())


class ServeEngine:
    def __init__(self, params, cfg: ModelConfig, max_len: int = 4096,
                 temperature: float = 0.0, eos_id: int = -1, top_k: int = 0,
                 prefill_chunk: Optional[int] = None,
                 prefix_cache: Optional[PrefixCache] = None,
                 spec_k: int = 0, spec_draft: str = "ngram",
                 spec_draft_nodes: int = 4,
                 spec_adaptive: bool = False, spec_accept_floor: float = 0.4,
                 spec_adapt_window: int = 8, spec_adapt_recovery: int = 4,
                 serve_nodes: Optional[int] = None,
                 slo_gap_ms: float = 0.0, slo_queue_depth: int = 0,
                 slo_degrade: tuple = (), slo_recovery_ticks: int = 8):
        """``prefill_chunk``: split prompts longer than this into chunks
        admitted one per tick, interleaved with decode (None/0 -> monolithic
        admission). ``prefix_cache``: reuse post-prefix streaming states
        across requests sharing a prompt prefix (full-prompt states are
        snapshotted after every completed prefill; chunk-boundary states
        only where they extend an existing cached prefix — warm_prefix
        seeds first-contact system prompts).

        ``spec_k`` >= 1 turns on speculative decoding for continuous-mode
        serving (greedy only): each decode tick drafts ``spec_k`` tokens
        (``spec_draft``: "ngram" — prompt-lookup from the request's own
        context, zero extra dispatches — or "nodes" — a small-S node-subset
        self-draft keeping the top ``spec_draft_nodes`` Laplace nodes per
        head) and scores them in ONE ``spec_verify`` dispatch, emitting
        every accepted token plus the model's bonus token. Token output is
        exactly the plain greedy stream; only the dispatch count changes.

        ``spec_adaptive``: per-request adaptive draft windows — a slot whose
        rolling accept rate (last ``spec_adapt_window`` drafted tokens, once
        the window fills) drops below ``spec_accept_floor`` halves its
        verified window (k -> max(1, k//2)) and steps back up (k -> 2k,
        capped at ``spec_k``) after ``spec_adapt_recovery`` consecutive
        healthy rounds — the same stepwise-degrade/stepwise-restore shape as
        the SLO node ladder. The cap rides the existing per-row ``valid``
        lane, so dispatch shapes and emitted tokens are unchanged; savings
        show up as fewer wasted draft positions (``spec_stats``).

        ``serve_nodes``: default STLT node budget for every request (None ->
        full S); each :class:`Request` may override it. Caps apply to
        decode/verify dispatches only — admission prefill always runs at
        full S, so carried states and cached prefixes stay full-fidelity
        and restoring the budget recovers quality instantly.

        ``slo_degrade``: a descending ladder of node budgets, e.g.
        ``(16, 8, 4)``, the scheduler steps DOWN when a decode tick breaches
        the SLO — inter-token wall gap > ``slo_gap_ms`` (when > 0) or
        post-admission queue depth >= ``slo_queue_depth`` (when > 0) — and
        back UP after ``slo_recovery_ticks`` consecutive healthy ticks.
        Degrading S trades per-token quality for throughput instead of
        queueing; ``node_stats`` records the trajectory (mirrors
        ``spec_stats``).
        """
        self.params = params
        self.cfg = cfg
        self.max_len = max_len
        self.temperature = temperature
        self.eos_id = eos_id
        self.top_k = top_k
        self.prefill_chunk = prefill_chunk or 0
        if self.prefill_chunk < 0:
            raise ValueError(f"prefill_chunk must be >= 0 (got {prefill_chunk})")
        if spec_k < 0:
            raise ValueError(f"spec_k must be >= 0 (got {spec_k})")
        if spec_draft not in ("ngram", "nodes"):
            raise ValueError(f"unknown spec_draft {spec_draft!r} "
                             "(expected 'ngram' or 'nodes')")
        self.spec_k = spec_k
        self.spec_draft = spec_draft
        self.spec_draft_nodes = spec_draft_nodes
        if spec_adaptive and spec_k < 2:
            raise ValueError(
                "spec_adaptive needs spec_k >= 2 (a 1-token window has no "
                f"room to shrink; got spec_k={spec_k})")
        if not 0.0 < spec_accept_floor <= 1.0:
            raise ValueError(
                f"spec_accept_floor must be in (0, 1] (got {spec_accept_floor})")
        if spec_adapt_window < 1 or spec_adapt_recovery < 1:
            raise ValueError(
                "spec_adapt_window and spec_adapt_recovery must be >= 1 "
                f"(got {spec_adapt_window}, {spec_adapt_recovery})")
        self.spec_adaptive = spec_adaptive
        self.spec_accept_floor = spec_accept_floor
        self.spec_adapt_window = spec_adapt_window
        self.spec_adapt_recovery = spec_adapt_recovery
        # per-serve speculative accounting (verify dispatches, draft/accept
        # token counts); reset at the top of every _serve_ticks run
        self.spec_stats: dict = {}
        self._has_stlt = any(bt in ("stlt", "stlt_rel")
                             for bt, _ in T.execution_plan(cfg))
        if spec_k and self._has_stlt and cfg.stlt_adaptive:
            # spec_verify scores the whole draft window under ONE pooled
            # adaptive mask, but per-token decode recomputes the mask each
            # step — the two would disagree, breaking spec token-exactness
            raise ValueError(
                "speculative decoding is incompatible with adaptive node "
                "masks (stlt_adaptive=True): verify pools one mask per "
                "window, decode pools one per token")
        S = cfg.stlt_nodes
        if serve_nodes is not None:
            if not self._has_stlt:
                raise ValueError("serve_nodes requires an STLT mixer")
            if not 1 <= serve_nodes <= S:
                raise ValueError(
                    f"serve_nodes must be in [1, {S}] (got {serve_nodes})")
        self.serve_nodes = serve_nodes
        slo_degrade = tuple(int(m) for m in (slo_degrade or ()))
        if slo_degrade:
            if not self._has_stlt:
                raise ValueError("slo_degrade requires an STLT mixer")
            if not (slo_gap_ms > 0 or slo_queue_depth > 0):
                raise ValueError(
                    "slo_degrade needs a trigger: set slo_gap_ms and/or "
                    "slo_queue_depth")
            for m in slo_degrade:
                if not 1 <= m <= S:
                    raise ValueError(
                        f"slo_degrade levels must be in [1, {S}] "
                        f"(got {slo_degrade})")
        if slo_recovery_ticks < 1:
            raise ValueError(
                f"slo_recovery_ticks must be >= 1 (got {slo_recovery_ticks})")
        self.slo_gap_ms = slo_gap_ms
        self.slo_queue_depth = slo_queue_depth
        self.slo_degrade = slo_degrade
        self.slo_recovery_ticks = slo_recovery_ticks
        # SLO degradation state machine (reset per _serve_ticks run):
        # _slo_level indexes slo_degrade (-1 = undegraded)
        self._slo_level = -1
        self._slo_streak = 0
        self._slo_last_wall: Optional[float] = None
        # per-serve node-budget accounting, mirrors spec_stats
        self.node_stats: dict = {}
        self._full_caps_cache: dict[int, jax.Array] = {}
        self.prefix_cache = prefix_cache
        self._prefill = _jit(T.prefill, cfg=cfg, max_len=max_len)
        self._prefill_chunk = _jit(T.prefill_chunk, cfg=cfg)
        self._prefill_rows = _jit(T.prefill_chunk_rows, donate_argnames="pool",
                                  cfg=cfg)
        # compiled _prefill_rows by (slots, chunk, matmul precision), then
        # by row bucket (_ops_prefill_rows)
        self._rows_programs: dict = {}
        self._step = _jit(T.decode_step, cfg=cfg)
        self._verify = _jit(T.spec_verify, cfg=cfg)
        self._insert = _jit(T.insert_slot, cfg=cfg)
        self._extract = _jit(T.extract_slot, cfg=cfg)
        self._reset = _jit(T.reset_slot, cfg=cfg, max_len=max_len)
        self._sample = _jit(sample_slot_tokens, top_k=top_k)
        self._split = jax.jit(split_slot_keys)
        # spans and counters of the tick phases (serving/trace.py); the
        # counters are reset at the top of every serve run, like spec_stats
        self._trace = TickTrace()
        self.tick_stats: dict = self._trace.stats
        self._fresh1 = None  # lazy pristine batch-1 template (_fresh_template)
        # only unbounded causal attention allocates a length-bounded cache;
        # windowed attention uses a ring and STLT/SSM states are O(1) in N
        self._length_bounded = any(
            bt == "attn" for bt, _ in T.execution_plan(cfg))

    # ------------------------------------------------------------------ simple
    def generate(self, prompts: np.ndarray, max_new_tokens: int, rng=None,
                 serve_nodes: Optional[int] = None):
        """prompts [B, L] -> generated tokens [B, max_new_tokens].

        ``serve_nodes`` caps the STLT node budget for every row of this
        call (None -> engine default -> full S); prefill runs at full S,
        exactly like the serving path."""
        rng = rng if rng is not None else jax.random.key(0)
        level = serve_nodes if serve_nodes is not None else self.serve_nodes
        S = self.cfg.stlt_nodes
        if level is not None and not 1 <= level <= S:
            raise ValueError(f"serve_nodes must be in [1, {S}] (got {level})")
        caps = jnp.full((len(prompts),), level if level is not None else S,
                        jnp.int32)
        logits, state = self._prefill(self.params, inputs=jnp.asarray(prompts))
        outs = []
        # split BEFORE the first sample: the carried chain must never reuse
        # a key that already produced a token (key reuse correlates draws)
        rng, sub = jax.random.split(rng)
        tok = sample_token(logits, sub, self.temperature, self.top_k)
        outs.append(tok)
        for i in range(max_new_tokens - 1):
            rng, sub = jax.random.split(rng)
            logits, state = self._step(self.params, token_t=tok, state=state,
                                       node_cap=caps)
            tok = sample_token(logits, sub, self.temperature, self.top_k)
            outs.append(tok)
        return np.stack([np.asarray(t) for t in outs], axis=1)

    # ------------------------------------------------------- continuous batching
    def serve(self, requests: list, slots: int = 4,
              prompt_len: Optional[int] = None, mode: str = "continuous",
              arrivals=None, rng_seed: int = 0, return_stats: bool = False,
              prefill_chunk: Optional[int] = None, coalesce: bool = True):
        """Serve a request list. Returns {request_id: np.ndarray tokens}
        (plus a per-request stats dict when ``return_stats``).

        ``prefill_chunk`` overrides the engine default for this call (0
        forces monolithic admission; None keeps the engine setting). Chunked
        admission (continuous mode only) folds long prompts through the
        resumable ``transformer.prefill_chunk`` one chunk per tick while the
        resident slots keep decoding, and is token-exact vs monolithic
        admission at any chunk size.

        ``coalesce`` (default True) advances ALL co-pending admissions with
        one batched masked ``prefill_chunk`` dispatch per tick — tail
        chunks padded to ``prefill_chunk`` with per-row ``valid_len``, the
        k pending rows padded to the row bucket [Kb, chunk], Kb the
        smallest of 1, 2, 4, ..., ``slots`` that holds k — so chunked
        admission compiles one prefill program per bucket regardless of
        prompt lengths. ``coalesce=False`` keeps the legacy
        one-request-per-tick path (one batch-1 dispatch per pending slot,
        tail chunks jitted at their natural length); both paths are
        token-exact vs each other and vs monolithic admission.

        mode="continuous": per-slot admission (default). mode="wave": the
        legacy engine — admit up to ``slots`` requests, drain them all, then
        admit the next wave. ``arrivals`` (ticks, aligned with ``requests``)
        gates admission; requests are admitted in arrival order. With
        ``prompt_len`` prompts are left-padded to one static prefill shape
        (one compile, padding enters the state); without it each request is
        prefilled at its natural length, which is token-exact vs ``generate``
        under greedy decoding (sampled requests draw from per-request
        ``fold_in(id)`` rng streams, which by design differ from
        ``generate``'s single split chain but are identical across modes).

        Every request must satisfy ``prompt tokens + max_new_tokens <=
        max_len`` (the attention KV allocation); violations raise at
        admission rather than silently truncating the cache.
        """
        if slots < 1:
            raise ValueError(f"slots must be >= 1 (got {slots})")
        if mode == "wave":
            return self._serve_wave(requests, slots, prompt_len,
                                    arrivals, rng_seed, return_stats)
        if mode != "continuous":
            raise ValueError(f"unknown serve mode {mode!r}")
        chunk = self.prefill_chunk if prefill_chunk is None else prefill_chunk
        if chunk < 0:
            raise ValueError(f"prefill_chunk must be >= 0 (got {chunk})")
        return self._serve_continuous(requests, slots, prompt_len, arrivals,
                                      rng_seed, return_stats, chunk, coalesce)

    def _padded(self, prompt: np.ndarray, prompt_len: Optional[int]):
        prompt = np.asarray(prompt, np.int32)
        if prompt_len is None or len(prompt) == prompt_len:
            return prompt
        if len(prompt) > prompt_len:
            raise ValueError(
                f"prompt of {len(prompt)} tokens exceeds prompt_len={prompt_len}")
        out = np.zeros(prompt_len, np.int32)
        out[prompt_len - len(prompt):] = prompt  # left-pad
        return out

    def _check_fits(self, req: Request, prompt_tokens: int):
        if self._length_bounded and prompt_tokens + req.max_new_tokens > self.max_len:
            raise ValueError(
                f"request {req.id}: {prompt_tokens} prompt tokens + "
                f"{req.max_new_tokens} new tokens exceeds max_len={self.max_len}")

    def _queue(self, requests, arrivals, prompt_len=None):
        """Validate the whole request set upfront (ids, budgets, lengths,
        arrivals) so a bad request fails before ANY decode work is spent,
        then return (arrival, request) pairs in arrival order."""
        ids = [r.id for r in requests]
        if len(set(ids)) != len(ids):
            raise ValueError(
                "duplicate request ids (results/stats are keyed by id and "
                f"rng streams are derived from it): {sorted(ids)}")
        for r in requests:
            if r.max_new_tokens < 1:
                raise ValueError(
                    f"request {r.id}: max_new_tokens must be >= 1 "
                    f"(got {r.max_new_tokens})")
            if r.serve_nodes is not None:
                if not self._has_stlt:
                    raise ValueError(
                        f"request {r.id}: serve_nodes requires an STLT mixer")
                if not 1 <= r.serve_nodes <= self.cfg.stlt_nodes:
                    raise ValueError(
                        f"request {r.id}: serve_nodes must be in "
                        f"[1, {self.cfg.stlt_nodes}] (got {r.serve_nodes})")
            n_prompt = len(np.asarray(r.prompt))
            if prompt_len is not None and n_prompt > prompt_len:
                raise ValueError(
                    f"request {r.id}: prompt of {n_prompt} tokens exceeds "
                    f"prompt_len={prompt_len}")
            self._check_fits(r, prompt_len if prompt_len is not None else n_prompt)
        arrivals = [0] * len(requests) if arrivals is None else list(arrivals)
        if len(arrivals) != len(requests):
            raise ValueError(
                f"arrivals has {len(arrivals)} entries for {len(requests)} requests")
        order = sorted(range(len(requests)), key=lambda i: arrivals[i])
        return [(int(arrivals[i]), requests[i]) for i in order]

    # ----------------------------------------------------------- prefix cache
    def _lookup_prefix(self, prompt: np.ndarray):
        """(resume offset, state-or-None, logits-or-None) for ``prompt``."""
        if self.prefix_cache is None:
            return 0, None, None
        entry = self.prefix_cache.lookup(prompt)
        if entry is None:
            return 0, None, None
        return entry.n_tokens, entry.state, entry.logits

    def _cache_insert(self, prompt: np.ndarray, n: int, state, logits,
                      pinned: bool = False):
        if self.prefix_cache is not None and n > 0:
            self.prefix_cache.insert(prompt[:n], state, logits, pinned=pinned)

    def warm_prefix(self, prompt, chunk: Optional[int] = None):
        """Prefill ``prompt`` (e.g. a shared system prompt) into the prefix
        cache without serving a request: snapshots the streaming state at
        every chunk boundary and at the full length, PINNED against LRU
        eviction by per-request snapshots. Returns the number of tokens
        actually prefilled (0 on a full cache hit).

        Masked-chunk contract: the tail remainder is masked-prefilled at
        the padded [1, chunk] shape (per-row ``valid_len``), so warming never
        truncates a non-boundary prefix to the last chunk boundary and never
        compiles a per-residue tail program — the EXACT-length entry always
        exists (regression-locked by tests/test_masked_prefill.py)."""
        if self.prefix_cache is None:
            raise ValueError("warm_prefix requires a prefix_cache")
        prompt = np.asarray(prompt, np.int32)
        chunk = chunk or self.prefill_chunk or len(prompt)
        if chunk < 1:
            raise ValueError(f"warm_prefix needs a non-empty prompt (chunk={chunk})")
        offset, state, logits = self._lookup_prefix(prompt)
        if offset == len(prompt):
            return 0
        if state is None:
            state = T.init_decode_state(self.cfg, 1, self.max_len)
        done = offset
        while done < len(prompt):
            n = min(chunk, len(prompt) - done)
            buf = np.zeros((1, chunk), np.int32)
            buf[0, :n] = prompt[done:done + n]
            logits, state = self._prefill_chunk(
                self.params, inputs=jnp.asarray(buf), state=state,
                valid_len=jnp.asarray([n], np.int32))
            done += n
            self._cache_insert(prompt, done, state, logits, pinned=True)
        return len(prompt) - offset

    # ------------------------------------------------------- dispatch ops
    # The unified tick body (_serve_ticks) is written against these
    # overridable primitives; ShardedServeEngine swaps in its shard_map'd
    # dispatches and routing without touching the loop itself.

    # the co-pending prefill dispatch takes only the pending rows, padded to
    # a row bucket (_ops_prefill_rows): a gather and scatter over one
    # device's pool. The sharded engine's rows sit on different devices, so
    # it always dispatches the full per-shard pool shape and its trace
    # stays two-shape
    _row_buckets = True

    def _fresh_template(self):
        """Shared pristine batch-1 decode state (immutable pytree): seeds
        fresh prefills and resets rows without re-paying the init dispatch."""
        if self._fresh1 is None:
            self._fresh1 = T.init_decode_state(self.cfg, 1, self.max_len)
        return self._fresh1

    def _ops_insert(self, pool, st1, g):
        return self._insert(pool, st1, g)

    def _ops_extract(self, pool, g):
        return self._extract(pool, g)

    def _ops_reset(self, pool, g):
        return self._reset(pool, g)

    def _ops_prefill_pool(self, params, toks, state, valid):
        """Full-pool masked chunk dispatch ([B, chunk] + per-row valid)."""
        return self._prefill_chunk(params, inputs=toks, state=state,
                                   valid_len=valid)

    def _ops_prefill_rows(self, params, toks, pool, idx, valid):
        """Masked chunk dispatch over the pool rows ``idx`` ([Kb, chunk] +
        per-row valid; the pool is donated): (Kb logits rows, new pool).
        The first dispatch over a pool of a given size compiles every row
        bucket ahead of time, so no later mix of co-pending admissions
        compiles inside a serve."""
        key = (pool["pos"].shape[0], toks.shape[1],
               jax.config.jax_default_matmul_precision)
        if key not in self._rows_programs:
            i32 = jnp.int32
            self._rows_programs[key] = {
                kb: self._prefill_rows.lower(
                    params, inputs=jax.ShapeDtypeStruct((kb, key[1]), i32),
                    pool=pool, idx=jax.ShapeDtypeStruct((kb,), i32),
                    valid_len=jax.ShapeDtypeStruct((kb,), i32)).compile()
                for kb in row_buckets(key[0])}
        return self._rows_programs[key][toks.shape[0]](
            params, inputs=toks, pool=pool, idx=idx, valid_len=valid)

    def _full_caps(self, b: int):
        """Cached full-S node-cap array: a cap == S row is the all-ones
        mask, so uncapped traffic and capped traffic share ONE compiled
        decode/verify program (the cap is a data argument, not a shape)."""
        if b not in self._full_caps_cache:
            self._full_caps_cache[b] = jnp.full((b,), self.cfg.stlt_nodes,
                                                jnp.int32)
        return self._full_caps_cache[b]

    def _ops_decode(self, params, tok, pool, caps=None):
        if caps is None:
            caps = self._full_caps(tok.shape[0])
        return self._step(params, token_t=tok, state=pool, node_cap=caps)

    def _ops_verify(self, params, toks, valid, pool, caps=None):
        """ONE spec_verify dispatch: score + accept + rollback ([B, k+1])."""
        if caps is None:
            caps = self._full_caps(toks.shape[0])
        return self._verify(params, inputs=toks, state=pool, valid_len=valid,
                            node_cap=caps)

    def _ops_lookup(self, prompt, h: int):
        return self._lookup_prefix(prompt)

    def _ops_cache_insert(self, prompt, n, state, logits, h: int):
        self._cache_insert(prompt, n, state, logits)

    def _route_arrivals(self, hosts, queue, tick):
        """Move every arrived request into a host queue (single host: FIFO
        passthrough; the sharded engine routes least-loaded)."""
        while queue and queue[0][0] <= tick:
            hosts[0].queue.append(queue.pop(0))

    # ------------------------------------------------------- SLO node budget
    def _row_caps(self, hosts, K: int) -> np.ndarray:
        """Per-row node budgets [B] for this decode tick: request override
        -> engine default -> full S, then clamped down by the current SLO
        degradation level. Free/pending rows get full S (no-op rows)."""
        S = self.cfg.stlt_nodes
        caps = np.full(len(hosts) * K, S, np.int32)
        ladder_cap = (self.slo_degrade[self._slo_level]
                      if self._slo_level >= 0 else S)
        for h, host in enumerate(hosts):
            sched = host.sched
            for local in np.flatnonzero(sched.live):
                req = sched.req[local]
                base = (req.serve_nodes if req.serve_nodes is not None
                        else self.serve_nodes)
                base = S if base is None else base
                caps[h * K + local] = max(1, min(base, ladder_cap, S))
        return caps

    def _slo_update(self, hosts, gap_ms: Optional[float]):
        """One step of the degrade/restore state machine, after a decode
        tick: any breach (inter-token wall gap or queue depth) steps one
        level DOWN the ladder and resets the healthy streak; a healthy
        streak of ``slo_recovery_ticks`` steps one level back UP."""
        if not self.slo_degrade:
            return
        ns = self.node_stats
        qdepth = sum(len(h_.queue) for h_ in hosts)
        gap_breach = bool(self.slo_gap_ms > 0 and gap_ms is not None
                          and gap_ms > self.slo_gap_ms)
        queue_breach = bool(self.slo_queue_depth > 0
                            and qdepth >= self.slo_queue_depth)
        if gap_breach:
            ns["gap_breaches"] += 1
        if queue_breach:
            ns["queue_breaches"] += 1
        if gap_breach or queue_breach:
            if self._slo_level < len(self.slo_degrade) - 1:
                self._slo_level += 1
                ns["degrade_steps"] += 1
            self._slo_streak = 0
        else:
            self._slo_streak += 1
            if self._slo_level >= 0 and self._slo_streak >= self.slo_recovery_ticks:
                self._slo_level -= 1
                ns["restore_steps"] += 1
                self._slo_streak = 0
        if self._slo_level >= 0:
            ns["ticks_degraded"] += 1
            ns["min_nodes"] = min(ns["min_nodes"],
                                  int(self.slo_degrade[self._slo_level]))

    def _make_draft(self, n_slots: int):
        if not self.spec_k:
            return None
        from repro.serving import speculative
        if self.spec_draft == "nodes":
            return speculative.NodeDraft(self, self.spec_k, n_slots,
                                         self.spec_draft_nodes)
        return speculative.NGramDraft(self.spec_k, n_slots)

    # ------------------------------------------------------------- continuous
    def _serve_continuous(self, requests, slots, prompt_len, arrivals,
                          rng_seed, return_stats, chunk_size, coalesce=True):
        return self._serve_ticks([_Host(slots)], requests, prompt_len,
                                 arrivals, rng_seed, return_stats, chunk_size,
                                 coalesce)

    # ------------------------------------------------------- disagg tick hooks
    # The unified tick body is additionally parameterized by three hooks so
    # the disaggregated controller (serving/disagg) can run prefill-role and
    # decode-role fleets through the SAME phase methods: a prefill host
    # intercepts promote to ship the O(S*d) state instead of going live, a
    # decode host admits shipped states without prefilling, and both stamp
    # token walls / SLO gaps from a per-role clock.

    def _now(self) -> float:
        """Wall-clock source for token_walls/SLO gap stamps. Role engines in
        the disagg controller override this with a simulated per-host clock
        that advances only by the host's OWN dispatch time — the single-box
        model of role-isolated hardware."""
        return time.perf_counter()

    def _handoff_promote(self, run, h, local, ent, logits1, st1) -> bool:
        """Promote-time interception point. Return True to claim the
        finished prefill (state + first-token logits) INSTEAD of going live
        — the slot is then released without decoding. The disagg prefill
        engine serializes the state here and ships it to a decode host."""
        return False

    def _ready_state(self, req):
        """(state, logits) for a request whose prefill already happened
        elsewhere (a disagg decode host holding a shipped state), or None
        for the normal admission path. A hit admits like a full-prompt
        cache hit: zero local prefill work, promote within the tick."""
        return None

    def _evacuate_host(self, run, h):
        """Host ``h`` is lost (disagg failure model): forget every local
        row and return what was in flight so the caller can requeue it —
        ``(kind, arrival, req, progress)`` per request, kind one of
        "queued" / "pending" (progress = prefilled tokens lost) / "live"
        (the caller owns ``run.results`` and the emitted-token
        accounting). Rows are cleared WITHOUT release() — the requests
        did not finish, their stats slots are re-held on whatever host
        recovers them — and pool rows are reset so a reused slot never
        sees the dead host's state. Token-exactness of the requeued work
        is the PR-6 carry/consume contract: streams depend only on
        ``(rng_seed, request.id)`` and step count, never on the host."""
        host = run.hosts[h]
        sched = host.sched
        lost = []
        for arrival, req in host.queue:
            lost.append(("queued", arrival, req, 0))
        host.queue = []
        for local, ent in list(host.pending.items()):
            req = ent["req"]
            st = sched.stats.get(req.id, {})
            lost.append(("pending", st.get("arrival", run.tick), req,
                         max(0, ent["done"] - st.get("cached_tokens", 0))))
        host.pending = {}
        for local in range(sched.n_slots):
            req = sched.req[local]
            if req is not None:
                if sched.live[local]:
                    st = sched.stats.get(req.id, {})
                    lost.append(("live", st.get("arrival", run.tick), req, 0))
                sched.stats.pop(req.id, None)
            sched.req[local] = None
            sched.live[local] = False
            sched.pending[local] = False
            sched.emitted[local] = 0
            if run.pool is not None:
                run.pool = self._ops_reset(run.pool, h * run.K + local)
        return lost

    # ------------------------------------------------------- serve run pieces
    def _serve_start(self, hosts, requests, prompt_len, arrivals, rng_seed,
                     chunk_size, coalesce=True) -> "_ServeRun":
        """Validate the request set and build the mutable per-run state the
        tick phases operate on. ``requests`` may be empty — the disagg
        controller starts empty runs and feeds arrivals through the
        transport instead."""
        B = len(hosts) * hosts[0].sched.n_slots
        queue = self._queue(requests, arrivals, prompt_len)
        run = _ServeRun(hosts, queue, chunk_size, coalesce, prompt_len,
                        jax.random.key(rng_seed), B)
        run.spec = self._make_draft(B)
        self.spec_stats = {"verify_calls": 0, "drafted": 0, "accepted": 0,
                           "emitted": 0, "k": self.spec_k}
        self.tick_stats = self._trace.reset()
        if run.spec is not None and self.spec_adaptive:
            from repro.serving import speculative
            run.spec_adapt = speculative.AdaptiveK(
                self.spec_k, B, floor=self.spec_accept_floor,
                window=self.spec_adapt_window,
                recovery=self.spec_adapt_recovery)
        self._slo_level = -1
        self._slo_streak = 0
        self._slo_last_wall = None
        self.node_stats = {"degrade_steps": 0, "restore_steps": 0,
                           "ticks_degraded": 0, "gap_breaches": 0,
                           "queue_breaches": 0,
                           "min_nodes": int(self.cfg.stlt_nodes),
                           "ladder": list(self.slo_degrade)}
        if run.spec is not None:
            if self.temperature and self.temperature > 0:
                raise ValueError(
                    "speculative decoding is greedy-only: the accept rule "
                    f"compares argmax tokens (temperature={self.temperature})")
            for _, r in queue:
                if r.temperature:
                    raise ValueError(
                        f"request {r.id}: speculative decoding is greedy-only "
                        f"(temperature={r.temperature})")
        run.temps = np.full(B, self.temperature, np.float32)
        run.keys = jax.random.split(run.base_key, B)
        return run

    def _ensure_pool(self, run):
        """The decode pool is built lazily on the first promote: a disagg
        prefill-role engine never promotes locally, so a prefill host never
        pays the decode pool's HBM (a full second KV pool for attention
        archs)."""
        if run.pool is None:
            run.pool = T.init_decode_state(self.cfg, run.B, self.max_len)
        return run.pool

    def _promote(self, run, h, local, ent, logits1, st1):
        """Prefill complete on host h: sample the first token, go live —
        unless a handoff hook claims the state for another fleet."""
        with self._trace.phase(PROMOTE, req=ent["req"].id):
            sched = run.hosts[h].sched
            req = ent["req"]
            if self._handoff_promote(run, h, local, ent, logits1, st1):
                # shipped elsewhere: free the slot without ever going live
                sched.release(local, run.tick)
                return
            g = h * run.K + local
            rkey = jax.random.fold_in(run.base_key, req.id)
            # split BEFORE sampling/storing: k0 is consumed by the first
            # token, the carried stream continues from the UNUSED half — no
            # key is ever both consumed and carried (key reuse would
            # correlate the first two draws of every sampled request)
            carry, k0 = jax.random.split(rkey)
            temp = (self.temperature if req.temperature is None
                    else req.temperature)
            first = sample_token(logits1, k0, temp, self.top_k)
            with self._trace.sync():
                t0 = int(first[0])
            run.pool = self._ops_insert(self._ensure_pool(run), st1, g)
            run.keys = run.keys.at[g].set(carry)
            run.tok[g] = t0
            run.temps[g] = temp
            sched.activate(local, run.tick)
            run.results[req.id] = [t0]
            sched.stats[req.id]["token_walls"].append(self._now())
            sched.emitted[local] = 1
            if (sched.emitted[local] >= sched.budgets[local]
                    or t0 == self.eos_id):
                sched.release(local, run.tick)   # prefill-only request
                run.pool = self._ops_reset(run.pool, g)
            elif run.spec is not None:
                run.spec.on_promote(g, ent["prompt"], t0)
                if run.spec_adapt is not None:
                    run.spec_adapt.reset(g)

    def _tick_admission(self, run):
        """Admission phase of one tick: fill free local rows from host
        queues, then advance every pending chunked prefill with at most one
        masked dispatch. Completed prefills promote (or hand off) within
        the same tick."""
        cfg = self.cfg
        hosts, K, B = run.hosts, run.K, run.B
        chunk_size, coalesce = run.chunk_size, run.coalesce
        # --- per-host admission into free local rows --------------------
        with self._trace.phase(ADMIT):
            for h, host in enumerate(hosts):
                sched = host.sched
                for local in sched.free_slots():
                    if not host.queue:
                        break
                    arrival, req = host.queue.pop(0)
                    prompt = self._padded(req.prompt, run.prompt_len)
                    ready = self._ready_state(req)
                    if ready is not None:
                        # prefilled elsewhere (disagg handoff): splice +
                        # promote with zero local prefill work — the whole
                        # prompt counts as cached on this host, exactly like
                        # a full-prompt hit
                        st1, logits1 = ready
                        ent = {"req": req, "prompt": prompt,
                               "done": len(prompt), "resumed": False}
                        sched.hold(local, req, arrival, run.tick,
                                   prompt_tokens=len(prompt),
                                   cached_tokens=len(prompt))
                        sched.stats[req.id].update(host=h,
                                                   admit_wall=self._now())
                        self._promote(run, h, local, ent, logits1, st1)
                        continue
                    g = h * K + local
                    offset, pstate, plogits = self._ops_lookup(prompt, h)
                    remaining = len(prompt) - offset
                    # per-request boundary snapshots are only worth caching
                    # when they EXTEND a known shared prefix (a unique
                    # prompt's boundaries have ~zero hit probability and
                    # would churn the LRU); warm_prefix covers first-contact
                    # system prompts
                    ent = {"req": req, "prompt": prompt, "done": offset,
                           "resumed": offset > 0}
                    sched.hold(local, req, arrival, run.tick,
                               prompt_tokens=len(prompt), cached_tokens=offset)
                    sched.stats[req.id].update(host=h, admit_wall=self._now())
                    if remaining == 0:
                        # full-prompt cache hit: the stored last-token logits
                        # stand in for the skipped prefill
                        self._promote(run, h, local, ent, plogits, pstate)
                    elif chunk_size and coalesce:
                        # incremental admission via the batched dispatch
                        # below (which promotes a <= one-chunk remainder
                        # within this same tick): seed the slot's
                        # prefill-pool row
                        if run.prefill_pool is None:
                            run.prefill_pool = T.init_decode_state(
                                cfg, B, self.max_len)
                        if pstate is None:
                            run.prefill_pool = self._ops_insert(
                                run.prefill_pool, self._fresh_template(), g)
                        else:
                            run.prefill_pool = self._ops_insert(
                                run.prefill_pool, pstate, g)
                        host.pending[local] = ent
                    elif chunk_size:
                        # legacy one-request-per-tick admission (batch-1
                        # states; single-host only — the sharded engine
                        # always coalesces)
                        ent["state"] = (pstate if pstate is not None
                                        else self._fresh_template())
                        host.pending[local] = ent
                    else:  # monolithic admission (single-host only)
                        with self._trace.prefill(1, remaining, remaining):
                            if pstate is None:
                                logits1, st1 = self._prefill(
                                    self.params,
                                    inputs=jnp.asarray(prompt[None]))
                            else:
                                logits1, st1 = self._prefill_chunk(
                                    self.params,
                                    inputs=jnp.asarray(prompt[None, offset:]),
                                    state=pstate)
                        self._ops_cache_insert(prompt, len(prompt), st1,
                                               logits1, h)
                        self._promote(run, h, local, ent, logits1, st1)

        # --- mixed step: ONE masked chunk dispatch advances every pending
        # admission (coalesce=True). On one device's pool it takes only the
        # k pending rows: [Kb, chunk], Kb the smallest of the row buckets
        # 1, 2, 4, ..., slots that holds k, padded with distinct rows that
        # are not pending (valid 0: bit-exact no-ops). The sharded engine
        # dispatches the whole pool ([K, chunk] per shard).
        pending = [(h * K + local, h, local, ent)
                   for h, host in enumerate(hosts)
                   for local, ent in host.pending.items()]
        if pending and coalesce:
            if self._row_buckets:
                busy = {g for g, *_ in pending}
                kb = next(b for b in row_buckets(B) if b >= len(busy))
                idx = sorted(busy) + [g for g in range(B)
                                      if g not in busy][:kb - len(busy)]
            else:
                idx = list(range(B))
            at = {g: i for i, g in enumerate(idx)}  # slot -> dispatch row
            valid = np.zeros(len(idx), np.int32)
            for g, _, _, ent in pending:
                valid[at[g]] = min(chunk_size,
                                   len(ent["prompt"]) - ent["done"])
            with self._trace.prefill(len(idx), chunk_size, int(valid.sum())):
                chunk_tok = np.zeros((len(idx), chunk_size), np.int32)
                for g, _, _, ent in pending:
                    done, n = ent["done"], valid[at[g]]
                    chunk_tok[at[g], :n] = ent["prompt"][done:done + n]
                if self._row_buckets:
                    rows, run.prefill_pool = self._ops_prefill_rows(
                        self.params, jnp.asarray(chunk_tok), run.prefill_pool,
                        jnp.asarray(np.array(idx, np.int32)),
                        jnp.asarray(valid))
                else:
                    rows, run.prefill_pool = self._ops_prefill_pool(
                        self.params, jnp.asarray(chunk_tok), run.prefill_pool,
                        jnp.asarray(valid))
                for g, h, local, ent in pending:
                    ent["done"] += int(valid[at[g]])
                    finished = ent["done"] == len(ent["prompt"])
                    if not (ent["resumed"] or finished):
                        continue
                    logits1 = (rows[at[g]] if self._row_buckets
                               else rows[g:g + 1])
                    # boundary snapshot -> the owning host's shard
                    st1 = self._ops_extract(run.prefill_pool, g)
                    self._ops_cache_insert(ent["prompt"], ent["done"], st1,
                                           logits1, h)
                    if finished:
                        del hosts[h].pending[local]
                        self._promote(run, h, local, ent, logits1, st1)
        # --- ...or one batch-1 chunk per pending slot (legacy path,
        # single-host only) ---------------------------------------------
        elif pending:
            host = hosts[0]
            for local in list(host.pending):
                ent = host.pending[local]
                done = ent["done"]
                n = min(chunk_size, len(ent["prompt"]) - done)
                with self._trace.prefill(1, n, n):
                    logits1, ent["state"] = self._prefill_chunk(
                        self.params,
                        inputs=jnp.asarray(ent["prompt"][None, done:done + n]),
                        state=ent["state"])
                ent["done"] += n
                if ent["resumed"] or ent["done"] == len(ent["prompt"]):
                    self._ops_cache_insert(ent["prompt"], ent["done"],
                                           ent["state"], logits1, 0)
                if ent["done"] == len(ent["prompt"]):
                    del host.pending[local]
                    self._promote(run, 0, local, ent, logits1, ent["state"])

        # release the prefill pool once every admission has drained (it
        # doubles resident state — a full second KV pool for attention
        # archs); the next chunked admission lazily rebuilds it
        if run.prefill_pool is not None and not run.any_pending():
            run.prefill_pool = None

    def _tick_decode(self, run) -> bool:
        """Decode phase of one tick: one batched decode step (or one
        draft-verify round) over the live rows, then release/reset finished
        rows. Returns whether a decode dispatch ran."""
        hosts, K = run.hosts, run.K
        decoded = run.any_live()
        if decoded and run.spec is not None:
            self._spec_tick(run)
        elif decoded:
            live = sum(int(h_.sched.live.sum()) for h_ in hosts)
            with self._trace.decode(live):
                caps = jnp.asarray(self._row_caps(hosts, K))
                run.keys, subs = self._split(run.keys)
                logits, run.pool = self._ops_decode(
                    self.params, jnp.asarray(run.tok), run.pool, caps)
                sampled = self._sample(logits, subs, jnp.asarray(run.temps))
                with self._trace.sync():
                    nxt = np.array(sampled)
            with self._trace.phase(RELEASE):
                run.tick += 1
                now = self._now()
                for h, host in enumerate(hosts):
                    sched = host.sched
                    row = nxt[h * K:(h + 1) * K]
                    new_live, new_emitted = advance_slots(
                        row, sched.live, sched.emitted, sched.budgets,
                        self.eos_id)
                    for local in np.flatnonzero(sched.live):
                        rid = sched.req[local].id
                        run.results[rid].append(int(row[local]))
                        sched.stats[rid]["token_walls"].append(now)
                    sched.emitted = new_emitted
                    for local in np.flatnonzero(sched.live & ~new_live):
                        sched.release(local, run.tick)
                        run.pool = self._ops_reset(run.pool, h * K + local)
                run.tok = nxt
        elif run.any_pending():
            run.tick += 1  # prefill-only tick (nothing decoding yet)
        return decoded

    def _serve_tick(self, run):
        """One full scheduler tick: idle fast-forward -> route arrivals ->
        admission phase -> decode phase -> SLO ladder -> cache TTL clock."""
        with self._trace.phase(TICK, tick=run.tick):
            tick_was = run.tick
            if (run.fast_forward and not run.any_live()
                    and not run.any_pending() and not run.any_queued()
                    and run.queue and run.queue[0][0] > run.tick):
                # idle: fast-forward to the next arrival
                run.tick = run.queue[0][0]
                # sweep the TTL clock across the jump BEFORE this tick's
                # admission lookups: an entry idle past its TTL expires
                # honestly, instead of being hit and then evicted by a
                # stale-clock sweep at the end of the loop body
                self._cache_tick(run.tick - tick_was)
                tick_was = run.tick

            self._route_arrivals(run.hosts, run.queue, run.tick)
            self._tick_admission(run)
            decoded = self._tick_decode(run)

            if self.slo_degrade:
                gap_ms = None
                if decoded:
                    now_slo = self._now()
                    if self._slo_last_wall is not None:
                        gap_ms = (now_slo - self._slo_last_wall) * 1e3
                    self._slo_last_wall = now_slo
                self._slo_update(run.hosts, gap_ms)

            self._cache_tick(run.tick - tick_was)

    def _serve_finish(self, run, return_stats):
        out = {rid: np.array(toks, np.int32)
               for rid, toks in run.results.items()}
        if run.spec_adapt is not None:
            self.spec_stats.update(run.spec_adapt.stats())
        if not return_stats:
            return out
        stats: dict[int, dict] = {}
        for host in run.hosts:
            stats.update(host.sched.stats)
        return out, stats

    def _serve_ticks(self, hosts, requests, prompt_len, arrivals, rng_seed,
                     return_stats, chunk_size, coalesce=True):
        """THE serve tick body (DESIGN.md §Serving) — one implementation
        driven by both engines (and, phase by phase, by the disagg
        controller's role fleets). ``hosts`` is a list of per-host local
        state (queue + Scheduler + pending prefills) over contiguous row
        ranges of one global slot pool (global slot g = h*K + local); all
        device work goes through the ``_ops_*`` dispatch primitives, which
        is the ONLY thing the sharded engine overrides. Per tick, in order:
        route arrivals -> per-host admission -> at most one masked prefill
        dispatch -> one decode step (or, with ``spec_k``, one draft-verify
        round) -> release/reset finished rows."""
        run = self._serve_start(hosts, requests, prompt_len, arrivals,
                                rng_seed, chunk_size, coalesce)
        while run.active():
            self._serve_tick(run)
        return self._serve_finish(run, return_stats)

    # ------------------------------------------------------------ speculative
    def _spec_tick(self, run):
        """One draft-verify-accept round (DESIGN.md §Serving): draft k
        tokens per live row, score the whole window in ONE ``spec_verify``
        dispatch, emit every accepted token plus the model's bonus token,
        and roll per-row state to exactly the accepted length. Token output
        is the plain greedy stream — only the dispatch count changes.

        With ``spec_adaptive`` the verified window per row is additionally
        capped at 1 + the row's CURRENT adaptive k (the ladder shrinks on
        low rolling accept rates and restores stepwise) — a data-only cap,
        like the budget cap, so the dispatch shape and the emitted stream
        never change."""
        hosts, K, B = run.hosts, run.K, run.B
        spec, tok, results = run.spec, run.tok, run.results
        adapt = run.spec_adapt
        L = self.spec_k + 1
        live_mask = np.concatenate([h_.sched.live for h_ in hosts])
        with self._trace.decode(int(live_mask.sum())):
            caps = jnp.asarray(self._row_caps(hosts, K))
            inputs = np.zeros((B, L), np.int32)
            inputs[:, 0] = tok
            inputs[:, 1:] = spec.propose(tok, live_mask)
            # cap the window at the remaining budget so a row never consumes
            # tokens past prompt+max_new_tokens (the dead-row valid=0
            # contract handles everything else); live rows always get >= 1
            valid = np.zeros(B, np.int32)
            for h, host in enumerate(hosts):
                sched = host.sched
                for local in np.flatnonzero(sched.live):
                    g = h * K + local
                    remaining = int(sched.budgets[local]
                                    - sched.emitted[local])
                    win = L if adapt is None else min(L, 1 + adapt.k_for(g))
                    valid[g] = min(win, remaining)
            greedy, commit, run.pool = self._ops_verify(
                self.params, jnp.asarray(inputs), jnp.asarray(valid), run.pool,
                caps)
            with self._trace.sync():
                greedy = np.asarray(greedy)
                commit = np.asarray(commit)
        with self._trace.phase(RELEASE):
            run.tick += 1
            now = self._now()
            sstats = self.spec_stats
            sstats["verify_calls"] += 1
            for h, host in enumerate(hosts):
                sched = host.sched
                for local in np.flatnonzero(sched.live):
                    g = h * K + local
                    rid = sched.req[local].id
                    sstats["drafted"] += int(valid[g]) - 1
                    sstats["accepted"] += int(commit[g]) - 1
                    if adapt is not None and valid[g] > 1:
                        adapt.observe(g, int(valid[g]) - 1,
                                      int(commit[g]) - 1)
                    emitted_now = []
                    for t in greedy[g, :commit[g]]:
                        emitted_now.append(int(t))
                        if int(t) == self.eos_id:
                            break  # tokens past EOS are never emitted
                    results[rid].extend(emitted_now)
                    sched.stats[rid]["token_walls"].extend(
                        [now] * len(emitted_now))
                    sched.emitted[local] += len(emitted_now)
                    sstats["emitted"] += len(emitted_now)
                    if (sched.emitted[local] >= sched.budgets[local]
                            or emitted_now[-1] == self.eos_id):
                        sched.release(local, run.tick)
                        run.pool = self._ops_reset(run.pool, g)
                    else:
                        tok[g] = emitted_now[-1]
                        spec.on_emit(g, emitted_now)
            # model-draft bookkeeping: roll the draft pool forward by
            # exactly the committed tokens (no-op for the n-gram draft)
            spec.commit(inputs, commit)

    def _cache_tick(self, n: int):
        """Advance the prefix cache's TTL clock by ``n`` scheduler ticks."""
        if self.prefix_cache is not None and n > 0:
            self.prefix_cache.tick(n)

    # ------------------------------------------------------------- wave (legacy)
    def _serve_wave(self, requests, slots, prompt_len, arrivals,
                    rng_seed, return_stats):
        """Admission-wave baseline: a whole wave must drain before any queued
        request is admitted — one long generation stalls every free slot.

        Sampling matches the continuous path per request (same fold_in(id)
        rng stream and per-request temperature), so for a given request set
        the two modes differ only in scheduling."""
        results: dict[int, list[int]] = {}
        stats: dict[int, dict] = {}
        queue = self._queue(requests, arrivals, prompt_len)
        base_key = jax.random.key(rng_seed)
        tick = 0
        while queue:
            if queue[0][0] > tick:
                tick = queue[0][0]
            wave = []
            while queue and queue[0][0] <= tick and len(wave) < slots:
                # waves are rectangular: everyone is padded to the wave's max
                # prompt length, so admitting a long prompt inflates every
                # co-resident's KV footprint. Defer the candidate (FIFO) if
                # adding it would overflow anyone's prompt+budget bound — a
                # request alone in a wave always fits (validated upfront).
                trial = wave + [queue[0]]
                plen_trial = prompt_len or max(len(r.prompt) for _, r in trial)
                if wave and self._length_bounded and any(
                        plen_trial + r.max_new_tokens > self.max_len
                        for _, r in trial):
                    break
                wave.append(queue.pop(0))
            sched = Scheduler(len(wave))
            plen = prompt_len or max(len(r.prompt) for _, r in wave)
            prompts = np.stack([self._padded(r.prompt, plen) for _, r in wave])
            temps = np.array(
                [self.temperature if r.temperature is None else r.temperature
                 for _, r in wave], np.float32)
            keys = jnp.stack(
                [jax.random.fold_in(base_key, r.id) for _, r in wave])
            # split before the first sample — the same carry/consume
            # discipline as promote(), so per-request streams stay identical
            # across wave/continuous/sharded scheduling
            keys, subs = self._split(keys)
            logits, state = self._prefill(self.params, inputs=jnp.asarray(prompts))
            tok = np.array(self._sample(logits, subs, jnp.asarray(temps)))
            for i, (arrival, r) in enumerate(wave):
                sched.bind(i, r, arrival, tick, prompt_tokens=len(r.prompt))
                results[r.id] = []
            while sched.live.any():
                new_live, new_emitted = advance_slots(
                    tok, sched.live, sched.emitted, sched.budgets, self.eos_id)
                now = self._now()
                for i in np.flatnonzero(sched.live):
                    results[sched.req[i].id].append(int(tok[i]))
                    sched.stats[sched.req[i].id]["token_walls"].append(now)
                sched.emitted = new_emitted
                for i in np.flatnonzero(sched.live & ~new_live):
                    sched.release(i, tick)
                if not sched.live.any():
                    break
                keys, subs = self._split(keys)
                logits, state = self._step(self.params, token_t=jnp.asarray(tok),
                                           state=state)
                tok = np.array(self._sample(logits, subs, jnp.asarray(temps)))
                tick += 1
            stats.update(sched.stats)
        out = {rid: np.array(toks, np.int32) for rid, toks in results.items()}
        return (out, stats) if return_stats else out
