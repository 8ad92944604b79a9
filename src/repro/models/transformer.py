"""Decoder-only transformer LM with pluggable token mixers.

Block types (``ModelConfig.block_types()``):
  attn       — GQA softmax attention (baseline)
  local_attn — sliding-window attention (recurrentgemma's attention third)
  stlt       — the paper's factorized STLT (linear)
  stlt_rel   — the paper's relevance-softmax STLT (figure formulation)
  mlstm/slstm— xLSTM cells (models/xlstm.py)
  rglru      — Griffin RG-LRU recurrent block (models/rglru.py)

Runs of consecutive identical block types are stacked and executed with
``jax.lax.scan`` (scan-over-layers) so a 94-layer model lowers as one layer
body — essential for compile time and HLO size at dry-run scale. Remat wraps
each block body.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import stlt as stlt_lib
from repro.models import attention as attn_lib
from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.models import rglru as rglru_lib
from repro.models import xlstm as xlstm_lib
from repro.utils import fold_key, trunc_normal

AUX_KEYS = ("reg", "aux_loss", "router_z", "s_eff")


def _zero_aux():
    return {k: jnp.zeros((), jnp.float32) for k in AUX_KEYS}


def _attn_cfg(cfg: ModelConfig, window: int = 0) -> attn_lib.AttentionConfig:
    return attn_lib.AttentionConfig(
        d_model=cfg.d_model,
        num_heads=cfg.num_heads,
        num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.head_dim,
        qkv_bias=cfg.qkv_bias,
        rope_fraction=cfg.rope_fraction,
        rope_theta=cfg.rope_theta,
        causal=True,
        window=window,
        blockwise_threshold=cfg.blockwise_threshold,
        param_dtype=cfg.p_dtype,
    )


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def init_block(key, cfg: ModelConfig, block_type: str) -> dict:
    ks = jax.random.split(key, 4)
    d = cfg.d_model
    p: dict = {"norm1": L.init_norm(cfg.norm, d, cfg.p_dtype)}
    if block_type in ("attn", "local_attn"):
        window = cfg.local_window if block_type == "local_attn" else 0
        p["attn"] = attn_lib.init_attention(ks[0], _attn_cfg(cfg, window))
    elif block_type in ("stlt", "stlt_rel"):
        p["stlt"] = stlt_lib.init_stlt(ks[0], cfg.stlt_config())
    elif block_type == "mlstm":
        p["cell"] = xlstm_lib.init_mlstm(ks[0], cfg)
    elif block_type == "slstm":
        p["cell"] = xlstm_lib.init_slstm(ks[0], cfg)
    elif block_type == "rglru":
        p["rec"] = rglru_lib.init_rglru_block(ks[0], cfg)
    else:
        raise ValueError(block_type)
    # xLSTM blocks have no separate FFN sub-block (the cell embeds projections)
    if block_type not in ("mlstm", "slstm"):
        p["norm2"] = L.init_norm(cfg.norm, d, cfg.p_dtype)
        if cfg.is_moe:
            p["moe"] = moe_lib.init_moe(ks[1], cfg.moe_config())
        else:
            p["ffn"] = L.init_ffn(ks[1], d, cfg.d_ff, act=cfg.act, dtype=cfg.p_dtype)
    return p


def apply_block(
    params: dict,
    cfg: ModelConfig,
    block_type: str,
    x: jax.Array,
    *,
    rng: Optional[jax.Array] = None,
    deterministic: bool = True,
    tau: Optional[jax.Array] = None,
):
    aux = _zero_aux()
    h = L.apply_norm(cfg.norm, params["norm1"], x)
    if block_type in ("attn", "local_attn"):
        window = cfg.local_window if block_type == "local_attn" else 0
        mixed = attn_lib.apply_attention(params["attn"], _attn_cfg(cfg, window), h)
    elif block_type in ("stlt", "stlt_rel"):
        mixed, sa = stlt_lib.apply_stlt(
            params["stlt"], cfg.stlt_config(), h,
            rng=rng, deterministic=deterministic, tau=tau,
        )
        aux["reg"] = sa["reg"].astype(jnp.float32)
        aux["s_eff"] = sa["s_eff"].mean().astype(jnp.float32)
    elif block_type == "mlstm":
        mixed = xlstm_lib.apply_mlstm(params["cell"], cfg, h)
    elif block_type == "slstm":
        mixed = xlstm_lib.apply_slstm(params["cell"], cfg, h)
    elif block_type == "rglru":
        mixed = rglru_lib.apply_rglru_block(params["rec"], cfg, h)
    else:
        raise ValueError(block_type)
    x = x + mixed.astype(x.dtype)
    if "norm2" in params:
        h2 = L.apply_norm(cfg.norm, params["norm2"], x)
        if cfg.is_moe:
            y, ma = moe_lib.apply_moe(params["moe"], cfg.moe_config(), h2)
            aux["aux_loss"] = ma["aux_loss"].astype(jnp.float32)
            aux["router_z"] = ma["router_z"].astype(jnp.float32)
        else:
            y = L.ffn(params["ffn"], h2, act=cfg.act)
        x = x + y.astype(x.dtype)
    return x, aux


# ---------------------------------------------------------------------------
# Whole model
# ---------------------------------------------------------------------------


def execution_plan(cfg: ModelConfig):
    """(block_type, count) runs. count > 1 => stacked + lax.scan'd.

    The plan is a pure function of the config, so the params pytree holds
    only arrays (optimizers/checkpointing never see structure metadata).
    """
    groups: list[list] = []
    for t in cfg.block_types():
        if groups and groups[-1][0] == t:
            groups[-1][1] += 1
        else:
            groups.append([t, 1])
    plan = []
    for t, c in groups:
        if cfg.scan_layers and c > 1:
            plan.append((t, c))
        else:
            plan.extend((t, 1) for _ in range(c))
    return tuple(plan)


def init_lm(key, cfg: ModelConfig) -> dict:
    ks = iter(jax.random.split(key, 4))
    params: dict = {}
    if cfg.input_mode in ("tokens", "both"):
        params["embed"] = {
            "embed": trunc_normal(next(ks), (cfg.vocab, cfg.d_model), stddev=0.02, dtype=cfg.p_dtype)
        }
    layers = []
    li = 0
    for btype, count in execution_plan(cfg):
        if count > 1:
            stack = [init_block(fold_key(key, 1000 + li + j), cfg, btype) for j in range(count)]
            layers.append(jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *stack))
        else:
            layers.append(init_block(fold_key(key, 1000 + li), cfg, btype))
        li += count
    params["layers"] = layers
    params["final_norm"] = L.init_norm(cfg.norm, cfg.d_model, cfg.p_dtype)
    if not cfg.tie_embeddings or cfg.input_mode == "embeddings":
        params["lm_head"] = {
            "kernel": trunc_normal(next(ks), (cfg.d_model, cfg.vocab), stddev=0.02, dtype=cfg.p_dtype)
        }
    return params


def _maybe_remat(fn, cfg: ModelConfig):
    if not cfg.remat:
        return fn
    policy = None
    if cfg.remat_policy == "dots":
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return jax.checkpoint(fn, prevent_cse=False, policy=policy)


def apply_lm(
    params: dict,
    cfg: ModelConfig,
    inputs: jax.Array,
    *,
    rng: Optional[jax.Array] = None,
    deterministic: bool = True,
    tau: Optional[jax.Array] = None,
):
    """Forward pass. inputs: int tokens [B, N] or embeddings [B, N, d].

    Returns (logits [B, N, V], aux).
    """
    if jnp.issubdtype(inputs.dtype, jnp.integer):
        x = L.embed(params["embed"], inputs).astype(cfg.act_dtype)
    else:  # precomputed embeddings (VLM patch/audio-frame stubs)
        x = inputs.astype(cfg.act_dtype)
    B, N = x.shape[0], x.shape[1]
    if cfg.mixer != "attention" or cfg.family in ("xlstm",):
        # STLT/SSM paths carry no RoPE -> absolute sinusoidal PE (paper: X+P)
        x = x + L.sinusoidal_pe(N, cfg.d_model, dtype=x.dtype)[None]

    total_aux = _zero_aux()
    rng = rng if rng is not None else jax.random.key(0)
    li = 0
    for (btype, count), stacked in zip(execution_plan(cfg), params["layers"]):
        if count > 1:
            keys = jax.random.split(fold_key(rng, li), count)

            def body(carry, scanned):
                x_in, aux_in = carry
                layer_params, k = scanned
                x_out, aux = apply_block(
                    layer_params, cfg, btype, x_in,
                    rng=k, deterministic=deterministic, tau=tau,
                )
                aux_out = {kk: aux_in[kk] + aux[kk] for kk in AUX_KEYS}
                return (x_out, aux_out), None

            body = _maybe_remat(body, cfg)
            (x, total_aux), _ = jax.lax.scan(body, (x, total_aux), (stacked, keys))
        else:
            fn = _maybe_remat(
                lambda p, xx: apply_block(
                    p, cfg, btype, xx, rng=fold_key(rng, li),
                    deterministic=deterministic, tau=tau,
                ),
                cfg,
            )
            x, aux = fn(stacked, x)
            total_aux = {kk: total_aux[kk] + aux[kk] for kk in AUX_KEYS}
        li += count

    x = L.apply_norm(cfg.norm, params["final_norm"], x)
    if "lm_head" in params:
        logits = x @ params["lm_head"]["kernel"]
    else:
        logits = L.unembed(params["embed"], x)
    # s_eff is summed only over STLT blocks (others contribute 0): normalize
    # by the STLT block count, not num_layers — hybrid stlt+attn stacks would
    # otherwise understate the reported S_eff.
    n_stlt = sum(c for bt, c in execution_plan(cfg) if bt in ("stlt", "stlt_rel"))
    total_aux["s_eff"] = total_aux["s_eff"] / max(1, n_stlt)
    return logits, total_aux


def lm_loss(
    params: dict,
    cfg: ModelConfig,
    batch: dict,
    *,
    rng: Optional[jax.Array] = None,
    deterministic: bool = False,
    tau: Optional[jax.Array] = None,
):
    """batch: {"inputs": [B,N] or [B,N,d], "labels": [B,N], optional "mask"}."""
    logits, aux = apply_lm(
        params, cfg, batch["inputs"], rng=rng, deterministic=deterministic, tau=tau
    )
    ce = L.cross_entropy(logits, batch["labels"], batch.get("mask"))
    loss = ce + aux["reg"] + aux["aux_loss"] + aux["router_z"]
    metrics = {"loss": loss, "ce": ce, **{k: aux[k] for k in AUX_KEYS}}
    return loss, metrics


# ---------------------------------------------------------------------------
# Decode (serving)
# ---------------------------------------------------------------------------


def init_decode_state(cfg: ModelConfig, batch: int, max_len: int):
    """Per-layer caches, mirroring the execution plan's group structure.

    Positions are per-sequence [batch] vectors (not scalars): a slot pool
    holds sequences admitted at different times, each at its own depth.
    """
    states = []
    for btype, count in execution_plan(cfg):
        one = _init_block_state(cfg, btype, batch, max_len)
        if count > 1:
            # repeat (not zero) so non-zero inits survive stacking — e.g. the
            # xLSTM max-tracker m = -1e30
            st = jax.tree_util.tree_map(
                lambda x: jnp.repeat(x[None], count, axis=0), one
            )
        else:
            st = one
        states.append(st)
    return {"layers": states, "pos": jnp.zeros((batch,), jnp.int32)}


def decode_state_layout(cfg: ModelConfig, batch: int = 1,
                        max_len: int = 4096):
    """Per-layer-kind wire spec of a decode state: a list of
    ``(block_type, count, leafspec)`` runs mirroring the execution plan,
    where ``leafspec`` is the run's state pytree with every array leaf
    replaced by ``(shape, dtype_str)``. Computed via ``eval_shape`` — no
    arrays are materialized — so the serving wire format
    (``serving/disagg/wire.py``) can validate a blob against the receiving
    model's config without shipping structure metadata alongside the
    payload."""
    out = []
    for btype, count in execution_plan(cfg):
        one = jax.eval_shape(
            functools.partial(_init_block_state, cfg, btype, batch, max_len))
        if count > 1:
            one = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct((count,) + x.shape, x.dtype),
                one)
        out.append((btype, count, jax.tree_util.tree_map(
            lambda l: (tuple(l.shape), str(l.dtype)), one)))
    return out


def _init_block_state(cfg: ModelConfig, btype: str, batch: int, max_len: int):
    dtype = cfg.act_dtype
    if btype in ("attn", "local_attn"):
        window = cfg.local_window if btype == "local_attn" else 0
        return attn_lib.init_kv_cache(_attn_cfg(cfg, window), batch, max_len, dtype)
    if btype in ("stlt", "stlt_rel"):
        return stlt_lib.init_stlt_state(cfg.stlt_config(), batch, jnp.float32)
    if btype == "mlstm":
        return xlstm_lib.init_mlstm_state(cfg, batch)
    if btype == "slstm":
        return xlstm_lib.init_slstm_state(cfg, batch)
    if btype == "rglru":
        return rglru_lib.init_rglru_state(cfg, batch)
    raise ValueError(btype)


def _block_ffn(params, cfg: ModelConfig, x):
    """Post-mixer half of a block (norm2 + FFN/MoE residual), aux discarded —
    shared by the prefill paths, which never train."""
    if "norm2" in params:
        h2 = L.apply_norm(cfg.norm, params["norm2"], x)
        if cfg.is_moe:
            y, _ = moe_lib.apply_moe(params["moe"], cfg.moe_config(), h2)
        else:
            y = L.ffn(params["ffn"], h2, act=cfg.act)
        x = x + y.astype(x.dtype)
    return x


def _head_logits(params, cfg: ModelConfig, x_sel):
    """Final norm + LM head on one selected position. x_sel [B, 1, d] -> [B, V]."""
    x_sel = L.apply_norm(cfg.norm, params["final_norm"], x_sel)[:, 0]
    if "lm_head" in params:
        return x_sel @ params["lm_head"]["kernel"]
    return L.unembed(params["embed"], x_sel)


def _last_logits(params, cfg: ModelConfig, x):
    """Logits at the last position. x [B, N, d] -> [B, V]."""
    return _head_logits(params, cfg, x[:, -1:, :])


def _logits_at(params, cfg: ModelConfig, x, idx):
    """Logits at per-row position ``idx`` [B] — the masked-prefill variant
    of ``_last_logits`` (the last VALID position of a padded tail chunk
    differs per row)."""
    return _head_logits(params, cfg, jnp.take_along_axis(x, idx[:, None, None], axis=1))


def _block_prefill(params, cfg: ModelConfig, btype: str, x, max_len: int):
    """Full-sequence forward + cache construction for one block.

    Attention keeps its own path (``prefill_kv_cache`` uses the blockwise
    flash attention for long prompts and needs ``max_len`` to size the
    cache); every other mixer is the state=None case of the resumable
    chunk prefill."""
    if btype in ("attn", "local_attn"):
        h = L.apply_norm(cfg.norm, params["norm1"], x)
        window = cfg.local_window if btype == "local_attn" else 0
        mixed, state = attn_lib.prefill_kv_cache(params["attn"], _attn_cfg(cfg, window), h, max_len)
        x = x + mixed.astype(x.dtype)
        return _block_ffn(params, cfg, x), state
    return _block_prefill_chunk(params, cfg, btype, x, None)


def prefill(params: dict, cfg: ModelConfig, inputs: jax.Array, max_len: int):
    """Parallel prefill over the whole prompt: (last-token logits, decode state).

    inputs: int tokens [B, N] or embeddings [B, N, d].
    """
    if jnp.issubdtype(inputs.dtype, jnp.integer):
        x = L.embed(params["embed"], inputs).astype(cfg.act_dtype)
    else:
        x = inputs.astype(cfg.act_dtype)
    B, N = x.shape[0], x.shape[1]
    if cfg.mixer != "attention" or cfg.family in ("xlstm",):
        x = x + L.sinusoidal_pe(N, cfg.d_model, dtype=x.dtype)[None]

    states = []
    for (btype, count), stacked in zip(execution_plan(cfg), params["layers"]):
        if count > 1:

            def body(x_in, layer_params):
                x_out, st = _block_prefill(layer_params, cfg, btype, x_in, max_len)
                return x_out, st

            x, st = jax.lax.scan(body, x, stacked)
        else:
            x, st = _block_prefill(stacked, cfg, btype, x, max_len)
        states.append(st)

    return _last_logits(params, cfg, x), {
        "layers": states, "pos": jnp.full((B,), N, jnp.int32)}


def _block_prefill_chunk(params, cfg: ModelConfig, btype: str, x, state,
                         valid=None, node_cap=None):
    """Advance one block's streaming state by one prompt chunk (state=None:
    fresh monolithic prefill — the mixers treat both uniformly).

    ``valid`` (optional [B] ints) is the per-row valid length of a padded
    chunk (two-shape serving, DESIGN.md §Serving). Every mixer masks its
    state update so the carry stops at valid[b]; on top of that, rows with
    valid == 0 keep their old state BIT-exactly via a final per-row select —
    load-bearing, not just insurance: e.g. a fresh mLSTM row (stabilizer
    m = -1e30) degenerates under the gate-neutralization trick when it sees
    only pad steps, and the engine's coalesced dispatch runs every slot of
    the prefill pool, pending or not.

    ``node_cap`` (optional [B] ints) is the per-row SLO node budget,
    forwarded to the STLT mixer only. Only ``spec_verify`` (which replaces
    decode steps) passes it — admission prefill always runs at full S so
    carried states and cached prefixes stay full-fidelity."""
    h = L.apply_norm(cfg.norm, params["norm1"], x)
    old_state = state
    if btype in ("attn", "local_attn"):
        window = cfg.local_window if btype == "local_attn" else 0
        mixed, state = attn_lib.prefill_chunk(
            params["attn"], _attn_cfg(cfg, window), h, state, valid=valid)
    elif btype == "stlt":
        mixed, state = stlt_lib.stlt_prefill(
            params["stlt"], cfg.stlt_config(), h, state, valid=valid,
            node_cap=node_cap)
    elif btype == "mlstm":
        mixed, state = xlstm_lib.mlstm_prefill(params["cell"], cfg, h, state,
                                               valid=valid)
    elif btype == "slstm":
        mixed, state = xlstm_lib.slstm_prefill(params["cell"], cfg, h, state,
                                               valid=valid)
    elif btype == "rglru":
        mixed, state = rglru_lib.rglru_prefill(params["rec"], cfg, h, state,
                                               valid=valid)
    else:
        raise ValueError(f"prefill unsupported for block type {btype!r}")
    if valid is not None and old_state is not None:
        keep = valid > 0
        state = jax.tree_util.tree_map(
            lambda n, o: jnp.where(
                keep.reshape((-1,) + (1,) * (n.ndim - 1)), n, o),
            state, old_state)
    x = x + mixed.astype(x.dtype)
    return _block_ffn(params, cfg, x), state


def prefill_chunk(params: dict, cfg: ModelConfig, inputs: jax.Array, state: dict,
                  valid_len: Optional[jax.Array] = None):
    """Resumable chunked prefill: advance EVERY layer's streaming state by one
    prompt chunk, carrying the state across calls.

    inputs: int tokens [B, N] or embeddings [B, N, d] — the next N prompt
    tokens for each row. ``state`` is a decode-state pytree from
    ``init_decode_state`` (fresh prompt) or a previous ``prefill_chunk`` /
    ``prefill`` call; ``state["pos"]`` is per-sequence [B], so co-resident
    rows may sit at different prompt depths (positional encodings are
    evaluated per row). Returns (last-token logits [B, V], new state) —
    splitting a prompt at ANY chunk boundaries and folding the chunks through
    this function is exact vs the monolithic ``prefill`` (DESIGN.md
    §Serving), because every mixer here is an RNN-style recurrence (STLT
    scan carry, hann ring, KV append, rg-LRU / xLSTM hidden states).

    ``valid_len`` (optional [B] ints, 0 <= valid_len[b] <= N) makes this a
    TWO-SHAPE program (DESIGN.md §Serving): every tail chunk is padded to
    one static N and row b treats positions >= valid_len[b] as padding.
    Each mixer masks its state update so the carry stops exactly at
    valid_len[b]; logits are read at the last VALID position per row;
    ``pos`` advances by valid_len. Rows with valid_len == 0 are bit-exact
    no-ops (their state and pos pass through unchanged), which is what lets
    the serving engine dispatch one batched chunk over the WHOLE slot pool
    regardless of how many slots are actually mid-prefill.
    """
    pos = state["pos"]
    if pos.ndim == 0:  # legacy scalar-pos states
        pos = jnp.full((inputs.shape[0],), pos, jnp.int32)
    if jnp.issubdtype(inputs.dtype, jnp.integer):
        x = L.embed(params["embed"], inputs).astype(cfg.act_dtype)
    else:
        x = inputs.astype(cfg.act_dtype)
    B, N = x.shape[0], x.shape[1]
    valid = None
    if valid_len is not None:
        valid = jnp.asarray(valid_len, jnp.int32)
    if cfg.mixer != "attention" or cfg.family in ("xlstm",):
        pe = jax.vmap(
            lambda p: L.sinusoidal_pe(N, cfg.d_model, offset=p, dtype=x.dtype)
        )(pos)
        x = x + pe

    new_states = []
    for (btype, count), stacked, st in zip(
        execution_plan(cfg), params["layers"], state["layers"]
    ):
        if count > 1:

            def body(x_in, scanned):
                layer_params, layer_state = scanned
                x_out, new_s = _block_prefill_chunk(
                    layer_params, cfg, btype, x_in, layer_state, valid=valid)
                return x_out, new_s

            x, new_s = jax.lax.scan(body, x, (stacked, st))
        else:
            x, new_s = _block_prefill_chunk(stacked, cfg, btype, x, st,
                                            valid=valid)
        new_states.append(new_s)

    if valid is None:
        return _last_logits(params, cfg, x), {"layers": new_states, "pos": pos + N}
    logits = _logits_at(params, cfg, x, jnp.maximum(valid - 1, 0))
    return logits, {"layers": new_states, "pos": pos + valid}


def _block_state_at(params, cfg: ModelConfig, btype: str, x, state, q):
    """One block's streaming state after the first ``q[b]`` tokens of window
    ``x`` [B, L, d] — the rollback half of speculative verify. Outputs are
    discarded; only the state at the per-row accepted length survives.

    STLT's exponential window reads the carry straight out of the PR-5
    closed-form snapshot (``scan.stlt_carry_snapshot`` with the window as a
    single chunk) — a select, not a recompute. Every other mixer reuses its
    PR-3 masked prefill (``valid=q``), whose contract already stops the
    state at q[b] and makes q == 0 rows bit-exact no-ops."""
    h = L.apply_norm(cfg.norm, params["norm1"], x)
    old_state = state
    if btype == "stlt":
        state = stlt_lib.stlt_state_at(params["stlt"], cfg.stlt_config(), h,
                                       state, q)
    elif btype in ("attn", "local_attn"):
        window = cfg.local_window if btype == "local_attn" else 0
        _, state = attn_lib.prefill_chunk(
            params["attn"], _attn_cfg(cfg, window), h, state, valid=q)
    elif btype == "mlstm":
        _, state = xlstm_lib.mlstm_prefill(params["cell"], cfg, h, state,
                                           valid=q)
    elif btype == "slstm":
        _, state = xlstm_lib.slstm_prefill(params["cell"], cfg, h, state,
                                           valid=q)
    elif btype == "rglru":
        _, state = rglru_lib.rglru_prefill(params["rec"], cfg, h, state,
                                           valid=q)
    else:
        raise ValueError(f"spec_verify unsupported for block type {btype!r}")
    keep = q > 0
    return jax.tree_util.tree_map(
        lambda n, o: jnp.where(
            keep.reshape((-1,) + (1,) * (n.ndim - 1)), n.astype(o.dtype), o),
        state, old_state)


def spec_verify(params: dict, cfg: ModelConfig, inputs: jax.Array, state: dict,
                valid_len: jax.Array, node_cap: Optional[jax.Array] = None):
    """Speculative verify-accept-rollback: score a k-token draft window in
    ONE dispatch and advance every layer's state by exactly the accepted
    length (DESIGN.md §Serving).

    ``inputs`` [B, L] is, per live row, ``[last emitted token, d_1 .. d_k]``
    (L = k + 1) — the pending token the plain decode loop would feed next,
    followed by the draft. ``valid_len`` [B] is 1 + (draft tokens to
    consider) for live rows and 0 for rows that should be bit-exact no-ops
    (padding rides along exactly as in the two-shape ``prefill_chunk``).

    Returns ``(greedy [B, L], commit [B], new_state)``:

    * ``greedy[b, j]`` — argmax of the model's logits after consuming
      ``inputs[b, :j+1]``; because the parallel (prefill) form and the
      recurrent (decode) form compute the same recurrence, ``greedy[b, 0]``
      is the token plain greedy decode would emit this tick.
    * ``commit[b]`` — 1 + the longest prefix of draft tokens matching the
      greedy continuation, clamped to ``valid_len[b]`` (0 for no-op rows).
      The engine emits ``greedy[b, :commit[b]]`` — all accepted drafts plus
      the model's own "bonus" token at the first mismatch — so the emitted
      stream is token-for-token what one-token-at-a-time greedy decode
      would produce.
    * ``new_state`` — state advanced by ``commit[b]`` tokens: the first
      forward pass runs all L positions but KEEPS NO state; a second
      state-only pass reads each layer's carry at the accepted length
      (closed-form snapshot for STLT, masked prefill for the rest), so a
      rejected draft suffix is never folded into any carry.

    ``node_cap`` (optional [B] ints) applies the per-row SLO node budget to
    the scoring pass — verify replaces decode steps, so capped rows must
    score their window under the same top-k node mask decode would use.
    """
    pos = state["pos"]
    if pos.ndim == 0:  # legacy scalar-pos states
        pos = jnp.full((inputs.shape[0],), pos, jnp.int32)
    x = L.embed(params["embed"], inputs).astype(cfg.act_dtype)
    B, N = x.shape[0], x.shape[1]
    valid = jnp.asarray(valid_len, jnp.int32)
    if cfg.mixer != "attention" or cfg.family in ("xlstm",):
        pe = jax.vmap(
            lambda p: L.sinusoidal_pe(N, cfg.d_model, offset=p, dtype=x.dtype)
        )(pos)
        x = x + pe

    # Pass 1 — scoring: forward all L positions through every block,
    # recording each block's INPUT window (what the state pass re-reads) and
    # discarding the advanced states. Causality makes position j's output
    # exact for j < valid[b] regardless of the padding beyond it.
    xs_saved = []
    for (btype, count), stacked, st in zip(
        execution_plan(cfg), params["layers"], state["layers"]
    ):
        if count > 1:

            def body(x_in, scanned):
                layer_params, layer_state = scanned
                x_out, _ = _block_prefill_chunk(
                    layer_params, cfg, btype, x_in, layer_state,
                    node_cap=node_cap)
                return x_out, x_in

            x, xs = jax.lax.scan(body, x, (stacked, st))
        else:
            xs = x
            x, _ = _block_prefill_chunk(stacked, cfg, btype, x, st,
                                        node_cap=node_cap)
        xs_saved.append(xs)

    xf = L.apply_norm(cfg.norm, params["final_norm"], x)
    if "lm_head" in params:
        logits = xf @ params["lm_head"]["kernel"]
    else:
        logits = L.unembed(params["embed"], xf)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    # Accept rule (greedy): take draft tokens while each one equals the
    # model's argmax at the previous position; commit = accepted + 1 (the
    # bonus token), clamped to the live window.
    match = (inputs[:, 1:] == greedy[:, :-1]).astype(jnp.int32)
    accepted = jnp.cumprod(match, axis=1).sum(axis=1)
    commit = jnp.where(valid > 0, jnp.minimum(accepted + 1, valid), 0)

    # Pass 2 — rollback: per-layer state at the accepted length.
    new_states = []
    for (btype, count), stacked, st, xs in zip(
        execution_plan(cfg), params["layers"], state["layers"], xs_saved
    ):
        if count > 1:

            def body2(carry, scanned):
                layer_params, layer_state, x_in = scanned
                return carry, _block_state_at(
                    layer_params, cfg, btype, x_in, layer_state, commit)

            _, new_s = jax.lax.scan(body2, 0, (stacked, st, xs))
        else:
            new_s = _block_state_at(stacked, cfg, btype, xs, st, commit)
        new_states.append(new_s)

    return greedy, commit, {"layers": new_states, "pos": pos + commit}


def _block_step(params, cfg: ModelConfig, btype: str, x_t, state, pos,
                node_cap=None):
    h = L.apply_norm(cfg.norm, params["norm1"], x_t[:, None, :])[:, 0]
    if btype in ("attn", "local_attn"):
        window = cfg.local_window if btype == "local_attn" else 0
        mixed, state = attn_lib.apply_attention_step(
            params["attn"], _attn_cfg(cfg, window), h, state
        )
    elif btype in ("stlt", "stlt_rel"):
        mixed, state = stlt_lib.apply_stlt_step(
            params["stlt"], cfg.stlt_config(), h, state, node_cap=node_cap)
    elif btype == "mlstm":
        mixed, state = xlstm_lib.apply_mlstm_step(params["cell"], cfg, h, state)
    elif btype == "slstm":
        mixed, state = xlstm_lib.apply_slstm_step(params["cell"], cfg, h, state)
    elif btype == "rglru":
        mixed, state = rglru_lib.apply_rglru_step(params["rec"], cfg, h, state)
    else:
        raise ValueError(btype)
    x_t = x_t + mixed.astype(x_t.dtype)
    if "norm2" in params:
        h2 = L.apply_norm(cfg.norm, params["norm2"], x_t[:, None, :])[:, 0]
        if cfg.is_moe:
            y, _ = moe_lib.apply_moe(params["moe"], cfg.moe_config(), h2[:, None, :])
            y = y[:, 0]
        else:
            y = L.ffn(params["ffn"], h2, act=cfg.act)
        x_t = x_t + y.astype(x_t.dtype)
    return x_t, state


def decode_step(params: dict, cfg: ModelConfig, token_t: jax.Array, state: dict,
                node_cap: Optional[jax.Array] = None):
    """One token for the whole stack. token_t [B] ints (or [B, d] embeddings).

    ``state["pos"]`` is a per-sequence [B] vector; positional encodings are
    evaluated per row so co-resident slots may sit at different depths.
    ``node_cap`` (optional [B] ints) is the per-row SLO node budget for STLT
    blocks (``cap == S`` rows run unmasked in the same compiled program).
    """
    pos = state["pos"]
    if pos.ndim == 0:  # legacy scalar-pos states
        pos = jnp.full((token_t.shape[0],), pos, jnp.int32)
    if jnp.issubdtype(token_t.dtype, jnp.integer):
        x_t = L.embed(params["embed"], token_t).astype(cfg.act_dtype)
    else:
        x_t = token_t.astype(cfg.act_dtype)
    if cfg.mixer != "attention" or cfg.family in ("xlstm",):
        pe = jax.vmap(
            lambda p: L.sinusoidal_pe(1, cfg.d_model, offset=p, dtype=x_t.dtype)[0]
        )(pos)
        x_t = x_t + pe

    new_states = []
    for (btype, count), stacked, st in zip(
        execution_plan(cfg), params["layers"], state["layers"]
    ):
        if count > 1:

            def body(x_in, scanned):
                layer_params, layer_state = scanned
                x_out, new_s = _block_step(layer_params, cfg, btype, x_in,
                                           layer_state, pos, node_cap=node_cap)
                return x_out, new_s

            x_t, new_s = jax.lax.scan(body, x_t, (stacked, st))
        else:
            x_t, new_s = _block_step(stacked, cfg, btype, x_t, st, pos,
                                     node_cap=node_cap)
        new_states.append(new_s)

    x_t = L.apply_norm(cfg.norm, params["final_norm"], x_t[:, None, :])[:, 0]
    if "lm_head" in params:
        logits = x_t @ params["lm_head"]["kernel"]
    else:
        logits = L.unembed(params["embed"], x_t)
    return logits, {"layers": new_states, "pos": pos + 1}


# ---------------------------------------------------------------------------
# Slot pool (continuous-batching serving)
# ---------------------------------------------------------------------------
#
# A decode-state pytree built by init_decode_state(cfg, batch=n_slots, ...) is
# a POOL: every leaf carries the slot axis — axis 0 normally, axis 1 for
# scan-over-layers groups (whose leaves are stacked [count, batch, ...]).
# The helpers below move rows (one sequence's state each) in and out of a
# pool along that axis, uniformly across attention KV caches, STLT
# h_re/h_im, hann ring buffers, rg-LRU / xLSTM recurrent states, and all
# per-sequence positions.


def _slot_axis(count: int) -> int:
    return 1 if count > 1 else 0


def take_slots(pool: dict, idx: jax.Array, cfg: ModelConfig) -> dict:
    """The rows ``idx`` ([k] ints) of a pooled decode state, as a batch-k
    state in that order."""
    layers = []
    for (btype, count), pl in zip(execution_plan(cfg), pool["layers"]):
        ax = _slot_axis(count)
        layers.append(jax.tree_util.tree_map(
            lambda p: jnp.take(p, idx, axis=ax, mode="clip"), pl))
    return {"layers": layers,
            "pos": jnp.take(pool["pos"], idx, axis=0, mode="clip")}


def put_slots(pool: dict, rows: dict, idx: jax.Array, cfg: ModelConfig) -> dict:
    """The inverse of ``take_slots``: write the batch-k state ``rows`` back
    at slots ``idx``. The indices must be distinct: a repeated index would
    make the scatter's result depend on the order of its writes."""
    def put(ax):
        return lambda p, r: p.at[(slice(None),) * ax + (idx,)].set(
            r.astype(p.dtype), unique_indices=True, mode="promise_in_bounds")

    layers = []
    for (btype, count), pl, rl in zip(
        execution_plan(cfg), pool["layers"], rows["layers"]
    ):
        layers.append(jax.tree_util.tree_map(put(_slot_axis(count)), pl, rl))
    return {"layers": layers, "pos": put(0)(pool["pos"], rows["pos"])}


def prefill_chunk_rows(params: dict, cfg: ModelConfig, inputs: jax.Array,
                       pool: dict, idx: jax.Array, valid_len: jax.Array):
    """``prefill_chunk`` over the rows ``idx`` ([k] distinct slots) of a
    pooled state: gather them, advance them by ``inputs`` [k, N] with
    ``valid_len`` [k], scatter them back. Returns (k last-token logits rows,
    each [1, V], new pool): a caller takes a row with no slice program of
    its own for each row and k. Slots outside ``idx`` are untouched, and a
    row of ``idx`` with ``valid_len`` 0 comes back bit for bit, so a
    dispatch sized to the rows that have prompt tokens pays for those rows
    and not for the pool."""
    logits, rows = prefill_chunk(params, cfg, inputs,
                                 take_slots(pool, idx, cfg), valid_len)
    return (tuple(logits[i:i + 1] for i in range(logits.shape[0])),
            put_slots(pool, rows, idx, cfg))


def insert_slot(pool: dict, state: dict, slot, cfg: ModelConfig) -> dict:
    """Splice a batch-1 decode state (e.g. fresh from ``prefill``) into slot
    ``slot`` of a pooled decode state. jit-safe; ``slot`` may be traced."""
    return put_slots(pool, state, jnp.reshape(slot, (1,)), cfg)


def extract_slot(pool: dict, slot, cfg: ModelConfig) -> dict:
    """The inverse of ``insert_slot``: the batch-1 decode state of one slot."""
    return take_slots(pool, jnp.reshape(slot, (1,)), cfg)


def reset_slot(pool: dict, slot, cfg: ModelConfig, max_len: int) -> dict:
    """Return ``slot`` to its pristine init state (zeros, pos 0, and the
    correct non-zero init for states like the xLSTM max-tracker)."""
    return insert_slot(pool, init_decode_state(cfg, 1, max_len), slot, cfg)
