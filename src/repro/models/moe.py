"""Mixture-of-Experts FFN: top-k routing, capacity-based dispatch, expert
parallelism, optional dense residual branch (Arctic).

Dispatch is the scatter/gather formulation (O(T*E) routing bookkeeping, never
the O(T*E*C) one-hot dispatch tensor): tokens are assigned a slot
``(expert, position_in_expert)`` via a masked cumulative sum, gathered into
``[E, C, d]`` expert batches (sharded over the ``model`` axis = EP), pushed
through the stacked expert FFNs with one einsum, and scattered back with
their gate weights. Tokens beyond capacity are dropped (standard GShard/
Switch semantics; the residual stream carries them unchanged).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.utils import lecun_normal


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_ff: int                    # per-expert hidden dim
    num_experts: int
    top_k: int
    capacity_factor: float = 1.25
    dense_residual: bool = False  # Arctic: parallel dense FFN branch
    dense_ff: int = 0             # hidden dim of the residual branch
    aux_loss_weight: float = 0.01
    router_z_weight: float = 1e-3
    act: str = "swiglu"
    param_dtype: Any = jnp.float32
    # EP sharding hints: mesh axis for the expert dim / the capacity dim of
    # the dispatched [E, C, d] batch. Resolved via with_sharding_constraint;
    # no-ops outside a mesh context.
    ep_axis: Optional[str] = None
    cap_axis: Optional[str] = None
    # dispatch strategy:
    #   "gather"    — global-view gather/scatter, GSPMD partitions it
    #                 (baseline; suffers involuntary remat at 256+ chips)
    #   "shard_map" — explicit EP: tokens are replicated over the model axis
    #                 (batch shards only on data), so dispatch is a LOCAL
    #                 select per expert shard and combine is one psum of
    #                 [T_loc, d] — the §Perf fix for MoE collectives.
    dispatch: str = "gather"
    fsdp_axis: Optional[str] = None  # data axis for the explicit weight gather


def init_moe(key, cfg: MoEConfig):
    ks = jax.random.split(key, 5)
    E, d, f = cfg.num_experts, cfg.d_model, cfg.d_ff
    p = {
        "router": lecun_normal(ks[0], (d, E), dtype=cfg.param_dtype),
        "w1": lecun_normal(ks[1], (E, d, f), fan_in=d, dtype=cfg.param_dtype),
        "w3": lecun_normal(ks[2], (E, d, f), fan_in=d, dtype=cfg.param_dtype),
        "w2": lecun_normal(ks[3], (E, f, d), fan_in=f, dtype=cfg.param_dtype),
    }
    if cfg.dense_residual:
        p["dense"] = L.init_ffn(ks[4], d, cfg.dense_ff or f, act=cfg.act, dtype=cfg.param_dtype)
    return p


def _capacity(tokens: int, cfg: MoEConfig) -> int:
    c = int(tokens * cfg.top_k * cfg.capacity_factor / cfg.num_experts)
    return max(8, -(-c // 8) * 8)  # round up to 8 for lane alignment


def apply_moe(params, cfg: MoEConfig, x: jax.Array):
    """x [B, N, d] -> (y [B, N, d], aux {aux_loss, router_z})."""
    if cfg.dispatch == "shard_map":
        mesh = _current_mesh()
        if mesh is not None and cfg.ep_axis in mesh.axis_names:
            return _apply_moe_shardmap(params, cfg, x, mesh)
    return _apply_moe_gather(params, cfg, x)


def _current_mesh():
    """The ambient mesh set by ``with mesh:`` at trace time (None if absent)."""
    try:
        from jax._src.mesh import thread_resources

        m = thread_resources.env.physical_mesh
        return None if m.empty else m
    except Exception:  # pragma: no cover
        return None


def _apply_moe_gather(params, cfg: MoEConfig, x: jax.Array):
    """Global-view dispatch (baseline). All ops are einsum/gather/scatter
    which XLA SPMD partitions — at 256+ chips the cross-shard gather
    triggers involuntary rematerialization (see EXPERIMENTS.md §Perf)."""
    B, N, d = x.shape
    T = B * N
    E, K = cfg.num_experts, cfg.top_k
    xt = x.reshape(T, d)

    logits = (xt @ params["router"]).astype(jnp.float32)  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, exp_idx = jax.lax.top_k(probs, K)  # [T, K]
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # --- load-balancing aux losses (Switch/GShard) --------------------------
    me = probs.mean(0)                                   # mean router prob per expert
    one_hot_top1 = jax.nn.one_hot(exp_idx[:, 0], E)
    ce = one_hot_top1.mean(0)                            # fraction routed (top-1)
    aux_loss = cfg.aux_loss_weight * E * jnp.sum(me * ce)
    router_z = cfg.router_z_weight * jnp.mean(jnp.square(jax.nn.logsumexp(logits, -1)))

    # --- slot assignment -----------------------------------------------------
    C = _capacity(T, cfg)
    flat_e = exp_idx.reshape(-1)                          # [T*K], K fastest
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)   # [T*K, E]
    pos_in_e = jnp.cumsum(onehot, axis=0) - onehot        # position BEFORE this token
    pos = jnp.take_along_axis(pos_in_e, flat_e[:, None], axis=1)[:, 0]  # [T*K]
    keep = pos < C
    # scatter token ids into [E, C] slot table (sentinel T = empty slot);
    # dropped tokens scatter to column C which mode="drop" discards.
    slot_tok = jnp.full((E, C), T, jnp.int32)  # T = sentinel -> gathers row of zeros
    src_tok = jnp.arange(T).repeat(K)
    slot_tok = slot_tok.at[flat_e, jnp.where(keep, pos, C)].set(src_tok, mode="drop")
    # gather expert inputs (extra zero row for the sentinel). Under pjit this
    # gather IS the EP all-to-all: tokens (sharded on data) -> expert batches
    # (sharded on model, capacity on data).
    xt_pad = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)], axis=0)
    x_e = xt_pad[slot_tok]                                # [E, C, d]
    if cfg.ep_axis:
        from jax.sharding import PartitionSpec as P

        from repro.utils import with_sharding_constraint

        x_e = with_sharding_constraint(x_e, P(cfg.ep_axis, cfg.cap_axis, None))

    # --- expert computation ---------------------------------------------------
    h1 = jnp.einsum("ecd,edf->ecf", x_e, params["w1"])
    if cfg.act == "swiglu":
        h3 = jnp.einsum("ecd,edf->ecf", x_e, params["w3"])
        h = jax.nn.silu(h1) * h3
    else:
        h = jax.nn.gelu(h1)
    y_e = jnp.einsum("ecf,efd->ecd", h, params["w2"])     # [E, C, d]

    # --- combine --------------------------------------------------------------
    gate_flat = (gate_vals.reshape(-1) * keep).astype(x.dtype)  # [T*K]
    y_slots = y_e[flat_e, jnp.minimum(pos, C - 1)]        # [T*K, d]
    contrib = y_slots * gate_flat[:, None]
    y = jnp.zeros((T, d), x.dtype).at[src_tok].add(contrib)

    if cfg.dense_residual:
        y = y + L.ffn(params["dense"], xt, act=cfg.act)

    return y.reshape(B, N, d), {"aux_loss": aux_loss, "router_z": router_z}


# ---------------------------------------------------------------------------
# explicit-EP dispatch (§Perf): shard_map with local select + one psum
# ---------------------------------------------------------------------------


def _apply_moe_shardmap(params, cfg: MoEConfig, x: jax.Array, mesh):
    """Tokens shard on the data axes and are REPLICATED across ``ep_axis``;
    each model rank selects the tokens routed to its local experts (no
    dispatch collective at all) and the combine is a single psum over the
    model axis. FSDP weight shards are gathered explicitly (backward
    reduce-scatters automatically)."""
    from jax.sharding import PartitionSpec as P

    ep = cfg.ep_axis
    fsdp = cfg.fsdp_axis if (cfg.fsdp_axis and cfg.fsdp_axis in mesh.axis_names) else None
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    E = cfg.num_experts
    n_shards = mesh.shape[ep]
    E_loc = E // n_shards
    B, N, d = x.shape

    w_spec = {
        "router": P(fsdp, None),
        "w1": P(ep, fsdp, None),
        "w3": P(ep, fsdp, None),
        "w2": P(ep, None, fsdp),
    }
    if cfg.dense_residual:
        w_spec["dense"] = {
            "w1": P(fsdp, ep), "w3": P(fsdp, ep), "w2": P(ep, fsdp),
        }
    in_specs = (w_spec, P(dp_axes, None, None))
    out_specs = (P(dp_axes, None, None), {"aux_loss": P(), "router_z": P()})

    def local_fn(w, x_loc):
        Bl, Nl, _ = x_loc.shape
        T = Bl * Nl
        xt = x_loc.reshape(T, d)
        gather = lambda a, ax: (jax.lax.all_gather(a, fsdp, axis=ax, tiled=True)
                                if fsdp else a)
        router = gather(w["router"], 0)
        w1 = gather(w["w1"], 1)
        w3 = gather(w["w3"], 1)
        w2 = gather(w["w2"], 2)  # fsdp shard lives on the output-d dim

        logits = (xt @ router).astype(jnp.float32)          # [T, E] (full E)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, exp_idx = jax.lax.top_k(probs, cfg.top_k)
        gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

        me = probs.mean(0)
        ce = jax.nn.one_hot(exp_idx[:, 0], E).mean(0)
        aux_loss = cfg.aux_loss_weight * E * jnp.sum(me * ce)
        router_z = cfg.router_z_weight * jnp.mean(jnp.square(jax.nn.logsumexp(logits, -1)))
        aux_loss = jax.lax.pmean(aux_loss, dp_axes) if dp_axes else aux_loss
        router_z = jax.lax.pmean(router_z, dp_axes) if dp_axes else router_z

        # --- local selection: my experts are [r*E_loc, (r+1)*E_loc) ----------
        r = jax.lax.axis_index(ep)
        local_e = exp_idx - r * E_loc                        # [T, K]
        mine = (local_e >= 0) & (local_e < E_loc)
        le_flat = jnp.where(mine, local_e, E_loc).reshape(-1)  # E_loc = drop bin
        onehot = jax.nn.one_hot(le_flat, E_loc + 1, dtype=jnp.int32)
        pos = (jnp.cumsum(onehot, axis=0) - onehot)[
            jnp.arange(le_flat.shape[0]), le_flat
        ]
        C = _capacity(T, cfg)
        keep = mine.reshape(-1) & (pos < C)
        slot_tok = jnp.full((E_loc, C), T, jnp.int32)
        src_tok = jnp.arange(T).repeat(cfg.top_k)
        slot_tok = slot_tok.at[
            jnp.where(keep, le_flat, E_loc), jnp.where(keep, pos, C)
        ].set(src_tok, mode="drop")
        xt_pad = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)], axis=0)
        x_e = xt_pad[slot_tok]                               # [E_loc, C, d] LOCAL

        h1 = jnp.einsum("ecd,edf->ecf", x_e, w1)
        if cfg.act == "swiglu":
            h = jax.nn.silu(h1) * jnp.einsum("ecd,edf->ecf", x_e, w3)
        else:
            h = jax.nn.gelu(h1)
        y_e = jnp.einsum("ecf,efd->ecd", h, w2)

        gate_flat = (gate_vals.reshape(-1) * keep).astype(x_loc.dtype)
        y_slots = y_e[jnp.minimum(le_flat, E_loc - 1), jnp.minimum(pos, C - 1)]
        y_partial = jnp.zeros((T, d), x_loc.dtype).at[src_tok].add(
            y_slots * gate_flat[:, None]
        )

        if cfg.dense_residual:
            dw1 = gather(w["dense"]["w1"], 0)                # [d, ff/ep]
            dw3 = gather(w["dense"]["w3"], 0)
            dw2 = gather(w["dense"]["w2"], 1)                # [ff/ep, d]
            hd = jax.nn.silu(xt @ dw1) * (xt @ dw3) if cfg.act == "swiglu" \
                else jax.nn.gelu(xt @ dw1)
            y_partial = y_partial + (hd @ dw2).astype(x_loc.dtype)  # partial over ff

        y = jax.lax.psum(y_partial, ep)                      # ONE combine collective
        return y.reshape(Bl, Nl, d), {"aux_loss": aux_loss, "router_z": router_z}

    w_in = {k: params[k] for k in ("router", "w1", "w3", "w2")}
    if cfg.dense_residual:
        w_in["dense"] = params["dense"]
    y, aux = jax.shard_map(
        local_fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )(w_in, x)
    return y, aux
