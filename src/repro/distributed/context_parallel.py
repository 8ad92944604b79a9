"""Context-parallel (sequence-sharded) STLT — the paper's streaming claim
made multi-chip (DESIGN.md §5).

For ``long-context prefill`` the sequence dim is sharded across a mesh axis.
A diagonal linear recurrence composes across shards in closed form: device i
computes its local chunked scan from a zero carry, then the per-device end
states are exchanged ONCE (O(devices * S * d) bytes — vs ring-attention's
O(N * d)) and each device applies the incoming-carry correction

    H_in(i)  = sum_{j<i} lambda^{N_loc * (i-1-j)} h_j
    z[n]    += Re(sum_k u_k lambda_k^{n+1} H_in[k])     (n local index)

implemented with shard_map + all_gather over the sequence axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import scan as scan_lib


def stlt_context_parallel(
    x: jax.Array,          # [B, N_global, d], seq sharded over `axis`
    log_mag: jax.Array,    # [S]
    theta: jax.Array,      # [S]
    u_re: jax.Array,       # [S]
    u_im: jax.Array,       # [S]
    mesh: Mesh,
    axis: str = "data",
    chunk: int = 128,
):
    """Unilateral factorized STLT over a sequence-sharded input."""

    def local_fn(x_loc, lm, th, ur, ui):
        # x_loc [B, N_loc, d]
        B, N_loc, d = x_loc.shape
        S = lm.shape[0]
        z_loc, (h_re, h_im) = scan_lib.stlt_chunked(
            x_loc, lm, th, ur, ui, chunk=chunk, return_state=True
        )
        # exchange end states (one all-gather of O(S*d) per device)
        g_re = jax.lax.all_gather(h_re, axis)   # [D, B, S, d]
        g_im = jax.lax.all_gather(h_im, axis)
        D = g_re.shape[0]
        i = jax.lax.axis_index(axis)
        # lambda^{N_loc * (i-1-j)} for j < i
        lam_re = jnp.exp(lm) * jnp.cos(th)
        lam_im = jnp.exp(lm) * jnp.sin(th)
        # log-space powers: lambda^(p*N_loc)
        j = jnp.arange(D)
        pw = (i - 1 - j) * N_loc                         # exponent per source
        valid = (j < i)
        mag = jnp.exp(jnp.maximum(pw, 0)[:, None] * lm[None, :])  # [D, S]
        ang = jnp.maximum(pw, 0)[:, None] * th[None, :]
        w_re = jnp.where(valid[:, None], mag * jnp.cos(ang), 0.0)
        w_im = jnp.where(valid[:, None], mag * jnp.sin(ang), 0.0)
        # H_in[k] = sum_j w_j h_j   (complex)
        Hin_re = jnp.einsum("ds,dbsk->bsk", w_re, g_re) - jnp.einsum(
            "ds,dbsk->bsk", w_im, g_im
        )
        Hin_im = jnp.einsum("ds,dbsk->bsk", w_re, g_im) + jnp.einsum(
            "ds,dbsk->bsk", w_im, g_re
        )
        # correction: z[n] += Re(sum_k u_k lambda^(n+1) H_in[k])
        n = jnp.arange(1, N_loc + 1, dtype=jnp.float32)
        mag_n = jnp.exp(n[:, None] * lm[None, :])        # [N_loc, S]
        ang_n = n[:, None] * th[None, :]
        c_re = mag_n * jnp.cos(ang_n)
        c_im = mag_n * jnp.sin(ang_n)
        # coefficient of h_re: Re(u lambda^n) ; of h_im: -Im(u lambda^n)
        A = ur[None, :] * c_re - ui[None, :] * c_im      # [N_loc, S]
        Bc = -(ur[None, :] * c_im + ui[None, :] * c_re)
        corr = jnp.einsum("ns,bsk->bnk", A, Hin_re) + jnp.einsum(
            "ns,bsk->bnk", Bc, Hin_im
        )
        return z_loc + corr.astype(z_loc.dtype)

    shmap = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(None, axis, None), P(None), P(None), P(None), P(None)),
        out_specs=P(None, axis, None),
        check_vma=False,  # scan carries inside are device-varying by design
    )
    return shmap(x, log_mag, theta, u_re, u_im)
