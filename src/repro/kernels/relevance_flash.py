"""Flash-tiled Pallas kernel for the STLT relevance readout.

The paper-figure mode computes

    R[n, m] = Re(sum_k m_k L[n,k,:] . conj(L[m,k,:])) / sqrt(S)
    Z       = softmax(R + causal_mask + key_pad_mask) V

where ``L`` is the (possibly bidirectional) Laplace transform of the
per-head inputs. Materializing R costs O(N^2) memory; this kernel streams
it block-by-block over a ``(row, q-tile, k-tile)`` grid and never holds
more than one [T, T] score tile (DESIGN.md §3):

* **Tile reconstruction.** A tile's L rows follow in closed form from the
  carry at the tile start (the PR-5 ``stlt_carry_snapshot`` algebra):

      L[t0+i] = sum_{j<=i} lambda^(i-j) x[t0+j]  +  lambda^(i+1) h(t0)

  The local sum is ONE real matmul per re/im part: the host bakes the
  per-node lower-triangular Toeplitz powers into a flattened
  ``[T*S, T]`` operator (row (i, k) holds lambda_k^(i-j)), so the whole
  [T, S, dh] coefficient tile is ``reshape(tri2t @ x_tile)`` — MXU work,
  no per-node loop. The carry injection ``lambda^(i+1) h(t0)`` is a
  [T, S] x [S, dh] broadcast. Bidirectional tiles add the mirrored
  upper-triangular operator plus ``lambda^(T-i) g(t1)`` from a reverse
  carry at the tile END, minus the double-counted center ``x`` (the
  ``L + L_rev - x`` correction). Tile-boundary carries ``h``/``g`` are
  precomputed on host by one O(N*S*dh) operator scan over tiles — the
  same Pre/Pim/dec chunk algebra as ``ops._filter_ops``.

* **Online softmax.** Standard FlashAttention accumulation: running row
  max ``m`` and denominator ``l`` in VMEM scratch, tile scores rescale
  the [T, dh] output accumulator by ``exp(m_old - m_new)``. Causal mode
  masks ``k > n`` in the diagonal tile and skips strictly-upper tiles
  (``pl.when(ki <= qi)``); the final tile of each q row divides through.
  Masked scores use a finite ``-1e30`` and probabilities are forced to
  exact zero, so fully-masked rows (e.g. an all-padding row) come out 0
  rather than NaN.

* **Masks and padding.** Adaptive node masks ``m_k`` fold into the
  query-side coefficients (matching the materialized ``Lw . conj(L)``
  contraction). ``kmask`` marks valid keys: masked positions are zeroed
  on the way into the transform (so bidirectional reverse carries never
  see pad garbage) and removed from every softmax row with -inf scores.

* **VJP.** The kernel forward pairs with a recompute-per-tile backward:
  ``jax.vjp`` of the jnp tiled reference (a remat'd scan over q tiles,
  the non-TPU dispatch target) — O(N*T) residuals, no [N, N] or
  [N, S, dh] materialization, mirroring the PR-5 recompute philosophy.

VMEM budget per grid cell is O(T^2 * S) for the Toeplitz operators plus
O(T * S * dh) for the coefficient tiles — independent of N, and above the
TPU compiler's default scoped limit at the paper's widths, so the kernel
asks for what ``_vmem_limit_bytes`` counts.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30  # finite -inf stand-in: exp underflows to exact 0, no NaNs


# ---------------------------------------------------------------------------
# host-side operator / carry precompute
# ---------------------------------------------------------------------------


def _flash_ops(x, log_mag, theta, tile: int, bidirectional: bool):
    """Per-row tile operators + tile-boundary carries for ``x`` [BH, Np, dh]
    (Np % tile == 0, pad/mask positions already zeroed).

    Returns a dict of float32 arrays:
      tri2t_re/im [BH, T*S, T]   flattened lower-tri Toeplitz: row (i, k),
                                 col j holds lambda_k^(i-j) for i >= j
      inj_re/im   [BH, T, S]     forward carry injection lambda^(i+1)
      hc_re/im    [BH, nt, S, dh] carry h at each tile START (h_0 = 0)
    and, when bidirectional:
      rtri2t_re/im [BH, T*S, T]  upper-tri mirror lambda_k^(j-i) for j >= i
      rinj_re/im   [BH, T, S]    reverse injection lambda^(T-i)
      gc_re/im     [BH, nt, S, dh] reverse carry g at each tile END
                                 (g for tile c = sum_{m >= (c+1)T} lambda^(m-(c+1)T) x[m])
    """
    BH, Np, dh = x.shape
    S = log_mag.shape[-1]
    T = tile
    nt = Np // T
    p = jnp.arange(T + 1, dtype=jnp.float32)                   # powers 0..T
    mag = jnp.exp(p[None, :, None] * log_mag[:, None, :])      # [BH, T+1, S]
    ang = p[None, :, None] * theta[:, None, :]
    pw_re = mag * jnp.cos(ang)
    pw_im = mag * jnp.sin(ang)

    idx = jnp.arange(T)
    diff = idx[:, None] - idx[None, :]                         # i - j

    def tri2t(pw, d):
        # [BH, T, T, S] gather of lambda^d masked to d >= 0, flattened so
        # that row (i*S + k) is node k's i-th Toeplitz row.
        t = jnp.where(d[None, :, :, None] >= 0,
                      pw[:, jnp.clip(d, 0, T), :], 0.0)
        return t.transpose(0, 1, 3, 2).reshape(BH, T * S, T)

    ops = {
        "tri2t_re": tri2t(pw_re, diff),
        "tri2t_im": tri2t(pw_im, diff),
        "inj_re": pw_re[:, 1:T + 1, :],
        "inj_im": pw_im[:, 1:T + 1, :],
    }

    xt = jnp.moveaxis(x.reshape(BH, nt, T, dh), 1, 0)          # [nt, BH, T, dh]
    dec_re = pw_re[:, T, :, None]                              # [BH, S, 1]
    dec_im = pw_im[:, T, :, None]
    pre_re = pw_re[:, T - 1 - idx, :].transpose(0, 2, 1)       # [BH, S, T]
    pre_im = pw_im[:, T - 1 - idx, :].transpose(0, 2, 1)
    zero = jnp.zeros((BH, S, dh), jnp.float32)

    def fwd_step(carry, x_c):
        r, i = carry
        r2 = jnp.einsum("bst,btd->bsd", pre_re, x_c) + dec_re * r - dec_im * i
        i2 = jnp.einsum("bst,btd->bsd", pre_im, x_c) + dec_re * i + dec_im * r
        return (r2, i2), (r, i)  # emit the carry at the tile START

    _, (hc_re, hc_im) = jax.lax.scan(fwd_step, (zero, zero), xt)
    ops["hc_re"] = jnp.moveaxis(hc_re, 0, 1)                   # [BH, nt, S, dh]
    ops["hc_im"] = jnp.moveaxis(hc_im, 0, 1)

    if bidirectional:
        ops["rtri2t_re"] = tri2t(pw_re, -diff)
        ops["rtri2t_im"] = tri2t(pw_im, -diff)
        ops["rinj_re"] = pw_re[:, T - idx, :]
        ops["rinj_im"] = pw_im[:, T - idx, :]
        rpre_re = pw_re[:, idx, :].transpose(0, 2, 1)          # lambda^j
        rpre_im = pw_im[:, idx, :].transpose(0, 2, 1)

        def rev_step(carry, x_c):
            r, i = carry  # g at this tile's END (g_{c+1})
            r2 = jnp.einsum("bst,btd->bsd", rpre_re, x_c) + dec_re * r - dec_im * i
            i2 = jnp.einsum("bst,btd->bsd", rpre_im, x_c) + dec_re * i + dec_im * r
            return (r2, i2), (r, i)

        _, (gc_re, gc_im) = jax.lax.scan(rev_step, (zero, zero), xt,
                                         reverse=True)
        ops["gc_re"] = jnp.moveaxis(gc_re, 0, 1)
        ops["gc_im"] = jnp.moveaxis(gc_im, 0, 1)
    return ops


def _reconstruct(xt, ops, hre, him, gre, gim, bidirectional: bool):
    """Batched tile coefficients: xt [BH, T, dh] -> L re/im [BH, T, S, dh].

    The jnp mirror of the in-kernel reconstruction (reference/VJP path).
    """
    BH, T, dh = xt.shape
    S = hre.shape[-2]
    l_re = jnp.einsum("bft,btd->bfd", ops["tri2t_re"], xt).reshape(BH, T, S, dh)
    l_im = jnp.einsum("bft,btd->bfd", ops["tri2t_im"], xt).reshape(BH, T, S, dh)
    l_re += ops["inj_re"][..., None] * hre[:, None] - ops["inj_im"][..., None] * him[:, None]
    l_im += ops["inj_re"][..., None] * him[:, None] + ops["inj_im"][..., None] * hre[:, None]
    if bidirectional:
        l_re += jnp.einsum("bft,btd->bfd", ops["rtri2t_re"], xt).reshape(BH, T, S, dh)
        l_im += jnp.einsum("bft,btd->bfd", ops["rtri2t_im"], xt).reshape(BH, T, S, dh)
        l_re += ops["rinj_re"][..., None] * gre[:, None] - ops["rinj_im"][..., None] * gim[:, None]
        l_im += ops["rinj_re"][..., None] * gim[:, None] + ops["rinj_im"][..., None] * gre[:, None]
        l_re -= xt[:, :, None, :]  # L + L_rev double-counts the center
    return l_re, l_im


def _pad_tiles(x, v, kmask, tile: int):
    """Pad [BH, N, ...] inputs to a tile multiple; zero masked/pad inputs."""
    BH, N, _ = x.shape
    pad = (-N) % tile
    km = jnp.ones((BH, N), jnp.float32) if kmask is None \
        else kmask.astype(jnp.float32)
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
        km = jnp.pad(km, ((0, 0), (0, pad)))
    # masked keys contribute nothing to L (bidirectional reverse carries
    # must never see pad garbage); their scores are -inf'd below too
    x = x * km[:, :, None]
    return x, v, km


# ---------------------------------------------------------------------------
# the Pallas kernel
# ---------------------------------------------------------------------------


def _flash_body(*refs, tile: int, S: int, dh: int, causal: bool):
    T = tile
    if causal:
        (xq_ref, xk_ref, v_ref, hq_re_ref, hq_im_ref, hk_re_ref, hk_im_ref,
         tri_re_ref, tri_im_ref, inj_re_ref, inj_im_ref, mk_ref, km_ref,
         z_ref, qre_s, qim_s, m_s, l_s, acc_s) = refs
    else:
        (xq_ref, xk_ref, v_ref, hq_re_ref, hq_im_ref, hk_re_ref, hk_im_ref,
         tri_re_ref, tri_im_ref, inj_re_ref, inj_im_ref, mk_ref, km_ref,
         gq_re_ref, gq_im_ref, gk_re_ref, gk_im_ref,
         rtri_re_ref, rtri_im_ref, rinj_re_ref, rinj_im_ref,
         z_ref, qre_s, qim_s, m_s, l_s, acc_s) = refs
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    tri_re, tri_im = tri_re_ref[0], tri_im_ref[0]      # [T*S, T]
    inj_re, inj_im = inj_re_ref[0], inj_im_ref[0]      # [T, S]

    def rec(xt, h_re, h_im, g_re, g_im):
        # closed-form tile coefficients: local Toeplitz matmul + carry
        # injection (see module docstring) -> [T, S, dh] re/im
        l_re = jnp.dot(tri_re, xt,
                       preferred_element_type=jnp.float32).reshape(T, S, dh)
        l_im = jnp.dot(tri_im, xt,
                       preferred_element_type=jnp.float32).reshape(T, S, dh)
        l_re += inj_re[:, :, None] * h_re[None] - inj_im[:, :, None] * h_im[None]
        l_im += inj_re[:, :, None] * h_im[None] + inj_im[:, :, None] * h_re[None]
        if not causal:
            rtri_re, rtri_im = rtri_re_ref[0], rtri_im_ref[0]
            rinj_re, rinj_im = rinj_re_ref[0], rinj_im_ref[0]
            l_re += jnp.dot(rtri_re, xt,
                            preferred_element_type=jnp.float32).reshape(T, S, dh)
            l_im += jnp.dot(rtri_im, xt,
                            preferred_element_type=jnp.float32).reshape(T, S, dh)
            l_re += rinj_re[:, :, None] * g_re[None] - rinj_im[:, :, None] * g_im[None]
            l_im += rinj_re[:, :, None] * g_im[None] + rinj_im[:, :, None] * g_re[None]
            l_re -= xt[:, None, :]
        return l_re, l_im

    @pl.when(ki == 0)
    def _init_q():
        gq_re = gq_im = None
        if not causal:
            gq_re, gq_im = gq_re_ref[0, 0], gq_im_ref[0, 0]
        ql_re, ql_im = rec(xq_ref[0], hq_re_ref[0, 0], hq_im_ref[0, 0],
                           gq_re, gq_im)
        mk = mk_ref[0, 0]  # adaptive node masks fold query-side (Lw . conj L)
        qre_s[...] = (ql_re * mk[None, :, None]).reshape(T, S * dh)
        qim_s[...] = (ql_im * mk[None, :, None]).reshape(T, S * dh)
        m_s[...] = jnp.full((T, 1), _NEG, jnp.float32)
        l_s[...] = jnp.zeros((T, 1), jnp.float32)
        acc_s[...] = jnp.zeros((T, dh), jnp.float32)

    @pl.when(jnp.logical_or(not causal, ki <= qi))
    def _tile():
        gk_re = gk_im = None
        if not causal:
            gk_re, gk_im = gk_re_ref[0, 0], gk_im_ref[0, 0]
        kl_re, kl_im = rec(xk_ref[0], hk_re_ref[0, 0], hk_im_ref[0, 0],
                           gk_re, gk_im)
        k_re = kl_re.reshape(T, S * dh)
        k_im = kl_im.reshape(T, S * dh)
        dn = (((1,), (1,)), ((), ()))  # contract the S*dh feature dim
        r = jax.lax.dot_general(qre_s[...], k_re, dn,
                                preferred_element_type=jnp.float32)
        r += jax.lax.dot_general(qim_s[...], k_im, dn,
                                 preferred_element_type=jnp.float32)
        r *= 1.0 / math.sqrt(S)

        valid = km_ref[0] > 0.0                                # [1, T]
        if causal:
            rows = qi * T + jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
            cols = ki * T + jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
            valid = jnp.logical_and(valid, cols <= rows)
        r = jnp.where(valid, r, _NEG)

        m_old = m_s[...]                                       # [T, 1]
        m_new = jnp.maximum(m_old, jnp.max(r, axis=1, keepdims=True))
        # force masked entries to exact zero (an all-masked row would
        # otherwise get exp(_NEG - _NEG) = 1 per key)
        p = jnp.where(valid, jnp.exp(r - m_new), 0.0)
        alpha = jnp.exp(m_old - m_new)
        m_s[...] = m_new
        l_s[...] = alpha * l_s[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_s[...] = alpha * acc_s[...] + jnp.dot(
            p, v_ref[0], preferred_element_type=jnp.float32)

    last = ki == (qi if causal else nk - 1)

    @pl.when(last)
    def _write():
        l = l_s[...]
        safe = jnp.where(l > 0, l, 1.0)
        z_ref[0] = jnp.where(l > 0, acc_s[...] / safe, 0.0)


def _vmem_limit_bytes(T: int, S: int, dh: int, causal: bool) -> int:
    """Scoped-VMEM budget of one grid cell, from the block shapes.

    Every buffer is float32 and lies in (8, 128) tiles, so a dh = 64 row
    takes 128 lanes. The pipeline double-buffers each block: x_q, x_k, v
    and z (4 * T x dh), four tile carries (4 * S x dh), the two flattened
    Toeplitz operators (2 * T*S x T), the two injections (2 * T x S) and
    the two masks (1 x S, 1 x T). Bidirectional tiles double the carries,
    operators and injections. Scratch is single: the query coefficients
    (2 * T x S*dh), m and l (2 * T x 1) and the accumulator (T x dh).
    The body's [T*S, dh] coefficient temporaries come on top: the TPU
    compiler keeps 7 of them live causal and 16 bidirectional (read off its
    report at T = 128, S = 64, dh = 64; a "highest"-precision matmul splits
    its f32 operands into bf16 parts, which takes 9.6 MiB more causal).
    4 MiB more covers Mosaic's internal scratch (2 MiB there).

    At T = 128, S = 64, dh = 64 that is 53.2 MiB causal (the compiler asked
    for 39.8 MiB at the default precision and 49.4 MiB at "highest",
    against a default scoped limit of 16 MiB) and 105.7 MiB bidirectional
    (it asked for 101.8 and 100.0 MiB); the v5e has 128 MiB of VMEM. Never
    below the default limit.
    """
    def tile(rows, cols):
        return -(-rows // 8) * 8 * (-(-cols // 128)) * 128

    per_dir = 4 * tile(S, dh) + 2 * tile(T * S, T) + 2 * tile(T, S)
    blocks = 4 * tile(T, dh) + per_dir + tile(1, S) + tile(1, T)
    if not causal:
        blocks += per_dir
    scratch = 2 * tile(T, S * dh) + 2 * tile(T, 1) + tile(T, dh)
    temps = (7 if causal else 16) * tile(T * S, dh)
    return max(16 << 20, 4 * (2 * blocks + scratch + temps) + (4 << 20))


@functools.partial(jax.jit, static_argnames=("tile", "causal", "interpret"))
def relevance_flash_kernel(x, v, mk, km, ops, *, tile: int,
                           causal: bool, interpret: bool = False):
    """One flash-tiled relevance dispatch over padded inputs.

    x/v [BH, Np, dh] (Np % tile == 0, masked x already zeroed), mk [BH, S]
    node masks, km [BH, Np] key-validity, ``ops`` the ``_flash_ops`` dict.
    Returns z [BH, Np, dh] float32. ONE pallas_call: R never leaves VMEM.
    """
    BH, Np, dh = x.shape
    S = mk.shape[-1]
    T = tile
    nt = Np // T
    grid = (BH, nt, nt)

    def q_idx(bh, qi, ki):
        return (bh, qi, 0)

    def k_idx(bh, qi, ki):
        return (bh, ki, 0)

    def op_idx(bh, qi, ki):
        return (bh, 0, 0)

    xspec_q = pl.BlockSpec((1, T, dh), q_idx)
    xspec_k = pl.BlockSpec((1, T, dh), k_idx)
    cspec_q = pl.BlockSpec((1, 1, S, dh), lambda bh, qi, ki: (bh, qi, 0, 0))
    cspec_k = pl.BlockSpec((1, 1, S, dh), lambda bh, qi, ki: (bh, ki, 0, 0))
    tri_spec = pl.BlockSpec((1, T * S, T), op_idx)
    inj_spec = pl.BlockSpec((1, T, S), op_idx)

    # masks ride as [BH, 1, S] / [BH, 1, Np]: a block's last two dims must
    # match the array's or be (8, 128)-aligned, and a (1, S) block of a
    # [BH, S] array is neither
    inputs = [x, x, v, ops["hc_re"], ops["hc_im"], ops["hc_re"], ops["hc_im"],
              ops["tri2t_re"], ops["tri2t_im"], ops["inj_re"], ops["inj_im"],
              mk[:, None, :], km[:, None, :]]
    in_specs = [xspec_q, xspec_k, xspec_k, cspec_q, cspec_q, cspec_k, cspec_k,
                tri_spec, tri_spec, inj_spec, inj_spec,
                pl.BlockSpec((1, 1, S), op_idx),
                pl.BlockSpec((1, 1, T), lambda bh, qi, ki: (bh, 0, ki))]
    if not causal:
        inputs += [ops["gc_re"], ops["gc_im"], ops["gc_re"], ops["gc_im"],
                   ops["rtri2t_re"], ops["rtri2t_im"],
                   ops["rinj_re"], ops["rinj_im"]]
        in_specs += [cspec_q, cspec_q, cspec_k, cspec_k,
                     tri_spec, tri_spec, inj_spec, inj_spec]

    scratch = [pltpu.VMEM((T, S * dh), jnp.float32),
               pltpu.VMEM((T, S * dh), jnp.float32),
               pltpu.VMEM((T, 1), jnp.float32),
               pltpu.VMEM((T, 1), jnp.float32),
               pltpu.VMEM((T, dh), jnp.float32)]
    body = functools.partial(_flash_body, tile=T, S=S, dh=dh, causal=causal)
    return pl.pallas_call(
        body,
        grid=grid,
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, T, dh), q_idx)],
        out_shape=[jax.ShapeDtypeStruct((BH, Np, dh), jnp.float32)],
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit_bytes(T, S, dh, causal)),
        interpret=interpret,
    )(*inputs)[0]


# ---------------------------------------------------------------------------
# jnp tiled reference (non-kernel dispatch target + recompute backward)
# ---------------------------------------------------------------------------


def relevance_flash_reference(x, v, log_mag, theta, mk, km, *,
                              tile: int, causal: bool):
    """Tiled online-softmax relevance in plain jnp — bit-for-bit the kernel's
    algorithm (same operators, same accumulation order), structured as a
    remat'd scan over q tiles so ``jax.grad`` through it IS the
    recompute-per-tile backward: O(N*T) residuals, never [N, N].
    """
    BH, N, dh = x.shape
    S = log_mag.shape[-1]
    T = tile
    x, v, km = _pad_tiles(x.astype(jnp.float32), v.astype(jnp.float32),
                          km, T)
    Np = x.shape[1]
    nt = Np // T
    ops = _flash_ops(x, log_mag.astype(jnp.float32),
                     theta.astype(jnp.float32), T, bidirectional=not causal)
    zero_c = jnp.zeros((nt, BH, S, dh), jnp.float32)
    xt = jnp.moveaxis(x.reshape(BH, nt, T, dh), 1, 0)      # [nt, BH, T, dh]
    vt = jnp.moveaxis(v.reshape(BH, nt, T, dh), 1, 0)
    kmt = jnp.moveaxis(km.reshape(BH, nt, T), 1, 0)        # [nt, BH, T]
    hct = jnp.moveaxis(ops["hc_re"], 1, 0), jnp.moveaxis(ops["hc_im"], 1, 0)
    gct = (jnp.moveaxis(ops["gc_re"], 1, 0), jnp.moveaxis(ops["gc_im"], 1, 0)) \
        if not causal else (zero_c, zero_c)
    ti = jnp.arange(nt)
    scale = 1.0 / math.sqrt(S)

    def q_body(_, q_in):
        qi, xq, hq_re, hq_im, gq_re, gq_im = q_in
        ql_re, ql_im = _reconstruct(xq, ops, hq_re, hq_im, gq_re, gq_im,
                                    not causal)
        q_re = (ql_re * mk[:, None, :, None]).reshape(BH, T, S * dh)
        q_im = (ql_im * mk[:, None, :, None]).reshape(BH, T, S * dh)

        def k_body(carry, k_in):
            m_old, l_old, acc = carry
            ki, xk, vk, kmk, hk_re, hk_im, gk_re, gk_im = k_in
            kl_re, kl_im = _reconstruct(xk, ops, hk_re, hk_im, gk_re, gk_im,
                                        not causal)
            k_re = kl_re.reshape(BH, T, S * dh)
            k_im = kl_im.reshape(BH, T, S * dh)
            r = (jnp.einsum("btf,buf->btu", q_re, k_re)
                 + jnp.einsum("btf,buf->btu", q_im, k_im)) * scale
            valid = kmk[:, None, :] > 0.0                  # [BH, 1, T]
            if causal:
                rows = qi * T + jnp.arange(T)
                cols = ki * T + jnp.arange(T)
                valid = jnp.logical_and(
                    valid, (cols[None, :] <= rows[:, None])[None])
            r = jnp.where(valid, r, _NEG)
            m_new = jnp.maximum(m_old, jnp.max(r, axis=-1))
            p = jnp.where(valid, jnp.exp(r - m_new[..., None]), 0.0)
            alpha = jnp.exp(m_old - m_new)
            l_new = alpha * l_old + p.sum(-1)
            acc = alpha[..., None] * acc + jnp.einsum("btu,bud->btd", p, vk)
            return (m_new, l_new, acc), None

        init = (jnp.full((BH, T), _NEG, jnp.float32),
                jnp.zeros((BH, T), jnp.float32),
                jnp.zeros((BH, T, dh), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(
            k_body, init, (ti, xt, vt, kmt, *hct, *gct))
        safe = jnp.where(l > 0, l, 1.0)
        z = jnp.where(l[..., None] > 0, acc / safe[..., None], 0.0)
        return None, z

    _, zt = jax.lax.scan(jax.checkpoint(q_body), None, (ti, xt, *hct, *gct))
    z = jnp.moveaxis(zt, 0, 1).reshape(BH, Np, dh)
    return z[:, :N]


# ---------------------------------------------------------------------------
# custom VJP + public dispatch
# ---------------------------------------------------------------------------


def _run_flash(x, v, log_mag, theta, mk, km, tile, causal, interpret):
    BH, N, dh = x.shape
    xp, vp, kmp = _pad_tiles(x.astype(jnp.float32), v.astype(jnp.float32),
                             km, tile)
    ops = _flash_ops(xp, log_mag.astype(jnp.float32),
                     theta.astype(jnp.float32), tile,
                     bidirectional=not causal)
    z = relevance_flash_kernel(xp, vp, mk.astype(jnp.float32), kmp, ops,
                               tile=tile, causal=causal, interpret=interpret)
    return z[:, :N]


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _rel_flash(x, v, log_mag, theta, mk, km, tile, causal, interpret):
    return _run_flash(x, v, log_mag, theta, mk, km, tile, causal, interpret)


def _rel_fwd(x, v, log_mag, theta, mk, km, tile, causal, interpret):
    z = _run_flash(x, v, log_mag, theta, mk, km, tile, causal, interpret)
    return z, (x, v, log_mag, theta, mk, km)


def _rel_bwd(tile, causal, interpret, res, dz):
    # recompute-per-tile backward: autodiff through the remat'd jnp tiled
    # reference — same math as the kernel, O(N*T) peak memory
    x, v, log_mag, theta, mk, km = res

    def ref(x_, v_, lm_, th_, mk_):
        return relevance_flash_reference(x_, v_, lm_, th_, mk_, km,
                                         tile=tile, causal=causal)

    _, vjp = jax.vjp(ref, x, v, log_mag, theta, mk)
    dx, dv, dlm, dth, dmk = vjp(dz.astype(jnp.float32))
    return (dx.astype(x.dtype), dv.astype(v.dtype), dlm, dth, dmk,
            jnp.zeros_like(km))


_rel_flash.defvjp(_rel_fwd, _rel_bwd)


def relevance_flash(
    x: jax.Array,                    # [BH, N, dh] transform inputs (per head)
    v: jax.Array,                    # [BH, N, dh] values
    log_mag: jax.Array,              # [BH, S] per-row poles
    theta: jax.Array,
    *,
    masks: Optional[jax.Array] = None,   # [BH, S] adaptive node masks
    kmask: Optional[jax.Array] = None,   # [BH, N] 1 = valid key, 0 = pad
    causal: bool = True,             # False = bidirectional (encoder) mode
    tile: int = 128,
    interpret: Optional[bool] = None,
    use_kernel: Optional[bool] = None,
):
    """Flash-tiled relevance readout: z = softmax-over-keys(R) @ v, [BH, N, dh].

    Dispatch mirrors ``ops.stlt_scan``: Pallas kernel on TPU (or
    ``interpret=True`` for CPU validation); the jnp tiled reference
    elsewhere. Differentiable in x/v/poles/masks either way — the kernel
    path runs the custom VJP (recompute-per-tile backward through the
    reference), the jnp path is remat'd for the same memory profile.
    """
    BH, N, dh = x.shape
    S = log_mag.shape[-1]
    mk = jnp.ones((BH, S), jnp.float32) if masks is None \
        else masks.astype(jnp.float32)
    km = None if kmask is None else kmask.astype(jnp.float32)
    on_tpu = jax.default_backend() == "tpu"
    if use_kernel is None:
        use_kernel = on_tpu or bool(interpret)
    if not use_kernel:
        return relevance_flash_reference(x, v, log_mag, theta, mk, km,
                                         tile=tile, causal=causal)
    interp = (not on_tpu) if interpret is None else interpret
    kmf = jnp.ones((BH, N), jnp.float32) if km is None else km
    return _rel_flash(x, v, log_mag, theta, mk, kmf, tile, causal, interp)
