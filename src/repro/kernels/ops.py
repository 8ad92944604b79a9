"""jit'd wrapper for the STLT Pallas kernel: host-side operator precompute,
padding, reverse handling, carry I/O, dispatch (kernel on TPU / interpret for
tests / jnp chunked scan elsewhere), and the custom VJP.

Carry-native contract (DESIGN.md §3): ``stlt_scan`` accepts an initial carry
``h0_re/h0_im`` [BH, S, d], a per-row ``valid`` length, and
``return_state=True`` — the kernel seeds its VMEM carry from h0 and emits the
snapshot state at ``valid[row]`` (default N) in the SAME dispatch, so a
resumed serving prefill chunk is exactly one scan pass (the PR-2..4 era
folded the carry in by linearity: a zero-state pass plus
``stlt_carry_outputs`` + ``stlt_final_state`` full-sequence passes).

VJP structure (DESIGN.md §3): z is a causal convolution with the combined
filter g[t] = sum_k Re(u_k lambda_k^t), so

  dL/dx  = the SAME kernel run anti-causally over dz    (kernel-accelerated)
  dL/d(poles, mixers) = ANALYTIC kernel path: accumulate adjoints of the
           tiny chunk operators (g via the lag-t correlation of dz with x —
           one C x C matmul per chunk — A/B/Pre/Pim/dec via an O(S*d)
           adjoint-carry scan), then chain through ``_filter_ops``'s
           N-independent pole/mixer Jacobians with ``jax.vjp``. No O(N*S*d)
           tensor is ever materialized; ``param_grads="recompute"`` keeps the
           old per-node jnp recompute for A/B benchmarks
           (benchmarks/kernels.py).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import scan as scan_lib
from repro.kernels.stlt_scan import stlt_scan_kernel


def _filter_ops(log_mag, theta, u_re, u_im, chunk: int):
    """Per-row chunk operators from poles — all tiny and N-independent.

    log_mag/theta/u_re/u_im: [BH, S] ->
      g   [BH, C]     combined causal filter g[t] = Re(sum_k u_k lambda_k^t)
      A,B [BH, C, S]  carry injection  (z_carry[i] = A[i,k] h_re + B[i,k] h_im)
      pre,pim [BH, S, C]  carry gather (h'[k] += sum_j lambda^(C-1-j) x[j])
      dec [BH, 2, S]  chunk-to-chunk decay lambda^C

    The analytic param-grad VJP chains through ``jax.vjp`` of THIS function
    (everything downstream of it is linear in the operators).
    """
    BH, S = log_mag.shape
    C = chunk
    p = jnp.arange(C + 1, dtype=jnp.float32)  # powers 0..C
    mag = jnp.exp(p[None, :, None] * log_mag[:, None, :])      # [BH, C+1, S]
    ang = p[None, :, None] * theta[:, None, :]
    pw_re = mag * jnp.cos(ang)
    pw_im = mag * jnp.sin(ang)
    g = jnp.einsum("bts,bs->bt", pw_re[:, :C], u_re) - jnp.einsum(
        "bts,bs->bt", pw_im[:, :C], u_im
    )  # [BH, C]
    a_re, a_im = pw_re[:, 1:], pw_im[:, 1:]  # lambda^(i+1), i=0..C-1
    A = u_re[:, None, :] * a_re - u_im[:, None, :] * a_im       # [BH, C, S]
    B = -(u_re[:, None, :] * a_im + u_im[:, None, :] * a_re)
    idx = jnp.arange(C)
    rev = C - 1 - idx
    pre = jnp.transpose(pw_re[:, rev], (0, 2, 1))               # [BH, S, C]
    pim = jnp.transpose(pw_im[:, rev], (0, 2, 1))
    dec = jnp.stack([pw_re[:, C], pw_im[:, C]], axis=1)         # [BH, 2, S]
    return g, A, B, pre, pim, dec


def _toeplitz(g):
    """g [BH, C] -> lower-triangular Toeplitz M [BH, C, C]."""
    C = g.shape[-1]
    idx = jnp.arange(C)
    diff = idx[:, None] - idx[None, :]
    return jnp.where(diff >= 0, g[:, jnp.clip(diff, 0, C - 1)], 0.0)


def _snapshot_ops(log_mag, theta, valid, n_tokens: int, chunk: int):
    """Per-row carry-snapshot operators for a snapshot at token ``valid[row]``
    (or ``n_tokens`` when valid is None), kernel-shaped.

    Returns (spre, spim [BH, S, C], sdec [BH, 2, S], snap [BH] int32):
    the snapshot chunk c* = (q-1)//C evaluates h_q = S @ X_c* + lambda^r h_c*
    with r the in-chunk offset — the closed-form per-row carry correction
    that makes padded tails and non-multiple lengths exact in ONE pass. The
    operator math lives in ``scan_lib.stlt_snapshot_operators`` (shared
    with the jnp engines' ``stlt_carry_snapshot`` — one algebra, two
    backends).
    """
    BH = log_mag.shape[0]
    if valid is None:
        q = jnp.full((BH,), n_tokens, jnp.int32)
    else:
        q = valid.astype(jnp.int32)
    cstar, w_re, w_im, d_re, d_im = scan_lib.stlt_snapshot_operators(
        log_mag, theta, q, chunk)
    spre = jnp.transpose(w_re, (0, 2, 1))                    # [BH, S, C]
    spim = jnp.transpose(w_im, (0, 2, 1))
    sdec = jnp.stack([d_re, d_im], axis=1)                   # [BH, 2, S]
    # valid == 0 rows never fire (their snapshot is h0, written at c == 0)
    snap = jnp.where(q > 0, cstar, -1).astype(jnp.int32)
    return spre, spim, sdec, snap


def _run_kernel(x, log_mag, theta, u_re, u_im, chunk, reverse, interpret,
                block_d, h0_re=None, h0_im=None, valid=None):
    """Pad/flip, precompute operators, dispatch ONE kernel pass.

    Returns (z [BH, N, d] in x.dtype, h_re, h_im [BH, S, d] float32) — the
    carry outputs snapshot the state at ``valid[row]`` (default N, the true
    unpadded length) in the scan direction.
    """
    BH, N, d = x.shape
    S = log_mag.shape[-1]
    xf = x.astype(jnp.float32)
    if reverse:
        xf = xf[:, ::-1, :]
    pad_n = (-N) % chunk
    pad_d = (-d) % block_d
    if pad_n or pad_d:
        xf = jnp.pad(xf, ((0, 0), (0, pad_n), (0, pad_d)))
    dp = d + pad_d
    lm = log_mag.astype(jnp.float32)
    th = theta.astype(jnp.float32)
    g, A, B, pre, pim, dec = _filter_ops(
        lm, th, u_re.astype(jnp.float32), u_im.astype(jnp.float32), chunk)
    m = _toeplitz(g)
    spre, spim, sdec, snap = _snapshot_ops(lm, th, valid, N, chunk)
    if h0_re is None:
        h0r = jnp.zeros((BH, S, dp), jnp.float32)
        h0i = h0r
    else:
        h0r = h0_re.astype(jnp.float32)
        h0i = h0_im.astype(jnp.float32)
        if pad_d:
            h0r = jnp.pad(h0r, ((0, 0), (0, 0), (0, pad_d)))
            h0i = jnp.pad(h0i, ((0, 0), (0, 0), (0, pad_d)))
    z, h_re, h_im = stlt_scan_kernel(
        snap, xf, m, A, B, pre, pim, dec, h0r, h0i, spre, spim, sdec,
        chunk=chunk, block_d=block_d, interpret=interpret)
    if pad_n or pad_d:
        z = z[:, :N, :d]
        h_re, h_im = h_re[:, :, :d], h_im[:, :, :d]
    if reverse:
        z = z[:, ::-1, :]
    return z.astype(x.dtype), h_re, h_im


def _ref_chunked(x, log_mag, theta, u_re, u_im, chunk, reverse,
                 h0_re=None, h0_im=None, valid=None, return_state=False):
    """jnp oracle path (per-row poles) — the non-TPU dispatch target and the
    ``param_grads="recompute"`` baseline. One pass: ``stlt_chunked`` is
    itself carry-native (h0 in, per-row valid snapshot out)."""
    BH, _, _ = x.shape
    S = log_mag.shape[-1]
    if h0_re is None and not return_state and valid is None:
        def per_row(xr, lm, th, ur, ui):
            return scan_lib.stlt_chunked(xr, lm, th, ur, ui, chunk=chunk,
                                         reverse=reverse)

        return jax.vmap(per_row)(x, log_mag, theta, u_re, u_im)

    h0r = jnp.zeros((BH, S, x.shape[-1]), jnp.float32) if h0_re is None else h0_re
    h0i = jnp.zeros((BH, S, x.shape[-1]), jnp.float32) if h0_im is None else h0_im

    if valid is None:
        # no per-row lengths: stlt_chunked's native last-position snapshot
        # covers forward AND reverse (reverse + per-row valid is rejected
        # upstream — the snapshot would count from the flipped end)
        def per_row(xr, lm, th, ur, ui, hr, hi):
            return scan_lib.stlt_chunked(
                xr, lm, th, ur, ui, chunk=chunk, reverse=reverse,
                return_state=True, h0_re=hr, h0_im=hi)

        z, (h_re, h_im) = jax.vmap(per_row)(x, log_mag, theta, u_re, u_im,
                                            h0r, h0i)
    else:
        def per_row(xr, lm, th, ur, ui, hr, hi, qr):
            return scan_lib.stlt_chunked(
                xr, lm, th, ur, ui, chunk=chunk, reverse=reverse,
                return_state=True, h0_re=hr, h0_im=hi, valid=qr[None])

        z, (h_re, h_im) = jax.vmap(per_row)(x, log_mag, theta, u_re, u_im,
                                            h0r, h0i, valid)
    if return_state:
        return z, (h_re, h_im)
    return z


# ---------------------------------------------------------------------------
# custom VJP (training path: zero initial carry, z-only output)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _stlt_scan(x, log_mag, theta, u_re, u_im, chunk, reverse, interpret,
               block_d, param_grads):
    z, _, _ = _run_kernel(x, log_mag, theta, u_re, u_im, chunk, reverse,
                          interpret, block_d)
    return z


def _fwd(x, log_mag, theta, u_re, u_im, chunk, reverse, interpret, block_d,
         param_grads):
    z, _, _ = _run_kernel(x, log_mag, theta, u_re, u_im, chunk, reverse,
                          interpret, block_d)
    return z, (x, log_mag, theta, u_re, u_im)


def _analytic_param_grads(x, dz, log_mag, theta, u_re, u_im, chunk, reverse):
    """dL/d(poles, mixers) through the chunk operators — the analytic kernel
    path (DESIGN.md §3).

    z depends on the params ONLY through the tiny operators
    (g, A, B, Pre, Pim, dec) of the chunked recurrence, so:
      * dg[t]  = sum_c sum_{i-j=t} dz_c[i,:] . x_c[j,:]  — the lag-t
        correlation of dz with x, ONE C x C matmul per chunk (O(N*C*d));
      * dA/dB  need the forward chunk-start carries (recomputed with the
        fused-operator recurrence, O((C+S)*d) per chunk — never the per-node
        O(C*S*d) materialization);
      * dPre/dPim/ddec need the adjoint carry, a reverse O(S*d) scan;
      * the operator cotangents chain through ``jax.vjp(_filter_ops)`` —
        N-independent [C, S]-sized Jacobians.
    """
    BH, N, d = x.shape
    C = chunk
    xf = x.astype(jnp.float32)
    dzf = dz.astype(jnp.float32)
    if reverse:
        xf, dzf = xf[:, ::-1, :], dzf[:, ::-1, :]
    pad = (-N) % C
    if pad:
        xf = jnp.pad(xf, ((0, 0), (0, pad), (0, 0)))
        dzf = jnp.pad(dzf, ((0, 0), (0, pad), (0, 0)))
    nc = xf.shape[1] // C
    xc = jnp.moveaxis(xf.reshape(BH, nc, C, d), 1, 0)    # [nc, BH, C, d]
    dzc = jnp.moveaxis(dzf.reshape(BH, nc, C, d), 1, 0)

    lm = log_mag.astype(jnp.float32)
    th = theta.astype(jnp.float32)
    ur = u_re.astype(jnp.float32)
    ui = u_im.astype(jnp.float32)
    (g, A, B, pre, pim, dec), op_vjp = jax.vjp(
        lambda *p: _filter_ops(*p, chunk=C), lm, th, ur, ui)
    del g
    dec_re, dec_im = dec[:, 0, :, None], dec[:, 1, :, None]  # [BH, S, 1]
    S = pre.shape[1]

    # forward chunk-START carries via the fused-operator recurrence
    def fwd_step(carry, x_c):
        r, i = carry
        r2 = jnp.einsum("bsc,bcd->bsd", pre, x_c) + dec_re * r - dec_im * i
        i2 = jnp.einsum("bsc,bcd->bsd", pim, x_c) + dec_re * i + dec_im * r
        return (r2, i2), (r, i)

    zero = jnp.zeros((BH, S, d), jnp.float32)
    _, (R, I) = jax.lax.scan(fwd_step, (zero, zero), xc)

    # reverse adjoint scan: carry = (adjoint of NEXT chunk's start carry,
    # running operator-cotangent accumulators)
    acc0 = (jnp.zeros((BH, C, C), jnp.float32),   # P  = sum dz_c x_c^T
            jnp.zeros((BH, C, S), jnp.float32),   # dA
            jnp.zeros((BH, C, S), jnp.float32),   # dB
            jnp.zeros((BH, S, C), jnp.float32),   # dPre
            jnp.zeros((BH, S, C), jnp.float32),   # dPim
            jnp.zeros((BH, S), jnp.float32),      # ddec_re
            jnp.zeros((BH, S), jnp.float32))      # ddec_im

    def bwd_step(carry, inp):
        dr, di, (P, dA, dB, dpre, dpim, ddre, ddim) = carry
        x_c, dz_c, r_c, i_c = inp
        P = P + jnp.einsum("bid,bjd->bij", dz_c, x_c)
        dA = dA + jnp.einsum("bid,bsd->bis", dz_c, r_c)
        dB = dB + jnp.einsum("bid,bsd->bis", dz_c, i_c)
        dpre = dpre + jnp.einsum("bsd,bcd->bsc", dr, x_c)
        dpim = dpim + jnp.einsum("bsd,bcd->bsc", di, x_c)
        ddre = ddre + (dr * r_c + di * i_c).sum(-1)
        ddim = ddim + (di * r_c - dr * i_c).sum(-1)
        dr_new = (jnp.einsum("bis,bid->bsd", A, dz_c)
                  + dec_re * dr + dec_im * di)
        di_new = (jnp.einsum("bis,bid->bsd", B, dz_c)
                  - dec_im * dr + dec_re * di)
        return (dr_new, di_new, (P, dA, dB, dpre, dpim, ddre, ddim)), None

    (_, _, (P, dA, dB, dpre, dpim, ddre, ddim)), _ = jax.lax.scan(
        bwd_step, (zero, zero, acc0), (xc, dzc, R, I), reverse=True)

    # collapse the Toeplitz cotangent onto the filter: dg[t] = sum of the
    # t-th lower diagonal of P
    idx = jnp.arange(C)
    diff = idx[:, None] - idx[None, :]
    dg = jnp.zeros((BH, C), jnp.float32).at[:, jnp.clip(diff, 0, C - 1)].add(
        jnp.where(diff[None] >= 0, P, 0.0))
    ddec = jnp.stack([ddre, ddim], axis=1)
    return op_vjp((dg, dA, dB, dpre, dpim, ddec))


def _bwd(chunk, reverse, interpret, block_d, param_grads, res, dz):
    x, log_mag, theta, u_re, u_im = res
    # dx: anti-causal pass of the same LTI filter over dz (kernel path)
    dx, _, _ = _run_kernel(dz.astype(jnp.float32), log_mag, theta, u_re, u_im,
                           chunk, not reverse, interpret, block_d)
    dx = dx.astype(x.dtype)
    if param_grads == "recompute":
        # legacy per-node jnp recompute (kept as the benchmark baseline)
        def param_path(lm, th, ur, ui):
            return _ref_chunked(jax.lax.stop_gradient(x), lm, th, ur, ui,
                                chunk, reverse)

        _, vjp = jax.vjp(param_path, log_mag, theta, u_re, u_im)
        dlm, dth, dur, dui = vjp(dz.astype(jnp.float32))
    else:
        dlm, dth, dur, dui = _analytic_param_grads(
            x, dz, log_mag, theta, u_re, u_im, chunk, reverse)
    return dx, dlm, dth, dur, dui


_stlt_scan.defvjp(_fwd, _bwd)


def stlt_scan(
    x: jax.Array,          # [BH, N, d]
    log_mag: jax.Array,    # [BH, S]
    theta: jax.Array,
    u_re: jax.Array,
    u_im: jax.Array,
    *,
    chunk: int = 128,
    reverse: bool = False,
    interpret: Optional[bool] = None,
    block_d: int = 128,
    use_kernel: Optional[bool] = None,
    h0_re: Optional[jax.Array] = None,   # [BH, S, d] initial carry
    h0_im: Optional[jax.Array] = None,
    valid: Optional[jax.Array] = None,   # [BH] per-row valid length
    return_state: bool = False,
    param_grads: str = "analytic",       # analytic | recompute
):
    """Fused factorized STLT: z = Re(sum_k u_k * scan(lambda_k, x)).

    Dispatch: Pallas kernel on TPU (or interpret=True for CPU validation);
    jnp chunked scan otherwise.

    Carry I/O: with ``h0_re/h0_im`` the scan resumes from that state;
    ``return_state=True`` additionally returns ``(h_re, h_im)`` — the state
    after ``valid[row]`` tokens (default: all N) — computed in the SAME
    single pass (DESIGN.md §3). The state path is serving-only and not
    differentiated; the training path (no h0/valid/state) runs the custom
    VJP whose parameter grads are analytic by default
    (``param_grads="recompute"`` keeps the legacy per-node jnp recompute as
    a benchmark baseline).
    """
    assert (valid is None and h0_re is None) or not reverse, \
        "carry resume / per-row valid snapshots are forward-only " \
        "(decoders are causal; DESIGN.md §3)"
    on_tpu = jax.default_backend() == "tpu"
    if use_kernel is None:
        use_kernel = on_tpu or bool(interpret)
    stateful = return_state or h0_re is not None or valid is not None
    if not use_kernel:
        return _ref_chunked(x, log_mag, theta, u_re, u_im, chunk, reverse,
                            h0_re=h0_re, h0_im=h0_im, valid=valid,
                            return_state=return_state)
    interp = (not on_tpu) if interpret is None else interpret
    if stateful:
        z, h_re, h_im = _run_kernel(x, log_mag, theta, u_re, u_im, chunk,
                                    reverse, interp, block_d,
                                    h0_re=h0_re, h0_im=h0_im, valid=valid)
        if return_state:
            return z, (h_re, h_im)
        return z
    return _stlt_scan(x, log_mag, theta, u_re, u_im, chunk, reverse, interp,
                      block_d, param_grads)
