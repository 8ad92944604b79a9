"""Pallas TPU kernel for the fused factorized STLT scan — carry-native.

Math (DESIGN.md §3): for chunk c with inputs X_c [C, d] and complex carry
h [S, d],

    z_c      = M @ X_c + A @ h_re + B @ h_im          (fused node readout)
    h_re'    = Pre @ X_c + dec_re*h_re - dec_im*h_im  (carry update)
    h_im'    = Pim @ X_c + dec_re*h_im + dec_im*h_re

where every operator is a tiny, N-independent function of the poles
(precomputed on host by ops.py):

    M[i,j]  = sum_k Re(u_k lambda_k^(i-j))   for i>=j   (lower-tri Toeplitz —
              the node sum collapses the S complex Toeplitz matmuls into ONE
              real C x C matmul; this is the key MXU trick)
    A[i,k]  =  Re(u_k lambda_k^(i+1)),  B[i,k] = -Im(u_k lambda_k^(i+1))
    Pre/Pim[k,j] = Re/Im(lambda_k^(C-1-j))
    dec = lambda^C

Carry I/O (DESIGN.md §3): the kernel is STATE-NATIVE. It seeds the VMEM
carry from an initial state ``(h0_re, h0_im)`` [BH, S, d] and emits a final
carry alongside ``z`` — so a resumed serving prefill chunk is exactly ONE
kernel dispatch (no linearity-folded free-response / closed-form passes).
The emitted carry is a per-row SNAPSHOT at token index ``valid[row]``
(defaults to N): the host precomputes in-chunk snapshot operators

    Spre/Spim[k,j] = Re/Im(lambda_k^(r-1-j)) for j < r, else 0
    sdec           = lambda^r,   r = in-chunk offset of valid[row]
    snap[row]      = the chunk that contains valid[row] (-1: never)

and the kernel evaluates ``h_valid = S @ X_c + sdec * h_chunk_start`` in the
ONE chunk with ``c == snap[row]`` — this is how padded tail chunks
(two-shape serving) leave the carry exactly where the unpadded chunk would,
without a second pass.
Rows with ``valid == 0`` return ``h0`` (written at c == 0, snap == -1).
``snap`` is a scalar-prefetch operand: it sits in SMEM before the grid runs,
so it costs one int32 per row whatever N is.

Grid: (BH, d/bd, N/C) with the chunk axis sequential ("arbitrary"), a VMEM
scratch pair for the running carry, and the carry outputs as revisited
(1, S, bd) blocks. All matmul shapes are multiples of the 128 MXU tile when
C = bd = 128. HBM traffic is x-in + z-out + the O(S*d) carry I/O per row —
the O(N*S*d) Laplace coefficients never leave VMEM, preserving the paper's
O(S*d) memory claim on-chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(snap_ref, x_ref, m_ref, a_ref, b_ref, pre_ref, pim_ref, dec_ref,
            h0re_ref, h0im_ref, spre_ref, spim_ref, sdec_ref,
            z_ref, hre_ref, him_ref, cre_ref, cim_ref):
    """One (row, d-block, chunk) grid step. cre/cim: running-carry scratch;
    hre/him: the snapshot carry output (a revisited block, written in the
    chunk ``snap[row]`` — or h0 at c == 0 for valid == 0 rows)."""
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        cre_ref[...] = h0re_ref[0]
        cim_ref[...] = h0im_ref[0]
        # valid == 0 rows: the state after 0 tokens is h0 (gate never fires)
        hre_ref[0] = h0re_ref[0]
        him_ref[0] = h0im_ref[0]

    x = x_ref[0]          # [C, bd]
    h_re = cre_ref[...]   # [S, bd]  carry at chunk START
    h_im = cim_ref[...]
    m = m_ref[0]          # [C, C]
    a = a_ref[0]          # [C, S]
    b = b_ref[0]
    pre = pre_ref[0]      # [S, C]
    pim = pim_ref[0]
    dec_re = dec_ref[0, 0, :]  # [S]
    dec_im = dec_ref[0, 1, :]

    z = jnp.dot(m, x, preferred_element_type=jnp.float32)
    z += jnp.dot(a, h_re, preferred_element_type=jnp.float32)
    z += jnp.dot(b, h_im, preferred_element_type=jnp.float32)
    z_ref[0] = z.astype(z_ref.dtype)

    # Carry snapshot at this row's valid position (one chunk per row fires).
    @pl.when(snap_ref[pl.program_id(0)] == c)
    def _snapshot():
        spre = spre_ref[0]         # [S, C]  lambda^(r-1-j), zero for j >= r
        spim = spim_ref[0]
        s_re = sdec_ref[0, 0, :]   # [S]     lambda^r
        s_im = sdec_ref[0, 1, :]
        sx = jnp.dot(spre, x, preferred_element_type=jnp.float32)
        sy = jnp.dot(spim, x, preferred_element_type=jnp.float32)
        hre_ref[0] = sx + s_re[:, None] * h_re - s_im[:, None] * h_im
        him_ref[0] = sy + s_re[:, None] * h_im + s_im[:, None] * h_re

    px = jnp.dot(pre, x, preferred_element_type=jnp.float32)
    qx = jnp.dot(pim, x, preferred_element_type=jnp.float32)
    new_re = px + dec_re[:, None] * h_re - dec_im[:, None] * h_im
    new_im = qx + dec_re[:, None] * h_im + dec_im[:, None] * h_re
    cre_ref[...] = new_re
    cim_ref[...] = new_im


@functools.partial(
    jax.jit, static_argnames=("chunk", "block_d", "interpret")
)
def stlt_scan_kernel(snap, x, m, a, b, pre, pim, dec, h0_re, h0_im,
                     spre, spim, sdec, *, chunk: int = 128,
                     block_d: int = 128, interpret: bool = False):
    """x [BH, N, d] (N % chunk == 0, d % block_d == 0); operators per row.

    m [BH, C, C]; a,b [BH, C, S]; pre,pim,spre,spim [BH, S, C];
    dec,sdec [BH, 2, S]; h0_re/h0_im [BH, S, d]; snap [BH] int32, the
    chunk holding each row's snapshot (-1 for valid == 0: never taken).
    Returns (z [BH, N, d], h_re [BH, S, d], h_im [BH, S, d]) float32 — the
    carry outputs are the per-row snapshot states (see module docstring).
    """
    BH, N, d = x.shape
    S = pre.shape[1]
    assert N % chunk == 0 and d % block_d == 0, (N, chunk, d, block_d)
    nc, nd = N // chunk, d // block_d
    assert snap.shape == (BH,), (snap.shape, BH)

    def op(shape):  # per-row operator block, fixed across d-blocks/chunks
        return pl.BlockSpec((1,) + shape, lambda bh, db, c, s: (bh, 0, 0))

    def cols(shape):  # per-row block that walks the d-blocks
        return pl.BlockSpec((1,) + shape, lambda bh, db, c, s: (bh, 0, db))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(BH, nd, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, block_d),
                         lambda bh, db, c, s: (bh, c, db)),
            op((chunk, chunk)),
            op((chunk, S)), op((chunk, S)),
            op((S, chunk)), op((S, chunk)),
            op((2, S)),
            cols((S, block_d)), cols((S, block_d)),
            op((S, chunk)), op((S, chunk)),
            op((2, S)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_d),
                         lambda bh, db, c, s: (bh, c, db)),
            cols((S, block_d)), cols((S, block_d)),
        ],
        scratch_shapes=[pltpu.VMEM((S, block_d), jnp.float32),
                        pltpu.VMEM((S, block_d), jnp.float32)],
    )
    z, h_re, h_im = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((BH, N, d), jnp.float32),
            jax.ShapeDtypeStruct((BH, S, d), jnp.float32),
            jax.ShapeDtypeStruct((BH, S, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(snap, x, m, a, b, pre, pim, dec, h0_re, h0_im, spre, spim, sdec)
    return z, h_re, h_im
