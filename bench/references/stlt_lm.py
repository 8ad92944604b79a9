"""Plain reference of the STLT language model, in jax.numpy, for the check
that decides ``correct``.

It follows the paper's model as the configurations state it and imports
nothing of the program: embedding, absolute sinusoidal encoding, then per
block ``x + STLT(norm1(x))`` and ``x + FFN(norm2(x))``, a final norm and
the tied head. The STLT mixer is written directly from its definition,

    h_k[t] = lambda_k h_k[t-1] + v[t],   z[t] = Re(sum_k m_k u_k h_k[t]),

as one causal convolution per head: z[p] = sum_{j<=p} K[p, j] v[j] with
K[p, j] = sum_k m_k(p) Re(u_k lambda_k^(p-j)), computed in row blocks over
the whole prefix (no truncation, no chunked scan, no carried state).

Adaptive node masks follow the serve-time contract (DESIGN.md, Serving): a
prompt chunk pools the normed layer input over everything up to the chunk's
end, and each decoded token pools the prefix up to itself. In training a
sequence pools over itself and draws logistic noise, as the train step does.

Precision: ``EXACT`` computes in float32 with every matmul at "highest".
The controls compute the same model one precision step lower: ``HIGH``
(three-pass matmuls) for float32 at "highest", ``BF16`` (activations stored
and multiplied in bfloat16) for other float32, ``FP8`` (matmul operands
rounded to float8_e4m3 with a per-tensor scale) for bfloat16.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8_MAX = 224.0


@dataclasses.dataclass(frozen=True)
class Precision:
    name: str
    dtype: object            # activations
    matmul: object           # lax precision of every matmul
    fp8: bool = False        # round matmul operands to float8_e4m3
    three_pass: bool = False  # bf16 hi/lo split, three products (as "high")
    round_store: bool = False  # round every stored activation explicitly


EXACT = Precision("float32-highest", jnp.float32, HI)
HIGH = Precision("float32-high", jnp.float32, jax.lax.Precision.DEFAULT,
                 three_pass=True)
BF16 = Precision("bfloat16", jnp.bfloat16, jax.lax.Precision.DEFAULT,
                 round_store=True)
FP8 = Precision("fp8-e4m3", jnp.bfloat16, jax.lax.Precision.DEFAULT, fp8=True)


def control_for(dtype: str, matmul_precision: str = "default") -> Precision:
    """The control: one precision step below what the configuration states:
    three-pass "high" for float32 at "highest", bfloat16 for other float32,
    fp8 for bfloat16."""
    if dtype == "float32":
        return HIGH if matmul_precision == "highest" else BF16
    return {"bfloat16": FP8}[dtype]


def _round(x, exponent_bits: int, mantissa_bits: int):
    """Round to a narrower float explicitly: a dtype round trip may be
    skipped by XLA, which lets fused values keep excess precision."""
    return jax.lax.reduce_precision(x.astype(jnp.float32), exponent_bits,
                                    mantissa_bits)


def _cast(x, prec: Precision):
    """Store ``x`` in the precision's activation dtype."""
    if prec.round_store:
        x = _round(x, 8, 7)
    return x.astype(prec.dtype)


def _q(x, prec: Precision):
    if not prec.fp8:
        return _cast(x, prec)
    # e4m3 with a per-tensor scale (amax to 224, inside e4m3's 240)
    s = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32))), 1e-30) / F8_MAX
    return (_round(x / s, 4, 3) * s).astype(prec.dtype)


def _mm(eq, a, b, prec: Precision):
    if prec.three_pass:
        # the TPU's "high": each f32 operand as a bf16 high part plus a bf16
        # remainder, and every product but lo x lo; written out, so that it
        # computes the same on any backend
        def split(x):
            x = x.astype(jnp.float32)
            hi = _round(x, 8, 7)
            return hi, _round(x - hi, 8, 7)

        (ah, al), (bh, bl) = split(a), split(b)
        mm = partial(jnp.einsum, eq, precision=HI,
                     preferred_element_type=jnp.float32)
        return (mm(ah, bh) + mm(ah, bl) + mm(al, bh)).astype(prec.dtype)
    return _cast(jnp.einsum(eq, _q(a, prec), _q(b, prec), precision=prec.matmul,
                            preferred_element_type=jnp.float32), prec)


# ---------------------------------------------------------------- layers
def _norm(kind, p, x, prec):
    x = x.astype(jnp.float32)
    if kind == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        y = (x - mu) / jnp.sqrt(var + 1e-5) * p["scale"] + p["bias"]
    else:
        y = x / jnp.sqrt((x * x).mean(-1, keepdims=True) + 1e-6) * p["scale"]
    return _cast(y, prec)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _ffn(act, p, x, prec, rows: int = 2048):
    """The FFN, over blocks of ``rows`` rows where x is long and 2-D."""
    if x.ndim == 2 and x.shape[0] > rows and x.shape[0] % rows == 0:
        y = jax.lax.map(lambda xb: _ffn(act, p, xb, prec, rows),
                        x.reshape(-1, rows, x.shape[-1]))
        return y.reshape(x.shape[0], -1)
    if act == "swiglu":
        a = _mm("...d,df->...f", x, p["w1"], prec).astype(jnp.float32)
        b = _mm("...d,df->...f", x, p["w3"], prec).astype(jnp.float32)
        h = _cast(a / (1.0 + jnp.exp(-a)) * b, prec)
        return _mm("...f,fd->...d", h, p["w2"], prec)
    a = _mm("...d,df->...f", x, p["w1"], prec).astype(jnp.float32) + p["b1"]
    h = _cast(_gelu_tanh(a), prec)
    return _cast(_mm("...f,fd->...d", h, p["w2"], prec).astype(jnp.float32)
                 + p["b2"], prec)


@partial(jax.jit, static_argnums=(0, 1))
def sinusoid(n: int, d: int):
    """The absolute encoding in float32, as the float32 model computes it:
    pe[p, 2i] = sin(p / 10000^(2i/d)), pe[p, 2i+1] = cos(...). (The angle's
    float32 rounding grows with p; past a few thousand positions it is the
    largest difference between exact arithmetic and any float32 run.)"""
    pos = jnp.arange(n)[:, None]
    dim = jnp.arange(0, d, 2)[None, :]
    ang = pos / jnp.power(10000.0, dim / d)
    pe = jnp.zeros((n, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(ang))
    return pe.at[:, 1::2].set(jnp.cos(ang[:, : d // 2]))


def _node_terms(nodes, n: int):
    """A[tau, h, k] = Re(u_k lambda_k^tau) for tau in [0, n), float32."""
    sigma = 1e-4 + jax.nn.softplus(nodes["sigma_hat"])
    T = 1.0 + jax.nn.softplus(nodes["T_hat"])
    log_mag = -(sigma + 1.0 / T[:, None])               # [H, S]
    theta = -nodes["omega"]
    tau = jnp.arange(n, dtype=jnp.float32)[:, None, None]
    mag = jnp.exp(tau * log_mag)
    return mag * (nodes["u_re"] * jnp.cos(tau * theta)
                  - nodes["u_im"] * jnp.sin(tau * theta))  # [n, H, S]


def _toeplitz(kern, v, rows: int, prec):
    """z[p] = sum_{j<=p} kern[p - j] * v[j] per head: kern [n, H] (lags),
    v [n, H, dh]. The lower-triangular Toeplitz matrix is applied block by
    block: the [rows, rows] block at block distance k is the same for every
    pair of blocks k apart, so one scan step per k applies it to all of v,
    shifted by k blocks."""
    n, H, dh = v.shape
    nb = n // rows
    i = jnp.arange(rows)[:, None]
    j = jnp.arange(rows)[None, :]
    vb = _cast(v.reshape(nb, rows, H, dh), prec)
    vpad = jnp.concatenate([jnp.zeros_like(vb), vb])

    @jax.checkpoint
    def step(z, k):
        lag = k * rows + i - j
        T = jnp.where((lag >= 0)[..., None], kern[jnp.clip(lag, 0, n - 1)], 0.0)
        vs = jax.lax.dynamic_slice_in_dim(vpad, nb - k, nb, 0)
        return z + _mm("ijh,bjhd->bihd", _cast(T, prec), vs,
                       prec).astype(jnp.float32), None

    z, _ = jax.lax.scan(step, jnp.zeros((nb, rows, H, dh), jnp.float32),
                        jnp.arange(nb))
    return z.reshape(n, H, dh)


def _causal_mix(v, A, masks, rows: int, prec):
    """z[p, h] = sum_{j<=p} K[p, j, h] v[j, h] for one sequence, with
    K[p, j] = sum_k m_k(p) A[p - j, k].

    v [n, H, dh]; A [n, H, S]; masks None, [H, S] (one per sequence) or
    [n, H, S] (one per position). With one mask per sequence K is a
    Toeplitz matrix of the node sum; with one per position, each node's
    Toeplitz product is weighted by that position's mask.
    """
    n = v.shape[0]
    rows = min(rows, n)
    if masks is None or masks.ndim == 2:
        g = A.sum(-1) if masks is None else (A * masks[None]).sum(-1)
        return _toeplitz(g, v, rows, prec)

    def node(z, s):
        y = _toeplitz(A[:, :, s], v, rows, prec)
        return z + masks[:, :, s, None] * y, None

    z, _ = jax.lax.scan(node, jnp.zeros(v.shape, jnp.float32),
                        jnp.arange(A.shape[-1]))
    return z


def _stlt(p, h, masks, heads: int, rows: int, prec):
    n, d = h.shape
    v = _mm("nd,de->ne", h, p["w_v"], prec).reshape(n, heads, d // heads)
    A = _node_terms(p["nodes"], n)
    z = _causal_mix(v, A, masks, rows, prec).reshape(n, d)
    return _mm("nd,de->ne", z, p["w_o"], prec)


def _adaptive(p, pooled):
    logits = jnp.einsum("...d,dhk->...hk", pooled, p["w_alpha"],
                        precision=HI) + p["b_alpha"]
    return logits


def stack_layers(params, num_layers: int):
    """One [num_layers, ...] tree from the program's layer groups."""
    if len(params["layers"]) == 1 and num_layers > 1:
        return params["layers"][0]
    groups = []
    for g in params["layers"]:
        lead = jax.tree_util.tree_leaves(g["norm1"])[0]
        stacked = lead.ndim == 2
        groups.append(g if stacked else jax.tree_util.tree_map(
            lambda a: a[None], g))
    out = jax.tree_util.tree_map(lambda *a: jnp.concatenate(a, 0), *groups)
    assert jax.tree_util.tree_leaves(out["norm1"])[0].shape[0] == num_layers
    return out


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


# ----------------------------------------------------------------- serving
@partial(jax.jit, static_argnames=("m", "n", "n_read", "rows", "prec"))
def served_logits(W, tokens, pe, prompt_len, read_start, *, m, n, n_read,
                  rows, prec):
    """Logits at positions [read_start, read_start + n_read) of one
    sequence of ``n`` tokens (prompt, then the served tokens), under the
    serve-time adaptive-mask contract. ``m`` is the configuration's
    ``model`` dict as a tuple of items."""
    m = dict(m)
    heads, d = m["num_heads"], m["d_model"]
    chunk = m["prefill_chunk"]
    x = W["embed"]["embed"][tokens].astype(jnp.float32)
    x = _cast(x + pe, prec)
    pos = jnp.arange(n)
    # pooled prefix end for each position: the prompt chunk's end inside
    # the prompt, the position itself after it
    end = jnp.where(pos < prompt_len,
                    jnp.minimum(prompt_len, (pos // chunk + 1) * chunk),
                    pos + 1)

    def layer(x, lw):
        lw = _f32(lw)
        h = _norm(m["norm"], lw["norm1"], x, prec)
        masks = None
        if m["stlt_adaptive"]:
            cs = jnp.concatenate([jnp.zeros((1, d), jnp.float32),
                                  jnp.cumsum(h.astype(jnp.float32), 0)])
            pooled = cs[end] / end[:, None].astype(jnp.float32)
            masks = jax.nn.sigmoid(_adaptive(lw["stlt"]["adaptive"], pooled))
        x = x + _stlt(lw["stlt"], h, masks, heads, rows, prec)
        x = x + _ffn(m["act"], lw["ffn"], _norm(m["norm"], lw["norm2"], x,
                                                prec), prec)
        return x, None

    x, _ = jax.lax.scan(layer, x, W["layers"])
    xr = jax.lax.dynamic_slice_in_dim(x, read_start, n_read, 0)
    xr = _norm(m["norm"], _f32(W["final_norm"]), xr, prec)
    emb = W["embed"]["embed"].astype(jnp.float32)
    return _mm("nd,vd->nv", xr, emb, prec).astype(jnp.float32)


def bucket(n: int) -> int:
    """Sequence lengths the reference compiles for: multiples of 1024 up to
    4096, of 4096 beyond, so a few programs cover a cell's whole mix."""
    step = 1024 if n <= 4096 else 4096
    return -(-n // step) * step


def serve_readings(W, m: dict, requests, n_read: int,
                   low: Optional[Precision] = None) -> dict:
    """The serve check's readings over ``requests``: (prompt, served
    tokens, [(output position, logits row the program produced)]).

    ``served_logit_error`` is the number compared: the largest amount, in
    units of the reference row's standard deviation, by which the served
    path departs from the reference, as the larger of

    * ``token_gap``: how far a served token's logit lies below the
      reference's best, at every served position (the contract's widest
      gap, divided by the row's spread so that configurations compare), and
    * ``logit_error``: |program logit - reference logit| at the rows the
      program produced (its first-token logits and sampled decode steps).

    With ``low`` the control's readings are added under ``control``: the
    reference in that precision read at the same positions (the token it
    puts first, and its logits at the program's rows).
    ``n_read`` (a multiple of 128, at least the longest served output) is
    the fixed number of positions read per request.
    """
    Wx = {"embed": W["embed"], "final_norm": W["final_norm"],
          "layers": stack_layers(W, m["num_layers"])}
    items = tuple(sorted(m.items()))
    zero = {"token_gap": 0.0, "logit_error": 0.0, "token_gap_logits": 0.0}
    out, ctl = dict(zero), dict(zero)

    def read(acc, ref, sd, pick, rows, pos):
        best = ref.max(-1)
        got = jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
        acc["token_gap_logits"] = max(acc["token_gap_logits"],
                                      float(jnp.max(best - got)))
        acc["token_gap"] = max(acc["token_gap"], float(jnp.max((best - got) / sd)))
        if len(pos):
            p = jnp.asarray(pos)
            d = jnp.abs(jnp.asarray(rows) - ref[p]).max(-1) / sd[p]
            acc["logit_error"] = max(acc["logit_error"], float(d.max()))

    for prompt, served, rows in requests:
        prompt = np.asarray(prompt, np.int32)
        served = np.asarray(served, np.int32)
        assert len(served) <= n_read
        n = bucket(len(prompt) - 1 + n_read)
        toks = np.zeros(n, np.int32)
        seq = np.concatenate([prompt, served[:-1]])
        toks[:len(seq)] = seq
        args = (jnp.asarray(toks), sinusoid(n, m["d_model"]),
                len(prompt), len(prompt) - 1)
        kw = dict(m=items, n=n, n_read=n_read, rows=128)
        ref = served_logits(Wx, *args, prec=EXACT, **kw)[:len(served)]
        sd = ref.std(-1)
        pos = [p for p, _ in rows]
        read(out, ref, sd, jnp.asarray(served), np.stack([r for _, r in rows])
             if rows else None, pos)
        if low is not None:
            lo = served_logits(Wx, *args, prec=low, **kw)[:len(served)]
            read(ctl, ref, sd, jnp.argmax(lo, -1),
                 lo[jnp.asarray(pos)] if pos else None, pos)
            del lo
        del ref
    for acc in (out, ctl):
        acc["served_logit_error"] = max(acc["token_gap"], acc["logit_error"])
    if low is not None:
        out["control"] = {**ctl, "precision": low.name}
    return out


# ---------------------------------------------------------------- training
def _train_loss(W, batch, pe, step, *, m, tcfg, rows, prec):
    heads, d = m["num_heads"], m["d_model"]
    inputs, labels = batch["inputs"], batch["labels"]
    B, n = inputs.shape
    tau = tcfg["tau_start"] + (tcfg["tau_end"] - tcfg["tau_start"]) * jnp.clip(
        step / max(1, int(tcfg["total_steps"] * tcfg["tau_frac"])), 0.0, 1.0)
    rng = jax.random.fold_in(jax.random.key(tcfg["seed"]), step)
    keys = jax.random.split(jax.random.fold_in(rng, 0), m["num_layers"])
    x = _cast(W["embed"]["embed"][inputs] + pe, prec)

    def seq_mix(lw, h, masks):
        return jax.vmap(lambda hh, mm: _stlt(lw["stlt"], hh, mm, heads, rows,
                                             prec))(h, masks)

    @jax.checkpoint
    def layer(x, scanned):
        lw, key = scanned
        h = _norm(m["norm"], lw["norm1"], x, prec)
        nodes = lw["stlt"]["nodes"]
        logits = _adaptive(lw["stlt"]["adaptive"], h.mean(1))       # [B,H,S]
        u = jax.random.uniform(key, logits.shape, minval=1e-6,
                               maxval=1.0 - 1e-6)
        masks = jax.nn.sigmoid((logits + jnp.log(u) - jnp.log1p(-u)) / tau)
        x = x + seq_mix(lw, h, masks)
        x = x + _ffn(m["act"], lw["ffn"], _norm(m["norm"], lw["norm2"], x,
                                                prec), prec)
        # the (Reg) loss: omega sparsity, smoothness of sorted sigma, and
        # the node-count penalty, on the batch-mean mask
        sigma = 1e-4 + jax.nn.softplus(nodes["sigma_hat"])
        mm = masks.mean(0)
        order = jnp.argsort(jax.lax.stop_gradient(sigma), axis=-1)
        s_sorted = jnp.take_along_axis(sigma, order, -1)
        m_sorted = jnp.take_along_axis(mm, order, -1)
        reg = (tcfg["lambda_omega"] * jnp.sum(jnp.abs(nodes["omega"]) * mm)
               + tcfg["lambda_sigma"] * jnp.sum(jnp.diff(s_sorted, axis=-1) ** 2
                                                * m_sorted[:, 1:]
                                                * m_sorted[:, :-1])
               + tcfg["lambda_mask"] * jnp.sum(mm))
        return x, reg

    x, regs = jax.lax.scan(layer, x, (W["layers"], keys))
    xf = _norm(m["norm"], W["final_norm"], x, prec).reshape(B * n, d)
    lab = labels.reshape(B * n)
    blk = min(1024, B * n)

    @jax.checkpoint
    def ce_block(i):
        xb = jax.lax.dynamic_slice_in_dim(xf, i * blk, blk, 0)
        lb = jax.lax.dynamic_slice_in_dim(lab, i * blk, blk, 0)
        lg = _mm("nd,vd->nv", xb, W["embed"]["embed"], prec).astype(jnp.float32)
        return (jax.nn.logsumexp(lg, -1)
                - jnp.take_along_axis(lg, lb[:, None], -1)[:, 0]).sum()

    ce = jax.lax.map(ce_block, jnp.arange(B * n // blk)).sum() / (B * n)
    return ce + regs.sum()


def _decayed(path: str, leaf) -> bool:
    """Decoupled weight decay applies to matrices: not to norms, biases,
    Laplace node parameters or the adaptive-mask bias. ``leaf`` carries the
    layer-stack dim, so a layer's matrix has three or more dims."""
    if any(k in path for k in ("norm", "nodes", "b_alpha")):
        return False
    if path.startswith("layers"):
        return leaf.ndim >= 3
    return leaf.ndim >= 2


def train_steps(W, m: dict, tcfg: dict, batches, rows: int = 512,
                prec: Precision = EXACT):
    """Run ``len(batches)`` AdamW steps of the plain model from weights W.

    Returns the losses, the per-leaf norms of the first clipped gradient,
    and the per-leaf norms of the parameters' change over all steps, each
    as {leaf path: value}.
    """
    W = _f32({"embed": W["embed"], "final_norm": W["final_norm"],
              "layers": stack_layers(W, m["num_layers"])})
    loss_fn = partial(_train_loss, m=m, tcfg=tcfg, rows=rows, prec=prec)
    grad_fn = jax.jit(jax.value_and_grad(loss_fn))
    flat, treedef = jax.tree_util.tree_flatten_with_path(W)
    paths = ["/".join(str(getattr(k, "key", k)) for k in p) for p, _ in flat]
    wd = jax.tree_util.tree_unflatten(
        treedef, [float(_decayed(pa, a)) for pa, (_, a) in zip(paths, flat)])
    b1, b2, eps, lam = tcfg["b1"], tcfg["b2"], tcfg["eps"], tcfg["weight_decay"]

    @jax.jit
    def update(params, grads, mu, nu, step):
        gn = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree_util.tree_leaves(grads)))
        grads = jax.tree_util.tree_map(
            lambda g: g * jnp.minimum(1.0, tcfg["grad_clip"] / jnp.maximum(gn, 1e-9)),
            grads)
        s = step.astype(jnp.float32)
        warm = max(1, tcfg["warmup_steps"])
        frac = jnp.clip((s - warm) / max(1, tcfg["total_steps"] - warm), 0.0, 1.0)
        decay = 0.1 + 0.9 * 0.5 * (1 + jnp.cos(jnp.pi * frac))
        lr = jnp.where(s < warm, tcfg["lr"] * jnp.minimum(1.0, s / warm),
                       tcfg["lr"] * decay)
        t = s + 1.0
        mu = jax.tree_util.tree_map(lambda a, g: b1 * a + (1 - b1) * g, mu, grads)
        nu = jax.tree_util.tree_map(lambda a, g: b2 * a + (1 - b2) * g * g, nu, grads)
        params = jax.tree_util.tree_map(
            lambda p, a, v, w: p - lr * ((a / (1 - b1 ** t))
                                         / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
                                         + w * lam * p),
            params, mu, nu, wd)
        return params, mu, nu, grads

    pe = sinusoid(batches[0]["inputs"].shape[1], m["d_model"])
    p0 = W
    params = W
    mu = jax.tree_util.tree_map(jnp.zeros_like, W)
    nu = jax.tree_util.tree_map(jnp.zeros_like, W)
    losses, first = [], None
    for step, batch in enumerate(batches):
        loss, grads = grad_fn(params, batch, pe, step)
        params, mu, nu, clipped = update(params, grads, mu, nu,
                                         jnp.asarray(step, jnp.int32))
        losses.append(float(loss))
        if first is None:
            first = leaf_norms(clipped)
    change = leaf_norms(jax.tree_util.tree_map(lambda a, b: a - b, params, p0))
    return losses, first, change


def leaf_norms(tree) -> dict:
    """{path: float32 norm} per leaf; layer stacks are split per layer."""
    out = {}
    for p, a in jax.tree_util.tree_flatten_with_path(tree)[0]:
        path = "/".join(str(getattr(k, "key", k)) for k in p)
        a = jnp.asarray(a, jnp.float32)
        if path.startswith("layers"):
            norms = jnp.sqrt(jnp.sum(a.reshape(a.shape[0], -1) ** 2, -1))
            for i, v in enumerate(np.asarray(norms)):
                out[f"{path}#{i}"] = float(v)
        else:
            out[path] = float(jnp.sqrt(jnp.sum(a * a)))
    return out
