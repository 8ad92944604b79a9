"""Train cells: ``launch/train.make_step`` with the default ``TrainConfig``.

Set-up makes the weights from the seed on the device, builds the compiled
step and its optimizer state, and drives that same object through the first
``check.steps`` steps with the window's own call and feed (which also
compiles it). The window then continues it, step after step, each ended by
``block_until_ready`` on the loss, until ``seconds`` have passed; it closes
at the end of the step that crosses that time, and the rate is all tokens
of the window's steps over its whole length.

Batches are token ids drawn on the device from the seed and the step,
Zipf-distributed over the vocabulary (rank r with weight r^-a, id r - 1).

The check runs once the window has closed and the peak memory has been
read: the plain reference (``bench/references``) trains the same weights
on the same batches for the first steps, and three numbers are compared,
each by its limit: the largest gap of a step's loss; the worst leaf's gap
between the norms of the first clipped gradient (the program's is read from
the optimizer's first moment after one step, mu = (1 - b1) g); and the worst
leaf's gap between the norms of the parameters' change over those steps,
leaving out leaves whose reference gradient is under a thousandth of the
median leaf's. Leaf gaps are measured against the larger of the reference
leaf's norm and the median leaf's.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench import flops as flops_lib
from bench import trace_reduce
from bench import weights as weights_lib
from bench.run import log, memory_peak_bytes, metric_reader
from bench.serve_cell import Tracer, build_config


def make_batches(vocab: int, batch: int, seq: int, a: float, seed: int):
    """A jitted step -> {"inputs", "labels"} feed from the seed."""
    import jax
    import jax.numpy as jnp

    w = np.arange(1, vocab + 1, dtype=np.float64) ** -a
    cdf = jnp.asarray(np.cumsum(w) / w.sum(), jnp.float32)
    key = weights_lib.seed_key(seed)

    @jax.jit
    def feed(step):
        u = jax.random.uniform(jax.random.fold_in(key, step), (batch, seq + 1))
        ids = jnp.minimum(jnp.searchsorted(cdf, u), vocab - 1).astype(jnp.int32)
        return {"inputs": ids[:, :-1], "labels": ids[:, 1:]}

    return feed


def leaf_gap(prog: dict, ref: dict, keep=None) -> tuple[float, str]:
    """Worst leaf: |prog - ref| / max(ref, median ref), and its name."""
    names = [k for k in ref if keep is None or k in keep]
    med = float(np.median([ref[k] for k in names]))
    worst, where = 0.0, ""
    for k in names:
        g = abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
        if g > worst:
            worst, where = g, k
    return worst, where


def compare(prog, ref) -> dict:
    """The three numbers of the check, from (losses, first-gradient leaf
    norms, change leaf norms) of the program and of the reference."""
    (p_loss, p_first, p_change), (r_loss, r_first, r_change) = prog, ref
    med_g = float(np.median(list(r_first.values())))
    moving = {k for k, v in r_first.items() if v >= 1e-3 * med_g}
    grad_gap, grad_leaf = leaf_gap(p_first, r_first)
    change_gap, change_leaf = leaf_gap(p_change, r_change, moving)
    log(f"losses {p_loss} vs reference {r_loss}; worst leaves: gradient "
        f"{grad_leaf}, change {change_leaf}; {len(r_first) - len(moving)} "
        "leaves left out of the change")
    return {"loss_gap": max(abs(a - b) for a, b in zip(p_loss, r_loss)),
            "first_grad_gap": grad_gap, "change_gap": change_gap}


def run(cell, peak: dict) -> dict:
    import jax

    from bench.references import stlt_lm
    from repro.configs.base import TrainConfig
    from repro.launch.train import make_step

    tr, opt_c = cell.traffic, cell.traffic["optimizer"]
    cfg = build_config(cell.config)
    m = cell.config["model"]
    B, N, n_check = tr["batch"], tr["seq_len"], tr["check"]["steps"]
    feed = make_batches(cfg.vocab, B, N, tr["zipf_exponent"], cell.seed)
    opt, step_fn = make_step(cfg, TrainConfig())
    params = weights_lib.make(cfg, cell.seed, served=False,
                              embed_std=tr["embed_std"])
    state = (params, jax.jit(opt.init)(params))
    p0, losses, mu1 = params, [], None
    for s in range(n_check):
        params, opt_state, met = step_fn(*state, feed(s), s)
        state = (params, opt_state)
        losses.append(met["loss"])
        if s == 0:
            mu1 = opt_state["mu"]
    jax.block_until_ready(state)
    p_check = state[0]
    setup_s = time.perf_counter() - cell.t_start
    compiled = cell.compiles.total()
    log(f"set-up {setup_s:.3f} s: {cell.compiles.compiled} compiled, "
        f"{cell.compiles.cache_hits} from the cache")

    tracer = Tracer(cell.trace, cell.seconds)
    step, steps_done = n_check, 0
    t0 = time.perf_counter()
    met = batch = None
    while True:
        now = time.perf_counter()
        if now - t0 >= cell.seconds:
            break
        tracer.poll(now - t0)
        with jax.profiler.TraceAnnotation("bench.data"):
            batch = feed(step)
        with jax.profiler.TraceAnnotation("bench.train_step"):
            params, opt_state, met = step_fn(*state, batch, step)
            met["loss"].block_until_ready()
        state = (params, opt_state)
        step += 1
        steps_done += 1
    t1 = time.perf_counter()
    tracer.close()
    in_window = cell.compiles.total() - compiled
    rate = steps_done * B * N / (t1 - t0)
    log(f"window: {steps_done} steps in {t1 - t0:.3f} s, {in_window} programs "
        f"compiled or loaded inside it")
    out = {"metrics": {"setup_s": setup_s, "train_tokens_per_s": rate},
           "device": {"memory_peak_bytes": memory_peak_bytes(jax)}}
    if cell.trace:
        tr_ = trace_reduce.load(tracer.dir)
        within = trace_reduce.window(tr_)
        obs = {"trace": tr_, "window": within, "model": m, "peak": peak,
               "flops": flops_lib, "train_tokens_per_s": rate}
        out["metrics"] = {}
        for spec in cell.per_layer:
            v = metric_reader(spec["name"])(obs)
            if v is not None:
                out["metrics"][spec["name"]] = v
        out["device"].update(busy_s=trace_reduce.busy_s(tr_, within),
                             window_s=trace_reduce.length(within))
        out["breakdown"] = {"device_ops": trace_reduce.top_ops(tr_, within),
                            "idle_gaps": trace_reduce.idle_gaps(tr_, within)}
        tracer.cleanup()

    prog_loss = [float(x) for x in losses]
    b1 = opt_c["b1"]
    L = m["num_layers"]
    prog_first = {k: v / (1 - b1) for k, v in
                  stlt_lm.leaf_norms(_as_ref(mu1, L)).items()}
    prog_change = stlt_lm.leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a - b, _as_ref(p_check, L), _as_ref(p0, L)))
    del state, params, opt_state, met, batch, p_check, mu1, losses
    gc.collect()
    batches = [feed(s) for s in range(n_check)]
    W = weights_lib.make(cfg, cell.seed, served=False, embed_std=tr["embed_std"])
    del p0
    ref = stlt_lm.train_steps(W, m, opt_c, batches)
    gaps = compare((prog_loss, prog_first, prog_change), ref)
    checks, ok = {}, True
    for name, v in gaps.items():
        lim = cell.limits[name]
        checks[name] = {"value": v, "limit": lim}
        ok = ok and lim is not None and np.isfinite(v) and v <= lim
    out.update(correct=bool(ok), checks=checks, attempted=steps_done + n_check,
               failed=0)
    return out


def _as_ref(tree, num_layers: int) -> dict:
    """The program's tree in the reference's layout: one layer stack."""
    from bench.references import stlt_lm

    return {**{k: v for k, v in tree.items() if k != "layers"},
            "layers": stlt_lm.stack_layers(tree, num_layers)}
