"""Smoke run of the STLT stack on a TPU, through the entry points a user calls.

The model is the paper's own (``stlt-base``: d_model 512, 6 layers, 8 heads,
S = 64 adaptive Laplace nodes, vocab 32000) at its published widths, with
random weights made from a seed. One process; nothing is read from outside
the checkout.

  python chip_smoke.py               # one chip: device, serve, kernels, train
  python chip_smoke.py --four-chips  # four chips: sharded serve vs one chip

Phases (one chip):
  device   the first device must be a TPU; there is no CPU fallback.
  serve    ``ServeEngine.serve`` (continuous, greedy) on 8 requests of
           256-1024 prompt tokens, 4 slots, 16 new tokens each; every
           request must equal ``ServeEngine.generate`` token for token.
  kernels  the model with ``stlt_engine="pallas"``: prefill and decode
           logits against the default engine, and ``relevance_flash`` at
           BH=16, N=4096, dh=64, S=64 against its jnp reference; both
           kernels run compiled (``interpret=False``).
  train    three steps of ``repro.launch.train.main`` (batch 8, seq 1024),
           then one step with the pallas engine (the kernel's custom VJP).

Parity checks run under ``jax.default_matmul_precision("highest")``: the
model is float32, and greedy argmax parity between two evaluation orders
should test the program, not bf16 rounding of the operands.

Any failure exits non-zero. The last line of standard output is one JSON
object, ``{"ok": true, "device": {...}}``. Times printed on the way are
smoke figures from one cold run, compiles included, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SEED = 0
ARCH = "stlt-base"
PROMPT_LENS = (256, 448, 777, 1024)   # two requests each
MAX_NEW = 16
SLOTS = 4
MAX_LEN = 2048
# Admission chunk: every prompt goes in as ONE chunk. stlt-base has adaptive
# node masks, and admitting an adaptive prompt in several chunks is a
# documented approximation (DESIGN.md, serving), not token-exact.
PREFILL_CHUNK = 1024
DECODE_STEPS = 4
TOL = dict(rtol=2e-3, atol=2e-3)      # kernel vs reference, float32
REL_SHAPE = dict(BH=16, N=4096, dh=64, S=64)
TRAIN_BATCH, TRAIN_SEQ = 8, 1024


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def device_phase(jax):
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"[smoke] no TPU: JAX found {len(devs)} "
                         f"{d.platform} device(s)")
    log(f"device: {d.device_kind} x{len(devs)}, jax {jax.__version__}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def cache_events(jax):
    """Count persistent-cache hits and misses as JAX reports them."""
    counts = {"hits": 0, "misses": 0}
    names = {"/jax/compilation_cache/cache_hits": "hits",
             "/jax/compilation_cache/cache_misses": "misses"}

    def on_event(event, **kwargs):
        if event in names:
            counts[names[event]] += 1

    jax.monitoring.register_event_listener(on_event)
    return counts


def build_model(jax):
    from repro import configs as configs_lib
    from repro.models import transformer as T
    from repro.utils import cast_params_for_compute, tree_size

    cfg = configs_lib.get_config(ARCH)
    params = cast_params_for_compute(T.init_lm(jax.random.key(SEED), cfg),
                                     cfg.act_dtype)
    log(f"model: {cfg.name}, {tree_size(params) / 1e6:.1f}M params, "
        f"d_model {cfg.d_model}, {cfg.num_layers} layers, S={cfg.stlt_nodes}")
    return cfg, params


def make_requests(cfg):
    from repro.serving.engine import Request

    rng = np.random.default_rng(SEED)
    lens = rng.permutation(np.repeat(PROMPT_LENS, 2))
    return [Request(rng.integers(3, cfg.vocab, int(n)).astype(np.int32),
                    MAX_NEW, id=i) for i, n in enumerate(lens)]


def check_tokens(got: dict, want: dict, what: str) -> None:
    for rid in sorted(want):
        if not np.array_equal(got[rid], want[rid]):
            raise AssertionError(f"{what}: request {rid} differs:\n"
                                 f"  got  {got[rid].tolist()}\n"
                                 f"  want {want[rid].tolist()}")


def serve_phase(jax, cfg, params, reqs):
    from repro.serving import ServeEngine

    eng = ServeEngine(params, cfg, max_len=MAX_LEN,
                      prefill_chunk=PREFILL_CHUNK)
    with jax.default_matmul_precision("highest"):
        t0 = time.perf_counter()
        out = eng.serve(reqs, slots=SLOTS, mode="continuous")
        dt = time.perf_counter() - t0
        want = {}
        for n in PROMPT_LENS:
            group = [r for r in reqs if len(r.prompt) == n]
            gen = eng.generate(np.stack([r.prompt for r in group]), MAX_NEW)
            want.update({r.id: row for r, row in zip(group, gen)})
    n_tok = sum(len(v) for v in out.values())
    log(f"serve: {len(reqs)} requests, {n_tok} tokens in {dt:.2f} s "
        f"({n_tok / dt:.1f} tok/s; smoke figure, cold, compiles included)")
    check_tokens(out, want, "serve vs generate")
    log(f"serve == generate for all {len(reqs)} requests")
    return out


def _kernel_compiled(jitted, *args, **kwargs):
    """Compile ``jitted`` for these arguments; fail unless it holds a Pallas
    kernel (a ``tpu_custom_call``)."""
    compiled = jitted.lower(*args, **kwargs).compile()
    if "tpu_custom_call" not in compiled.as_text():
        raise AssertionError("compiled program holds no Pallas kernel")
    return compiled


def _assert_close(name, got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if not np.isfinite(got).all():
        raise AssertionError(f"{name}: non-finite output")
    err = float(np.max(np.abs(got - want)))
    log(f"{name}: max |diff| {err:.3e} (tolerance rtol={TOL['rtol']}, "
        f"atol={TOL['atol']})")
    np.testing.assert_allclose(got, want, err_msg=name, **TOL)


def kernel_phase(jax, cfg, params, reqs):
    import jax.numpy as jnp

    from repro.kernels import relevance_flash as rf
    from repro.models import transformer as T

    prompts = jnp.asarray(np.stack(
        [r.prompt for r in reqs if len(r.prompt) == max(PROMPT_LENS)]))

    def prefill_decode(c, tokens, require_kernel):
        pre = jax.jit(functools.partial(T.prefill, cfg=c, max_len=MAX_LEN))
        if require_kernel:
            pre = _kernel_compiled(pre, params, inputs=prompts)
        logits, state = pre(params, inputs=prompts)
        step = jax.jit(functools.partial(T.decode_step, cfg=c))
        outs = [logits]
        for i in range(DECODE_STEPS):
            tok = jnp.argmax(logits, -1).astype(jnp.int32) \
                if tokens is None else tokens[i]
            logits, state = step(params, token_t=tok, state=state)
            outs.append(logits)
        return outs

    with jax.default_matmul_precision("highest"):
        ref = prefill_decode(cfg, None, False)
        fed = [jnp.argmax(x, -1).astype(jnp.int32) for x in ref[:-1]]
        cfg_k = dataclasses.replace(cfg, stlt_engine="pallas")
        got = prefill_decode(cfg_k, fed, True)
    for i, (g, r) in enumerate(zip(got, ref)):
        _assert_close("pallas prefill logits" if i == 0
                      else f"pallas decode step {i} logits", g, r)

    BH, N, dh, S = (REL_SHAPE[k] for k in ("BH", "N", "dh", "S"))
    rng = np.random.default_rng(SEED)
    x = jnp.asarray(rng.normal(size=(BH, N, dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(BH, N, dh)), jnp.float32)
    lm = jnp.asarray(-rng.uniform(0.005, 1.0, (BH, S)), jnp.float32)
    th = jnp.asarray(-rng.uniform(0, 1.5, (BH, S)), jnp.float32)
    # the kernel's dots take the precision of the context they are traced
    # in, like the reference's: at the default they round to bf16
    with jax.default_matmul_precision("highest"):
        flash = _kernel_compiled(jax.jit(functools.partial(
            rf.relevance_flash, causal=True, use_kernel=True,
            interpret=False)), x, v, lm, th)
        t0 = time.perf_counter()
        z = jax.block_until_ready(flash(x, v, lm, th))
        dt = time.perf_counter() - t0
        z_ref = jax.jit(functools.partial(
            rf.relevance_flash_reference, tile=128, causal=True))(
                x, v, lm, th, jnp.ones((BH, S), jnp.float32), None)
    log(f"relevance_flash BH={BH} N={N} dh={dh} S={S} causal: {dt:.3f} s "
        "(smoke figure, one call)")
    _assert_close("relevance_flash vs reference", z, z_ref)


def train_phase(jax, cfg):
    import jax.numpy as jnp

    from repro.configs.base import TrainConfig
    from repro.data import ByteCorpus
    from repro.launch import train as train_lib
    from repro.models import transformer as T

    batch, seq = TRAIN_BATCH, TRAIN_SEQ
    _, logged = train_lib.main(["--arch", ARCH, "--steps", "3",
                                "--batch", str(batch), "--seq", str(seq),
                                "--log-every", "1"])
    if len(logged) != 3:
        raise AssertionError(f"train logged {len(logged)} steps, not 3")

    cfg_k = dataclasses.replace(cfg, stlt_engine="pallas")
    tcfg = TrainConfig(total_steps=1, warmup_steps=10)
    opt, step_fn = train_lib.make_step(cfg_k, tcfg)
    params = T.init_lm(jax.random.key(tcfg.seed), cfg_k)
    opt_state = opt.init(params)
    data = {k: jnp.asarray(v) for k, v in
            ByteCorpus().batch(0, batch, seq).items()}
    step = _kernel_compiled(step_fn, params, opt_state, data, 0)
    _, _, metrics = step(params, opt_state, data, 0)
    logged.append({"step": "pallas", **{k: float(v)
                                        for k, v in metrics.items()}})
    for m in logged:
        if not (np.isfinite(m["loss"]) and np.isfinite(m["grad_norm"])):
            raise AssertionError(f"train step {m['step']}: loss "
                                 f"{m['loss']}, grad norm {m['grad_norm']}")
    log("train: losses " + ", ".join(f"{m['loss']:.4f}" for m in logged)
        + "; grad norms "
        + ", ".join(f"{m['grad_norm']:.3f}" for m in logged)
        + " (last: pallas engine, kernel VJP)")


def four_chip_phase(jax, cfg, params, reqs, n_chips=4):
    from repro.serving import ServeEngine, ShardedServeEngine

    if jax.device_count() < n_chips:
        raise SystemExit(f"[smoke] --four-chips needs {n_chips} devices, "
                         f"JAX found {jax.device_count()}")
    with jax.default_matmul_precision("highest"):
        want = ServeEngine(params, cfg, max_len=MAX_LEN,
                           prefill_chunk=PREFILL_CHUNK).serve(
                               reqs, slots=SLOTS, mode="continuous")
        eng = ShardedServeEngine(params, cfg, n_hosts=n_chips,
                                 slots_per_host=SLOTS, max_len=MAX_LEN,
                                 prefill_chunk=PREFILL_CHUNK)
        pools = []
        decode = eng._ops_decode

        def spy(*args, **kwargs):
            logits, pool = decode(*args, **kwargs)
            if not pools:
                pools.append(pool)
            return logits, pool

        eng._ops_decode = spy
        t0 = time.perf_counter()
        got, stats = eng.serve(reqs, return_stats=True)
        dt = time.perf_counter() - t0
    n_tok = sum(len(v) for v in got.values())
    log(f"sharded serve over {n_chips} chips: {n_tok} tokens in {dt:.2f} s "
        f"(smoke figure, cold, compiles included); requests per host "
        f"{np.bincount([s['host'] for s in stats.values()], minlength=n_chips).tolist()}")
    check_tokens(got, want, "ShardedServeEngine vs ServeEngine")
    log(f"ShardedServeEngine == one-chip ServeEngine for all {len(reqs)} "
        "requests")

    held = {}
    total = 0
    for leaf in jax.tree_util.tree_leaves(pools[0]):
        total += leaf.nbytes
        for shard in leaf.addressable_shards:
            held[shard.device] = held.get(shard.device, 0) + shard.data.nbytes
    log(f"slot pool: {total} bytes; bytes held per device "
        f"{sorted(held.values())}")
    if len(held) != n_chips or any(b * n_chips != total
                                   for b in held.values()):
        raise AssertionError("the slot pool is not split evenly over "
                             f"{n_chips} devices")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-serve phase on four chips")
    args = ap.parse_args(argv)

    import jax

    from repro.utils import enable_compile_cache

    device = device_phase(jax)
    cache_dir = enable_compile_cache()
    cache = cache_events(jax)
    t0 = time.perf_counter()
    cfg, params = build_model(jax)
    reqs = make_requests(cfg)
    if args.four_chips:
        four_chip_phase(jax, cfg, params, reqs)
    else:
        serve_phase(jax, cfg, params, reqs)
        kernel_phase(jax, cfg, params, reqs)
        train_phase(jax, cfg)
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s; compile "
        f"cache {cache_dir}: {cache['hits']} hits, {cache['misses']} misses")
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
