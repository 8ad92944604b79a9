"""End-to-end training driver.

Runs for real on whatever devices exist (CPU here, a pod via the same code
path on TPU): builds the model from a config (--arch or --preset), the
deterministic data pipeline, the optimizer + schedule, checkpoint-restart,
and the jitted train step from launch/steps.py.

Examples:
  PYTHONPATH=src python -m repro.launch.train --preset paper-small --steps 200
  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --reduced \
      --steps 50 --batch 8 --seq 256
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs as configs_lib
from repro.configs.base import ModelConfig, TrainConfig
from repro.checkpoint import CheckpointManager
from repro.core.adaptive import anneal_tau
from repro.data import ByteCorpus, lm_batch_stream
from repro.launch import steps as steps_lib
from repro.models import transformer as T
from repro.optim import clip_by_global_norm, make_optimizer, make_schedule
from repro.optim.adamw import apply_updates
from repro.utils import cast_params_for_compute, enable_compile_cache, tree_size


def paper_small(vocab: int = 256) -> ModelConfig:
    """A CPU-trainable slice of the paper's base model."""
    return ModelConfig(
        name="stlt-paper-small", family="lm", vocab=vocab, num_layers=4,
        d_model=256, num_heads=8, num_kv_heads=8, d_ff=1024, mixer="stlt",
        stlt_nodes=32, stlt_adaptive=True, act="gelu", norm="layernorm",
        dtype="float32", scan_layers=False, remat=False,
    )


def make_step(cfg: ModelConfig, tcfg: TrainConfig):
    opt = make_optimizer(cfg.optimizer)
    sched = make_schedule(tcfg.schedule, tcfg.learning_rate, tcfg.warmup_steps,
                          tcfg.total_steps)

    @jax.jit
    def step_fn(params, opt_state, batch, step):
        tau = anneal_tau(step, tcfg.total_steps, tcfg.adaptive_tau_start,
                         tcfg.adaptive_tau_end)
        rng = jax.random.fold_in(jax.random.key(tcfg.seed), step)

        def loss_fn(p):
            p = cast_params_for_compute(p, cfg.act_dtype)
            return T.lm_loss(p, cfg, batch, rng=rng, deterministic=False, tau=tau)

        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        grads, gnorm = clip_by_global_norm(grads, tcfg.grad_clip)
        updates, opt_state = opt.update(grads, opt_state, params, sched(step))
        params = apply_updates(params, updates)
        return params, opt_state, {**metrics, "grad_norm": gnorm}

    return opt, step_fn


def main(argv=None):
    """Train; returns the final ``{"params", "opt"}`` state and the logged
    metrics, one dict per logged step."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="assigned arch id")
    ap.add_argument("--preset", default=None, choices=[None, "paper-small"])
    ap.add_argument("--variant", default="native")
    ap.add_argument("--reduced", action="store_true", help="smoke-size config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--data", default="bytes", choices=["bytes", "synthetic"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--save-every", type=int, default=100)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.preset == "paper-small" or args.arch is None:
        cfg = paper_small()
    else:
        cfg = configs_lib.get_config(args.arch, args.variant)
        if args.reduced:
            cfg = cfg.reduced()
    if cfg.family == "encdec":
        raise SystemExit("use benchmarks/translation.py for enc-dec training")
    vocab = cfg.vocab
    tcfg = TrainConfig(learning_rate=args.lr, total_steps=args.steps,
                       warmup_steps=max(10, args.steps // 10))

    corpus = ByteCorpus() if args.data == "bytes" else None

    def batch_fn(step: int):
        if corpus is not None and vocab >= 256:
            return corpus.batch(step, args.batch, args.seq)
        return lm_batch_stream(0, step, args.batch, args.seq, vocab)

    opt, step_fn = make_step(cfg, tcfg)

    def init_state():
        params = T.init_lm(jax.random.key(tcfg.seed), cfg)
        return {"params": params, "opt": opt.init(params)}

    start = -1
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir)
        state, start = mgr.restore_or_init(init_state)
    else:
        mgr, state = None, init_state()
    print(f"[train] {cfg.name}: {tree_size(state['params'])/1e6:.1f}M params, "
          f"{jax.device_count()} device(s), resume from step {start}")

    t_last, tok_per_step = time.time(), args.batch * args.seq
    log = []
    for step in range(start + 1, args.steps):
        batch = {k: jnp.asarray(v) for k, v in batch_fn(step).items()
                 if k in ("inputs", "labels", "mask")}
        params, opt_state, metrics = step_fn(state["params"], state["opt"], batch, step)
        state = {"params": params, "opt": opt_state}
        if step % args.log_every == 0 or step == args.steps - 1:
            dt = time.time() - t_last
            t_last = time.time()
            m = {k: float(v) for k, v in metrics.items()}
            log.append({"step": step, **m})
            print(f"[train] step {step:5d} loss {m['loss']:.4f} ce {m['ce']:.4f} "
                  f"gnorm {m['grad_norm']:.2f} s_eff {m.get('s_eff', 0):.1f} "
                  f"({tok_per_step * args.log_every / max(dt, 1e-9):.0f} tok/s)")
        if mgr and step % args.save_every == 0 and step > 0:
            mgr.save(step, state)
    if mgr:
        mgr.save(args.steps - 1, state)
        mgr.wait()
    print("[train] done")
    return state, log


if __name__ == "__main__":
    main()
