"""Distributed runtime tests. Multi-device cases run in subprocesses so the
forced device count never leaks into this process (smoke tests must see 1
device)."""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_subprocess(body: str, devices: int = 8):
    code = textwrap.dedent(body)
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=900)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    return r.stdout


def test_param_specs_cover_every_leaf_single_device():
    """Spec construction itself needs no devices."""
    import jax
    from repro import configs as configs_lib
    from repro.distributed import sharding as sh
    from repro.launch import steps as steps_lib

    mesh = jax.make_mesh((1, 1), ("data", "model"))
    for arch in ("qwen3-moe-235b-a22b", "recurrentgemma-9b", "whisper-base"):
        cfg = configs_lib.get_config(arch)
        shapes = steps_lib.abstract_params(cfg)
        specs = sh.param_specs(shapes, cfg, mesh)
        n_leaves = len(jax.tree_util.tree_leaves(shapes))
        from jax.sharding import PartitionSpec
        n_specs = len(jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, PartitionSpec)))
        assert n_leaves == n_specs, arch


def test_context_parallel_stlt_matches_serial():
    _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.context_parallel import stlt_context_parallel
        from repro.core import scan as scan_lib
        mesh = jax.make_mesh((4,), ("data",))
        rng = np.random.default_rng(0)
        B, N, d, S = 2, 64, 16, 6
        x = jnp.asarray(rng.normal(size=(B, N, d)), jnp.float32)
        lm = jnp.asarray(-rng.uniform(0.01, 0.5, S), jnp.float32)
        th = jnp.asarray(-rng.uniform(0, 1, S), jnp.float32)
        u = (rng.normal(size=(2, S))/S).astype(np.float32)
        z_ref = scan_lib.stlt_chunked(x, lm, th, u[0], u[1], chunk=16)
        z_cp = stlt_context_parallel(x, lm, th, jnp.asarray(u[0]), jnp.asarray(u[1]), mesh, chunk=16)
        err = float(jnp.max(jnp.abs(z_cp - z_ref)) / jnp.max(jnp.abs(z_ref)))
        assert err < 1e-5, err
    """)


def test_pipeline_parallel_matches_serial():
    _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.pipeline import pipeline_apply
        mesh = jax.make_mesh((4,), ("pipe",))
        rng = np.random.default_rng(0)
        D, M, mb, dd = 4, 6, 2, 8
        Ws = jnp.asarray(rng.normal(size=(D, dd, dd)) / np.sqrt(dd), jnp.float32)
        xm = jnp.asarray(rng.normal(size=(M, mb, dd)), jnp.float32)
        stage = lambda W, x: jnp.tanh(x @ W)
        y = pipeline_apply(stage, Ws, xm, mesh)
        y_ref = xm
        for i in range(D):
            y_ref = stage(Ws[i], y_ref)
        assert float(jnp.max(jnp.abs(y - y_ref))) < 1e-5
    """)


def test_sharded_train_step_matches_single_device():
    """The pjit train step on a 2x2 mesh produces the same loss trajectory
    as the unsharded step — sharding must not change the math."""
    _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import ModelConfig, TrainConfig
        from repro.launch import steps as S
        from repro.distributed import sharding as sh
        from jax.sharding import NamedSharding, PartitionSpec as P
        import dataclasses

        cfg = ModelConfig(name="t", family="lm", vocab=64, num_layers=2,
                          d_model=32, num_heads=4, num_kv_heads=2, d_ff=64,
                          dtype="float32", scan_layers=False, remat=False,
                          mixer="stlt", stlt_nodes=4, stlt_chunk=8)
        shape = dataclasses.replace(
            __import__("repro.configs.base", fromlist=["SHAPES"]).SHAPES["train_4k"],
            seq_len=32, global_batch=4)
        tcfg = TrainConfig(total_steps=10, warmup_steps=2)

        mesh = jax.make_mesh((2, 2), ("data", "model"))
        prog = S.build_train_step(cfg, shape, mesh, tcfg)
        from repro.models import transformer as T
        from repro.optim import make_optimizer
        params = T.init_lm(jax.random.key(0), cfg)
        opt = make_optimizer("adamw")
        ostate = opt.init(params)
        rng = np.random.default_rng(0)
        batch = {"inputs": jnp.asarray(rng.integers(0, 64, (4, 32)), jnp.int32),
                 "labels": jnp.asarray(rng.integers(0, 64, (4, 32)), jnp.int32)}

        # unsharded reference
        p1, o1, m1 = jax.jit(prog.fn)(params, ostate, batch, jnp.asarray(0))
        # sharded
        named = lambda t: jax.tree_util.tree_map(
            lambda sp: NamedSharding(mesh, sp), t,
            is_leaf=lambda x: isinstance(x, P))
        jitted = jax.jit(prog.fn, in_shardings=named(prog.in_shardings),
                         out_shardings=named(prog.out_shardings))
        with mesh:
            p2, o2, m2 = jitted(params, ostate, batch, jnp.asarray(0))
        assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4, (m1["loss"], m2["loss"])
        d = max(float(jnp.abs(a - b).max()) for a, b in
                zip(jax.tree_util.tree_leaves(p1), jax.tree_util.tree_leaves(p2)))
        assert d < 1e-4, d
    """)


def test_gradient_compression_halves_wire_bytes():
    """bf16-compressed psum moves half the bytes of fp32 (shard_map-visible)."""
    _run_subprocess(r"""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        mesh = jax.make_mesh((4,), ("data",))

        def allreduce(x, compress):
            def f(x):
                g = x.astype(jnp.bfloat16) if compress else x
                s = jax.lax.psum(g, "data")
                return s.astype(jnp.float32)
            return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("data"),
                                         out_specs=P(None), check_vma=False))(x)

        x = jnp.ones((4, 1024), jnp.float32)
        # NB: inspect the PRE-backend lowering — the CPU backend legalizes
        # bf16 reductions to f32 ("region_promoted"), TPU keeps them bf16.
        t32 = jax.jit(lambda x: allreduce(x, False)).lower(x).as_text()
        t16 = jax.jit(lambda x: allreduce(x, True)).lower(x).as_text()
        import re
        def ar_sig(t):  # the region op's type signature spans lines
            m = re.search(r'all_reduce.*?\(tensor<([^>]+)>\)', t, re.S)
            assert m, "no all_reduce found"
            return m.group(1)
        assert ar_sig(t32).endswith("f32"), ar_sig(t32)
        assert ar_sig(t16).endswith("bf16"), ar_sig(t16)
    """)


def test_shardmap_moe_matches_gather_dispatch():
    """§Perf explicit-EP dispatch == the global-view gather path (fwd+grads)."""
    _run_subprocess("""
        import jax, jax.numpy as jnp, dataclasses
        from repro.models import moe as M
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        cfg_g = M.MoEConfig(d_model=16, d_ff=32, num_experts=8, top_k=2,
                            capacity_factor=8.0, param_dtype=jnp.float32,
                            ep_axis="model", cap_axis="data",
                            dense_residual=True, dense_ff=32)
        cfg_s = dataclasses.replace(cfg_g, dispatch="shard_map", fsdp_axis="data")
        params = M.init_moe(jax.random.key(0), cfg_g)
        x = jax.random.normal(jax.random.key(1), (4, 6, 16))
        def loss(p, cfg):
            y, aux = M.apply_moe(p, cfg, x)
            return (y ** 2).sum() + aux["aux_loss"]
        with mesh:
            ls, gs = jax.jit(jax.value_and_grad(lambda p: loss(p, cfg_s)))(params)
        lg, gg = jax.jit(jax.value_and_grad(lambda p: loss(p, cfg_g)))(params)
        assert abs(float(ls) - float(lg)) < 1e-2, (ls, lg)
        for a, b in zip(jax.tree_util.tree_leaves(gs), jax.tree_util.tree_leaves(gg)):
            rel = float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-9))
            assert rel < 1e-3, rel
    """)


def test_wd_mask_excludes_node_params():
    from repro.optim.adamw import default_wd_mask
    import jax.numpy as jnp
    from repro.core import stlt as stlt_lib
    from repro.core.stlt import STLTConfig
    import jax

    cfg = STLTConfig(d_model=32, num_heads=4, num_nodes=8)
    p = {"stlt": stlt_lib.init_stlt(jax.random.key(0), cfg)}
    mask = default_wd_mask(p)
    assert float(mask["stlt"]["nodes"]["u_re"]) == 0.0
    assert float(mask["stlt"]["nodes"]["sigma_hat"]) == 0.0
    assert float(mask["stlt"]["w_v"]) == 1.0
