"""Ahead-of-time compiles for a described TPU v5e: the Pallas kernels and
the stlt-base serve steps at the paper's widths, through the chip's own
compiler, with no chip attached.

Interpret mode accepts block shapes and VMEM footprints that the TPU
compiler refuses (a block whose last two dims are neither (8, 128)-aligned
nor the array's own, a kernel above the scoped VMEM limit). These tests
catch such refusals without a chip. Nothing runs: they check that each
program compiles and, where a kernel belongs, that it holds one
(``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and test workers import every
test file.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

BH, DH, S = 16, 64, 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a program compiled for a described chip cannot be read back from the
    # persistent cache without that chip; keep these compiles out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile(fn, *args, **kwargs):
    return jax.jit(fn).lower(*args, **kwargs).compile().as_text()


def _scan_args(one_chip, n):
    x = _spec(one_chip, (BH, n, DH))
    p = _spec(one_chip, (BH, S))
    return x, p, p, p, p


def _scan(x, lm, th, ur, ui, **kw):
    from repro.kernels import ops as kops

    return kops.stlt_scan(x, lm, th, ur, ui, use_kernel=True,
                          interpret=False, **kw)


@pytest.mark.parametrize("n", [2048, 4096])
def test_stlt_scan_forward_compiles(one_chip, n):
    assert "tpu_custom_call" in _compile(_scan, *_scan_args(one_chip, n))


@pytest.mark.parametrize("n", [2048, 4096])
def test_stlt_scan_stateful_compiles(one_chip, n):
    h = _spec(one_chip, (BH, S, DH))
    valid = _spec(one_chip, (BH,), jnp.int32)

    def stateful(x, lm, th, ur, ui, h0_re, h0_im, valid):
        return _scan(x, lm, th, ur, ui, h0_re=h0_re, h0_im=h0_im,
                     valid=valid, return_state=True)

    assert "tpu_custom_call" in _compile(
        stateful, *_scan_args(one_chip, n), h, h, valid)


@pytest.mark.parametrize("n", [2048, 4096])
def test_stlt_scan_grad_compiles(one_chip, n):
    grad = jax.grad(lambda *a: _scan(*a).sum(), argnums=(0, 1, 2, 3, 4))
    assert "tpu_custom_call" in _compile(grad, *_scan_args(one_chip, n))


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("n,precision", [(4096, "default"),
                                         (4096, "highest"),
                                         (32768, "default")])
def test_relevance_flash_compiles(one_chip, n, precision, causal):
    """A "highest"-precision context reaches the kernel's dots, and f32
    matmuls split into bf16 parts take more VMEM."""
    from repro.kernels import relevance_flash as rf

    x = _spec(one_chip, (BH, n, DH))
    p = _spec(one_chip, (BH, S))
    fn = functools.partial(rf.relevance_flash, causal=causal,
                           use_kernel=True, interpret=False)
    with jax.default_matmul_precision(precision):
        assert "tpu_custom_call" in _compile(fn, x, x, p, p)


def _stlt_base(one_chip, batch, max_len):
    """stlt-base with the pallas engine, as abstract params and pool state
    placed on the described chip."""
    from repro import configs as configs_lib
    from repro.models import transformer as T

    cfg = dataclasses.replace(configs_lib.get_config("stlt-base"),
                              stlt_engine="pallas")

    def place(tree):
        return jax.tree_util.tree_map(
            lambda a: _spec(one_chip, a.shape, a.dtype), tree)

    params = place(jax.eval_shape(
        lambda: T.init_lm(jax.random.key(0), cfg)))
    state = place(jax.eval_shape(
        lambda: T.init_decode_state(cfg, batch, max_len)))
    return cfg, params, state


@pytest.fixture
def compiled_kernel(monkeypatch):
    """Steer the pallas engine to the compiled kernel: on a CPU host its
    dispatch would otherwise take the jnp path."""
    import repro.kernels.ops as kops

    monkeypatch.setattr(kops, "stlt_scan", functools.partial(
        kops.stlt_scan, use_kernel=True, interpret=False))


def test_stlt_base_prefill_chunk_compiles(one_chip, compiled_kernel):
    """The serve admission step: a [4 slots, 256] masked chunk."""
    from repro.models import transformer as T

    cfg, params, state = _stlt_base(one_chip, batch=4, max_len=2048)
    toks = _spec(one_chip, (4, 256), jnp.int32)
    valid = _spec(one_chip, (4,), jnp.int32)
    fn = functools.partial(T.prefill_chunk, cfg=cfg)
    assert "tpu_custom_call" in _compile(fn, params, inputs=toks,
                                         state=state, valid_len=valid)


def test_stlt_base_decode_step_compiles(one_chip, compiled_kernel):
    """The serve decode step. One token advances the O(S*d) carry in closed
    form (``scan.stlt_decode_step``), so no kernel belongs in it."""
    from repro.models import transformer as T

    cfg, params, state = _stlt_base(one_chip, batch=4, max_len=2048)
    tok = _spec(one_chip, (4,), jnp.int32)
    caps = _spec(one_chip, (4,), jnp.int32)
    fn = functools.partial(T.decode_step, cfg=cfg)
    text = _compile(fn, params, token_t=tok, state=state, node_cap=caps)
    assert "tpu_custom_call" not in text


def test_stlt_base_prefill_chunk_rows_compiles(one_chip, compiled_kernel):
    """The row-bucketed admission step: 2 of 4 pool rows gathered, a
    [2, 256] masked chunk, the rows scattered back into the donated pool
    in place (the pool's buffers alias the output's)."""
    from repro.models import transformer as T

    cfg, params, pool = _stlt_base(one_chip, batch=4, max_len=2048)
    toks = _spec(one_chip, (2, 256), jnp.int32)
    rows = _spec(one_chip, (2,), jnp.int32)
    fn = functools.partial(T.prefill_chunk_rows, cfg=cfg)
    text = jax.jit(fn, donate_argnames="pool").lower(
        params, inputs=toks, pool=pool, idx=rows,
        valid_len=rows).compile().as_text()
    assert "tpu_custom_call" in text
    assert "input_output_alias" in text
