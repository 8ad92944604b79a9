"""Shared utilities: PRNG handling, initializers, pytree helpers, dtypes.

The framework is pure JAX (no flax/optax in this environment): parameters are
nested dicts of jnp arrays, modules are ``init_*``/``*_apply`` function pairs,
and optimizers/checkpointing operate on raw pytrees.
"""
from __future__ import annotations

import dataclasses
import math
import os
from typing import Any, Callable, Iterable, Mapping

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any

# ---------------------------------------------------------------------------
# PRNG helpers
# ---------------------------------------------------------------------------


def key_iter(key: jax.Array):
    """Infinite iterator of fresh PRNG keys."""
    while True:
        key, sub = jax.random.split(key)
        yield sub


def fold_key(key: jax.Array, *data: int) -> jax.Array:
    for d in data:
        key = jax.random.fold_in(key, d)
    return key


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------


def trunc_normal(key, shape, stddev=0.02, dtype=jnp.float32):
    return stddev * jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype)


def lecun_normal(key, shape, fan_in=None, dtype=jnp.float32):
    fan_in = fan_in if fan_in is not None else shape[0]
    return trunc_normal(key, shape, stddev=1.0 / math.sqrt(max(1, fan_in)), dtype=dtype)


def he_normal(key, shape, fan_in=None, dtype=jnp.float32):
    fan_in = fan_in if fan_in is not None else shape[0]
    return trunc_normal(key, shape, stddev=math.sqrt(2.0 / max(1, fan_in)), dtype=dtype)


def zeros(shape, dtype=jnp.float32):
    return jnp.zeros(shape, dtype)


def ones(shape, dtype=jnp.float32):
    return jnp.ones(shape, dtype)


# ---------------------------------------------------------------------------
# Pytree helpers
# ---------------------------------------------------------------------------


def tree_size(tree: PyTree) -> int:
    """Total number of scalar parameters in a pytree."""
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))


def tree_bytes(tree: PyTree) -> int:
    return sum(
        int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
        for x in jax.tree_util.tree_leaves(tree)
    )


def cast_params_for_compute(tree: PyTree, dtype, min_size: int = 65536) -> PyTree:
    """Mixed-precision policy: cast the FLOPs-carrying matrices (ndim>=2 and
    large) to the activation dtype; keep small/1D params (norm scales,
    biases, Laplace nodes) in float32 — pole precision matters for long
    half-lives."""

    def cast(x):
        if (
            hasattr(x, "ndim") and x.ndim >= 2
            and int(np.prod(x.shape)) > min_size
            and jnp.issubdtype(x.dtype, jnp.floating)
        ):
            return x.astype(dtype)
        return x

    return jax.tree_util.tree_map(cast, tree)


def tree_cast(tree: PyTree, dtype) -> PyTree:
    def cast(x):
        if jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(dtype)
        return x

    return jax.tree_util.tree_map(cast, tree)


def tree_zeros_like(tree: PyTree, dtype=None) -> PyTree:
    return jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, dtype or x.dtype), tree
    )


def tree_norm(tree: PyTree) -> jax.Array:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree_util.tree_leaves(tree)]
    return jnp.sqrt(sum(leaves))


def tree_flatten_with_paths(tree: PyTree) -> list[tuple[str, jax.Array]]:
    """Flatten into (dotted-path, leaf) pairs — used by checkpointing/sharding."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        name = "/".join(_path_str(p) for p in path)
        out.append((name, leaf))
    return out


def _path_str(p) -> str:
    if isinstance(p, jax.tree_util.DictKey):
        return str(p.key)
    if isinstance(p, jax.tree_util.SequenceKey):
        return str(p.idx)
    if isinstance(p, jax.tree_util.GetAttrKey):
        return str(p.name)
    return str(p)


# ---------------------------------------------------------------------------
# Numerics
# ---------------------------------------------------------------------------


def softplus(x):
    return jax.nn.softplus(x)


def inv_softplus(y: float) -> float:
    """x such that softplus(x) = y (for parameter initialization)."""
    # softplus(x) = log(1+e^x)  =>  x = log(e^y - 1)
    return float(np.log(np.expm1(y)))


def with_sharding_constraint(x, spec):
    """Apply a sharding constraint if a mesh context can resolve it; no-op
    otherwise (single-device tests trace the same code without a mesh)."""
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except Exception:  # pragma: no cover - no mesh context / unbound axes
        return x


def trace_probe(fn: Callable, log: list, name: str | None = None) -> Callable:
    """Wrap ``fn`` so every TRACE of it is recorded in ``log``.

    ``jax.jit`` re-traces (and re-compiles) the wrapped Python callable once
    per distinct input shape/dtype signature, so wrapping a function BEFORE
    it is jitted turns ``log`` into a compilation counter: each entry is
    ``(name, shape)`` where shape is taken from the ``inputs`` kwarg (or the
    first array argument). The serving compile-count regression tests and
    benchmarks/serving.py use this to prove chunked admission compiles a
    fixed set of programs whatever the prompt lengths.
    """
    import functools

    label = name or getattr(fn, "__name__", "fn")

    @functools.wraps(fn)
    def probed(*args, **kwargs):
        arr = kwargs.get("inputs")
        if arr is None:
            arr = next((a for a in args if hasattr(a, "shape")), None)
        log.append((label, None if arr is None else tuple(arr.shape)))
        return fn(*args, **kwargs)

    return probed


_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here. Otherwise the cache goes to ``.jax_cache`` at the
    root of the checkout: a fixed path, because the path is part of what a
    later run must find again. Entry points call this from ``main()``,
    never at import.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
