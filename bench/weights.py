"""Random weights for a configuration, made from the seed on the device.

The tree has the layout of ``repro.models.transformer.init_lm`` (taken with
``jax.eval_shape``, so nothing of the program's initialisation runs), and
every leaf is drawn here by a rule keyed on its name, in ONE jitted call.
The plain reference regenerates the same tree from the same seed, so it
never reads weights that the program made or changed.

The scales follow the program's initialisers except for the serving
embedding (see ``make``).

Served type: the matrices that carry the FLOPs (two or more dims, over
65,536 elements) are stored in the configuration's activation dtype, the
rest (norms, biases, Laplace nodes) in float32, as a deployment would serve
them.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

EPS_SIGMA = 1e-4     # sigma = EPS_SIGMA + softplus(sigma_hat)
T_MIN = 1.0          # T = T_MIN + softplus(T_hat)
SERVED_MIN_SIZE = 65536


def _inv_softplus(y):
    return np.log(np.expm1(y))


def _normal(key, shape, std):
    return std * jnp.clip(jax.random.normal(key, shape, jnp.float32), -2.0, 2.0)


def _leaf(key, name: str, shape, init_T: float, embed_std: float):
    """One leaf by its name. Shapes may carry a leading layer-stack dim."""
    if name == "embed":
        return _normal(key, shape, embed_std)
    if name == "scale":
        return 1.0 + 0.05 * jax.random.normal(key, shape, jnp.float32)
    if name in ("bias", "b1", "b2"):
        return 0.02 * jax.random.normal(key, shape, jnp.float32)
    if name == "w_alpha":
        return _normal(key, shape, 0.02)
    if name == "b_alpha":
        return 2.0 + jax.random.normal(key, shape, jnp.float32)
    if name == "sigma_hat":
        S = shape[-1]
        base = _inv_softplus(np.maximum(np.geomspace(1e-3, 1.0, S) - EPS_SIGMA,
                                        1e-6))
        return jnp.asarray(base, jnp.float32) + 0.01 * jax.random.normal(
            key, shape, jnp.float32)
    if name == "omega":
        return jax.random.uniform(key, shape, jnp.float32, 0.0, math.pi / 4)
    if name == "T_hat":
        return (_inv_softplus(init_T - T_MIN)
                + 0.1 * jax.random.normal(key, shape, jnp.float32))
    if name in ("u_re", "u_im"):
        return jax.random.normal(key, shape, jnp.float32) / shape[-1]
    if len(shape) >= 2:  # projection matrices: w_v, w_o, w1, w2, w3, kernel
        return _normal(key, shape, 1.0 / math.sqrt(shape[-2]))
    return 0.02 * jax.random.normal(key, shape, jnp.float32)


def _path_name(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def served_dtype(shape, act_dtype):
    n = int(np.prod(shape))
    return act_dtype if len(shape) >= 2 and n > SERVED_MIN_SIZE else jnp.float32


def seed_key(seed: int):
    """A PRNG key from a seed of up to 64 bits."""
    seed = int(seed) % (2 ** 64)
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF),
                              seed >> 62)


def make(cfg, seed: int, served: bool = True, embed_std: float = 0.5):
    """The weight tree of ``cfg`` from ``seed``: one jitted call on the
    default device. ``served`` stores the big matrices in the activation
    dtype; otherwise every leaf is float32.

    ``embed_std``: rms 0.5 per dim makes a token's content weigh about as
    much as the sinusoidal position (rms 0.71) it is added to, so a random
    model's outputs depend on the prompt and not on the position alone;
    training starts from the program's own 0.02."""
    from repro.models import transformer as T

    shapes = jax.eval_shape(lambda: T.init_lm(jax.random.key(0), cfg))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [_path_name(p) for p, _ in flat]
    leaves = [(n.split("/")[-1], tuple(s.shape), zlib.crc32(n.encode()))
              for n, (_, s) in zip(names, flat)]
    act = cfg.act_dtype

    @jax.jit
    def build(key):
        out = []
        for name, shape, h in leaves:
            x = _leaf(jax.random.fold_in(key, h), name, shape, cfg.stlt_init_T,
                      embed_std)
            out.append(x.astype(served_dtype(shape, act)) if served else x)
        return jax.tree_util.tree_unflatten(treedef, out)

    return build(seed_key(seed))
