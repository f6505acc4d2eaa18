"""Seeded random weights in the layout the program serves, made by the
benchmark and not by the program, so that the reference can read them.

The tree's shapes come from the program's own serving init
(``jax.eval_shape``: shapes only, nothing is computed); every value is
drawn here, in one jitted call on the device, in the serving dtype:

- embedding and output tables: N(0, 0.02^2), with the padding rows past
  ``vocab_size`` zero, so no padded id can ever be the best token;
- RMSNorm scales (block, q/k and final): 1 + N(0, 0.1^2), so a norm that
  drops its scale is seen;
- the router bias, which Qwen3 does not have: zero;
- every other matrix: N(0, 1/fan_in), fan_in being its second-to-last
  axis.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed32(seed: int) -> int:
    """A 31-bit key seed from a seed of any size."""
    return int(np.random.SeedSequence(seed).generate_state(1)[0]) & 0x7FFFFFFF


def _leaf_name(path) -> str:
    return next(str(p.key) for p in reversed(path) if hasattr(p, "key"))


def _draw(key, name: str, shape, dtype, vocab: int):
    if name in ("embed", "unembed"):
        w = jax.random.normal(key, shape, jnp.float32) * 0.02
        w = jnp.where(jnp.arange(shape[0])[:, None] < vocab, w, 0.0)
    elif "norm" in name:
        w = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif name == "router_bias":
        w = jnp.zeros(shape, jnp.float32)
    else:
        w = jax.random.normal(key, shape, jnp.float32) * shape[-2] ** -0.5
    return w.astype(dtype)


def param_shapes(cfg):
    from repro.launch.serve import serving_init

    return jax.eval_shape(serving_init(cfg), 0)


@functools.lru_cache(maxsize=None)
def _maker(cfg):
    shapes = param_shapes(cfg)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def make(seed):
        key = jax.random.key(seed)
        return treedef.unflatten([
            _draw(jax.random.fold_in(key, i), _leaf_name(path), s.shape,
                  s.dtype, cfg.vocab_size)
            for i, (path, s) in enumerate(leaves)])

    return jax.jit(make)


def make_weights(cfg, seed: int, device=None):
    """The weights for ``seed``, on ``device`` (default: the first)."""
    with jax.default_device(device or jax.devices()[0]):
        return _maker(cfg)(jnp.int32(seed32(seed)))
