"""Seeded random weights in the layout the program serves, made by the
benchmark and not by the program, so that the reference can read them.

The tree's shapes come from the program's own serving init
(``jax.eval_shape``: shapes only, nothing is computed); every value is
drawn here, in one jitted call on the device, in the serving dtype.  A
leaf is named by its keys below the layer group (``mixer.wq``; ``embed``
at the top).  Its draw is the family module's (``DRAWS[name](key,
shape)``, in float32) where the module names the leaf, else:

- embedding and output tables: N(0, 0.02^2), with the padding rows past
  ``vocab_size`` zero, so no padded id can ever be the best token;
- RMSNorm scales (block, q/k and final): 1 + N(0, 0.1^2), so a norm that
  drops its scale is seen;
- the router bias: zero, a router without one (a family whose router
  has a bias draws it in ``DRAWS``);
- every other matrix: N(0, 1/fan_in), fan_in being its second-to-last
  axis.

A leaf that is a vector in each layer (under ``groups`` its first axis
stacks the layers) has no fan-in: it needs the family's draw, and
without one the weights are refused.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed32(seed: int) -> int:
    """A 31-bit key seed from a seed of any size."""
    return int(np.random.SeedSequence(seed).generate_state(1)[0]) & 0x7FFFFFFF


def _keys(path) -> list[str]:
    return [str(p.key) for p in path if hasattr(p, "key")]


def _rule(path, shape, draws: dict):
    """How the leaf at ``path`` is drawn: a family draw or a generic rule."""
    keys = _keys(path)
    stacked = keys[0] == "groups"
    name = ".".join(keys[1:] if stacked else keys)
    leaf = keys[-1]
    if name in draws:
        return draws[name]
    if leaf in ("embed", "unembed"):
        return "table"
    if "norm" in leaf:
        return "norm"
    if leaf == "router_bias":
        return "zero"
    if len(shape) - stacked < 2:
        raise ValueError(
            f"{jax.tree_util.keystr(path)}: {name} is a vector in each layer, "
            f"so it has no fan-in, and the family module has no draw for it "
            f"(DRAWS[{name!r}])")
    return "matrix"


def _draw(key, rule, shape, dtype, vocab: int):
    if callable(rule):
        w = rule(key, shape)
    elif rule == "table":
        w = jax.random.normal(key, shape, jnp.float32) * 0.02
        w = jnp.where(jnp.arange(shape[0])[:, None] < vocab, w, 0.0)
    elif rule == "norm":
        w = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif rule == "zero":
        w = jnp.zeros(shape, jnp.float32)
    else:
        w = jax.random.normal(key, shape, jnp.float32) * shape[-2] ** -0.5
    return w.astype(dtype)


def param_shapes(cfg):
    from repro.launch.serve import serving_init

    return jax.eval_shape(serving_init(cfg), 0)


@functools.lru_cache(maxsize=None)
def _maker(cfg, family):
    shapes = param_shapes(cfg)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    rules = [_rule(path, s.shape, family.DRAWS) for path, s in leaves]

    def make(seed):
        key = jax.random.key(seed)
        return treedef.unflatten([
            _draw(jax.random.fold_in(key, i), rule, s.shape, s.dtype,
                  cfg.vocab_size)
            for i, (rule, (_, s)) in enumerate(zip(rules, leaves))])

    return jax.jit(make)


def make_weights(cfg, family, seed: int, device=None):
    """The weights for ``seed``, drawn by the rules above and ``family``'s
    ``DRAWS``, on ``device`` (default: the first)."""
    with jax.default_device(device or jax.devices()[0]):
        return _maker(cfg, family)(jnp.int32(seed32(seed)))
