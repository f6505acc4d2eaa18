"""Gated DeltaNet mixer (Qwen3-Next; Yang et al., arXiv:2412.06464).

Per value head, with a (key_dim, value_dim) state ``S``, one position:

    S <- S * exp(g)
    S <- S + k (beta * (v - S^T k))^T
    o  = S^T q

with ``q`` and ``k`` L2-normalised (``q`` also scaled by key_dim^-1/2),
``beta = sigmoid(b)`` and ``g = -exp(A_log) * softplus(a + dt_bias)``.
Each key head serves ``value_heads / key_heads`` value heads.  q, k and
v pass through a causal depthwise conv (no bias) and SiLU first; the
output is a gated RMSNorm per head, ``rms(o) * w * silu(z)``, then the
output projection.

The projections keep the published layout: ``in_qkvz`` groups its
outputs per key head as [q, k, v, z] (v and z of all that head's value
heads), ``in_ba`` per key head as [b, a]; the conv's channels are all
q, then all k, then all v.

Training and prefill use the chunked form (WY representation within a
chunk, the state carried across chunks); decode updates the per-lane
state one position at a time.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..sharding import Ax
from .layers import causal_conv1d, dense_init

CHUNK = 64


class GDNState(NamedTuple):
    conv: jax.Array  # (b, conv_width - 1, conv channels): last inputs
    s: jax.Array     # (b, value_heads, key_dim, value_dim) float32


def _dims(cfg):
    hk, hv = cfg.gdn_key_heads, cfg.gdn_value_heads
    dk, dv = cfg.gdn_key_dim, cfg.gdn_value_dim
    return hk, hv, dk, dv


def _conv_channels(cfg) -> int:
    hk, hv, dk, dv = _dims(cfg)
    return 2 * hk * dk + hv * dv


def init_gdn(key, cfg):
    d = cfg.d_model
    hk, hv, dk, dv = _dims(cfg)
    ks = jax.random.split(key, 5)
    params = {
        "in_qkvz": dense_init(ks[0], d, 2 * hk * dk + 2 * hv * dv,
                              "embed", "heads")[0],
        "in_ba": dense_init(ks[1], d, 2 * hv, "embed", "heads")[0],
        "conv": jax.random.normal(ks[2], (cfg.conv_width, _conv_channels(cfg)),
                                  jnp.float32) * cfg.conv_width ** -0.5,
        "A_log": jnp.log(jax.random.uniform(ks[3], (hv,), jnp.float32,
                                            1e-3, 16.0)),
        "dt_bias": jnp.ones((hv,), jnp.float32),
        "out_norm": jnp.ones((dv,), jnp.float32),
        "out": dense_init(ks[4], hv * dv, d, "heads", "embed")[0],
    }
    axes = {
        "in_qkvz": Ax("embed", "heads"), "in_ba": Ax("embed", "heads"),
        "conv": Ax("conv", None), "A_log": Ax(None), "dt_bias": Ax(None),
        "out_norm": Ax("head_dim"), "out": Ax("heads", "embed"),
    }
    return params, axes


def init_gdn_state(cfg, batch: int) -> GDNState:
    """Zero state; the conv's inputs are held in the compute dtype."""
    hk, hv, dk, dv = _dims(cfg)
    return GDNState(
        conv=jnp.zeros((batch, cfg.conv_width - 1, _conv_channels(cfg)),
                       jnp.dtype(cfg.compute_dtype)),
        s=jnp.zeros((batch, hv, dk, dv), jnp.float32))


def gdn_state_specs(cfg, batch: int) -> GDNState:
    hk, hv, dk, dv = _dims(cfg)
    sds = jax.ShapeDtypeStruct
    return GDNState(
        conv=sds((batch, cfg.conv_width - 1, _conv_channels(cfg)),
                 jnp.dtype(cfg.compute_dtype)),
        s=sds((batch, hv, dk, dv), jnp.float32))


def _l2norm(x, eps: float = 1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def _inputs(params, cfg, x, conv_state):
    """Projections, conv and gates of x (b, s, d): q, k (b, s, hv, dk)
    normalised and in float32, v, z (b, s, hv, dv), beta and g (b, s, hv)
    in float32, and the conv's new state."""
    b, s, _ = x.shape
    hk, hv, dk, dv = _dims(cfg)
    r = hv // hk
    dt = x.dtype
    qkvz = (x @ params["in_qkvz"].astype(dt)).reshape(b, s, hk, -1)
    q, k, v, z = jnp.split(qkvz, [dk, 2 * dk, 2 * dk + r * dv], axis=-1)
    ba = (x @ params["in_ba"].astype(dt)).reshape(b, s, hk, 2 * r)
    beta = jax.nn.sigmoid(ba[..., :r].reshape(b, s, hv).astype(jnp.float32))
    a = ba[..., r:].reshape(b, s, hv).astype(jnp.float32)
    g = -jnp.exp(params["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        a + params["dt_bias"].astype(jnp.float32))
    mixed = jnp.concatenate([q.reshape(b, s, hk * dk), k.reshape(b, s, hk * dk),
                             v.reshape(b, s, hv * dv)], axis=-1)
    mixed, new_conv = causal_conv1d(mixed, params["conv"], conv_state)
    mixed = jax.nn.silu(mixed)
    q, k, v = jnp.split(mixed, [hk * dk, 2 * hk * dk], axis=-1)

    def heads(t):   # (b, s, hk * dk) -> (b, s, hv, dk), key head j // r
        return jnp.repeat(t.reshape(b, s, hk, dk).astype(jnp.float32), r,
                          axis=2)

    q = _l2norm(heads(q)) * dk ** -0.5
    k = _l2norm(heads(k))
    return (q, k, v.reshape(b, s, hv, dv), z.reshape(b, s, hv, dv), beta, g,
            new_conv)


def _output(params, cfg, o, z, dt):
    """Gated RMSNorm per head, then the output projection."""
    b, s = o.shape[:2]
    of = o.astype(jnp.float32)
    of = of * jax.lax.rsqrt(jnp.mean(of * of, -1, keepdims=True) + cfg.norm_eps)
    of = of * params["out_norm"].astype(jnp.float32) * jax.nn.silu(
        z.astype(jnp.float32))
    return of.astype(dt).reshape(b, s, -1) @ params["out"].astype(dt)


def _chunked(q, k, v, beta, g, s0):
    """The recurrence over (b, h, L, .) inputs in chunks of CHUNK
    positions: within a chunk, (I + strictly-lower(beta k k^T decay))^-1
    turns the delta rule's updates into matmuls.  Returns the outputs
    (b, h, L, dv) and the last state."""
    b, h, n, dk = k.shape
    dv = v.shape[-1]
    c = min(CHUNK, n)
    pad = -n % c
    if pad:
        def padded(t):
            return jnp.pad(t, [(0, 0), (0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 3))
        q, k, v, beta, g = map(padded, (q, k, v, beta, g))
    m = (n + pad) // c

    def chunks(t):   # (b, h, L, ...) -> (m, b, h, c, ...)
        return jnp.moveaxis(t.reshape((b, h, m, c) + t.shape[3:]), 2, 0)

    q, k, v, beta, g = map(chunks, (q, k, v, beta, g))
    g = jnp.cumsum(g, axis=-1)                                # (m, b, h, c)
    lower = jnp.tril(jnp.ones((c, c), bool))
    strict = jnp.tril(jnp.ones((c, c), bool), -1)
    decay = jnp.where(lower, jnp.exp(jnp.where(
        lower, g[..., :, None] - g[..., None, :], 0.0)), 0.0)
    kb = k * beta[..., None]
    a = jnp.where(strict, jnp.einsum("...id,...jd->...ij", kb, k) * decay, 0.0)
    eye = jnp.eye(c, dtype=a.dtype)
    t = jax.scipy.linalg.solve_triangular(eye + a, jnp.broadcast_to(eye, a.shape),
                                          lower=True)
    u = t @ (v * beta[..., None])                  # (m, b, h, c, dv)
    w = t @ (kb * jnp.exp(g)[..., None])           # (m, b, h, c, dk)
    qk = jnp.where(lower, jnp.einsum("...id,...jd->...ij", q, k) * decay, 0.0)

    def step(s, inp):
        qi, ki, ui, wi, gi, qki = inp
        vn = ui - wi @ s
        out = (qi * jnp.exp(gi)[..., None]) @ s + qki @ vn
        last = gi[..., -1:]
        s = s * jnp.exp(last)[..., None] + jnp.swapaxes(
            ki * jnp.exp(last - gi)[..., None], -1, -2) @ vn
        return s, out

    s, out = jax.lax.scan(step, s0, (q, k, u, w, g, qk))
    out = jnp.moveaxis(out, 0, 2).reshape(b, h, m * c, dv)
    return out[:, :, :n], s


def gdn(params, cfg, x):
    """Train/prefill.  x (b, s, d) -> ((b, s, d), final GDNState)."""
    b = x.shape[0]
    hk, hv, dk, dv = _dims(cfg)
    q, k, v, z, beta, g, conv = _inputs(params, cfg, x, None)

    def by_head(t):
        return jnp.swapaxes(t, 1, 2)

    o, s = _chunked(by_head(q), by_head(k), by_head(v.astype(jnp.float32)),
                    by_head(beta), by_head(g),
                    jnp.zeros((b, hv, dk, dv), jnp.float32))
    return _output(params, cfg, by_head(o), z, x.dtype), GDNState(conv=conv, s=s)


def gdn_decode(params, cfg, x, state: GDNState):
    """One position per lane.  x (b, 1, d) -> ((b, 1, d), new state)."""
    q, k, v, z, beta, g, conv = _inputs(params, cfg, x, state.conv)
    q, k, beta, g = q[:, 0], k[:, 0], beta[:, 0], g[:, 0]
    v = v[:, 0].astype(jnp.float32)
    s = state.s * jnp.exp(g)[..., None, None]
    delta = beta[..., None] * (v - jnp.einsum("bhkv,bhk->bhv", s, k))
    s = s + k[..., :, None] * delta[..., None, :]
    o = jnp.einsum("bhkv,bhk->bhv", s, q)
    out = _output(params, cfg, o[:, None], z, x.dtype)
    return out, GDNState(conv=conv.astype(state.conv.dtype), s=s)
