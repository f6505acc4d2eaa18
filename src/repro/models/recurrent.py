"""Recurrent mixers: mLSTM / sLSTM (xLSTM, arXiv:2405.04517) and RG-LRU
(RecurrentGemma / Griffin, arXiv:2402.19427).

TPU adaptation notes: training/prefill uses parallel forms
(chunkwise mLSTM with carried (C, n, m) state; associative-scan RG-LRU);
decode uses O(1) recurrent state updates.  sLSTM has no parallel form
(hidden-to-hidden recurrence) and is scanned over time — the xLSTM pattern
keeps sLSTM to 1-in-8 blocks so this stays cheap.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..sharding import Ax, shard_as
from .layers import causal_conv1d, conv1d_init, dense_init

# ---------------------------------------------------------------------------
# mLSTM — matrix-memory LSTM
# ---------------------------------------------------------------------------


class MLSTMState(NamedTuple):
    c: jax.Array  # (b, h, hd, hd) matrix memory
    n: jax.Array  # (b, h, hd) normalizer
    m: jax.Array  # (b, h) stabilizer (log-space)


def init_mlstm(key, cfg):
    d = cfg.d_model
    h = cfg.num_heads
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 6)
    params = {
        "wq": dense_init(ks[0], d, h * hd, "embed", "heads")[0],
        "wk": dense_init(ks[1], d, h * hd, "embed", "heads")[0],
        "wv": dense_init(ks[2], d, h * hd, "embed", "heads")[0],
        "wo": dense_init(ks[3], h * hd, d, "heads", "embed")[0],
        "wi_gate": dense_init(ks[4], d, h, "embed", "heads")[0],
        "wf_gate": dense_init(ks[5], d, h, "embed", "heads")[0],
        "f_bias": jnp.full((h,), 3.0, jnp.float32),  # forget-open init
        "i_bias": jnp.zeros((h,), jnp.float32),
    }
    axes = {
        "wq": Ax("embed", "heads"), "wk": Ax("embed", "heads"),
        "wv": Ax("embed", "heads"), "wo": Ax("heads", "embed"),
        "wi_gate": Ax("embed", "heads"), "wf_gate": Ax("embed", "heads"),
        "f_bias": Ax("heads"), "i_bias": Ax("heads"),
    }
    return params, axes


def init_mlstm_state(cfg, batch: int, dtype=jnp.float32) -> MLSTMState:
    h, hd = cfg.num_heads, cfg.resolved_head_dim
    return MLSTMState(
        c=jnp.zeros((batch, h, hd, hd), dtype),
        n=jnp.zeros((batch, h, hd), dtype),
        m=jnp.full((batch, h), -1e30, dtype),
    )


def mlstm_state_specs(cfg, batch: int, dtype=jnp.float32) -> MLSTMState:
    h, hd = cfg.num_heads, cfg.resolved_head_dim
    sds = jax.ShapeDtypeStruct
    return MLSTMState(c=sds((batch, h, hd, hd), dtype),
                      n=sds((batch, h, hd), dtype),
                      m=sds((batch, h), dtype))


def _mlstm_proj(params, cfg, x):
    b, s, _ = x.shape
    h, hd = cfg.num_heads, cfg.resolved_head_dim
    dt = x.dtype
    q = (x @ params["wq"].astype(dt)).reshape(b, s, h, hd) / (hd ** 0.5)
    k = (x @ params["wk"].astype(dt)).reshape(b, s, h, hd) / (hd ** 0.5)
    v = (x @ params["wv"].astype(dt)).reshape(b, s, h, hd)
    logi = (x.astype(jnp.float32) @ params["wi_gate"]) + params["i_bias"]
    logf = jax.nn.log_sigmoid(
        (x.astype(jnp.float32) @ params["wf_gate"]) + params["f_bias"])
    return q, k, v, logi, logf  # gates: (b, s, h) in log space


def mlstm_parallel(params, cfg, x, chunk: int = 256,
                   state: Optional[MLSTMState] = None):
    """Chunkwise-parallel mLSTM: intra-chunk quadratic + carried state.

    Memory O(s * chunk); exact (up to fp) match of the recurrent form.
    Returns (y, final_state).
    """
    b, s, d = x.shape
    h, hd = cfg.num_heads, cfg.resolved_head_dim
    q, k, v, logi, logf = _mlstm_proj(params, cfg, x)
    if state is None:
        state = init_mlstm_state(cfg, b)
    nchunk = (s + chunk - 1) // chunk
    pad = nchunk * chunk - s
    if pad:
        zf = lambda a: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        q, k, v = zf(q), zf(k), zf(v)
        logi = jnp.pad(logi, ((0, 0), (0, pad), (0, 0)), constant_values=-1e30)
        logf = jnp.pad(logf, ((0, 0), (0, pad), (0, 0)))

    def to_chunks(a):
        return a.reshape((b, nchunk, chunk) + a.shape[2:]).transpose(
            (1, 0, 2) + tuple(range(3, a.ndim + 1)))

    qc, kc, vc = to_chunks(q), to_chunks(k), to_chunks(v)
    lic, lfc = to_chunks(logi), to_chunks(logf)

    def body(carry, inp):
        c, n, m = carry                      # (b,h,hd,hd), (b,h,hd), (b,h)
        qj, kj, vj, li, lf = inp             # (b,chunk,h,...)
        csum = jnp.cumsum(lf, axis=1)        # (b, chunk, h)
        total = csum[:, -1]                  # (b, h)
        # log decay from chunk start to position t (inclusive of f_t)
        # intra-chunk pair weights: D[t,s'] = csum[t]-csum[s'] + li[s']
        a_pair = (csum[:, :, None, :] - csum[:, None, :, :]
                  + li[:, None, :, :])       # (b, t, s', h)
        tri = jnp.tril(jnp.ones((chunk, chunk), bool))
        a_pair = jnp.where(tri[None, :, :, None], a_pair, -jnp.inf)
        # inter-chunk: contribution of carried state to position t
        a_carry = csum + m[:, None, :]       # (b, t, h)
        m_intra = a_pair.max(axis=2)         # (b, t, h)
        m_new_t = jnp.maximum(a_carry, m_intra)
        # stabilized weights
        w_pair = jnp.exp(a_pair - m_new_t[:, :, None, :])     # (b,t,s',h)
        w_carry = jnp.exp(a_carry - m_new_t)                   # (b,t,h)
        # scores
        sc = jnp.einsum("bthd,bshd->btsh", qj, kj).astype(jnp.float32)
        sc = sc * w_pair
        num_intra = jnp.einsum("btsh,bshd->bthd", sc.astype(qj.dtype), vj)
        den_intra = sc.astype(jnp.float32).sum(axis=2)           # (b,t,h)
        num_carry = jnp.einsum(
            "bthd,bhde->bthe", qj.astype(jnp.float32) * w_carry[..., None],
            c)
        den_carry = jnp.einsum(
            "bthd,bhd->bth", qj.astype(jnp.float32) * w_carry[..., None], n)
        # xLSTM normalizer: max(|q . n_cum|, exp(-m)) on the *signed* sum
        den = jnp.maximum(jnp.abs(den_intra + den_carry), jnp.exp(-m_new_t))
        y = (num_intra.astype(jnp.float32) + num_carry) / den[..., None]
        # ---- update carried state to end of chunk -----------------------
        m_end = jnp.maximum(total + m, (total[:, None] - csum + li).max(1))
        decay_c = jnp.exp(total + m - m_end)                   # (b, h)
        kw = jnp.exp(total[:, None] - csum + li - m_end[:, None])  # (b,t,h)
        c_new = c * decay_c[..., None, None] + jnp.einsum(
            "bthd,bthe->bhde", (kj.astype(jnp.float32) * kw[..., None]),
            vj.astype(jnp.float32))
        n_new = n * decay_c[..., None] + jnp.einsum(
            "bth,bthd->bhd", kw, kj.astype(jnp.float32))
        return (c_new, n_new, m_end), y.astype(x.dtype)

    (c, n, m), ys = jax.lax.scan(
        body, (state.c, state.n, state.m), (qc, kc, vc, lic, lfc))
    y = ys.transpose(1, 0, 2, 3, 4).reshape(b, nchunk * chunk, h, hd)
    y = y[:, :s].reshape(b, s, h * hd)
    out = y @ params["wo"].astype(x.dtype)
    out = shard_as(out, "batch", "seq", "embed_act")
    return out, MLSTMState(c=c, n=n, m=m)


def mlstm_decode(params, cfg, x, state: MLSTMState):
    """One-token recurrent update (O(1) state)."""
    b, s, d = x.shape
    assert s == 1
    h, hd = cfg.num_heads, cfg.resolved_head_dim
    q, k, v, logi, logf = _mlstm_proj(params, cfg, x)
    q, k, v = q[:, 0], k[:, 0], v[:, 0]          # (b, h, hd)
    li, lf = logi[:, 0], logf[:, 0]              # (b, h)
    m_new = jnp.maximum(lf + state.m, li)
    f = jnp.exp(lf + state.m - m_new)
    i = jnp.exp(li - m_new)
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    c = state.c * f[..., None, None] + i[..., None, None] * (
        kf[..., :, None] * vf[..., None, :])
    n = state.n * f[..., None] + i[..., None] * kf
    qf = q.astype(jnp.float32)
    num = jnp.einsum("bhd,bhde->bhe", qf, c)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", qf, n)),
                      jnp.exp(-m_new))
    y = (num / den[..., None]).astype(x.dtype).reshape(b, 1, h * hd)
    out = y @ params["wo"].astype(x.dtype)
    out = shard_as(out, "batch", "seq", "embed_act")
    return out, MLSTMState(c=c, n=n, m=m_new)


# ---------------------------------------------------------------------------
# sLSTM — scalar-memory LSTM with block-diagonal recurrence
# ---------------------------------------------------------------------------


class SLSTMState(NamedTuple):
    c: jax.Array  # (b, d) cell
    n: jax.Array  # (b, d) normalizer
    h: jax.Array  # (b, d) hidden
    m: jax.Array  # (b, d) stabilizer


def init_slstm(key, cfg):
    d = cfg.d_model
    h = cfg.num_heads
    hd = d // h
    ks = jax.random.split(key, 3)
    params = {
        # input projections for 4 gates (i, f, z, o)
        "w": dense_init(ks[0], d, 4 * d, "embed", "mlp")[0],
        # block-diagonal recurrent weights per head: (4, h, hd, hd)
        "r": jax.random.normal(ks[1], (4, h, hd, hd), jnp.float32)
        * (1.0 / hd) ** 0.5,
        "b": jnp.concatenate([
            jnp.zeros((d,), jnp.float32),           # i
            jnp.full((d,), 3.0, jnp.float32),       # f (open)
            jnp.zeros((2 * d,), jnp.float32),       # z, o
        ]),
    }
    axes = {"w": Ax("embed", "mlp"), "r": Ax(None, "heads", None, None),
            "b": Ax("mlp")}
    return params, axes


def init_slstm_state(cfg, batch: int, dtype=jnp.float32) -> SLSTMState:
    d = cfg.d_model
    z = jnp.zeros((batch, d), dtype)
    return SLSTMState(c=z, n=z, h=z, m=jnp.full((batch, d), -1e30, dtype))


def slstm_state_specs(cfg, batch: int, dtype=jnp.float32) -> SLSTMState:
    d = cfg.d_model
    sds = jax.ShapeDtypeStruct
    return SLSTMState(c=sds((batch, d), dtype), n=sds((batch, d), dtype),
                      h=sds((batch, d), dtype), m=sds((batch, d), dtype))


def _slstm_step(params, cfg, state: SLSTMState, zx):
    """zx: (b, 4d) pre-activations from the input projection."""
    b = zx.shape[0]
    d = cfg.d_model
    h = cfg.num_heads
    hd = d // h
    hh = state.h.reshape(b, h, hd)
    rec = jnp.einsum("bhd,ghde->gbhe", hh.astype(jnp.float32), params["r"])
    rec = rec.reshape(4, b, d)
    z = zx.astype(jnp.float32).reshape(b, 4, d).transpose(1, 0, 2) + rec
    li = z[0]
    lf = jax.nn.log_sigmoid(z[1])
    cell_in = jnp.tanh(z[2])
    o = jax.nn.sigmoid(z[3])
    m_new = jnp.maximum(lf + state.m, li)
    f = jnp.exp(lf + state.m - m_new)
    i = jnp.exp(li - m_new)
    c = f * state.c + i * cell_in
    n = jnp.maximum(f * state.n + i, 1e-6)
    hnew = o * (c / n)
    return SLSTMState(c=c, n=n, h=hnew, m=m_new)


def slstm(params, cfg, x, state: Optional[SLSTMState] = None):
    """Sequential scan over time (no parallel form exists)."""
    b, s, d = x.shape
    if state is None:
        state = init_slstm_state(cfg, b)
    zx = x @ params["w"].astype(x.dtype) + params["b"].astype(x.dtype)

    def body(st, z_t):
        st2 = _slstm_step(params, cfg, st, z_t)
        return st2, st2.h

    final, hs = jax.lax.scan(body, state, zx.transpose(1, 0, 2))
    y = hs.transpose(1, 0, 2).astype(x.dtype)
    return shard_as(y, "batch", "seq", "embed_act"), final


def slstm_decode(params, cfg, x, state: SLSTMState):
    b, s, d = x.shape
    assert s == 1
    zx = (x @ params["w"].astype(x.dtype) + params["b"].astype(x.dtype))[:, 0]
    st = _slstm_step(params, cfg, state, zx)
    return st.h[:, None, :].astype(x.dtype), st


# ---------------------------------------------------------------------------
# RG-LRU — real-gated linear recurrent unit (Griffin / RecurrentGemma)
# ---------------------------------------------------------------------------


class RGLRUState(NamedTuple):
    h: jax.Array          # (b, w) recurrent state
    conv: jax.Array       # (b, conv_width-1, w) conv tail


def init_rglru(key, cfg):
    d = cfg.d_model
    w = cfg.lru_width or d
    ks = jax.random.split(key, 6)
    # a-parameter initialized so a ~ U(0.9, 0.999) at r=1
    lam = jnp.log(jnp.expm1(-jnp.log(
        jax.random.uniform(ks[0], (w,), jnp.float32, 0.9, 0.999)) / 8.0))
    params = {
        "wx": dense_init(ks[1], d, w, "embed", "lru")[0],
        "wgate": dense_init(ks[2], d, w, "embed", "lru")[0],
        "conv": conv1d_init(ks[3], cfg.conv_width, w)[0],
        "w_r": dense_init(ks[4], w, w, "lru", "lru")[0],
        "w_i": dense_init(ks[5], w, w, "lru", "lru")[0],
        "lam": lam,
        "wo": dense_init(jax.random.fold_in(key, 7), w, d, "lru", "embed")[0],
    }
    axes = {
        "wx": Ax("embed", "lru"), "wgate": Ax("embed", "lru"),
        "conv": Ax("conv", "lru"), "w_r": Ax("lru", "lru"),
        "w_i": Ax("lru", "lru"), "lam": Ax("lru"),
        "wo": Ax("lru", "embed"),
    }
    return params, axes


def init_rglru_state(cfg, batch: int, dtype=jnp.float32) -> RGLRUState:
    w = cfg.lru_width or cfg.d_model
    return RGLRUState(h=jnp.zeros((batch, w), dtype),
                      conv=jnp.zeros((batch, cfg.conv_width - 1, w), dtype))


def rglru_state_specs(cfg, batch: int, dtype=jnp.float32) -> RGLRUState:
    w = cfg.lru_width or cfg.d_model
    sds = jax.ShapeDtypeStruct
    return RGLRUState(h=sds((batch, w), dtype),
                      conv=sds((batch, cfg.conv_width - 1, w), dtype))


_LRU_C = 8.0


def _rglru_coeffs(params, u):
    """u: (b, s, w) conv output -> per-step (a, bx) of h = a*h + bx."""
    uf = u.astype(jnp.float32)
    r = jax.nn.sigmoid(uf @ params["w_r"])
    i = jax.nn.sigmoid(uf @ params["w_i"])
    log_a = -_LRU_C * jax.nn.softplus(params["lam"]) * r
    a = jnp.exp(log_a)
    # sqrt(1 - a^2) multiplier keeps the state norm bounded
    bx = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) * (i * uf)
    return a, bx


def rglru(params, cfg, x, state: Optional[RGLRUState] = None):
    """Griffin recurrent block: gate branch * (conv -> RG-LRU) branch."""
    b, s, d = x.shape
    if state is None:
        state = init_rglru_state(cfg, b)
    dt = x.dtype
    gate = jax.nn.gelu((x @ params["wgate"].astype(dt)), approximate=True)
    u = x @ params["wx"].astype(dt)
    u, conv_state = causal_conv1d(u, params["conv"], state.conv
                                  if state.conv.shape[1] else None)
    a, bx = _rglru_coeffs(params, u)
    # associative linear recurrence h_t = a_t h_{t-1} + bx_t
    a0 = jnp.concatenate([jnp.ones((b, 1, a.shape[-1]), a.dtype), a], axis=1)
    b0 = jnp.concatenate([state.h[:, None, :].astype(bx.dtype), bx], axis=1)

    def comb(l, r):
        al, bl = l
        ar, br = r
        return al * ar, bl * ar + br

    _, hs = jax.lax.associative_scan(comb, (a0, b0), axis=1)
    hs = hs[:, 1:]  # drop the injected initial state
    y = (hs.astype(dt) * gate) @ params["wo"].astype(dt)
    y = shard_as(y, "batch", "seq", "embed_act")
    return y, RGLRUState(h=hs[:, -1], conv=conv_state.astype(state.conv.dtype))


def rglru_decode(params, cfg, x, state: RGLRUState):
    b, s, d = x.shape
    assert s == 1
    dt = x.dtype
    gate = jax.nn.gelu((x @ params["wgate"].astype(dt)), approximate=True)
    u = x @ params["wx"].astype(dt)
    u, conv_state = causal_conv1d(u, params["conv"], state.conv)
    a, bx = _rglru_coeffs(params, u)
    h = a[:, 0] * state.h + bx[:, 0]
    y = (h[:, None, :].astype(dt) * gate) @ params["wo"].astype(dt)
    y = shard_as(y, "batch", "seq", "embed_act")
    return y, RGLRUState(h=h, conv=conv_state.astype(state.conv.dtype))
