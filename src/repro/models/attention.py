"""GQA/MQA attention: RoPE (optionally on a leading share of each head's
dims), optional qk-norm, an optional sigmoid output gate, causal +
sliding-window masks, memory-bounded flash-style KV-block streaming for
long sequences, and a ring-buffer KV cache for decode.

Layout note: KV heads are broadcast to the full query-head count before
the score einsums ("repeat-KV").  This keeps every score/context tensor
shardable on the query-head axis for *all* assigned archs — including MQA
(kv=1) and GQA shapes whose kv-head or group counts don't divide the
model axis (e.g. 32 q heads = 8 kv x 4 groups on model=16).  The repeat
is a broadcast, and each device materializes only its own head shard.

Paths:
  * `full`   — one einsum; used for short train sequences.
  * `flash`  — lax.scan over KV blocks with online softmax; bounds memory
               at 32k/500k.  This is the pure-JAX reference of the Pallas
               kernel in repro.kernels.flash_attention (same math).
  * `decode` — single query position against the KV cache.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..sharding import Ax, shard_as
from .layers import apply_rope, dense_init, rms_norm, use_weight

NEG_INF = -1e30


def init_attention(key, cfg):
    hd = cfg.resolved_head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    # with an output gate, q_proj gives [query, gate] per head
    q_out = cfg.num_heads * hd * (2 if cfg.attn_gate else 1)
    params = {
        "wq": dense_init(k1, cfg.d_model, q_out, "embed", "heads")[0],
        "wk": dense_init(k2, cfg.d_model, cfg.num_kv_heads * hd, "embed", "kv_heads")[0],
        "wv": dense_init(k3, cfg.d_model, cfg.num_kv_heads * hd, "embed", "kv_heads")[0],
        "wo": dense_init(k4, cfg.num_heads * hd, cfg.d_model, "heads", "embed")[0],
    }
    axes = {
        "wq": Ax("embed", "heads"),
        "wk": Ax("embed", "kv_heads"),
        "wv": Ax("embed", "kv_heads"),
        "wo": Ax("heads", "embed"),
    }
    if cfg.qk_norm:
        params["q_norm"] = jnp.ones((hd,), jnp.float32)
        params["k_norm"] = jnp.ones((hd,), jnp.float32)
        axes["q_norm"] = Ax("head_dim")
        axes["k_norm"] = Ax("head_dim")
    return params, axes


class KVCache(NamedTuple):
    """KV cache; sized to the window (ring buffer) when window > 0 —
    ring-ness is derived statically from the `window` argument at the
    call sites, so the cache pytree holds only arrays."""

    k: jax.Array    # (b, S, kv_heads, hd)   S = max_len (or window)
    v: jax.Array
    pos: jax.Array  # (b,) int32: absolute position of next token per lane


def init_kv_cache(cfg, batch: int, max_len: int, window: int = 0,
                  dtype=jnp.bfloat16) -> KVCache:
    hd = cfg.resolved_head_dim
    size = min(window, max_len) if window else max_len
    shape = (batch, size, cfg.num_kv_heads, hd)
    return KVCache(
        k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
        pos=jnp.zeros((batch,), jnp.int32),
    )


def kv_cache_specs(cfg, batch: int, max_len: int, window: int = 0,
                   dtype=jnp.bfloat16) -> KVCache:
    """ShapeDtypeStruct version for the dry-run (no allocation)."""
    hd = cfg.resolved_head_dim
    size = min(window, max_len) if window else max_len
    shape = (batch, size, cfg.num_kv_heads, hd)
    sds = jax.ShapeDtypeStruct
    return KVCache(k=sds(shape, dtype), v=sds(shape, dtype),
                   pos=sds((batch,), jnp.int32))


class KVCacheQ(NamedTuple):
    """Int8-quantized KV cache (per-token, per-kv-head max-abs scales).

    Halves decode HBM traffic — the memory-bound decode lever for
    long-context dense models (codeqwen decode_32k)."""

    k: jax.Array        # int8 (b, S, kvh, hd)
    v: jax.Array
    k_scale: jax.Array  # f32 (b, S, kvh)
    v_scale: jax.Array
    pos: jax.Array


def init_kv_cache_q(cfg, batch: int, max_len: int, window: int = 0) -> KVCacheQ:
    hd = cfg.resolved_head_dim
    size = min(window, max_len) if window else max_len
    shape = (batch, size, cfg.num_kv_heads, hd)
    sshape = (batch, size, cfg.num_kv_heads)
    return KVCacheQ(k=jnp.zeros(shape, jnp.int8),
                    v=jnp.zeros(shape, jnp.int8),
                    k_scale=jnp.zeros(sshape, jnp.float32),
                    v_scale=jnp.zeros(sshape, jnp.float32),
                    pos=jnp.zeros((batch,), jnp.int32))


def kv_cache_q_specs(cfg, batch: int, max_len: int, window: int = 0) -> KVCacheQ:
    hd = cfg.resolved_head_dim
    size = min(window, max_len) if window else max_len
    shape = (batch, size, cfg.num_kv_heads, hd)
    sshape = (batch, size, cfg.num_kv_heads)
    sds = jax.ShapeDtypeStruct
    return KVCacheQ(k=sds(shape, jnp.int8), v=sds(shape, jnp.int8),
                    k_scale=sds(sshape, jnp.float32),
                    v_scale=sds(sshape, jnp.float32),
                    pos=sds((batch,), jnp.int32))


def _quantize_token(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """x (b, 1, kvh, hd) -> (int8 values, f32 scale (b, 1, kvh))."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _project_qkv(params, cfg, x, sin, cos):
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    dt = x.dtype
    wq = use_weight(params["wq"].astype(dt), cfg, None, "heads")
    wk = use_weight(params["wk"].astype(dt), cfg, None, "kv_heads")
    wv = use_weight(params["wv"].astype(dt), cfg, None, "kv_heads")
    gate = None
    if cfg.attn_gate:
        q, gate = jnp.split((x @ wq).reshape(b, s, cfg.num_heads, 2 * hd), 2,
                            axis=-1)
        gate = gate.reshape(b, s, cfg.num_heads * hd)
    else:
        q = (x @ wq).reshape(b, s, cfg.num_heads, hd)
    k = (x @ wk).reshape(b, s, cfg.num_kv_heads, hd)
    v = (x @ wv).reshape(b, s, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.norm_eps)
    q = _rotate(q, sin, cos, cfg.rotary_dim)
    k = _rotate(k, sin, cos, cfg.rotary_dim)
    q = shard_as(q, "batch", "seq", "heads", "head_dim")
    k = shard_as(k, "batch", "seq", "kv_heads", "head_dim")
    v = shard_as(v, "batch", "seq", "kv_heads", "head_dim")
    return q, k, v, gate


def _rotate(x, sin, cos, rotary_dim: int):
    """Rotary embedding of the first ``rotary_dim`` dims of each head; the
    rest pass through."""
    if rotary_dim == x.shape[-1]:
        return apply_rope(x, sin, cos)
    return jnp.concatenate([apply_rope(x[..., :rotary_dim], sin, cos),
                            x[..., rotary_dim:]], axis=-1)


def _gated(ctx, gate):
    """The attention output times sigmoid(gate), where there is a gate."""
    if gate is None:
        return ctx
    return ctx * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(ctx.dtype)


def _repeat_kv(k: jax.Array, num_heads: int) -> jax.Array:
    """(b, s, kvh, hd) -> (b, s, h, hd) broadcast across groups."""
    b, s, kvh, hd = k.shape
    g = num_heads // kvh
    if g == 1:
        return k
    k = jnp.broadcast_to(k[:, :, :, None, :], (b, s, kvh, g, hd))
    k = k.reshape(b, s, num_heads, hd)
    return shard_as(k, "batch", "seq", "heads", "head_dim")


def _mask(si: jax.Array, sj: jax.Array, window: int) -> jax.Array:
    """(i, j) allowed?  causal, optional sliding window."""
    m = sj[None, :] <= si[:, None]
    if window > 0:
        m &= (si[:, None] - sj[None, :]) < window
    return m


def _attend_full(q, k, v, cfg, window: int):
    """Single-einsum attention (short sequences)."""
    b, s, h, hd = q.shape
    k = _repeat_kv(k, h)
    v = _repeat_kv(v, h)
    scale = 1.0 / math.sqrt(hd)
    scores = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32) * scale
    scores = shard_as(scores, "batch", "heads", "seq", None)
    idx = jnp.arange(s)
    mask = _mask(idx, idx, window)
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhst,bthd->bshd", probs, v)
    return out


def _attend_flash(q, k, v, cfg, window: int, block: int = 1024):
    """Online-softmax streaming over KV blocks (pure-JAX flash reference).

    Memory is O(s * block) instead of O(s^2).  Matches the Pallas kernel
    in repro.kernels.flash_attention; tested against it."""
    b, s, h, hd = q.shape
    k = _repeat_kv(k, h)
    v = _repeat_kv(v, h)
    scale = 1.0 / math.sqrt(hd)
    nb = (s + block - 1) // block
    pad = nb * block - s
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(b, nb, block, h, hd).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nb, block, h, hd).transpose(1, 0, 2, 3, 4)
    qi = jnp.arange(s)

    def body(carry, inputs):
        m, l, acc = carry
        jblk, kj, vj = inputs
        kidx = jblk * block + jnp.arange(block)
        sc = jnp.einsum("bshd,bthd->bhst", q, kj).astype(jnp.float32) * scale
        sc = shard_as(sc, "batch", "heads", "seq", None)
        msk = kidx[None, :] <= qi[:, None]  # (s, block) causal
        if window > 0:
            msk &= (qi[:, None] - kidx[None, :]) < window
        msk &= (kidx < s)[None, :]
        sc = jnp.where(msk[None, None], sc, NEG_INF)
        m_new = jnp.maximum(m, sc.max(axis=-1))
        p = jnp.exp(sc - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhst,bthd->bhsd", p.astype(q.dtype), vj
        ).astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, s), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s), jnp.float32)
    a0 = jnp.zeros((b, h, s, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (jnp.arange(nb), kb, vb)
    )
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def attention(params, cfg, x, sin, cos, *, window: int = 0):
    """Train/prefill attention.  x: (b, s, d) -> (b, s, d)."""
    b, s, _ = x.shape
    q, k, v, gate = _project_qkv(params, cfg, x, sin, cos)
    if s > cfg.flash_threshold:
        ctx = _attend_flash(q, k, v, cfg, window)
    else:
        ctx = _attend_full(q, k, v, cfg, window)
    ctx = _gated(ctx.reshape(b, s, cfg.num_heads * cfg.resolved_head_dim), gate)
    wo = use_weight(params["wo"].astype(x.dtype), cfg, "heads", None)
    out = ctx @ wo
    return shard_as(out, "batch", "seq", "embed_act")


# the most of one layer's past K (or V) that decode reads in one pass: a
# pass that size is staged through the core's own memory (128 MiB on a
# TPU v5e) instead of copied out in HBM and read again
PAST_PASS_BYTES = 64 * 2**20


def lanes_per_pass(lanes: int, lane_bytes: int) -> int:
    """The most lanes, a divisor of `lanes`, whose past K fits
    `PAST_PASS_BYTES` (at least one)."""
    n = max(1, min(lanes, PAST_PASS_BYTES // lane_bytes))
    while lanes % n:
        n -= 1
    return n


def _attend_decode(qg, past, new, valid, scale):
    """One token's attention over its lanes' valid past slots and itself.

    qg (b, kvh, g, hd); past: the lanes' slots of this layer as the cache
    holds them (k, v: (b, S, kvh, hd), int8 ones with k_scale, v_scale:
    (b, S, kvh)); new: the token as the cache holds it, without S;
    valid (b, S).  Returns the context (b, kvh, g, hd), float32."""
    quant = "k_scale" in new
    if quant:
        # contract against int8 values; fold the per-token scale into the
        # scores/probs afterwards (keeps HBM reads at 1 byte/elem)
        qf = qg.astype(jnp.float32)
        sc = jnp.einsum("bkgd,btkd->bkgt", qf, past["k"].astype(jnp.float32))
        sc = sc * past["k_scale"].transpose(0, 2, 1)[:, :, None, :] * scale
        sc_new = jnp.einsum("bkgd,bkd->bkg", qf, new["k"].astype(jnp.float32))
        sc_new = sc_new * new["k_scale"][:, :, None] * scale
    else:
        sc = jnp.einsum("bkgd,btkd->bkgt", qg, past["k"].astype(qg.dtype)
                        ).astype(jnp.float32) * scale
        sc_new = jnp.einsum("bkgd,bkd->bkg", qg, new["k"].astype(qg.dtype)
                            ).astype(jnp.float32) * scale
    sc = jnp.where(valid[:, None, None, :], sc, NEG_INF)
    # softmax over the past slots and the new token
    top = jnp.maximum(sc.max(axis=-1), sc_new)
    e = jnp.exp(sc - top[..., None])
    e_new = jnp.exp(sc_new - top)
    total = e.sum(axis=-1) + e_new
    probs = e / total[..., None]
    p_new = (e_new / total)[..., None]
    if quant:
        pw = probs * past["v_scale"].transpose(0, 2, 1)[:, :, None, :]
        ctx = jnp.einsum("bkgt,btkd->bkgd", pw, past["v"].astype(jnp.float32))
        v_new = new["v_scale"][..., None] * new["v"].astype(jnp.float32)
    else:
        ctx = jnp.einsum("bkgt,btkd->bkgd", probs.astype(qg.dtype),
                         past["v"].astype(qg.dtype),
                         preferred_element_type=jnp.float32)
        v_new = new["v"].astype(jnp.float32)
    return ctx + p_new * v_new[:, :, None, :]


def attention_decode(params, cfg, x, sin, cos, cache,
                     *, window: int = 0, layer=None):
    """One-token decode.  x: (b, 1, d); cache holds past KV (bf16 KVCache
    or int8 KVCacheQ).

    With ``layer``, ``cache`` is a stack of layers' caches (a leading
    layer axis) and this is layer ``layer``: only the new token's K and V
    are written into the stack, in place, and the stack comes back.  The
    layer's past slots are read from the stack before the write, a pass
    of `lanes_per_pass` lanes at a time, and the new token is scored
    apart, so no copy of the layer is written back."""
    if layer is None:
        out, cache = attention_decode(params, cfg, x, sin, cos,
                                      jax.tree.map(lambda a: a[None], cache),
                                      window=window, layer=0)
        return out, jax.tree.map(lambda a: a[0], cache)
    b, s, _ = x.shape
    assert s == 1
    hd = cfg.resolved_head_dim
    q, k, v, gate = _project_qkv(params, cfg, x, sin, cos)
    size = cache.k.shape[2]
    pos = cache.pos[layer]                                        # (b,)
    # per-lane positions: each batch lane writes at its own slot (true
    # continuous batching — lanes restart independently, see serve.engine)
    slot = jax.lax.rem(pos, size) if window > 0 else pos         # (b,)
    # the new token as the cache holds it
    if isinstance(cache, KVCacheQ):
        kq, ks = _quantize_token(k)
        vq, vs = _quantize_token(v)
        new = dict(k=kq[:, 0], v=vq[:, 0], k_scale=ks[:, 0], v_scale=vs[:, 0])
    else:
        new = dict(k=k[:, 0].astype(cache.k.dtype),
                   v=v[:, 0].astype(cache.v.dtype))
    # the past slots that hold an earlier position of each lane; the slot
    # being written holds none (ring: slot t holds position pos - age)
    t = jnp.arange(size)
    if window > 0:
        age = jax.lax.rem(slot[:, None] - t[None, :] + size, size)  # (b,S)
        valid = (age > 0) & (age <= jnp.minimum(pos, size - 1)[:, None])
    else:
        valid = t[None, :] < pos[:, None]                           # (b,S)
    h = cfg.num_heads
    kvh = cfg.num_kv_heads
    # decode keeps KV un-repeated (grouped einsum): the cache is the
    # memory-bound object — broadcasting it g-fold would multiply HBM
    # traffic; the cache seq dim is sharded on the model axis instead
    # (rule 'seq_cache'), with GSPMD inserting the tiny softmax-stat
    # collectives.
    qg = q.reshape(b, kvh, h // kvh, hd)
    scale = 1.0 / math.sqrt(hd)
    n = lanes_per_pass(b, math.prod(cache.k.shape[2:])
                       * cache.k.dtype.itemsize)

    parts = []
    for lo in range(0, b, n):
        past = {f: jax.lax.dynamic_slice(a, (layer, lo) + (0,) * (a.ndim - 2),
                                         (1, n) + a.shape[2:])[0]
                for f in new for a in [getattr(cache, f)]}
        ctx = _attend_decode(qg[lo:lo + n], past,
                             {f: a[lo:lo + n] for f, a in new.items()},
                             valid[lo:lo + n], scale)
        # the next pass, and the writes below, wait for this pass: XLA
        # then reads one pass at a time into the core's memory, instead
        # of copying the layer out in HBM to keep it past the write
        ctx, cache = jax.lax.optimization_barrier((ctx, cache))
        parts.append(ctx)
    ctx = jnp.concatenate(parts)
    ctx = _gated(ctx.astype(q.dtype).reshape(b, 1, h * hd), gate)
    out = ctx @ params["wo"].astype(x.dtype)
    out = shard_as(out, "batch", "seq", "embed_act")
    lanes = jnp.arange(b)
    written = {f: getattr(cache, f).at[layer, lanes, slot].set(a)
               for f, a in new.items()}
    return out, cache._replace(pos=cache.pos.at[layer].set(pos + 1),
                               **written)
