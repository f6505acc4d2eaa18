"""Plain float32 Qwen3-Next forward pass, for one sequence.

Written from the published architecture (Qwen/Qwen3-Next-80B-A3B
``config.json`` and ``modeling_qwen3_next.py``), in ``jax.numpy`` at
``highest`` matmul precision, with no cache and no batching; it imports
nothing of the program, and shares with ``qwen3.py`` only its generic
helpers (matmul with the fp8 control, RMSNorm, the output head and the
gaps).  Layers come in periods of ``full_attention_interval``: the last
of each period is gated attention, the others Gated DeltaNet.

- Gated DeltaNet: ``in_proj_qkvz`` grouped per key head as [q, k, v, z]
  (v and z of the head's ``linear_num_value_heads / linear_num_key_heads``
  value heads), ``in_proj_ba`` per key head as [b, a]; a causal depthwise
  conv of width ``linear_conv_kernel_dim`` over [all q, all k, all v], no
  bias, then SiLU; q and k repeated to the value heads (value head ``j``
  reads key head ``j // r``), L2-normalised, q scaled by key_dim^-1/2;
  ``beta = sigmoid(b)``, ``g = -exp(A_log) * softplus(a + dt_bias)``; per
  value head and position, in order, ``S <- S exp(g)``,
  ``S <- S + k (beta (v - S^T k))^T``, ``o = S^T q``; then
  ``rms(o) * w * silu(z)`` per head and ``out_proj``.
- Gated attention: ``q_proj`` gives [query, gate] per head; RMSNorm of
  each query and key head; rotary embedding (rotate-half, base
  ``rope_theta``) on the first ``partial_rotary_factor`` of each head's
  dims only; causal grouped-query softmax attention; the output times
  ``sigmoid(gate)``; ``o_proj``.
- MoE in every layer: softmax over all ``published`` experts' router
  logits, top ``num_experts_per_tok``, the gates renormalised to sum to
  one; the SwiGLU experts this chip holds (``num_experts`` of them, ids
  0 .. num_experts - 1) weighted by their gates, the others' part left
  out; plus ``sigmoid(shared_expert_gate . x) * shared_expert(x)``.

The published RMSNorms scale by ``1 + w``; the weights here hold that
scale itself (``1 + w``), as the program does, so every norm below
multiplies by the stored scale; the gated norm's ``w`` is stored as is.
The multi-token-prediction head is left out: serving does not use it.

Weights are read from the benchmark's tree (``bench.weights``, the
layout the program serves: pattern position ``p`` of period ``g`` is
``groups[p][g]``) and upcast from bf16 one layer at a time.
``fp8=True`` is the control: every matmul's operands rounded through
float8 e4m3 as in ``qwen3.py``; the recurrence stays in float32.

It is also the family's module for the benchmark: ``SOURCE_KEYS``,
``used_keys``, ``conventions``, ``PUBLISHED_FIELDS`` (the routed
experts, cut to the share one chip holds), ``counts``, ``gdn_counts``
and ``DRAWS`` (``A_log``, ``dt_bias`` and the conv).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reckon import Counts
from bench.reference.qwen3 import (BF16, BUCKET, F32, HIGHEST, _embed_rows,
                                   _final, _gaps, _mm, _rms, _table)

F32_BYTES = 4

SOURCE_KEYS = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "vocab_size": "vocab_size",
    "tie_word_embeddings": "tie_embeddings",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "partial_rotary_factor": "rotary_fraction",
    "linear_conv_kernel_dim": "conv_width",
    "linear_num_key_heads": "gdn_key_heads",
    "linear_num_value_heads": "gdn_value_heads",
    "linear_key_head_dim": "gdn_key_dim",
    "linear_value_head_dim": "gdn_value_dim",
    "num_experts": "moe.held",
    "num_experts_per_tok": "moe.top_k",
    "moe_intermediate_size": "moe.d_ff",
    "shared_expert_intermediate_size": "moe.shared_d_ff",
}
# the experts a chip holds are a share of the published count, which the
# router still scores
PUBLISHED_FIELDS = {"num_experts": "moe.num_experts"}


def _uniform_log(key, shape):
    return jnp.log(jax.random.uniform(key, shape, F32, 0.0, 16.0))


def _ones(key, shape):
    return jnp.ones(shape, F32)


def _conv(key, shape):
    # fan-in is the kernel's width, not the channels
    return jax.random.normal(key, shape, F32) * shape[-2] ** -0.5


DRAWS = {"mixer.A_log": _uniform_log, "mixer.dt_bias": _ones,
         "mixer.conv": _conv}


def _require(cond: bool, what: str) -> None:
    if not cond:
        raise ValueError(f"configuration file: {what}")


def used_keys(config: dict) -> list[str]:
    """The source keys the program's model is built from.  Every layer is
    sparse, so the dense FFN width is never built."""
    _require(config.get("decoder_sparse_step", 1) == 1
             and not config.get("mlp_only_layers"),
             "the program builds every layer sparse")
    return [k for k in SOURCE_KEYS if k in config]


def conventions(config: dict, cfg) -> None:
    """Conventions of the family that the source states in words or in
    its modeling code, held against the program's ``ModelConfig``."""
    period = config["full_attention_interval"]
    _require(config["model_type"] == "qwen3_next", "a qwen3_next model")
    _require(cfg.block_pattern == ("gdn",) * (period - 1) + ("attn",)
             and config["num_hidden_layers"] % period == 0,
             "whole periods of DeltaNet layers, then one attention layer")
    _require(config["hidden_act"] == "silu" and cfg.activation == "swiglu",
             "SwiGLU experts")
    _require(config["torch_dtype"] == cfg.compute_dtype == "bfloat16",
             "bf16 weights")
    _require(cfg.qk_norm and cfg.attn_gate and cfg.kv_cache_dtype == "bfloat16",
             "q/k RMSNorm, the attention output gate and a bf16 cache")
    _require(config["norm_topk_prob"] and cfg.moe.dispatch == "dense",
             "renormalised top-k gates")


def _layers(c: dict) -> tuple[int, int]:
    """(DeltaNet layers, attention layers)."""
    n = c["num_hidden_layers"] // c["full_attention_interval"]
    return c["num_hidden_layers"] - n, n


def _gdn_sizes(c: dict) -> tuple[int, int, int]:
    """(key width, value width, conv channels) of a DeltaNet layer."""
    kd = c["linear_num_key_heads"] * c["linear_key_head_dim"]
    vd = c["linear_num_value_heads"] * c["linear_value_head_dim"]
    return kd, vd, 2 * kd + vd


def _gdn_params(c: dict) -> int:
    d = c["hidden_size"]
    kd, vd, _ = _gdn_sizes(c)
    return d * (2 * kd + 2 * vd) + d * 2 * c["linear_num_value_heads"] + vd * d


def _attn_params(c: dict) -> int:
    d, hd = c["hidden_size"], c["head_dim"]
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    return 2 * d * h * hd + 2 * d * kv * hd + h * hd * d


def _recurrence_flops(c: dict) -> int:
    """Per position and DeltaNet layer: S^T k, the rank-one update and
    S^T q, each a multiply-add per state entry."""
    return (6 * c["linear_num_value_heads"] * c["linear_key_head_dim"]
            * c["linear_value_head_dim"])


def _gdn_bytes(c: dict) -> tuple[int, int]:
    """(weight bytes, state bytes per lane) of one DeltaNet layer."""
    kd, vd, ch = _gdn_sizes(c)
    weights = (_gdn_params(c) + c["linear_conv_kernel_dim"] * ch
               + 2 * c["linear_num_value_heads"] + c["linear_value_head_dim"])
    state = (F32_BYTES * c["linear_num_value_heads"] * c["linear_key_head_dim"]
             * c["linear_value_head_dim"]
             + BF16 * (c["linear_conv_kernel_dim"] - 1) * ch)
    return BF16 * weights, state


def counts(c: dict) -> Counts:
    """Model FLOPs count each multiply-add as two: the projections, the
    DeltaNet conv and recurrence, the router over the published experts,
    the routed experts this chip holds (``num_experts_per_tok`` times the
    held share on average), the shared expert and its gate, and the
    output head per position; attention ``4 * heads * head_dim`` per
    attended position and attention layer.  Embedding lookups, norms,
    gates' elementwise work and softmax are left out.  Bytes: every
    weight once in bf16, each cached position's keys and values, and each
    lane's DeltaNet state (float32 S, bf16 conv inputs)."""
    d = c["hidden_size"]
    n_gdn, n_attn = _layers(c)
    layers = n_gdn + n_attn
    experts, published = c["num_experts"], c["published"]["num_experts"]
    expert = 3 * d * c["moe_intermediate_size"]
    shared = 3 * d * c["shared_expert_intermediate_size"] + d
    routed = c["num_experts_per_tok"] * experts * expert // published
    ffn_flops = d * published + routed + shared
    _, _, ch = _gdn_sizes(c)
    conv = c["linear_conv_kernel_dim"] * ch
    per_position = 2 * (n_gdn * (_gdn_params(c) + conv)
                        + n_attn * _attn_params(c)
                        + layers * ffn_flops + d * c["vocab_size"])
    per_position += n_gdn * _recurrence_flops(c)
    gdn_w, gdn_state = _gdn_bytes(c)
    ffn_held = d * published + experts * expert + shared + d
    tables = (1 if c["tie_word_embeddings"] else 2) * c["vocab_size"] * d
    attn_held = _attn_params(c) + 2 * c["head_dim"] + d
    weights = (BF16 * (tables + n_attn * attn_held + layers * ffn_held + d)
               + n_gdn * (gdn_w + BF16 * d))
    return Counts(
        per_position=per_position,
        per_attended=4 * n_attn * c["num_attention_heads"] * c["head_dim"],
        weights=weights,
        cache=BF16 * 2 * n_attn * c["num_key_value_heads"] * c["head_dim"],
        state=n_gdn * gdn_state)


def gdn_counts(c: dict) -> Counts:
    """The DeltaNet mixers alone (their norms, projections, conv and
    recurrence; no attention, FFN or head), by the rules of ``counts``."""
    n_gdn, _ = _layers(c)
    _, _, ch = _gdn_sizes(c)
    gdn_w, gdn_state = _gdn_bytes(c)
    return Counts(
        per_position=n_gdn * (2 * (_gdn_params(c) + c["linear_conv_kernel_dim"]
                                   * ch) + _recurrence_flops(c)),
        per_attended=0, weights=n_gdn * gdn_w, cache=0,
        state=n_gdn * gdn_state)


def _spec(conf: dict, fp8: bool) -> tuple:
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "rope_theta", "rms_norm_eps", "num_experts",
            "num_experts_per_tok", "partial_rotary_factor",
            "linear_conv_kernel_dim", "linear_num_key_heads",
            "linear_num_value_heads", "linear_key_head_dim",
            "linear_value_head_dim")
    return tuple((k, conf[k]) for k in keys) + (("fp8", fp8),)


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _ffn(x, ff, w, c):
    """MoE of the held experts plus the shared expert; ``w(a)`` upcasts
    this layer's slice of a stacked leaf, ``w(a, e)`` expert ``e``'s."""
    fp8 = c["fp8"]
    n = x.shape[0]
    h = x
    probs = jax.nn.softmax(_mm(h, w(ff["router"]), fp8), axis=-1)
    top, idx = jax.lax.top_k(probs, c["num_experts_per_tok"])
    gates = top / top.sum(-1, keepdims=True)
    comb = jnp.zeros_like(probs).at[jnp.arange(n)[:, None], idx].add(gates)

    def expert(y, e):
        out = _mm(jax.nn.silu(_mm(h, w(ff["wg"], e), fp8))
                  * _mm(h, w(ff["wi"], e), fp8), w(ff["wo"], e), fp8)
        return y + comb[:, e, None] * out, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                        jnp.arange(c["num_experts"]))
    sh = ff["shared"]
    shared = _mm(jax.nn.silu(_mm(h, w(sh["wg"]), fp8)) * _mm(h, w(sh["wi"]), fp8),
                 w(sh["wo"]), fp8)
    return y + jax.nn.sigmoid(_mm(h, w(ff["shared_gate"]), fp8)) * shared


def _slicer(i):
    def w(a, e=None):
        return (a[i] if e is None else a[i, e]).astype(F32)
    return w


@functools.partial(jax.jit, static_argnames="spec")
def _gdn_layer(x, lw, i, spec):
    c = dict(spec)
    fp8, eps = c["fp8"], c["rms_norm_eps"]
    hk, hv = c["linear_num_key_heads"], c["linear_num_value_heads"]
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    r, width = hv // hk, c["linear_conv_kernel_dim"]
    n = x.shape[0]
    w = _slicer(i)
    m = lw["mixer"]
    h = _rms(x, w(lw["norm1"]), eps)
    qkvz = _mm(h, w(m["in_qkvz"]), fp8).reshape(n, hk, 2 * dk + 2 * r * dv)
    q, k = qkvz[..., :dk], qkvz[..., dk:2 * dk]
    v = qkvz[..., 2 * dk:2 * dk + r * dv].reshape(n, hv * dv)
    z = qkvz[..., 2 * dk + r * dv:].reshape(n, hv, dv)
    ba = _mm(h, w(m["in_ba"]), fp8).reshape(n, hk, 2 * r)
    beta = jax.nn.sigmoid(ba[..., :r].reshape(n, hv))
    g = -jnp.exp(w(m["A_log"])) * jax.nn.softplus(ba[..., r:].reshape(n, hv)
                                                  + w(m["dt_bias"]))
    mixed = jnp.concatenate([q.reshape(n, hk * dk), k.reshape(n, hk * dk), v], -1)
    kern = w(m["conv"])                                  # (width, channels)
    padded = jnp.concatenate([jnp.zeros((width - 1, mixed.shape[1]), F32), mixed])
    mixed = jax.nn.silu(sum(padded[j:j + n] * kern[j] for j in range(width)))
    q = jnp.repeat(mixed[:, :hk * dk].reshape(n, hk, dk), r, axis=1)
    k = jnp.repeat(mixed[:, hk * dk:2 * hk * dk].reshape(n, hk, dk), r, axis=1)
    v = mixed[:, 2 * hk * dk:].reshape(n, hv, dv)
    q = _l2(q) / math.sqrt(dk)
    k = _l2(k)

    def position(s, inp):
        qt, kt, vt, bt, gt = inp
        s = s * jnp.exp(gt)[:, None, None]
        mem = jnp.einsum("hkv,hk->hv", s, kt, precision=HIGHEST)
        s = s + kt[:, :, None] * (bt[:, None] * (vt - mem))[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, qt, precision=HIGHEST)

    _, o = jax.lax.scan(position, jnp.zeros((hv, dk, dv), F32),
                        (q, k, v, beta, g))
    o = _rms(o, w(m["out_norm"]), eps) * jax.nn.silu(z)
    x = x + _mm(o.reshape(n, hv * dv), w(m["out"]), fp8)
    return x + _ffn(_rms(x, w(lw["norm2"]), eps), lw["ffn"], w, c)


def _rope_part(x, theta: float, dims: int):
    """Rotate-half rotary embedding of the first ``dims`` of each head of
    x (L, heads, hd), positions 0..L-1; the rest pass through."""
    half = dims // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:dims]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., dims:]], -1)


@functools.partial(jax.jit, static_argnames="spec")
def _attn_layer(x, lw, i, spec):
    c = dict(spec)
    fp8, eps = c["fp8"], c["rms_norm_eps"]
    h_, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    dims = int(hd * c["partial_rotary_factor"])
    n = x.shape[0]
    w = _slicer(i)
    at = lw["mixer"]
    h = _rms(x, w(lw["norm1"]), eps)
    qg = _mm(h, w(at["wq"]), fp8).reshape(n, h_, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:].reshape(n, h_ * hd)
    k = _mm(h, w(at["wk"]), fp8).reshape(n, kv, hd)
    v = _mm(h, w(at["wv"]), fp8).reshape(n, kv, hd)
    q = _rope_part(_rms(q, w(at["q_norm"]), eps), c["rope_theta"], dims)
    k = _rope_part(_rms(k, w(at["k_norm"]), eps), c["rope_theta"], dims)
    k = jnp.repeat(k, h_ // kv, axis=1)
    v = jnp.repeat(v, h_ // kv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / math.sqrt(hd)
    causal = jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST).reshape(n, h_ * hd)
    x = x + _mm(o * jax.nn.sigmoid(gate), w(at["wo"]), fp8)
    return x + _ffn(_rms(x, w(lw["norm2"]), eps), lw["ffn"], w, c)


def hidden(conf: dict, w: dict, tokens, fp8: bool = False):
    """Final-normed hidden states (L_padded, d) of one sequence."""
    n = len(tokens)
    padded = np.zeros(BUCKET * -(-n // BUCKET), np.int32)
    padded[:n] = tokens
    x = _embed_rows(w["embed"], jnp.asarray(padded))
    spec = _spec(conf, fp8)
    period = conf["full_attention_interval"]
    for i in range(conf["num_hidden_layers"]):
        p, g = i % period, i // period
        layer = _attn_layer if p == period - 1 else _gdn_layer
        x = layer(x, w["groups"][p], jnp.int32(g), spec)
    return _final(x, w["final_norm"], eps=conf["rms_norm_eps"])


def gaps(conf: dict, w: dict, prompt, served, control: bool = False):
    """For each served token: how far its reference logit lies below the
    reference's best at that position.  With ``control``, also the same
    gap of the token the fp8 pass puts first.  Served ids outside the
    vocabulary read ``inf``."""
    seq = list(prompt) + list(served[:-1])
    h_ref = hidden(conf, w, seq)
    h_ctl = hidden(conf, w, seq, fp8=True) if control else h_ref
    n = len(served)
    tok = np.asarray(served, np.int64)
    bad = (tok < 0) | (tok >= conf["vocab_size"])
    rows = BUCKET * -(-n // BUCKET)
    pos = np.full(rows, len(seq) - 1, np.int32)
    pos[:n] = np.arange(len(prompt) - 1, len(seq))
    ids = np.zeros(rows, np.int32)
    ids[:n] = np.where(bad, 0, tok)
    out_s, out_c = [], []
    for lo in range(0, rows, BUCKET):
        s, c = _gaps(h_ref, h_ctl, _table(conf, w),
                     jnp.asarray(pos[lo:lo + BUCKET]),
                     jnp.asarray(ids[lo:lo + BUCKET]),
                     conf["vocab_size"], control)
        out_s.append(np.asarray(s))
        out_c.append(np.asarray(c))
    served_gap = np.where(bad, np.inf, np.concatenate(out_s)[:n])
    return served_gap, (np.concatenate(out_c)[:n] if control else None)
