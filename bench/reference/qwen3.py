"""Plain float32 Qwen3 forward pass, dense and MoE, for one sequence.

Written from the published architecture (Qwen/Qwen3-4B and
Qwen/Qwen3-30B-A3B ``config.json`` and modeling code), in ``jax.numpy``
at ``highest`` matmul precision, with no cache and no batching; it
imports nothing of the program.  Per layer: RMSNorm, q/k/v projections,
RMSNorm of each query and key head, rotary embedding (rotate-half, base
``rope_theta``), causal grouped-query attention (query head ``j`` reads
key head ``j // (heads / kv_heads)``), output projection, residual;
RMSNorm, then a SwiGLU MLP ``down(silu(gate x) * up x)``, or a router
(softmax over all experts, top ``num_experts_per_tok``, gates
renormalised to sum to one) over SwiGLU experts; residual.  Then the
final RMSNorm and the output head (the embedding table when tied).

Weights are read from the benchmark's tree (``bench.weights``, the
layout the program serves) and upcast from bf16 one layer at a time,
inside the layer's program, so the reference fits beside them.

``fp8=True`` is the control: the same pass with every matmul's operands
rounded through float8 e4m3, one absmax scale per token row and per
weight output column.

It is also the family's module for the benchmark (a configuration file
names it with ``"reference": "qwen3"``): besides ``gaps``, the source
keys the program must match (``SOURCE_KEYS``, ``used_keys``,
``conventions``), the FLOPs and bytes of a step (``counts``) and the
draws of leaves the generic rules of ``bench/weights.py`` do not cover
(``DRAWS``, none).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reckon import Counts

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
BUCKET = 256   # sequences are padded to a multiple of this many positions
BF16 = 2

# source key -> ModelConfig field ("moe." for MoEConfig fields)
SOURCE_KEYS = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "tie_word_embeddings": "tie_embeddings",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "num_experts": "moe.num_experts",
    "num_experts_per_tok": "moe.top_k",
    "moe_intermediate_size": "moe.d_ff",
}
# reduced source key -> field that must still equal its published value
PUBLISHED_FIELDS: dict[str, str] = {}
DRAWS: dict = {}


def _require(cond: bool, what: str) -> None:
    if not cond:
        raise ValueError(f"configuration file: {what}")


def used_keys(config: dict) -> list[str]:
    """The source keys of ``config`` that the program's model is built
    from."""
    keys = [k for k in SOURCE_KEYS if k in config]
    if "num_experts" in config:
        # every layer is sparse, so the dense FFN width is never built
        _require(config.get("decoder_sparse_step", 1) == 1
                 and not config.get("mlp_only_layers"),
                 "the program builds every layer sparse")
        keys.remove("intermediate_size")
    return keys


def conventions(config: dict, cfg) -> None:
    """Conventions of the family that the source states in words, held
    against the program's ``ModelConfig``; raises ``ValueError``."""
    _require(config["hidden_act"] == "silu" and cfg.activation == "swiglu",
             "a SwiGLU MLP")
    _require(config["torch_dtype"] == cfg.compute_dtype == "bfloat16",
             "bf16 weights")
    _require(cfg.qk_norm and cfg.kv_cache_dtype == "bfloat16",
             "q/k RMSNorm and a bf16 cache")
    if "num_experts" in config:
        _require(config["norm_topk_prob"] and cfg.moe.dispatch == "dense",
                 "renormalised top-k gates")


def _attn_params(c: dict) -> int:
    d, hd = c["hidden_size"], c["head_dim"]
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    return 2 * d * h * hd + 2 * d * kv * hd


def _ffn_params(c: dict, routed: bool) -> int:
    d = c["hidden_size"]
    if "num_experts" not in c:
        return 3 * d * c["intermediate_size"]
    experts = c["num_experts_per_tok"] if routed else c["num_experts"]
    return d * c["num_experts"] + experts * 3 * d * c["moe_intermediate_size"]


def counts(c: dict) -> Counts:
    """Model FLOPs count each multiply-add as two: the projections, the
    MLP or the router and the ``num_experts_per_tok`` experts a token is
    routed to, and the output head over the model's vocabulary per
    position; attention ``4 * heads * head_dim`` per attended position
    and layer (scores and weighted values).  Embedding lookups, norms and
    softmax are left out.  Bytes: every weight once in bf16, and each
    cached position's keys and values; no recurrent state."""
    d, layers = c["hidden_size"], c["num_hidden_layers"]
    matmul = layers * (_attn_params(c) + _ffn_params(c, routed=True))
    tables = (1 if c["tie_word_embeddings"] else 2) * c["vocab_size"] * d
    norms = 2 * d + 2 * c["head_dim"]
    held = _attn_params(c) + _ffn_params(c, routed=False) + norms
    return Counts(
        per_position=2 * (matmul + d * c["vocab_size"]),
        per_attended=4 * layers * c["num_attention_heads"] * c["head_dim"],
        weights=BF16 * (tables + layers * held + d),
        cache=BF16 * 2 * layers * c["num_key_value_heads"] * c["head_dim"],
        state=0)


def _q8(x, axis: int):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(x, w, fp8: bool):
    """x (..., k) @ w (k, n)."""
    if fp8:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.einsum("...k,kn->...n", x, w, precision=HIGHEST)


def _rms(x, scale, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta: float):
    """x (L, heads, hd), positions 0..L-1."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _spec(conf: dict, fp8: bool) -> tuple:
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "rope_theta", "rms_norm_eps", "num_experts",
            "num_experts_per_tok")
    return tuple((k, conf.get(k)) for k in keys) + (("fp8", fp8),)


@functools.partial(jax.jit, static_argnames="spec")
def _layer(x, lw, i, spec):
    c = dict(spec)
    fp8, eps = c["fp8"], c["rms_norm_eps"]
    h_, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    n = x.shape[0]

    def w(a):   # this layer's slice, upcast
        return a[i].astype(F32)

    at = lw["mixer"]
    h = _rms(x, w(lw["norm1"]), eps)
    q = _mm(h, w(at["wq"]), fp8).reshape(n, h_, hd)
    k = _mm(h, w(at["wk"]), fp8).reshape(n, kv, hd)
    v = _mm(h, w(at["wv"]), fp8).reshape(n, kv, hd)
    q = _rope(_rms(q, w(at["q_norm"]), eps), c["rope_theta"])
    k = _rope(_rms(k, w(at["k_norm"]), eps), c["rope_theta"])
    k = jnp.repeat(k, h_ // kv, axis=1)
    v = jnp.repeat(v, h_ // kv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / math.sqrt(hd)
    causal = jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)
    x = x + _mm(o.reshape(n, h_ * hd), w(at["wo"]), fp8)

    ff = lw["ffn"]
    h = _rms(x, w(lw["norm2"]), eps)
    if c["num_experts"] is None:
        y = _mm(jax.nn.silu(_mm(h, w(ff["wg"]), fp8)) * _mm(h, w(ff["wi"]), fp8),
                w(ff["wo"]), fp8)
    else:
        probs = jax.nn.softmax(_mm(h, w(ff["router"]), fp8), axis=-1)
        top, idx = jax.lax.top_k(probs, c["num_experts_per_tok"])
        gates = top / top.sum(-1, keepdims=True)
        comb = jnp.zeros_like(probs).at[jnp.arange(n)[:, None], idx].add(gates)

        def expert(y, e):
            def we(a):
                return a[i, e].astype(F32)

            out = _mm(jax.nn.silu(_mm(h, we(ff["wg"]), fp8))
                      * _mm(h, we(ff["wi"]), fp8), we(ff["wo"]), fp8)
            return y + comb[:, e, None] * out, None

        y, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                            jnp.arange(c["num_experts"]))
    return x + y


@jax.jit
def _embed_rows(table, tokens):
    return table[tokens].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _final(x, scale, eps):
    return _rms(x, scale.astype(F32), eps)


def _table(conf: dict, w: dict):
    return w["embed"] if conf["tie_word_embeddings"] else w["unembed"]


def hidden(conf: dict, w: dict, tokens, fp8: bool = False):
    """Final-normed hidden states (L_padded, d) of one sequence."""
    n = len(tokens)
    padded = np.zeros(BUCKET * -(-n // BUCKET), np.int32)
    padded[:n] = tokens
    x = _embed_rows(w["embed"], jnp.asarray(padded))
    lw = w["groups"][0]
    spec = _spec(conf, fp8)
    for i in range(conf["num_hidden_layers"]):
        x = _layer(x, lw, jnp.int32(i), spec)
    return _final(x, w["final_norm"], eps=conf["rms_norm_eps"])


@functools.partial(jax.jit, static_argnames=("vocab", "fp8"))
def _head(h, table, vocab, fp8):
    t = table[:vocab].astype(F32)
    if fp8:
        h, t = _q8(h, -1), _q8(t, -1)
    return jnp.einsum("nd,vd->nv", h, t, precision=HIGHEST)


def logits(conf: dict, w: dict, h, pos, fp8: bool = False) -> np.ndarray:
    """Logits over the model's vocabulary at positions ``pos``."""
    return np.asarray(_head(h[jnp.asarray(pos)], _table(conf, w),
                            conf["vocab_size"], fp8))


@functools.partial(jax.jit, static_argnames=("vocab", "with_control"))
def _gaps(h_ref, h_ctl, table, pos, tok, vocab, with_control):
    ref = _head(h_ref[pos], table, vocab, False)
    best = ref.max(-1)
    served = best - jnp.take_along_axis(ref, tok[:, None], -1)[:, 0]
    if not with_control:
        return served, served
    first = jnp.argmax(_head(h_ctl[pos], table, vocab, True), -1)
    return served, best - jnp.take_along_axis(ref, first[:, None], -1)[:, 0]


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
    # rows of 256 positions bound the (rows, vocab) logits; the last row
    # is padded, so one program serves every length
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
