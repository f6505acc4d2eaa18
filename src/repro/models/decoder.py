"""Unified decoder LM covering every registered architecture.

Layer stacking: the block pattern (e.g. ('attn',) or ('rglru','rglru',
'local_attn') or 7x'mlstm'+1x'slstm') is tiled over num_layers as
``G full groups + R remainder layers``.  Group parameters are stacked with
a leading G axis and executed under `jax.lax.scan` (bounded HLO size for
the 512-device dry-run); remainder layers are unrolled.  Remat policy is
configurable per config ('none' | 'dots' | 'full').

Decode: per-layer caches (KV ring buffers / recurrent states) are stacked
per pattern position and carried through the same scan; attention writes
each token's K and V into its stack in place.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..sharding import Ax, shard_as
from .attention import (
    KVCache,
    KVCacheQ,
    attention,
    attention_decode,
    init_attention,
    init_kv_cache,
    init_kv_cache_q,
    kv_cache_q_specs,
    kv_cache_specs,
)
from .deltanet import (
    GDNState,
    gdn,
    gdn_decode,
    gdn_state_specs,
    init_gdn,
    init_gdn_state,
)
from .layers import (
    embed_init,
    embed_tokens,
    norm_init,
    rms_norm,
    rope_tables,
    softcap,
    unembed_logits,
)
from .mlp import init_mlp, mlp
from .moe import init_moe, moe
from .recurrent import (
    MLSTMState,
    RGLRUState,
    SLSTMState,
    init_mlstm,
    init_mlstm_state,
    init_rglru,
    init_rglru_state,
    init_slstm,
    init_slstm_state,
    mlstm_decode,
    mlstm_parallel,
    mlstm_state_specs,
    rglru,
    rglru_decode,
    rglru_state_specs,
    slstm,
    slstm_decode,
    slstm_state_specs,
)

_MIXER_INIT = {
    "attn": init_attention,
    "local_attn": init_attention,
    "mlstm": init_mlstm,
    "slstm": init_slstm,
    "rglru": init_rglru,
    "gdn": init_gdn,
}


def _has_ffn(cfg) -> bool:
    return cfg.d_ff > 0 or cfg.moe is not None


def _scope(name: str, on: bool):
    """A named scope, so that the device trace names the ops, where the
    layer is one of the newer kinds (the others' programs stay as they
    were)."""
    return jax.named_scope(name) if on else contextlib.nullcontext()


def _mixer_scope(cfg, kind: str):
    if kind == "gdn":
        return _scope("gdn", True)
    return _scope("gated_attn", kind in ("attn", "local_attn") and cfg.attn_gate)


def _ffn(params, cfg, x):
    """The block's FFN (MoE or dense) of the normed x."""
    if cfg.moe is not None:
        e = cfg.moe
        with _scope("moe_share", bool(e.held or e.shared_d_ff)):
            y, aux, _load = moe(params, cfg, x)
        return y, aux
    return mlp(params, cfg, x), jnp.zeros((), jnp.float32)


# ---------------------------------------------------------------------------
# block init / apply
# ---------------------------------------------------------------------------


def init_block(key, cfg, kind: str):
    k1, k2 = jax.random.split(key)
    mix_p, mix_a = _MIXER_INIT[kind](k1, cfg)
    params = {"norm1": norm_init(cfg.d_model)[0], "mixer": mix_p}
    axes = {"norm1": Ax("embed"), "mixer": mix_a}
    if cfg.moe is not None:
        ff_p, ff_a = init_moe(k2, cfg)
        params["norm2"] = norm_init(cfg.d_model)[0]
        params["ffn"] = ff_p
        axes["norm2"] = Ax("embed")
        axes["ffn"] = ff_a
    elif cfg.d_ff > 0:
        ff_p, ff_a = init_mlp(k2, cfg)
        params["norm2"] = norm_init(cfg.d_model)[0]
        params["ffn"] = ff_p
        axes["norm2"] = Ax("embed")
        axes["ffn"] = ff_a
    return params, axes


def block_apply(params, cfg, kind: str, x, sin, cos):
    """Training/prefill block: returns (x, aux_loss)."""
    h = rms_norm(x, params["norm1"], cfg.norm_eps)
    window = cfg.window if kind == "local_attn" else 0
    with _mixer_scope(cfg, kind):
        if kind in ("attn", "local_attn"):
            mix = attention(params["mixer"], cfg, h, sin, cos, window=window)
        elif kind == "mlstm":
            mix, _ = mlstm_parallel(params["mixer"], cfg, h)
        elif kind == "slstm":
            mix, _ = slstm(params["mixer"], cfg, h)
        elif kind == "rglru":
            mix, _ = rglru(params["mixer"], cfg, h)
        elif kind == "gdn":
            mix, _ = gdn(params["mixer"], cfg, h)
        else:
            raise KeyError(kind)
    x = x + mix
    aux = jnp.zeros((), jnp.float32)
    if _has_ffn(cfg):
        h2 = rms_norm(x, params["norm2"], cfg.norm_eps)
        y, aux_l = _ffn(params["ffn"], cfg, h2)
        x = x + y
        aux = aux + aux_l
    return x, aux


def block_decode(params, cfg, kind: str, x, sin, cos, cache, layer=None):
    """One-token decode block: returns (x, cache).  ``layer`` (attention
    only) makes ``cache`` a stack of layers' caches, see attention_decode."""
    h = rms_norm(x, params["norm1"], cfg.norm_eps)
    window = cfg.window if kind == "local_attn" else 0
    with _mixer_scope(cfg, kind):
        if kind in ("attn", "local_attn"):
            mix, cache = attention_decode(params["mixer"], cfg, h, sin, cos,
                                          cache, window=window, layer=layer)
        elif kind == "mlstm":
            mix, cache = mlstm_decode(params["mixer"], cfg, h, cache)
        elif kind == "slstm":
            y, cache = slstm_decode(params["mixer"], cfg, h, cache)
            mix = y
        elif kind == "rglru":
            mix, cache = rglru_decode(params["mixer"], cfg, h, cache)
        elif kind == "gdn":
            mix, cache = gdn_decode(params["mixer"], cfg, h, cache)
        else:
            raise KeyError(kind)
    x = x + mix
    if _has_ffn(cfg):
        h2 = rms_norm(x, params["norm2"], cfg.norm_eps)
        y, _aux = _ffn(params["ffn"], cfg, h2)
        x = x + y
    return x, cache


# ---------------------------------------------------------------------------
# decoder init
# ---------------------------------------------------------------------------


def _group_split(cfg) -> tuple[int, tuple[str, ...], tuple[str, ...]]:
    period = len(cfg.block_pattern)
    g = cfg.num_layers // period
    r = cfg.num_layers % period
    return g, cfg.block_pattern, cfg.pattern_layers[g * period:]


def _stack_init(init_fn, keys):
    """Stacked (G, ...) params of G layers, one per key.  vmap traces the
    layer once (the values equal a per-key loop), so a jitted init
    compiles one layer instead of G."""
    captured = {}

    def params_only(key):
        p, captured["axes"] = init_fn(key)
        return p

    params = jax.vmap(params_only)(keys)
    axes = jax.tree.map(lambda ax: Ax("stack", *ax.names), captured["axes"])
    return params, axes


def init_decoder(key, cfg):
    g, pattern, remainder = _group_split(cfg)
    keys = jax.random.split(key, 4 + len(pattern) + len(remainder))
    params: dict[str, Any] = {}
    axes: dict[str, Any] = {}
    params["embed"], axes["embed"] = embed_init(keys[0], cfg.padded_vocab,
                                                cfg.d_model)
    if not cfg.tie_embeddings:
        params["unembed"], axes["unembed"] = embed_init(
            keys[1], cfg.padded_vocab, cfg.d_model)
    params["final_norm"] = norm_init(cfg.d_model)[0]
    axes["final_norm"] = Ax("embed")

    grp_p, grp_a = [], []
    if g > 0:
        for pi, kind in enumerate(pattern):
            sub = jax.random.split(keys[2 + pi], g)
            p, a = _stack_init(lambda k, kind=kind: init_block(k, cfg, kind),
                               sub)
            grp_p.append(p)
            grp_a.append(a)
    params["groups"] = tuple(grp_p)
    axes["groups"] = tuple(grp_a)

    rem_p, rem_a = [], []
    for ri, kind in enumerate(remainder):
        p, a = init_block(keys[2 + len(pattern) + ri], cfg, kind)
        rem_p.append(p)
        rem_a.append(a)
    params["remainder"] = tuple(rem_p)
    axes["remainder"] = tuple(rem_a)
    return params, axes


def decoder_param_specs(cfg):
    """(param ShapeDtypeStructs, axes tree) without allocation.

    Ax leaves are plain Python objects, so they can't flow *out* of
    eval_shape — capture them via a side channel instead."""
    captured = {}

    def params_only(key):
        p, a = init_decoder(key, cfg)
        captured["axes"] = a
        return p

    specs = jax.eval_shape(params_only, jax.random.key(0))
    return specs, captured["axes"]


def init_decoder_axes(cfg):
    """Axes tree without allocating params."""
    return decoder_param_specs(cfg)[1]


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------


def _remat(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn)


def forward(params, cfg, tokens, prefix_embed=None):
    """tokens (b, s_body) [+ prefix (b, P, d)] -> logits (b, s, v), aux."""
    compute = jnp.dtype(cfg.compute_dtype)
    x = embed_tokens(params["embed"], tokens, compute)
    if prefix_embed is not None:
        x = jnp.concatenate([prefix_embed.astype(compute), x], axis=1)
    b, s, _ = x.shape
    sin, cos = rope_tables(jnp.arange(s), cfg.rotary_dim, cfg.rope_theta,
                           jnp.float32)

    g, pattern, remainder = _group_split(cfg)
    aux0 = jnp.zeros((), jnp.float32)

    if g > 0:
        def group_body(carry, grp_params):
            x, aux = carry
            for pi, kind in enumerate(pattern):
                x, a = block_apply(grp_params[pi], cfg, kind, x, sin, cos)
                aux = aux + a
            return (x, aux), None

        body = _remat(group_body, cfg.remat)
        (x, aux0), _ = jax.lax.scan(body, (x, aux0), params["groups"],
                                    unroll=g if cfg.scan_unroll else 1)

    for ri, kind in enumerate(remainder):
        x, a = block_apply(params["remainder"][ri], cfg, kind, x, sin, cos)
        aux0 = aux0 + a

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = unembed_logits(x, table, cfg)
    logits = softcap(logits, cfg.logit_softcap)
    return logits, aux0


def _hidden_states(params, cfg, tokens, prefix_embed=None):
    """Shared trunk of forward() up to the final norm (no unembed)."""
    compute = jnp.dtype(cfg.compute_dtype)
    x = embed_tokens(params["embed"], tokens, compute)
    if prefix_embed is not None:
        x = jnp.concatenate([prefix_embed.astype(compute), x], axis=1)
    b, s, _ = x.shape
    sin, cos = rope_tables(jnp.arange(s), cfg.rotary_dim, cfg.rope_theta,
                           jnp.float32)
    g, pattern, remainder = _group_split(cfg)
    aux0 = jnp.zeros((), jnp.float32)
    if g > 0:
        def group_body(carry, grp_params):
            x, aux = carry
            for pi, kind in enumerate(pattern):
                x, a = block_apply(grp_params[pi], cfg, kind, x, sin, cos)
                aux = aux + a
            return (x, aux), None

        body = _remat(group_body, cfg.remat)
        (x, aux0), _ = jax.lax.scan(body, (x, aux0), params["groups"],
                                    unroll=g if cfg.scan_unroll else 1)
    for ri, kind in enumerate(remainder):
        x, a = block_apply(params["remainder"][ri], cfg, kind, x, sin, cos)
        aux0 = aux0 + a
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, aux0


def loss_fn(params, cfg, tokens, labels, prefix_embed=None,
            z_loss: float = 1e-4):
    """Next-token CE over the token body (prefix positions excluded).

    The logits are never materialized at (b, s, vocab): the unembed + CE
    is computed in checkpointed seq chunks of cfg.loss_chunk positions,
    bounding the transient at (b, chunk, vocab)."""
    x, aux = _hidden_states(params, cfg, tokens, prefix_embed)
    if prefix_embed is not None:
        x = x[:, prefix_embed.shape[1]:, :]
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]

    def chunk_loss(xc, lc):
        logits = unembed_logits(xc, table, cfg)
        logits = softcap(logits, cfg.logit_softcap)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return jnp.sum(lse - picked), jnp.sum(jnp.square(lse))

    b, s, _ = x.shape
    chunk = cfg.loss_chunk
    if chunk <= 0 or s % chunk != 0 or s <= chunk:
        ce_sum, z_sum = chunk_loss(x, labels)
    else:
        nc = s // chunk
        xc = x.reshape(b, nc, chunk, -1).transpose(1, 0, 2, 3)
        lc = labels.reshape(b, nc, chunk).transpose(1, 0, 2)

        def body(acc, inp):
            ce, zz = jax.checkpoint(chunk_loss)(*inp)
            return (acc[0] + ce, acc[1] + zz), None

        (ce_sum, z_sum), _ = jax.lax.scan(
            body, (jnp.zeros(()), jnp.zeros(())), (xc, lc))
    n_tok = b * s
    ce = ce_sum / n_tok
    zl = z_loss * z_sum / n_tok
    return ce + zl + aux, {"ce": ce, "z_loss": zl, "aux": aux}


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


class DecodeState(NamedTuple):
    group_caches: tuple      # per pattern position: stacked (G, ...) caches
    rem_caches: tuple        # per remainder layer
    pos: jax.Array           # (b,) int32 absolute position per lane


def _cache_for(cfg, kind: str, batch: int, max_len: int, spec: bool):
    if kind in ("attn", "local_attn"):
        window = cfg.window if kind == "local_attn" else 0
        if cfg.kv_cache_dtype == "int8":
            fn = kv_cache_q_specs if spec else init_kv_cache_q
        else:
            fn = kv_cache_specs if spec else init_kv_cache
        return fn(cfg, batch, max_len, window=window)
    if kind == "mlstm":
        return (mlstm_state_specs if spec else init_mlstm_state)(cfg, batch)
    if kind == "slstm":
        return (slstm_state_specs if spec else init_slstm_state)(cfg, batch)
    if kind == "rglru":
        return (rglru_state_specs if spec else init_rglru_state)(cfg, batch)
    if kind == "gdn":
        return (gdn_state_specs if spec else init_gdn_state)(cfg, batch)
    raise KeyError(kind)


def _stack_caches(caches):
    return jax.tree.map(lambda *a: jnp.stack(a), *caches)


def _stack_cache_specs(caches):
    def stk(*a):
        return jax.ShapeDtypeStruct((len(a),) + a[0].shape, a[0].dtype)
    return jax.tree.map(stk, *caches)


def init_decode_state(cfg, batch: int, max_len: int,
                      spec: bool = False) -> DecodeState:
    g, pattern, remainder = _group_split(cfg)
    group_caches = []
    for kind in pattern:
        per = [_cache_for(cfg, kind, batch, max_len, spec) for _ in range(g)]
        group_caches.append(
            (_stack_cache_specs if spec else _stack_caches)(per))
    rem = tuple(_cache_for(cfg, kind, batch, max_len, spec)
                for kind in remainder)
    pos = (jax.ShapeDtypeStruct((batch,), jnp.int32) if spec
           else jnp.zeros((batch,), jnp.int32))
    return DecodeState(group_caches=tuple(group_caches), rem_caches=rem,
                       pos=pos)


def _cache_axes_for(cfg, kind: str):
    if kind in ("attn", "local_attn"):
        if cfg.kv_cache_dtype == "int8":
            return KVCacheQ(
                k=Ax("batch", "seq_cache", "kv_heads", "head_dim"),
                v=Ax("batch", "seq_cache", "kv_heads", "head_dim"),
                k_scale=Ax("batch", "seq_cache", "kv_heads"),
                v_scale=Ax("batch", "seq_cache", "kv_heads"),
                pos=Ax())
        return KVCache(k=Ax("batch", "seq_cache", "kv_heads", "head_dim"),
                       v=Ax("batch", "seq_cache", "kv_heads", "head_dim"),
                       pos=Ax())
    if kind == "mlstm":
        return MLSTMState(c=Ax("batch", "heads", None, None),
                          n=Ax("batch", "heads", None), m=Ax("batch", "heads"))
    if kind == "slstm":
        return SLSTMState(c=Ax("batch", None), n=Ax("batch", None),
                          h=Ax("batch", None), m=Ax("batch", None))
    if kind == "rglru":
        return RGLRUState(h=Ax("batch", "lru"), conv=Ax("batch", None, "lru"))
    if kind == "gdn":
        return GDNState(conv=Ax("batch", None, None),
                        s=Ax("batch", "heads", None, None))
    raise KeyError(kind)


def decode_state_axes(cfg) -> DecodeState:
    """Logical axes tree matching init_decode_state (for shardings)."""
    g, pattern, remainder = _group_split(cfg)
    group_caches = []
    for kind in pattern:
        ax = _cache_axes_for(cfg, kind)
        group_caches.append(
            jax.tree.map(lambda a: Ax("stack", *a.names), ax))
    rem = tuple(_cache_axes_for(cfg, kind) for kind in remainder)
    return DecodeState(group_caches=tuple(group_caches), rem_caches=rem,
                       pos=Ax("batch"))


def decode_step(params, cfg, state: DecodeState, tokens):
    """tokens (b, 1) -> (logits (b, 1, v), new state)."""
    compute = jnp.dtype(cfg.compute_dtype)
    x = embed_tokens(params["embed"], tokens, compute)
    # per-lane rope phase over the rotated dims: (b, 1, rotary_dim/2)
    sin, cos = rope_tables(state.pos[:, None], cfg.rotary_dim, cfg.rope_theta,
                           jnp.float32)
    g, pattern, remainder = _group_split(cfg)

    if g > 0:
        # caches ride in the scan CARRY (not xs/ys): the in-loop
        # updates of the carried buffers are aliasable in-place by XLA,
        # avoiding a second cache-sized buffer — the xs/ys formulation
        # double-buffers the (large) KV caches.
        def group_body(carry, inp):
            x, caches = carry
            gi, grp_params = inp
            for pi, kind in enumerate(pattern):
                if kind in ("attn", "local_attn"):
                    # writes its one new position into the stack
                    x, upd = block_decode(grp_params[pi], cfg, kind, x, sin,
                                          cos, caches[pi], layer=gi)
                else:
                    # a recurrent state is rewritten whole every step:
                    # the layer's is taken out of the stack and put back
                    cache_g = jax.tree.map(
                        lambda c: jax.lax.dynamic_index_in_dim(
                            c, gi, axis=0, keepdims=False), caches[pi])
                    x, c2 = block_decode(grp_params[pi], cfg, kind, x, sin,
                                         cos, cache_g)
                    upd = jax.tree.map(
                        lambda full, new: jax.lax.dynamic_update_index_in_dim(
                            full, new.astype(full.dtype), gi, axis=0),
                        caches[pi], c2)
                caches = caches[:pi] + (upd,) + caches[pi + 1:]
            return (x, caches), None

        (x, new_group_caches), _ = jax.lax.scan(
            group_body, (x, state.group_caches),
            (jnp.arange(g, dtype=jnp.int32), params["groups"]))
    else:
        new_group_caches = state.group_caches

    new_rem = []
    for ri, kind in enumerate(remainder):
        x, c = block_decode(params["remainder"][ri], cfg, kind, x, sin, cos,
                            state.rem_caches[ri])
        new_rem.append(c)

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = unembed_logits(x, table, cfg)
    logits = softcap(logits, cfg.logit_softcap)
    return logits, DecodeState(group_caches=new_group_caches,
                               rem_caches=tuple(new_rem),
                               pos=state.pos + 1)
