"""Top-k Mixture-of-Experts with DLS-driven load balancing.

The LB4OMP mapping: experts are *workers*, tokens are *loop
iterations*, and the router's per-expert load raggedness is exactly the
load-imbalance problem the paper's techniques address.

Balancing mechanisms:
  1. aux-loss (Switch-style)  — the common baseline;
  2. adaptive router bias     — the AWF reformulation: per-expert bias
     updated between steps from measured expert loads (same inverse-time
     weighting as techniques._AWFBase; see balance/moe.py).  Auxiliary-
     loss-free balancing via self-scheduling weights.

Dispatch implementations:
  * 'dense'  — every expert runs on every token, gate-combined; in one
    pass at decode sizes, scanned over expert chunks at prefill and
    training sizes so memory stays bounded.  Clean HLO but inflates
    compute by E/top_k — the baseline whose waste the roofline's
    MODEL_FLOPS/HLO_FLOPS ratio exposes.
  * 'ragged' — sort-based dispatch: tokens sorted by expert id, gathered
    into (E, C, d) tiles with DLS-planned capacity.  This is the layout
    consumed by the grouped-matmul Pallas kernel
    (repro.kernels.grouped_matmul) and the §Perf optimized path.

A layer may hold a share of the experts (``MoEConfig.held``, the dense
path only): the router scores all ``num_experts`` and picks the top-k
among them, and only the held experts' part of the gated sum is
computed; what the other experts would add is left out.  A shared expert
(``MoEConfig.shared_d_ff``) is added once, behind its sigmoid gate.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..sharding import Ax, shard_as
from .layers import activate, dense_init, use_weight


def init_moe(key, cfg):
    d = cfg.d_model
    e = cfg.moe
    ff = e.d_ff
    gated = cfg.activation in ("swiglu", "geglu")
    k_r, k_i, k_g, k_o = jax.random.split(key, 4)

    def expert_stack(k, a, b):
        scale = (1.0 / a) ** 0.5
        w = jax.random.truncated_normal(
            k, -2.0, 2.0, (e.num_held, a, b), jnp.float32)
        return w * scale

    params = {
        "router": dense_init(k_r, d, e.num_experts, "embed", "experts")[0],
        "router_bias": jnp.zeros((e.num_experts,), jnp.float32),
        "wi": expert_stack(k_i, d, ff),
        "wo": expert_stack(k_o, ff, d),
    }
    axes = {
        "router": Ax("embed", "experts"),
        "router_bias": Ax("experts"),
        "wi": Ax("experts", "embed", "expert_mlp"),
        "wo": Ax("experts", "expert_mlp", "embed"),
    }
    if gated:
        params["wg"] = expert_stack(k_g, d, ff)
        axes["wg"] = Ax("experts", "embed", "expert_mlp")
    if e.shared_d_ff:
        ks = jax.random.split(k_r, 4)
        params["shared"] = {
            "wi": dense_init(ks[1], d, e.shared_d_ff, "embed", "mlp")[0],
            "wg": dense_init(ks[2], d, e.shared_d_ff, "embed", "mlp")[0],
            "wo": dense_init(ks[3], e.shared_d_ff, d, "mlp", "embed")[0]}
        axes["shared"] = {"wi": Ax("embed", "mlp"), "wg": Ax("embed", "mlp"),
                          "wo": Ax("mlp", "embed")}
        params["shared_gate"] = dense_init(ks[0], d, 1, "embed", None)[0]
        axes["shared_gate"] = Ax("embed", None)
    return params, axes


def _route(params, cfg, x):
    """Router: top-k expert ids + renormalized weights + aux loss + load.

    The adaptive bias (balance/moe.py) shifts *selection* only — combine
    weights come from the unbiased probabilities (DeepSeek-style aux-free
    balancing, which is the AWF self-scheduling weight update in disguise).
    """
    e = cfg.moe
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    biased = probs + params["router_bias"][None, None, :]
    _, idx = jax.lax.top_k(biased, e.top_k)                  # (b, s, k)
    gate = jnp.take_along_axis(probs, idx, axis=-1)          # (b, s, k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    sel = jax.nn.one_hot(idx, e.num_experts, dtype=jnp.float32).sum(2)
    frac_tokens = sel.mean((0, 1)) / e.top_k
    frac_probs = probs.mean((0, 1))
    aux = e.num_experts * jnp.sum(frac_tokens * frac_probs) * e.router_aux_loss
    load = sel.sum((0, 1))  # tokens per expert (AWF balancer telemetry)
    return idx, gate, aux, load


def _capacity(cfg, tokens: int) -> int:
    e = cfg.moe
    c = int(e.capacity_factor * tokens * e.top_k / e.num_experts)
    return max(8, -(-c // 8) * 8)  # round up to multiple of 8


# Largest (tokens, experts, expert d_ff) transient, in bytes, for which
# every expert runs in one pass. Decode (32 tokens x 128 x 768 in bf16,
# 6.3e6 B) sits far below it; training and prefill at thousands of tokens
# sit far above it and keep the chunked scan.
WHOLE_PASS_BYTES = 32 * 2**20
EXPERT_CHUNK = 16


def expert_chunk_for(tokens: int, num_experts: int, d_ff: int,
                     itemsize: int) -> int:
    """Experts per step of `moe_dense`: all of them when the transient is
    small, else chunks of `EXPERT_CHUNK`."""
    if tokens * num_experts * d_ff * itemsize <= WHOLE_PASS_BYTES:
        return num_experts
    return min(EXPERT_CHUNK, num_experts)


def moe_dense(params, cfg, x, expert_chunk: int | None = None):
    """Baseline: run every expert on every token, combine by gates.

    When the (b, s, experts, ff) transient is small (`expert_chunk_for`),
    every expert runs in one pass, and the expert stacks are read where
    they lie: inside a layer scan they stay slices of the stacked
    parameters, never copied out. At prefill and training scale the
    experts are scanned in chunks of `EXPERT_CHUNK` so the transient stays
    bounded. `expert_chunk` forces a chunk size."""
    b, s, d = x.shape
    e = cfg.moe
    idx, gate, aux, load = _route(params, cfg, x)
    dt = x.dtype
    held = e.num_held
    if expert_chunk is None:
        expert_chunk = expert_chunk_for(b * s, held, e.d_ff,
                                        jnp.dtype(dt).itemsize)
    ec = min(expert_chunk, held)
    assert held % ec == 0
    nchunk = held // ec
    # per-token weight for every expert (0 if not selected)
    wfull = jnp.zeros((b, s, e.num_experts), jnp.float32)
    bidx = jnp.arange(b)[:, None, None]
    sidx = jnp.arange(s)[None, :, None]
    wfull = wfull.at[bidx, sidx, idx].add(gate)
    if held < e.num_experts:
        wfull = wfull[..., :held]
    wg = params.get("wg")

    def experts(wi_c, wo_c, wg_c, w_c):
        h_lin = jnp.einsum("bsd,edf->bsef", x, wi_c.astype(dt))
        if wg_c is not None:
            h = activate(jnp.einsum("bsd,edf->bsef", x, wg_c.astype(dt)),
                         h_lin, cfg.activation)
        else:
            h = activate(h_lin, None, cfg.activation)
        y = jnp.einsum("bsef,efd->bsed", h, wo_c.astype(dt))
        return jnp.einsum("bsed,bse->bsd", y, w_c.astype(dt))

    if nchunk == 1:
        y = experts(params["wi"], params["wo"], wg, wfull)
        return shard_as(y, "batch", "seq", "embed_act"), aux, load

    wi = params["wi"].reshape(nchunk, ec, d, -1)
    wo = params["wo"].reshape(nchunk, ec, -1, d)
    if wg is not None:
        wg = wg.reshape(nchunk, ec, d, -1)
    wchunk = wfull.reshape(b, s, nchunk, ec).transpose(2, 0, 1, 3)

    def body(acc, inp):
        return acc + experts(*inp), None

    xs = (wi, wo, wg, wchunk)  # wg None: an empty leaf, sliced to None
    # checkpoint the chunk body: the (b, s, chunk, ff) transients are
    # recomputed in backward instead of saved across all E/chunk steps
    y, _ = jax.lax.scan(jax.checkpoint(body), jnp.zeros((b, s, d), dt), xs)
    return shard_as(y, "batch", "seq", "embed_act"), aux, load


def moe_ragged(params, cfg, x):
    """Group-local sort-based dispatch (§Perf iteration 2).

    Iteration 1 (global sort-gather) removed the E/top_k compute inflation
    but let GSPMD all-gather the full token matrix every layer (the sort
    indices cross data shards) — wire bytes grew 4.7x.  This version keeps
    dispatch LOCAL: tokens are split into `moe_groups` groups along the
    batch dim (groups == data shards), each group sorts/gathers its own
    tokens into (E, C_g, d) tiles, and only the expert dimension crosses devices (the standard
    MoE all-to-all pattern, inferred by GSPMD from the sharding specs).
    """
    b, s, d = x.shape
    e = cfg.moe
    assert e.num_held == e.num_experts, "ragged dispatch holds every expert"
    idx, gate, aux, load = _route(params, cfg, x)
    groups = min(cfg.moe_groups, b)
    while b % groups != 0:
        groups //= 2
    ng = (b // groups) * s                    # tokens per group
    nk = ng * e.top_k                         # slots per group
    cap = _capacity(cfg, ng)
    xf = x.reshape(groups, ng, d)
    eidx = idx.reshape(groups, nk)
    gatef = gate.reshape(groups, nk)
    tok = jnp.broadcast_to(
        (jnp.arange(nk, dtype=jnp.int32) // e.top_k)[None], (groups, nk))

    order = jnp.argsort(eidx, axis=1, stable=True)
    es = jnp.take_along_axis(eidx, order, axis=1)           # (G, Nk)
    # segment starts per expert via batched searchsorted
    starts = jax.vmap(
        lambda row: jnp.searchsorted(row, jnp.arange(e.num_experts),
                                     side="left"))(es)       # (G, E)
    rank = (jnp.arange(nk, dtype=jnp.int32)[None]
            - jnp.take_along_axis(starts, es, axis=1).astype(jnp.int32))
    keep = rank < cap
    slot = jnp.where(keep, es * cap + rank, e.num_experts * cap)
    gidx = jnp.arange(groups)[:, None]
    z_tok = jnp.zeros((groups, e.num_experts * cap + 1), jnp.int32)
    z_gate = jnp.zeros((groups, e.num_experts * cap + 1), gatef.dtype)
    z_valid = jnp.zeros((groups, e.num_experts * cap + 1), jnp.bool_)
    tok_s = jnp.take_along_axis(tok, order, axis=1)
    gate_s = jnp.take_along_axis(gatef, order, axis=1)
    table_tok = z_tok.at[gidx, slot].set(tok_s)
    table_gate = z_gate.at[gidx, slot].set(gate_s)
    table_valid = z_valid.at[gidx, slot].set(keep)

    tok_e = table_tok[:, :-1].reshape(groups, e.num_experts, cap)
    gate_e = table_gate[:, :-1].reshape(groups, e.num_experts, cap)
    valid_e = table_valid[:, :-1].reshape(groups, e.num_experts, cap)
    # group-local gather: batched take_along_axis keeps it on-shard
    xe = jnp.take_along_axis(
        xf[:, :, None, :],  # (G, ng, 1, d)
        tok_e.reshape(groups, -1, 1, 1).astype(jnp.int32), axis=1
    ).reshape(groups, e.num_experts, cap, d)
    xe = xe * valid_e[..., None].astype(x.dtype)
    # §Perf iteration A5: the token matrix is batch-sharded over (pod,
    # data) only — it is already REPLICATED across the model axis, so the
    # sort/gather dispatch is computed redundantly-but-locally on every
    # model shard (cheap elementwise work), the expert einsums run
    # expert-sharded with zero dispatch collectives, and the only wire
    # cost is one partial-sum all-reduce of the combined output per layer.
    # (Iterations A3/A4 — capacity-shard + axis-swap all-to-all — left
    # ~10 GiB/layer of residual gathers.)
    xe = shard_as(xe, "moe_group", None, None, "embed_act")
    dt = x.dtype
    wi = use_weight(params["wi"].astype(dt), cfg, "experts", None, "expert_mlp")
    h_lin = jnp.einsum("gecd,edf->gecf", xe, wi)
    if "wg" in params:
        wg = use_weight(params["wg"].astype(dt), cfg, "experts", None,
                        "expert_mlp")
        h = activate(jnp.einsum("gecd,edf->gecf", xe, wg),
                     h_lin, cfg.activation)
    else:
        h = activate(h_lin, None, cfg.activation)
    h = shard_as(h, "moe_group", "experts", "capacity", "expert_mlp")
    wo = use_weight(params["wo"].astype(dt), cfg, "experts", "expert_mlp",
                    None)
    ye = jnp.einsum("gecf,efd->gecd", h, wo)
    ye = shard_as(ye, "moe_group", "experts", None, "embed_act")
    w = (gate_e * valid_e.astype(gate_e.dtype))[..., None]
    contrib = (ye * w.astype(ye.dtype)).reshape(groups,
                                                e.num_experts * cap, d)
    y = jnp.zeros((groups, ng, d), ye.dtype)
    y = y.at[gidx, tok_e.reshape(groups, -1), :].add(contrib)
    y = y.reshape(b, s, d)
    return shard_as(y, "batch", "seq", "embed_act"), aux, load


def shared_expert(params, cfg, x):
    """The shared expert's SwiGLU output times sigmoid(shared_gate . x)."""
    dt = x.dtype
    sp = params["shared"]
    h = activate(x @ sp["wg"].astype(dt), x @ sp["wi"].astype(dt),
                 cfg.activation)
    gate = jax.nn.sigmoid((x @ params["shared_gate"].astype(dt)).astype(
        jnp.float32)).astype(dt)
    return gate * (h @ sp["wo"].astype(dt))


def moe(params, cfg, x):
    if cfg.moe.dispatch == "ragged":
        y, aux, load = moe_ragged(params, cfg, x)
    else:
        y, aux, load = moe_dense(params, cfg, x)
    if cfg.moe.shared_d_ff:
        y = y + shared_expert(params, cfg, x)
    return y, aux, load
