"""Per-arch smoke tests (reduced configs) + model-level invariants:
forward/decode shape + NaN checks, decode==teacher-forced-forward
consistency, MoE dispatch agreement, loss gradients."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, smoke_config
from repro.models import (
    decode_step,
    forward,
    init_decode_state,
    init_decoder,
    loss_fn,
)

ALL_ARCHS = sorted(ARCHS)


def _setup(name, **over):
    cfg = smoke_config(ARCHS[name])
    if over:
        cfg = dataclasses.replace(cfg, **over)
    params, axes = init_decoder(jax.random.key(0), cfg)
    return cfg, params


@pytest.mark.parametrize("name", ALL_ARCHS)
def test_arch_smoke_forward_step(name):
    """Assignment requirement: reduced same-family config, one forward +
    one train step on CPU, asserting shapes and no NaNs."""
    cfg, params = _setup(name)
    b, s = 2, 32
    toks = jax.random.randint(jax.random.key(1), (b, s), 0, cfg.vocab_size)
    labels = jax.random.randint(jax.random.key(2), (b, s), 0, cfg.vocab_size)
    prefix = None
    if cfg.prefix_len:
        prefix = jax.random.normal(
            jax.random.key(3), (b, cfg.prefix_len, cfg.d_model), jnp.bfloat16)
    logits, aux = jax.jit(lambda p: forward(p, cfg, toks, prefix))(params)
    assert logits.shape == (b, s + cfg.prefix_len, cfg.padded_vocab)
    assert not bool(jnp.any(jnp.isnan(logits)))

    # one real gradient step
    def loss(p):
        return loss_fn(p, cfg, toks, labels, prefix)[0]

    val, grads = jax.jit(jax.value_and_grad(loss))(params)
    assert np.isfinite(float(val))
    gnorm = jax.tree.reduce(
        lambda a, b: a + b,
        jax.tree.map(lambda g: float(jnp.sum(jnp.abs(g))), grads))
    assert np.isfinite(gnorm) and gnorm > 0


@pytest.mark.parametrize("name", ALL_ARCHS)
def test_arch_smoke_decode_step(name):
    cfg, params = _setup(name)
    b = 2
    st = init_decode_state(cfg, b, max_len=16)
    toks = jax.random.randint(jax.random.key(1), (b, 1), 0, cfg.vocab_size)
    logits, st2 = jax.jit(lambda p, s, t: decode_step(p, cfg, s, t))(
        params, st, toks)
    assert logits.shape == (b, 1, cfg.padded_vocab)
    assert not bool(jnp.any(jnp.isnan(logits)))
    assert int(st2.pos[0]) == 1


@pytest.mark.parametrize("name", ["stablelm-3b", "qwen3-4b", "xlstm-1.3b",
                                  "recurrentgemma-2b",
                                  "granite-moe-1b-a400m", "musicgen-medium",
                                  "qwen3-next-80b-a3b"])
def test_decode_matches_forward(name):
    """Step-by-step decode must reproduce teacher-forced logits (validates
    KV ring buffers, mLSTM chunkwise algebra, RG-LRU scan, MoE decode,
    the Gated DeltaNet chunked form)."""
    cfg, params = _setup(name, prefix_len=0, compute_dtype="float32")
    b, s = 2, 20
    toks = jax.random.randint(jax.random.key(1), (b, s), 0, cfg.vocab_size)
    full, _ = jax.jit(lambda p: forward(p, cfg, toks))(params)
    st = init_decode_state(cfg, b, max_len=s)
    step = jax.jit(lambda p, st, t: decode_step(p, cfg, st, t))
    outs = []
    for i in range(s):
        lg, st = step(params, st, toks[:, i:i + 1])
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, axis=1)
    rel = float(jnp.max(jnp.abs(dec - full))
                / (jnp.max(jnp.abs(full)) + 1e-9))
    assert rel < 2e-2, rel


@pytest.mark.parametrize("name,over,lane_passes", [
    ("qwen3-4b", {}, False),
    ("recurrentgemma-2b", {}, False),
    ("qwen3-4b", {"kv_cache_dtype": "int8"}, False),
    ("qwen3-next-80b-a3b", {}, False),
    ("qwen3-next-80b-a3b", {}, True),
], ids=["qwen3-4b", "recurrentgemma-2b", "qwen3-4b-int8",
        "qwen3-next-80b-a3b", "qwen3-next-80b-a3b-lane-passes"])
def test_decode_lanes_at_different_positions_match_forward(
        monkeypatch, name, over, lane_passes):
    """Continuous batching: lane 1 is reset to position 0 mid-run through
    the engine's splice, as admission does, so the two lanes sit at
    different positions and lane 1's cache still holds its first request.
    Each lane's step-by-step logits match forward on that lane's own
    tokens; the run outlasts recurrentgemma's window, so its ring wraps.
    With lane passes, decode reads the cache one lane per pass, as a
    cache too large for one pass is read (`lanes_per_pass`)."""
    from repro.models import attention
    from repro.serve.engine import _splice_lane

    if lane_passes:
        monkeypatch.setattr(attention, "PAST_PASS_BYTES", 1)
    cfg, params = _setup(name, prefix_len=0, compute_dtype="float32", **over)
    steps, reset, max_len = 44, 6, 48
    toks = jax.random.randint(jax.random.key(1), (3, steps), 0,
                              cfg.vocab_size)
    lane0 = toks[0]
    first, second = toks[1, :reset], toks[2, :steps - reset]
    fwd = jax.jit(lambda t: forward(params, cfg, t[None])[0][0])
    want = jnp.stack([fwd(lane0), jnp.concatenate([fwd(first), fwd(second)])])
    st = init_decode_state(cfg, 2, max_len=max_len)
    fresh = init_decode_state(cfg, 1, max_len=max_len)
    step = jax.jit(lambda st, t: decode_step(params, cfg, st, t))
    lane1 = jnp.concatenate([first, second])
    outs = []
    for i in range(steps):
        if i == reset:
            st = _splice_lane(st, fresh, 1)
        lg, st = step(st, jnp.stack([lane0[i], lane1[i]])[:, None])
        outs.append(lg[:, 0])
    assert st.pos.tolist() == [steps, steps - reset]
    dec = jnp.stack(outs, axis=1)
    for lane in range(2):
        rel = float(jnp.max(jnp.abs(dec[lane] - want[lane]))
                    / (jnp.max(jnp.abs(want[lane])) + 1e-9))
        assert rel < 2e-2, (lane, rel)


@pytest.mark.parametrize("arch,lanes,max_len,expect", [
    ("qwen3-4b", 16, 2048, 16),
    ("qwen3-moe-30b-a3b", 32, 2048, 32),
    ("qwen3-next-80b-a3b", 128, 4096, 16),
    ("qwen3-next-80b-a3b", 12, 8192, 6),
])
def test_decode_lanes_per_pass_rule(arch, lanes, max_len, expect):
    """The benchmark's caches in bf16: qwen3-4b and the MoE read a layer's
    past K in one pass; longgen's 128 lanes x 4096 take passes of 16
    lanes, and a lane count with no divisor that fits takes the largest
    one that does."""
    from repro.models.attention import lanes_per_pass

    cfg = ARCHS[arch]
    lane_bytes = max_len * cfg.num_kv_heads * cfg.resolved_head_dim * 2
    assert lanes_per_pass(lanes, lane_bytes) == expect


@pytest.mark.parametrize("expert_chunk", [None, 2])
def test_moe_dense_vs_ragged_dispatch(expert_chunk):
    """The two dispatch paths are equivalent when capacity drops nothing,
    with the dense path in one pass (None) and chunk-scanned (2)."""
    from repro.models.moe import init_moe, moe_dense, moe_ragged

    cfg = smoke_config(ARCHS["granite-moe-1b-a400m"])
    cfg = dataclasses.replace(
        cfg, compute_dtype="float32",
        moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    params, _ = init_moe(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 16, cfg.d_model),
                          jnp.float32)
    yd, aux_d, load_d = moe_dense(params, cfg, x, expert_chunk=expert_chunk)
    yr, aux_r, load_r = moe_ragged(params, cfg, x)
    np.testing.assert_allclose(np.asarray(yd), np.asarray(yr),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(load_d), np.asarray(load_r))


def test_moe_dense_one_pass_matches_chunked_scan():
    """All experts in one pass and the chunked scan compute the same sum."""
    from repro.models.moe import init_moe, moe_dense

    cfg = smoke_config(ARCHS["qwen3-moe-30b-a3b"])
    cfg = dataclasses.replace(cfg, compute_dtype="float32")
    params, _ = init_moe(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (4, 1, cfg.d_model),
                          jnp.float32)
    whole = jax.jit(lambda p, x: moe_dense(p, cfg, x))
    chunked = jax.jit(lambda p, x: moe_dense(p, cfg, x, expert_chunk=1))
    y1, aux1, load1 = whole(params, x)
    y2, aux2, load2 = chunked(params, x)
    assert "while" not in whole.lower(params, x).as_text()
    assert "while" in chunked.lower(params, x).as_text()
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(load1), np.asarray(load2))
    assert float(aux1) == float(aux2)


@pytest.mark.parametrize("tokens,expect", [(32 * 1, 128), (8 * 4096, 16)])
def test_moe_expert_chunk_rule(tokens, expect):
    """qwen3-moe widths in bf16: decode at 32 lanes runs all 128 experts
    at once; a training batch of 8 x 4096 tokens scans chunks of 16."""
    from repro.models.moe import expert_chunk_for

    e = ARCHS["qwen3-moe-30b-a3b"].moe
    assert (e.num_experts, e.d_ff) == (128, 768)
    assert expert_chunk_for(tokens, e.num_experts, e.d_ff, 2) == expect


def test_moe_capacity_drops_tokens():
    from repro.models.moe import init_moe, moe_ragged

    cfg = smoke_config(ARCHS["granite-moe-1b-a400m"])
    cfg = dataclasses.replace(
        cfg, compute_dtype="float32",
        moe=dataclasses.replace(cfg.moe, capacity_factor=0.25))
    params, _ = init_moe(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 64, cfg.d_model),
                          jnp.float32)
    y, _aux, load = moe_ragged(params, cfg, x)
    assert y.shape == x.shape
    assert not bool(jnp.any(jnp.isnan(y)))


def test_router_bias_shifts_expert_selection():
    """The AWF balancer's bias must change routing (aux-free balancing)."""
    from repro.models.moe import init_moe, _route

    cfg = smoke_config(ARCHS["granite-moe-1b-a400m"])
    cfg = dataclasses.replace(cfg, compute_dtype="float32")
    params, _ = init_moe(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (1, 64, cfg.d_model))
    idx0, _, _, load0 = _route(params, cfg, x)
    hot = int(np.argmax(np.asarray(load0)))
    bias = params["router_bias"].at[hot].set(-1.0)  # push away from hot
    idx1, _, _, load1 = _route({**params, "router_bias": bias}, cfg, x)
    assert float(load1[hot]) < float(load0[hot])


def test_long_context_flags():
    assert ARCHS["xlstm-1.3b"].supports_long_context
    assert ARCHS["recurrentgemma-2b"].supports_long_context
    for a in ("qwen3-4b", "granite-20b", "musicgen-medium", "internvl2-1b"):
        assert not ARCHS[a].supports_long_context


def test_param_counts_near_nameplate():
    expect = {
        "granite-moe-1b-a400m": (1.0e9, 1.7e9),
        "qwen3-moe-30b-a3b": (28e9, 33e9),
        "codeqwen1.5-7b": (6.5e9, 9e9),
        "granite-20b": (18e9, 22e9),
        "qwen3-4b": (3.5e9, 5e9),
        "stablelm-3b": (2.4e9, 3.4e9),
    }
    for name, (lo, hi) in expect.items():
        n = ARCHS[name].param_count()
        assert lo <= n <= hi, (name, n)
    # MoE active params
    assert ARCHS["qwen3-moe-30b-a3b"].active_param_count() < 4e9
    assert ARCHS["granite-moe-1b-a400m"].active_param_count() < 0.6e9
