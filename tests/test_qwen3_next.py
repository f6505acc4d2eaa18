"""Qwen3-Next's parts of the program at smoke width: the expert share and
the shared expert, and a lane reused by the engine starting from zero
DeltaNet state.  The comparisons against the float32 reference are in
bench/tests/test_bench_qwen3_next.py."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, smoke_config
from repro.models import init_decoder
from repro.models.moe import init_moe, moe, shared_expert

SHARES = 4


def _cfg(held: int = 0):
    cfg = dataclasses.replace(smoke_config(ARCHS["qwen3-next-80b-a3b"]),
                              compute_dtype="float32")
    moe_cfg = dataclasses.replace(cfg.moe, num_experts=8, top_k=3, held=held)
    return dataclasses.replace(cfg, moe=moe_cfg)


def test_expert_shares_sum_to_the_uncut_layer():
    """Four chips' shares of 2 of 8 experts each, routed over all 8: their
    outputs, less the shared expert that each adds once, sum to the layer
    that holds every expert."""
    whole = _cfg()
    params, _ = init_moe(jax.random.key(0), whole)
    x = jax.random.normal(jax.random.key(1), (2, 5, whole.d_model))
    full, _, _ = moe(params, whole, x)

    per = whole.moe.num_experts // SHARES
    share_cfg = _cfg(held=per)
    total = jnp.zeros_like(full)
    for j in range(SHARES):
        mine = np.arange(j * per, (j + 1) * per)
        # this share's experts first: the router's columns are permuted
        # with them, so the same experts are picked with the same gates
        order = np.r_[mine, np.setdiff1d(np.arange(whole.moe.num_experts), mine)]
        p = dict(params, router=params["router"][:, order],
                 router_bias=params["router_bias"][order],
                 wi=params["wi"][mine], wg=params["wg"][mine],
                 wo=params["wo"][mine])
        y, _, _ = moe(p, share_cfg, x)
        total = total + y
    total = total - (SHARES - 1) * shared_expert(params, whole, x)
    np.testing.assert_allclose(total, full, rtol=1e-5, atol=1e-5)
    # each share alone is not the layer: the absent experts' part is missing
    assert float(jnp.abs(y - full).max()) > 1e-2


def test_reused_lane_starts_from_zero_state():
    """One lane serves request A, then request B: B's logits equal those of
    an engine that served B alone, so the splice zeroed the DeltaNet
    state, its conv inputs and the lane's cache position."""
    from repro.serve.engine import DecodeEngine
    from repro.serve.scheduler import Request

    cfg = smoke_config(ARCHS["qwen3-next-80b-a3b"])
    params, _ = init_decoder(jax.random.key(2), cfg)
    rng = np.random.default_rng(0)
    prompt_a = rng.integers(0, cfg.vocab_size, 7).tolist()
    prompt_b = rng.integers(0, cfg.vocab_size, 5).tolist()

    def serve(prompts):
        """B's output tokens and the logits of the last 8 steps, all B's
        (its 5 prompt tokens and 6 outputs take 10 steps)."""
        eng = DecodeEngine(cfg, params, slots=1, max_len=32)
        for rid, prompt in enumerate(prompts):
            eng.submit(Request(rid, 0.0, len(prompt), 6), prompt=prompt)
        rows = []
        last = len(prompts) - 1
        while len(eng.output(last)) < 6:
            eng.run(max_steps=1)
            rows.append(np.asarray(eng.last_logits[0, 0]))
        return eng, list(eng.output(last)), np.stack(rows[-8:])

    eng, tokens, reused = serve([prompt_a, prompt_b])
    _, fresh_tokens, fresh = serve([prompt_b])
    # A left state behind on the lane, so a splice that kept it would show
    assert float(jnp.abs(eng.state.group_caches[0].s).max()) > 0
    assert tokens == fresh_tokens
    np.testing.assert_array_equal(reused, fresh)
