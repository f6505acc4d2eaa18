"""The engine's prefill-then-decode logits against the plain float32
reference's full forward pass, at smoke width on the CPU, for both
configurations; the fp8 control fails the same comparison."""

from __future__ import annotations

import hashlib

import jax
import numpy as np
import pytest

from bench import cells
from bench.reference import qwen3
from bench.weights import make_weights

# Per position, the largest logit error as a share of the reference
# logits' spread; the comparison takes the median over positions.
# Measured at this width: bf16 engine 0.025-0.04 (dense) and up to 0.13
# (MoE, where a router near-tie in bf16 picks another expert and moves a
# few positions); fp8 control 0.34-0.43.
TOLERANCE = 0.2


def served_logits(conf, weights, seed: int):
    """Request 0's logits row at every step it took, beside another lane,
    and its prompt and served tokens."""
    from repro.serve.engine import DecodeEngine
    from repro.serve.scheduler import Request

    cfg = cells.model_config(conf)
    eng = DecodeEngine(cfg, weights, slots=2, max_len=64)
    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, conf["vocab_size"], 9).tolist()
    eng.submit(Request(0, 0.0, 9, 12), prompt=prompt)
    eng.submit(Request(1, 0.0, 5, 20),
               prompt=rng.integers(0, conf["vocab_size"], 5).tolist())
    rows = []
    while len(eng.output(0)) < 12:
        lane = eng.lane_requests.index(0) if 0 in eng.lane_requests else 0
        eng.run(max_steps=1)
        rows.append(np.asarray(eng.last_logits[lane, 0, :conf["vocab_size"]]))
    return np.stack(rows), prompt, eng.output(0)


def median_error(a, ref):
    return float(np.median(np.abs(a - ref).max(-1)) / ref.std())


@pytest.mark.parametrize("name", ["qwen3-4b", "qwen3-moe-30b-a3b-8l"])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_engine_matches_reference_and_fp8_does_not(smoke_conf, name, seed):
    conf = smoke_conf(name)
    w = make_weights(cells.model_config(conf), cells.family(conf), seed)
    got, prompt, served = served_logits(conf, w, seed)
    seq = prompt + served[:-1]
    pos = np.arange(len(seq))
    ref = qwen3.logits(conf, w, qwen3.hidden(conf, w, seq), pos)
    fp8 = qwen3.logits(conf, w, qwen3.hidden(conf, w, seq, fp8=True), pos,
                       fp8=True)
    assert got.shape == ref.shape == (len(seq), conf["vocab_size"])
    assert median_error(got, ref) < TOLERANCE
    assert median_error(fp8, ref) > TOLERANCE


def test_gaps_read_inf_for_ids_outside_the_vocabulary(smoke_conf):
    conf = smoke_conf("qwen3-4b")
    w = make_weights(cells.model_config(conf), cells.family(conf), 0)
    g, _ = qwen3.gaps(conf, w, [1, 2, 3], [4, conf["vocab_size"], 5])
    assert np.isinf(g[1]) and np.isfinite(g[[0, 2]]).all()
    assert (g[[0, 2]] >= 0).all()


def test_weights_are_seeded_and_padding_rows_zero(smoke_conf):
    conf = smoke_conf("qwen3-moe-30b-a3b-8l")
    cfg = cells.model_config(conf)
    a, b, c = (make_weights(cfg, cells.family(conf), s)
               for s in (7, 7, 2**40 + 7))
    assert np.array_equal(a["embed"], b["embed"])
    assert not np.array_equal(a["embed"], c["embed"])
    for table in (a["embed"], a["unembed"]):
        assert table.dtype == cfg.compute_dtype
        assert not np.asarray(table[conf["vocab_size"]:]).any()
    assert not np.asarray(a["groups"][0]["ffn"]["router_bias"]).any()


# sha256 over each leaf's shape, dtype and bytes, in the tree's order
DIGESTS = {
    "qwen3-4b": "1f91b485f7969f5dbad0af3be071d7f6d5db5258899ee28837d5de6ab998bb67",
    "qwen3-moe-30b-a3b-8l":
        "07aff0a6552f3d9ba3110dacf4f15a4726fce52fc44f70ed8ccd3044992c10f4",
}


@pytest.mark.parametrize("name", sorted(DIGESTS))
def test_smoke_weights_are_drawn_as_before_family_modules(smoke_conf, name):
    """The digests were computed at seed 1234567891 on a copy of the tree
    from before the weight draws took a family module, so both cells'
    weights are bit-identical across that move."""
    conf = smoke_conf(name)
    w = make_weights(cells.model_config(conf), cells.family(conf), 1234567891)
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(w):
        a = np.asarray(leaf)
        h.update(str((a.shape, a.dtype.name)).encode())
        h.update(a.tobytes())
    assert h.hexdigest() == DIGESTS[name]
