"""FLOP and byte counts of both configurations against hand sums, as
their family module (``bench/reference/qwen3.py``) reckons them."""

from __future__ import annotations

import json
import math
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import cells, reckon, stats
from bench.drive import ReqRec, Run

ROOT = Path(__file__).resolve().parents[2]


def conf(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


def counts(c) -> reckon.Counts:
    return cells.family(c).counts(c)


def test_qwen3_4b_counts():
    c = counts(conf("qwen3-4b"))
    attn = 2 * 2560 * 4096 + 2 * 2560 * 1024          # 26,214,400
    mlp = 3 * 2560 * 9728                              # 74,711,040
    assert c.per_position == 2 * (
        36 * (attn + mlp) + 2560 * 151936) == 8_044_544_000
    assert c.per_attended == 4 * 36 * 32 * 128
    norms = 2 * 2560 + 2 * 128
    assert c.weights == 2 * (
        151936 * 2560 + 36 * (attn + mlp + norms) + 2560) == 8_044_936_192
    assert c.cache == 147_456
    assert c.state == 0


def test_qwen3_moe_8l_counts():
    c = counts(conf("qwen3-moe-30b-a3b-8l"))
    attn = 2 * 2048 * 4096 + 2 * 2048 * 512           # 18,874,368
    expert = 3 * 2048 * 768                            # 4,718,592
    routed = 2048 * 128 + 8 * expert
    assert c.per_position == 2 * (
        8 * (attn + routed) + 2048 * 151936) == 1_532_493_824
    assert c.per_attended == 4 * 8 * 32 * 128
    held = 2048 * 128 + 128 * expert
    norms = 2 * 2048 + 2 * 128
    assert c.weights == 2 * (
        2 * 151936 * 2048 + 8 * (attn + held + norms) + 2048) == 11_214_594_048
    assert c.cache == 16_384
    assert c.state == 0


@pytest.mark.parametrize("name, extra", [
    # what the program stores beyond the model: 128 padding rows of each
    # table, and qwen3-moe's zero router bias
    ("qwen3-4b", 128 * 2560 * 2),
    ("qwen3-moe-30b-a3b-8l", 2 * 128 * 2048 * 2 + 8 * 128 * 2),
])
def test_weight_bytes_match_the_served_tree(name, extra):
    from bench import cells, weights

    c = conf(name)
    shapes = weights.param_shapes(cells.model_config(c))
    stored = sum(math.prod(a.shape) * a.dtype.itemsize
                 for a in jax.tree.leaves(shapes))
    assert stored == counts(c).weights + extra


def test_flops_and_least_bytes_of_the_work_of_each_step():
    # prompt 2 on a lane from 0.05, outputs at 0.2, 0.3, 0.4
    a = ReqRec(0, 0.0, [1, 1], 3, admit_step=0)
    a.token_step, a.token_t = [1, 2, 3], [0.2, 0.3, 0.4]
    t1 = np.arange(1, 6) * 0.1
    run = Run(requests=[a], step_t0=t1 - 0.05, step_t1=t1,
              window=(0.0, 1.0), attempted=[a], drain_end=1.0)
    w = stats.work(run, [0.1, 0.2, 0.3, 0.4, 0.5])
    positions, attended, cached = [4 / 3, 1, 1, 0], [22 / 9, 3, 4, 0], [2, 3, 4, 0]
    c = counts(conf("qwen3-4b"))
    np.testing.assert_allclose(
        reckon.flops(c, w),
        8_044_544_000 * np.array(positions) + 4 * 36 * 32 * 128 * np.array(attended))
    np.testing.assert_allclose(
        reckon.least_bytes(c, w),
        np.array([1, 1, 1, 0]) * 8_044_936_192 + 147_456 * np.array(cached))
