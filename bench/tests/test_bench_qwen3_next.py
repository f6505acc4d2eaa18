"""The Qwen3-Next family at smoke width on the CPU: the engine against the
plain float32 reference (whole decoder, and the DeltaNet recurrence
alone), the fp8 control failing the same comparison, and the family's
source keys, draws and counts through the harness; plus the cell this
family's configuration brings, resolved from the checkout's own
files."""

from __future__ import annotations

import dataclasses
import json
import shutil
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import cells, measure, readers, reckon, stats, traffic
from bench.drive import ReqRec, Run
from bench.reference import qwen3_next
from bench.weights import make_weights

ROOT = Path(__file__).resolve().parents[2]
RECORDED = Path(__file__).parent / "data" / "qwen3-4b-decode-3steps.xplane.pb"
CONFIG = "qwen3-next-80b-a3b-8l-ep4"
LONGGEN = f"{CONFIG}.longgen"
SMALL = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
         "head_dim": 16, "vocab_size": 256, "num_hidden_layers": 4,
         "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
         "linear_num_key_heads": 2, "linear_num_value_heads": 4,
         "linear_key_head_dim": 16, "linear_value_head_dim": 16,
         "num_experts": 64}
# Per position, the largest logit error as a share of the reference
# logits' spread; the comparison takes the median over positions.
# Measured at this width (seeds 1-6): bf16 engine 0.072-0.261 (a top-10
# of 512 router near-tie in bf16 picks another expert and moves a few
# positions), fp8 control 0.68-1.99.  The float32 engine's widest error
# reads 0.0024 (seed 4): its attention cache is still held in bf16.
TOLERANCE = 0.45
F32_TOLERANCE = 5e-3
MIX = {"name": "longgen-smoke", "loop": "closed", "backlog_per_slot": 2,
       "block": 16, "drain_cap_s": 30,
       "prompt": {"median": 8, "sigma": 0.7, "min": 2, "max": 24},
       "output": {"median": 12, "sigma": 0.6, "min": 4, "max": 40}}


def smoke_conf() -> dict:
    """The configuration file at smoke width: one period of four layers,
    64 of the 512 routed experts held, every width it shrinks listed."""
    src = json.loads((ROOT / "bench/configs" / f"{CONFIG}.json").read_text())
    conf = dict(src, name=f"{CONFIG}-smoke", **SMALL)
    conf.update(reduced=sorted(SMALL), slots=4, max_len=64,
                check={"served_tokens": 10**6,
                       "limits": {"logit_gap_mean": 0.02}})
    return conf


def served_logits(conf, weights, seed: int, dtype: str = "bfloat16"):
    """Request 0's logits row at every step it took, beside another lane,
    and its prompt and served tokens."""
    from repro.serve.engine import DecodeEngine
    from repro.serve.scheduler import Request

    cfg = dataclasses.replace(cells.model_config(conf), compute_dtype=dtype)
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


def reference_logits(conf, w, seq, fp8=False):
    h = qwen3_next.hidden(conf, w, seq, fp8=fp8)
    t = w["unembed"][:conf["vocab_size"]].astype(jnp.float32)
    return np.asarray(jnp.einsum("nd,vd->nv", h[:len(seq)], t,
                                 precision=jax.lax.Precision.HIGHEST))


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_engine_matches_reference_and_fp8_does_not(seed):
    conf = smoke_conf()
    w = make_weights(cells.model_config(conf), cells.family(conf), seed)
    got, prompt, served = served_logits(conf, w, seed)
    seq = prompt + served[:-1]
    ref = reference_logits(conf, w, seq)
    fp8 = reference_logits(conf, w, seq, fp8=True)
    assert got.shape == ref.shape == (len(seq), conf["vocab_size"])
    assert median_error(got, ref) < TOLERANCE
    assert median_error(fp8, ref) > TOLERANCE


def test_float32_engine_matches_reference():
    """The same prefill and decode in float32 (weights upcast): the
    engine's logits are the reference's."""
    conf = smoke_conf()
    w = make_weights(cells.model_config(conf), cells.family(conf), 4)
    w32 = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    got, prompt, served = served_logits(conf, w32, 4, dtype="float32")
    ref = reference_logits(conf, w, prompt + served[:-1])
    assert np.abs(got - ref).max() / ref.std() < F32_TOLERANCE


def test_deltanet_decode_matches_the_reference_recurrence():
    """One DeltaNet mixer in float32: the program's decode steps over 70
    positions and its chunked form both give the reference layer's
    mixer output (the reference's layer less its residual and FFN)."""
    from repro.models.deltanet import gdn, gdn_decode, init_gdn_state

    conf = smoke_conf()
    cfg = dataclasses.replace(cells.model_config(conf), compute_dtype="float32")
    w = make_weights(cfg, cells.family(conf), 11)
    layer = jax.tree.map(lambda a: a[:1], w["groups"][0])
    mixer = jax.tree.map(lambda a: a[0], layer["mixer"])
    n = 70
    x = jax.random.normal(jax.random.key(3), (n, conf["hidden_size"]))

    # the reference layer with its FFN and second norm zeroed adds only
    # the mixer's output to x
    zeroed = dict(layer, ffn=jax.tree.map(jnp.zeros_like, layer["ffn"]))
    spec = qwen3_next._spec(conf, False)
    ref = np.asarray(qwen3_next._gdn_layer(x, zeroed, jnp.int32(0), spec) - x)

    h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                          + conf["rms_norm_eps"]) * layer["norm1"][0]
    par, _ = gdn(mixer, cfg, h[None])
    state = init_gdn_state(cfg, 1)
    steps = []
    for t in range(n):
        y, state = gdn_decode(mixer, cfg, h[None, t:t + 1], state)
        steps.append(y[0, 0])
    dec = np.stack(steps)
    scale = np.abs(ref).max()
    assert np.abs(dec - ref).max() / scale < 2e-3
    assert np.abs(np.asarray(par[0]) - ref).max() / scale < 2e-3


def test_gated_attention_matches_the_reference():
    """One gated attention mixer in float32, rotary on a quarter of each
    head: the program's forward and its decode steps give the reference
    layer's mixer output (the reference's layer less its residual and
    FFN)."""
    from repro.models.attention import (attention, attention_decode,
                                        init_kv_cache)
    from repro.models.layers import rope_tables

    conf = smoke_conf()
    cfg = dataclasses.replace(cells.model_config(conf), compute_dtype="float32")
    w = make_weights(cfg, cells.family(conf), 12)
    layer = jax.tree.map(lambda a: a[:1].astype(jnp.float32), w["groups"][3])
    mixer = jax.tree.map(lambda a: a[0], layer["mixer"])
    n = 40
    x = jax.random.normal(jax.random.key(4), (n, conf["hidden_size"]))
    zeroed = dict(layer, ffn=jax.tree.map(jnp.zeros_like, layer["ffn"]))
    spec = qwen3_next._spec(conf, False)
    ref = np.asarray(qwen3_next._attn_layer(x, zeroed, jnp.int32(0), spec) - x)

    h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                          + conf["rms_norm_eps"]) * layer["norm1"][0]
    sin, cos = rope_tables(jnp.arange(n), cfg.rotary_dim, cfg.rope_theta)
    full = np.asarray(attention(mixer, cfg, h[None], sin, cos)[0])
    cache = init_kv_cache(cfg, 1, n, dtype=jnp.float32)
    steps = []
    for t in range(n):
        sin, cos = rope_tables(jnp.full((1, 1), t), cfg.rotary_dim,
                               cfg.rope_theta)
        y, cache = attention_decode(mixer, cfg, h[None, t:t + 1], sin, cos,
                                    cache)
        steps.append(y[0, 0])
    scale = np.abs(ref).max()
    assert np.abs(full - ref).max() / scale < 1e-4
    assert np.abs(np.stack(steps) - ref).max() / scale < 1e-4


def test_family_keys_draws_and_counts_through_the_harness():
    conf = smoke_conf()
    fam = cells.family(conf)
    cfg = cells.model_config(conf)
    assert (cfg.moe.held, cfg.moe.num_experts, cfg.moe.top_k) == (64, 512, 10)
    assert cfg.block_pattern == ("gdn", "gdn", "gdn", "attn")
    with pytest.raises(ValueError, match="published"):
        cells.model_config(dict(conf, published={"num_experts": 256,
                                                 "num_hidden_layers": 48}))
    with pytest.raises(ValueError, match="whole periods"):
        fam.conventions(dict(conf, full_attention_interval=2), cfg)

    w = make_weights(cfg, fam, 5)
    mixer = w["groups"][0]["mixer"]
    a_log = np.asarray(mixer["A_log"], np.float32)
    assert (a_log <= np.log(16.0) + 1e-2).all() and np.unique(a_log).size > 1
    assert (np.asarray(mixer["dt_bias"], np.float32) == 1).all()
    conv = np.asarray(mixer["conv"], np.float32)
    assert conv.shape[-2] == conf["linear_conv_kernel_dim"]
    assert 0.4 < conv.std() < 0.6        # N(0, 1/4): fan-in is the kernel

    # the published configuration's counts, from the file's own numbers
    full = json.loads((ROOT / "bench/configs" / f"{CONFIG}.json").read_text())
    c, g = fam.counts(full), fam.gdn_counts(full)
    assert c.cache == 4096 and c.state == g.state == 12_877_824
    assert 8.2e9 < c.weights < 8.35e9
    assert 0 < g.per_position < c.per_position and g.weights < c.weights

    # the readers over the family's counts
    t1 = 0.1 * (np.arange(10) + 1)
    req = ReqRec(0, 0.0, [1] * 3, 4, admit_step=0)
    req.token_step, req.token_t = [1, 2, 3, 4], [t1[k] for k in (1, 2, 3, 4)]
    run = Run(requests=[req], step_t0=t1 - 0.05, step_t1=t1, window=(0.0, 1.0),
              attempted=[req], drain_end=1.0, trace_span=(0.1, 1.0))
    view = SimpleNamespace(
        run=run, conf=full, family=fam,
        trace=SimpleNamespace(main_program=lambda: ("step", 9, 0.9)),
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    work = stats.work(run, run.window)
    assert readers.step_mfu(view) == pytest.approx(
        100 * float(reckon.flops(c, work)[0]) / 197e12)
    assert 0 < readers.step_roofline(view) < 100


def test_a_traced_longgen_run_at_smoke_width(tmp_path, peaks, monkeypatch):
    """The cell's entries, metrics and family run end to end; the readers
    of the cell's per-layer metrics find what they read."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for sub in ("bench/configs", "bench/traffic", "bench/metrics",
                "bench/reference"):
        shutil.copytree(ROOT / sub, tmp_path / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "bench/configs" / f"{CONFIG}.json").write_text(
        json.dumps(smoke_conf()))
    (tmp_path / "bench/traffic/longgen.json").write_text(json.dumps(MIX))
    monkeypatch.setattr(measure, "_trace_file", lambda tmp: str(RECORDED))
    cell = cells.load_cell(LONGGEN, tmp_path)
    # 4 s, so that the traced last 3 s start after the window's first step
    res = measure.measure(cell, 2**31 + 5, 4.0, True, jax.devices(), peaks,
                          time.perf_counter())
    assert res["correct"] is True
    per_layer = {m["name"] for m in cell.per_layer}
    assert {"step_mfu.longgen", "hbm_roofline.longgen"} <= per_layer
    assert per_layer <= set(res["metrics"])
    for name in ("step_mfu.longgen", "hbm_roofline.longgen"):
        assert res["metrics"][name]["value"] > 0


def test_new_cell_resolves_from_the_checkout():
    """The longgen cell's configuration resolves against the program, and
    its mix's longest prompt and answer fit the configuration's lanes."""
    cell = cells.load_cell(LONGGEN)
    cfg = cells.model_config(cell.config)
    mix, conf = cell.traffic, cell.config
    assert mix["prompt"]["max"] + mix["output"]["max"] <= conf["max_len"]
    first = next(traffic.closed_backlog(mix, 2**33 + 7, vocab=cfg.vocab_size,
                                        max_len=conf["max_len"]))
    assert len(first.prompt) >= mix["prompt"]["min"]
    assert (cfg.num_layers, cfg.moe.held, cfg.moe.num_experts) == (8, 128, 512)
    assert conf["slots"] == 128
