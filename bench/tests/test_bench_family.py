"""A model family is added with new files only: its module
(``bench/reference/<module>.py``), a configuration file that names it,
and new ``BENCHMARK.json`` entries.  The harness holds no family's
knowledge, and refuses what it cannot draw or resolve."""

from __future__ import annotations

import json
import shutil
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from bench import cells, measure, readers, reckon, stats
from bench.drive import ReqRec, Run
from bench.weights import make_weights

ROOT = Path(__file__).resolve().parents[2]
RECORDED = Path(__file__).parent / "data" / "qwen3-4b-decode-3steps.xplane.pb"
COPIED = ("bench/configs", "bench/traffic", "bench/metrics", "bench/reference")
STATE = 10**9
MIX = {"name": "chat-toy", "loop": "open", "rate_rps": 20.0, "pre_s": 0.2,
       "drain_cap_s": 30,
       "prompt": {"median": 6, "sigma": 0.5, "min": 2, "max": 16},
       "output": {"median": 6, "sigma": 0.5, "min": 2, "max": 16}}

TOY = '''\
"""Qwen3's reference and counts, with one more source key, a draw of its
own and a recurrent state of STATE bytes per lane."""

import dataclasses

import jax.numpy as jnp

from bench.reference import qwen3

SOURCE_KEYS = dict(qwen3.SOURCE_KEYS, toy_window="window")
PUBLISHED_FIELDS = {}
DRAWS = {"mixer.q_norm": lambda key, shape: jnp.full(shape, 1.5)}
CALLS = []
conventions = qwen3.conventions


def used_keys(config):
    return qwen3.used_keys(config) + ["toy_window"]


def counts(conf):
    return dataclasses.replace(qwen3.counts(conf), state=STATE)


def gaps(conf, w, prompt, served, control=False):
    CALLS.append(len(served))
    return qwen3.gaps(conf, w, prompt, served, control=control)
'''.replace("STATE", str(STATE))


def add_toy_family(root: Path, smoke_conf) -> None:
    """The files and entries a configuration of a new family brings."""
    (root / "bench/reference/toy.py").write_text(TOY)
    conf = dict(smoke_conf("qwen3-4b"), name="toy-smoke", reference="toy",
                toy_window=0)
    (root / "bench/configs/toy-smoke.json").write_text(json.dumps(conf))
    (root / "bench/traffic/chat-toy.json").write_text(json.dumps(MIX))
    (root / "bench/metrics/hbm_roofline.toy.py").write_text(
        "from bench.readers import step_roofline as read  # noqa: F401\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "toy-smoke", "source": conf["source"],
                             "file": "bench/configs/toy-smoke.json",
                             "reduced": conf["reduced"], "why": "toy"})
    bench["workloads"].append({"name": "toy-smoke.chat", "config": "toy-smoke",
                               "traffic": "chat-toy", "chips": 1, "why": "toy"})
    bench["per_layer"].append({"name": "hbm_roofline.toy", "unit": "%",
                               "better": "higher", "source": "device_trace",
                               "layer": "decode program", "moves": "setup_s",
                               "workloads": ["toy-smoke.chat"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def on_lane(rid, prompt_len, admit_step, token_steps, t1):
    r = ReqRec(rid, 0.0, [1] * prompt_len, len(token_steps),
               admit_step=admit_step)
    r.token_step, r.token_t = token_steps, [t1[k] for k in token_steps]
    return r


def stub_view(conf, family):
    """Ten step calls of 0.1 s, the last nine traced, two requests on lanes."""
    t1 = 0.1 * (np.arange(10) + 1)
    a = on_lane(0, 2, 0, [1, 2, 3], t1)
    b = on_lane(1, 3, 3, [5, 6], t1)
    run = Run(requests=[a, b], step_t0=t1 - 0.05, step_t1=t1,
              window=(0.0, 1.0), attempted=[a, b], drain_end=1.0,
              trace_span=(0.1, 1.0))
    trace = SimpleNamespace(main_program=lambda: ("step", 9, 0.0009))
    return SimpleNamespace(run=run, conf=conf, trace=trace, family=family,
                           peaks={"bf16_flops_per_s": 197e12,
                                  "hbm_bytes_per_s": 819e9})


def test_a_family_is_added_with_new_files_only(tmp_path, peaks, smoke_conf,
                                               monkeypatch):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for sub in COPIED:
        shutil.copytree(ROOT / sub, tmp_path / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    add_toy_family(tmp_path, smoke_conf)

    cell = cells.load_cell("toy-smoke.chat", tmp_path)
    toy = cell.family
    assert Path(toy.__file__) == tmp_path / "bench/reference/toy.py"
    conf = cell.config
    qwen3 = cells.family(dict(conf, reference="qwen3"), tmp_path)

    # model_config: the family's extra key is held against the program
    cfg = cells.model_config(conf, tmp_path)
    assert cfg.window == 0 and cfg.d_model == conf["hidden_size"]
    with pytest.raises(ValueError, match="toy_window"):
        cells.model_config(dict(conf, toy_window=128), tmp_path)

    # weights: the family's draw, every other leaf as Qwen3 draws it
    mine = jax.tree.leaves_with_path(make_weights(cfg, toy, 5))
    theirs = jax.tree.leaves(make_weights(cfg, qwen3, 5))
    for (path, a), b in zip(mine, theirs):
        if jax.tree_util.keystr(path).endswith("['q_norm']"):
            assert (np.asarray(a) == 1.5).all()
        else:
            assert np.array_equal(a, b)

    # reckon and the readers: the state is read and written once per lane
    view, base = stub_view(conf, toy), stub_view(conf, qwen3)
    w = stats.work(view.run, [0.0, 0.2, 0.4, 0.6, 0.8])
    np.testing.assert_array_equal(
        reckon.least_bytes(toy.counts(conf), w)
        - reckon.least_bytes(qwen3.counts(conf), w), 2 * STATE * w.lanes)
    assert readers.step_mfu(view) == readers.step_mfu(base)
    assert readers.step_roofline(view) > readers.step_roofline(base)

    # check.compare: a whole run, traced, goes through the family's gaps
    monkeypatch.setattr(measure, "_trace_file", lambda tmp: str(RECORDED))
    toy.CALLS.clear()
    res = measure.measure(cell, 5, 1.0, True, jax.devices(), peaks,
                          time.perf_counter())
    assert res["correct"] is True
    assert sum(toy.CALLS) == res["checks"]["tokens_compared"]["value"] > 0
    assert res["metrics"]["hbm_roofline.toy"]["value"] > 0

    # nothing that was there was edited; BENCHMARK.json only gained entries
    for sub in COPIED:
        for f in (ROOT / sub).rglob("*"):
            if f.is_file() and "__pycache__" not in f.parts:
                assert (tmp_path / f.relative_to(ROOT)).read_bytes() == \
                    f.read_bytes(), f
    old = json.loads((ROOT / "BENCHMARK.json").read_text())
    new = json.loads((tmp_path / "BENCHMARK.json").read_text())
    assert new.keys() == old.keys()
    for key, value in old.items():
        assert (new[key][:len(value)] if isinstance(value, list)
                else new[key]) == value


def test_a_reduced_key_can_be_held_to_its_published_value(smoke_root,
                                                           smoke_conf,
                                                           monkeypatch):
    conf = smoke_conf("qwen3-moe-30b-a3b-8l")
    fam = cells.family(conf, smoke_root)
    monkeypatch.setattr(fam, "PUBLISHED_FIELDS",
                        {"num_experts": "moe.num_experts"})
    cells.model_config(dict(conf, published={"num_experts": 8}), smoke_root)
    with pytest.raises(ValueError, match="num_experts was 128 as published"):
        cells.model_config(dict(conf, published={"num_experts": 128}),
                           smoke_root)


def test_a_vector_leaf_without_a_family_draw_is_refused():
    from repro.configs import get_arch, smoke_config

    cfg = smoke_config(get_arch("recurrentgemma-2b"))
    qwen3 = cells.family({"reference": "qwen3"})
    with pytest.raises(ValueError, match=r"mixer\.lam"):
        make_weights(cfg, qwen3, 0)


@pytest.mark.parametrize("reference", [None, "", "../qwen3"])
def test_a_configuration_without_a_family_module_is_refused(smoke_root,
                                                           reference):
    path = smoke_root / "bench/configs/qwen3-4b-smoke.json"
    conf = json.loads(path.read_text())
    del conf["reference"]
    if reference is not None:
        conf["reference"] = reference
    path.write_text(json.dumps(conf))
    with pytest.raises(ValueError, match='"reference"'):
        cells.load_cell("qwen3-4b.chat", smoke_root)
    with pytest.raises(ValueError, match='"reference"'):
        cells.model_config(conf, smoke_root)
