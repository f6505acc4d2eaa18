"""BENCHMARK.json and the files it names."""

from __future__ import annotations

import json
import re
from pathlib import Path

import pytest

from bench import cells

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_every_cell_resolves_to_its_files():
    for w in BENCH["workloads"]:
        cell = cells.load_cell(w["name"])
        assert cell.traffic["name"] == w["traffic"]
        assert cell.config["name"] == w["config"]
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert callable(cells.metric_reader(m["name"]))


def test_names_units_and_layers():
    names = [c["name"] for c in BENCH["configs"]]
    names += [w["name"] for w in BENCH["workloads"]]
    names += [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_configs_match_the_program_and_list_what_they_cut(entry):
    conf = json.loads((ROOT / entry["file"]).read_text())
    assert conf["source"] == entry["source"]
    assert conf["reduced"] == entry["reduced"]
    cfg = cells.model_config(conf)
    assert cfg.num_layers == conf["num_hidden_layers"]
    assert cfg.d_model == conf["hidden_size"]


def test_a_width_that_differs_and_is_not_listed_is_refused():
    conf = json.loads((ROOT / "bench/configs/qwen3-4b.json").read_text())
    with pytest.raises(ValueError, match="hidden_size"):
        cells.model_config(dict(conf, hidden_size=2048))
    with pytest.raises(ValueError, match="does not use"):
        cells.model_config(dict(conf, reduced=["max_position_embeddings"]))
    moe = json.loads((ROOT / "bench/configs/qwen3-moe-30b-a3b-8l.json").read_text())
    with pytest.raises(ValueError, match="num_hidden_layers"):
        cells.model_config(dict(moe, reduced=[]))
    assert cells.model_config(moe).moe.num_experts == 128


def test_unknown_workload_is_refused():
    with pytest.raises(KeyError, match="unknown workload"):
        cells.load_cell("no-such-cell")
