"""Resolve a cell of ``BENCHMARK.json`` into what a run needs, by name.

A configuration file holds the source's ``config.json`` numbers under
the source's own keys, the keys it changed (``reduced``), the sizes set
by hand (``assumed``), the deployment it stands for, and the engine's
``slots`` and ``max_len``.  The model is the program's registered
architecture (``arch``): every source number it uses must equal the
file's, and only the keys listed in ``reduced`` are replaced.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any, Callable

ROOT = Path(__file__).resolve().parents[1]

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


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file
    traffic: dict         # the traffic mix file
    end_to_end: tuple     # BENCHMARK.json metric entries of this cell
    per_layer: tuple
    root: Path


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / conf_entry["file"]).read_text())
    traffic_file = root / "bench" / "traffic" / f"{w['traffic']}.json"
    return Cell(
        name=name, chips=int(w["chips"]), config=config,
        traffic=json.loads(traffic_file.read_text()),
        end_to_end=tuple(m for m in bench["end_to_end"] if _applies(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _applies(m, name)),
        root=root)


def metric_reader(name: str, root: Path = ROOT) -> Callable[[Any], Any]:
    """``read(run)`` of ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _used_keys(config: dict) -> list[str]:
    keys = [k for k in SOURCE_KEYS if k in config]
    if "num_experts" in config:
        # every layer is sparse, so the dense FFN width is never built
        _require(config.get("decoder_sparse_step", 1) == 1
                 and not config.get("mlp_only_layers"),
                 "the program builds every layer sparse")
        keys.remove("intermediate_size")
    return keys


def _require(cond: bool, what: str) -> None:
    if not cond:
        raise ValueError(f"configuration file: {what}")


def model_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file, checked
    against the file's numbers."""
    from repro.configs import get_arch

    cfg = get_arch(config["arch"])
    reduced = set(config.get("reduced", []))
    moe: dict = {}
    top: dict = {}
    for key in _used_keys(config):
        field = SOURCE_KEYS[key]
        obj, attr = ((cfg.moe, field[4:]) if field.startswith("moe.")
                     else (cfg, field))
        want = config[key]
        if key in reduced:
            (moe if obj is cfg.moe else top)[attr] = type(getattr(obj, attr))(want)
        elif getattr(obj, attr) != want:
            raise ValueError(f"{config['name']}: {key}={want} in the file, "
                             f"but the program's {config['arch']} has "
                             f"{attr}={getattr(obj, attr)}")
    unknown = reduced - set(_used_keys(config))
    if unknown:
        raise ValueError(f"reduced keys the program does not use: {unknown}")
    # conventions of the family that the source states in words
    _require(config["hidden_act"] == "silu" and cfg.activation == "swiglu",
             "a SwiGLU MLP")
    _require(config["torch_dtype"] == cfg.compute_dtype == "bfloat16",
             "bf16 weights")
    _require(cfg.qk_norm and cfg.kv_cache_dtype == "bfloat16",
             "q/k RMSNorm and a bf16 cache")
    if "num_experts" in config:
        _require(config["norm_topk_prob"] and cfg.moe.dispatch == "dense",
                 "renormalised top-k gates")
    if moe:
        top["moe"] = dataclasses.replace(cfg.moe, **moe)
    return dataclasses.replace(cfg, **top) if top else cfg
