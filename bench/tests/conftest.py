"""Smoke-width cells for the benchmark's CPU tests.

``smoke_root`` is a checkout-like tree in a temporary directory: its own
``BENCHMARK.json`` with two smoke cells (dense chat, MoE offline), their
configuration and traffic files, and copies of ``bench/metrics`` and
``bench/reference``.  The
configurations keep the Qwen3 architectures and list every width they
shrink under ``reduced``.
"""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

SMALL = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
         "head_dim": 16, "vocab_size": 256, "num_hidden_layers": 2}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}


def smoke_config(name: str) -> dict:
    src = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
    conf = dict(src, name=f"{name}-smoke", **SMALL)
    changed = dict(SMALL)
    if "num_experts" in conf:
        changed.update(num_experts=8, num_experts_per_tok=2,
                       moe_intermediate_size=32)
    else:
        changed.update(intermediate_size=128)
    conf.update(changed, reduced=sorted(changed), slots=4, max_len=64)
    # readings at this width, comparing every finished request (1 s
    # windows; seeds 1-12, 77, 78, 2**31 + 5, 2**33 + 7): dense sound
    # runs' widest gap 0.00054-0.0081, its fp8 control 0.056-0.149; MoE
    # sound runs' mean gap 0.00022-0.00165, its control 0.0112-0.0173
    if "num_experts" in conf:
        limits = {"logit_gap_mean": 0.005}
    else:
        limits = {"logit_gap": 0.02}
    conf["check"] = {"served_tokens": 10**6, "limits": limits}
    return conf


CHAT = {"name": "chat-smoke", "loop": "open", "rate_rps": 40.0, "pre_s": 0.2,
        "drain_cap_s": 30,
        "prompt": {"median": 6, "sigma": 0.5, "min": 2, "max": 16},
        "output": {"median": 6, "sigma": 0.5, "min": 2, "max": 16}}
OFFLINE = {"name": "offline-smoke", "loop": "closed", "backlog_per_slot": 2,
           "block": 16,
           "prompt": {"median": 8, "sigma": 0.5, "min": 2, "max": 24},
           "output": {"median": 8, "sigma": 0.5, "min": 2, "max": 24}}


def write_tree(root: Path) -> Path:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for sub in ("configs", "traffic"):
        (root / "bench" / sub).mkdir(parents=True, exist_ok=True)
    for sub in ("metrics", "reference"):
        shutil.copytree(ROOT / "bench" / sub, root / "bench" / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    configs = []
    for name in ("qwen3-4b", "qwen3-moe-30b-a3b-8l"):
        conf = smoke_config(name)
        path = root / "bench" / "configs" / f"{conf['name']}.json"
        path.write_text(json.dumps(conf))
        configs.append({"name": conf["name"], "source": conf["source"],
                        "file": str(path.relative_to(root)),
                        "reduced": conf["reduced"], "why": "smoke"})
    for mix in (CHAT, OFFLINE):
        (root / "bench" / "traffic" / f"{mix['name']}.json").write_text(
            json.dumps(mix))
    renames = {"qwen3-4b.chat": ("qwen3-4b-smoke", "chat-smoke"),
               "qwen3-moe-30b-a3b-8l.offline": ("qwen3-moe-30b-a3b-8l-smoke",
                                                "offline-smoke")}
    cells = []
    for w in bench["workloads"]:
        config, traffic = renames[w["name"]]
        cells.append(dict(w, config=config, traffic=traffic))

    bench.update(configs=configs, workloads=cells)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture
def smoke_root(tmp_path) -> Path:
    return write_tree(tmp_path)


@pytest.fixture
def peaks() -> dict:
    return dict(PEAKS)


@pytest.fixture
def smoke_conf():
    return smoke_config
