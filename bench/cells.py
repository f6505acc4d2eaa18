"""Resolve a cell of ``BENCHMARK.json`` into what a run needs, by name.

A configuration file holds the source's ``config.json`` numbers under
the source's own keys, the keys it changed (``reduced``), the sizes set
by hand (``assumed``), the deployment it stands for, the engine's
``slots`` and ``max_len``, and its family module (``reference``).  The
model is the program's registered architecture (``arch``): every source
number it uses, by the family's map of source keys to the program's
fields, must equal the file's, and only the keys listed in ``reduced``
are replaced.  All that is particular to a family is in its module.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Any, Callable

ROOT = Path(__file__).resolve().parents[1]


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file
    traffic: dict         # the traffic mix file
    end_to_end: tuple     # BENCHMARK.json metric entries of this cell
    per_layer: tuple
    root: Path
    family: ModuleType    # bench/reference/<config's "reference">.py


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
        root=root, family=family(config, root))


def _load(path: Path, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: Path = ROOT) -> Callable[[Any], Any]:
    """``read(run)`` of ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    return _load(path, f"bench_metric_{name.replace('.', '_')}").read


# family modules by their source, so that one loaded from another root
# keeps its compiled programs
_FAMILIES: dict[bytes, ModuleType] = {}


def family(config: dict, root: Path = ROOT) -> ModuleType:
    """The family module a configuration file names under
    ``"reference"``: ``bench/reference/<module>.py``, with the family's
    reference (``gaps``), source keys (``SOURCE_KEYS``, ``used_keys``,
    ``conventions``, ``PUBLISHED_FIELDS``), counts (``counts``) and
    weight draws (``DRAWS``)."""
    name = config.get("reference")
    if not isinstance(name, str) or not name.isidentifier():
        raise ValueError(
            f"{config.get('name')}: the configuration file names no family "
            f"module: it needs \"reference\": \"<module>\" for "
            f"bench/reference/<module>.py")
    path = root / "bench" / "reference" / f"{name}.py"
    source = path.read_bytes()
    if source not in _FAMILIES:
        _FAMILIES[source] = _load(path, f"bench_family_{name}")
    return _FAMILIES[source]


def _field(cfg, field: str):
    """The object holding ``field`` ("a.b": ``cfg.a``'s ``b``) and the
    attribute's name."""
    head, _, attr = field.rpartition(".")
    return (getattr(cfg, head) if head else cfg), head, attr


def model_config(config: dict, root: Path = ROOT):
    """The program's ``ModelConfig`` for a configuration file, checked
    against the file's numbers by the rules of the family it names."""
    from repro.configs import get_arch

    fam = family(config, root)
    cfg = get_arch(config["arch"])
    reduced = set(config.get("reduced", []))
    used = fam.used_keys(config)
    changes: dict[str, dict] = {}   # by "" (the ModelConfig) or a field's name
    for key in used:
        obj, head, attr = _field(cfg, fam.SOURCE_KEYS[key])
        want = config[key]
        if key in reduced:
            changes.setdefault(head, {})[attr] = type(getattr(obj, attr))(want)
        elif getattr(obj, attr) != want:
            raise ValueError(f"{config['name']}: {key}={want} in the file, "
                             f"but the program's {config['arch']} has "
                             f"{attr}={getattr(obj, attr)}")
    unknown = reduced - set(used)
    if unknown:
        raise ValueError(f"reduced keys the program does not use: {unknown}")
    top = changes.pop("", {})
    for head, fields in changes.items():
        top[head] = dataclasses.replace(getattr(cfg, head), **fields)
    cfg = dataclasses.replace(cfg, **top) if top else cfg
    for key in reduced & set(fam.PUBLISHED_FIELDS):
        obj, _, attr = _field(cfg, fam.PUBLISHED_FIELDS[key])
        want = config["published"][key]
        if getattr(obj, attr) != want:
            raise ValueError(f"{config['name']}: {key} was {want} as "
                             f"published, but the program's {attr} is "
                             f"{getattr(obj, attr)}")
    fam.conventions(config, cfg)
    return cfg
