"""Hooks shared by every test directory of the repository.

``bench/tests/conftest.py`` builds its smoke tree (``write_tree``) from
``BENCHMARK.json`` by mapping each cell it has a smoke version of (the
chat and offline cells) to that version, and knows no other cell.  Its
tests run those two cells only.  So that the tree still builds while the
benchmark holds further cells, ``write_tree`` is handed a copy of
``BENCHMARK.json`` that lists only the cells it maps; everything else it
reads is the checkout's own.  The cells beyond them have their own tests
(``bench/tests/test_bench_qwen3_next.py``).
"""

from __future__ import annotations

import json
from pathlib import Path

SMOKE_CELLS = ("qwen3-4b.chat", "qwen3-moe-30b-a3b-8l.offline")


def _source_with_smoke_cells(checkout: Path, at: Path) -> Path:
    """A tree at ``at`` that is ``checkout`` but for a ``BENCHMARK.json``
    holding only ``SMOKE_CELLS``."""
    bench = json.loads((checkout / "BENCHMARK.json").read_text())
    bench["workloads"] = [w for w in bench["workloads"]
                          if w["name"] in SMOKE_CELLS]
    at.mkdir(exist_ok=True)
    (at / "BENCHMARK.json").write_text(json.dumps(bench))
    if not (at / "bench").exists():
        (at / "bench").symlink_to(checkout / "bench", target_is_directory=True)
    return at


def pytest_plugin_registered(plugin, manager):
    if not str(getattr(plugin, "__file__", "")).endswith(
            str(Path("bench", "tests", "conftest.py"))):
        return
    write_tree = plugin.write_tree

    def write_smoke_tree(root: Path) -> Path:
        checkout = plugin.ROOT
        plugin.ROOT = _source_with_smoke_cells(
            checkout, root.with_name(root.name + "-source"))
        try:
            return write_tree(root)
        finally:
            plugin.ROOT = checkout

    plugin.write_tree = write_smoke_tree
