"""Benchmark entry point: one function per paper table/figure plus the
framework benches and the roofline table.  Prints
``name,us_per_call,derived`` CSV rows (and saves JSON under results/),
then consolidates every bench that ran into
``results/BENCH_summary.json`` — one row per bench with wall time and
any reported ``speedup`` — so the perf trajectory is trackable run over
run from a single artifact.

    PYTHONPATH=src python -m benchmarks.run [--only fig5,roofline] [--fast]
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from . import (adaptive_bench, batch_bench, cluster_balance,
               framework_bench, graph_campaign_bench, kernel_sched_bench,
               paper_campaign, resilience_bench, steal_bench, trial_bench)
from .common import RESULTS, emit


def _write_summary(summary: dict) -> None:
    """Merge this run's per-bench stats into results/BENCH_summary.json.

    Keyed by bench name so a partial ``--only`` run refreshes its own
    rows without dropping the others; the previous run's rows for the
    same benches are replaced (latest wins), and the file carries one
    timestamp per bench for trajectory tracking.
    """
    out = RESULTS / "BENCH_summary.json"
    merged: dict = {}
    if out.exists():
        try:
            merged = json.loads(out.read_text())
        except (ValueError, OSError):  # pragma: no cover - corrupt file
            merged = {}
    merged.update(summary)
    RESULTS.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(merged, indent=1, sort_keys=True))
    print(f"# wrote {out} ({len(summary)} benches updated)",
          file=sys.stderr)


def _speedup_of(rows: list[dict]) -> float | None:
    """The bench's headline speedup, if any row reports one."""
    vals = [r["speedup"] for r in rows
            if isinstance(r.get("speedup"), (int, float))]
    return max(vals) if vals else None


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names")
    ap.add_argument("--fast", action="store_true",
                    help="smaller Ns for quick runs")
    args = ap.parse_args()

    n_small = 50_000 if args.fast else 200_000
    benches = {
        "fig2_3": lambda: paper_campaign.fig2_fig3(n=n_small),
        "fig5": lambda: paper_campaign.fig5(),
        "fig6": lambda: paper_campaign.fig6(n=n_small),
        "fig7": lambda: paper_campaign.fig7(n=n_small),
        "fig8": lambda: paper_campaign.fig8(n=n_small),
        "fig9_10": lambda: paper_campaign.fig9_10(n=n_small),
        "fig11": lambda: paper_campaign.fig11(
            n=200_000 if args.fast else 1_000_000),
        "moe_balance": framework_bench.moe_balance,
        "auto_select": framework_bench.auto_select,
        "serving": framework_bench.serving,
        "kernels": framework_bench.kernels,
        "packing": framework_bench.packing,
        # *_quick names: emit() writes results/<name>.json, so the
        # run.py-sized rows must not overwrite the committed full-run
        # batch_speedup.json / adaptive_speedup.json history artifacts
        # (python -m benchmarks.batch_bench / .adaptive_bench own those)
        "batch_speedup_quick": lambda: batch_bench.rows(
            n=n_small, reps=3 if args.fast else 10),
        "adaptive_speedup_quick": lambda: adaptive_bench.rows(
            n=n_small, reps=3 if args.fast else 10),
        "graph_campaign_quick": lambda: graph_campaign_bench.rows(
            n=n_small, reps=3 if args.fast else 10),
        "kernel_sched": kernel_sched_bench.rows,
        # quick-sized; named so emit() doesn't overwrite the committed
        # full-run cluster_balance.json artifact
        "cluster_balance_quick": cluster_balance.rows,
        # work-stealing vs pure DLS (loop + cluster level); quick-sized,
        # named so emit() doesn't overwrite the committed steal_bench.json
        "steal_quick": steal_bench.rows,
        # scenario trials (fault/elasticity + bootstrap CIs); quick-sized,
        # named so emit() doesn't overwrite the committed trial_suite.json
        "trial_quick": trial_bench.rows,
        # reclamation/quarantine value on the fault scenarios; quick-
        # sized, named so emit() doesn't overwrite resilience_bench.json
        "resilience_quick": resilience_bench.rows,
    }
    # roofline needs dry-run artifacts; include when present
    try:
        from . import roofline

        if roofline.RESULTS.exists() and any(roofline.RESULTS.iterdir()):
            benches["roofline"] = lambda: roofline.rows("pod1", "baseline")
            benches["roofline_pod2"] = lambda: roofline.rows(
                "pod2", "baseline")
    except Exception as e:  # pragma: no cover
        print(f"# roofline unavailable: {e}", file=sys.stderr)

    selected = (args.only.split(",") if args.only else list(benches))
    print("name,us_per_call,derived")
    stamp = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    summary: dict = {}
    for name in selected:
        if name not in benches:
            print(f"# unknown bench {name}", file=sys.stderr)
            continue
        t0 = time.time()
        rows = benches[name]()
        emit(rows, name)
        wall = time.time() - t0
        print(f"# {name}: {len(rows)} rows in {wall:.1f}s",
              file=sys.stderr)
        entry = dict(rows=len(rows), wall_s=round(wall, 2),
                     timestamp=stamp)
        speedup = _speedup_of(rows)
        if speedup is not None:
            entry["speedup"] = speedup
        summary[name] = entry
    if summary:
        _write_summary(summary)


if __name__ == "__main__":
    main()
