"""Run one benchmark cell on the accelerator and print one JSON line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  ``--trace 0`` reports the cell's
end-to-end metrics; ``--trace 1`` its per-layer metrics, from a profiler
trace of the window's last seconds and from the run's records, with the
device's busy time and a breakdown.  The last line of standard output is
the result; the last lines of standard error are the numbers compared
for ``correct``, each beside its limit.  Without a TPU (or with fewer
chips than the cell asks for) it exits with 1 and prints no result.

JAX's persistent compilation cache is ``$JAX_COMPILATION_CACHE_DIR``
where that is set, else ``.jax_cache/`` at the checkout's root.
"""

from __future__ import annotations

import time

START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(prog="python bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def use_compile_cache() -> None:
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    # every program, however quick to compile, so a warm set-up builds none
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    args = parse_args(argv)
    from bench import cells, device

    cell = cells.load_cell(args.workload)
    use_compile_cache()
    t_jax = time.perf_counter()
    try:
        devices, peaks = device.accelerator(cell.chips)
    except device.NoAccelerator as e:
        print(f"no result: {e}", file=sys.stderr)
        return 1
    from bench.measure import measure

    print(f"start s: to JAX imported {t_jax - START:.3f}, to the chip found "
          f"{time.perf_counter() - t_jax:.3f}, to the harness imported "
          f"{time.perf_counter() - START:.3f}", flush=True)

    result = measure(cell, args.seed, args.seconds, bool(args.trace),
                     devices, peaks, START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
