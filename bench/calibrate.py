"""Readings that set a cell's ``correct`` limit; not part of a benchmark run.

    python bench/calibrate.py --workload <cell> --seconds <s> --seeds <n> [<n> ...] [--no-listeners]

On the accelerator, in one process, runs the cell once per seed as a
benchmark run does (its own load, its sample of finished requests), and
reads beside the program's numbers the control's: the family module's
reference in the precision below the configuration's (``gaps(...,
control=True)``), at the same prompts and served tokens, taking the gap
of the token it puts first.  The control's numbers are judged by
the cell's own limits, so each line says whether the program and the
control come out correct.  ``--no-listeners`` leaves out the compile and
garbage-collection listeners, to see whether a host stall comes without
them.  One JSON line per seed.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from run import ROOT, use_compile_cache  # noqa: F401  (sets sys.path)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python bench/calibrate.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--no-listeners", action="store_true")
    args = ap.parse_args(argv)

    from bench import cells, device, measure

    cell = cells.load_cell(args.workload)
    use_compile_cache()
    devices, peaks = device.accelerator(cell.chips)
    for seed in args.seeds:
        t0 = time.perf_counter()
        res = measure.measure(cell, seed, args.seconds, False, devices, peaks,
                              t0, control=True, listen=not args.no_listeners)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "correct": res["correct"],
                          "control_correct": res["control"]["correct"],
                          **res["numbers"],
                          "metrics": {k: v["value"]
                                      for k, v in res["metrics"].items()},
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
