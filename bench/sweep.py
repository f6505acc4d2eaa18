"""Find the highest rate an open-loop cell sustains; not part of a run.

    python bench/sweep.py --workload <cell> --seconds <s> --rates <r> [<r> ...]

On the accelerator, in one process: builds the cell's engine once, then
serves the cell's mix at each rate in turn (``pre_s`` of arrivals, a
window of ``--seconds``, a full drain), and prints per rate the requests
due, the backlog still waiting at the close, the queue wait over the
window's first and second halves, and the time to first token.  A rate
is sustained while the backlog at the close stays within a few requests
and the second half's queue wait does not grow past the first's.
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from run import ROOT, use_compile_cache  # noqa: F401  (sets sys.path)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python bench/sweep.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    from bench import cells, device, measure, stats
    from bench.drive import drive
    from bench.traffic import open_loop
    from bench.weights import make_weights

    cell = cells.load_cell(args.workload)
    use_compile_cache()
    devices, _ = device.accelerator(cell.chips)
    conf = cell.config
    cfg = cells.model_config(conf, cell.root)
    weights = make_weights(cfg, cell.family, args.seed, devices[0])
    engine = measure.build_engine(cfg, weights, conf["slots"], conf["max_len"],
                                  devices[0])
    measure.warm_up(engine, conf["slots"])
    for rate in args.rates:
        mix = dict(cell.traffic, rate_rps=rate)
        traffic = open_loop(mix, args.seed, args.seconds, vocab=cfg.vocab_size,
                            max_len=conf["max_len"])
        run = drive(engine, traffic, open_loop=True, seconds=args.seconds,
                    pre_s=mix["pre_s"], backlog=0, drain_cap_s=600.0)
        ws, we = run.window
        mid = (ws + we) / 2
        waits = np.array(stats.queue_wait(run))
        dues = np.array([r.due for r in run.attempted])
        backlog = sum(1 for r in run.attempted
                      if not (r.admit_step >= 0 and r.admit < we))
        ttft = stats.ttft(run)
        steps = (run.step_t1 >= ws) & (run.step_t1 < we)
        print(json.dumps({
            "rate_rps": rate, "due": len(run.attempted),
            "waiting_at_close": backlog,
            "queue_wait_p50_first_half_s": float(np.median(waits[dues < mid])),
            "queue_wait_p50_second_half_s": float(np.median(waits[dues >= mid])),
            "queue_wait_p90_s": stats.percentile(waits, 90),
            "ttft_p50_s": stats.percentile(ttft, 50),
            "ttft_p90_s": stats.percentile(ttft, 90),
            "steps_per_s": float(steps.sum() / args.seconds),
            "failed": stats.failed(run)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
