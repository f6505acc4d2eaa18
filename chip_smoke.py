"""Smoke run of the serving path on a TPU: ``python chip_smoke.py``.

Serves qwen3-4b at its published widths (bf16 weights, random from a
seed) through ``repro.launch.serve`` on one chip: 8 decode slots,
``max_len`` 2048, 8 seeded requests with 16-128 prompt tokens and 32-64
new tokens, default fac2 admission.  It checks that every request
completes, that the logits are finite, and lane isolation: request 0
decoded alone through the same engine gives the same greedy tokens as in
the batched run.

``--four-chips`` runs only the cluster path instead: ``--replicas 4``
with awf_b node-level routing over four engines, engine ``i`` on device
``i``, compared per request with one engine on device 0.

Every timing printed is a smoke figure, not a benchmark.  The script
fails (non-zero exit, no ``"ok"`` line) when JAX finds no TPU or when a
check fails.  The last line of a passing run is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARGV = ["--arch", "qwen3-4b", "--full", "--slots", "8", "--max-len", "2048",
        "--requests", "8", "--prompt-len", "16", "128",
        "--new-tokens", "32", "64", "--seed", "0"]


class Failed(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise Failed(what)
    print(f"check passed: {what}")


def tree_bytes(tree) -> int:
    """Bytes of the arrays (or shape structs) in ``tree``."""
    import math

    import jax
    import numpy as np

    return sum(math.prod(leaf.shape) * np.dtype(leaf.dtype).itemsize
               for leaf in jax.tree.leaves(tree))


def memory(tag: str, count: int = 1) -> None:
    """Device memory in use now and at peak so far, per device."""
    import jax

    for d in jax.devices()[:count]:
        st = d.memory_stats() or {}
        print(f"memory after {tag}: device {d.id} "
              f"bytes_in_use={st.get('bytes_in_use')} "
              f"peak_bytes_in_use={st.get('peak_bytes_in_use')}")


def logits_finite(eng) -> bool:
    import jax.numpy as jnp

    lg = eng.last_logits
    return (lg is not None
            and lg.shape == (eng.slots, 1, eng.cfg.padded_vocab)
            and bool(jnp.isfinite(lg).all()))


def serve_batch(serve, sv, args, requests):
    """All ``requests`` through one fresh engine on device 0."""
    eng = serve.make_engine(sv, args)
    for r in requests:
        eng.submit(r)
    t0 = time.perf_counter()
    stats = eng.run()
    eng.last_logits.block_until_ready()
    wall = time.perf_counter() - t0
    return eng, stats, wall, {r.rid: list(eng.output(r.rid))
                              for r in requests}


def run_probing(eng, rid: int, probe_step: int):
    """``eng.run()`` paused after ``probe_step`` decode steps to read the
    logits row of the lane decoding ``rid``.  Returns (completed, steps,
    tokens, wall seconds, that row as float32 NumPy)."""
    import numpy as np

    t0 = time.perf_counter()
    first = eng.run(max_steps=probe_step)
    lane = eng.lane_requests.index(rid)
    row = np.asarray(eng.last_logits[lane, 0])
    rest = eng.run()
    eng.last_logits.block_until_ready()
    wall = time.perf_counter() - t0
    return (first.completed + rest.completed, first.steps + rest.steps,
            first.tokens + rest.tokens, wall, row)


def one_chip(serve, sv, args) -> None:
    import dataclasses

    import numpy as np

    reqs = sv.requests
    r0 = reqs[0]
    # request 0 is admitted at the first step in both runs and decodes
    # for prompt + new - 1 steps; one step before its last it still holds
    # its lane, with its whole cache written
    probe = r0.prompt_len + r0.max_new_tokens - 2

    eng = serve.make_engine(sv, args)
    memory("engine creation")
    for r in reqs:
        eng.submit(r)
    done, steps, tokens, wall, row_batched = run_probing(eng, r0.rid, probe)
    batched = {r.rid: list(eng.output(r.rid)) for r in reqs}
    memory("batched run")
    print(f"batched run: completed={done}/{len(reqs)} decode_steps={steps} "
          f"new_tokens={tokens} wall_s={wall} "
          f"(smoke figure, includes compile)")
    check(done == len(reqs), "every request completed")
    check(logits_finite(eng) and bool(np.isfinite(row_batched).all()),
          "batched-run logits finite")
    check(all(len(batched[r.rid]) == r.max_new_tokens for r in reqs),
          "every request emitted its max_new_tokens")

    eng.submit(dataclasses.replace(r0))
    done, steps, tokens, wall, row_solo = run_probing(eng, r0.rid, probe)
    solo = eng.output(r0.rid)
    memory("solo run")
    print(f"solo run of request 0: decode_steps={steps} new_tokens={tokens} "
          f"wall_s={wall} (smoke figure)")
    print(f"request 0 greedy tokens, batched: {batched[0][:16]}")
    print(f"request 0 greedy tokens, solo:    {solo[:16]}")
    check(done == 1, "solo request completed")
    check(logits_finite(eng) and bool(np.isfinite(row_solo).all()),
          "solo-run logits finite")
    check(solo == batched[0],
          "lane isolation: request 0 alone decodes the same greedy tokens")
    # the greedy tokens of a deep random model can settle on one token, so
    # the logits are compared too.  A lane's arithmetic reads no other
    # lane, so they should be bitwise equal; the check allows 1e-2 (the
    # logits' spread is about 1) and the line above reports which held
    diff = float(np.max(np.abs(row_batched - row_solo)))
    print(f"request 0 logits at step {probe}: batched vs solo "
          f"max|diff|={diff} bitwise_equal={np.array_equal(row_batched, row_solo)} "
          f"std={float(row_batched.std())} distinct batched tokens="
          f"{len({t for out in batched.values() for t in out})}")
    check(diff <= 1e-2,
          "lane isolation: request 0's logits alone match the batched run")


def four_chips(serve, sv, args) -> None:
    import jax

    reqs = sv.requests
    eng, stats, wall, ref = serve_batch(serve, sv, args, reqs)
    print(f"one engine on device 0: completed={stats.completed}/{len(reqs)} "
          f"decode_steps={stats.steps} wall_s={wall} (smoke figure)")
    check(stats.completed == len(reqs), "one-chip run completed")
    del eng  # frees its decode state on device 0
    memory("one-chip reference run", 4)

    args.replicas = 4
    t0 = time.perf_counter()
    out = serve.run_cluster(sv, args)
    wall = time.perf_counter() - t0
    print(f"cluster: completed={out['completed']}/{len(reqs)} "
          f"replica_requests={out['replica_requests']} "
          f"replica_steps={out['replica_steps']} "
          f"replica_devices={out['replica_devices']} "
          f"wall_s={wall} (smoke figure, includes compile)")
    memory("cluster run", 4)
    check(out["completed"] == len(reqs), "cluster run completed")
    check(all(logits_finite(e) for e in out["engines"]),
          "every replica's logits finite")
    check(out["outputs"] == ref,
          "greedy outputs per request match the one-chip engine")
    ids = [d.id for d in jax.devices()[:4]]
    check(out["replica_devices"] == [[i] for i in ids],
          "replica i holds its params and state on device i only")
    check(all(n > 0 for n in out["replica_requests"]),
          "every replica served requests")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-replica cluster path and the "
                         "one-chip engine it is compared with")
    opts = ap.parse_args(argv)

    import jax

    from repro.launch import serve
    from repro.launch.compile_cache import use_compile_cache

    print(f"compile cache: {use_compile_cache()}")
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX's first device is {dev.platform} "
              f"({dev.device_kind}); this smoke runs on the chip only",
              file=sys.stderr)
        return 1
    need = 4 if opts.four_chips else 1
    if len(devices) < need:
        print(f"need {need} chips, JAX sees {len(devices)}", file=sys.stderr)
        return 1
    print(f"device: {dev.platform} kind={dev.device_kind!r} "
          f"count={len(devices)}")

    compile_s = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compile_s.append(secs)
        if event == "/jax/core/compile/backend_compile_duration" else None)

    args = serve.parse_args(ARGV)
    t0 = time.perf_counter()
    sv = serve.build(args)
    jax.block_until_ready(sv.params)
    cfg = sv.cfg
    print(f"model {cfg.name}: layers={cfg.num_layers} d_model={cfg.d_model} "
          f"heads={cfg.num_heads}/{cfg.num_kv_heads} "
          f"head_dim={cfg.resolved_head_dim} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size} tied={cfg.tie_embeddings} "
          f"weights={cfg.compute_dtype}")
    print(f"param_bytes={tree_bytes(sv.params)} "
          f"init_s={time.perf_counter() - t0} (smoke figure)")
    memory("weight init")
    print("requests (prompt_len, max_new_tokens): "
          f"{[(r.prompt_len, r.max_new_tokens) for r in sv.requests]}")

    from repro.models import init_decode_state

    state = init_decode_state(cfg, args.slots, max_len=args.max_len,
                              spec=True)
    print(f"state_bytes={tree_bytes(state)} per engine "
          f"({args.slots} slots x max_len {args.max_len})")

    try:
        (four_chips if opts.four_chips else one_chip)(serve, sv, args)
    except Failed as e:
        print(f"check FAILED: {e}", file=sys.stderr)
        return 1

    memory("all phases", need)
    print(f"backend compile s={sum(compile_s)} over {len(compile_s)} "
          f"programs (smoke figure)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
