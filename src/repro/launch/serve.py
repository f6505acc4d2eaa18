"""Serving driver: ``python -m repro.launch.serve --arch <id> [...]``.

Boots the DecodeEngine (continuous batching with DLS admission and
lane-isolated KV/recurrent caches) on the selected architecture and
pushes a synthetic ragged request mix through it.  Weights are random
(seeded) and built on the device in the compute dtype (bf16), which is
what lets ``--full`` widths fit one chip.

With ``--replicas N`` the driver runs the two-level cluster path
(`repro.serve.cluster`): a ``ClusterRouter`` distributes the request
stream across N replica engines with the ``--node-technique`` schedule
(a replica pull is a node-sized chunk; replicas report measured decode
steps back, so adaptive node techniques learn replica throughput), and
each replica's engine keeps its own intra-node ``--technique``.  Replica
``i`` holds its params and state on local device ``i`` (modulo the
device count); the host drives the replica engines one after another.

`parse_args` + `build` are the one way to set up a serving run; the CLI
here and ``chip_smoke.py`` both go through them.
"""

from __future__ import annotations

import argparse
import dataclasses
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ARCHS, ModelConfig, get_arch, smoke_config
from ..core.schedule import ScheduleSpec, resolve
from ..models import init_decoder
from ..serve.engine import DecodeEngine
from ..serve.scheduler import Request
from .compile_cache import use_compile_cache


@dataclasses.dataclass
class Serving:
    """Everything a serving run needs besides the engines."""

    cfg: ModelConfig
    params: Any
    spec: ScheduleSpec        # intra-engine admission technique
    node_spec: ScheduleSpec   # node-level technique (--replicas > 1)
    requests: list[Request]


def serving_init(cfg: ModelConfig):
    """Jitted ``seed -> params``: random weights created on the device,
    floating leaves cast to ``cfg.compute_dtype`` inside the same program,
    so the float32 tree (training's masters) is never held whole."""
    dt = jnp.dtype(cfg.compute_dtype)

    def init(seed):
        params, _ = init_decoder(jax.random.key(seed), cfg)
        return jax.tree.map(
            lambda a: a.astype(dt) if jnp.issubdtype(a.dtype, jnp.floating)
            else a, params)

    return jax.jit(init)


def make_requests(n: int, *, seed: int, prompt_len: Sequence[int],
                  new_tokens: Sequence[int]) -> list[Request]:
    """``n`` seeded requests; prompt and new-token counts are drawn
    uniformly from the inclusive ``(lo, hi)`` ranges."""
    rng = np.random.default_rng(seed)
    return [Request(
        rid=i, arrival=0.0,
        prompt_len=int(rng.integers(prompt_len[0], prompt_len[1] + 1)),
        max_new_tokens=int(rng.integers(new_tokens[0], new_tokens[1] + 1)))
        for i in range(n)]


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(prog="python -m repro.launch.serve")
    ap.add_argument("--arch", required=True, choices=sorted(ARCHS))
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--prompt-len", type=int, nargs=2, metavar=("LO", "HI"),
                    default=None,
                    help="inclusive prompt-length range "
                         "(default: 4 .. max_len/4)")
    ap.add_argument("--new-tokens", type=int, nargs=2, metavar=("LO", "HI"),
                    default=None,
                    help="inclusive new-token range (default: 4 .. max_len/4)")
    ap.add_argument("--technique", default=None,
                    help="DLS admission ScheduleSpec, e.g. 'fac2,8' "
                         "(default: $LB_SCHEDULE, else fac2)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serving replicas; >1 enables the two-level "
                         "cluster path (node-level DLS over engines)")
    ap.add_argument("--node-technique", default="awf_b",
                    help="node-level ScheduleSpec for --replicas > 1 "
                         "(a replica pull is a node-sized chunk)")
    ap.add_argument("--kv8", action="store_true",
                    help="int8-quantized KV cache")
    ap.add_argument("--full", action="store_true",
                    help="published widths (default: the smoke config)")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def build(args: argparse.Namespace) -> Serving:
    """Config, admission specs, seeded requests and on-device params."""
    cfg = get_arch(args.arch)
    if not args.full:
        cfg = smoke_config(cfg)
    if args.kv8:
        cfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
    default_range = (4, args.max_len // 4)
    requests = make_requests(
        args.requests, seed=args.seed,
        prompt_len=args.prompt_len or default_range,
        new_tokens=args.new_tokens or default_range)
    return Serving(
        cfg=cfg, params=serving_init(cfg)(args.seed),
        spec=resolve(args.technique, default="fac2"),
        node_spec=resolve(args.node_technique, default="awf_b"),
        requests=requests)


def make_engine(sv: Serving, args: argparse.Namespace,
                device: Optional[jax.Device] = None) -> DecodeEngine:
    return DecodeEngine(sv.cfg, sv.params, slots=args.slots,
                        max_len=args.max_len, technique=sv.spec,
                        device=device)


def _device_ids(*trees) -> list[int]:
    """Ids of the devices holding any array leaf of ``trees``."""
    return sorted({d.id for t in trees for leaf in jax.tree.leaves(t)
                   for d in leaf.devices()})


def run_cluster(sv: Serving, args: argparse.Namespace) -> dict:
    """Two-level serving: node-level DLS over replica DecodeEngines.

    Replica ``i`` is placed on ``jax.devices()[i % n]``.  The engines run
    one node-sized chunk at a time, one after another on the host.  The
    router's measured unit is decode steps — the same unit the engines
    feed their intra-node scheduler.
    """
    from ..core.metrics import cov, percent_imbalance
    from ..serve.cluster import ClusterRouter

    replicas = args.replicas
    devices = jax.devices()
    engines = [make_engine(sv, args, device=devices[i % len(devices)])
               for i in range(replicas)]
    router = ClusterRouter(replicas, schedule=sv.node_spec)
    for r in sv.requests:
        router.submit(r)
    steps = np.zeros(replicas)
    completed = tokens = 0
    outputs: dict[int, list[int]] = {}
    while True:
        rep = int(np.argmin(steps))
        chunk = router.pull(rep)
        if not chunk:
            break
        for q in chunk:
            engines[rep].submit(q)
        stats = engines[rep].run()
        router.complete(rep, busy=float(stats.steps))
        steps[rep] += stats.steps
        completed += stats.completed
        tokens += stats.tokens
        for q in chunk:
            outputs[q.rid] = engines[rep].output(q.rid)
    return dict(completed=completed, tokens=tokens, outputs=outputs,
                engines=engines,
                replica_steps=steps.tolist(),
                replica_requests=router.replica_requests.tolist(),
                replica_devices=[_device_ids(e.params, e.state)
                                 for e in engines],
                node_chunks=router.node_chunks,
                cross_node_cov=cov(steps),
                cross_node_pi=percent_imbalance(steps))


def main(argv: Optional[Sequence[str]] = None):
    args = parse_args(argv)
    use_compile_cache()
    sv = build(args)

    if args.replicas > 1:
        print(f"arch={sv.cfg.name} replicas={args.replicas} "
              f"slots={args.slots} schedule={sv.node_spec}/{sv.spec}")
        out = run_cluster(sv, args)
        print(f"completed={out['completed']}/{args.requests} "
              f"tokens={out['tokens']} node_chunks={out['node_chunks']} "
              f"replica_requests={out['replica_requests']} "
              f"replica_devices={out['replica_devices']}")
        print(f"cross-node steps c.o.v.={out['cross_node_cov']:.3f} "
              f"p.i.={out['cross_node_pi']:.1f}%")
        return

    print(f"arch={sv.cfg.name} slots={args.slots} technique={sv.spec}")
    eng = make_engine(sv, args)
    for r in sv.requests:
        eng.submit(r)
    stats = eng.run()
    print(f"completed={stats.completed}/{args.requests} "
          f"steps={stats.steps} new_tokens={stats.tokens} "
          f"({stats.tok_per_s:.0f} tok/s)")
    print("sample output:", eng.output(0)[:12])


if __name__ == "__main__":
    main()
