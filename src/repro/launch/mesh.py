"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (required by the dry-run's
xla_force_host_platform_device_count dance).
"""

from __future__ import annotations

import jax
import numpy as np

from ..sharding import DEFAULT_RULES, ShardingRules


def _auto_mesh(shape, axes):
    """Mesh whose axes are all ``Auto``: the sharding rules place arrays
    with ``with_sharding_constraint``, which Explicit axes refuse."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False,
                         dm_shape: tuple[int, int] | None = None):
    """16x16 = 256 chips/pod; multi-pod adds a leading pod=2 axis.
    `dm_shape` overrides the (data, model) split (TP/FSDP ratio knob,
    §Perf) — the product must stay 256."""
    d, m = dm_shape or (16, 16)
    assert d * m == 256, (d, m)
    shape = (2, d, m) if multi_pod else (d, m)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist locally (smoke/integration tests)."""
    n = len(jax.devices())
    return _auto_mesh((n, 1), ("data", "model"))


def replica_submeshes(mesh, num_replicas: int, axis: str = "data"):
    """Replica = data-parallel submesh — the cluster layer's "node".

    Splits ``mesh`` into ``num_replicas`` contiguous submeshes along
    ``axis`` (each keeps the full model axis), one per serving replica:
    the ``ClusterRouter`` (`repro.serve.cluster`) hands node-sized
    request chunks to replicas, and each replica's ``DecodeEngine`` runs
    on its own submesh with its intra-node technique.  The axis size
    must divide evenly — replicas are homogeneous in device count
    (heterogeneous *throughput* is what the node-level AWF weights
    learn).
    """
    if num_replicas <= 0:
        raise ValueError(f"need num_replicas > 0, got {num_replicas}")
    ax = mesh.axis_names.index(axis)
    size = mesh.devices.shape[ax]
    if size % num_replicas:
        raise ValueError(
            f"mesh axis {axis!r} of size {size} does not split into "
            f"{num_replicas} replicas")
    return [jax.sharding.Mesh(sub, mesh.axis_names)
            for sub in np.split(mesh.devices, num_replicas, axis=ax)]


def production_rules(mesh, overrides: dict | None = None) -> ShardingRules:
    rules = DEFAULT_RULES.with_mesh(mesh)
    # KV caches are sharded along the *sequence* dim on the model axis by
    # default: it works for every kv-head count (incl. MQA) and bounds the
    # per-device cache at S/16.  MHA archs whose kv-heads divide the model
    # axis override this to head-sharding (no softmax-stat collectives).
    rules = rules.replace(seq_cache="model")
    if overrides:
        rules = rules.replace(**overrides)
    return rules
