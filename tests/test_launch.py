"""Launch-layer tests: HLO collective parsing, sharding rule resolution,
variant plumbing, input specs — everything that doesn't need 512 devices.

(The real 512-device lower+compile proof is exercised by
`python -m repro.launch.dryrun --all --both-meshes`; its artifacts are
validated in test_dryrun_artifacts.py when present.)
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS, SHAPES, input_specs, shape_applicable
from repro.sharding import DEFAULT_RULES, logical_to_spec, shard_as, use_rules


# --- collective parser -------------------------------------------------------


def test_parse_collectives_counts_known_hlo():
    from repro.launch.dryrun import parse_collectives

    hlo = """
  %ar = f32[1024,512] all-reduce(f32[1024,512] %x), replica_groups={{0,1,2,3}}, to_apply=%add
  %ag = bf16[64,256] all-gather(bf16[16,256] %y), replica_groups=[4,16]<=[64], dimensions={0}
  %rs = f32[128] reduce-scatter(f32[1024] %z), replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=%add
  %cp = f32[32,32] collective-permute(f32[32,32] %w), source_target_pairs={{0,1}}
  %noise = f32[2,2] add(f32[2,2] %a, f32[2,2] %b)
"""
    out = parse_collectives(hlo)
    assert out["ops"]["all-reduce"]["count"] == 1
    ar_bytes = 1024 * 512 * 4
    assert out["ops"]["all-reduce"]["result_bytes"] == ar_bytes
    assert out["ops"]["all-reduce"]["wire_bytes"] == pytest.approx(
        2 * ar_bytes * 3 / 4)
    assert out["ops"]["all-gather"]["count"] == 1
    ag_bytes = 64 * 256 * 2
    assert out["ops"]["all-gather"]["wire_bytes"] == pytest.approx(
        ag_bytes * 15 / 16)
    assert out["ops"]["reduce-scatter"]["wire_bytes"] == pytest.approx(
        128 * 4 * 7)
    assert "add" not in out["ops"]
    assert out["n_ops"] == 4


def test_parse_collectives_skips_trivial_groups():
    from repro.launch.dryrun import parse_collectives

    hlo = "%ar = f32[8] all-reduce(f32[8] %x), replica_groups={{0}}, to_apply=%a"
    assert parse_collectives(hlo)["n_ops"] == 0


# --- sharding rules ----------------------------------------------------------


@pytest.fixture(scope="module")
def mesh():
    n = len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def test_logical_to_spec_divisibility_fallback(mesh):
    rules = DEFAULT_RULES.with_mesh(mesh)
    # vocab divisible by model(1) -> sharded (trivially); heads dim of 14
    # not divisible by a hypothetical 16 would fall back — emulate with a
    # 2-way data mesh if available
    spec = logical_to_spec(rules, ("batch", "seq"), (4, 128))
    assert isinstance(spec, P)


def test_logical_to_spec_no_duplicate_mesh_axes(mesh):
    rules = DEFAULT_RULES.with_mesh(mesh)
    # batch -> (pod, data); embed -> data: the second use must drop
    spec = logical_to_spec(rules, ("batch", "embed"), (8, 64))
    flat = []
    for s in spec:
        if isinstance(s, (tuple, list)):
            flat.extend(s)
        elif s is not None:
            flat.append(s)
    assert len(flat) == len(set(flat))


def test_shard_as_noop_without_rules():
    x = jnp.ones((4, 4))
    assert shard_as(x, "batch", "seq") is x


def test_shard_as_applies_constraint(mesh):
    rules = DEFAULT_RULES.with_mesh(mesh)
    with use_rules(rules):
        y = jax.jit(lambda x: shard_as(x, "batch", None))(jnp.ones((4, 4)))
    assert y.shape == (4, 4)


# --- configs / input specs ---------------------------------------------------


def test_input_specs_shapes():
    cfg = ARCHS["qwen3-4b"]
    sp = input_specs(cfg, SHAPES["train_4k"])
    assert sp["tokens"].shape == (256, 4096)
    assert sp["labels"].shape == (256, 4096)
    sp = input_specs(cfg, SHAPES["decode_32k"])
    assert sp["tokens"].shape == (128, 1)
    # vlm prefix reduces the token body
    vlm = ARCHS["internvl2-1b"]
    sp = input_specs(vlm, SHAPES["train_4k"])
    assert sp["tokens"].shape == (256, 4096 - vlm.prefix_len)
    assert sp["prefix_embed"].shape == (256, vlm.prefix_len, vlm.d_model)


def test_shape_applicability_matrix():
    n_skip = 0
    for arch in ARCHS.values():
        for shape in SHAPES.values():
            ok, reason = shape_applicable(arch, shape)
            if not ok:
                n_skip += 1
                assert shape.name == "long_500k"
                assert "full-attention" in reason
    assert n_skip == 9  # exactly the 9 structurally-skipped cells


def test_variant_config_composition():
    from repro.launch.dryrun import variant_config, variant_rules

    cfg = variant_config(ARCHS["qwen3-moe-30b-a3b"], "ragged+zero3")
    assert cfg.moe.dispatch == "ragged"
    rules = variant_rules("ragged+zero3")
    assert rules["embed"] is None
    assert rules["mlp"] == ("model", "data")
    cfg = variant_config(ARCHS["codeqwen1.5-7b"], "kv8")
    assert cfg.kv_cache_dtype == "int8"
    with pytest.raises(KeyError):
        variant_config(ARCHS["qwen3-4b"], "nope")


def test_make_production_mesh_shapes():
    # the mesh constructor itself is a pure function of flags; on a 1-CPU
    # host it will fail to build 256 devices, so only validate the axis
    # logic via the spec (the dry-run proves the real thing)
    from repro.launch.mesh import make_production_mesh

    if len(jax.devices()) >= 512:
        m = make_production_mesh(multi_pod=True)
        assert m.shape == {"pod": 2, "data": 16, "model": 16}


def test_replica_submeshes_partition_data_axis():
    """Replica = data-parallel submesh: the split covers every device
    exactly once, keeps axis names, and rejects non-dividing counts."""
    from repro.launch.mesh import make_host_mesh, replica_submeshes

    mesh = make_host_mesh()
    n = mesh.devices.shape[0]
    subs = replica_submeshes(mesh, n)
    assert len(subs) == n
    seen = []
    for sub in subs:
        assert sub.axis_names == mesh.axis_names
        assert sub.devices.shape == (1,) + mesh.devices.shape[1:]
        seen.extend(sub.devices.flat)
    assert sorted(d.id for d in seen) == sorted(
        d.id for d in mesh.devices.flat)
    with pytest.raises(ValueError, match="does not split"):
        replica_submeshes(mesh, 2 * n + 1)
    with pytest.raises(ValueError):
        replica_submeshes(mesh, 0)


# --- kv8 decode consistency --------------------------------------------------


def test_kv8_decode_close_to_bf16():
    import dataclasses

    from repro.configs import smoke_config
    from repro.models import (decode_step, forward, init_decode_state,
                              init_decoder)

    cfg = dataclasses.replace(smoke_config(ARCHS["codeqwen1.5-7b"]),
                              prefix_len=0, compute_dtype="float32")
    params, _ = init_decoder(jax.random.key(0), cfg)
    b, s = 2, 16
    toks = jax.random.randint(jax.random.key(1), (b, s), 0, cfg.vocab_size)
    full, _ = jax.jit(lambda p: forward(p, cfg, toks))(params)

    cfg8 = dataclasses.replace(cfg, kv_cache_dtype="int8")
    st = init_decode_state(cfg8, b, max_len=s)
    step = jax.jit(lambda p, st, t: decode_step(p, cfg8, st, t))
    outs = []
    for i in range(s):
        lg, st = step(params, st, toks[:, i:i + 1])
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, axis=1)
    rel = float(jnp.max(jnp.abs(dec - full)) /
                (jnp.max(jnp.abs(full)) + 1e-9))
    assert rel < 5e-2, rel  # int8 cache: small, bounded degradation


# --- serving launcher ---------------------------------------------------------


def _serve_args(*extra):
    from repro.launch.serve import parse_args

    return parse_args(["--arch", "qwen3-4b", "--requests", "6",
                       "--slots", "2", "--max-len", "32", *extra])


def test_serving_build_casts_weights_and_draws_requests():
    from repro.launch.serve import build

    sv = build(_serve_args("--prompt-len", "3", "5",
                           "--new-tokens", "2", "2"))
    assert {leaf.dtype for leaf in jax.tree.leaves(sv.params)} == {
        jnp.dtype(sv.cfg.compute_dtype)}
    assert [r.rid for r in sv.requests] == list(range(6))
    assert all(3 <= r.prompt_len <= 5 for r in sv.requests)
    assert all(r.max_new_tokens == 2 for r in sv.requests)


def test_serve_cluster_matches_one_engine():
    """The --replicas path serves every request once, with the same greedy
    tokens as one engine, and reports where each replica's arrays live."""
    from repro.launch.serve import build, make_engine, run_cluster

    args = _serve_args("--replicas", "3")
    sv = build(args)
    eng = make_engine(sv, args)
    for r in sv.requests:
        eng.submit(r)
    assert eng.run().completed == len(sv.requests)
    out = run_cluster(sv, args)
    assert out["completed"] == len(sv.requests)
    assert out["outputs"] == {r.rid: eng.output(r.rid) for r in sv.requests}
    n = len(jax.devices())
    assert out["replica_devices"] == [[jax.devices()[i % n].id]
                                      for i in range(3)]
