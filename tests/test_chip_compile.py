"""Compile the main path for a described TPU v5e chip (no chip attached).

The TPU compiler refuses what interpret mode accepts: Mosaic layouts a
kernel cannot use, and programs that do not fit the device's memory.
These tests compile the three Pallas kernels at real widths, and the
full-width qwen3-4b, 8-layer qwen3-moe-30b-a3b and 8-layer
qwen3-next-80b-a3b (128 of 512 experts held) decode steps exactly as
``DecodeEngine`` jits them.
A compile is not a run: nothing here says anything about results or
times.

The topology is described inside a module fixture, never at import:
only one process may hold the TPU library, and every test worker
imports this file.
"""

import dataclasses
import hashlib
import math
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

# what the compiler lets one v5e program use ("Used ... of 15.75G hbm")
V5E_PROGRAM_BYTES = 15.75e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _is_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles(one_chip):
    from repro.kernels.flash_attention.flash_attention import (
        flash_attention_bhsd)

    q = _spec((32, 2048, 128), jnp.bfloat16, one_chip)
    compiled = jax.jit(flash_attention_bhsd).lower(q, q, q).compile()
    assert _is_kernel(compiled)


def test_flash_attention_sched_ragged_compiles(one_chip):
    from repro.kernels.flash_attention.flash_attention import (
        flash_attention_sched_bhsd)

    bh, s = 32, 2048
    kv_lens = np.linspace(100, s, bh).astype(np.int64)
    q = _spec((bh, s, 128), jnp.bfloat16, one_chip)
    compiled = jax.jit(lambda q, k, v: flash_attention_sched_bhsd(
        q, k, v, kv_lens=kv_lens)).lower(q, q, q).compile()
    assert _is_kernel(compiled)


@pytest.mark.parametrize("e,d,f", [(128, 2048, 768), (32, 1024, 512)])
def test_grouped_matmul_compiles(one_chip, e, d, f):
    from repro.kernels.grouped_matmul.grouped_matmul import (
        grouped_matmul_tiles)

    t = 2 * e
    compiled = jax.jit(grouped_matmul_tiles).lower(
        _spec((t, 128, d), jnp.bfloat16, one_chip),
        _spec((e, d, f), jnp.bfloat16, one_chip),
        _spec((t,), jnp.int32, one_chip)).compile()
    assert _is_kernel(compiled)


def _top_level(text: str) -> list[str]:
    """The instructions of the entry and the loop bodies: of every
    computation that no fusion calls."""
    fused = set(re.findall(r"fusion\(.*?calls=(%[\w.-]+)", text))
    top, in_fusion = [], False
    for line in text.splitlines():
        if re.match(r"^\S.*\{$", line):
            words = line.split()
            in_fusion = words[words[0] == "ENTRY"] in fused
        elif not in_fusion and re.match(r"\s+(ROOT )?%\S+ = ", line):
            top.append(line.strip())
    return top


def _results(line: str) -> tuple[str, list[tuple[str, str]]]:
    """An instruction's op and its results as (type, layout), e.g.
    ("bf16[36,16,2048,8,128]", "4,3,2,1,0:T(8,128)(2,1)")."""
    rhs = line.split(" = ", 1)[1]
    end = rhs.index(") ") + 1 if rhs.startswith("(") else rhs.index(" ")
    op = rhs[end + 1:].split("(", 1)[0]
    return op, re.findall(r"(\w+\[[\d,]*\])\{([^}]*)\}", rhs[:end])


def test_qwen3_4b_decode_step_fits_one_chip(one_chip):
    """bf16 weights, 8 lanes x max_len 2048, state donated: the program
    fits, and the donated state is aliased instead of double-buffered."""
    from repro.configs import get_arch
    from repro.launch.serve import serving_init
    from repro.models import init_decode_state
    from repro.serve.engine import decode_program

    cfg = get_arch("qwen3-4b")

    def place(tree):
        return jax.tree.map(
            lambda s: _spec(s.shape, s.dtype, one_chip), tree)

    params = place(jax.eval_shape(serving_init(cfg), 0))
    assert {leaf.dtype for leaf in jax.tree.leaves(params)} == {
        jnp.dtype(jnp.bfloat16)}
    state = place(init_decode_state(cfg, 8, max_len=2048, spec=True))
    tokens = _spec((8, 1), jnp.int32, one_chip)
    mem = decode_program(cfg).lower(params, state, tokens).compile(
    ).memory_analysis()

    state_bytes = sum(math.prod(leaf.shape) * leaf.dtype.itemsize
                      for leaf in jax.tree.leaves(state))
    assert mem.alias_size_in_bytes >= 0.99 * state_bytes
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert used < V5E_PROGRAM_BYTES, used


def test_qwen3_moe_decode_step_reads_experts_in_place(one_chip):
    """qwen3-moe-30b-a3b, 8 layers, bf16, 32 lanes x 2048: the dense MoE
    runs every expert in one pass at decode, so no layer's expert stack
    is copied out of the stacked weights into a buffer of its own (three
    such copies a layer made 1.34e9 B of temp)."""
    from repro.configs import get_arch
    from repro.launch.serve import serving_init
    from repro.models import init_decode_state
    from repro.serve.engine import decode_program

    cfg = dataclasses.replace(get_arch("qwen3-moe-30b-a3b"), num_layers=8)

    def place(tree):
        return jax.tree.map(
            lambda s: _spec(s.shape, s.dtype, one_chip), tree)

    params = place(jax.eval_shape(serving_init(cfg), 0))
    state = place(init_decode_state(cfg, 32, max_len=2048, spec=True))
    tokens = _spec((32, 1), jnp.int32, one_chip)
    compiled = decode_program(cfg).lower(params, state, tokens).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 0.2e9

    expert_stacks = ("bf16[1,128,2048,768]", "bf16[1,128,768,2048]",
                     "bf16[128,2048,768]", "bf16[128,768,2048]")
    copies = [ln for ln in _top_level(compiled.as_text())
              if ln.split(" = ", 1)[1].startswith(expert_stacks)]
    assert not copies, copies


def test_qwen3_4b_serving_init_builds_bf16_on_chip(one_chip):
    """The serving init writes the bf16 weights directly: its scratch is a
    small fraction of the float32 tree it never holds."""
    from repro.configs import get_arch
    from repro.launch.serve import serving_init

    cfg = get_arch("qwen3-4b")
    seed = _spec((), jnp.int32, one_chip)
    mem = serving_init(cfg).lower(seed).compile().memory_analysis()
    f32_tree_bytes = 2 * mem.output_size_in_bytes
    assert mem.temp_size_in_bytes < 0.05 * f32_tree_bytes
    assert mem.output_size_in_bytes + mem.temp_size_in_bytes < (
        V5E_PROGRAM_BYTES)


def _decode_step(cfg, lanes: int, max_len: int, one_chip):
    from repro.launch.serve import serving_init
    from repro.models import init_decode_state
    from repro.serve.engine import decode_program

    def place(tree):
        return jax.tree.map(
            lambda s: _spec(s.shape, s.dtype, one_chip), tree)

    params = place(jax.eval_shape(serving_init(cfg), 0))
    state = place(init_decode_state(cfg, lanes, max_len=max_len, spec=True))
    tokens = _spec((lanes, 1), jnp.int32, one_chip)
    return decode_program(cfg).lower(params, state, tokens).compile(), state


def _hlo_digest(compiled) -> str:
    """sha256 of the compiled module's text without its metadata (op
    names, source lines) and the file and stack-frame tables, which
    follow the program's source files and not what the program does."""
    lines = compiled.as_text().splitlines()
    first = next(i for i, ln in enumerate(lines)
                 if ln.startswith(("%", "ENTRY")))
    text = "\n".join([lines[0]] + lines[first:])
    text = re.sub(r",? metadata=\{[^}]*\}", "", text)
    return hashlib.sha256(text.encode()).hexdigest()


# the decode programs as they write each token's K and V into the
# stacked cache in place (same JAX and TPU compiler): a later change that
# alters them has to re-pin them here, on purpose
DECODE_HLO = {
    ("qwen3-4b", 36, 8):
        "6ef8784ce1ec7a72552b3f2c7fb6f440c90a0eea57413042b31a02aacf1645b7",
    ("qwen3-moe-30b-a3b", 8, 32):
        "9ce086d34a965cc8da2a216e5b0572b0546b6d695ef34fd4799fa4967e3ba2ac",
}


@pytest.mark.parametrize("arch,layers,lanes", sorted(DECODE_HLO))
def test_existing_decode_steps_compile_to_the_same_hlo(one_chip, arch, layers,
                                                       lanes):
    """The benchmark's qwen3-4b and qwen3-moe-30b-a3b-8l decode steps
    (max_len 2048) compile to the programs pinned above, so a change meant
    for another model or path leaves them as they are."""
    from repro.configs import get_arch

    cfg = dataclasses.replace(get_arch(arch), num_layers=layers)
    compiled, _ = _decode_step(cfg, lanes, 2048, one_chip)
    assert _hlo_digest(compiled) == DECODE_HLO[(arch, layers, lanes)]


# the benchmark's decode programs: layers, lanes, max_len, experts held
BENCH_DECODE = {
    "qwen3-4b": (36, 16, 2048, 0),
    "qwen3-moe-30b-a3b": (8, 32, 2048, 0),
    "qwen3-next-80b-a3b": (8, 128, 4096, 128),
}


@pytest.mark.parametrize("arch", sorted(BENCH_DECODE))
def test_decode_step_writes_one_position_of_the_stacked_cache(one_chip,
                                                              arch):
    """Each layer's attention writes the new token's K and V into the
    stacked cache in place: outside any fusion no instruction writes a
    layer back into the stack (a dynamic-update-slice) or copies the stack
    or a layer of it, and the part of a layer read at a time is staged in
    the core's own memory (S(1)), not copied out in HBM.  The donated state
    is still aliased, not double-buffered."""
    from repro.configs import get_arch

    layers, lanes, max_len, held = BENCH_DECODE[arch]
    cfg = dataclasses.replace(get_arch(arch), num_layers=layers)
    if held:
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe,
                                                               held=held))
    compiled, state = _decode_step(cfg, lanes, max_len, one_chip)
    state_bytes = sum(math.prod(leaf.shape) * leaf.dtype.itemsize
                      for leaf in jax.tree.leaves(state))
    assert compiled.memory_analysis().alias_size_in_bytes >= (
        0.99 * state_bytes)

    kv = [c for c in state.group_caches if hasattr(c, "k")]
    assert kv
    stacks = {f"bf16{list(c.k.shape)}".replace(" ", "") for c in kv}
    # a layer, or a pass of it: every shape that ends in (S, kvh, hd)
    tails = {"," + ",".join(map(str, c.k.shape[2:])) + "]" for c in kv}
    faults, parts = [], 0
    for line in _top_level(compiled.as_text()):
        op, results = _results(line)
        name = line.split(" = ", 1)[0]
        for typ, layout in results:
            if not typ.startswith("bf16[") or not typ.endswith(tuple(tails)):
                continue
            if op.startswith("copy") or "dynamic-update-slice" in name + op:
                faults.append(line)
            elif typ not in stacks:
                parts += 1
                if "S(1)" not in layout:
                    faults.append(line)
    assert not faults, faults
    assert parts >= 2 * len(kv), parts   # the K and V read of each kind


def test_qwen3_next_longgen_decode_step_fits_one_chip(one_chip):
    """qwen3-next-80b-a3b as the longgen cell runs it: 8 layers (6 Gated
    DeltaNet, 2 gated attention), 128 of 512 experts held, 128 lanes x
    4096, bf16: the step program fits one chip, and the donated DeltaNet
    state and KV cache are aliased instead of double-buffered."""
    from repro.configs import get_arch

    base = get_arch("qwen3-next-80b-a3b")
    cfg = dataclasses.replace(base, num_layers=8,
                              moe=dataclasses.replace(base.moe, held=128))
    compiled, state = _decode_step(cfg, 128, 4096, one_chip)
    mem = compiled.memory_analysis()
    state_bytes = sum(math.prod(leaf.shape) * leaf.dtype.itemsize
                      for leaf in jax.tree.leaves(state))
    assert mem.alias_size_in_bytes >= 0.99 * state_bytes
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert used < 16e9 and used < V5E_PROGRAM_BYTES, used
