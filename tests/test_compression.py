"""Gradient compression: quantization fidelity, error feedback
convergence, shard_map psum semantics."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.optim.compression import (
    EFState,
    compressed_psum,
    dequantize_int8,
    ef_compress_decompress,
    ef_init,
    quantize_int8,
    wire_bytes_saved,
)


def test_quantize_roundtrip_error_bounded():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 1, (333, 77)).astype(np.float32))
    q, s = quantize_int8(x)
    deq = dequantize_int8(q, s, x.shape)
    err = jnp.max(jnp.abs(deq - x))
    # per-block max-abs scaling bounds error by scale/2 ~ amax/254
    assert float(err) <= float(jnp.max(jnp.abs(x))) / 127.0 + 1e-6


def test_error_feedback_preserves_sum():
    """EF residual carries the lost mass: sum over steps of decompressed
    grads converges to the sum of true grads."""
    rng = np.random.default_rng(1)
    grads = {"w": jnp.asarray(rng.normal(0, 1e-3, 4096).astype(np.float32))}
    ef = ef_init(grads)
    total_true = jnp.zeros(4096)
    total_deq = jnp.zeros(4096)
    for i in range(20):
        g = {"w": grads["w"] * (1 + 0.1 * i)}
        deq, ef = ef_compress_decompress(g, ef)
        total_true += g["w"]
        total_deq += deq["w"]
    resid = float(jnp.max(jnp.abs(total_true - (total_deq + ef.residual["w"]))))
    assert resid < 1e-4


def test_compressed_psum_matches_exact():
    n_dev = len(jax.devices())
    from jax.sharding import AxisType, PartitionSpec as P

    mesh = jax.make_mesh((n_dev,), ("pod",), axis_types=(AxisType.Auto,))
    x = jnp.asarray(np.random.default_rng(2).normal(
        0, 1, (n_dev, 512)).astype(np.float32))

    @jax.jit
    def run(x):
        return jax.shard_map(
            lambda v: compressed_psum(v[0], "pod"),
            mesh=mesh, in_specs=P("pod"), out_specs=P(),
        )(x)

    out = run(x)  # replicated sum, shape (512,)
    exact = jnp.sum(x, axis=0)
    rel = float(jnp.max(jnp.abs(out - exact))
                / (jnp.max(jnp.abs(exact)) + 1e-9))
    assert rel < 2e-2


def test_wire_accounting():
    grads = {"a": jnp.zeros((1024, 1024)), "b": jnp.zeros((777,))}
    acc = wire_bytes_saved(grads)
    assert acc["int8_bytes"] < 0.3 * acc["f32_bytes"]
    assert acc["elements"] == 1024 * 1024 + 777
