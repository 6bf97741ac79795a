"""Gradient-compression collectives + int8 codec."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.parallel.collectives import (bucketed_psum, compressed_psum,
                                        dequantize_int8, quantize_int8)


def test_int8_roundtrip_error_bound():
    x = jax.random.normal(jax.random.PRNGKey(0), (128,)) * 3.0
    q, s = quantize_int8(x)
    err = jnp.max(jnp.abs(dequantize_int8(q, s) - x))
    assert float(err) <= float(s) * 0.5 + 1e-6


def _one_device_mesh():
    return Mesh(np.array(jax.devices()[:1]), ("dp",))


def test_compressed_psum_single_participant_identity():
    mesh = _one_device_mesh()
    x = jax.random.normal(jax.random.PRNGKey(1), (64,))
    f = shard_map(functools.partial(compressed_psum, axis_name="dp"),
                  mesh=mesh, in_specs=P(), out_specs=P())
    y = f(x)
    # single participant: the only error is quantization (<= scale/2)
    scale = float(jnp.max(jnp.abs(x))) / 127.0
    np.testing.assert_allclose(np.asarray(y), np.asarray(x),
                               atol=scale * 0.51 + 1e-7)


def test_bucketed_psum_preserves_tree():
    mesh = _one_device_mesh()
    tree = {"w": jnp.ones((130,)), "b": jnp.arange(7, dtype=jnp.float32)}
    f = shard_map(
        functools.partial(bucketed_psum, axis_name="dp", bucket_bytes=256),
        mesh=mesh, in_specs=P(), out_specs=P())
    out = f(tree)
    np.testing.assert_allclose(np.asarray(out["w"]), np.ones(130), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(out["b"]),
                               np.arange(7, dtype=np.float32), rtol=1e-6)


def test_compression_wire_bytes():
    """int8 payload is 4x smaller than fp32 (8x vs bf16 grads upcast)."""
    x = jnp.ones((1024,), jnp.float32)
    q, _ = quantize_int8(x)
    assert q.dtype == jnp.int8 and q.nbytes * 4 == x.nbytes
