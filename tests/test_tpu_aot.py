"""Ahead-of-time compiles of the ring kernels for a described TPU v5e.

The Mosaic compiler is installed even where no chip is attached: each
kernel of the int8 deployment path is lowered with ``interpret=False``
and compiled for one chip of a described ``v5e:2x2`` at zoo widths
(``mcunet-320kb-imagenet``'s ring and layers, the streaming ``ds-cnn``
stem).  Nothing runs, so this pins what the chip's compiler accepts —
memory spaces, MXU operand dtypes, tile-aligned slices, scoped VMEM —
not results.  The topology is described inside a fixture, never while
the module is imported, so every xdist worker collects the same tests.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import conv2d, quantized, stream

I8, I32, F32 = jnp.int8, jnp.int32, jnp.float32
N_320KB = 31680          # mcunet-320kb-imagenet's executed ring, segments
N_DSCNN = 640            # streaming ds-cnn's ring


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's compiles cannot be read back from the cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _q(cin, cout, wshape=None, n=N_320KB):
    """int8 pool + (w, b, mult, shift) shapes of a quantized conv."""
    return [((n, 128), I8), (wshape or (cin, cout), I8), ((cout,), I32),
            ((cout,), I32), ((cout,), I32)]


REQ = dict(mult_in=1 << 30, shift_in=-1, mult_aux=1 << 30, shift_aux=-1)

CASES = {
    "gemm_q": (quantized.ring_gemm_q, _q(96, 1000), dict(
        m_rows=1, d_in=96, d_out=1000, in_ptr=594, out_ptr=584,
        block_rows=1)),
    "conv_pw_q-s1-row_block4": (quantized.ring_conv_pw_q, _q(16, 80), dict(
        h_in=44, w_in=44, h_out=44, w_out=44, c_in=16, c_out=80,
        in_ptr=7788, out_ptr=5852, row_block=4, activation="relu")),
    "conv_pw_q-s2": (quantized.ring_conv_pw_q, _q(3, 16), dict(
        h_in=176, w_in=176, h_out=88, w_out=88, c_in=3, c_out=16, stride=2,
        in_ptr=7920, out_ptr=7920, activation="relu")),
    "conv_dw_q-3x3-s1": (quantized.ring_conv_dw_q, _q(80, 80, (3, 3, 80)),
                         dict(h_in=44, w_in=44, h_out=44, w_out=44, c=80,
                              rs=3, in_ptr=5852, out_ptr=5808,
                              activation="relu")),
    "conv_dw_q-3x3-s2": (quantized.ring_conv_dw_q,
                         _q(288, 288, (3, 3, 288)),
                         dict(h_in=11, w_in=11, h_out=6, w_out=6, c=288,
                              rs=3, stride=2, in_ptr=957, out_ptr=954,
                              activation="relu")),
    "conv_k2d_q-3x3-s2": (quantized.ring_conv_k2d_q,
                          _q(3, 16, (3, 3, 3, 16)),
                          dict(h_in=176, w_in=176, h_out=88, w_out=88,
                               c_in=3, c_out=16, k=3, stride=2,
                               in_ptr=7920, out_ptr=0, activation="relu")),
    "add_q": (quantized.ring_add_q, [((N_320KB, 128), I8)], dict(
        rows=121, d=48, in_ptr=1188, aux_ptr=1452, out_ptr=1188, **REQ)),
    "avgpool_q": (quantized.ring_avgpool_q, [((N_320KB, 128), I8)], dict(
        h=6, w=6, c=96, in_ptr=594, out_ptr=594, mult=1 << 30, shift=-5)),
    "conv_stream_q": (stream.ring_conv_stream_q,
                      _q(1, 64, (5, 5, 1, 64), n=N_DSCNN),
                      dict(h_win=49, w_in=10, h_out=25, w_out=5, c_in=1,
                           c_out=64, k=5, stride=2, hop=1, in_ptr=130,
                           out_ptr=20, state_ptr=150, activation="relu")),
    "gru_cell_q": (stream.ring_gru_cell_q,
                   [((N_DSCNN, 128), I8), ((64, 192), I8), ((64, 192), I8)]
                   + [((192,), I32)] * 5,
                   dict(d_in=64, d_h=64, in_ptr=0, out_ptr=4,
                        state_ptr=600)),
    "conv_dw-f32-3x3-s2": (conv2d.ring_conv_dw,
                           [((N_320KB, 128), F32), ((3, 3, 24), F32),
                            ((24,), F32)],
                           dict(h_in=88, w_in=88, h_out=44, w_out=44, c=24,
                                rs=3, stride=2, in_ptr=7832, out_ptr=7788,
                                activation="relu")),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    kernel, shapes, static = CASES[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = kernel.lower(*args, **static, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
