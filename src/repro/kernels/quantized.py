"""Int8 ring kernels — the quantized whole-network PoolOps on TPU.

Every kernel runs the shared ring skeleton of its access pattern
(:mod:`repro.kernels.conv2d`): the int8 pool is seen as the wide ring
(:mod:`repro.kernels.ring`), async copies perform the ``addr % n_segments``
circular-buffer bounds check, and input/output aliasing updates the pool in
place.  What changes is the element math — the MCU deployment form:

  * loads are int8 segments (one pool segment is ``SEG_WIDTH`` bytes, so
    the executed ring is byte-comparable to the paper's
    ``mcu_bottleneck_bytes``),
  * the Dot feeds int8 operands to the MXU and accumulates in int32
    (``preferred_element_type=jnp.int32`` — the SMLAD/``VMLADAVA.S8``
    analogue),
  * the store epilogue is the CMSIS-NN fixed-point requantization
    (:func:`repro.quant.requant.requantize_i32`: multiplier+shift,
    round-to-nearest-even, saturating int8) with per-output-channel
    constants streamed from "Flash" like the weights.

Scalar requant pairs (residual add, avgpool) are static kernel
parameters; per-channel pairs ride in the epilogue table next to the bias.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..quant.requant import act_i32, requantize_i32
from . import conv2d
from .ring import epilogue_table


def _sat(v):
    return jnp.clip(v, -128, 127)


@functools.partial(
    jax.jit,
    static_argnames=("m_rows", "d_in", "d_out", "in_ptr", "out_ptr",
                     "block_rows", "activation", "interpret"),
    donate_argnums=(0,))
def ring_gemm_q(pool: jax.Array, w: jax.Array, b: jax.Array,
                mult: jax.Array, shift: jax.Array, *, m_rows: int,
                d_in: int, d_out: int, in_ptr: int, out_ptr: int,
                block_rows: int = 8, activation: str | None = None,
                interpret: bool = False) -> jax.Array:
    """Int8 Fig.-4 FC kernel: int8 In @ int8 W -> int32 acc -> requantize
    per output channel on store."""
    if m_rows % block_rows:
        raise ValueError("block_rows must divide m_rows")
    h = m_rows // block_rows
    return conv2d.conv_pw(pool, w, epilogue_table(b, d_out, mult, shift),
                          h_in=h, w_in=block_rows, h_out=h,
                          w_out=block_rows, c_in=d_in, c_out=d_out,
                          in_ptr=in_ptr, out_ptr=out_ptr,
                          activation=activation, interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("h_in", "w_in", "h_out", "w_out", "c_in", "c_out",
                     "stride", "resample", "in_ptr", "out_ptr",
                     "activation", "row_block", "interpret"),
    donate_argnums=(0,))
def ring_conv_pw_q(pool: jax.Array, w: jax.Array, b: jax.Array,
                   mult: jax.Array, shift: jax.Array, *, h_in: int,
                   w_in: int, h_out: int, w_out: int, c_in: int,
                   c_out: int, stride: int = 1, resample: bool = False,
                   in_ptr: int = 0, out_ptr: int = 0,
                   activation: str | None = None, row_block: int = 1,
                   interpret: bool = False) -> jax.Array:
    """Int8 pointwise conv in the ring, ``row_block`` output image rows
    per step (blocking requires the identity pixel map — see
    :func:`repro.kernels.conv2d.ring_conv_pw`)."""
    return conv2d.conv_pw(pool, w, epilogue_table(b, c_out, mult, shift),
                          h_in=h_in, w_in=w_in, h_out=h_out, w_out=w_out,
                          c_in=c_in, c_out=c_out, stride=stride,
                          resample=resample, in_ptr=in_ptr, out_ptr=out_ptr,
                          activation=activation, row_block=row_block,
                          interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("h_in", "w_in", "h_out", "w_out", "c", "rs", "stride",
                     "padding", "in_ptr", "out_ptr", "activation",
                     "interpret"),
    donate_argnums=(0,))
def ring_conv_dw_q(pool: jax.Array, w: jax.Array, b: jax.Array,
                   mult: jax.Array, shift: jax.Array, *, h_in: int,
                   w_in: int, h_out: int, w_out: int, c: int, rs: int = 3,
                   stride: int = 1, padding: str = "same", in_ptr: int = 0,
                   out_ptr: int = 0, activation: str | None = None,
                   interpret: bool = False) -> jax.Array:
    """Int8 depthwise RSxRS conv inside the ring (int32 VPU taps)."""
    return conv2d.conv_halo(pool, w, epilogue_table(b, c, mult, shift),
                            h_in=h_in, w_in=w_in, h_out=h_out, w_out=w_out,
                            c_in=c, c_out=c, k=rs, stride=stride,
                            padding=padding, in_ptr=in_ptr, out_ptr=out_ptr,
                            activation=activation, depthwise=True,
                            interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("h_in", "w_in", "h_out", "w_out", "c_in", "c_out",
                     "k", "stride", "padding", "in_ptr", "out_ptr",
                     "activation", "interpret"),
    donate_argnums=(0,))
def ring_conv_k2d_q(pool: jax.Array, w: jax.Array, b: jax.Array,
                    mult: jax.Array, shift: jax.Array, *, h_in: int,
                    w_in: int, h_out: int, w_out: int, c_in: int,
                    c_out: int, k: int = 3, stride: int = 1,
                    padding: str = "same", in_ptr: int = 0,
                    out_ptr: int = 0, activation: str | None = None,
                    interpret: bool = False) -> jax.Array:
    """Int8 k x k conv inside the ring: int8 halo rows -> int32 dot per
    tap -> per-output-channel requantize on store (symmetric zero point
    keeps the padding exact)."""
    return conv2d.conv_halo(pool, w, epilogue_table(b, c_out, mult, shift),
                            h_in=h_in, w_in=w_in, h_out=h_out, w_out=w_out,
                            c_in=c_in, c_out=c_out, k=k, stride=stride,
                            padding=padding, in_ptr=in_ptr, out_ptr=out_ptr,
                            activation=activation, depthwise=False,
                            interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("rows", "d", "in_ptr", "aux_ptr", "out_ptr",
                     "mult_in", "shift_in", "mult_aux", "shift_aux",
                     "activation", "interpret"),
    donate_argnums=(0,))
def ring_add_q(pool: jax.Array, *, rows: int, d: int, in_ptr: int,
               aux_ptr: int, out_ptr: int, mult_in: int, shift_in: int,
               mult_aux: int, shift_aux: int,
               activation: str | None = None,
               interpret: bool = False) -> jax.Array:
    """Int8 residual add: both operands requantized to the output scale,
    summed (optional int32-domain relu), saturated — streamed in whole
    pixel-row blocks."""
    def combine(x, r):
        acc = requantize_i32(x, mult_in, shift_in) \
            + requantize_i32(r, mult_aux, shift_aux)
        return _sat(act_i32(acc, activation))   # post-add relu (int32)

    return conv2d.add(pool, combine, rows=rows, d=d, in_ptr=in_ptr,
                      aux_ptr=aux_ptr, out_ptr=out_ptr, interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("h", "w", "c", "in_ptr", "out_ptr", "mult", "shift",
                     "interpret"),
    donate_argnums=(0,))
def ring_avgpool_q(pool: jax.Array, *, h: int, w: int, c: int, in_ptr: int,
                   out_ptr: int, mult: int, shift: int,
                   interpret: bool = False) -> jax.Array:
    """Int8 global average pool: int32 row sums accumulated in VMEM, one
    requantized output row stored at the last step (the 1/(h*w) mean
    normalization is folded into the multiplier)."""
    return conv2d.avgpool(
        pool, lambda s: _sat(requantize_i32(s, mult, shift)), h=h, w=w,
        c=c, in_ptr=in_ptr, out_ptr=out_ptr, interpret=interpret)
