"""Symmetric int8 quantization parameters + calibration.

The scheme is the deployable MCUNet/DORY form:

  * activations — per-tensor symmetric (``zero_point == 0``), scale
    calibrated as ``amax(|x|)/127`` over the float reference forward;
  * weights — per-output-channel symmetric, so each output channel gets
    its own requant ``(multiplier, shift)`` pair;
  * biases — int32 at the accumulator scale ``s_in * s_w[c]``.

Everything here is host-side (numpy) planning; the in-kernel arithmetic
lives in :mod:`repro.quant.requant`.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.spans import span
from .requant import quantize_multiplier

QMIN, QMAX = -127, 127   # symmetric: -128 is never produced by quantize()
SCALE_FLOOR = 1e-8       # all-zero tensors/channels quantize at scale 1e-8


@dataclasses.dataclass(frozen=True)
class QParams:
    """Symmetric quantization parameters of one tensor.

    ``scale`` is a float for per-tensor params or a ``[c]`` numpy array
    for per-channel (``axis`` names the channel axis of the tensor).
    ``zero_point`` is always 0 in this scheme; it is carried so the
    record stays honest about the affine form."""

    scale: object
    axis: int | None = None
    zero_point: int = 0

    @property
    def per_channel(self) -> bool:
        return self.axis is not None

    def _bcast(self, ndim: int) -> np.ndarray:
        s = np.asarray(self.scale, np.float64)
        if self.axis is None:
            return s
        shape = [1] * ndim
        shape[self.axis] = -1
        return s.reshape(shape)


def calibrate(x, axis: int | None = None) -> QParams:
    """Symmetric scale(s) from float data: ``amax(|x|) / 127``.

    ``axis=None`` gives one per-tensor scale; an integer gives one scale
    per slice of that axis (per-channel weights)."""
    x = np.asarray(x, np.float64)
    if axis is None:
        amax = float(np.abs(x).max()) if x.size else 0.0
        return QParams(scale=max(amax / QMAX, SCALE_FLOOR), axis=None)
    reduce_axes = tuple(i for i in range(x.ndim) if i != axis)
    amax = np.abs(x).max(axis=reduce_axes)
    return QParams(scale=np.maximum(amax / QMAX, SCALE_FLOOR), axis=axis)


def host_array(a, dtype) -> np.ndarray:
    """``np.asarray(a, dtype)``.  For a device array the host first waits
    for the device to produce it and for the copy back: that wait is a
    ``vmcu.sync`` span."""
    if not isinstance(a, jax.Array):
        return np.asarray(a, dtype)
    with span("vmcu.sync"):
        return np.asarray(a, dtype)


def _quantize_host(x, qp: QParams) -> np.ndarray:
    x = np.asarray(x, np.float64)
    q = np.rint(x / qp._bcast(x.ndim))
    return np.clip(q, QMIN, QMAX).astype(np.int8)


def quantize(x, qp: QParams):
    """Float -> int8 (round-to-nearest-even, clamped to [-127, 127])."""
    return jnp.asarray(_quantize_host(x, qp))


def quantize_thresholds(qp: QParams) -> np.ndarray:
    """The positive float32 edges of the per-tensor :func:`quantize`.

    ``t[k - 1]`` (k = 1..127) is the smallest float32 that
    :func:`quantize` maps to ``k`` or above.  :func:`quantize` is odd
    (``rint`` rounds half to even and the clamp is symmetric), so these
    edges fix it on both sides of zero.  Each starts at
    ``(k - 0.5) * scale`` and steps one ulp at a time until
    :func:`quantize` itself confirms it."""
    k = np.arange(1, QMAX + 1)
    t = ((k - 0.5) * float(qp.scale)).astype(np.float32)
    up, down = np.float32(np.inf), np.float32(-np.inf)
    while (low := _quantize_host(t, qp) < k).any():
        t = np.where(low, np.nextafter(t, up), t)
    while (high := _quantize_host(np.nextafter(t, down), qp) >= k).any():
        t = np.where(high, np.nextafter(t, down), t)
    return t


@jax.jit
def _quantize_on_device(x, t):
    # |x| quantizes to the largest k whose edge it reaches: the edges
    # rise, so one exact compare and select per edge, in order.  They
    # are normal float32 (>= SCALE_FLOOR / 2), so a flushed subnormal
    # changes no compare.
    a = jnp.abs(x.astype(jnp.float32))
    q = jnp.zeros(x.shape, jnp.int32)
    for k in range(1, QMAX + 1):
        q = jnp.where(a >= t[k - 1], k, q)
    return jnp.where(x < 0, -q, q).astype(jnp.int8)


def device_quantizer(qp: QParams):
    """``x -> quantize(x, qp)`` for a per-tensor ``qp`` and a device
    array ``x`` of a float dtype that widens exactly to float32: one
    jitted device program, bit-identical to the host :func:`quantize`
    (NaN aside), whose result stays on the device."""
    t = jnp.asarray(quantize_thresholds(qp))
    return lambda x: _quantize_on_device(x, t)


def dequantize(q, qp: QParams):
    """Int8 -> float32."""
    q = np.asarray(q, np.float64)
    return jnp.asarray((q * qp._bcast(q.ndim)).astype(np.float32))


def quantize_bias(b, in_scale: float, w_qp: QParams) -> jnp.ndarray:
    """Bias at the int32 accumulator scale ``s_in * s_w[c]``."""
    s = np.asarray(w_qp.scale, np.float64) * float(in_scale)
    bq = np.rint(np.asarray(b, np.float64) / s)
    return jnp.asarray(np.clip(bq, -(1 << 30), 1 << 30).astype(np.int32))


def requant_pair(in_scale: float, w_qp: QParams,
                 out_scale: float) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-channel ``(multiplier[c], shift[c])`` int32 arrays encoding
    ``s_in * s_w[c] / s_out``."""
    sw = np.atleast_1d(np.asarray(w_qp.scale, np.float64))
    mults, shifts = zip(*(quantize_multiplier(float(in_scale) * float(s)
                                              / float(out_scale))
                          for s in sw))
    return (jnp.asarray(np.array(mults, np.int32)),
            jnp.asarray(np.array(shifts, np.int32)))


def requant_scalar(ratio: float) -> tuple[int, int]:
    """Scalar ``(multiplier, shift)`` for a plain scale ratio (residual
    add operands, average-pool normalization)."""
    return quantize_multiplier(float(ratio))
