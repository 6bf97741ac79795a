"""The device quantize (``quant.device_quantizer``) against the host
``quantize`` it replaces on the batched ``jnp`` path: bit-identical on
random values, on every rounding edge and its float32 neighbours, and on
the extremes; and the edges themselves (``quantize_thresholds``).
NaN is outside the contract (the host's cast of NaN to int8 is
implementation-defined)."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.quant import (QMAX, QMIN, QParams, calibrate, device_quantizer,
                         quantize, quantize_thresholds)
from repro.quant.qtensor import SCALE_FLOOR

F32 = np.float32


def _calibrated_scale() -> float:
    """The input scale a calibration over normal inputs gives."""
    rng = np.random.default_rng(3)
    return calibrate(rng.standard_normal((2, 49, 10)).astype(F32)).scale


SCALES = [SCALE_FLOOR, _calibrated_scale(), 1.0, 0.1, 1 / 3, 777.7]


def _edges(scale: float) -> np.ndarray:
    """Every ``(k +- 0.5) * scale`` in float32 with two ulps each way."""
    mid = ((np.arange(QMIN - 1, QMAX + 2) - 0.5) * scale).astype(F32)
    out = [mid]
    for to in (F32(np.inf), F32(-np.inf)):
        v = mid
        for _ in range(2):
            v = np.nextafter(v, to)
            out.append(v)
    return np.concatenate(out)


def _inputs(scale: float) -> np.ndarray:
    rng = np.random.default_rng(int(scale * 1e9) % (1 << 31))
    big = F32(np.finfo(F32).max)
    special = np.array([0.0, -0.0, np.inf, -np.inf, 1e30, -1e30, big, -big,
                        1e-40, -1e-40], F32)
    wide = rng.standard_normal(100_000).astype(F32) * F32(160 * scale)
    flat = rng.uniform(-128 * scale, 128 * scale, 100_000).astype(F32)
    return np.concatenate([special, _edges(scale), wide, flat])


@pytest.mark.parametrize("scale", SCALES)
def test_device_quantize_bitwise_equals_host(scale):
    qp = QParams(scale=scale)
    x = _inputs(scale)
    want = np.asarray(quantize(x, qp))
    got = np.asarray(device_quantizer(qp)(jnp.asarray(x)))
    assert got.dtype == np.int8
    np.testing.assert_array_equal(got, want)
    assert {int(want.min()), int(want.max())} == {QMIN, QMAX}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float16])
def test_device_quantize_of_narrow_floats(dtype):
    qp = QParams(scale=_calibrated_scale())
    x = jnp.asarray(_inputs(qp.scale)).astype(dtype)
    want = np.asarray(quantize(np.asarray(x, np.float64), qp))
    np.testing.assert_array_equal(
        np.asarray(device_quantizer(qp)(x)), want)


@pytest.mark.parametrize("scale", SCALES)
def test_thresholds_are_the_host_quantize_edges(scale):
    qp = QParams(scale=scale)
    t = quantize_thresholds(qp)
    assert t.dtype == F32 and t.shape == (QMAX,)
    assert (np.diff(t) > 0).all()
    k = np.arange(1, QMAX + 1)
    below = np.nextafter(t, F32(-np.inf))
    np.testing.assert_array_equal(np.asarray(quantize(t, qp)), k)
    np.testing.assert_array_equal(np.asarray(quantize(below, qp)), k - 1)
    # the host quantize is odd, so the edges fix the negative side too
    np.testing.assert_array_equal(np.asarray(quantize(-t, qp)), -k)
    np.testing.assert_array_equal(np.asarray(quantize(-below, qp)), 1 - k)
    # normal float32 numbers: a flushed subnormal moves no compare
    assert t[0] >= np.finfo(F32).tiny
