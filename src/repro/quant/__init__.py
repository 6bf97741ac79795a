"""Int8 quantized execution subsystem (DESIGN.md §8).

``qtensor`` — symmetric per-tensor/per-channel int8 params + calibration;
``requant`` — TFLite/CMSIS-NN fixed-point requantization as pure jnp.
The network-level bridge (``quantize_net`` / ``run_net_quantized``) lives
in :mod:`repro.graph.run`; the int8 executor paths in
:mod:`repro.core.executors` and :mod:`repro.kernels.quantized`.
"""
from .qtensor import (QMAX, QMIN, QParams, calibrate, dequantize,
                      device_quantizer, host_array, quantize, quantize_bias,
                      quantize_thresholds, requant_pair, requant_scalar)
from .requant import (INT32_MAX, INT32_MIN, SHIFT_MAX, SHIFT_MIN, act_i32,
                      quantize_multiplier, requantize, requantize_i32)

__all__ = [
    "QMAX", "QMIN", "QParams", "calibrate", "dequantize",
    "device_quantizer", "host_array", "quantize", "quantize_bias",
    "quantize_thresholds", "requant_pair", "requant_scalar",
    "INT32_MAX", "INT32_MIN", "SHIFT_MAX", "SHIFT_MIN", "act_i32",
    "quantize_multiplier", "requantize", "requantize_i32",
]
