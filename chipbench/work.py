"""The work a network's layers need, counted from their shapes.

Operations are ``2 x`` the nominal multiply-accumulates (zero-padding
taps included).  Bytes are the dense int8 tensors a layer must read and
write (input, residual operand, output) plus its parameters: int8
weights and, per output channel, an int32 bias and an int32 requant
multiplier and shift.  Neither depends on how the program lays the
tensors out or which executor runs them.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

from chipbench.reference import kind_of

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The chip's peaks; a device that is not in the table is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}")
    return table[device_kind]


def macs(layer: dict) -> int:
    return kind_of(layer).macs(layer)


def act_bytes(layer: dict) -> int:
    """Dense int8 activation bytes a layer reads and writes, per input."""
    n_out = layer["h_out"] * layer["w_out"] * layer["c_out"]
    return kind_of(layer).reads(layer) + n_out


def param_bytes(layer: dict) -> int:
    shape = kind_of(layer).weight_shape(layer)
    return 0 if shape is None else math.prod(shape) + 12 * layer["c_out"]


def least_time(layers: list[dict], batch: int, peak: dict) -> list[tuple]:
    """``[(name, seconds, bound)]``: the least time each layer of one
    call over ``batch`` inputs can take on the chip, the larger of its
    operations over the int8 peak and its bytes (parameters read once
    per call) over the HBM bandwidth, and which of the two bounds it."""
    out = []
    for layer in layers:
        t_ops = 2 * macs(layer) * batch / peak["int8_ops_per_s"]
        t_mem = (act_bytes(layer) * batch + param_bytes(layer)) \
            / peak["hbm_bytes_per_s"]
        out.append((layer["name"], max(t_ops, t_mem),
                    "compute" if t_ops >= t_mem else "memory"))
    return out


def roofline_share(record: dict, trace: dict | None, ops: tuple[str, ...],
                   kinds: tuple[str, ...] | None = None) -> float | None:
    """Percent of their roofline that the device ops named ``ops*`` (a
    name starting with one of ``ops``) reach in the traced window: the
    least time of the window's work in the layers of ``kinds`` (every
    layer where ``None``; ``record["least_layers"]``) over those ops'
    device seconds (``trace["op_s"]``).  ``None`` where none of them
    ran in the window."""
    if not trace:
        return None
    device_s = sum(s for name, s in trace["op_s"].items()
                   if name.startswith(ops))
    if device_s <= 0:
        return None
    least = sum(t for _, kind, t in record["least_layers"]
                if kinds is None or kind in kinds)
    return 100.0 * record["traced"]["calls"] * least / device_s
