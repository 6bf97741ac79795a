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
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The chip's peaks; a device that is not in the table is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}")
    return table[device_kind]


def macs(layer: dict) -> int:
    kind = layer["kind"]
    out_px = layer["h_out"] * layer["w_out"]
    if kind in ("conv", "fc"):
        return out_px * layer.get("k", 1) ** 2 * layer["c_in"] * \
            layer["c_out"]
    if kind == "dw":
        return out_px * layer["k"] ** 2 * layer["c_in"]
    return 0


def act_bytes(layer: dict) -> int:
    """Dense int8 activation bytes a layer reads and writes, per input."""
    n_in = layer["h"] * layer["w"] * layer["c_in"]
    n_out = layer["h_out"] * layer["w_out"] * layer["c_out"]
    return n_in * (2 if layer["kind"] == "add" else 1) + n_out


def param_bytes(layer: dict) -> int:
    kind = layer["kind"]
    if kind in ("conv", "fc"):
        w = layer.get("k", 1) ** 2 * layer["c_in"] * layer["c_out"]
    elif kind == "dw":
        w = layer["k"] ** 2 * layer["c_in"]
    else:
        return 0
    return w + 12 * layer["c_out"]


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
