"""Plain reference of the benchmark's networks, independent of the program.

A network is a list of layers (built by ``chipbench/nets/<family>.py``
from the ``widths`` table of a configuration file).  Tensor 0 is the
input image ``[h, w, c]``; layer ``i`` writes tensor ``i + 1`` and reads
tensor ``src`` (an ``add`` also reads tensor ``aux``).

* :func:`float_forward` is the float64 forward pass on the host: what
  the int8 deployment approximates, and what decides ``correct``.
* :func:`calibrate` derives symmetric ``bits``-wide tables from float
  weights and calibration inputs (per-tensor activations, per-channel
  weights, Q31 requant pairs), the scheme an int8 MCU runtime uses.
* :func:`int_forward` runs those tables in exact integer arithmetic
  (numpy int64): integer accumulate, ``relu`` on the accumulator, one
  round-to-nearest-even requantization.  With ``bits=4`` it is the
  lower-precision control.

What each layer kind computes lives in one table, :data:`KINDS`; a
family file (``chipbench/nets/<family>.py``) may bring entries of its
own in a ``KINDS`` dict of :class:`Kind` (``common.net_layers`` adds
them), so a new architecture is new files only.

Nothing here imports the program: no ring, no executor, no Pallas.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Callable

import numpy as np

SCALE_FLOOR = 1e-8


# ---------------------------------------------------------------------------
# Layer lists.
# ---------------------------------------------------------------------------

def out_extent(n: int, k: int, stride: int, pad: str) -> int:
    if pad == "same":
        return -(-n // stride)
    return (n - k) // stride + 1


class Builder:
    """Appends layers in execution order and tracks the current tensor."""

    def __init__(self, h: int, w: int, c: int):
        self.layers: list[dict] = []
        self.shapes = [(h, w, c)]          # tensor index -> (h, w, c)

    @property
    def cur(self) -> int:
        return len(self.shapes) - 1

    def push(self, layer: dict, shape) -> int:
        """Append ``layer``, which writes a tensor of ``shape``; returns
        that tensor's index."""
        self.layers.append(layer)
        self.shapes.append(shape)
        return self.cur

    def conv(self, name, c_out, *, k=1, stride=1, pad="same", relu=True,
             src=None, resample=None) -> int:
        """``k x k`` conv (``k == 1``: pointwise).  ``resample=(h, w)``
        is a nearest-grid 1x1 adapter to that grid."""
        src = self.cur if src is None else src
        h, w, c = self.shapes[src]
        if resample is not None:
            ho, wo = resample
        else:
            ho, wo = out_extent(h, k, stride, pad), out_extent(w, k, stride,
                                                               pad)
        return self.push(dict(name=name, kind="conv", src=src, h=h, w=w,
                              c_in=c, c_out=c_out, k=k, stride=stride,
                              pad=pad, relu=relu,
                              resample=resample is not None,
                              h_out=ho, w_out=wo), (ho, wo, c_out))

    def dw(self, name, *, k=3, stride=1, relu=True) -> int:
        h, w, c = self.shapes[-1]
        ho, wo = out_extent(h, k, stride, "same"), out_extent(w, k, stride,
                                                              "same")
        return self.push(dict(name=name, kind="dw", src=self.cur, h=h, w=w,
                              c_in=c, c_out=c, k=k, stride=stride,
                              pad="same", relu=relu, h_out=ho, w_out=wo),
                         (ho, wo, c))

    def add(self, name, aux: int, *, relu=False) -> int:
        h, w, c = self.shapes[-1]
        if self.shapes[aux] != (h, w, c):
            raise ValueError(f"{name}: add of {self.shapes[aux]} and "
                             f"{(h, w, c)}")
        return self.push(dict(name=name, kind="add", src=self.cur, aux=aux,
                              h=h, w=w, c_in=c, c_out=c, relu=relu,
                              h_out=h, w_out=w), (h, w, c))

    def head(self, num_classes: int) -> None:
        h, w, c = self.shapes[-1]
        self.push(dict(name="head.pool", kind="avgpool", src=self.cur, h=h,
                       w=w, c_in=c, c_out=c, h_out=1, w_out=1), (1, 1, c))
        self.push(dict(name="head.fc", kind="fc", src=self.cur, h=1, w=1,
                       c_in=c, c_out=num_classes, relu=False, h_out=1,
                       w_out=1), (1, 1, num_classes))


# ---------------------------------------------------------------------------
# Shared layer arithmetic (float64 or int64, same code).
# ---------------------------------------------------------------------------

def _taps(x, layer):
    """Yield ``(r, c, window)`` for every tap of a k x k layer: the
    strided ``[h_out, w_out, c]`` slice of the zero-padded input."""
    k, s = layer["k"], layer["stride"]
    ho, wo = layer["h_out"], layer["w_out"]
    lo = (k - 1) // 2 if layer["pad"] == "same" else 0
    h, w, _ = x.shape
    hi_h = max(0, (ho - 1) * s + k - lo - h)
    hi_w = max(0, (wo - 1) * s + k - lo - w)
    xp = np.pad(x, ((lo, hi_h), (lo, hi_w), (0, 0)))
    for r in range(k):
        for c in range(k):
            yield r, c, xp[r:r + s * (ho - 1) + 1:s, c:c + s * (wo - 1) + 1:s]


def _matmul(a, b):
    """Exact for integer operands: int8 x int8 products summed in
    float64 stay far below 2**53."""
    if a.dtype.kind == "f":
        return a @ b
    return np.rint(a.astype(np.float64) @ b.astype(np.float64)).astype(
        np.int64)


def _acc_fc(x, w, layer):
    return _matmul(x.reshape(1, -1), w).reshape(1, 1, -1)


def _acc_conv(x, w, layer):
    if layer["k"] == 1:
        if layer["resample"]:
            h, w_in, _ = x.shape
            ri = (np.arange(layer["h_out"]) * h) // layer["h_out"]
            ci = (np.arange(layer["w_out"]) * w_in) // layer["w_out"]
            sub = x[ri][:, ci]
        else:
            s = layer["stride"]
            sub = x[::s, ::s][:layer["h_out"], :layer["w_out"]]
        ho, wo, c = sub.shape
        return _matmul(sub.reshape(ho * wo, c), w).reshape(ho, wo, -1)
    acc = 0
    for r, c, win in _taps(x, layer):
        ho, wo, ci = win.shape
        acc = acc + _matmul(win.reshape(ho * wo, ci), w[r, c]).reshape(
            ho, wo, -1)
    return acc


def _acc_dw(x, w, layer):
    acc = 0
    for r, c, win in _taps(x, layer):
        acc = acc + win * w[r, c]
    return acc


# ---------------------------------------------------------------------------
# Requantization.
# ---------------------------------------------------------------------------

def quantize_multiplier(real: float) -> tuple[int, int]:
    """``real ~= m * 2**(shift - 31)`` with ``2**30 <= m < 2**31``."""
    if real == 0.0:
        return 0, 0
    frac, exp = math.frexp(real)
    m = round(frac * (1 << 31))
    if m == 1 << 31:
        m >>= 1
        exp += 1
    if not -31 <= exp <= 30:
        raise ValueError(f"scale ratio {real} out of requant range")
    return m, exp


def requant_pairs(ratios) -> tuple[np.ndarray, np.ndarray]:
    """Q31 ``(multipliers, shifts)`` of each scale ratio."""
    m, s = zip(*(quantize_multiplier(float(r)) for r in np.atleast_1d(
        ratios)))
    return np.array(m, np.int64), np.array(s, np.int64)


def requantize_i32(acc, m, shift):
    """``RNE(acc * m * 2**(shift - 31))`` saturated to int32, then to
    ``[-2**24, 2**24]``; exact in int64 (``|acc * m| < 2**62``)."""
    acc = np.asarray(acc, np.int64)
    prod = acc * np.asarray(m, np.int64)
    s = 31 - np.asarray(shift, np.int64)
    q = prod >> s
    rem = prod - (q << s)
    half = np.int64(1) << (s - 1)
    q = q + ((rem > half) | ((rem == half) & ((q & 1) == 1)))
    q = np.clip(q, -(1 << 31), (1 << 31) - 1)
    return np.clip(q, -(1 << 24), 1 << 24)


# ---------------------------------------------------------------------------
# Layer kinds.
# ---------------------------------------------------------------------------

def _fan_in(layer, shape) -> int:
    return math.prod(shape[:-1])


@dataclass(frozen=True)
class Kind:
    """What the benchmark knows of one layer kind.

    * ``program``: the program's op kinds that may run a layer of it.
    * ``forward(layer, ts, wb)``: its float64 output before its ``relu``,
      from the tensors ``ts`` so far; ``wb`` is its ``(w, b)`` (``None``
      for a kind without weights).
    * ``tables(layer, wb, scales, out, qmax)``: its integer tables, from
      every tensor's activation scale (``out`` indexes its own output)
      and the largest code ``qmax``.
    * ``int_forward(layer, ts, table)``: its int64 output from the
      integer tensors ``ts``, ``relu`` included, before saturation.
    * ``weight_shape(layer)``: its float weights' shape, ``None`` for
      none; ``fan_in(layer, shape)``: the inputs each output sums over
      (what scales the seeded weights).
    * ``macs(layer)``: nominal multiply-accumulates per input;
      ``reads(layer)``: dense activation elements read per input.
    """

    program: tuple[str, ...]
    forward: Callable
    tables: Callable
    int_forward: Callable
    weight_shape: Callable = lambda layer: None
    fan_in: Callable = _fan_in
    macs: Callable = lambda layer: 0
    reads: Callable = lambda layer: layer["h"] * layer["w"] * layer["c_in"]


def linear_kind(acc, program, weight_shape, macs, fan_in=_fan_in) -> Kind:
    """A kind that accumulates ``acc(x, w, layer)`` over its input and
    weights, adds a per-channel bias and applies ``relu``; int tables
    with per-output-channel weight scales and Q31 requant pairs."""

    def forward(layer, ts, wb):
        w, b = wb
        return acc(ts[layer["src"]], np.asarray(w, np.float64), layer) + \
            np.asarray(b, np.float64)

    def tables(layer, wb, scales, out, qmax):
        s_in, s_out = scales[layer["src"]], scales[out]
        w, b = (np.asarray(a, np.float64) for a in wb)
        s_w = np.maximum(np.abs(w.reshape(-1, w.shape[-1])).max(0) / qmax,
                         SCALE_FLOOR)
        w_q = np.clip(np.rint(w / s_w), -qmax, qmax).astype(np.int64)
        b_q = np.clip(np.rint(b / (s_in * s_w)), -(1 << 30), 1 << 30
                      ).astype(np.int64)
        return (w_q, b_q) + requant_pairs(s_in * s_w / s_out)

    def int_forward(layer, ts, table):
        w_q, b_q, m, s = table
        a = acc(ts[layer["src"]], w_q, layer) + b_q
        if layer.get("relu"):
            a = np.maximum(a, 0)
        return requantize_i32(a, m, s)

    return Kind(program, forward, tables, int_forward,
                weight_shape=weight_shape, fan_in=fan_in, macs=macs)


def _out_px(layer) -> int:
    return layer["h_out"] * layer["w_out"]


def _add_tables(layer, wb, scales, out, qmax):
    return requant_pairs(scales[layer["src"]] / scales[out]) + \
        requant_pairs(scales[layer["aux"]] / scales[out])


def _add_int(layer, ts, table):
    m_i, s_i, m_a, s_a = table
    y = requantize_i32(ts[layer["src"]], m_i, s_i) + requantize_i32(
        ts[layer["aux"]], m_a, s_a)
    return np.maximum(y, 0) if layer.get("relu") else y


KINDS: dict[str, Kind] = {
    "conv": linear_kind(
        _acc_conv, ("conv_pw", "conv_k2d", "conv_stream"),
        weight_shape=lambda lr: (lr["c_in"], lr["c_out"]) if lr["k"] == 1
        else (lr["k"], lr["k"], lr["c_in"], lr["c_out"]),
        macs=lambda lr: _out_px(lr) * lr["k"] ** 2 * lr["c_in"] * lr["c_out"]),
    "dw": linear_kind(
        _acc_dw, ("conv_dw",),
        weight_shape=lambda lr: (lr["k"], lr["k"], lr["c_in"]),
        macs=lambda lr: _out_px(lr) * lr["k"] ** 2 * lr["c_in"],
        fan_in=lambda lr, shape: shape[0] * shape[1]),
    "fc": linear_kind(
        _acc_fc, ("gemm",),
        weight_shape=lambda lr: (lr["c_in"], lr["c_out"]),
        macs=lambda lr: _out_px(lr) * lr["c_in"] * lr["c_out"]),
    "add": Kind(
        ("add",),
        forward=lambda lr, ts, wb: ts[lr["src"]] + ts[lr["aux"]],
        tables=_add_tables, int_forward=_add_int,
        reads=lambda lr: 2 * lr["h"] * lr["w"] * lr["c_in"]),
    "avgpool": Kind(
        ("pool_avg",),
        forward=lambda lr, ts, wb: ts[lr["src"]].mean(axis=(0, 1),
                                                       keepdims=True),
        tables=lambda lr, wb, scales, out, qmax: requant_pairs(
            scales[lr["src"]] / (lr["h"] * lr["w"] * scales[out])),
        int_forward=lambda lr, ts, table: requantize_i32(
            ts[lr["src"]].sum(axis=(0, 1), keepdims=True), *table)),
}


def kind_of(layer: dict) -> Kind:
    try:
        return KINDS[layer["kind"]]
    except KeyError:
        raise KeyError(f"layer {layer['name']!r} has kind {layer['kind']!r},"
                       f" which is not in the table (a family file brings "
                       f"its own kinds in KINDS)") from None


def add_kinds(kinds: dict) -> None:
    """Add a family's kinds to the table; a name the table holds for
    another kind is an error."""
    for name, kind in kinds.items():
        if KINDS.setdefault(name, kind) is not kind:
            raise ValueError(f"layer kind {name!r} is already in the table")


# ---------------------------------------------------------------------------
# Float forward.
# ---------------------------------------------------------------------------

def float_forward(layers, weights, x, *, taps: list | None = None):
    """float64 forward of one input ``x`` (``[h, w, c]``); returns the
    flat output.  ``weights[i]`` is ``(w, b)`` or ``None``.  ``taps``
    collects every tensor (input first)."""
    ts = [np.asarray(x, np.float64)]
    for layer, wb in zip(layers, weights):
        y = kind_of(layer).forward(layer, ts, wb)
        if layer.get("relu"):
            y = np.maximum(y, 0.0)
        ts.append(y)
    if taps is not None:
        taps.extend(ts)
    return ts[-1].reshape(-1)


# ---------------------------------------------------------------------------
# Calibration and integer forward.
# ---------------------------------------------------------------------------

def calibrate(layers, weights, calib, *, bits: int = 8) -> dict:
    """Symmetric ``bits``-wide tables: per-tensor activation scales from
    the amax over the float forward of ``calib`` (``[n, h, w, c]``),
    then each layer's tables (for the built-in kinds: per-output-channel
    weight scales, int32 biases at the accumulator scale, and Q31
    requant pairs)."""
    qmax = (1 << (bits - 1)) - 1
    amax = np.zeros(len(layers) + 1)
    for x in calib:
        taps: list = []
        float_forward(layers, weights, x, taps=taps)
        amax = np.maximum(amax, [np.abs(t).max() for t in taps])
    scales = np.maximum(amax / qmax, SCALE_FLOOR)
    tables = [kind_of(layer).tables(layer, wb, scales, i + 1, qmax)
              for i, (layer, wb) in enumerate(zip(layers, weights))]
    return {"bits": bits, "scales": scales, "tables": tables}


def int_forward(layers, q: dict, x):
    """Integer forward of one float input ``x``: quantize on entry,
    integer layers, dequantize the output (float64, flat)."""
    bits, scales, tables = q["bits"], q["scales"], q["tables"]
    qmax = (1 << (bits - 1)) - 1
    lo, hi = -qmax - 1, qmax
    x = np.asarray(x, np.float64)
    ts = [np.clip(np.rint(x / scales[0]), -qmax, qmax).astype(np.int64)]
    for layer, t in zip(layers, tables):
        ts.append(np.clip(kind_of(layer).int_forward(layer, ts, t), lo, hi))
    return (ts[-1] * scales[-1]).reshape(-1)


def max_rel_err(ys, refs) -> float:
    """The largest ``||y - ref|| / ||ref||`` over the sampled outputs."""
    return max(float(np.linalg.norm(np.asarray(y, np.float64).reshape(-1)
                                    - r) / max(np.linalg.norm(r), 1e-30))
               for y, r in zip(ys, (np.asarray(r, np.float64).reshape(-1)
                                    for r in refs)))
