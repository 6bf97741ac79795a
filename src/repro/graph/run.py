"""Executor bridge: run a planned network end-to-end on any backend.

``run_net`` stages the input image into the ring, executes the NetPlan's
merged :class:`PoolProgram` on ``sim``/``jnp``/``pallas`` and fetches the
output; ``certify_net`` drives the sim oracle (raises
:class:`PoolClobberError` iff any cross-layer offset is unsafe);
``reference_forward`` computes the same network as a plain-XLA forward
pass with no pool mechanics — the float-tolerance ground truth for the
ring backends.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..core.executors import execute, run_program
from ..core.program import PoolProgram, resolve_activation
from ..obs.spans import span
from .netplan import NetPlan


def _prog(plan) -> PoolProgram:
    return plan.program if isinstance(plan, NetPlan) else plan


def init_net_params(plan, key=None, dtype=jnp.float32) -> list:
    """Random, magnitude-controlled parameters for every op of the plan
    (weights scaled ~1/sqrt(fan_in) so deep nets stay in float range)."""
    program = _prog(plan)
    if key is None:
        key = jax.random.PRNGKey(0)
    gain = 2.0 ** 0.5  # He init: ReLU halves the variance
    params = []
    for op in program.ops:
        if op.kind in ("gemm", "conv_pw"):
            key, k1 = jax.random.split(key)
            w = jax.random.normal(k1, (op.d_in, op.d_out), dtype)
            params.append((w * gain / (op.d_in ** 0.5), None))
        elif op.kind == "conv_dw":
            key, k1 = jax.random.split(key)
            w = jax.random.normal(k1, (op.rs, op.rs, op.d_in), dtype)
            params.append((w / op.rs, None))
        elif op.kind in ("conv_k2d", "conv_stream"):
            key, k1 = jax.random.split(key)
            w = jax.random.normal(k1, (op.rs, op.rs, op.d_in, op.d_out),
                                  dtype)
            params.append((w * gain / ((op.rs * op.rs * op.d_in) ** 0.5),
                           None))
        elif op.kind == "gru_cell":
            key, k1, k2 = jax.random.split(key, 3)
            w = jax.random.normal(k1, (op.d_in, 3 * op.d_out), dtype) \
                / (op.d_in ** 0.5)
            u = jax.random.normal(k2, (op.d_out, 3 * op.d_out), dtype) \
                / (op.d_out ** 0.5)
            params.append((w, u, None))
        elif op.kind == "ib_fused":
            key, k1, k2, k3 = jax.random.split(key, 4)
            w1 = jax.random.normal(k1, (op.d_in, op.d_mid), dtype) \
                / (op.d_in ** 0.5)
            wd = jax.random.normal(k2, (op.rs, op.rs, op.d_mid), dtype) \
                / op.rs
            w2 = jax.random.normal(k3, (op.d_mid, op.d_out), dtype) \
                / (op.d_mid ** 0.5)
            params.append((w1, wd, w2))
        elif op.kind == "fused_mlp":
            key, k1, k2, k3 = jax.random.split(key, 4)
            wg = jax.random.normal(k1, (op.d_in, op.d_ff), dtype) \
                / (op.d_in ** 0.5)
            wu = jax.random.normal(k2, (op.d_in, op.d_ff), dtype) \
                / (op.d_in ** 0.5)
            wd = jax.random.normal(k3, (op.d_ff, op.d_in), dtype) \
                / op.d_ff
            params.append((wg, wu, wd))
        else:
            params.append(None)
    return params


def _conv_ref(img, w, *, stride: int, pad_lo: int, h_out: int, w_out: int,
              groups: int = 1) -> jax.Array:
    """Independent conv oracle via ``lax.conv_general_dilated`` (NOT the
    executors' tap/gather formulation, so a shared indexing bug cannot
    cancel out).  High padding is chosen so the output is exactly
    ``ceil(h/stride)`` — the planner's 'same' convention."""
    h_in, w_in, _ = img.shape
    rs = w.shape[0]
    ph = (h_out - 1) * stride + rs - pad_lo - h_in
    pw = (w_out - 1) * stride + rs - pad_lo - w_in
    out = jax.lax.conv_general_dilated(
        img[None], w.astype(jnp.float32),
        window_strides=(stride, stride),
        padding=((pad_lo, ph), (pad_lo, pw)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups)
    return out[0]


def reference_forward(plan, x: jax.Array, params, *,
                      intermediates: list | None = None) -> jax.Array:
    """Plain-XLA forward pass of the planned network (no pool).

    ``x`` is ``[rows, d]`` — the flattened input image.  Residual ``add``
    ops read the saved input of their source op, exactly as the ring
    executors read the held interval.

    ``intermediates`` (if a list) collects the float input tensor of
    every op followed by the network output — the taps int8 calibration
    (:func:`quantize_net`) derives its activation scales from.
    """
    from ..core.rowsched import conv_k2d_pad, resample_src

    program = _prog(plan)
    saved: dict[int, jax.Array] = {}
    cur = x.astype(jnp.float32)
    for i, (op, p) in enumerate(zip(program.ops, params)):
        saved[i] = cur
        if intermediates is not None:
            intermediates.append(cur)
        # branch convs (ResNet shortcut projections) read the held input
        # of op ``in_op``, not the chained tensor
        src = saved[op.in_op] if op.in_op >= 0 else cur
        act = resolve_activation(op.activation)
        if op.kind in ("gemm", "conv_pw"):
            w, b = p if p[1] is not None else (p[0], jnp.zeros(op.d_out))
            wf = w.astype(jnp.float32)
            if op.kind == "conv_pw" and op.resample:
                # the nearest-grid adapter is gather-by-definition
                img = src.reshape(op.h_in, op.w_in, op.d_in)
                ridx = [resample_src(r, op.h_in, op.h_out)
                        for r in range(op.h_out)]
                cidx = [resample_src(c, op.w_in, op.w_out)
                        for c in range(op.w_out)]
                sub = img[jnp.array(ridx)][:, jnp.array(cidx)]
                y = jnp.einsum("hwc,cd->hwd", sub, wf)
                cur = act(y + b).reshape(op.rows_out, op.d_out)
            elif op.kind == "conv_pw":
                img = src.reshape(op.h_in, op.w_in, op.d_in)
                y = _conv_ref(img, wf.reshape(1, 1, op.d_in, op.d_out),
                              stride=op.stride, pad_lo=0,
                              h_out=op.h_out, w_out=op.w_out)
                cur = act(y + b).reshape(op.rows_out, op.d_out)
            else:
                cur = act(src @ wf + b)
        elif op.kind == "conv_dw":
            w, b = p if p[1] is not None else (p[0], jnp.zeros(op.d_out))
            img = src.reshape(op.h_in, op.w_in, op.d_in)
            y = _conv_ref(img,
                          w.astype(jnp.float32).reshape(op.rs, op.rs, 1,
                                                        op.d_in),
                          stride=op.stride, pad_lo=(op.rs - 1) // 2,
                          h_out=op.h_out, w_out=op.w_out,
                          groups=op.d_in)
            cur = act(y + b).reshape(op.rows_out, op.d_out)
        elif op.kind == "conv_k2d":
            w, b = p if p[1] is not None else (p[0], jnp.zeros(op.d_out))
            img = src.reshape(op.h_in, op.w_in, op.d_in)
            y = _conv_ref(img, w.astype(jnp.float32),
                          stride=op.stride,
                          pad_lo=conv_k2d_pad(op.rs, op.padding),
                          h_out=op.h_out, w_out=op.w_out)
            cur = act(y + b).reshape(op.rows_out, op.d_out)
        elif op.kind == "conv_stream":
            # one streaming step from reset: the window is the zero
            # state (== zero padding, exactly what VirtualPool.alloc
            # leaves in the state region) with the frame appended
            w, b = p if p[1] is not None else (p[0], jnp.zeros(op.d_out))
            frame = src.reshape(op.hop, op.w_in, op.d_in)
            state = jnp.zeros((op.h_in - op.hop, op.w_in, op.d_in),
                              jnp.float32)
            win = jnp.concatenate([state, frame], axis=0)
            y = _conv_ref(win, w.astype(jnp.float32),
                          stride=op.stride,
                          pad_lo=conv_k2d_pad(op.rs, op.padding),
                          h_out=op.h_out, w_out=op.w_out)
            cur = act(y + b).reshape(op.rows_out, op.d_out)
        elif op.kind == "gru_cell":
            from ..quant.requant import gru_update
            w, u, b = p if p[2] is not None else \
                (p[0], p[1], jnp.zeros(3 * op.d_out))
            h = jnp.zeros((1, op.d_out), jnp.float32)
            gx = src @ w.astype(jnp.float32) + b.astype(jnp.float32)
            gh = h @ u.astype(jnp.float32)
            cur = gru_update(gx, gh, h, op.d_out)
        elif op.kind == "ib_fused":
            from ..kernels.inverted_bottleneck import \
                inverted_bottleneck_ref
            w1, wd, w2 = p
            a = src.reshape(op.h_in, op.w_in, op.d_in)
            cur = inverted_bottleneck_ref(a, w1, wd, w2,
                                          residual=op.residual) \
                .astype(jnp.float32).reshape(op.rows_out, op.d_out)
        elif op.kind == "add":
            cur = act(cur + saved[op.aux_op])
        elif op.kind == "pool_avg":
            img = cur.reshape(op.h_in, op.w_in, op.d_in)
            cur = jnp.mean(img, axis=(0, 1))[None, :]
        elif op.kind == "fused_mlp":
            from ..kernels.ref import fused_mlp_ref
            wg, wu, wd = p
            cur = fused_mlp_ref(cur, wg, wu, wd, gated=op.gated,
                                residual=op.residual,
                                activation=op.activation) \
                .astype(jnp.float32)
        elif op.kind == "elementwise":
            cur = act(cur)
        else:
            raise NotImplementedError(op.kind)
    if intermediates is not None:
        intermediates.append(cur)
    return cur


def run_net(plan, x: jax.Array, params, *, backend: str = "jnp",
            **kwargs) -> jax.Array:
    """Stage ``x`` at the plan's input pointer, execute every group
    through the one ring, fetch the network output."""
    program = _prog(plan)
    y, _pool = run_program(program, x, params, backend=backend, **kwargs)
    return y


def certify_net(plan):
    """Run the whole NetProgram through the SegmentPool clobber oracle.

    Returns the oracle (peak_live, reads/writes stats); raises
    :class:`repro.core.pool.PoolClobberError` iff any op's write lands on
    a segment some later op still needs — i.e. the cross-layer chaining
    is provably safe when this returns.
    """
    return execute(_prog(plan), backend="sim")


# ---------------------------------------------------------------------------
# Int8 quantized execution (DESIGN.md §8).
# ---------------------------------------------------------------------------

_Q_KINDS = ("gemm", "conv_pw", "conv_dw", "conv_k2d", "add", "pool_avg",
            "conv_stream", "gru_cell")
_Q_ACTIVATIONS = (None, "identity", "relu")


@dataclasses.dataclass
class QuantizedNet:
    """A calibrated int8 deployment of one planned network.

    ``program`` is the SAME solved plan re-typed int8
    (``with_dtype("int8")`` — segment geometry, and therefore the sim
    certificate, is shared with the float plan); ``qparams`` are the
    per-op executor entries (int8 weights, int32 biases, requant
    multiplier/shift constants); ``act_scales[i]`` is the symmetric
    scale of tensor ``i`` (0 = network input, ``i`` = output of op
    ``i-1``)."""

    plan: object                       # the float NetPlan / PoolProgram
    program: "PoolProgram"             # int8-typed program
    params: list                       # float params (reference forward)
    qparams: list                      # int8 executor entries
    act_scales: tuple[float, ...]

    @property
    def in_scale(self) -> float:
        return self.act_scales[0]

    @property
    def out_scale(self) -> float:
        return self.act_scales[-1]

    @property
    def pool_bytes(self) -> int:
        """The executed int8 ring footprint — byte-comparable to the
        byte-granular ``mcu_bottleneck_bytes`` now."""
        return self.program.pool_bytes


def _check_quantizable(program: PoolProgram) -> None:
    for op in program.ops:
        if op.kind not in _Q_KINDS:
            raise ValueError(
                f"op kind {op.kind!r} has no int8 execution path — plan "
                "the net with plan_net(..., fused_exec=False) so modules "
                "lower to their unfused pw/dw/pw(/add) runs")
        if op.activation not in _Q_ACTIVATIONS:
            raise ValueError(f"activation {op.activation!r} has no int8 "
                             "form (relu/None only)")


def _quantize_net(plan, params, *, calib: jax.Array | None = None,
                  n_calib: int = 2, key=None) -> QuantizedNet:
    """Calibrate an int8 deployment from the float reference forward.

    ``plan`` must lower to the unfused op vocabulary (``plan_net(...,
    fused_exec=False)``); ``calib`` is ``[n, rows, d]`` float calibration
    inputs (random normal when omitted).  Per-tensor symmetric activation
    scales come from the amax over the captured reference intermediates;
    weights are per-output-channel; every op gets CMSIS-NN-style
    ``(multiplier, shift)`` requant constants relating
    ``s_in * s_w[c] / s_out``.
    """
    from ..quant import (calibrate, quantize, quantize_bias, requant_pair,
                         requant_scalar)

    program = _prog(plan)
    _check_quantizable(program)
    if calib is None:
        if key is None:
            key = jax.random.PRNGKey(0)
        calib = jax.random.normal(
            key, (n_calib, program.in_rows, program.in_dim))

    # 1. activation scales from the captured reference intermediates
    n_ops = len(program.ops)
    amax = [0.0] * (n_ops + 1)
    with span("calibrate", batches=len(calib), taps=n_ops + 1):
        for x in calib:
            taps: list = []
            reference_forward(program, x, params, intermediates=taps)
            for i, t in enumerate(taps):
                amax[i] = max(amax[i], float(jnp.abs(t).max()))
    with span("act_scales"):
        act_qps = [calibrate(jnp.array([a])) for a in amax]
        act_scales = tuple(float(qp.scale) for qp in act_qps)
    if any(op.kind == "gru_cell" for op in program.ops):
        # the GRU hidden state IS the op output and lives in the pool at
        # the FIXED Q7 scale 1/128 across invocations — pin it before
        # any downstream requant constant is derived from it
        scales = list(act_scales)
        for i, op in enumerate(program.ops):
            if op.kind == "gru_cell":
                scales[i + 1] = 1.0 / 128.0
        act_scales = tuple(scales)

    # 2. per-op weight quantization + requant constants
    qparams: list = []
    with span("quantize_ops", ops=n_ops):
        for i, (op, p) in enumerate(zip(program.ops, params)):
            # branch convs read the held input of op ``in_op`` — their
            # input scale is that tensor's, not the chained tensor's
            s_in = act_scales[op.in_op if op.in_op >= 0 else i]
            s_out = act_scales[i + 1]
            if op.kind in ("gemm", "conv_pw", "conv_dw", "conv_k2d",
                           "conv_stream"):
                w, b = p if p[1] is not None else (p[0], None)
                axis = {"conv_dw": 2, "conv_k2d": 3,
                        "conv_stream": 3}.get(op.kind, 1)
                w_qp = calibrate(w, axis=axis)
                w_q = quantize(w, w_qp)
                b_q = (quantize_bias(b, s_in, w_qp) if b is not None
                       else jnp.zeros((op.d_out,), jnp.int32))
                mult, shift = requant_pair(s_in, w_qp, s_out)
                qparams.append((w_q, b_q, mult, shift))
            elif op.kind == "add":
                s_aux = act_scales[op.aux_op]   # the held source is op
                #                                 aux_op's INPUT tensor
                m_i, s_i = requant_scalar(s_in / s_out)
                m_a, s_a = requant_scalar(s_aux / s_out)
                qparams.append((m_i, s_i, m_a, s_a))
            elif op.kind == "pool_avg":
                m, s = requant_scalar(s_in / (op.h_in * op.w_in * s_out))
                qparams.append((m, s))
            elif op.kind == "gru_cell":
                # Q12 gate domain (scale 1/4096): both accumulators are
                # requantized into it, the bias is folded there, and the
                # recurrent input is the fixed Q7 hidden state
                w, u, b = p
                w_qp = calibrate(w, axis=1)
                u_qp = calibrate(u, axis=1)
                w_q, u_q = quantize(w, w_qp), quantize(u, u_qp)
                b_q12 = (jnp.asarray(
                    jnp.round(jnp.asarray(b, jnp.float32) * 4096.0),
                    jnp.int32) if b is not None
                    else jnp.zeros((3 * op.d_out,), jnp.int32))
                mx, sx = requant_pair(s_in, w_qp, 1.0 / 4096.0)
                mu, su = requant_pair(1.0 / 128.0, u_qp, 1.0 / 4096.0)
                qparams.append((w_q, u_q, b_q12, mx, sx, mu, su))
    return QuantizedNet(plan=plan, program=program.with_dtype("int8"),
                        params=list(params), qparams=qparams,
                        act_scales=act_scales)


def quantize_net(plan, params, **kwargs) -> QuantizedNet:
    """Deprecated direct entry — use ``repro.compile(net, target=...,
    dtype="int8")``, whose ``quantize`` pass runs this calibration with
    the target's dtype/idiom defaults.  The shim keeps the exact legacy
    behavior (same defaults, same QuantizedNet)."""
    import warnings

    warnings.warn(
        "direct quantize_net() entry is deprecated; use "
        "repro.compile(net, target=..., dtype='int8') — the driver runs "
        "quantize_net as its 'quantize' pass",
        DeprecationWarning, stacklevel=2)
    return _quantize_net(plan, params, **kwargs)


def run_net_quantized(qnet: QuantizedNet, x: jax.Array, *,
                      backend: str = "jnp", **kwargs) -> jax.Array:
    """Quantize ``x``, execute the int8 program on the ring, dequantize.

    The pool is an int8 array — ``n_segments * seg_width`` BYTES of
    state, the deployable footprint — and every op accumulates in int32
    and requantizes on store (sim certifies the identical schedule)."""
    import numpy as np

    from ..quant import QParams, dequantize, host_array, quantize

    with span("vmcu.quantize", on="host"):
        x_q = quantize(host_array(x, np.float64),
                       QParams(scale=qnet.in_scale))
    y_q, _pool = run_program(qnet.program, x_q, qnet.qparams,
                             backend=backend, **kwargs)
    with span("vmcu.dequantize"):
        return dequantize(host_array(y_q, np.float64),
                          QParams(scale=qnet.out_scale))


def quantized_agreement(qnet: QuantizedNet, *, n: int = 8, key=None,
                        backend: str = "jnp") -> dict:
    """Top-line int8-vs-float agreement over random inputs.

    Returns ``cosine`` (mean cosine similarity of the flattened
    outputs), ``argmax_agreement`` (fraction of inputs whose top-1
    output index matches) and ``n``."""
    import numpy as np

    if key is None:
        key = jax.random.PRNGKey(42)
    program = qnet.program
    xs = jax.random.normal(key, (n, program.in_rows, program.in_dim))
    cos, agree = [], []
    for x in xs:
        ref = np.asarray(reference_forward(program, x, qnet.params))
        got = np.asarray(run_net_quantized(qnet, x, backend=backend))
        a, b = ref.ravel(), got.ravel()
        denom = (np.linalg.norm(a) * np.linalg.norm(b)) or 1.0
        cos.append(float(a @ b / denom))
        agree.append(int(np.argmax(a) == np.argmax(b)))
    return {"cosine": float(np.mean(cos)),
            "argmax_agreement": float(np.mean(agree)), "n": n}
