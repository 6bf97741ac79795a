"""Pluggable executors: run the SAME PoolProgram on interchangeable backends.

  * ``sim``    — drives the byte-exact :class:`SegmentPool` clobber oracle
                 with the paper-faithful fine-grained schedule (Fig. 4);
                 raises :class:`PoolClobberError` iff the plan is unsafe.
  * ``jnp``    — jit-able modular-indexing scans (the ring_buffer path);
                 runs on any backend, any seg_width, aligned or not.
  * ``pallas`` — the TPU ring kernels (segment_matmul / fused_mlp /
                 elementwise); requires an aligned program
                 (``block_rows`` set) and ``seg_width == SEG_WIDTH``.

``jnp`` and ``pallas`` produce allclose results from one plan object; the
``sim`` backend proves the plan clobber-free.  New backends register with
:func:`register_executor` (DESIGN.md §4).
"""
from __future__ import annotations

import functools
import time
from typing import Callable

import jax
import jax.numpy as jnp

from ..obs.spans import span
from .pool import SegmentPool
from .program import (EXECUTABLE_KINDS, PoolProgram, resolve_activation)
from .vpool import (VirtualPool, fetch_rows, fetch_segments, segments_for,
                    stage_rows, stage_segments)

# ---------------------------------------------------------------------------
# Registry.
# ---------------------------------------------------------------------------

_EXECUTORS: dict[str, Callable] = {}


def register_executor(name: str):
    """Register ``fn(program, pool, params, **kw)`` as backend ``name``."""
    def deco(fn):
        _EXECUTORS[name] = fn
        return fn
    return deco


def executor_names() -> tuple[str, ...]:
    return tuple(sorted(_EXECUTORS))


def execute(program: PoolProgram, pool=None, params=None, *,
            backend: str = "jnp", **kwargs):
    """Run ``program`` on ``backend``.

    ``pool`` is a :class:`VirtualPool` (or raw ``[n_segments, seg_width]``
    array) with the program input already staged at ``program.input_ptr``;
    ``params`` is one entry per op — ``(w, b)`` for gemm (``b`` may be
    None), ``(w_gate, w_up, w_down)`` for fused_mlp, ``None`` for
    elementwise.  Returns the updated pool handle (``sim`` ignores
    pool/params and returns the SegmentPool with its access statistics).
    """
    try:
        fn = _EXECUTORS[backend]
    except KeyError:
        raise ValueError(f"unknown backend {backend!r}; registered: "
                         f"{executor_names()}") from None
    if not program.executable:
        raise NotImplementedError(
            f"program contains plan-only ops "
            f"({[op.kind for op in program.ops]}); only kinds "
            f"{EXECUTABLE_KINDS} are executable")
    return fn(program, pool, params, **kwargs)


def run_program(program: PoolProgram, x: jax.Array, params, *,
                backend: str = "jnp", **kwargs):
    """Convenience: alloc a pool, stage ``x``, execute, fetch the output.

    Returns ``(y, pool)``.  Array backends only (use ``execute`` with
    ``backend="sim"`` for the oracle).  The three steps are the
    ``vmcu.stage``, ``vmcu.ring`` and ``vmcu.fetch`` spans."""
    with span("vmcu.stage"):
        pool = VirtualPool.alloc(program.spec(x.dtype))
        pool = pool.stage_rows(x, program.input_ptr)
    with span("vmcu.ring"):
        pool = execute(program, pool, params, backend=backend, **kwargs)
    with span("vmcu.fetch"):
        y = pool.fetch_rows(program.output_ptr, program.out_rows,
                            program.out_dim)
    return y, pool


def _normalize_qparams(program: PoolProgram, params):
    """Validate int8 param entries — see DESIGN.md §8.

    ``(w_q, b_q, mult, shift)`` for gemm/conv (int8 weight, int32 bias at
    the accumulator scale, per-channel requant pair), ``(mult_in,
    shift_in, mult_aux, shift_aux)`` for add, ``(mult, shift)`` for
    pool_avg.
    """
    if params is None:
        raise ValueError("quantized programs need explicit qparams "
                         "(see graph.run.quantize_net)")
    params = list(params)
    if len(params) != len(program.ops):
        raise ValueError(f"{len(params)} qparam entries for "
                         f"{len(program.ops)} ops")
    out = []
    for op, p in zip(program.ops, params):
        if op.kind in ("gemm", "conv_pw", "conv_dw", "conv_k2d",
                       "conv_stream"):
            w, b, mult, shift = p
            if b is None:
                b = jnp.zeros((op.d_out,), jnp.int32)
            out.append((w, b, mult, shift))
        elif op.kind == "gru_cell":
            # (w_q, u_q, b_q12, mult_x, shift_x, mult_u, shift_u):
            # int8 input/recurrent weights, Q12 bias, per-channel requant
            # pairs taking both accumulators to the Q12 gate domain
            w, u, b, mx, sx, mu, su = p
            if b is None:
                b = jnp.zeros((3 * op.d_out,), jnp.int32)
            out.append((w, u, b, mx, sx, mu, su))
        elif op.kind in ("add", "pool_avg"):
            out.append(tuple(p))
        else:
            raise NotImplementedError(
                f"op kind {op.kind!r} has no int8 execution path — lower "
                "the net with fused_exec=False (repro.compile does for "
                "int8 targets)")
    return out


def _normalize_params(program: PoolProgram, params):
    if program.quantized:
        return _normalize_qparams(program, params)
    if params is None:
        params = [None] * len(program.ops)
    params = list(params)
    if len(params) != len(program.ops):
        raise ValueError(f"{len(params)} param entries for "
                         f"{len(program.ops)} ops")
    out = []
    for op, p in zip(program.ops, params):
        if op.kind in ("gemm", "conv_pw", "conv_dw", "conv_k2d",
                       "conv_stream"):
            w, b = p
            if b is None:
                b = jnp.zeros((op.d_out,), w.dtype)
            out.append((w, b))
        elif op.kind == "gru_cell":
            w, u, b = p
            if b is None:
                b = jnp.zeros((3 * op.d_out,), w.dtype)
            out.append((w, u, b))
        elif op.kind == "fused_mlp":
            wg, wu, wd = p
            if wg is None:  # ungated MLPs may omit the gate projection
                wg = wu
            out.append((wg, wu, wd))
        elif op.kind == "ib_fused":
            w1, wd, w2 = p
            out.append((w1, wd, w2))
        else:
            if p is not None:
                raise ValueError(f"{op.kind} op takes no params")
            out.append(None)
    return out


def _as_array(pool):
    return pool.array if isinstance(pool, VirtualPool) else pool


def _like_input(pool, array):
    return VirtualPool(array) if isinstance(pool, VirtualPool) else array


# ---------------------------------------------------------------------------
# jnp backend — shared with ring_buffer's chain apply.
# ---------------------------------------------------------------------------

def gemm_ring_scan(pool: jax.Array, w: jax.Array, b: jax.Array, *,
                   in_ptr: int, out_ptr: int, m_rows: int, n_segments: int,
                   block_rows: int, activation: str | None) -> jax.Array:
    """One FC layer streamed through the ring as a coalesced superblock.

    The jnp mirror of the Pallas ring-GEMM (paper Fig. 4): gather the
    input segments at the modular index, MXU-dot against the un-pooled
    ("Flash") weight in fp32, scatter the output rows at the solved
    offset.  ``block_rows`` is the plan's DMA alignment (it must divide
    ``m_rows``); execution coalesces all row-blocks into ONE
    gather/compute/scatter, which DESIGN.md §15 proves bit-identical to
    the certified per-step schedule.
    """
    d_in, d_out = w.shape
    if m_rows % block_rows:
        raise ValueError("block_rows must divide m_rows")
    act = resolve_activation(activation)
    # Superblock coalescing: the certified schedule proves a store at step
    # t only lands on segments already freed (never read at any step >= t),
    # so gathering EVERY input row before the first store reads exactly the
    # bytes the per-step scan would have read, and the store targets are
    # pairwise distinct — one fetch/dot/stage replaces the whole scan.
    x = fetch_rows(pool, in_ptr, m_rows, d_in, n_segments)
    y = jnp.dot(x, w, preferred_element_type=jnp.float32)
    y = act(y + b.astype(jnp.float32)).astype(pool.dtype)
    return stage_rows(pool, y, out_ptr, n_segments)


def mlp_ring_scan(pool: jax.Array, w_gate, w_up, w_down, *, ptr: int,
                  m_rows: int, n_segments: int, block_rows: int,
                  d_model: int, ff_tile: int, gated: bool, residual: bool,
                  activation: str) -> jax.Array:
    """In-place fused MLP, mirroring the Pallas kernel's per-``ff_tile``
    accumulation order so the two backends agree to float tolerance."""
    d_ff = w_up.shape[1]
    act = resolve_activation(activation)
    # In-place op (delta == 0): every row's output depends only on that
    # row's input and lands on the segments it was read from, so the
    # per-row-block scan coalesces into one fetch/compute/stage.
    x = fetch_rows(pool, ptr, m_rows, d_model,
                   n_segments).astype(jnp.float32)
    acc = jnp.zeros((m_rows, d_model), jnp.float32)
    for f in range(d_ff // ff_tile):
        sl = slice(f * ff_tile, (f + 1) * ff_tile)
        up = jnp.dot(x, w_up[:, sl].astype(jnp.float32),
                     preferred_element_type=jnp.float32)
        if gated:
            gate = jnp.dot(x, w_gate[:, sl].astype(jnp.float32),
                           preferred_element_type=jnp.float32)
            h = act(gate) * up
        else:
            h = act(up)
        acc = acc + jnp.dot(h, w_down[sl, :].astype(jnp.float32),
                            preferred_element_type=jnp.float32)
    y = acc + x if residual else acc
    return stage_rows(pool, y.astype(pool.dtype), ptr, n_segments)


def elementwise_ring_scan(pool: jax.Array, *, ptr: int, m_rows: int,
                          n_segments: int, block_rows: int, d: int,
                          fn: str) -> jax.Array:
    """In-place element-wise map over resident rows (applied to the whole
    padded tile — every registered fn maps 0 to 0, preserving padding)."""
    seg_w = pool.shape[1]
    d_segs = segments_for(d, seg_w)
    f = resolve_activation(fn)
    # In-place, row-local (delta == 0): coalesce the whole scan.
    x = fetch_segments(pool, ptr, m_rows * d_segs,
                       n_segments).astype(jnp.float32)
    return stage_segments(pool, f(x).astype(pool.dtype), ptr, n_segments)


# ---------------------------------------------------------------------------
# jnp whole-network ops: gather rows (modular) -> fp32 math -> scatter.
# The interleaved ring schedule is certified by the sim backend; here the
# full gather happens before the scatter, which is numerically identical.
# ---------------------------------------------------------------------------

def _pw_maps(op) -> tuple[list[int], list[int]]:
    """Static source row/col index maps of a conv_pw op (the ONE
    resample map lives in ``core.rowsched``)."""
    from .rowsched import resample_src

    if op.resample:
        ridx = [resample_src(p, op.h_in, op.h_out)
                for p in range(op.h_out)]
        cidx = [resample_src(q, op.w_in, op.w_out)
                for q in range(op.w_out)]
    else:
        ridx = [p * op.stride for p in range(op.h_out)]
        cidx = [q * op.stride for q in range(op.w_out)]
    return ridx, cidx


def _image_ptr(pool, op) -> int:
    """Effective base pointer of the op's input image — the source base
    advanced past the rows below the slice window (``in_row0``; 0 for
    every unsliced op)."""
    if not op.in_row0:
        return op.in_ptr
    return op.in_ptr + op.in_row0 * op.w_in * segments_for(op.d_in,
                                                           pool.shape[1])


def _conv_pads(op) -> tuple[int, int, int, int]:
    """Exact ``(pad_t, pad_b, pad_l, pad_r)`` of a dw / k2d conv — the
    minimal zero border such that every tap's strided slice is in
    bounds.  Identical maths for every padding mode (same / valid /
    same_top / same_mid); for the legacy modes it selects the same
    elements as the previous generous symmetric padding."""
    from .rowsched import conv_k2d_pad, conv_k2d_pad_w

    pad_t = conv_k2d_pad(op.rs, op.padding)
    pad_l = conv_k2d_pad_w(op.rs, op.padding)
    pad_b = max(0, op.stride * (op.h_out - 1) + op.rs - pad_t - op.h_in)
    pad_r = max(0, op.stride * (op.w_out - 1) + op.rs - pad_l - op.w_in)
    return pad_t, pad_b, pad_l, pad_r


def _fetch_image(pool, op, n):
    rows = op.rows_in
    x = fetch_rows(pool, _image_ptr(pool, op), rows, op.d_in, n)
    return x.reshape(op.h_in, op.w_in, op.d_in).astype(jnp.float32)


def _store_image(pool, op, img, n):
    y = img.reshape(op.rows_out, op.d_out).astype(pool.dtype)
    return stage_rows(pool, y, op.out_ptr, n)


def conv_pw_ring(pool, w, b, *, op, n_segments):
    img = _fetch_image(pool, op, n_segments)
    ridx, cidx = _pw_maps(op)
    sub = img[jnp.array(ridx)][:, jnp.array(cidx)]
    y = jnp.einsum("hwc,cd->hwd", sub, w.astype(jnp.float32))
    y = resolve_activation(op.activation)(y + b.astype(jnp.float32))
    return _store_image(pool, op, y, n_segments)


def conv_dw_ring(pool, w, b, *, op, n_segments):
    img = _fetch_image(pool, op, n_segments)
    pad_t, pad_b, pad_l, pad_r = _conv_pads(op)
    s = op.stride
    padded = jnp.pad(img, ((pad_t, pad_b), (pad_l, pad_r), (0, 0)))
    acc = jnp.zeros((op.h_out, op.w_out, op.d_in), jnp.float32)
    for r in range(op.rs):
        for c in range(op.rs):
            tap = padded[r:r + s * (op.h_out - 1) + 1:s,
                         c:c + s * (op.w_out - 1) + 1:s]
            acc = acc + tap * w[r, c].astype(jnp.float32)[None, None]
    y = resolve_activation(op.activation)(acc + b.astype(jnp.float32))
    return _store_image(pool, op, y, n_segments)


def conv_k2d_ring(pool, w, b, *, op, n_segments):
    """General k x k conv: ``w`` is ``[k, k, c_in, c_out]``."""
    img = _fetch_image(pool, op, n_segments)
    pad_t, pad_b, pad_l, pad_r = _conv_pads(op)
    s = op.stride
    padded = jnp.pad(img, ((pad_t, pad_b), (pad_l, pad_r), (0, 0)))
    acc = jnp.zeros((op.h_out, op.w_out, op.d_out), jnp.float32)
    for r in range(op.rs):
        for c in range(op.rs):
            tap = padded[r:r + s * (op.h_out - 1) + 1:s,
                         c:c + s * (op.w_out - 1) + 1:s]
            acc = acc + jnp.einsum("hwc,cd->hwd", tap,
                                   w[r, c].astype(jnp.float32))
    y = resolve_activation(op.activation)(acc + b.astype(jnp.float32))
    return _store_image(pool, op, y, n_segments)


def ib_fused_ring(pool, w1, wd, w2, *, op, n_segments):
    """Fused inverted bottleneck, same math as
    ``kernels.inverted_bottleneck.inverted_bottleneck_ref`` (stride 1,
    'same' padding, ReLU after PW1 and DW)."""
    a = _fetch_image(pool, op, n_segments)
    h, w = op.h_in, op.w_in
    rs, pad = op.rs, (op.rs - 1) // 2
    bexp = jnp.maximum(jnp.einsum("hwc,cm->hwm", a,
                                  w1.astype(jnp.float32)), 0.0)
    bp = jnp.pad(bexp, ((pad, pad), (pad, pad), (0, 0)))
    cacc = sum(bp[r:r + h, s:s + w] * wd[r, s].astype(jnp.float32)[None,
                                                                   None]
               for r in range(rs) for s in range(rs))
    cacc = jnp.maximum(cacc, 0.0)
    e = jnp.einsum("hwm,mo->hwo", cacc, w2.astype(jnp.float32))
    if op.residual:
        e = e + a
    return _store_image(pool, op, e, n_segments)


def add_ring(pool, *, op, n_segments):
    x = fetch_rows(pool, op.in_ptr, op.rows_in, op.d_in, n_segments)
    res = fetch_rows(pool, op.aux_ptr, op.rows_in, op.d_in, n_segments)
    y = resolve_activation(op.activation)(
        x.astype(jnp.float32) + res.astype(jnp.float32)).astype(pool.dtype)
    return stage_rows(pool, y, op.out_ptr, n_segments)


def pool_avg_ring(pool, *, op, n_segments):
    img = _fetch_image(pool, op, n_segments)
    y = jnp.mean(img, axis=(0, 1), keepdims=False)[None, :]
    return stage_rows(pool, y.astype(pool.dtype), op.out_ptr, n_segments)


# -- streaming ops: ring-resident state shifted in place (repro.stream) ----

def _shift_window(pool, op, n):
    """conv_stream state update: fetch the ring-resident ``h_win x w_in``
    window at ``state_ptr``, drop the oldest ``hop`` image rows, append
    the staged frame, and write the shifted window back to the state
    region (same dtype — the writeback is exact for int8 pools).
    Returns ``(pool, window_rows)``."""
    wrows = op.h_in * op.w_in
    state = fetch_rows(pool, op.state_ptr, wrows, op.d_in, n)
    frame = fetch_rows(pool, op.in_ptr, op.rows_in, op.d_in, n)
    win = jnp.concatenate([state[op.hop * op.w_in:], frame], axis=0)
    return stage_rows(pool, win, op.state_ptr, n), win


def conv_stream_ring(pool, w, b, *, op, n_segments):
    """Sliding-window temporal conv: one per-frame step = state shift +
    append + full ``k x k`` conv over the window (``w`` is
    ``[k, k, c_in, c_out]``, exactly a conv_k2d over ``h_win x w_in``)."""
    pool, win = _shift_window(pool, op, n_segments)
    img = win.reshape(op.h_in, op.w_in, op.d_in).astype(jnp.float32)
    pad_t, pad_b, pad_l, pad_r = _conv_pads(op)
    s = op.stride
    padded = jnp.pad(img, ((pad_t, pad_b), (pad_l, pad_r), (0, 0)))
    acc = jnp.zeros((op.h_out, op.w_out, op.d_out), jnp.float32)
    for r in range(op.rs):
        for c in range(op.rs):
            tap = padded[r:r + s * (op.h_out - 1) + 1:s,
                         c:c + s * (op.w_out - 1) + 1:s]
            acc = acc + jnp.einsum("hwc,cd->hwd", tap,
                                   w[r, c].astype(jnp.float32))
    y = resolve_activation(op.activation)(acc + b.astype(jnp.float32))
    return _store_image(pool, op, y, n_segments)


def gru_cell_ring(pool, w, u, b, *, op, n_segments):
    """Gated recurrence: hidden state is the pool-resident row at
    ``state_ptr``; the updated state is written back AND chained at
    ``out_ptr`` (gate math: :func:`repro.quant.requant.gru_update`)."""
    from ..quant.requant import gru_update

    x = fetch_rows(pool, op.in_ptr, 1, op.d_in,
                   n_segments).astype(jnp.float32)
    h = fetch_rows(pool, op.state_ptr, 1, op.d_out,
                   n_segments).astype(jnp.float32)
    gx = jnp.dot(x, w.astype(jnp.float32),
                 preferred_element_type=jnp.float32) + b.astype(jnp.float32)
    gh = jnp.dot(h, u.astype(jnp.float32),
                 preferred_element_type=jnp.float32)
    hp = gru_update(gx, gh, h, op.d_out).astype(pool.dtype)
    pool = stage_rows(pool, hp, op.state_ptr, n_segments)
    return stage_rows(pool, hp, op.out_ptr, n_segments)


# ---------------------------------------------------------------------------
# jnp int8 ops: int8 gather -> int32 accumulate -> fixed-point requantize
# on store.  Geometry (and therefore the sim certificate) is identical to
# the fp32 path; only the element arithmetic changes (DESIGN.md §8).
# ---------------------------------------------------------------------------

def _q_act(acc, activation):
    """Int32-domain activation — the one shared definition
    (:func:`repro.quant.requant.act_i32`)."""
    from ..quant.requant import act_i32

    return act_i32(acc, activation)


def _fetch_image_q(pool, op, n):
    x = fetch_rows(pool, _image_ptr(pool, op), op.rows_in, op.d_in, n)
    return x.reshape(op.h_in, op.w_in, op.d_in).astype(jnp.int32)


def conv_pw_ring_q(pool, w, b, mult, shift, *, op, n_segments):
    from ..quant.requant import requantize

    img = _fetch_image_q(pool, op, n_segments)
    ridx, cidx = _pw_maps(op)
    sub = img[jnp.array(ridx)][:, jnp.array(cidx)]
    acc = jnp.einsum("hwc,cd->hwd", sub, w.astype(jnp.int32))
    acc = _q_act(acc + b.astype(jnp.int32), op.activation)
    q = requantize(acc, mult[None, None, :], shift[None, None, :])
    return _store_image(pool, op, q, n_segments)


def conv_k2d_ring_q(pool, w, b, mult, shift, *, op, n_segments):
    """Int8 k x k conv: int32 accumulate over every tap, per-channel
    requantize on store (zero padding is exact — symmetric quantization
    keeps the zero point at 0)."""
    from ..quant.requant import requantize

    img = _fetch_image_q(pool, op, n_segments)
    pad_t, pad_b, pad_l, pad_r = _conv_pads(op)
    s = op.stride
    padded = jnp.pad(img, ((pad_t, pad_b), (pad_l, pad_r), (0, 0)))
    acc = jnp.zeros((op.h_out, op.w_out, op.d_out), jnp.int32)
    for r in range(op.rs):
        for c in range(op.rs):
            tap = padded[r:r + s * (op.h_out - 1) + 1:s,
                         c:c + s * (op.w_out - 1) + 1:s]
            acc = acc + jnp.einsum("hwc,cd->hwd", tap,
                                   w[r, c].astype(jnp.int32))
    acc = _q_act(acc + b.astype(jnp.int32), op.activation)
    q = requantize(acc, mult[None, None, :], shift[None, None, :])
    return _store_image(pool, op, q, n_segments)


def conv_dw_ring_q(pool, w, b, mult, shift, *, op, n_segments):
    from ..quant.requant import requantize

    img = _fetch_image_q(pool, op, n_segments)
    pad_t, pad_b, pad_l, pad_r = _conv_pads(op)
    s = op.stride
    padded = jnp.pad(img, ((pad_t, pad_b), (pad_l, pad_r), (0, 0)))
    acc = jnp.zeros((op.h_out, op.w_out, op.d_in), jnp.int32)
    for r in range(op.rs):
        for c in range(op.rs):
            tap = padded[r:r + s * (op.h_out - 1) + 1:s,
                         c:c + s * (op.w_out - 1) + 1:s]
            acc = acc + tap * w[r, c].astype(jnp.int32)[None, None]
    acc = _q_act(acc + b.astype(jnp.int32), op.activation)
    q = requantize(acc, mult[None, None, :], shift[None, None, :])
    return _store_image(pool, op, q, n_segments)


def gemm_ring_scan_q(pool, w, b, mult, shift, *, in_ptr, out_ptr, m_rows,
                     n_segments, block_rows, d_in, d_out, activation):
    from ..quant.requant import requantize

    # Coalesced like the fp32 path (DESIGN.md §15); integer math makes
    # the equivalence exact at every element.
    x = fetch_rows(pool, in_ptr, m_rows, d_in,
                   n_segments).astype(jnp.int32)
    acc = jnp.dot(x, w.astype(jnp.int32), preferred_element_type=jnp.int32)
    acc = _q_act(acc + b.astype(jnp.int32), activation)
    y = requantize(acc, mult[None, :], shift[None, :])
    return stage_rows(pool, y, out_ptr, n_segments)


def add_ring_q(pool, mult_in, shift_in, mult_aux, shift_aux, *, op,
               n_segments):
    """Residual add with both operands rescaled to the output scale:
    ``sat8(rq(x, s_x/s_o) + rq(res, s_r/s_o))`` — CMSIS-NN's elementwise
    -add form (each operand requantized once, sum clamped)."""
    from ..quant.requant import requantize_i32

    x = fetch_rows(pool, op.in_ptr, op.rows_in, op.d_in, n_segments)
    res = fetch_rows(pool, op.aux_ptr, op.rows_in, op.d_in, n_segments)
    ya = requantize_i32(x.astype(jnp.int32), mult_in, shift_in)
    yb = requantize_i32(res.astype(jnp.int32), mult_aux, shift_aux)
    acc = _q_act(ya + yb, op.activation)   # post-add relu (int32 domain)
    q = jnp.clip(acc, -128, 127).astype(jnp.int8)
    return stage_rows(pool, q, op.out_ptr, n_segments)


def pool_avg_ring_q(pool, mult, shift, *, op, n_segments):
    """Global average pool: int32 SUM over the window, the ``1/(h*w)``
    folded into the requant multiplier."""
    from ..quant.requant import requantize

    img = _fetch_image_q(pool, op, n_segments)
    acc = jnp.sum(img, axis=(0, 1))[None, :]
    q = requantize(acc, mult, shift)
    return stage_rows(pool, q, op.out_ptr, n_segments)


def conv_stream_ring_q(pool, w, b, mult, shift, *, op, n_segments):
    """Int8 sliding-window conv: the state shift/writeback is a pure int8
    copy (exact), the conv is the conv_k2d int32-accumulate pipeline."""
    from ..quant.requant import requantize

    pool, win = _shift_window(pool, op, n_segments)
    img = win.reshape(op.h_in, op.w_in, op.d_in).astype(jnp.int32)
    pad_t, pad_b, pad_l, pad_r = _conv_pads(op)
    s = op.stride
    padded = jnp.pad(img, ((pad_t, pad_b), (pad_l, pad_r), (0, 0)))
    acc = jnp.zeros((op.h_out, op.w_out, op.d_out), jnp.int32)
    for r in range(op.rs):
        for c in range(op.rs):
            tap = padded[r:r + s * (op.h_out - 1) + 1:s,
                         c:c + s * (op.w_out - 1) + 1:s]
            acc = acc + jnp.einsum("hwc,cd->hwd", tap,
                                   w[r, c].astype(jnp.int32))
    acc = _q_act(acc + b.astype(jnp.int32), op.activation)
    q = requantize(acc, mult[None, None, :], shift[None, None, :])
    return _store_image(pool, op, q, n_segments)


def gru_cell_ring_q(pool, w, u, b, mx, sx, mu, su, *, op, n_segments):
    """Int8 GRU cell, CMSIS-NN discipline: both matmul accumulators are
    requantized to the Q12 gate domain, the update runs the shared
    fixed-point pipeline (:func:`repro.quant.requant.gru_update_q12`),
    and the hidden state stays at the FIXED Q7 scale 1/128 — fully
    integer, so jnp and Pallas agree bitwise."""
    from ..quant.requant import gru_update_q12, requantize_i32

    x = fetch_rows(pool, op.in_ptr, 1, op.d_in, n_segments)
    h = fetch_rows(pool, op.state_ptr, 1, op.d_out, n_segments)
    gx = requantize_i32(
        jnp.dot(x.astype(jnp.int32), w.astype(jnp.int32),
                preferred_element_type=jnp.int32), mx, sx)
    gx = gx + b.astype(jnp.int32)
    gh = requantize_i32(
        jnp.dot(h.astype(jnp.int32), u.astype(jnp.int32),
                preferred_element_type=jnp.int32), mu, su)
    hp = gru_update_q12(gx, gh, h, op.d_out)
    pool = stage_rows(pool, hp, op.state_ptr, n_segments)
    return stage_rows(pool, hp, op.out_ptr, n_segments)


def _apply_op_q(pool: jax.Array, op, p, *, n: int, br: int,
                rows: int) -> jax.Array:
    """Apply ONE int8 op — the loop body shared by the whole-program jit
    and the per-op traced path (same jaxpr either way)."""
    if op.kind == "gemm":
        w, b, mult, shift = p
        return gemm_ring_scan_q(pool, w, b, mult, shift,
                                in_ptr=op.in_ptr, out_ptr=op.out_ptr,
                                m_rows=rows, n_segments=n,
                                block_rows=br, d_in=op.d_in,
                                d_out=op.d_out,
                                activation=op.activation)
    if op.kind == "conv_pw":
        w, b, mult, shift = p
        return conv_pw_ring_q(pool, w, b, mult, shift, op=op,
                              n_segments=n)
    if op.kind == "conv_dw":
        w, b, mult, shift = p
        return conv_dw_ring_q(pool, w, b, mult, shift, op=op,
                              n_segments=n)
    if op.kind == "conv_k2d":
        w, b, mult, shift = p
        return conv_k2d_ring_q(pool, w, b, mult, shift, op=op,
                               n_segments=n)
    if op.kind == "add":
        mi, si, ma, sa = p
        return add_ring_q(pool, mi, si, ma, sa, op=op, n_segments=n)
    if op.kind == "pool_avg":
        mult, shift = p
        return pool_avg_ring_q(pool, mult, shift, op=op, n_segments=n)
    if op.kind == "conv_stream":
        w, b, mult, shift = p
        return conv_stream_ring_q(pool, w, b, mult, shift, op=op,
                                  n_segments=n)
    if op.kind == "gru_cell":
        w, u, b, mx, sx, mu, su = p
        return gru_cell_ring_q(pool, w, u, b, mx, sx, mu, su, op=op,
                               n_segments=n)
    raise NotImplementedError(f"no int8 jnp path for {op.kind}")


def _run_jnp_q(pool: jax.Array, params, program: PoolProgram) -> jax.Array:
    br = program.block_rows or 1
    n = program.n_segments
    for op, p in zip(program.ops, params):
        rows = op.rows_in or program.m_rows
        pool = _apply_op_q(pool, op, p, n=n, br=br, rows=rows)
    return pool


def _apply_op(pool: jax.Array, op, p, *, n: int, br: int,
              rows: int) -> jax.Array:
    """Apply ONE fp32 op — see :func:`_apply_op_q`."""
    if op.kind == "gemm":
        w, b = p
        return gemm_ring_scan(pool, w, b, in_ptr=op.in_ptr,
                              out_ptr=op.out_ptr, m_rows=rows,
                              n_segments=n, block_rows=br,
                              activation=op.activation)
    if op.kind == "fused_mlp":
        wg, wu, wd = p
        return mlp_ring_scan(pool, wg, wu, wd, ptr=op.in_ptr,
                             m_rows=rows, n_segments=n,
                             block_rows=br, d_model=op.d_in,
                             ff_tile=op.ff_tile, gated=op.gated,
                             residual=op.residual,
                             activation=op.activation)
    if op.kind == "elementwise":
        return elementwise_ring_scan(pool, ptr=op.in_ptr, m_rows=rows,
                                     n_segments=n, block_rows=br,
                                     d=op.d_in, fn=op.activation)
    if op.kind == "conv_pw":
        w, b = p
        return conv_pw_ring(pool, w, b, op=op, n_segments=n)
    if op.kind == "conv_dw":
        w, b = p
        return conv_dw_ring(pool, w, b, op=op, n_segments=n)
    if op.kind == "conv_k2d":
        w, b = p
        return conv_k2d_ring(pool, w, b, op=op, n_segments=n)
    if op.kind == "ib_fused":
        w1, wd, w2 = p
        return ib_fused_ring(pool, w1, wd, w2, op=op, n_segments=n)
    if op.kind == "add":
        return add_ring(pool, op=op, n_segments=n)
    if op.kind == "pool_avg":
        return pool_avg_ring(pool, op=op, n_segments=n)
    if op.kind == "conv_stream":
        w, b = p
        return conv_stream_ring(pool, w, b, op=op, n_segments=n)
    if op.kind == "gru_cell":
        w, u, b = p
        return gru_cell_ring(pool, w, u, b, op=op, n_segments=n)
    raise NotImplementedError(op.kind)


@functools.partial(jax.jit, static_argnames=("program",),
                   donate_argnums=(0,))
def _run_jnp(pool: jax.Array, params, program: PoolProgram) -> jax.Array:
    br = program.block_rows or 1
    n = program.n_segments
    if program.quantized:
        return _run_jnp_q(pool, params, program)
    for op, p in zip(program.ops, params):
        rows = op.rows_in or program.m_rows
        pool = _apply_op(pool, op, p, n=n, br=br, rows=rows)
    return pool


@functools.partial(jax.jit, static_argnames=("program", "i"),
                   donate_argnums=(0,))
def _run_jnp_op(pool: jax.Array, p, program: PoolProgram,
                i: int) -> jax.Array:
    """One op of ``program`` as its own jit unit (the traced path)."""
    op = program.ops[i]
    rows = op.rows_in or program.m_rows
    br = program.block_rows or 1
    n = program.n_segments
    if program.quantized:
        return _apply_op_q(pool, op, p, n=n, br=br, rows=rows)
    return _apply_op(pool, op, p, n=n, br=br, rows=rows)


@register_executor("jnp")
def run_program_jnp(program: PoolProgram, pool, params, *, tracer=None,
                    **_kw):
    """``tracer=None`` runs the pre-existing whole-program jit
    (bit-identical, zero tracing cost).  With a RingTracer, ops run as
    separate jit units, each synchronized (``block_until_ready``) so the
    recorded per-op wall times are device time, not dispatch time."""
    params = _normalize_params(program, params)
    arr = _as_array(pool)
    if tracer is None:
        arr = _run_jnp(arr, params, program)
    else:
        tracer.backend = "jnp"
        for i, p in enumerate(params):
            t0 = time.perf_counter()
            arr = _run_jnp_op(arr, p, program, i)
            jax.block_until_ready(arr)
            tracer.record(i, time.perf_counter() - t0)
    return _like_input(pool, arr)


# ---------------------------------------------------------------------------
# pallas backend.
# ---------------------------------------------------------------------------

def _pw_row_block(op, n_seg: int, in_ptr: int, seg_width: int,
                  limit: int) -> int:
    """Largest safe pointwise-conv row block ``<= limit``.

    Blocking needs the identity pixel map (stride 1, no resample) so a
    block's source rows are contiguous, plus DMA no-wrap alignment: the
    pool length and both pointers must be multiples of the block's input
    and output chunk sizes (a mid-block modular wrap would split the
    single async copy).  Execution granularity only — the plan geometry
    and its certificates are untouched (DESIGN.md §15).
    """
    if limit <= 1 or op.stride != 1 or op.resample:
        return 1
    ic = op.w_in * segments_for(op.d_in, seg_width)
    oc = op.w_out * segments_for(op.d_out, seg_width)
    for rb in range(min(limit, op.h_out), 1, -1):
        if op.h_out % rb:
            continue
        if n_seg % (rb * ic) or in_ptr % (rb * ic):
            continue
        if n_seg % (rb * oc) or op.out_ptr % (rb * oc):
            continue
        return rb
    return 1


@register_executor("pallas")
def run_program_pallas(program: PoolProgram, pool, params, *,
                       interpret: bool | None = None, tracer=None,
                       kernel_block_rows: int = 8, **_kw):
    # Lazy import: core must stay importable without the kernels package.
    from ..kernels.conv2d import (ring_add, ring_avgpool, ring_conv_dw,
                                  ring_conv_k2d, ring_conv_pw)
    from ..kernels.elementwise import ring_elementwise
    from ..kernels.fused_mlp import ring_fused_mlp
    from ..kernels.inverted_bottleneck import ring_inverted_bottleneck
    from ..kernels.ring import pallas_interpret
    from ..kernels.segment_matmul import SEG_WIDTH as KSEG, ring_gemm
    from ..kernels.stream import ring_conv_stream, ring_gru_cell

    if program.block_rows is None:
        raise ValueError("pallas backend needs an aligned program — plan "
                         "with block_rows=<int>")
    if program.seg_width != KSEG:
        raise ValueError(f"pallas kernels use seg_width={KSEG}, program "
                         f"has {program.seg_width}")
    interpret = pallas_interpret(interpret)
    arr = _as_array(pool)
    br = program.block_rows
    if tracer is not None:
        tracer.backend = "pallas"
    if program.quantized:
        return _like_input(pool, _run_pallas_q(
            arr, _normalize_params(program, params), program, br,
            interpret, tracer=tracer,
            kernel_block_rows=kernel_block_rows))
    for i, (op, p) in enumerate(zip(program.ops,
                                    _normalize_params(program, params))):
        rows = op.rows_in or program.m_rows
        t0 = time.perf_counter() if tracer is not None else 0.0
        with span("vmcu.op", index=i, kind=op.kind):
            if op.kind == "gemm":
                w, b = p
                arr = ring_gemm(arr, w, b, m_rows=rows, d_in=op.d_in,
                                d_out=op.d_out, in_ptr=op.in_ptr,
                                out_ptr=op.out_ptr, block_rows=br,
                                activation=op.activation, interpret=interpret)
            elif op.kind == "fused_mlp":
                wg, wu, wd = p
                arr = ring_fused_mlp(arr, wg, wu, wd, m_rows=rows,
                                     d_model=op.d_in, ptr=op.in_ptr,
                                     block_rows=br, ff_tile=op.ff_tile,
                                     gated=op.gated, residual=op.residual,
                                     activation=op.activation,
                                     interpret=interpret)
            elif op.kind == "elementwise":
                arr = ring_elementwise(arr, m_rows=rows, d=op.d_in,
                                       ptr=op.in_ptr, fn=op.activation,
                                       block_rows=br, interpret=interpret)
            elif op.kind == "conv_pw":
                w, b = p
                iptr = _image_ptr(arr, op)
                arr = ring_conv_pw(arr, w, b, h_in=op.h_in, w_in=op.w_in,
                                   h_out=op.h_out, w_out=op.w_out,
                                   c_in=op.d_in, c_out=op.d_out,
                                   stride=op.stride, resample=op.resample,
                                   in_ptr=iptr, out_ptr=op.out_ptr,
                                   activation=op.activation,
                                   row_block=_pw_row_block(
                                       op, arr.shape[0], iptr,
                                       program.seg_width, kernel_block_rows),
                                   interpret=interpret)
            elif op.kind == "conv_dw":
                w, b = p
                arr = ring_conv_dw(arr, w, b, h_in=op.h_in, w_in=op.w_in,
                                   h_out=op.h_out, w_out=op.w_out, c=op.d_in,
                                   rs=op.rs, stride=op.stride,
                                   padding=op.padding,
                                   in_ptr=_image_ptr(arr, op),
                                   out_ptr=op.out_ptr,
                                   activation=op.activation,
                                   interpret=interpret)
            elif op.kind == "conv_k2d":
                w, b = p
                arr = ring_conv_k2d(arr, w, b, h_in=op.h_in, w_in=op.w_in,
                                    h_out=op.h_out, w_out=op.w_out,
                                    c_in=op.d_in, c_out=op.d_out, k=op.rs,
                                    stride=op.stride, padding=op.padding,
                                    in_ptr=_image_ptr(arr, op),
                                    out_ptr=op.out_ptr,
                                    activation=op.activation,
                                    interpret=interpret)
            elif op.kind == "ib_fused":
                w1, wd, w2 = p
                arr = ring_inverted_bottleneck(
                    arr, w1, wd, w2, H=op.h_in, W=op.w_in, C_in=op.d_in,
                    C_mid=op.d_mid, C_out=op.d_out, RS=op.rs,
                    in_ptr=op.in_ptr, out_ptr=op.out_ptr,
                    residual=op.residual, interpret=interpret)
            elif op.kind == "add":
                arr = ring_add(arr, rows=rows, d=op.d_in, in_ptr=op.in_ptr,
                               aux_ptr=op.aux_ptr, out_ptr=op.out_ptr,
                               activation=op.activation, interpret=interpret)
            elif op.kind == "pool_avg":
                arr = ring_avgpool(arr, h=op.h_in, w=op.w_in, c=op.d_in,
                                   in_ptr=op.in_ptr, out_ptr=op.out_ptr,
                                   interpret=interpret)
            elif op.kind == "conv_stream":
                w, b = p
                arr = ring_conv_stream(arr, w, b, h_win=op.h_in, w_in=op.w_in,
                                       h_out=op.h_out, w_out=op.w_out,
                                       c_in=op.d_in, c_out=op.d_out, k=op.rs,
                                       stride=op.stride, padding=op.padding,
                                       hop=op.hop, in_ptr=op.in_ptr,
                                       out_ptr=op.out_ptr,
                                       state_ptr=op.state_ptr,
                                       activation=op.activation,
                                       interpret=interpret)
            elif op.kind == "gru_cell":
                w, u, b = p
                arr = ring_gru_cell(arr, w, u, b, d_in=op.d_in, d_h=op.d_out,
                                    in_ptr=op.in_ptr, out_ptr=op.out_ptr,
                                    state_ptr=op.state_ptr,
                                    interpret=interpret)
            else:
                raise NotImplementedError(op.kind)
        if tracer is not None:
            jax.block_until_ready(arr)
            tracer.record(i, time.perf_counter() - t0)
    return _like_input(pool, arr)


def _run_pallas_q(arr, params, program: PoolProgram, br, interpret,
                  tracer=None, kernel_block_rows: int = 8):
    """Int8 program on the Pallas ring kernels (``kernels.quantized``)."""
    from ..kernels.quantized import (ring_add_q, ring_avgpool_q,
                                     ring_conv_dw_q, ring_conv_k2d_q,
                                     ring_conv_pw_q, ring_gemm_q)
    from ..kernels.stream import ring_conv_stream_q, ring_gru_cell_q

    for i, (op, p) in enumerate(zip(program.ops, params)):
        rows = op.rows_in or program.m_rows
        t0 = time.perf_counter() if tracer is not None else 0.0
        with span("vmcu.op", index=i, kind=op.kind):
            if op.kind == "gemm":
                w, b, mult, shift = p
                arr = ring_gemm_q(arr, w, b, mult, shift, m_rows=rows,
                                  d_in=op.d_in, d_out=op.d_out,
                                  in_ptr=op.in_ptr, out_ptr=op.out_ptr,
                                  block_rows=br, activation=op.activation,
                                  interpret=interpret)
            elif op.kind == "conv_pw":
                w, b, mult, shift = p
                iptr = _image_ptr(arr, op)
                arr = ring_conv_pw_q(arr, w, b, mult, shift, h_in=op.h_in,
                                     w_in=op.w_in, h_out=op.h_out,
                                     w_out=op.w_out, c_in=op.d_in,
                                     c_out=op.d_out, stride=op.stride,
                                     resample=op.resample,
                                     in_ptr=iptr, out_ptr=op.out_ptr,
                                     activation=op.activation,
                                     row_block=_pw_row_block(
                                         op, arr.shape[0], iptr,
                                         program.seg_width,
                                         kernel_block_rows),
                                     interpret=interpret)
            elif op.kind == "conv_dw":
                w, b, mult, shift = p
                arr = ring_conv_dw_q(arr, w, b, mult, shift, h_in=op.h_in,
                                     w_in=op.w_in, h_out=op.h_out,
                                     w_out=op.w_out, c=op.d_in, rs=op.rs,
                                     stride=op.stride, padding=op.padding,
                                     in_ptr=_image_ptr(arr, op),
                                     out_ptr=op.out_ptr,
                                     activation=op.activation,
                                     interpret=interpret)
            elif op.kind == "conv_k2d":
                w, b, mult, shift = p
                arr = ring_conv_k2d_q(arr, w, b, mult, shift, h_in=op.h_in,
                                      w_in=op.w_in, h_out=op.h_out,
                                      w_out=op.w_out, c_in=op.d_in,
                                      c_out=op.d_out, k=op.rs,
                                      stride=op.stride, padding=op.padding,
                                      in_ptr=_image_ptr(arr, op),
                                      out_ptr=op.out_ptr,
                                      activation=op.activation,
                                      interpret=interpret)
            elif op.kind == "add":
                mi, si, ma, sa = p
                arr = ring_add_q(arr, rows=rows, d=op.d_in, in_ptr=op.in_ptr,
                                 aux_ptr=op.aux_ptr, out_ptr=op.out_ptr,
                                 mult_in=mi, shift_in=si, mult_aux=ma,
                                 shift_aux=sa, activation=op.activation,
                                 interpret=interpret)
            elif op.kind == "pool_avg":
                mult, shift = p
                arr = ring_avgpool_q(arr, h=op.h_in, w=op.w_in, c=op.d_in,
                                     in_ptr=op.in_ptr, out_ptr=op.out_ptr,
                                     mult=mult, shift=shift,
                                     interpret=interpret)
            elif op.kind == "conv_stream":
                w, b, mult, shift = p
                arr = ring_conv_stream_q(arr, w, b, mult, shift,
                                         h_win=op.h_in, w_in=op.w_in,
                                         h_out=op.h_out, w_out=op.w_out,
                                         c_in=op.d_in, c_out=op.d_out,
                                         k=op.rs, stride=op.stride,
                                         padding=op.padding, hop=op.hop,
                                         in_ptr=op.in_ptr,
                                         out_ptr=op.out_ptr,
                                         state_ptr=op.state_ptr,
                                         activation=op.activation,
                                         interpret=interpret)
            elif op.kind == "gru_cell":
                w, u, b, mx, sx, mu, su = p
                arr = ring_gru_cell_q(arr, w, u, b, mx, sx, mu, su,
                                      d_in=op.d_in, d_h=op.d_out,
                                      in_ptr=op.in_ptr, out_ptr=op.out_ptr,
                                      state_ptr=op.state_ptr,
                                      interpret=interpret)
            else:
                raise NotImplementedError(
                    f"no int8 pallas kernel for {op.kind}")
        if tracer is not None:
            jax.block_until_ready(arr)
            tracer.record(i, time.perf_counter() - t0)
    return arr


# ---------------------------------------------------------------------------
# sim backend — the clobber oracle.
# ---------------------------------------------------------------------------

def _sim_rowsched_op(sim: SegmentPool, program: PoolProgram, i: int) -> None:
    """Replay one conv-family op through the oracle from the SAME row
    schedule the planner solved its delta with (``core.rowsched``)."""
    from .rowsched import schedule_for_op

    op = program.ops[i]
    sched = schedule_for_op(op, program.seg_width)
    frees = sched.frees()
    ic, oc = sched.in_chunk, sched.out_chunk
    # branch ops (in_op >= 0) read the held INPUT of op in_op — segment
    # ownership tags carry that op's index, exactly like aux reads
    iown = op.in_op if op.in_op >= 0 else i
    # sliced ops (repro.partial): reads window the source record at row
    # offset in_row0; writes land inside the SHARED output tensor owned
    # by op out_op at row offset out_row0
    r0 = op.in_row0
    oown = op.out_op if op.out_op >= 0 else i + 1
    w0 = op.out_row0
    for t in range(sched.steps):
        for r in sched.reads[t]:
            for s in range(ic):
                seg = (r0 + r) * ic + s
                sim.read(op.in_ptr + seg, owner=(iown, seg))
        if sched.aux_reads is not None:
            ac = sched.aux_chunk
            for r in sched.aux_reads[t]:
                for s in range(ac):
                    seg = r * ac + s
                    sim.read(op.aux_ptr + seg, owner=(op.aux_op, seg))
                    sim.free(op.aux_ptr + seg, owner=(op.aux_op, seg))
        if not op.hold_input:
            for r in frees[t]:
                for s in range(ic):
                    seg = (r0 + r) * ic + s
                    sim.free(op.in_ptr + seg, owner=(iown, seg))
        for r in sched.writes[t]:
            for s in range(oc):
                sim.write(op.out_ptr + r * oc + s,
                          owner=(oown, (w0 + r) * oc + s))
    if op.free_src:
        # last slice of a held source: release the WHOLE record (earlier
        # slices held it; re-freeing an already-free segment is benign)
        src_rows = op.h_src or sched.in_rows
        for seg in range(src_rows * ic):
            sim.free(op.in_ptr + seg, owner=(iown, seg))


def _sim_stream_op(sim: SegmentPool, program: PoolProgram, i: int) -> None:
    """conv_stream / gru_cell through the oracle: whole-state read then a
    same-owner whole-state rewrite (the executors fetch the full window /
    hidden vector, shift, and write it back — a FOREIGN write into the
    live state region is exactly the clobber this catches), followed by
    the frame traffic via the op's row schedule."""
    op = program.ops[i]
    for j in range(op.state_segments):
        sim.read(op.state_ptr + j, owner=("state", i, j))
    for j in range(op.state_segments):
        sim.write(op.state_ptr + j, owner=("state", i, j))
    _sim_rowsched_op(sim, program, i)


@register_executor("sim")
def run_program_sim(program: PoolProgram, pool=None, params=None, *,
                    tracer=None, **_kw) -> SegmentPool:
    """Execute the program's schedule in the SegmentPool simulator.

    GEMM ops run the paper's fine-grained Fig.-4 schedule (input segment
    freed after its LAST read) — strictly harder than the block-granular
    TPU schedule, so a clobber-free sim run certifies the kernels.
    Conv-family ops replay the row schedule their delta was solved with
    (``core.rowsched``); residual sources are freed by the consuming add.
    Returns the SegmentPool for access statistics (peak_live etc.).

    A ``tracer`` (:class:`repro.obs.RingTracer`) snapshots the pool's
    read/write/free counters around every op — measured per-op traffic
    from the oracle itself, asserted bit-equal to the schedule-derived
    static counters.
    """
    sw = program.seg_width
    if isinstance(pool, SegmentPool):
        # persistent streaming session (repro.stream): state records from
        # the previous step are still live under their ("state", i, j)
        # owners — the next step must prove it never clobbers them
        sim = pool
    else:
        sim = SegmentPool(program.n_segments,
                          segment_bytes=sw * program.elem_bytes)
        for i, op in enumerate(program.ops):
            for j in range(op.state_segments):
                sim.write(op.state_ptr + j, owner=("state", i, j))
    if tracer is not None:
        tracer.backend = "sim"
    first = program.ops[0]
    for j in range(first.in_segments):
        sim.write(first.in_ptr + j, owner=(0, j))
    for i, op in enumerate(program.ops):
        m = op.rows_in or program.m_rows
        if tracer is not None:
            pre = (sim.reads, sim.writes, sim.frees)
            t0 = time.perf_counter()
        if op.kind == "gemm":
            k_segs = segments_for(op.d_in, sw)
            n_segs = segments_for(op.d_out, sw)
            for r in range(m):
                for n in range(n_segs):
                    for k in range(k_segs):
                        seg = r * k_segs + k
                        sim.read(op.in_ptr + seg, owner=(i, seg))
                        if n == n_segs - 1 and not op.hold_input:
                            sim.free(op.in_ptr + seg, owner=(i, seg))
                    outseg = r * n_segs + n
                    sim.write(op.out_ptr + outseg, owner=(i + 1, outseg))
        elif op.kind in ("fused_mlp", "elementwise"):
            # per-row in-place at delta == 0
            d_segs = segments_for(op.d_in, sw)
            for r in range(m):
                for s in range(d_segs):
                    seg = r * d_segs + s
                    sim.read(op.in_ptr + seg, owner=(i, seg))
                    if not op.hold_input:
                        sim.free(op.in_ptr + seg, owner=(i, seg))
                for s in range(d_segs):
                    seg = r * d_segs + s
                    sim.write(op.out_ptr + seg, owner=(i + 1, seg))
        elif op.kind in ("conv_stream", "gru_cell"):
            _sim_stream_op(sim, program, i)
        else:
            _sim_rowsched_op(sim, program, i)
        if tracer is not None:
            tracer.record(i, time.perf_counter() - t0)
            tracer.record_sim(i, reads=sim.reads - pre[0],
                              writes=sim.writes - pre[1],
                              frees=sim.frees - pre[2], live=sim.live)
    last = program.ops[-1]
    for j in range(last.out_segments):  # outputs must survive the ring
        sim.read(last.out_ptr + j, owner=(len(program.ops), j))
    for i, op in enumerate(program.ops):  # ...and so must persistent state
        for j in range(op.state_segments):
            sim.read(op.state_ptr + j, owner=("state", i, j))
    if tracer is not None:
        tracer.finish_sim(sim)
    return sim
