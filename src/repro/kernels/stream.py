"""Streaming ring kernels — persistent temporal state on the segment ring.

Per-frame ops for ``repro.stream`` (DESIGN.md §14), following the
``conv2d`` skeletons (the wide ring view of ``kernels/ring.py``, async
copies, input/output aliasing):

  * ``ring_conv_stream``   — sliding-window temporal conv.  Grid step 0
                             assembles the shifted window in a VMEM
                             scratch (DMA the kept state rows + the new
                             frame rows) and DMAs it back to the state
                             region; every grid step then computes one
                             output image row from the VMEM-resident
                             window (the scratch persists across the
                             sequential grid, like the avgpool
                             accumulator) with the halo conv's tap loop.
  * ``ring_gru_cell``      — gated recurrence: the hidden row at
                             ``state_ptr`` is read, updated with the
                             shared hard-gate math
                             (``repro.quant.requant.gru_update``), and
                             stored to BOTH the state region and the
                             chained output.
  * ``*_q`` twins          — the int8 deployment forms (int32
                             accumulate, CMSIS-NN requantize; the GRU
                             runs the fully-integer Q12 pipeline, so jnp
                             and Pallas agree bitwise).

The state region never wraps — the planner places it above the frame
program's linear extent (``core.program``, wrap-free placement) — so the
state offsets here are static Python ints; only the per-row output
offset needs the ``% n_segments`` bounds check.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.rowsched import conv_k2d_pad, conv_k2d_pad_w
from ..quant.requant import gru_update, gru_update_q12, requantize_i32
from .conv2d import conv_taps, halo_width, tap_weights
from .ring import (L, epilogue, epilogue_table, lanes, load_px, mxu_dot,
                   pad_to, ring_call, round_to, seg_buf, segs, store_px,
                   weight_dtype, zero_halo)


# ---------------------------------------------------------------------------
# Sliding-window temporal conv.
# ---------------------------------------------------------------------------

def _shift_window_p0(p, ring, out_ref, wv, sem_in, sem_out, *, in_ptr: int,
                     state_ptr: int, wc: int, h_win: int, hop: int):
    """Grid step 0: build the shifted window in VMEM and write it back."""
    keep = (h_win - hop) * wc

    @pl.when(p == 0)
    def _():
        cp1 = pltpu.make_async_copy(
            ring.at[pl.ds(state_ptr + hop * wc, keep)],
            wv.at[pl.ds(0, keep)], sem_in)
        cp1.start()
        cp1.wait()
        cp2 = pltpu.make_async_copy(ring.at[pl.ds(in_ptr, hop * wc)],
                                    wv.at[pl.ds(keep, hop * wc)], sem_in)
        cp2.start()
        cp2.wait()
        st = pltpu.make_async_copy(wv, out_ref.at[pl.ds(state_ptr,
                                                        h_win * wc)],
                                   sem_out)
        st.start()
        st.wait()


def _stream_kernel(ring, w_ref, e_ref, out_ref, wv, hv, yv, sem_in, sem_out,
                   *, in_ptr: int, out_ptr: int, state_ptr: int, n_seg: int,
                   h_win: int, w_in: int, w_out: int, ks: int, ns: int,
                   c_out: int, k: int, stride: int, hop: int, pad_v: int,
                   pad_h: int, activation: str | None, store_dtype):
    p = pl.program_id(0)
    wc = w_in * ks
    _shift_window_p0(p, ring, out_ref, wv, sem_in, sem_out, in_ptr=in_ptr,
                     state_ptr=state_ptr, wc=wc, h_win=h_win, hop=hop)

    @pl.when(p == 0)
    def _zero_halo():
        zero_halo(hv, pad_h * ks, pad_h * ks + wc)

    acc = jnp.zeros((w_out, ns * L), e_ref.dtype)
    for r in range(k):
        src = p * stride - pad_v + r
        srcc = jnp.clip(src, 0, h_win - 1)
        hv[pl.ds(pad_h * ks, wc)] = wv[pl.ds(srcc * wc, wc)]
        acc = conv_taps(acc, hv, w_ref, r=r, k=k, w_out=w_out, nsegs=ks,
                        stride=stride, depthwise=False,
                        valid=(src >= 0) & (src < h_win))
    y = epilogue(acc, e_ref, activation, store_dtype=store_dtype,
                 c_out=c_out)
    store_px(yv, y, ns)
    ooff = jax.lax.rem(out_ptr + p * (w_out * ns), n_seg)
    store = pltpu.make_async_copy(yv, out_ref.at[pl.ds(ooff, w_out * ns)],
                                  sem_out)
    store.start()
    store.wait()


def conv_stream(pool: jax.Array, w: jax.Array, etab: jax.Array, *,
                h_win: int, w_in: int, h_out: int, w_out: int, c_in: int,
                c_out: int, k: int, stride: int, padding: str, hop: int,
                in_ptr: int, out_ptr: int, state_ptr: int,
                activation: str | None, interpret: bool) -> jax.Array:
    """Streaming-conv skeleton shared by the fp32 and int8 forms: the
    window shift/writeback is an exact copy; each output row copies its
    k window rows into a zero-haloed row buffer and runs the k x k taps."""
    n_seg = pool.shape[0]
    ks, ns = segs(c_in), segs(c_out)
    wc = w_in * ks
    if h_win % hop:
        raise ValueError("hop must divide h_win")
    if n_seg % wc or n_seg % (w_out * ns) or in_ptr % wc \
            or out_ptr % (w_out * ns) or state_ptr % wc:
        raise ValueError("pool/pointers not image-row aligned")
    if state_ptr + h_win * wc > n_seg or in_ptr + hop * wc > n_seg:
        raise ValueError("state/frame region wraps — streaming programs "
                         "must be planned wrap-free (core.program)")
    pad_h = conv_k2d_pad_w(k, padding)
    kernel = functools.partial(
        _stream_kernel, in_ptr=in_ptr, out_ptr=out_ptr, state_ptr=state_ptr,
        n_seg=n_seg, h_win=h_win, w_in=w_in, w_out=w_out, ks=ks, ns=ns,
        c_out=c_out, k=k, stride=stride, hop=hop,
        pad_v=conv_k2d_pad(k, padding), pad_h=pad_h, activation=activation,
        store_dtype=pool.dtype)
    return ring_call(
        kernel, pool, (tap_weights(w, c_in, c_out, False), etab),
        grid=(h_out,),
        scratch_shapes=[
            seg_buf(h_win * wc, pool.dtype),                 # window
            seg_buf(halo_width(k, stride, pad_h, w_in, w_out) * ks,
                    pool.dtype),                             # haloed row
            seg_buf(w_out * ns, pool.dtype),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("h_win", "w_in", "h_out", "w_out", "c_in", "c_out",
                     "k", "stride", "padding", "hop", "in_ptr", "out_ptr",
                     "state_ptr", "activation", "interpret"),
    donate_argnums=(0,))
def ring_conv_stream(pool: jax.Array, w: jax.Array, b: jax.Array, *,
                     h_win: int, w_in: int, h_out: int, w_out: int,
                     c_in: int, c_out: int, k: int = 3, stride: int = 1,
                     padding: str = "same", hop: int = 1, in_ptr: int = 0,
                     out_ptr: int = 0, state_ptr: int = 0,
                     activation: str | None = None,
                     interpret: bool = False) -> jax.Array:
    """One streaming step: shift the ring-resident ``[h_win, w_in, c_in]``
    window by ``hop`` image rows, append the staged frame, write the
    window back at ``state_ptr``, and emit the full k x k conv output
    ``[h_out, w_out, c_out]`` at ``out_ptr`` (``w``: [k, k, c_in,
    c_out])."""
    return conv_stream(pool, w, epilogue_table(b, c_out), h_win=h_win,
                       w_in=w_in, h_out=h_out, w_out=w_out, c_in=c_in,
                       c_out=c_out, k=k, stride=stride, padding=padding,
                       hop=hop, in_ptr=in_ptr, out_ptr=out_ptr,
                       state_ptr=state_ptr, activation=activation,
                       interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("h_win", "w_in", "h_out", "w_out", "c_in", "c_out",
                     "k", "stride", "padding", "hop", "in_ptr", "out_ptr",
                     "state_ptr", "activation", "interpret"),
    donate_argnums=(0,))
def ring_conv_stream_q(pool: jax.Array, w: jax.Array, b: jax.Array,
                       mult: jax.Array, shift: jax.Array, *, h_win: int,
                       w_in: int, h_out: int, w_out: int, c_in: int,
                       c_out: int, k: int = 3, stride: int = 1,
                       padding: str = "same", hop: int = 1,
                       in_ptr: int = 0, out_ptr: int = 0,
                       state_ptr: int = 0, activation: str | None = None,
                       interpret: bool = False) -> jax.Array:
    """Int8 streaming conv: the window shift/writeback is an exact int8
    copy; the conv is the conv_k2d int32-accumulate + per-channel
    requantize pipeline."""
    return conv_stream(pool, w, epilogue_table(b, c_out, mult, shift),
                       h_win=h_win, w_in=w_in, h_out=h_out, w_out=w_out,
                       c_in=c_in, c_out=c_out, k=k, stride=stride,
                       padding=padding, hop=hop, in_ptr=in_ptr,
                       out_ptr=out_ptr, state_ptr=state_ptr,
                       activation=activation, interpret=interpret)


# ---------------------------------------------------------------------------
# GRU cell.
# ---------------------------------------------------------------------------

def _gru_kernel(ring, w_ref, u_ref, e_ref, out_ref, xv, hv, sem_in, sem_out,
                *, in_ptr: int, out_ptr: int, state_ptr: int, n_seg: int,
                store_dtype):
    ci, co = xv.shape[0], hv.shape[0]
    for src, buf in ((in_ptr, xv), (state_ptr, hv)):
        cp = pltpu.make_async_copy(ring.at[pl.ds(src, buf.shape[0])], buf,
                                   sem_in)
        cp.start()
        cp.wait()
    x, h = load_px(xv, 0, 1, ci), load_px(hv, 0, 1, co)
    if e_ref.dtype == jnp.int32:
        # both int32 accumulators requantize to the Q12 gate domain
        gx = requantize_i32(mxu_dot(x, w_ref[...]), e_ref[1:2, :],
                            e_ref[2:3, :]) + e_ref[0:1, :]
        gh = requantize_i32(mxu_dot(h, u_ref[...]), e_ref[3:4, :],
                            e_ref[4:5, :])
        hp = gru_update_q12(gx, gh, h, co * L).astype(jnp.int32)
    else:
        gx = mxu_dot(x, w_ref[...]) + e_ref[0:1, :]
        gh = mxu_dot(h, u_ref[...])
        hp = round_to(gru_update(gx, gh, h, co * L), store_dtype)
    store_px(hv, hp, co)
    for dst in (state_ptr, out_ptr % n_seg):
        st = pltpu.make_async_copy(hv, out_ref.at[pl.ds(dst, co)], sem_out)
        st.start()
        st.wait()


def _gates(a: jax.Array, d_h: int, rows: int, dtype) -> jax.Array:
    """``[..., 3*d_h]`` gate columns -> ``[rows, 3*lanes(d_h)]``: each gate
    (z, r, n) on its own whole lanes, zero-padded."""
    a = jnp.reshape(a, (-1, 3, d_h))
    return pad_to(a, (rows, 3, lanes(d_h)), dtype).reshape(rows, -1)


def gru_cell(pool: jax.Array, w: jax.Array, u: jax.Array, etab: jax.Array,
             *, d_in: int, d_h: int, in_ptr: int, out_ptr: int,
             state_ptr: int, interpret: bool) -> jax.Array:
    """GRU-step skeleton: ``h`` is the pool-resident row at ``state_ptr``;
    ``h'`` is written back there AND chained at ``out_ptr``."""
    n_seg = pool.shape[0]
    ci, co = segs(d_in), segs(d_h)
    if n_seg % ci or n_seg % co or in_ptr % ci or out_ptr % co \
            or state_ptr % co:
        raise ValueError("pool/pointers not row aligned")
    if state_ptr + co > n_seg or in_ptr + ci > n_seg:
        raise ValueError("state/frame region wraps — streaming programs "
                         "must be planned wrap-free (core.program)")
    kernel = functools.partial(_gru_kernel, in_ptr=in_ptr, out_ptr=out_ptr,
                               state_ptr=state_ptr, n_seg=n_seg,
                               store_dtype=pool.dtype)
    wd = weight_dtype(w)
    return ring_call(
        kernel, pool,
        (_gates(w, d_h, lanes(d_in), wd), _gates(u, d_h, lanes(d_h), wd),
         etab),
        grid=(1,),
        scratch_shapes=[
            seg_buf(ci, pool.dtype),
            seg_buf(co, pool.dtype),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("d_in", "d_h", "in_ptr", "out_ptr", "state_ptr",
                     "interpret"),
    donate_argnums=(0,))
def ring_gru_cell(pool: jax.Array, w: jax.Array, u: jax.Array,
                  b: jax.Array, *, d_in: int, d_h: int, in_ptr: int = 0,
                  out_ptr: int = 0, state_ptr: int = 0,
                  interpret: bool = False) -> jax.Array:
    """One GRU step in the ring: ``h' = gru_update(x@w + b, h@u, h)``
    with ``h`` the pool-resident row at ``state_ptr``; ``h'`` is written
    back there AND chained at ``out_ptr``."""
    return gru_cell(pool, w, u, _gates(b, d_h, 1, jnp.float32), d_in=d_in,
                    d_h=d_h, in_ptr=in_ptr, out_ptr=out_ptr,
                    state_ptr=state_ptr, interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("d_in", "d_h", "in_ptr", "out_ptr", "state_ptr",
                     "interpret"),
    donate_argnums=(0,))
def ring_gru_cell_q(pool: jax.Array, w: jax.Array, u: jax.Array,
                    b: jax.Array, mult_x: jax.Array, shift_x: jax.Array,
                    mult_u: jax.Array, shift_u: jax.Array, *, d_in: int,
                    d_h: int, in_ptr: int = 0, out_ptr: int = 0,
                    state_ptr: int = 0,
                    interpret: bool = False) -> jax.Array:
    """Int8 GRU step: both int32 accumulators requantize to the Q12 gate
    domain, the update is the shared fully-integer pipeline
    (``gru_update_q12``) and the hidden state stays at the fixed Q7
    scale — bitwise-equal to the jnp executor."""
    etab = jnp.concatenate([_gates(v, d_h, 1, jnp.int32) for v in
                            (b, mult_x, shift_x, mult_u, shift_u)])
    return gru_cell(pool, w, u, etab, d_in=d_in, d_h=d_h, in_ptr=in_ptr,
                    out_ptr=out_ptr, state_ptr=state_ptr,
                    interpret=interpret)
