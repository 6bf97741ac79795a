"""Ring conv / residual / pool kernels — the whole-network PoolOps on TPU.

One skeleton per access pattern, shared by the fp32 ops (here) and the
int8 deployment ops (:mod:`repro.kernels.quantized`); the two differ only
in the MXU operand dtype and the store epilogue (:func:`ring.epilogue`):

  * :func:`conv_pw`   — (strided / resampling) pointwise conv, and the
                        Fig.-4 GEMM (a 1-pixel-wide image).  ``row_block``
                        source image rows are RAMLoaded per grid step
                        (contiguous segments); strided columns are strided
                        pixel loads, resampled columns a VMEM gather.
  * :func:`conv_halo` — depthwise / general k x k conv.  The k halo rows
                        of an output row are RAMLoaded (clamped at the
                        image edge, masked) into a row buffer whose zero
                        halo columns are the horizontal padding, so every
                        tap is one strided pixel load.
  * :func:`add`       — residual add: blocks of pixel rows from the chained
                        operand and the *held* residual source, summed in
                        place over the operand.
  * :func:`avgpool`   — global average pool: one image row accumulated per
                        grid step, the single output row stored last.

All run on the wide ring view (:mod:`repro.kernels.ring`): async copies
with the ``addr % n_segments`` bounds check, input/output aliasing so the
pool is updated in place, the next block staged into a spare VMEM slot
while the current one computes (DESIGN.md §15).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.program import resolve_activation
from ..core.rowsched import conv_k2d_pad, conv_k2d_pad_w, resample_src
from .ring import (L, epilogue, epilogue_table, lanes, load_px, mxu_dot,
                   no_wrap_block, pad_to, ring_call, round_to, seg_buf, segs,
                   store_px, weight_dtype, zero_halo)

# Segments one add block may stage per operand and slot.
ADD_BLOCK_SEGS = 512


# ---------------------------------------------------------------------------
# Pointwise conv (and GEMM).
# ---------------------------------------------------------------------------

def _pw_kernel(ring, w_ref, e_ref, out_ref, xv, yv, *scratch, in_ptr: int,
               out_ptr: int, n_seg: int, h_in: int, w_in: int, h_out: int,
               w_out: int, ks: int, ns: int, c_out: int, stride: int,
               resample: bool, row_block: int, num_blocks: int,
               activation: str | None, store_dtype):
    *gather, sem_in, sem_out = scratch
    p = pl.program_id(0)
    in_chunk, out_chunk = xv.shape[1], yv.shape[0]
    slot = jax.lax.rem(p, 2)

    def ram_load(block, into):
        # row_block > 1 only when stride == 1 and not resample (the
        # driver's blocking rule), so a block's source rows are the
        # contiguous run starting at its first source row
        if resample:
            # traced mirror of core.rowsched.resample_src
            src = jax.lax.div(block * h_in, h_out)
        else:
            src = block * row_block * stride
        off = jax.lax.rem(in_ptr + src * (w_in * ks), n_seg)
        return pltpu.make_async_copy(ring.at[pl.ds(off, in_chunk)],
                                     xv.at[into], sem_in.at[into])

    # Double-buffered RAMLoad: stage block p+1 while block p computes
    # (safe pre-store: block p+1's input is still live — DESIGN.md §15).
    @pl.when(p == 0)
    def _prime():
        ram_load(0, 0).start()

    @pl.when(p + 1 < num_blocks)
    def _prefetch():
        ram_load(p + 1, 1 - slot).start()

    ram_load(p, slot).wait()
    if resample:
        g, = gather
        for q in range(w_out):
            c = resample_src(q, w_in, w_out)
            g[pl.ds(q * ks, ks)] = xv[slot, pl.ds(c * ks, ks)]
        x = load_px(g, 0, w_out, ks)
    else:
        x = load_px(xv.at[slot], 0, row_block * w_out, ks, stride)
    y = epilogue(mxu_dot(x, w_ref[...]), e_ref, activation,
                 store_dtype=store_dtype, c_out=c_out)
    store_px(yv, y, ns)
    ooff = jax.lax.rem(out_ptr + p * out_chunk, n_seg)
    store = pltpu.make_async_copy(yv, out_ref.at[pl.ds(ooff, out_chunk)],
                                  sem_out)
    store.start()
    store.wait()


def conv_pw(pool: jax.Array, w: jax.Array, etab: jax.Array, *, h_in: int,
            w_in: int, h_out: int, w_out: int, c_in: int, c_out: int,
            stride: int = 1, resample: bool = False, in_ptr: int = 0,
            out_ptr: int = 0, activation: str | None = None,
            row_block: int = 1, interpret: bool = False) -> jax.Array:
    """Pointwise conv skeleton (``etab``: :func:`ring.epilogue_table`)."""
    n_seg = pool.shape[0]
    ks, ns = segs(c_in), segs(c_out)
    if n_seg % (w_in * ks) or n_seg % (w_out * ns) \
            or in_ptr % (w_in * ks) or out_ptr % (w_out * ns):
        raise ValueError("pool/pointers not image-row aligned")
    if row_block != 1 and (stride != 1 or resample or h_out % row_block):
        raise ValueError("row_block needs stride==1, no resample, and "
                         "row_block | h_out")
    num_blocks = h_out // row_block
    wp = pad_to(w, (ks * L, ns * L), weight_dtype(w))
    kernel = functools.partial(
        _pw_kernel, in_ptr=in_ptr, out_ptr=out_ptr, n_seg=n_seg,
        h_in=h_in, w_in=w_in, h_out=h_out, w_out=w_out, ks=ks, ns=ns,
        c_out=c_out, stride=stride, resample=resample, row_block=row_block,
        num_blocks=num_blocks, activation=activation,
        store_dtype=pool.dtype)
    gather = [seg_buf(w_out * ks, pool.dtype)] if resample else []
    return ring_call(
        kernel, pool, (wp, etab), grid=(num_blocks,),
        scratch_shapes=[
            seg_buf(row_block * w_in * ks, pool.dtype, slots=2),
            seg_buf(row_block * w_out * ns, pool.dtype),
            *gather,
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("h_in", "w_in", "h_out", "w_out", "c_in", "c_out",
                     "stride", "resample", "in_ptr", "out_ptr",
                     "activation", "row_block", "interpret"),
    donate_argnums=(0,))
def ring_conv_pw(pool: jax.Array, w: jax.Array, b: jax.Array, *, h_in: int,
                 w_in: int, h_out: int, w_out: int, c_in: int, c_out: int,
                 stride: int = 1, resample: bool = False, in_ptr: int = 0,
                 out_ptr: int = 0, activation: str | None = None,
                 row_block: int = 1, interpret: bool = False) -> jax.Array:
    """Pointwise conv ``[h_in, w_in, c_in] -> [h_out, w_out, c_out]`` in
    the ring; rows live one pixel per ``segs(c)`` segments, row-major.

    ``row_block`` image rows are fused per grid step (blocking requires
    the identity pixel map: ``stride == 1`` and no resampling); the next
    block's rows stage into the spare VMEM slot while the current block
    computes (DESIGN.md §15)."""
    return conv_pw(pool, w, epilogue_table(b, c_out), h_in=h_in, w_in=w_in,
                   h_out=h_out, w_out=w_out, c_in=c_in, c_out=c_out,
                   stride=stride, resample=resample, in_ptr=in_ptr,
                   out_ptr=out_ptr, activation=activation,
                   row_block=row_block, interpret=interpret)


# ---------------------------------------------------------------------------
# Halo convs: depthwise and general k x k (the streaming conv shares the
# tap loop).
# ---------------------------------------------------------------------------

def halo_width(k: int, stride: int, pad_h: int, w_in: int, w_out: int) -> int:
    """Pixels of a zero-haloed row buffer: the padded image row, widened
    until the last output column's rightmost tap is inside it."""
    return max(pad_h + w_in, (w_out - 1) * stride + k)


def conv_taps(acc, row, w_ref, *, r: int, k: int, w_out: int, nsegs: int,
              stride: int, depthwise: bool, valid):
    """Accumulate the k taps of kernel row ``r`` over the haloed row
    buffer ``row`` (``valid`` masks a row outside the image)."""
    for s in range(k):
        tap = load_px(row, s, w_out, nsegs, stride) * valid.astype(acc.dtype)
        if depthwise:
            acc = acc + tap * w_ref[pl.ds(r * k + s, 1), :]
        else:
            acc = acc + mxu_dot(tap, w_ref[r * k + s])
    return acc


def _halo_kernel(ring, w_ref, e_ref, out_ref, xv, yv, sem_in, sem_out, *,
                 in_ptr: int, out_ptr: int, n_seg: int, h_in: int,
                 w_in: int, h_out: int, w_out: int, ks: int, ns: int,
                 c_out: int, k: int, stride: int, pad_v: int, pad_h: int,
                 depthwise: bool, activation: str | None, store_dtype):
    p = pl.program_id(0)
    wc = w_in * ks

    def tap_load(row_p, r, into):
        srcc = jnp.clip(row_p * stride - pad_v + r, 0, h_in - 1)
        off = jax.lax.rem(in_ptr + srcc * wc, n_seg)
        return pltpu.make_async_copy(ring.at[pl.ds(off, wc)],
                                     xv.at[into, pl.ds(pad_h * ks, wc)],
                                     sem_in.at[into])

    # Pipelined halo loads: the (p, r) tap sequence is double-buffered —
    # tap r+1 (or the next output row's first tap) stages while tap r
    # accumulates.  The cross-row prefetch precedes row p's RAMStore,
    # which is safe because row p+1's halo is still live (DESIGN.md §15).
    @pl.when(p == 0)
    def _prime():
        for slot in range(2):
            zero_halo(xv.at[slot], pad_h * ks, pad_h * ks + wc)
        tap_load(0, 0, 0).start()

    acc = jnp.zeros((w_out, ns * L), e_ref.dtype)
    for r in range(k):
        slot = jax.lax.rem(p * k + r, 2)
        spare = 1 - slot
        if r + 1 < k:
            tap_load(p, r + 1, spare).start()
        else:
            @pl.when(p + 1 < h_out)
            def _prefetch():
                tap_load(p + 1, 0, spare).start()
        tap_load(p, r, slot).wait()
        src = p * stride - pad_v + r
        acc = conv_taps(acc, xv.at[slot], w_ref, r=r, k=k, w_out=w_out,
                        nsegs=ks, stride=stride, depthwise=depthwise,
                        valid=(src >= 0) & (src < h_in))
    y = epilogue(acc, e_ref, activation, store_dtype=store_dtype,
                 c_out=c_out)
    store_px(yv, y, ns)
    ooff = jax.lax.rem(out_ptr + p * (w_out * ns), n_seg)
    store = pltpu.make_async_copy(yv, out_ref.at[pl.ds(ooff, w_out * ns)],
                                  sem_out)
    store.start()
    store.wait()


def tap_weights(w: jax.Array, c_in: int, c_out: int,
                depthwise: bool) -> jax.Array:
    """Lane-padded tap weights: depthwise ``[k*k, lanes(c)]`` in the
    accumulator dtype (a VPU multiply), else ``[k*k, lanes(c_in),
    lanes(c_out)]`` MXU operands."""
    k = w.shape[0]
    if depthwise:
        dt = jnp.int32 if w.dtype == jnp.int8 else jnp.float32
        return pad_to(w.reshape(k * k, c_in), (k * k, lanes(c_in)), dt)
    return pad_to(w.reshape(k * k, c_in, c_out),
                  (k * k, lanes(c_in), lanes(c_out)), weight_dtype(w))


def conv_halo(pool: jax.Array, w: jax.Array, etab: jax.Array, *,
              h_in: int, w_in: int, h_out: int, w_out: int, c_in: int,
              c_out: int, k: int, stride: int, padding: str, in_ptr: int,
              out_ptr: int, activation: str | None, depthwise: bool,
              interpret: bool) -> jax.Array:
    """Depthwise / k x k conv skeleton (``w``: ``[k, k, c]`` or ``[k, k,
    c_in, c_out]``); output row ``p`` reads the clamped input halo rows
    ``p*stride - pad .. + k - 1``, masked at the edges."""
    n_seg = pool.shape[0]
    ks, ns = segs(c_in), segs(c_out)
    if n_seg % (w_in * ks) or n_seg % (w_out * ns) \
            or in_ptr % (w_in * ks) or out_ptr % (w_out * ns):
        raise ValueError("pool/pointers not image-row aligned")
    pad_h = conv_k2d_pad_w(k, padding)
    wp = halo_width(k, stride, pad_h, w_in, w_out)
    kernel = functools.partial(
        _halo_kernel, in_ptr=in_ptr, out_ptr=out_ptr, n_seg=n_seg,
        h_in=h_in, w_in=w_in, h_out=h_out, w_out=w_out, ks=ks, ns=ns,
        c_out=c_out, k=k, stride=stride, pad_v=conv_k2d_pad(k, padding),
        pad_h=pad_h, depthwise=depthwise, activation=activation,
        store_dtype=pool.dtype)
    return ring_call(
        kernel, pool, (tap_weights(w, c_in, c_out, depthwise), etab),
        grid=(h_out,),
        scratch_shapes=[
            seg_buf(wp * ks, pool.dtype, slots=2),     # haloed row slots
            seg_buf(w_out * ns, pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("h_in", "w_in", "h_out", "w_out", "c", "rs", "stride",
                     "padding", "in_ptr", "out_ptr", "activation",
                     "interpret"),
    donate_argnums=(0,))
def ring_conv_dw(pool: jax.Array, w: jax.Array, b: jax.Array, *, h_in: int,
                 w_in: int, h_out: int, w_out: int, c: int, rs: int = 3,
                 stride: int = 1, padding: str = "same", in_ptr: int = 0,
                 out_ptr: int = 0, activation: str | None = None,
                 interpret: bool = False) -> jax.Array:
    """Depthwise RSxRS conv inside the ring.

    ``w``: [rs, rs, c]; output row ``p`` reads the clamped input halo
    rows ``p*stride - pad .. + rs - 1`` (masked at the edges).  The
    slice-padding modes (``same_top``/``same_mid``) drop the vertical
    top pad while keeping the horizontal one."""
    return conv_halo(pool, w, epilogue_table(b, c), h_in=h_in, w_in=w_in,
                     h_out=h_out, w_out=w_out, c_in=c, c_out=c, k=rs,
                     stride=stride, padding=padding, in_ptr=in_ptr,
                     out_ptr=out_ptr, activation=activation, depthwise=True,
                     interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("h_in", "w_in", "h_out", "w_out", "c_in", "c_out",
                     "k", "stride", "padding", "in_ptr", "out_ptr",
                     "activation", "interpret"),
    donate_argnums=(0,))
def ring_conv_k2d(pool: jax.Array, w: jax.Array, b: jax.Array, *,
                  h_in: int, w_in: int, h_out: int, w_out: int, c_in: int,
                  c_out: int, k: int = 3, stride: int = 1,
                  padding: str = "same", in_ptr: int = 0, out_ptr: int = 0,
                  activation: str | None = None,
                  interpret: bool = False) -> jax.Array:
    """General k x k conv ``[h_in, w_in, c_in] -> [h_out, w_out, c_out]``
    inside the ring.

    ``w``: [k, k, c_in, c_out]; output row ``p`` RAMLoads the k input
    halo rows ``p*stride - pad .. + k - 1`` (rows/cols outside the image
    masked to the zero padding), dots each tap against the Flash weight
    slice and RAMStores one output image row at the solved offset."""
    return conv_halo(pool, w, epilogue_table(b, c_out), h_in=h_in,
                     w_in=w_in, h_out=h_out, w_out=w_out, c_in=c_in,
                     c_out=c_out, k=k, stride=stride, padding=padding,
                     in_ptr=in_ptr, out_ptr=out_ptr, activation=activation,
                     depthwise=False, interpret=interpret)


# ---------------------------------------------------------------------------
# Residual add.
# ---------------------------------------------------------------------------

def _add_kernel(ring, out_ref, xv, rv, sem_in, sem_out, *, in_ptr: int,
                aux_ptr: int, out_ptr: int, n_seg: int, steps: int,
                combine):
    t = pl.program_id(0)
    chunk = xv.shape[1]
    slot = jax.lax.rem(t, 2)

    def ram_load(block, into):
        off_x = jax.lax.rem(in_ptr + block * chunk, n_seg)
        off_r = jax.lax.rem(aux_ptr + block * chunk, n_seg)
        cp1 = pltpu.make_async_copy(ring.at[pl.ds(off_x, chunk)],
                                    xv.at[into], sem_in.at[into, 0])
        cp2 = pltpu.make_async_copy(ring.at[pl.ds(off_r, chunk)],
                                    rv.at[into], sem_in.at[into, 1])
        return cp1, cp2

    # Both operand blocks double-buffer: block t+1 (operand + held
    # residual) stages while block t sums — the prefetch precedes block
    # t's in-place store, safe because block t+1's sources are still live.
    @pl.when(t == 0)
    def _prime():
        for cp in ram_load(0, 0):
            cp.start()

    @pl.when(t + 1 < steps)
    def _prefetch():
        for cp in ram_load(t + 1, 1 - slot):
            cp.start()

    for cp in ram_load(t, slot):
        cp.wait()
    xv[slot] = combine(xv[slot], rv[slot])
    off_o = jax.lax.rem(out_ptr + t * chunk, n_seg)
    st = pltpu.make_async_copy(xv.at[slot], out_ref.at[pl.ds(off_o, chunk)],
                               sem_out)
    st.start()
    st.wait()


def add(pool: jax.Array, combine, *, rows: int, d: int, in_ptr: int,
        aux_ptr: int, out_ptr: int, interpret: bool) -> jax.Array:
    """Residual-add skeleton: ``Out = combine(In, Res)`` over whole pixel
    blocks (as many rows per step as fit ``ADD_BLOCK_SEGS`` without a
    block wrapping the ring end)."""
    n_seg = pool.shape[0]
    nsegs = segs(d)
    if n_seg % nsegs or in_ptr % nsegs or aux_ptr % nsegs \
            or out_ptr % nsegs:
        raise ValueError("pool/pointers not row aligned")
    rb = no_wrap_block(rows, nsegs, (in_ptr, aux_ptr, out_ptr), n_seg,
                       ADD_BLOCK_SEGS)
    kernel = functools.partial(_add_kernel, in_ptr=in_ptr, aux_ptr=aux_ptr,
                               out_ptr=out_ptr, n_seg=n_seg,
                               steps=rows // rb, combine=combine)
    return ring_call(
        kernel, pool, (), grid=(rows // rb,),
        scratch_shapes=[
            seg_buf(rb * nsegs, pool.dtype, slots=2),    # 2-slot operand
            seg_buf(rb * nsegs, pool.dtype, slots=2),    # 2-slot residual
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("rows", "d", "in_ptr", "aux_ptr", "out_ptr",
                     "activation", "interpret"),
    donate_argnums=(0,))
def ring_add(pool: jax.Array, *, rows: int, d: int, in_ptr: int,
             aux_ptr: int, out_ptr: int, activation: str | None = None,
             interpret: bool = False) -> jax.Array:
    """``Out[t] = act(In[t] + Res[t])`` streamed in pixel-row blocks; the
    residual source rows die exactly as they are read (the planner held
    them live until here)."""
    act = resolve_activation(activation)
    dtype = pool.dtype
    return add(pool, lambda x, r: round_to(act(x + r), dtype), rows=rows,
               d=d, in_ptr=in_ptr, aux_ptr=aux_ptr, out_ptr=out_ptr,
               interpret=interpret)


# ---------------------------------------------------------------------------
# Global average pool.
# ---------------------------------------------------------------------------

def _avgpool_kernel(ring, out_ref, xv, yv, acc_v, sem_in, sem_out, *,
                    in_ptr: int, out_ptr: int, n_seg: int, h: int, w: int,
                    nsegs: int, finish):
    p = pl.program_id(0)
    slot = jax.lax.rem(p, 2)

    def ram_load(row, into):
        off = jax.lax.rem(in_ptr + row * (w * nsegs), n_seg)
        return pltpu.make_async_copy(ring.at[pl.ds(off, w * nsegs)],
                                     xv.at[into], sem_in.at[into])

    # Double-buffered row loads; nothing stores until the last step, so
    # the prefetch trivially precedes every write.
    @pl.when(p == 0)
    def _prime():
        ram_load(0, 0).start()

    @pl.when(p + 1 < h)
    def _prefetch():
        ram_load(p + 1, 1 - slot).start()

    ram_load(p, slot).wait()
    rowsum = jnp.sum(load_px(xv.at[slot], 0, w, nsegs), axis=0,
                     keepdims=True)

    @pl.when(p == 0)
    def _init():
        acc_v[...] = jnp.zeros_like(acc_v)

    acc_v[0:1, :] = acc_v[0:1, :] + rowsum

    @pl.when(p == h - 1)
    def _emit():
        store_px(yv, finish(acc_v[0:1, :]), nsegs)
        st = pltpu.make_async_copy(
            yv, out_ref.at[pl.ds(out_ptr % n_seg, nsegs)], sem_out)
        st.start()
        st.wait()


def avgpool(pool: jax.Array, finish, *, h: int, w: int, c: int,
            in_ptr: int, out_ptr: int, interpret: bool) -> jax.Array:
    """Global-average-pool skeleton: ``finish`` maps the ``[1, lanes(c)]``
    sum over ``h * w`` pixels to the stored output row."""
    n_seg = pool.shape[0]
    nsegs = segs(c)
    if n_seg % (w * nsegs) or in_ptr % (w * nsegs) or out_ptr % nsegs:
        raise ValueError("pool/pointers not aligned")
    kernel = functools.partial(_avgpool_kernel, in_ptr=in_ptr,
                               out_ptr=out_ptr, n_seg=n_seg, h=h, w=w,
                               nsegs=nsegs, finish=finish)
    acc_dtype = jnp.int32 if pool.dtype == jnp.int8 else jnp.float32
    return ring_call(
        kernel, pool, (), grid=(h,),
        scratch_shapes=[
            seg_buf(w * nsegs, pool.dtype, slots=2),      # 2-slot rows
            seg_buf(nsegs, pool.dtype),
            pltpu.VMEM((8, nsegs * L), acc_dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("h", "w", "c", "in_ptr", "out_ptr", "interpret"),
    donate_argnums=(0,))
def ring_avgpool(pool: jax.Array, *, h: int, w: int, c: int, in_ptr: int,
                 out_ptr: int, interpret: bool = False) -> jax.Array:
    """Global average pool ``[h, w, c] -> [1, 1, c]`` in the ring: one
    image row accumulated per grid step, single output row at the end."""
    dtype = pool.dtype
    return avgpool(pool, lambda s: round_to(s / (h * w), dtype), h=h, w=w,
                   c=c, in_ptr=in_ptr, out_ptr=out_ptr, interpret=interpret)
