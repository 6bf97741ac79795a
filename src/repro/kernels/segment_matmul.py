"""Ring-buffer GEMM — the vMCU fully-connected kernel (paper Fig. 4), TPU-native.

MCU mapping (paper)            → TPU mapping (here)
  RAM segment pool             → HBM pool array [n_segments, SEG_WIDTH]
                                 (memory_space=pl.ANY, aliased in/out;
                                 kernels see its wide view — kernels/ring.py)
  RAMLoad  (+ modulo check)    → async_copy pool→VMEM scratch at
                                 (in_ptr + block·k_segs) % n_segments
  FlashLoad (weights in Flash) → BlockSpec-streamed HBM→VMEM weight tiles
  Dot (2x2x16 SADD16/SMLAD)    → MXU jnp.dot on the (block_rows, d_in) tile,
                                 fp32 (int8: int32) accumulation
  RAMStore (+ modulo check)    → async_copy VMEM→pool at
                                 (out_ptr + block·n_segs) % n_segments
  RAMFree                      → implicit: the ring pointer advance IS the
                                 free (dead segments are overwritten)

Two-level tiling exactly as §5.1: the outer level walks `block_rows` rows of
segments through the ring; the inner level is the MXU tile (the hardware
"instruction lane").

Alignment adaptation (DESIGN.md): DMA needs contiguous ranges, so the pool
length is rounded to a multiple of both the input and output block segment
counts and pointers are block-aligned — mid-block wrap never occurs.  The
planner's delta is rounded up accordingly (never down: safety is preserved).
"""
from __future__ import annotations

import functools
import math

import jax

from ..core.vpool import SEG_WIDTH  # noqa: F401  (re-exported)
from ..core.vpool import fetch_rows as _pool_fetch_rows
from ..core.vpool import stage_rows as _pool_stage_rows
from .conv2d import conv_pw
from .ring import epilogue_table, segs


def aligned_pool_geometry(m_rows: int, d_in: int, d_out: int,
                          delta_segments: int, block_rows: int
                          ) -> tuple[int, int, int]:
    """Round the planner's geometry to DMA-safe alignment.

    Returns (n_segments, in_ptr, out_ptr) with in_ptr % bk == 0,
    out_ptr % bn == 0, n_segments % lcm(bk, bn) == 0 and
    in_ptr - out_ptr >= delta_segments.
    """
    k_segs, n_segs = segs(d_in), segs(d_out)
    bk, bn = block_rows * k_segs, block_rows * n_segs
    out_ptr = 0
    # smallest bk-multiple >= delta (shifting In UP is always safe)
    in_ptr = -(-delta_segments // bk) * bk
    span = max(in_ptr + m_rows * k_segs, m_rows * n_segs)
    align = math.lcm(bk, bn)
    n_segments = -(-span // align) * align
    return n_segments, in_ptr, out_ptr


@functools.partial(
    jax.jit,
    static_argnames=("m_rows", "d_in", "d_out", "in_ptr", "out_ptr",
                     "block_rows", "activation", "interpret"),
    donate_argnums=(0,))
def ring_gemm(pool: jax.Array, w: jax.Array, b: jax.Array, *, m_rows: int,
              d_in: int, d_out: int, in_ptr: int, out_ptr: int,
              block_rows: int = 8, activation: str | None = None,
              interpret: bool = False) -> jax.Array:
    """Run ``Out[m_rows, d_out] = In[m_rows, d_in] @ w + b`` inside the ring.

    ``pool``: [n_segments, SEG_WIDTH]; input rows resident at ``in_ptr``;
    output lands at ``out_ptr`` (planner-solved, block-aligned).  Returns the
    updated pool (same buffer — donated & aliased).  Each grid step is one
    ``block_rows`` block: the pointwise-conv skeleton over a
    ``block_rows``-pixel-wide image (:func:`repro.kernels.conv2d.conv_pw`).
    """
    n_seg = pool.shape[0]
    k_segs, n_segs = segs(d_in), segs(d_out)
    bk, bn = block_rows * k_segs, block_rows * n_segs
    if m_rows % block_rows:
        raise ValueError("block_rows must divide m_rows")
    if n_seg % math.lcm(bk, bn) or in_ptr % bk or out_ptr % bn:
        raise ValueError("pool/pointers not block-aligned; use "
                         "aligned_pool_geometry()")
    h = m_rows // block_rows
    return conv_pw(pool, w, epilogue_table(b, d_out), h_in=h,
                   w_in=block_rows, h_out=h, w_out=block_rows, c_in=d_in,
                   c_out=d_out, in_ptr=in_ptr, out_ptr=out_ptr,
                   activation=activation, interpret=interpret)


def stage_rows(pool: jax.Array, rows: jax.Array, ptr: int) -> jax.Array:
    """Alias of :func:`repro.core.vpool.stage_rows` (the one impl)."""
    return _pool_stage_rows(pool, rows, ptr)


def fetch_rows(pool: jax.Array, ptr: int, m: int, d: int) -> jax.Array:
    """Alias of :func:`repro.core.vpool.fetch_rows` (the one impl)."""
    return _pool_fetch_rows(pool, ptr, m, d)
