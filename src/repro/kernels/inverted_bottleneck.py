"""Fused inverted-bottleneck kernel — the paper's Fig. 6, on TPU.

PW-expand → DW 3x3 → PW-project → (+residual), streamed row-by-row through
the ring pool: tensor B (the C_mid-wide expansion) exists only as a
(RS+ ) row workspace in VMEM — never in HBM — and output rows of E
overwrite consumed rows of A at the Eq.-2 offset.

Layout: NHWC with N folded into rows; one grid step produces one output
row (W × C_out).  The workspace holds RS rows of B (the DW halo) — the
row-cache variant of the paper's 11-segment workspace (DESIGN.md §1).
Stride-1, 'same' padding (MCUNet's dominant configuration; the planner in
:mod:`repro.core.graph_planner` handles the general case analytically).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ring import lanes, load_px, pad_to, ring_call, round_to, seg_buf, store_px


def _kernel(ring, w1_ref, wd_ref, w2_ref, out_ref, b_rows, a_row, y_row,
            sem_in, sem_out, *, in_ptr: int, out_ptr: int, n_seg: int,
            H: int, W: int, RS: int, residual: bool, store_dtype):
    """Grid step p computes output row p (W x C_out segments)."""
    p = pl.program_id(0)
    pad = (RS - 1) // 2

    def ram_load(h_idx, into):
        off = jax.lax.rem(in_ptr + h_idx * W, n_seg)
        cp = pltpu.make_async_copy(ring.at[pl.ds(off, W)], into, sem_in)
        cp.start()
        cp.wait()

    # --- load the A rows this output row needs (halo) and expand to B ----
    # b_rows: VMEM [RS, pad + W + pad] ring of expanded rows (zero halo
    # columns = the 'same' padding); row r of the halo lives at slot
    # (p + r) % RS — a second, inner vMCU ring.
    def expand_row(h_idx, slot):
        """PW1: A[h_idx] (W x C_in) -> B slot (W x C_mid)."""
        ram_load(h_idx, a_row)
        b = jnp.dot(load_px(a_row, 0, W, 1), w1_ref[...],
                    preferred_element_type=jnp.float32)
        b = round_to(jnp.maximum(b, 0.0), store_dtype)            # ReLU
        b_rows[slot, pl.ds(pad, W)] = b.reshape(W, 1, b.shape[1])

    # Invariant: A-row h (expanded to B) lives at halo slot h % RS.
    # First output row primes rows 0..pad; each later row expands exactly
    # one new row (p + pad).  Writes past H land in slots whose reads are
    # always masked (src_h >= H), so the invariant holds for live rows.
    @pl.when(p == 0)
    def _prime():
        b_rows[...] = jnp.zeros(b_rows.shape, b_rows.dtype)
        for r in range(pad + 1):
            expand_row(min(r, H - 1), r % RS)

    @pl.when(p > 0)
    def _advance():
        h = jnp.clip(p + pad, 0, H - 1)
        expand_row(h, jax.lax.rem(p + pad, RS))

    # --- DW RSxRS over the halo + PW2, one output row ---------------------
    acc = jnp.zeros((W, b_rows.shape[-1]), jnp.float32)
    for r in range(RS):
        src_h = p + r - pad
        slot = jax.lax.rem(jnp.clip(src_h, 0, H - 1), RS)
        ok = ((src_h >= 0) & (src_h < H)).astype(jnp.float32)
        for s in range(RS):
            tap = b_rows[slot, pl.ds(s, W)].reshape(W, b_rows.shape[-1])
            acc += tap * ok * wd_ref[pl.ds(r * RS + s, 1), :]
    c_row = jnp.maximum(acc, 0.0)                       # [W, C_mid]
    d_row = jnp.dot(c_row, w2_ref[...],
                    preferred_element_type=jnp.float32)  # [W, C_out]

    if residual:
        ram_load(p, y_row)
        d_row = d_row + load_px(y_row, 0, W, 1)

    store_px(y_row, round_to(d_row, store_dtype), 1)
    off = jax.lax.rem(out_ptr + p * W, n_seg)
    st = pltpu.make_async_copy(y_row, out_ref.at[pl.ds(off, W)], sem_out)
    st.start()
    st.wait()


@functools.partial(
    jax.jit,
    static_argnames=("H", "W", "C_in", "C_mid", "C_out", "RS", "in_ptr",
                     "out_ptr", "residual", "interpret"),
    donate_argnums=(0,))
def ring_inverted_bottleneck(pool: jax.Array, w1: jax.Array, wd: jax.Array,
                             w2: jax.Array, *, H: int, W: int, C_in: int,
                             C_mid: int, C_out: int, RS: int = 3,
                             in_ptr: int = 0, out_ptr: int = 0,
                             residual: bool = True,
                             interpret: bool = False) -> jax.Array:
    """pool: [n_segments, seg_width] with A resident at ``in_ptr`` (one
    segment per pixel, row-major).  w1: [C_in, C_mid]; wd: [RS, RS, C_mid]
    depthwise; w2: [C_mid, C_out].  Returns the pool with E at ``out_ptr``.
    """
    seg_w = pool.shape[1]
    if max(C_in, C_out) > seg_w or C_mid > 8 * seg_w:
        raise ValueError("channel widths exceed segment geometry")
    pad = (RS - 1) // 2
    cm = lanes(C_mid)
    kernel = functools.partial(
        _kernel, in_ptr=in_ptr, out_ptr=out_ptr, n_seg=pool.shape[0], H=H,
        W=W, RS=RS, residual=residual, store_dtype=pool.dtype)
    return ring_call(
        kernel, pool,
        (pad_to(w1, (seg_w, cm), jnp.float32),
         pad_to(wd.reshape(RS * RS, C_mid), (RS * RS, cm), jnp.float32),
         pad_to(w2, (cm, seg_w), jnp.float32)),
        grid=(H,),
        scratch_shapes=[
            pltpu.VMEM((RS, W + 2 * pad, 1, cm), jnp.float32),  # B halo ring
            seg_buf(W, pool.dtype),                     # A row staging
            seg_buf(W, pool.dtype),                     # E row staging
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret)


def inverted_bottleneck_ref(a: jax.Array, w1: jax.Array, wd: jax.Array,
                            w2: jax.Array, *, residual: bool = True
                            ) -> jax.Array:
    """Oracle: A [H,W,C_in] -> E [H,W,C_out], stride 1, 'same' padding,
    ReLU after PW1 and DW (matching the kernel)."""
    H, W, C_in = a.shape
    RS = wd.shape[0]
    pad = (RS - 1) // 2
    b = jnp.maximum(jnp.einsum("hwc,cm->hwm", a.astype(jnp.float32),
                               w1.astype(jnp.float32)), 0.0)
    bp = jnp.pad(b, ((pad, pad), (pad, pad), (0, 0)))
    c = sum(bp[r:r + H, s:s + W] * wd[r, s].astype(jnp.float32)[None, None]
            for r in range(RS) for s in range(RS))
    c = jnp.maximum(c, 0.0)
    e = jnp.einsum("hwm,mo->hwo", c, w2.astype(jnp.float32))
    if residual:
        e = e + a.astype(jnp.float32)
    return e.astype(a.dtype)
