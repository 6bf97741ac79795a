"""The kernels' view of the segment ring, and the pieces every ring kernel
shares.

Mosaic tiles the two minor dimensions of a TPU array.  An int8
``[n_segments, SEG_WIDTH]`` pool is laid out in ``(8, 128)(4, 1)`` tiles, so
a DMA may only slice it at multiples of 8 segments — but ring pointers and
image rows are arbitrary segment counts (an MCUNet row is 11 or 6 pixels).
The kernels therefore see the pool as a **wide ring**
``[n_segments, 1, SEG_WIDTH]`` in a 32-bit dtype, which Mosaic tiles
``(1, 128)``: the segment index is a leading, untiled dimension, so a DMA
may start and end at any segment.  int8 values ride exactly in int32
lanes; bf16 rides in f32 and every store rounds back to the pool dtype.
:func:`ring_call` converts at the kernel boundary, so plans, pointers and
the pool the executors hold are unchanged.

Inside a kernel a *pixel* is ``segs(c)`` consecutive segments;
:func:`load_px` / :func:`store_px` move whole pixel rows between a segment
buffer and a lane-dense ``[pixels, segs * SEG_WIDTH]`` value (strided
pixel loads replace in-kernel gathers).  Weights are zero-padded to whole
lanes on the host, so every matmul and epilogue runs on full 128-lane
tiles and padding lanes stay zero.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.program import resolve_activation
from ..core.vpool import SEG_WIDTH, segments_for
from ..quant.requant import act_i32, requantize_i32

L = SEG_WIDTH


def pallas_interpret(requested: bool | None = None) -> bool:
    """Whether Pallas kernels run in interpret mode — decided here only.

    Interpret mode is the CPU test path: it is chosen on the ``cpu``
    platform and nowhere else.  On a TPU every kernel compiles through
    Mosaic (a kernel that does not compile fails the run); any other
    platform has no kernel path and raises.  ``requested`` is a caller's
    explicit choice, which may not ask for interpret mode off the CPU."""
    platform = jax.default_backend()
    if platform not in ("cpu", "tpu"):
        raise RuntimeError(f"Pallas ring kernels run on a TPU (or "
                           f"interpreted on the CPU); platform is "
                           f"{platform!r}")
    if requested is None:
        return platform == "cpu"
    if requested and platform != "cpu":
        raise ValueError(f"interpret mode runs only on the cpu platform, "
                         f"not {platform!r}")
    return requested


def segs(d: int) -> int:
    return segments_for(d, L)


def lanes(d: int) -> int:
    """``d`` rounded up to whole segments (the padded lane extent)."""
    return segs(d) * L


def wide_dtype(dtype) -> jnp.dtype:
    return jnp.dtype(jnp.int32 if jnp.issubdtype(dtype, jnp.integer)
                     else jnp.float32)


def seg_buf(n: int, dtype, slots: int | None = None):
    """VMEM scratch of ``n`` segments (``slots`` of them if given)."""
    lead = (slots,) if slots else ()
    return pltpu.VMEM(lead + (n, 1, L), wide_dtype(dtype))


def whole(a: jax.Array) -> pl.BlockSpec:
    """A grid-invariant operand resident in VMEM as one block."""
    return pl.BlockSpec(a.shape, lambda *_: (0,) * a.ndim)


def ring_call(kernel, pool: jax.Array, operands, *, grid, scratch_shapes,
              interpret: bool, in_specs=None) -> jax.Array:
    """Run ``kernel(ring, *operand_refs, out_ring, *scratch)`` over the
    wide view of ``pool`` (aliased in place) and return the pool.
    Operands are whole VMEM blocks unless ``in_specs`` says otherwise."""
    n = pool.shape[0]
    ring = pool.astype(wide_dtype(pool.dtype)).reshape(n, 1, L)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  *(in_specs or [whole(a) for a in operands])],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(ring.shape, ring.dtype),
        scratch_shapes=scratch_shapes,
        input_output_aliases={0: 0},
        interpret=interpret,
    )(ring, *operands)
    return out.reshape(n, L).astype(pool.dtype)


def pad_to(a: jax.Array, shape, dtype) -> jax.Array:
    """Zero-pad ``a`` (trailing-aligned) up to ``shape`` in ``dtype``."""
    a = jnp.asarray(a, dtype)
    lead = len(shape) - a.ndim
    a = a.reshape((1,) * lead + a.shape)
    return jnp.pad(a, [(0, s - d) for s, d in zip(shape, a.shape)])


def weight_dtype(w: jax.Array):
    """MXU operand dtype: int8 weights stay int8, floats widen to f32."""
    return jnp.int8 if w.dtype == jnp.int8 else jnp.float32


def epilogue_table(b, c_out: int, mult=None, shift=None) -> jax.Array:
    """Per-output-channel epilogue constants as one ``[rows, lanes]``
    operand: ``[bias]`` (f32) or ``[bias; multiplier; shift]`` (int32),
    zero in the padding lanes (so padding lanes requantize to zero)."""
    n = lanes(c_out)
    if mult is None:
        b = jnp.zeros((c_out,)) if b is None else b
        return pad_to(jnp.reshape(b, (1, -1)), (1, n), jnp.float32)
    rows = [jnp.reshape(jnp.asarray(v), (1, -1)) for v in (b, mult, shift)]
    return pad_to(jnp.concatenate(
        [r.astype(jnp.int32) for r in rows]), (3, n), jnp.int32)


def epilogue(acc, e_ref, activation, *, store_dtype, c_out: int):
    """Store epilogue on a lane-dense accumulator: int8 — bias, int32
    activation, CMSIS-NN requantize, saturate; float — bias, activation,
    rounding to the pool dtype, padding lanes zeroed."""
    if e_ref.dtype == jnp.int32:
        acc = act_i32(acc + e_ref[0:1, :], activation)
        return jnp.clip(requantize_i32(acc, e_ref[1:2, :], e_ref[2:3, :]),
                        -128, 127)
    y = resolve_activation(activation)(acc + e_ref[0:1, :])
    lane = jax.lax.broadcasted_iota(jnp.int32, y.shape, 1)
    return round_to(jnp.where(lane < c_out, y, 0.0), store_dtype)


def round_to(y, store_dtype):
    """Round an f32 value to the pool dtype while keeping it wide (an
    integer pool's float path casts, as a narrow store would)."""
    if jnp.dtype(store_dtype) == jnp.float32 or y.dtype != jnp.float32:
        return y
    return y.astype(store_dtype).astype(wide_dtype(store_dtype))


def mxu_dot(x, w):
    """``x @ w`` on the MXU: int8 operands accumulate in int32 (the wide
    ring's int32 lanes are narrowed first — exact), floats in f32."""
    if w.dtype == jnp.int8:
        return jnp.dot(x.astype(jnp.int8), w,
                       preferred_element_type=jnp.int32)
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def load_px(ref, start, count: int, nsegs: int, stride: int = 1):
    """Pixels ``start, start + stride, ...`` (``count`` of them) of a
    segment buffer ``ref [k, 1, L]`` as a lane-dense ``[count, nsegs*L]``
    value; ``start`` may be traced."""
    parts = []
    for j in range(nsegs):
        step = stride * nsegs
        idx = pl.ds(start * nsegs + j, count,
                    stride=step) if step > 1 else pl.ds(start * nsegs + j,
                                                        count)
        parts.append(ref[idx].reshape(count, L))
    return parts[0] if nsegs == 1 else jnp.concatenate(parts, axis=1)


def store_px(ref, y, nsegs: int) -> None:
    """Write ``y [pixels, nsegs*L]`` into the segment buffer ``ref``."""
    p = y.shape[0]
    for j in range(nsegs):
        part = y[:, j * L:(j + 1) * L].reshape(p, 1, L)
        if nsegs == 1:
            ref[pl.ds(0, p)] = part
        else:
            ref[pl.ds(j, p, stride=nsegs)] = part


def zero_halo(buf, lo: int, hi: int) -> None:
    """Zero segments ``[0, lo)`` and ``[hi, end)`` of a segment buffer —
    the halo that holds a conv's zero padding."""
    n = buf.shape[0]
    if lo:
        buf[pl.ds(0, lo)] = jnp.zeros((lo, 1, L), buf.dtype)
    if n > hi:
        buf[pl.ds(hi, n - hi)] = jnp.zeros((n - hi, 1, L), buf.dtype)


def no_wrap_block(rows: int, seg_per_row: int, ptrs, n_seg: int,
                  max_segs: int) -> int:
    """Largest row count ``rb`` dividing ``rows`` (``rb * seg_per_row <=
    max_segs``) such that no ``rb``-row block at any of ``ptrs`` wraps the
    ring end — each block is then one contiguous DMA."""
    for rb in range(min(rows, max(1, max_segs // seg_per_row)), 0, -1):
        if rows % rb:
            continue
        chunk = rb * seg_per_row
        if all((p + t * chunk) % n_seg + chunk <= n_seg
               for p in ptrs for t in range(rows // rb)):
            return rb
    raise ValueError("rows wrap the ring end mid-segment")
