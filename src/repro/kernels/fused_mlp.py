"""Fused streaming MLP — the inverted-bottleneck kernel (paper Fig. 6) for
transformers.

The paper fuses PW-expand → DW → PW-project → add with an 11-segment
workspace so intermediate tensors never exist in RAM.  The transformer
analogue fuses up-projection → activation (optionally gated) → down-
projection → residual-add: the ``[rows, d_ff]`` intermediate — the widest
tensor in the network — never exists in HBM.  Per vMCU Eq. (2) this chain's
input/output offset is ZERO (each output row depends only on its own input
row), so the kernel runs fully **in place** in the ring pool: the output row
block overwrites its own input row block, beating the single-layer 50% bound
exactly as §5.2 promises.

Grid = (row_blocks, ff_blocks); ff is the inner (fastest) axis so the
``d_ff`` reduction accumulates in an fp32 VMEM scratch while weight tiles
stream from HBM ("Flash").  The row block is the vMCU outer tile; the MXU
tile is the inner tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ring import lanes, load_px, pad_to, ring_call, round_to, seg_buf, segs
from .ring import store_px


def _kernel(ring, wg_ref, wu_ref, wd_ref, out_ref, xv, acc_v, sem_in,
            sem_out, *, ptr: int, n_seg: int, block_rows: int, d_segs: int,
            gated: bool, residual: bool, activation: str, store_dtype):
    m, f = pl.program_id(0), pl.program_id(1)
    nf = pl.num_programs(1)
    bd = block_rows * d_segs

    # Load the input row-block once per row (first ff step).
    @pl.when(f == 0)
    def _load():
        off = jax.lax.rem(ptr + m * bd, n_seg)
        cp = pltpu.make_async_copy(ring.at[pl.ds(off, bd)], xv, sem_in)
        cp.start()
        cp.wait()

    x = load_px(xv, 0, block_rows, d_segs)          # [block_rows, lanes]

    # Workspace: one [block_rows, ff_tile] slice of the intermediate —
    # the "11 segments" of Fig. 6; d_ff is never materialized.
    up = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
    if gated:
        gate = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
        if activation == "gelu":
            h = jax.nn.gelu(gate) * up
        else:
            h = jax.nn.silu(gate) * up
    else:
        h = jax.nn.gelu(up) if activation == "gelu" else jax.nn.silu(up)
    part = jnp.dot(h, wd_ref[...], preferred_element_type=jnp.float32)

    @pl.when(f == 0)
    def _init():
        acc_v[...] = jnp.zeros_like(acc_v)

    acc_v[...] += part

    # Final ff step: residual add and in-place RAMStore (delta == 0).
    @pl.when(f == nf - 1)
    def _store():
        y = acc_v[...]
        if residual:
            y = y + x
        store_px(xv, round_to(y, store_dtype), d_segs)
        off = jax.lax.rem(ptr + m * bd, n_seg)
        cp = pltpu.make_async_copy(xv, out_ref.at[pl.ds(off, bd)], sem_out)
        cp.start()
        cp.wait()


@functools.partial(
    jax.jit,
    static_argnames=("m_rows", "d_model", "ptr", "block_rows", "ff_tile",
                     "gated", "residual", "activation", "interpret"),
    donate_argnums=(0,))
def ring_fused_mlp(pool: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                   w_down: jax.Array, *, m_rows: int, d_model: int, ptr: int,
                   block_rows: int = 8, ff_tile: int = 512,
                   gated: bool = True, residual: bool = True,
                   activation: str = "gelu",
                   interpret: bool = False) -> jax.Array:
    """In-place fused MLP over rows resident at ``ptr`` in the ring pool.

    w_gate/w_up: [d_model, d_ff]; w_down: [d_ff, d_model].  The d_ff axis is
    tiled by ``ff_tile``; each tile's weights stream HBM→VMEM via BlockSpec
    (zero-padded to whole lanes of ``d_model``).
    """
    n_seg = pool.shape[0]
    d_ff = w_up.shape[1]
    d_segs = segs(d_model)
    bd = block_rows * d_segs
    if m_rows % block_rows or d_ff % ff_tile:
        raise ValueError("block_rows | m_rows and ff_tile | d_ff required")
    if n_seg % bd or ptr % bd:
        raise ValueError("pool/ptr must be row-block aligned")
    dp = lanes(d_model)
    wg, wu = (pad_to(a, (dp, d_ff), jnp.float32) for a in (w_gate, w_up))
    wd = pad_to(w_down, (d_ff, dp), jnp.float32)
    kernel = functools.partial(
        _kernel, ptr=ptr, n_seg=n_seg, block_rows=block_rows,
        d_segs=d_segs, gated=gated, residual=residual,
        activation=activation, store_dtype=pool.dtype)
    return ring_call(
        kernel, pool, (wg, wu, wd),
        grid=(m_rows // block_rows, d_ff // ff_tile),
        in_specs=[
            pl.BlockSpec((dp, ff_tile), lambda m, f: (0, f)),
            pl.BlockSpec((dp, ff_tile), lambda m, f: (0, f)),
            pl.BlockSpec((ff_tile, dp), lambda m, f: (f, 0)),
        ],
        scratch_shapes=[
            seg_buf(bd, pool.dtype),
            pltpu.VMEM((block_rows, dp), jnp.float32),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret)
