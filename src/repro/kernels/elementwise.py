"""In-place ring elementwise kernel — delta == 0 pool ops on TPU.

The simplest PoolProgram op: map a registered element-wise fn over rows
resident in the ring pool, writing each row-block back over itself (the
paper's in-place epilogue case — RAMStore at the input pointer).  Same
RAMLoad / compute / RAMStore skeleton as the ring GEMM (Fig. 4), with the
modulo bounds check on every block offset.

The fn is applied to the whole padded block of segments; every fn
in :data:`repro.core.program.ACTIVATIONS` maps 0 -> 0, so segment padding
columns stay zero through the ring.
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.program import resolve_activation
from .ring import ring_call, round_to, seg_buf, segs


def _kernel(ring, out_ref, xv, sem_in, sem_out, *, ptr: int, n_seg: int,
            bd: int, num_blocks: int, fn: str, store_dtype):
    i = pl.program_id(0)
    slot = jax.lax.rem(i, 2)

    def ram_load(block, into):
        off = jax.lax.rem(ptr + block * bd, n_seg)
        return pltpu.make_async_copy(ring.at[pl.ds(off, bd)],
                                     xv.at[into], sem_in.at[into])

    # Double-buffered RAMLoad: block i+1 stages while block i computes.
    # Block i's in-place store covers block i's rows only, never block
    # i+1's still-live rows, so the prefetch is clobber-free.
    @pl.when(i == 0)
    def _prime():
        ram_load(0, 0).start()

    @pl.when(i + 1 < num_blocks)
    def _prefetch():
        ram_load(i + 1, 1 - slot).start()

    ram_load(i, slot).wait()
    xv[slot] = round_to(resolve_activation(fn)(xv[slot]), store_dtype)
    off = jax.lax.rem(ptr + i * bd, n_seg)
    store = pltpu.make_async_copy(xv.at[slot], out_ref.at[pl.ds(off, bd)],
                                  sem_out)
    store.start()
    store.wait()


@functools.partial(
    jax.jit,
    static_argnames=("m_rows", "d", "ptr", "fn", "block_rows", "interpret"),
    donate_argnums=(0,))
def ring_elementwise(pool: jax.Array, *, m_rows: int, d: int, ptr: int,
                     fn: str = "gelu", block_rows: int = 1,
                     interpret: bool = False) -> jax.Array:
    """Apply ``fn`` in place to ``[m_rows, d]`` rows resident at ``ptr``."""
    n_seg = pool.shape[0]
    bd = block_rows * segs(d)
    if m_rows % block_rows:
        raise ValueError("block_rows must divide m_rows")
    if n_seg % bd or ptr % bd:
        raise ValueError("pool/ptr must be row-block aligned")
    kernel = functools.partial(_kernel, ptr=ptr, n_seg=n_seg, bd=bd,
                               num_blocks=m_rows // block_rows, fn=fn,
                               store_dtype=pool.dtype)
    return ring_call(
        kernel, pool, (), grid=(m_rows // block_rows,),
        scratch_shapes=[
            seg_buf(bd, pool.dtype, slots=2),              # double buffer
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret)
