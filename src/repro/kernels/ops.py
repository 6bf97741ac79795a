"""Jit'd public wrappers around the Pallas kernels.

Each wrapper is now a one-call demonstration of the unified API: plan a
:class:`PoolProgram`, alloc a :class:`VirtualPool`, ``execute`` on the
``pallas`` backend, fetch the result.  Production code keeps the pool
alive across a longer program (see examples/quickstart.py).

On the CPU platform every kernel runs in ``interpret=True`` mode — the
kernel body executes in Python, validating ring logic and numerics; on a
TPU the same call sites compile through Mosaic
(:func:`repro.kernels.ring.pallas_interpret` decides, and nothing else).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ref
from .ring import pallas_interpret
from .segment_matmul import (SEG_WIDTH, aligned_pool_geometry, fetch_rows,
                             ring_gemm, stage_rows)
from .fused_mlp import ring_fused_mlp
from .ring_decode import ring_cache_update, ring_decode_attention
from ..core.executors import execute
from ..core.program import FusedMLPSpec, GemmSpec, plan_program
from ..core.vpool import VirtualPool, segments_for


def segment_gemm(x: jax.Array, w: jax.Array, b: jax.Array | None = None, *,
                 block_rows: int = 8) -> tuple[jax.Array, dict]:
    """Plan + stage + run the ring GEMM; returns (result, plan_info).

    This is the one-call demonstration path; production code keeps the pool
    alive across layers (see examples/quickstart.py).
    """
    m, d_in = x.shape
    d_out = w.shape[1]
    program = plan_program(m, d_in, [GemmSpec(d_out)], seg_width=SEG_WIDTH,
                           block_rows=block_rows,
                           elem_bytes=jnp.dtype(x.dtype).itemsize)
    pool = VirtualPool.alloc(program.spec(x.dtype))
    pool = pool.stage_rows(x, program.input_ptr)
    pool = execute(program, pool, [(w, b)], backend="pallas",
                   interpret=pallas_interpret())
    y = pool.fetch_rows(program.output_ptr, m, d_out)
    op = program.ops[0]
    info = dict(n_segments=program.n_segments, in_ptr=op.in_ptr,
                out_ptr=op.out_ptr, delta=op.delta,
                pool_bytes=program.physical_pool_bytes,
                naive_bytes=program.naive_bytes)
    return y, info


def fused_mlp(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
              w_down: jax.Array, *, block_rows: int = 8, ff_tile: int = 512,
              gated: bool = True, residual: bool = True,
              activation: str = "gelu") -> jax.Array:
    """In-place fused MLP through a fresh ring pool (delta == 0)."""
    m, d = x.shape
    program = plan_program(
        m, d,
        [FusedMLPSpec(d_ff=w_up.shape[1], gated=gated, residual=residual,
                      activation=activation, ff_tile=ff_tile)],
        seg_width=SEG_WIDTH, block_rows=block_rows,
        elem_bytes=jnp.dtype(x.dtype).itemsize)
    pool = VirtualPool.alloc(program.spec(x.dtype))
    pool = pool.stage_rows(x, program.input_ptr)
    pool = execute(program, pool, [(w_gate, w_up, w_down)],
                   backend="pallas", interpret=pallas_interpret())
    return pool.fetch_rows(program.output_ptr, m, d)


def decode_attention(q: jax.Array, k_ring: jax.Array, v_ring: jax.Array,
                     seq_len: jax.Array, *, window: int, block: int = 128,
                     softcap: float | None = None) -> jax.Array:
    return ring_decode_attention(q, k_ring, v_ring,
                                 jnp.asarray(seq_len, jnp.int32),
                                 window=window, block=block, softcap=softcap,
                                 interpret=pallas_interpret())


__all__ = [
    "segment_gemm", "fused_mlp", "decode_attention", "ring_cache_update",
    "ring_gemm", "ring_fused_mlp", "ring_decode_attention",
    "aligned_pool_geometry", "stage_rows", "fetch_rows", "SEG_WIDTH", "ref",
    "segments_for",
]
