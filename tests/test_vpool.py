"""VirtualPool geometry + staging: alignment edge cases, mid-block wrap,
and the single shared stage/fetch + ceil-div helpers."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (FusedMLPSpec, GemmSpec, PoolSpec, VirtualPool,
                        ceil_div, plan_program, segments_for)
from repro.core.vpool import fetch_rows, stage_rows
from repro.kernels.segment_matmul import SEG_WIDTH, aligned_pool_geometry

KEY = jax.random.PRNGKey(0)


# -- the one ceil-div segment helper ----------------------------------------

def test_segments_for_matches_all_legacy_spellings():
    for d in [1, 31, 32, 33, 127, 128, 129, 300, 4096]:
        for w in [1, 16, 32, 128]:
            assert segments_for(d, w) == -(-d // w) == math.ceil(d / w)
    assert ceil_div(0, 8) == 0
    from repro.core.ring_buffer import _segs as rb_segs
    from repro.kernels.ring import segs as km_segs
    assert rb_segs(300, 128) == km_segs(300) == segments_for(300)


# -- aligned_pool_geometry edge cases ---------------------------------------

def test_aligned_geometry_delta_zero_is_in_place():
    """delta == 0 (square in-place plans): both pointers collapse to 0."""
    n, in_ptr, out_ptr = aligned_pool_geometry(16, 128, 128, 0, 4)
    assert in_ptr == 0 and out_ptr == 0
    assert n >= 16 and n % 4 == 0


def test_aligned_geometry_ragged_dims():
    """Dims not divisible by SEG_WIDTH still produce safe aligned plans."""
    m, d_in, d_out, br = 24, 300, 130, 8
    k_segs, n_segs = segments_for(d_in), segments_for(d_out)
    bk, bn = br * k_segs, br * n_segs
    n, in_ptr, out_ptr = aligned_pool_geometry(m, d_in, d_out, 1, br)
    assert in_ptr % bk == 0 and out_ptr % bn == 0
    assert in_ptr - out_ptr >= 1  # never rounded below the solved delta
    assert n % math.lcm(bk, bn) == 0


@pytest.mark.parametrize("m,d_in,d_out,delta,br", [
    (8, 128, 128, 0, 4), (24, 300, 130, 1, 8), (32, 64, 640, 128, 8),
    (16, 96, 64, 5, 2), (512, 256, 256, 1, 8),
])
def test_aligned_geometry_never_wraps_mid_block(m, d_in, d_out, delta, br):
    """Every contiguous DMA block must fit before the pool's end."""
    k_segs, n_segs = segments_for(d_in), segments_for(d_out)
    bk, bn = br * k_segs, br * n_segs
    n, in_ptr, out_ptr = aligned_pool_geometry(m, d_in, d_out, delta, br)
    for i in range(m // br):
        assert (in_ptr + i * bk) % n + bk <= n, "mid-block wrap (in)"
        assert (out_ptr + i * bn) % n + bn <= n, "mid-block wrap (out)"


def test_program_alignment_never_wraps_mid_block():
    """Same invariant for whole aligned programs (chain + fused MLP)."""
    program = plan_program(16, 256,
                           [GemmSpec(384, "gelu"), GemmSpec(256),
                            FusedMLPSpec(512, ff_tile=256)],
                           block_rows=8)
    program.check_alignment()  # raises on any mid-block wrap
    with pytest.raises(ValueError, match="block_rows=None"):
        plan_program(16, 256, [GemmSpec(384)],
                     block_rows=None).check_alignment()


def test_aligned_delta_never_below_solved_delta():
    """Alignment may only round the offset UP (safety preserved)."""
    for delta in [0, 1, 5, 17, 64, 129]:
        for br in [1, 2, 8]:
            _, in_ptr, out_ptr = aligned_pool_geometry(16, 256, 384,
                                                       delta, br)
            assert in_ptr - out_ptr >= delta


# -- the one stage/fetch implementation -------------------------------------

@pytest.mark.parametrize("d", [128, 64, 300])
def test_stage_fetch_roundtrip(d):
    m, n_seg = 4, 64
    x = jax.random.normal(KEY, (m, d))
    pool = jnp.zeros((n_seg, SEG_WIDTH))
    pool = stage_rows(pool, x, 7 * segments_for(d))
    got = fetch_rows(pool, 7 * segments_for(d), m, d)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(x))


def test_stage_fetch_wraps_modulo():
    """Staging past the end of the ring wraps — the paper's bounds check."""
    m, d, n_seg = 4, 128, 8
    x = jax.random.normal(KEY, (m, d))
    pool = jnp.zeros((n_seg, SEG_WIDTH))
    pool = stage_rows(pool, x, n_seg - 2)  # wraps after two segments
    got = fetch_rows(pool, n_seg - 2, m, d)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(x))
    np.testing.assert_array_equal(np.asarray(pool[0]), np.asarray(x[2]))


def test_virtual_pool_handle():
    spec = PoolSpec(32, 128, jnp.float32)
    vp = VirtualPool.alloc(spec)
    assert vp.spec == spec and vp.nbytes == 32 * 128 * 4
    x = jax.random.normal(KEY, (2, 200))
    got = vp.stage_rows(x, 3).fetch_rows(3, 2, 200)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(x))
    with pytest.raises(ValueError):
        PoolSpec(0, 128)


def test_legacy_aliases_are_the_shared_impl():
    from repro.core import ring_buffer
    from repro.kernels import segment_matmul
    x = jax.random.normal(KEY, (3, 96))
    pool = jnp.zeros((16, SEG_WIDTH))
    a = ring_buffer.write_rows(pool, x, 2, 16)
    b = segment_matmul.stage_rows(pool, x, 2)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(
        np.asarray(ring_buffer.read_rows(a, 2, 3, 96, 16)),
        np.asarray(segment_matmul.fetch_rows(b, 2, 3, 96)))
