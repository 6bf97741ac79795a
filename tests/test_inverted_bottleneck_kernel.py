"""Fused inverted-bottleneck Pallas kernel (paper Fig. 6) vs oracle —
including the in-ring overlap (E overwrites consumed A rows)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.inverted_bottleneck import (inverted_bottleneck_ref,
                                               ring_inverted_bottleneck)

KEY = jax.random.PRNGKey(0)


def _run(H, Cin, Cmid, Cout, res, RS=3, halo_rows=3):
    W = H
    ks = jax.random.split(KEY, 4)
    a = jax.random.normal(ks[0], (H, W, Cin), jnp.float32)
    w1 = jax.random.normal(ks[1], (Cin, Cmid), jnp.float32) / np.sqrt(Cin)
    wd = jax.random.normal(ks[2], (RS, RS, Cmid), jnp.float32) * 0.3
    w2 = jax.random.normal(ks[3], (Cmid, Cout), jnp.float32) / np.sqrt(Cmid)
    seg_w = 128
    in_ptr = halo_rows * W           # Eq.-2 offset, row-aligned
    n_seg = in_ptr + H * W + W
    pool = jnp.zeros((n_seg, seg_w), jnp.float32)
    flat = jnp.pad(a.reshape(H * W, Cin), ((0, 0), (0, seg_w - Cin)))
    pool = pool.at[in_ptr:in_ptr + H * W].set(flat)
    pool = ring_inverted_bottleneck(pool, w1, wd, w2, H=H, W=W, C_in=Cin,
                                    C_mid=Cmid, C_out=Cout, RS=RS,
                                    in_ptr=in_ptr, out_ptr=0,
                                    residual=res, interpret=True)
    got = pool[:H * W, :Cout].reshape(H, W, Cout)
    want = inverted_bottleneck_ref(a, w1, wd, w2, residual=res)
    return got, want


@pytest.mark.parametrize("H,Cin,Cmid,Cout,res", [
    (8, 16, 48, 16, True),     # paper S1 shape family
    (6, 8, 24, 12, False),     # no residual (channel change)
    (10, 16, 32, 16, True),
    (5, 8, 16, 8, True),       # tiny image (paper S5-like)
])
def test_matches_oracle_with_ring_overlap(H, Cin, Cmid, Cout, res):
    got, want = _run(H, Cin, Cmid, Cout, res)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_intermediate_never_materialized():
    """The C_mid-wide tensor B exists only as an RS-row VMEM workspace —
    structurally guaranteed: the pool never holds a C_mid-wide row."""
    H, Cin, Cmid, Cout = 8, 16, 48, 16
    got, want = _run(H, Cin, Cmid, Cout, True)
    # pool segment width (128) < W * Cmid bytes per row proves B>pool rows;
    # the assertion of interest is simply numerical correctness above plus
    # the workspace shape in the kernel (RS rows), checked here statically.
    from repro.kernels import inverted_bottleneck as ib
    import inspect
    src = inspect.getsource(ib.ring_inverted_bottleneck)
    assert "pltpu.VMEM((RS, W + 2 * pad, 1, cm)" in src
