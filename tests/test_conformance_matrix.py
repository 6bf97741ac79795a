"""THE backend x dtype conformance matrix.

One cell for EVERY (executable op kind, backend, dtype) combination —
no silent skips: unsupported cells are explicit ``xfail(strict=True)``
entries in :data:`UNSUPPORTED`, so the support surface is
machine-readable.  Cells assert

  * ``jnp`` / ``pallas`` fp32  — allclose against ``kernels/ref.py``,
  * ``jnp`` / ``pallas`` int8  — BITWISE equality against the
    ``kernels/ref.py`` ``*_q_ref`` oracles (integer math is exact),
  * ``sim``                    — the clobber-oracle certificate (the sim
    backend replays the schedule; it has no numeric output).

This file subsumes the previous ad-hoc per-op backend-equivalence
copies (``test_program.test_cross_backend_equivalence``,
``test_program.test_elementwise_op_runs_on_all_backends``,
``test_quant_execution.test_int8_gemm_scan_blocks_match_pallas``).

A second grid pins the new ``conv_k2d`` kind across its whole envelope:
k in {3, 5} x stride in {1, 2} x padding in {same, valid}.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.executors import execute, run_program
from repro.core.graph_planner import ModuleConfig
from repro.core.program import (AvgPoolSpec, ConvDWSpec, ConvK2DSpec,
                                ConvPWSpec, ConvStreamSpec,
                                ElementwiseSpec, EXECUTABLE_KINDS,
                                FusedMLPSpec, GemmSpec, GRUCellSpec,
                                IBModuleSpec, ResidualAddSpec,
                                plan_program)
from repro.graph.run import _quantize_net
from repro.kernels import ref
from repro.quant import QParams, quantize

KEY = jax.random.PRNGKey(0)
BACKENDS = ("sim", "jnp", "pallas")
DTYPES = ("float32", "int8")

# The machine-readable unsupported surface.  A cell listed here MUST
# fail (strict xfail) — if an int8 path is ever added, the entry has to
# be removed, keeping this table honest.
UNSUPPORTED = {
    ("fused_mlp", "int8"):
        "no int8 fused-MLP path — d_ff tiles accumulate in fp32 only",
    ("elementwise", "int8"):
        "gelu/silu have no single-multiplier int8 form "
        "(relu rides on the producing op instead)",
    ("ib_fused", "int8"):
        "int8 requires unfused module lowering (fused_exec=False)",
}


@dataclasses.dataclass
class Cell:
    program: object
    params: list
    x: jax.Array
    ref_fp32: object          # (x, params) -> [out_rows, d_out]
    ref_int8: object          # (x_q, qparams, ops) -> int8 array


def _rand(key, *shape):
    return jax.random.normal(key, shape)


def _cell_gemm() -> Cell:
    m, d_in, d_out = 8, 160, 96
    prog = plan_program(m, d_in, [GemmSpec(d_out, activation="relu")],
                        block_rows=4)
    k1, k2, k3 = jax.random.split(KEY, 3)
    w = _rand(k1, d_in, d_out) / d_in ** 0.5
    b = _rand(k2, d_out) / 8
    return Cell(
        prog, [(w, b)], _rand(k3, m, d_in),
        lambda x, p: ref.elementwise_ref(
            ref.gemm_ref(x, p[0][0], p[0][1]), "relu"),
        lambda x_q, qp, ops: ref.gemm_q_ref(x_q, *qp[0],
                                            activation="relu"))


def _cell_conv_pw(h=6, w_=5, c_in=160, c_out=64, s=2) -> Cell:
    prog = plan_program(h * w_, c_in,
                        [ConvPWSpec(h, w_, c_in, c_out, stride=s,
                                    activation="relu")], block_rows=1)
    k1, k2, k3 = jax.random.split(KEY, 3)
    w = _rand(k1, c_in, c_out) / c_in ** 0.5
    b = _rand(k2, c_out) / 8

    def fp32(x, p):
        y = ref.conv_pw_ref(x.reshape(h, w_, c_in), p[0][0], p[0][1],
                            stride=s, activation="relu")
        return y.reshape(-1, c_out)

    def int8(x_q, qp, ops):
        y = ref.conv_pw_q_ref(x_q.reshape(h, w_, c_in), *qp[0], stride=s,
                              activation="relu")
        return y.reshape(-1, c_out)

    return Cell(prog, [(w, b)], _rand(k3, h * w_, c_in), fp32, int8)


def _cell_conv_dw(h=6, w_=6, c=48, rs=3, s=2) -> Cell:
    prog = plan_program(h * w_, c,
                        [ConvDWSpec(h, w_, c, rs=rs, stride=s,
                                    activation="relu")], block_rows=1)
    k1, k2, k3 = jax.random.split(KEY, 3)
    w = _rand(k1, rs, rs, c) / rs
    b = _rand(k2, c) / 8

    def fp32(x, p):
        y = ref.conv_dw_ref(x.reshape(h, w_, c), p[0][0], p[0][1],
                            stride=s, activation="relu")
        return y.reshape(-1, c)

    def int8(x_q, qp, ops):
        y = ref.conv_dw_q_ref(x_q.reshape(h, w_, c), *qp[0], stride=s,
                              activation="relu")
        return y.reshape(-1, c)

    return Cell(prog, [(w, b)], _rand(k3, h * w_, c), fp32, int8)


def _cell_conv_k2d(h=7, w_=6, c_in=24, c_out=40, k=3, s=2) -> Cell:
    prog = plan_program(h * w_, c_in,
                        [ConvK2DSpec(h, w_, c_in, c_out, k=k, stride=s,
                                     activation="relu")], block_rows=1)
    k1, k2, k3 = jax.random.split(KEY, 3)
    w = _rand(k1, k, k, c_in, c_out) / (k * k * c_in) ** 0.5
    b = _rand(k2, c_out) / 8

    def fp32(x, p):
        y = ref.conv_k2d_ref(x.reshape(h, w_, c_in), p[0][0], p[0][1],
                             stride=s, activation="relu")
        return y.reshape(-1, c_out)

    def int8(x_q, qp, ops):
        y = ref.conv_k2d_q_ref(x_q.reshape(h, w_, c_in), *qp[0],
                               stride=s, activation="relu")
        return y.reshape(-1, c_out)

    return Cell(prog, [(w, b)], _rand(k3, h * w_, c_in), fp32, int8)


def _cell_add(h=4, w_=4, c=32) -> Cell:
    prog = plan_program(h * w_, c,
                        [ConvPWSpec(h, w_, c, c, activation=None),
                         ResidualAddSpec(1, activation="relu")],
                        block_rows=1)
    k1, k2 = jax.random.split(KEY)
    w = _rand(k1, c, c) / c ** 0.5
    zb = jnp.zeros((c,))

    def fp32(x, p):
        y = ref.conv_pw_ref(x.reshape(h, w_, c), p[0][0], zb)
        return ref.add_ref(y.reshape(-1, c), x, activation="relu")

    def int8(x_q, qp, ops):
        y = ref.conv_pw_q_ref(x_q.reshape(h, w_, c), *qp[0])
        return ref.add_q_ref(y.reshape(-1, c), x_q, *qp[1],
                             activation="relu")

    return Cell(prog, [(w, None), None], _rand(k2, h * w_, c), fp32,
                int8)


def _cell_pool_avg(h=5, w_=4, c=32) -> Cell:
    prog = plan_program(h * w_, c, [AvgPoolSpec(h, w_, c)], block_rows=1)
    return Cell(
        prog, [None], _rand(KEY, h * w_, c),
        lambda x, p: ref.avgpool_ref(x.reshape(h, w_, c)),
        lambda x_q, qp, ops: ref.avgpool_q_ref(x_q.reshape(h, w_, c),
                                               *qp[0]))


def _cell_fused_mlp() -> Cell:
    m, d, f = 8, 256, 512
    prog = plan_program(m, d,
                        [FusedMLPSpec(f, gated=True, residual=True,
                                      activation="gelu", ff_tile=256)],
                        block_rows=8)
    k1, k2, k3, k4 = jax.random.split(KEY, 4)
    wg = _rand(k1, d, f) / d ** 0.5
    wu = _rand(k2, d, f) / d ** 0.5
    wd = _rand(k3, f, d) / f
    return Cell(
        prog, [(wg, wu, wd)], _rand(k4, m, d),
        lambda x, p: ref.fused_mlp_ref(x, *p[0], gated=True,
                                       residual=True, activation="gelu"),
        None)


def _cell_elementwise() -> Cell:
    m, d = 8, 256
    prog = plan_program(m, d, [ElementwiseSpec("gelu")], block_rows=8)
    return Cell(prog, [None], _rand(KEY, m, d),
                lambda x, p: ref.elementwise_ref(x, "gelu"), None)


def _cell_ib_fused() -> Cell:
    cfg = ModuleConfig(name="cell", hw=6, c_in=16, c_mid=24, c_out=16,
                       rs=3, strides=(1, 1, 1))
    prog = plan_program(cfg.hw * cfg.hw, cfg.c_in, [IBModuleSpec(cfg)],
                        block_rows=1)
    k1, k2, k3, k4 = jax.random.split(KEY, 4)
    w1 = _rand(k1, cfg.c_in, cfg.c_mid) / cfg.c_in ** 0.5
    wd = _rand(k2, cfg.rs, cfg.rs, cfg.c_mid) / cfg.rs
    w2 = _rand(k3, cfg.c_mid, cfg.c_out) / cfg.c_mid ** 0.5

    def fp32(x, p):
        y = ref.ib_fused_ref(x.reshape(cfg.hw, cfg.hw, cfg.c_in), *p[0],
                             residual=True)
        return y.reshape(-1, cfg.c_out)

    return Cell(prog, [(w1, wd, w2)],
                _rand(k4, cfg.hw * cfg.hw, cfg.c_in), fp32, None)


def _cell_conv_stream() -> Cell:
    """One stream step from the zero (reset) state: the fresh pool's
    zero-initialized window IS the reference conv's zero padding, so a
    single ``run_program`` call is a well-defined matrix cell."""
    h_win, w_, c_in, c_out, hop = 6, 5, 24, 32, 2
    prog = plan_program(hop * w_, c_in,
                        [ConvStreamSpec(h_win, w_, c_in, c_out, k=3,
                                        stride=1, hop=hop,
                                        activation="relu")], block_rows=1)
    k1, k2, k3 = jax.random.split(KEY, 3)
    w = _rand(k1, 3, 3, c_in, c_out) / (9 * c_in) ** 0.5
    b = _rand(k2, c_out) / 8

    def fp32(x, p):
        state = jnp.zeros((h_win, w_, c_in))
        y, _ = ref.conv_stream_ref(state, x.reshape(hop, w_, c_in),
                                   p[0][0], p[0][1], activation="relu")
        return y.reshape(-1, c_out)

    def int8(x_q, qp, ops):
        state_q = jnp.zeros((h_win, w_, c_in), jnp.int8)
        y, _ = ref.conv_stream_q_ref(state_q,
                                     x_q.reshape(hop, w_, c_in),
                                     *qp[0], activation="relu")
        return y.reshape(-1, c_out)

    return Cell(prog, [(w, b)], _rand(k3, hop * w_, c_in), fp32, int8)


def _cell_gru_cell() -> Cell:
    """One recurrence step from the zero hidden state (Q7 zero-point is
    0, so int8 zero state == float zero state)."""
    d_in, d_h = 40, 32
    prog = plan_program(1, d_in, [GRUCellSpec(d_h)], block_rows=1)
    k1, k2, k3, k4 = jax.random.split(KEY, 4)
    w = _rand(k1, d_in, 3 * d_h) / d_in ** 0.5
    u = _rand(k2, d_h, 3 * d_h) / d_h ** 0.5
    b = _rand(k3, 3 * d_h) / 8

    def fp32(x, p):
        h = jnp.zeros((1, d_h))
        return ref.gru_cell_ref(x, h, *p[0])

    def int8(x_q, qp, ops):
        h_q7 = jnp.zeros((1, d_h), jnp.int8)
        return ref.gru_cell_q_ref(x_q, h_q7, *qp[0])

    return Cell(prog, [(w, u, b)], _rand(k4, 1, d_in), fp32, int8)


CELL_BUILDERS = {
    "gemm": _cell_gemm,
    "conv_pw": _cell_conv_pw,
    "conv_dw": _cell_conv_dw,
    "conv_k2d": _cell_conv_k2d,
    "add": _cell_add,
    "pool_avg": _cell_pool_avg,
    "fused_mlp": _cell_fused_mlp,
    "elementwise": _cell_elementwise,
    "ib_fused": _cell_ib_fused,
    "conv_stream": _cell_conv_stream,
    "gru_cell": _cell_gru_cell,
}


def test_matrix_covers_every_executable_kind():
    """Adding an executable op kind without a matrix cell is an error —
    the conformance surface may never silently shrink."""
    assert set(CELL_BUILDERS) == set(EXECUTABLE_KINDS)
    assert set(k for k, _ in UNSUPPORTED) <= set(EXECUTABLE_KINDS)


def _grid():
    cells = []
    for kind in EXECUTABLE_KINDS:
        for backend in BACKENDS:
            for dtype in DTYPES:
                marks = ()
                reason = UNSUPPORTED.get((kind, dtype))
                if reason is not None:
                    marks = pytest.mark.xfail(reason=reason, strict=True)
                cells.append(pytest.param(kind, backend, dtype,
                                          marks=marks,
                                          id=f"{kind}-{backend}-{dtype}"))
    return cells


def _tol(expected):
    scale = float(np.abs(np.asarray(expected)).max()) or 1.0
    return dict(rtol=3e-4, atol=3e-5 * scale)


def _assert_int8_cell(cell: Cell, backend: str, **kw) -> None:
    """Quantize the cell; sim certifies, jnp / pallas equal the
    ``*_q_ref`` oracle bitwise."""
    qnet = _quantize_net(cell.program, cell.params)
    if backend == "sim":
        sim = execute(qnet.program, backend="sim")
        assert sim.peak_live <= qnet.program.n_segments
        return
    x_q = quantize(cell.x, QParams(scale=qnet.in_scale))
    y, _ = run_program(qnet.program, x_q, qnet.qparams, backend=backend,
                       **kw)
    expected = cell.ref_int8(x_q, qnet.qparams, qnet.program.ops)
    assert y.dtype == np.int8
    np.testing.assert_array_equal(np.asarray(y), np.asarray(expected))


@pytest.mark.parametrize("kind,backend,dtype", _grid())
def test_conformance_cell(kind, backend, dtype):
    cell = CELL_BUILDERS[kind]()
    if dtype == "int8":
        # unsupported kinds raise here — the strict-xfail contract
        _assert_int8_cell(cell, backend)
    else:
        if backend == "sim":
            sim = execute(cell.program, backend="sim")
            assert sim.peak_live <= cell.program.n_segments
            return
        y, _ = run_program(cell.program, cell.x, cell.params,
                           backend=backend)
        expected = cell.ref_fp32(cell.x, cell.params)
        np.testing.assert_allclose(np.asarray(y), np.asarray(expected),
                                   **_tol(expected))


# ---------------------------------------------------------------------------
# Execution-granularity blocking (kernel_block_rows): every kind x
# block x dtype on the pallas backend — blocking must not move a bit.
# ---------------------------------------------------------------------------

#: 1 = the fine-grained certified schedule; 8 = the Target default
#: (``Target.kernel_block_rows``).
KERNEL_BLOCKS = (1, 8)


def _blocked_grid():
    cells = []
    for kind in EXECUTABLE_KINDS:
        for block in KERNEL_BLOCKS:
            for dtype in DTYPES:
                marks = ()
                reason = UNSUPPORTED.get((kind, dtype))
                if reason is not None:
                    marks = pytest.mark.xfail(reason=reason, strict=True)
                cells.append(pytest.param(
                    kind, block, dtype, marks=marks,
                    id=f"{kind}-rb{block}-{dtype}"))
    return cells


@pytest.mark.parametrize("kind,block,dtype", _blocked_grid())
def test_blocked_pallas_cell(kind, block, dtype):
    """The pallas backend at execution granularity 1 and the target
    default 8 both agree with the ref oracle (bitwise for int8) —
    kernel blocking is invisible to the numbers."""
    cell = CELL_BUILDERS[kind]()
    if dtype == "int8":
        _assert_int8_cell(cell, "pallas", kernel_block_rows=block)
    else:
        y, _ = run_program(cell.program, cell.x, cell.params,
                           backend="pallas", kernel_block_rows=block)
        expected = cell.ref_fp32(cell.x, cell.params)
        np.testing.assert_allclose(np.asarray(y), np.asarray(expected),
                                   **_tol(expected))


def test_blocked_conv_pw_multi_row_engages():
    """A stride-1 pointwise conv whose geometry satisfies the driver's
    divisor rule: the multi-row path (row_block > 1) must actually
    engage AND stay bitwise-identical to the ref oracle for int8."""
    from repro.core.executors import _pw_row_block

    h, w_, c_in, c_out = 8, 4, 96, 64
    prog = plan_program(h * w_, c_in,
                        [ConvPWSpec(h, w_, c_in, c_out,
                                    activation="relu")], block_rows=1)
    op = next(o for o in prog.ops if o.kind == "conv_pw")
    rb = _pw_row_block(op, prog.n_segments, op.in_ptr, prog.seg_width, 8)
    assert rb > 1, "geometry was chosen so blocking engages"

    k1, k2, k3 = jax.random.split(KEY, 3)
    w = _rand(k1, c_in, c_out) / c_in ** 0.5
    b = _rand(k2, c_out) / 8
    x = _rand(k3, h * w_, c_in)
    expected = ref.conv_pw_ref(x.reshape(h, w_, c_in), w, b,
                               activation="relu").reshape(-1, c_out)
    y, _ = run_program(prog, x, [(w, b)], backend="pallas",
                       kernel_block_rows=8)
    np.testing.assert_allclose(np.asarray(y), np.asarray(expected),
                               **_tol(expected))

    qnet = _quantize_net(prog, [(w, b)])
    x_q = quantize(x, QParams(scale=qnet.in_scale))
    expected_q = ref.conv_pw_q_ref(x_q.reshape(h, w_, c_in),
                                   *qnet.qparams[0], activation="relu") \
        .reshape(-1, c_out)
    for block in KERNEL_BLOCKS:
        y_q, _ = run_program(qnet.program, x_q, qnet.qparams,
                             backend="pallas", kernel_block_rows=block)
        np.testing.assert_array_equal(np.asarray(y_q),
                                      np.asarray(expected_q))


def test_batched_vmap_pallas_cell():
    """A leading batch dimension vmapped straight over the blocked
    pallas path: every lane equals the single-sample run."""
    cell = CELL_BUILDERS["gemm"]()

    def run_one(xi):
        y, _ = run_program(cell.program, xi, cell.params,
                           backend="pallas", kernel_block_rows=8)
        return y

    xb = jnp.stack([cell.x, cell.x * 0.5, -cell.x])
    yb = jax.vmap(run_one)(xb)
    assert yb.shape[0] == 3
    for i in range(3):
        np.testing.assert_allclose(np.asarray(yb[i]),
                                   np.asarray(run_one(xb[i])),
                                   rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# Odd image widths (3, 7 and 49 pixels) and stride 2: the strided pixel
# loads of pw / dw / k2d and the whole-row blocks of add / avgpool, int8
# bitwise against the ref oracles (1- and 2-segment pixels).
# ---------------------------------------------------------------------------

ODD_WIDTH_CELLS = {
    "conv_pw-s2": lambda w_: _cell_conv_pw(h=4, w_=w_, c_in=24, c_out=40),
    "conv_dw-s1": lambda w_: _cell_conv_dw(h=3, w_=w_, c=24, s=1),
    "conv_dw-s2": lambda w_: _cell_conv_dw(h=4, w_=w_, c=136, s=2),
    "conv_k2d-s2": lambda w_: _cell_conv_k2d(h=4, w_=w_, c_in=3, c_out=16),
    "add": lambda w_: _cell_add(h=2, w_=w_, c=48),
    "pool_avg": lambda w_: _cell_pool_avg(h=3, w_=w_, c=160),
}


@pytest.mark.parametrize("w_", (3, 7, 49))
@pytest.mark.parametrize("case", sorted(ODD_WIDTH_CELLS))
def test_odd_width_int8_bitwise(case, w_):
    cell = ODD_WIDTH_CELLS[case](w_)
    for backend in ("jnp", "pallas"):
        _assert_int8_cell(cell, backend)


# ---------------------------------------------------------------------------
# conv_k2d envelope: k x stride x padding across backends and dtypes.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", (3, 5))
@pytest.mark.parametrize("stride", (1, 2))
@pytest.mark.parametrize("padding", ("same", "valid"))
@pytest.mark.parametrize("dtype", DTYPES)
def test_conv_k2d_envelope(k, stride, padding, dtype):
    """Every (k, stride, padding) geometry: sim certifies, jnp and
    pallas agree with the ref oracle (bitwise for int8)."""
    h, w_, c_in, c_out = 9, 8, 24, 32
    prog = plan_program(h * w_, c_in,
                        [ConvK2DSpec(h, w_, c_in, c_out, k=k,
                                     stride=stride, padding=padding,
                                     activation="relu")], block_rows=1)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(k * 10 + stride), 3)
    w = _rand(k1, k, k, c_in, c_out) / (k * k * c_in) ** 0.5
    b = _rand(k2, c_out) / 8
    x = _rand(k3, h * w_, c_in)
    sim = execute(prog, backend="sim")
    assert sim.peak_live <= prog.n_segments
    if dtype == "float32":
        expected = ref.conv_k2d_ref(x.reshape(h, w_, c_in), w, b,
                                    stride=stride, padding=padding,
                                    activation="relu") \
            .reshape(-1, c_out)
        for backend in ("jnp", "pallas"):
            y, _ = run_program(prog, x, [(w, b)], backend=backend)
            np.testing.assert_allclose(np.asarray(y),
                                       np.asarray(expected),
                                       **_tol(expected))
    else:
        qnet = _quantize_net(prog, [(w, b)])
        x_q = quantize(x, QParams(scale=qnet.in_scale))
        expected = ref.conv_k2d_q_ref(x_q.reshape(h, w_, c_in),
                                      *qnet.qparams[0], stride=stride,
                                      padding=padding,
                                      activation="relu") \
            .reshape(-1, c_out)
        for backend in ("jnp", "pallas"):
            y, _ = run_program(qnet.program, x_q, qnet.qparams,
                               backend=backend)
            np.testing.assert_array_equal(np.asarray(y),
                                          np.asarray(expected))


def test_conv_k2d_tight_delta_clobbers_at_minus_one():
    """The k-halo frontier widens Eq. (1): the solved offset is exact —
    shrinking it by one segment must clobber in the oracle."""
    from repro.core.pool import PoolClobberError

    spec = ConvK2DSpec(9, 8, 24, 32, k=5, stride=1, padding="same")
    safe = plan_program(72, 24, [spec], block_rows=None)
    execute(safe, backend="sim")
    tight = plan_program(72, 24, [spec], block_rows=None, delta_slack=1)
    with pytest.raises(PoolClobberError):
        execute(tight, backend="sim")
