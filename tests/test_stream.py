"""Streaming inference subsystem acceptance (DESIGN.md §14).

Persistent temporal state on the segment ring — the fourth lifetime
class.  Pinned here:

  * graph conversion round-trip (``to_streaming`` / ``to_full``),
  * >= 8 consecutive DS-CNN frames on sim (zero clobbers), jnp and
    pallas, with int8 BITWISE jnp == pallas agreement per step,
  * streaming-vs-full-recompute equivalence: once the window has
    filled, every stream step reproduces the one-shot net on the
    current window (bitwise in int8, exact in fp32) when the twin
    shares the stream's weights and quantization,
  * the static certificate's per-step counters times N equal the sim
    oracle's N-step counters (the multi-step horizon proof is not
    advisory — it predicts the byte traffic exactly),
  * the state liveness diagnostics VMCU211/212/213 fire on hand-broken
    plans, in agreement with the sim oracle where it can see the bug,
  * multi-state chains (conv_stream window + GRU hidden vector) track
    the kernels/ref.py oracles step by step in fp32 and bitwise int8,
  * the streaming DS-CNN state + frame ring fits the 128 KB
    cortex-m4 budget,
  * int8 calibration of a streaming net on the full windows of its
    clip: the one-shot twin's scales on those windows bit for bit, and
    one-shot calibration unchanged (pinned).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.analysis.verifier import verify_program
from repro.core.executors import execute, run_program
from repro.core.pool import PoolClobberError
from repro.core.program import (AvgPoolSpec, ConvStreamSpec, GRUCellSpec,
                                plan_program)
from repro.core.vpool import VirtualPool
from repro.graph import QuantizedNet, build_ad_autoencoder, build_ds_cnn
from repro.graph.run import _quantize_net
from repro.kernels import ref
from repro.quant import QParams, quantize
from repro.stream import to_full, to_streaming

KEY = jax.random.PRNGKey(7)
N_FRAMES = 8
SRAM_CORTEX_M4 = 128 * 1024


# ---------------------------------------------------------------------------
# Graph conversion.
# ---------------------------------------------------------------------------

def test_to_streaming_round_trip():
    g = build_ds_cnn()
    gs = to_streaming(g)
    assert gs.name == "ds-cnn-stream"
    stems = [n for n in gs.nodes.values() if n.kind == "conv_stream"]
    assert len(stems) == 1
    win = g.nodes[g.input_id()].out
    assert stems[0].h_win == win.h and stems[0].hop == 1
    frame = gs.nodes[gs.input_id()].out
    assert (frame.h, frame.w, frame.d) == (1, win.w, win.d)
    assert to_streaming(gs) is gs                    # idempotent
    gf = to_full(gs)
    assert gf.name == g.name
    assert [n.kind for n in gf.nodes.values()] \
        == [n.kind for n in g.nodes.values()]
    assert gf.nodes[gf.input_id()].out == win


def test_to_streaming_rejects_non_conv_stem():
    with pytest.raises(ValueError, match="conv_k2d stem"):
        to_streaming(build_ad_autoencoder())


def test_to_full_requires_single_stream_stem():
    with pytest.raises(ValueError, match="conv_stream"):
        to_full(build_ds_cnn())


# ---------------------------------------------------------------------------
# DS-CNN streaming compile: >= 8 consecutive frames on every backend.
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def ds_int8():
    return repro.compile("ds-cnn", "cortex-m4", dtype="int8",
                         streaming=True)


@pytest.fixture(scope="module")
def ds_fp32():
    return repro.compile("ds-cnn", "host-sim", streaming=True)


def _frames(program, n, key=KEY):
    return jax.random.normal(
        key, (n, program.ops[0].rows_in, program.in_dim))


def test_stream_sim_n_frames_zero_clobbers(ds_int8):
    """Eight consecutive frames through the clobber oracle on ONE
    persistent pool — state survives every step or the sim raises."""
    sess = ds_int8.stream(backend="sim")
    for _ in range(N_FRAMES):
        counters = sess.step()
    assert counters["steps"] == N_FRAMES
    assert counters["peak_live"] <= ds_int8.qnet.program.n_segments


def test_stream_int8_jnp_pallas_bitwise(ds_int8):
    prog = ds_int8.qnet.program
    frames_q = quantize(_frames(prog, N_FRAMES),
                        QParams(scale=ds_int8.qnet.in_scale))
    sj = ds_int8.stream(backend="jnp")
    sp = ds_int8.stream(backend="pallas")
    for f in frames_q:
        y_j, y_p = sj.step(f), sp.step(f)
        assert y_j.dtype == jnp.int8
        np.testing.assert_array_equal(np.asarray(y_j), np.asarray(y_p))
    # ... and the whole ring (frame extent AND state) agrees bitwise
    np.testing.assert_array_equal(np.asarray(sj._pool.array),
                                  np.asarray(sp._pool.array))


def test_stream_fp32_jnp_pallas_allclose(ds_fp32):
    frames = _frames(ds_fp32.program, N_FRAMES)
    sj = ds_fp32.stream(backend="jnp")
    sp = ds_fp32.stream(backend="pallas")
    for f in frames:
        y_j, y_p = sj.step(f), sp.step(f)
        np.testing.assert_allclose(np.asarray(y_j), np.asarray(y_p),
                                   rtol=3e-4, atol=1e-5)


def test_stream_reset_restarts_from_zero_state(ds_int8):
    prog = ds_int8.qnet.program
    frames_q = quantize(_frames(prog, 3),
                        QParams(scale=ds_int8.qnet.in_scale))
    sess = ds_int8.stream(backend="jnp")
    first = [np.asarray(sess.step(f)) for f in frames_q]
    assert sess.steps == 3
    sess.reset()
    assert sess.steps == 0
    again = [np.asarray(sess.step(f)) for f in frames_q]
    for a, b in zip(first, again):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# Streaming == full recompute, once the window has filled.
# ---------------------------------------------------------------------------

def _window_frames(x, prog):
    """Split the full one-shot input into per-step frames."""
    rows = prog.ops[0].rows_in
    return x.reshape(-1, rows, prog.in_dim)


def test_stream_matches_one_shot_int8_bitwise(ds_int8):
    """After ``h_win`` frames the stream output equals the one-shot
    DS-CNN on the same window BITWISE — provided the twin shares the
    stream's weights AND quantization (the two compiles calibrate on
    different random inputs, so the qparams are copied, not
    re-derived)."""
    cf = repro.compile("ds-cnn", "cortex-m4", dtype="int8",
                       certify=False)
    qs = ds_int8.qnet
    twin = QuantizedNet(plan=None, program=cf.qnet.program,
                        params=qs.params, qparams=qs.qparams,
                        act_scales=qs.act_scales)
    h_win = ds_int8.program.ops[0].h_in
    x = jax.random.normal(KEY, (twin.program.in_rows,
                                twin.program.in_dim))
    x_q = quantize(x, QParams(scale=qs.in_scale))
    y_full, _ = run_program(twin.program, x_q, twin.qparams,
                            backend="jnp")
    sess = ds_int8.stream(backend="jnp")
    y_stream = sess.run(_window_frames(x_q, qs.program))
    assert sess.steps == h_win
    np.testing.assert_array_equal(np.asarray(y_stream),
                                  np.asarray(y_full))


def test_stream_matches_one_shot_fp32(ds_fp32):
    cf = repro.compile("ds-cnn", "host-sim", certify=False)
    params = ds_fp32.ensure_params()   # shared weights, aligned op lists
    h_win = ds_fp32.program.ops[0].h_in
    x = jax.random.normal(KEY, (cf.program.in_rows, cf.program.in_dim))
    y_full, _ = run_program(cf.program, x, params, backend="jnp")
    sess = ds_fp32.stream(backend="jnp")
    y_stream = sess.run(_window_frames(x, ds_fp32.program))
    assert sess.steps == h_win
    np.testing.assert_allclose(np.asarray(y_stream),
                               np.asarray(y_full), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# The multi-step certificate: static counters x N == sim counters(N).
# ---------------------------------------------------------------------------

def test_static_certificate_predicts_n_step_sim_counters(ds_int8):
    prog = ds_int8.qnet.program
    res = verify_program(prog)
    assert res.safe is True
    st = res.stats
    assert st["stream_horizon"] == "unbounded"
    assert st["n_states"] == 1
    state = st["state_segments"]
    assert state == sum(op.state_segments for op in prog.ops)
    sess = ds_int8.stream(backend="sim")
    for k in range(1, N_FRAMES + 1):
        c = sess.step()
        # the state is pre-written once; every step then re-reads and
        # rewrites it, so the per-step static stats add linearly
        assert c["reads"] == k * st["reads"]
        assert c["writes"] == state + k * (st["writes"] - state)
        assert c["peak_live"] == st["peak_live"]


def test_compile_certificate_carries_stream_horizon(ds_int8):
    cert = ds_int8.certificate
    assert cert["clobbers"] == 0
    assert cert["stream_horizon"] == "unbounded"
    assert cert["n_states"] == 1 and cert["state_segments"] > 0


def test_stream_state_fits_cortex_m4_budget(ds_int8):
    """Acceptance: frame ring + persistent state together fit the
    paper's 128 KB board, and the state is wrap-free above the frame
    program's linear extent."""
    prog = ds_int8.qnet.program
    assert prog.physical_pool_bytes <= SRAM_CORTEX_M4
    sess = ds_int8.stream(backend="sim")
    assert 0 < sess.state_bytes < prog.physical_pool_bytes
    for op in prog.ops:
        if op.state_segments:
            assert op.state_ptr + op.state_segments <= prog.n_segments
            for other in prog.ops:
                # frame traffic lives strictly below every state region
                assert other.in_ptr + other.in_segments <= op.state_ptr
                assert other.out_ptr + other.out_segments <= op.state_ptr


# ---------------------------------------------------------------------------
# State liveness diagnostics: VMCU211 / 212 / 213.
# ---------------------------------------------------------------------------

def _stream_prog():
    return plan_program(10, 24,
                        [ConvStreamSpec(6, 5, 24, 32, k=3, hop=2,
                                        activation="relu")], block_rows=1)


def _mutate_op0(prog, **kw):
    ops = list(prog.ops)
    ops[0] = dataclasses.replace(ops[0], **kw)
    return dataclasses.replace(prog, ops=tuple(ops))


def test_vmcu211_state_clobbered_by_frame_traffic():
    prog = _stream_prog()
    bad = _mutate_op0(prog, state_ptr=prog.ops[0].out_ptr)
    res = verify_program(bad)
    assert res.safe is False
    assert res.errors[0].code == "VMCU211"
    # agreement: the sim oracle sees the same clobber
    with pytest.raises(PoolClobberError):
        execute(bad, backend="sim")


def test_vmcu212_wrong_state_extent():
    prog = _stream_prog()
    bad = _mutate_op0(prog, state_segments=prog.ops[0].state_segments - 1)
    res = verify_program(bad)
    assert res.safe is False
    assert res.errors[0].code == "VMCU212"


def test_vmcu213_state_wraps_ring():
    prog = _stream_prog()
    bad = _mutate_op0(prog, state_ptr=prog.n_segments - 1)
    res = verify_program(bad)
    assert res.safe is False
    assert res.errors[0].code == "VMCU213"


def test_stream_prog_static_stats_match_sim_exactly():
    """The small synthetic stream program, adversarially: static stats
    equal the sim pool counters bit for bit (the verifier's agreement
    contract extends to the state lifetime class)."""
    prog = _stream_prog()
    res = verify_program(prog)
    assert res.safe is True
    sim = execute(prog, backend="sim")
    assert res.stats["reads"] == sim.reads
    assert res.stats["writes"] == sim.writes
    assert res.stats["peak_live"] == sim.peak_live


# ---------------------------------------------------------------------------
# Multi-state chain: conv_stream window + GRU hidden vector, vs oracle.
# ---------------------------------------------------------------------------

H_WIN, W_, C_IN, C_OUT, HOP, D_H = 6, 5, 8, 16, 2, 24


def _chain_prog():
    return plan_program(HOP * W_, C_IN, [
        ConvStreamSpec(H_WIN, W_, C_IN, C_OUT, k=3, hop=HOP,
                       activation="relu"),
        AvgPoolSpec(H_WIN, W_, C_OUT),
        GRUCellSpec(D_H)], block_rows=1)


def _chain_params():
    k1, k2, k3, k4, k5 = jax.random.split(KEY, 5)
    w = jax.random.normal(k1, (3, 3, C_IN, C_OUT)) / (9 * C_IN) ** 0.5
    b = jax.random.normal(k2, (C_OUT,)) / 8
    wg = jax.random.normal(k3, (C_OUT, 3 * D_H)) / C_OUT ** 0.5
    ug = jax.random.normal(k4, (D_H, 3 * D_H)) / D_H ** 0.5
    bg = jax.random.normal(k5, (3 * D_H,)) / 8
    return [(w, b), None, (wg, ug, bg)]


def test_chain_two_states_certified():
    res = verify_program(_chain_prog())
    assert res.safe is True
    assert res.stats["n_states"] == 2
    assert res.stats["stream_horizon"] == "unbounded"


def test_chain_fp32_tracks_oracle_step_by_step():
    prog, params = _chain_prog(), _chain_params()
    (w, b), _, (wg, ug, bg) = params
    pool = VirtualPool.alloc(prog.spec(jnp.float32))
    state = jnp.zeros((H_WIN, W_, C_IN))
    h = jnp.zeros((1, D_H))
    frames = jax.random.normal(KEY, (5, HOP * W_, C_IN))
    for frame in frames:
        pool = pool.stage_rows(frame, prog.input_ptr)
        pool = execute(prog, pool, params, backend="jnp")
        y = pool.fetch_rows(prog.output_ptr, prog.out_rows, prog.out_dim)
        yc, state = ref.conv_stream_ref(state,
                                        frame.reshape(HOP, W_, C_IN),
                                        w, b, activation="relu")
        h = ref.gru_cell_ref(ref.avgpool_ref(yc), h, wg, ug, bg)
        np.testing.assert_allclose(np.asarray(y), np.asarray(h),
                                   rtol=3e-4, atol=1e-5)


@pytest.mark.parametrize("backend", ("jnp", "pallas"))
def test_chain_int8_bitwise_tracks_q_oracle(backend):
    """Both persistent state classes through the fixed-point pipeline:
    the ring execution stays BITWISE equal to the q-oracles for every
    step — the Q7 hidden state and int8 window survive exactly."""
    prog, params = _chain_prog(), _chain_params()
    qnet = _quantize_net(prog, params)
    qprog = qnet.program
    pool = VirtualPool.alloc(qprog.spec(jnp.int8))
    state_q = jnp.zeros((H_WIN, W_, C_IN), jnp.int8)
    h_q7 = jnp.zeros((1, D_H), jnp.int8)
    frames = jax.random.normal(KEY, (5, HOP * W_, C_IN))
    frames_q = quantize(frames, QParams(scale=qnet.in_scale))
    for frame_q in frames_q:
        pool = pool.stage_rows(frame_q, qprog.input_ptr)
        pool = execute(qprog, pool, qnet.qparams, backend=backend)
        y = pool.fetch_rows(qprog.output_ptr, qprog.out_rows,
                            qprog.out_dim)
        yc, state_q = ref.conv_stream_q_ref(
            state_q, frame_q.reshape(HOP, W_, C_IN), *qnet.qparams[0],
            activation="relu")
        ya = ref.avgpool_q_ref(yc, *qnet.qparams[1])
        h_q7 = ref.gru_cell_q_ref(ya, h_q7, *qnet.qparams[2])
        assert y.dtype == jnp.int8
        np.testing.assert_array_equal(np.asarray(y), np.asarray(h_q7))


# ---------------------------------------------------------------------------
# Session API edges.
# ---------------------------------------------------------------------------

def test_session_requires_streaming_compile():
    cn = repro.compile("ds-cnn", "host-sim", certify=False)
    with pytest.raises(ValueError, match="streaming=True"):
        cn.stream()


def test_session_array_backend_needs_frames(ds_fp32):
    sess = ds_fp32.stream(backend="jnp")
    with pytest.raises(ValueError, match="frame"):
        sess.step()


def test_session_trace_collects_per_step_artifacts(ds_fp32):
    sess = ds_fp32.stream(backend="jnp", trace=True)
    frames = _frames(ds_fp32.program, 2)
    for f in frames:
        sess.step(f)
    assert len(sess.traces) == 2
    for tr in sess.traces:
        assert tr.events, "trace artifact must carry per-op events"


# ---------------------------------------------------------------------------
# Int8 calibration of a streaming net: the clip's full windows.
# ---------------------------------------------------------------------------

# act_scales (float.hex) and a digest of every qparam array of the
# one-shot int8 compiles on cortex-m4 (default params and calibration),
# recorded before streaming calibration slid its clip through the window
PINNED_ONE_SHOT = {
    "ds-cnn": (
        ["0x1.f94e5ab56ad5bp-6", "0x1.87d2a74e9d3a7p-5",
         "0x1.4bb2e5cb972e6p-5", "0x1.394febd7af5ecp-5",
         "0x1.f5f5efdfbf7f0p-6", "0x1.67a2a142850a1p-6",
         "0x1.755f4e9d3a74fp-6", "0x1.719eeddbb76eep-7",
         "0x1.3a12cd9b366cep-7", "0x1.c7e00c183060cp-8",
         "0x1.8e09fbf7efdfcp-9", "0x1.1aa3489122449p-9"],
        "4c2b5daa949852d1b9daa2bdecd99f259de1fbc5d3a104c68a6050c905f2d1e8"),
    "resnet-8": (
        ["0x1.f94e5ab56ad5bp-6", "0x1.b09550a142851p-5",
         "0x1.6ce5eddbb76eep-5", "0x1.ce9cf1e3c78f2p-5",
         "0x1.47b76eddbb76fp-4", "0x1.1b37408102041p-4",
         "0x1.859a4a952a54bp-5", "0x1.637fbf7efdfbfp-4",
         "0x1.a0b1efdfbf7f0p-4", "0x1.48e8408102041p-4",
         "0x1.0702b972e5cb9p-4", "0x1.fb34810204081p-4",
         "0x1.1041bb76eddbbp-3", "0x1.75e770e1c3871p-5",
         "0x1.b4024c993264dp-6"],
        "f77043aabccc825b6d3c01451ebb09ace770b44d3e86b018097a92beb0567c4f"),
}
# largest relative error of a stream step's output against the float
# reference on its window, once the window has filled: full-window
# calibration reads 0.02-0.07 on the CPU, the zero-state calibration
# of each frame 0.90-0.94 (saturated after the stem)
STREAM_TOL = 0.2


def _qparams_digest(qparams) -> str:
    import hashlib

    h = hashlib.sha256()
    for entry in qparams:
        for a in entry:
            a = np.asarray(a)
            h.update(str(a.dtype).encode())
            h.update(str(a.shape).encode())
            h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def _stream_int8(**kw):
    return repro.compile("ds-cnn", "cortex-m4", dtype="int8",
                         streaming=True, certify=False, **kw)


def _clip(stem, n_windows, seed=3):
    return np.random.default_rng(seed).standard_normal(
        (stem.h_in + (n_windows - 1) * stem.hop, stem.w_in, stem.d_in)
    ).astype(np.float32)


def test_stream_calibration_equals_one_shot_on_the_full_windows(ds_int8):
    params = ds_int8.qnet.params
    stem = ds_int8.program.ops[0]
    clip = _clip(stem, 3)
    cs = _stream_int8(params=params, calib=clip)
    windows = np.stack([clip[t:t + stem.h_in] for t in range(3)])
    cf = repro.compile("ds-cnn", "cortex-m4", dtype="int8", certify=False,
                       params=params, calib=windows)
    assert [s.hex() for s in cs.qnet.act_scales] \
        == [s.hex() for s in cf.qnet.act_scales]
    assert _qparams_digest(cs.qnet.qparams) \
        == _qparams_digest(cf.qnet.qparams)


def test_stream_calibration_clip_shorter_than_the_window_raises(ds_int8):
    stem = ds_int8.program.ops[0]
    short = _clip(stem, 1)[:stem.h_in - 1]
    with pytest.raises(ValueError, match=f"at least {stem.h_in}"):
        _stream_int8(params=ds_int8.qnet.params, calib=short)


def test_stream_calibration_without_a_clip_draws_n_calib_windows(
        ds_int8, monkeypatch):
    import repro.graph.run as run_mod

    params = ds_int8.qnet.params
    stem = ds_int8.program.ops[0]
    seen = []
    forward = run_mod.reference_forward

    def spy(program, x, p, **kw):
        seen.append(np.shape(kw.get("window")))
        return forward(program, x, p, **kw)

    monkeypatch.setattr(run_mod, "reference_forward", spy)
    drawn = _stream_int8(params=params, n_calib=3)
    assert seen == [(stem.h_in, stem.w_in, stem.d_in)] * 3
    monkeypatch.setattr(run_mod, "reference_forward", forward)
    clip = jax.random.normal(jax.random.PRNGKey(0),
                             (stem.h_in + 2 * stem.hop, stem.w_in,
                              stem.d_in))
    handed = _stream_int8(params=params, calib=clip)
    assert drawn.qnet.act_scales == handed.qnet.act_scales


def _zero_state_calibration(qnet, clip) -> QuantizedNet:
    """The calibration of each clip row as one step from reset (the
    zero state with the row appended), with the same requant rules."""
    from repro.graph.run import (_act_scales, _requant_tables,
                                 reference_forward)

    prog = qnet.plan.program
    amax = [0.0] * (len(prog.ops) + 1)
    for row in clip:
        taps: list = []
        reference_forward(prog, row, qnet.params, intermediates=taps)
        for i, t in enumerate(taps):
            amax[i] = max(amax[i], float(jnp.abs(t).max()))
    scales = _act_scales(prog, amax)
    return QuantizedNet(plan=qnet.plan, program=qnet.program,
                        params=qnet.params,
                        qparams=_requant_tables(prog, qnet.params, scales),
                        act_scales=scales)


def test_stream_int8_session_tracks_the_float_window_reference(ds_int8):
    from repro.graph.run import reference_forward

    stem = ds_int8.program.ops[0]
    clip = _clip(stem, 2)
    cn = _stream_int8(params=ds_int8.qnet.params, calib=clip)
    sound, zero_state = cn.qnet, _zero_state_calibration(cn.qnet, clip)
    frames = np.random.default_rng(11).standard_normal(
        (stem.h_in + 2, stem.w_in, stem.d_in)).astype(np.float32)
    prog = ds_int8.program
    errs = {}
    for name, qnet in (("sound", sound), ("zero_state", zero_state)):
        cn.qnet = qnet
        sess = cn.stream(backend="jnp")
        worst = 0.0
        for t, f in enumerate(frames):
            y = np.asarray(sess.step(f))
            if t >= stem.h_in - 1:
                win = frames[t - stem.h_in + 1:t + 1]
                ref_y = np.asarray(reference_forward(prog, f, qnet.params,
                                                     window=win))
                worst = max(worst, float(np.linalg.norm(y - ref_y)
                                         / np.linalg.norm(ref_y)))
        errs[name] = worst
    assert errs["sound"] < STREAM_TOL < errs["zero_state"], errs


@pytest.mark.parametrize("net", sorted(PINNED_ONE_SHOT))
def test_one_shot_calibration_is_unchanged_bitwise(net):
    cn = repro.compile(net, "cortex-m4", dtype="int8", certify=False)
    scales, digest = PINNED_ONE_SHOT[net]
    assert [s.hex() for s in cn.qnet.act_scales] == scales
    assert _qparams_digest(cn.qnet.qparams) == digest
