"""The plain reference against the program, on the CPU at small sizes.

The benchmark's reference takes no table from the program; these tests
hand it the program's own int8 tables to show that its integer math is
the program's, bit for bit, and that its float forward is the network
the program plans."""
import hashlib
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from chipbench_testlib import NETS
from chipbench import common, work
from chipbench import reference as ref
from chipbench.modes.stream import window

import repro
from repro.graph.run import reference_forward
from repro.quant import requantize_i32


def _tables(cn) -> dict:
    qn = cn.qnet
    return {"bits": 8, "scales": np.asarray(qn.act_scales, np.float64),
            "tables": [tuple(np.asarray(a, np.int64) for a in qp)
                       for qp in qn.qparams]}


def _compile(net, **kw):
    return repro.compile(net, "cortex-m7", dtype="int8",
                         key=jax.random.PRNGKey(5), **kw)


@pytest.mark.parametrize("net", ["ds-cnn", "resnet-8"])
def test_int8_reference_equals_the_jnp_executor_bitwise(net):
    layers = common.net_layers(NETS[net])
    cn = _compile(net)
    common.check_program(cn.program, layers)
    h, w, c = NETS[net]["widths"]["input"]
    rng = np.random.default_rng(11)
    for _ in range(2):
        x = rng.standard_normal((h * w, c)).astype(np.float32)
        got = np.asarray(cn.run(x, backend="jnp")).reshape(-1)
        want = ref.int_forward(layers, _tables(cn), x.reshape(h, w, c))
        np.testing.assert_array_equal(got, want.astype(np.float32))


def test_int8_reference_equals_the_stream_three_steps_past_the_window():
    layers = common.net_layers(NETS["ds-cnn"])
    cn = repro.compile("ds-cnn", "cortex-m4", dtype="int8", streaming=True,
                       key=jax.random.PRNGKey(6))
    common.check_program(cn.program, layers)
    q = _tables(cn)
    h_win = NETS["ds-cnn"]["widths"]["input"][0]
    frames = np.random.default_rng(12).standard_normal(
        (h_win + 3, 10, 1)).astype(np.float32)
    session = cn.stream(backend="jnp")
    for t, frame in enumerate(frames):
        got = np.asarray(session.step(frame)).reshape(-1)
        if t >= h_win:
            want = ref.int_forward(layers, q, window(frames, h_win, t))
            np.testing.assert_array_equal(got, want.astype(np.float32))


@pytest.mark.parametrize("net", ["ds-cnn", "resnet-8", "mcunet-5fps-vww"])
def test_float_reference_is_the_planned_network(net):
    layers = common.net_layers(NETS[net])
    cn = _compile(net, check_budget=False)
    weights = [None if p is None or lr["kind"] in ("add", "avgpool") else
               (np.asarray(p[0]), np.zeros(lr["c_out"]))
               for p, lr in zip(cn.qnet.params, layers)]
    h, w, c = NETS[net]["widths"]["input"]
    x = np.random.default_rng(13).standard_normal((h * w, c))
    want = np.asarray(reference_forward(cn.qnet.program, x.astype(
        np.float32), cn.qnet.params)).reshape(-1)
    got = ref.float_forward(layers, weights, x.reshape(h, w, c))
    assert np.linalg.norm(got - want) < 1e-5 * np.linalg.norm(want)


def test_requantize_matches_the_programs_on_edges_and_ties():
    rng = np.random.default_rng(14)
    acc = np.concatenate([rng.integers(-2 ** 31, 2 ** 31, 4000),
                          [0, 1, -1, 2 ** 31 - 1, -2 ** 31, 3 << 20,
                           -(3 << 20), 5 << 10, -(5 << 10)]])
    mult = rng.integers(1 << 30, 1 << 31, acc.size)
    shift = rng.integers(-31, 31, acc.size)
    mult[-9:], shift[-9:] = 1 << 30, -10       # exact .5 ties
    got = ref.requantize_i32(acc, mult, shift)
    want = np.asarray(requantize_i32(acc.astype(np.int32),
                                     mult.astype(np.int32),
                                     shift.astype(np.int32)))
    np.testing.assert_array_equal(got, want)


def test_lower_precision_control_reads_far_above_int8():
    layers = common.net_layers(NETS["ds-cnn"])
    weights = common.host_weights(common.make_weights(layers, 21))
    rng = np.random.default_rng(21)
    calib = [rng.standard_normal((49, 10, 1)) for _ in range(2)]
    xs = [rng.standard_normal((49, 10, 1)) for _ in range(6)]
    err = {}
    for bits in (8, 4):
        q = ref.calibrate(layers, weights, calib, bits=bits)
        err[bits] = ref.max_rel_err(
            [ref.int_forward(layers, q, x) for x in xs],
            [ref.float_forward(layers, weights, x) for x in xs])
    assert err[4] > 3 * err[8]


# Outputs of the reference and of ``work.least_time`` for every net of
# ``nets.json``, recorded before the layer kinds moved into one table
# (``reference.KINDS``): the table must give them bit for bit.
RECORDED = json.loads((Path(__file__).parent / "data" /
                       "reference_recorded.json").read_text())


def _digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("net", sorted(NETS))
def test_kind_table_gives_the_recorded_outputs_bitwise(net):
    want = RECORDED[net]
    layers = common.net_layers(NETS[net])
    weights = common.host_weights(common.make_weights(layers, 1234567))
    assert _digest([a for wb in weights if wb for a in wb]) == \
        want["weights"]
    shape = tuple(NETS[net]["widths"]["input"])
    rng = np.random.default_rng(7654321)
    calib = [rng.standard_normal(shape) for _ in range(2)]
    xs = [rng.standard_normal(shape) for _ in range(2)]
    taps: list = []
    ys = [ref.float_forward(layers, weights, x, taps=taps) for x in xs]
    assert [[v.hex() for v in y] for y in ys] == want["float_out"]
    assert _digest(taps) == want["float_taps"]
    for bits in (8, 4):
        q = ref.calibrate(layers, weights, calib, bits=bits)
        assert _digest([q["scales"]] + [a for t in q["tables"] for a in t]) \
            == want[f"tables{bits}"]
        assert [[float(v).hex() for v in ref.int_forward(layers, q, x)]
                for x in xs] == want[f"int{bits}_out"]
    peak = work.peaks("TPU v5 lite")
    for batch in (1, 256):
        assert [[n, t.hex(), b] for n, t, b in
                work.least_time(layers, batch, peak)] == want[f"least{batch}"]
