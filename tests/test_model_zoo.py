"""MLPerf-Tiny model-zoo acceptance: DS-CNN, ResNet-8 and
MobileNetV1-0.25 compile through ``repro.compile(net, "cortex-m4")``
and run end-to-end on every backend in fp32 AND int8 — sim certifies
zero clobbers, jnp/pallas match the plain-XLA reference (int8
bitwise across backends)."""
import jax
import numpy as np
import pytest

import jax.numpy as jnp

import repro
from repro.core.executors import run_program
from repro.graph import (build_ad_autoencoder, build_ds_cnn,
                         build_mobilenet_v1, build_resnet8,
                         reference_forward)
from repro.quant import QParams, quantize

KEY = jax.random.PRNGKey(0)
ZOO = ("ds-cnn", "resnet-8", "mobilenetv1-0.25")


def _tol(ref):
    scale = float(np.abs(np.asarray(ref)).max()) or 1.0
    return dict(rtol=3e-4, atol=3e-5 * scale)


def test_zoo_builders_validate():
    for build, n_convs in ((build_ds_cnn, 9), (build_resnet8, 9),
                           (build_mobilenet_v1, 27)):
        g = build()
        g.validate()
        convs = [n for n in g.nodes.values()
                 if n.kind.startswith("conv")]
        assert len(convs) == n_convs
        # every zoo net exercises a real k x k spatial conv
        assert any(n.kind == "conv_k2d" for n in g.nodes.values())


def test_zoo_fits_cortex_m4_sram():
    """Deployability: every zoo net's byte-granular bottleneck fits the
    paper's 128 KB board, well under the tensor-level baseline."""
    for net in ZOO:
        cn = repro.compile(net, "cortex-m4", quantize=False,
                           certify=False)
        rep = cn.report()
        assert rep["fits_sram"], rep
        assert rep["mcu_bottleneck_bytes"] \
            < rep["tinyengine_bottleneck_bytes"]


@pytest.mark.slow
@pytest.mark.parametrize("net", ZOO)
def test_zoo_fp32_all_backends(net):
    """host-sim fp32 compile: certify (sim), then jnp and pallas match
    the plain-XLA reference forward."""
    cn = repro.compile(net, "host-sim")          # certify pass included
    assert cn.certificate["clobbers"] == 0
    cn.program.check_alignment()
    params = cn.ensure_params()
    x = jax.random.normal(KEY, (cn.program.in_rows, cn.program.in_dim))
    ref = reference_forward(cn.program, x, params)
    tol = _tol(ref)
    for backend in ("jnp", "pallas"):
        y = cn.run(x, backend=backend)
        assert y.shape == (cn.program.out_rows, cn.program.out_dim)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), **tol)


@pytest.mark.slow
@pytest.mark.parametrize("net", ZOO)
def test_zoo_int8_all_backends_bitwise(net):
    """cortex-m4 int8 compile: sim-certified, jnp == pallas BITWISE on
    the whole ring state, and the dequantized output tracks the float
    reference (cosine + argmax agreement)."""
    from repro.graph.run import quantized_agreement

    cn = repro.compile(net, "cortex-m4")         # int8 + quantize + certify
    assert cn.quantized and cn.certificate["clobbers"] == 0
    qnet = cn.qnet
    x = jax.random.normal(KEY, (cn.program.in_rows, cn.program.in_dim))
    x_q = quantize(x, QParams(scale=qnet.in_scale))
    y_j, pool_j = run_program(qnet.program, x_q, qnet.qparams,
                              backend="jnp")
    y_p, pool_p = run_program(qnet.program, x_q, qnet.qparams,
                              backend="pallas")
    assert y_j.dtype == np.int8 and y_p.dtype == np.int8
    np.testing.assert_array_equal(np.asarray(y_j), np.asarray(y_p))
    np.testing.assert_array_equal(np.asarray(pool_j.array),
                                  np.asarray(pool_p.array))
    rep = quantized_agreement(qnet, n=4)
    assert rep["cosine"] >= 0.99, rep
    assert rep["argmax_agreement"] >= 0.75, rep


# ---------------------------------------------------------------------------
# MLPerf-Tiny anomaly detection: the ToyADMOS FC autoencoder.
# ---------------------------------------------------------------------------

def test_ad_toyadmos_builder_validates():
    g = build_ad_autoencoder()
    g.validate()
    fcs = [n for n in g.nodes.values() if n.kind == "fc"]
    assert len(fcs) == 10                    # 4 enc + latent + 4 dec + head
    assert fcs[-1].out.d == 640 and fcs[-1].activation is None
    assert all(n.activation == "relu" for n in fcs[:-1])


def test_ad_toyadmos_fp32_all_backends():
    cn = repro.compile("ad-toyadmos", "host-sim")
    assert cn.certificate["clobbers"] == 0
    params = cn.ensure_params()
    x = jax.random.normal(KEY, (cn.program.in_rows, cn.program.in_dim))
    ref = reference_forward(cn.program, x, params)
    tol = _tol(ref)
    for backend in ("jnp", "pallas"):
        y = cn.run(x, backend=backend)
        assert y.shape == (1, 640)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), **tol)


def test_ad_toyadmos_int8_cortex_m4_bitwise():
    from repro.graph.run import quantized_agreement

    cn = repro.compile("ad-toyadmos", "cortex-m4")
    assert cn.quantized and cn.certificate["clobbers"] == 0
    assert cn.report()["fits_sram"]
    qnet = cn.qnet
    x = jax.random.normal(KEY, (cn.program.in_rows, cn.program.in_dim))
    x_q = quantize(x, QParams(scale=qnet.in_scale))
    y_j, _ = run_program(qnet.program, x_q, qnet.qparams, backend="jnp")
    y_p, _ = run_program(qnet.program, x_q, qnet.qparams,
                         backend="pallas")
    np.testing.assert_array_equal(np.asarray(y_j), np.asarray(y_p))
    rep = quantized_agreement(qnet, n=4)
    assert rep["cosine"] >= 0.99, rep


def test_ad_toyadmos_alias_resolves():
    cn = repro.compile("toyadmos", "host-sim", certify=False)
    assert cn.net_name == "ad-toyadmos"


# ---------------------------------------------------------------------------
# Batched CompiledNet.run: one shared plan vmapped over a leading dim.
# ---------------------------------------------------------------------------

def _on_quantize_edges(shape, scale):
    """Float32 inputs on every rounding edge ``(k +- 0.5) * scale`` of
    the int8 range and one ulp to each side of it, cycled to ``shape``."""
    mid = ((np.arange(-128, 129) - 0.5) * scale).astype(np.float32)
    edges = np.concatenate([mid, np.nextafter(mid, np.float32(np.inf)),
                            np.nextafter(mid, np.float32(-np.inf))])
    return jnp.asarray(np.resize(edges, shape))


@pytest.mark.parametrize("inputs", ["normal", "quantize_edges"])
def test_batched_run_int8_bitwise_matches_loop(inputs):
    """A leading batch dim vmaps ONE shared plan; the int8 path stays
    bitwise identical to the per-sample loop.  The batch quantizes on
    the device, each sample on the host: the same int8 input even on
    the quantizer's rounding edges."""
    cn = repro.compile("ad-toyadmos", "cortex-m4")
    shape = (3, cn.program.in_rows, cn.program.in_dim)
    if inputs == "normal":
        x = jax.random.normal(KEY, shape)
    else:
        x = _on_quantize_edges(shape, cn.qnet.in_scale)
    y_b = cn.run(x)
    assert y_b.shape == (3, 1, 640)
    y_l = jnp.stack([cn.run(xi) for xi in x])
    np.testing.assert_array_equal(np.asarray(y_b), np.asarray(y_l))
    # pallas batches via the per-sample loop — same bitwise surface
    y_p = cn.run(x, backend="pallas")
    np.testing.assert_array_equal(np.asarray(y_p), np.asarray(y_l))


def test_batched_run_fp32_matches_loop():
    cn = repro.compile("ds-cnn", "host-sim")
    x = jax.random.normal(KEY, (2, cn.program.in_rows, cn.program.in_dim))
    y_b = cn.run(x)
    y_l = jnp.stack([cn.run(xi) for xi in x])
    assert y_b.shape == y_l.shape
    np.testing.assert_allclose(np.asarray(y_b), np.asarray(y_l),
                               **_tol(y_l))


def test_resnet8_shortcut_projection_plan_shape():
    """The downsampling stacks lower to the branch pattern: main-path
    k2d convs with the block input held, a shortcut projection reading
    the held tensor (in_op), and a post-add relu."""
    cn = repro.compile("resnet-8", "host-sim", certify=False)
    ops = cn.program.ops
    kinds = [op.kind for op in ops]
    assert kinds.count("conv_k2d") == 7          # stem + 3 stacks x 2
    assert kinds.count("add") == 3
    branch = [op for op in ops if op.in_op >= 0]
    assert len(branch) == 2                      # R1.sc, R2.sc
    for op in branch:
        assert op.kind == "conv_pw" and op.stride == 2
        # the held source op must not free the shared block input
        assert ops[op.in_op].hold_input
    for op in ops:
        if op.kind == "add":
            assert op.activation == "relu" and op.aux_op >= 0
