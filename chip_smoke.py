#!/usr/bin/env python3
"""Chip smoke test: the int8 deployment path on one TPU, end to end.

One process, no children.  Phases:

  (i)   device check — off a TPU it exits non-zero, names the platform it
        found and prints no result line;
  (ii)  ``mcunet-320kb-imagenet`` for ``cortex-m7`` (int8, quantized,
        certified, published MCUNet widths, 1000 classes): three seeded
        inferences through ``CompiledNet.run`` on the ``jnp`` and
        ``pallas`` executors, int8 outputs and the whole pool bitwise
        equal between them, agreement with the float reference, one
        batch-8 ``jnp`` run, and proof that the Pallas kernels compiled
        through Mosaic (``tpu_custom_call``) rather than interpreted;
  (iii) the streaming ``ds-cnn`` for ``cortex-m4``: one window of frames
        stepped on both executors, bitwise equal at every step and, once
        the window has filled, equal to the one-shot net on that window.

Host seconds for compile / first call / warm call are printed per phase,
labelled with the device; they are set-up timings, not benchmark numbers.
The last line of stdout is the JSON result.  Any failure raises before it.

    python chip_smoke.py [--seed N]
"""
from __future__ import annotations

import argparse
import importlib.metadata
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import repro  # noqa: E402
from repro.compile.cache import use_compile_cache  # noqa: E402
from repro.core.executors import run_program  # noqa: E402
from repro.graph.run import quantized_agreement  # noqa: E402
from repro.kernels.quantized import ring_gemm_q  # noqa: E402
from repro.kernels.ring import pallas_interpret  # noqa: E402
from repro.quant import QParams, quantize  # noqa: E402

NET, TARGET = "mcunet-320kb-imagenet", "cortex-m7"
STREAM_NET, STREAM_TARGET = "ds-cnn", "cortex-m4"
MIN_COSINE = 0.95       # sanity bound against garbage output only
BATCH = 8


def log(msg: str) -> None:
    print(msg, flush=True)


def timed(fn):
    """``fn()`` with its result ready, and the host seconds it took."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def require(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def version(dist: str) -> str:
    try:
        return importlib.metadata.version(dist)
    except importlib.metadata.PackageNotFoundError:
        return "not installed"


def device_check():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found platform "
                 f"{dev.platform!r} ({dev.device_kind})")
    log(f"device: {dev.device_kind} x{len(jax.devices())}")
    log(f"jax {jax.__version__}, jaxlib {version('jaxlib')}, "
        f"libtpu {version('libtpu')}")
    return dev


def whole_net(dev: str, seed: int, net: str = NET, target: str = TARGET):
    """Phase (ii) minus the Mosaic check; returns the compiled net."""
    cn, t = timed(lambda: repro.compile(net, target))
    log(f"[{dev}] {net}/{target} compile {t:.3f}s")
    require(cn.quantized and cn.certificate["clobbers"] == 0,
            "int8 compile must be quantized and certified clobber-free")
    qn = cn.qnet
    prog = qn.program
    out_shape = (prog.out_rows, prog.out_dim)
    xs = jax.random.normal(jax.random.PRNGKey(seed),
                           (BATCH, prog.in_rows, prog.in_dim))
    ys = {}
    for backend in ("jnp", "pallas"):
        y0, first = timed(lambda: cn.run(xs[0], backend=backend))
        _, warm = timed(lambda: cn.run(xs[0], backend=backend))
        log(f"[{dev}] {net} run {backend}: first call {first:.3f}s, "
            f"warm call {warm:.3f}s")
        ys[backend] = np.stack([np.asarray(y0)] + [
            np.asarray(cn.run(x, backend=backend)) for x in xs[1:3]])
    require(ys["jnp"].shape == (3, *out_shape), f"output {ys['jnp'].shape}")
    require(bool(np.isfinite(ys["jnp"]).all()), "non-finite output")
    np.testing.assert_array_equal(ys["jnp"], ys["pallas"])
    for i in range(3):
        x_q = quantize(xs[i], QParams(scale=qn.in_scale))
        y_j, pool_j = run_program(prog, x_q, qn.qparams, backend="jnp")
        y_p, pool_p = run_program(prog, x_q, qn.qparams, backend="pallas")
        require(y_j.dtype == jnp.int8 and y_p.dtype == jnp.int8,
                "int8 outputs expected")
        np.testing.assert_array_equal(np.asarray(y_j), np.asarray(y_p))
        np.testing.assert_array_equal(np.asarray(pool_j.array),
                                      np.asarray(pool_p.array))
    log(f"[{dev}] {net}: jnp == pallas bitwise on 3 inputs "
        f"(int8 outputs and the whole {prog.n_segments}x{prog.seg_width} "
        f"pool)")
    yb, t = timed(lambda: cn.run(xs, backend="jnp"))
    require(yb.shape == (BATCH, *out_shape), f"batch output {yb.shape}")
    np.testing.assert_array_equal(np.asarray(yb)[:3], ys["jnp"])
    log(f"[{dev}] {net} batch-{BATCH} jnp run {t:.3f}s (first call), "
        f"equal to the single runs")
    rep = quantized_agreement(qn, n=4, key=jax.random.PRNGKey(seed + 1))
    log(f"[{dev}] {net} quantized_agreement: {rep}")
    require(rep["cosine"] >= MIN_COSINE,
            f"cosine {rep['cosine']} below {MIN_COSINE}")
    return cn


def check_mosaic(cn) -> None:
    """The pallas run was compiled, not interpreted: the one interpret
    switch says so, and a ring kernel lowers to a Mosaic custom call."""
    require(pallas_interpret() is False, "pallas would run interpreted")
    prog, qparams = cn.qnet.program, cn.qnet.qparams
    i = next(j for j, op in enumerate(prog.ops) if op.kind == "gemm")
    op = prog.ops[i]
    w, b, mult, shift = qparams[i]
    if b is None:
        b = jnp.zeros((op.d_out,), jnp.int32)
    pool = jax.ShapeDtypeStruct((prog.n_segments, prog.seg_width), jnp.int8)
    text = ring_gemm_q.lower(
        pool, w, b, mult, shift, m_rows=op.rows_in or prog.m_rows,
        d_in=op.d_in, d_out=op.d_out, in_ptr=op.in_ptr, out_ptr=op.out_ptr,
        block_rows=prog.block_rows, activation=op.activation).as_text()
    require("tpu_custom_call" in text, "ring_gemm_q did not lower to Mosaic")
    log("pallas kernels lowered to tpu_custom_call (interpret=False)")


def streaming(dev: str, seed: int, net: str = STREAM_NET,
              target: str = STREAM_TARGET) -> None:
    """Phase (iii)."""
    cn, t = timed(lambda: repro.compile(net, target, dtype="int8",
                                        streaming=True))
    log(f"[{dev}] {net}/{target} streaming compile {t:.3f}s")
    qs = cn.qnet
    h_win = cn.program.ops[0].h_in
    one_shot = repro.compile(net, target, dtype="int8", certify=False)
    full = one_shot.qnet.program
    x_q = quantize(jax.random.normal(jax.random.PRNGKey(seed + 2),
                                     (full.in_rows, full.in_dim)),
                   QParams(scale=qs.in_scale))
    frames = x_q.reshape(-1, qs.program.ops[0].rows_in, full.in_dim)
    require(frames.shape[0] == h_win, f"{frames.shape[0]} frames for a "
            f"{h_win}-row window")
    sessions = {b: cn.stream(backend=b) for b in ("jnp", "pallas")}
    for step, f in enumerate(frames):
        y = {}
        for b, sess in sessions.items():
            y[b], t = timed(lambda: sess.step(f))
            if step < 2:
                log(f"[{dev}] {net} stream {b} step {step}: {t:.3f}s "
                    f"({'first' if step == 0 else 'warm'} call)")
        np.testing.assert_array_equal(np.asarray(y["jnp"]),
                                      np.asarray(y["pallas"]))
    np.testing.assert_array_equal(np.asarray(sessions["jnp"]._pool.array),
                                  np.asarray(sessions["pallas"]._pool.array))
    y_full, _ = run_program(full, x_q, qs.qparams, backend="jnp")
    np.testing.assert_array_equal(np.asarray(y["jnp"]), np.asarray(y_full))
    log(f"[{dev}] {net}: {h_win} stream steps jnp == pallas bitwise; the "
        f"filled window equals the one-shot net")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    dev = device_check()
    log(f"compile cache: {use_compile_cache()}")
    label = dev.device_kind
    cn = whole_net(label, args.seed)
    check_mosaic(cn)
    streaming(label, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
