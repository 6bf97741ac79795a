"""The inference path's ``vmcu.*`` spans on the JAX profiler's clock.

An int8 zoo net runs through ``CompiledNet.run`` (batch 1 on ``jnp`` and
on ``pallas``, then a batch on ``jnp``) with the profiler off and then
recording; the host line of the trace must hold the spans nested as in
DESIGN.md §12, and the outputs must not move by a bit.
"""
import warnings

import numpy as np
import pytest

import jax
from jax.profiler import ProfileData, TraceAnnotation

import repro
from repro.obs.spans import collect, span

CALLS = (("jnp", 1), ("pallas", 1), ("jnp", 3))
LAYER_SPANS = {"vmcu.quantize", "vmcu.dequantize"}


def _xplane(root):
    found = sorted(root.rglob("*.xplane.pb"))
    assert len(found) == 1, found
    return found[0]


def _events(path):
    """``[(start, end, name, stats)]`` of the ``vmcu.*`` host events."""
    plane = ProfileData.from_file(str(path)).find_plane_with_name(
        "/host:CPU")
    out = []
    with warnings.catch_warnings():   # the stats type's own import warning
        warnings.simplefilter("ignore", DeprecationWarning)
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("vmcu."):
                    out.append((e.start_ns, e.end_ns, e.name,
                                dict(e.stats)))
    return sorted(out, key=lambda ev: (ev[0], -ev[1]))


def _parent(evs, k):
    """Name of the innermost event around ``evs[k]`` (or ``None``)."""
    s, e = evs[k][:2]
    around = [ev for i, ev in enumerate(evs)
              if i != k and ev[0] <= s and e <= ev[1]
              and (ev[0], -ev[1]) < (s, -e)]
    return max(around, key=lambda ev: (ev[0], -ev[1]))[2] if around else None


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    cn = repro.compile("ds-cnn", "cortex-m4", dtype="int8", certify=False,
                       n_calib=1)
    rng = np.random.default_rng(5)
    shape = (cn.program.in_rows, cn.program.in_dim)
    x1 = rng.standard_normal(shape).astype(np.float32)
    xs = [x1, x1, rng.standard_normal((3,) + shape).astype(np.float32)]

    def run_all():
        return [np.asarray(cn.run(x, backend=be).block_until_ready())
                for (be, _), x in zip(CALLS, xs)]

    off = run_all()
    root = tmp_path_factory.mktemp("prof")
    with jax.profiler.trace(str(root)):
        on = run_all()
    return cn, off, on, _events(_xplane(root))


def test_outputs_bitwise_equal_with_the_profiler_on_and_off(traced):
    _cn, off, on, _evs = traced
    for a, b in zip(off, on):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    np.testing.assert_array_equal(off[0], off[1])   # jnp == pallas


def test_one_run_span_per_call_with_backend_and_batch(traced):
    _cn, _off, _on, evs = traced
    runs = [ev for ev in evs if ev[2] == "vmcu.run"]
    assert [(r[3]["backend"], r[3]["batch"]) for r in runs] == list(CALLS)
    for k, ev in enumerate(evs):
        if ev[2] != "vmcu.run":
            assert any(r[0] <= ev[0] and ev[1] <= r[1] for r in runs), ev
        else:
            assert _parent(evs, k) is None


def test_layer_spans_of_each_call(traced):
    cn, _off, _on, evs = traced
    runs = [ev for ev in evs if ev[2] == "vmcu.run"]
    kinds = [op.kind for op in cn.program.ops]
    for (backend, batch), run in zip(CALLS, runs):
        inside = [ev for ev in evs if run[0] <= ev[0] and ev[1] <= run[1]
                  and ev is not run]
        names = [ev[2] for ev in inside]
        for name in ("vmcu.quantize", "vmcu.stage", "vmcu.ring",
                     "vmcu.fetch", "vmcu.dequantize"):
            assert name in names, (backend, batch, name)
        assert names.count("vmcu.ring") == 1
        ops = [ev for ev in inside if ev[2] == "vmcu.op"]
        if backend == "pallas":
            assert [ev[3]["kind"] for ev in ops] == kinds
            assert [ev[3]["index"] for ev in ops] == list(range(len(kinds)))
            ring = next(ev for ev in inside if ev[2] == "vmcu.ring")
            assert all(ring[0] <= ev[0] and ev[1] <= ring[1] for ev in ops)
        else:
            assert not ops
        order = [n for n in names if n in ("vmcu.stage", "vmcu.ring",
                                           "vmcu.fetch")]
        assert order == ["vmcu.stage", "vmcu.ring", "vmcu.fetch"]


def test_sync_only_under_a_host_io_span(traced):
    _cn, _off, _on, evs = traced
    syncs = [k for k, ev in enumerate(evs) if ev[2] == "vmcu.sync"]
    # every call waits for its output, and only for it: the batch's
    # input is quantized on the device
    assert len(syncs) == len(CALLS)
    assert {_parent(evs, k) for k in syncs} == {"vmcu.dequantize"}


def test_quantize_span_says_where_it_ran(traced):
    """The batched ``jnp`` call quantizes on the device, with no host
    wait in it; a 2-D call quantizes on the host (its host input needs
    no wait either)."""
    _cn, _off, _on, evs = traced
    runs = [ev for ev in evs if ev[2] == "vmcu.run"]
    for (backend, batch), run in zip(CALLS, runs):
        quants = [k for k, ev in enumerate(evs) if ev[2] == "vmcu.quantize"
                  and run[0] <= ev[0] and ev[1] <= run[1]]
        assert len(quants) == 2
        where = "device" if batch > 1 else "host"
        assert {evs[k][3]["on"] for k in quants} == {where}, (backend, batch)
        waits = [k for k, ev in enumerate(evs) if ev[2] == "vmcu.sync"
                 and _parent(evs, k) == "vmcu.quantize"
                 and run[0] <= ev[0] and ev[1] <= run[1]]
        assert not waits, (backend, batch)


def test_span_annotates_the_profiler_without_a_collector(tmp_path):
    with span("vmcu.test_idle") as s:
        assert s is None
    with jax.profiler.trace(str(tmp_path)):
        assert TraceAnnotation.is_enabled()
        with span("vmcu.test", kind="conv_pw") as s:
            assert s is None
        with collect() as col:
            with span("vmcu.test_collected", index=2) as s:
                assert s is not None and s.attrs == {"index": 2}
    assert [c.name for c in col.spans] == ["vmcu.test_collected"]
    names = [ev[2] for ev in _events(_xplane(tmp_path))]
    assert names == ["vmcu.test", "vmcu.test_collected"]
