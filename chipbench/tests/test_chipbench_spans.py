"""The program's ``vmcu.*`` spans reduced with the device trace
(``spans.py``, merged by ``run.reduce_trace``) and the readers of
``layer_metrics/`` that take them.

``spans.xplane.pb`` was recorded on one TPU v5e: ``ds-cnn`` int8 on
``cortex-m4`` (11 ops), three warm batch-1 ``CompiledNet.run`` calls on
``pallas`` inside one ``chipbench.window``, each under a
``chipbench.call`` annotation and 2 ms apart.  The plane of HLO protos
(``/host:metadata``) and the HLO stats of the device ops' metadata were
dropped to keep the file small; no reduction reads them."""
import importlib.util
from pathlib import Path

import pytest

from chipbench_testlib import ROOT
from chipbench import run, work
from chipbench import spans as sp
from chipbench import trace as tr

DATA = Path(__file__).parent / "data"
SPANS, SMALL = DATA / "spans.xplane.pb", DATA / "small.xplane.pb"
CALLS, OPS = 3, 11
READERS = ("host_io_ms", "dispatch_ms", "host_io_wait", "dispatch_wait")


def _reader(name):
    path = ROOT / "chipbench" / "layer_metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"reader_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.fixture(scope="module")
def traced():
    return run.reduce_trace(SPANS)


def test_spans_count_once_per_call_and_self_within_total(traced):
    counts = {n: v["count"] for n, v in traced["spans"].items()}
    assert counts == {"vmcu.run": CALLS, "vmcu.quantize": 2 * CALLS,
                      "vmcu.stage": CALLS, "vmcu.ring": CALLS,
                      "vmcu.op": OPS * CALLS, "vmcu.fetch": CALLS,
                      "vmcu.dequantize": CALLS, "vmcu.sync": CALLS}
    for v in traced["spans"].values():
        assert 0 <= v["self_s"] <= v["total_s"] + 1e-12
    s = traced["spans"]
    assert s["vmcu.ring"]["total_s"] == pytest.approx(
        s["vmcu.ring"]["self_s"] + s["vmcu.op"]["total_s"])
    assert s["vmcu.dequantize"]["total_s"] >= s["vmcu.sync"]["total_s"]
    assert s["vmcu.run"]["total_s"] <= traced["window_s"]


def test_span_idle_sums_to_the_idle_time(traced):
    idle = traced["window_s"] - traced["busy_s"]
    assert sum(traced["span_idle"].values()) == pytest.approx(idle,
                                                              rel=0.01)
    # waits count toward the span they wait in
    assert "vmcu.sync" not in traced["span_idle"]
    assert set(traced["span_idle"]) <= set(traced["spans"]) | {"outside"}
    # a span holds no more idle time than its own length
    for name, sec in traced["span_idle"].items():
        if name != "outside":
            assert sec <= traced["spans"][name]["total_s"] + 1e-9


def test_existing_keys_read_the_same_on_the_small_trace():
    before = tr.reduce(SMALL)
    after = {**before, **sp.reduce(SMALL)}
    assert {k: after[k] for k in before} == before
    assert after["spans"] == {}
    idle = before["window_s"] - before["busy_s"]
    assert after["span_idle"] == {"outside": pytest.approx(idle)}


def test_readers_on_the_trace(traced):
    record = {"traced": {"calls": CALLS}}
    got = {n: _reader(n)(record, traced) for n in READERS}
    s, idle = traced["spans"], traced["span_idle"]
    host = ("vmcu.quantize", "vmcu.dequantize", "vmcu.stage", "vmcu.fetch")
    assert got["host_io_ms"] == pytest.approx(
        1e3 * sum(s[n]["self_s"] for n in host) / CALLS)
    assert got["dispatch_ms"] == pytest.approx(
        1e3 * s["vmcu.ring"]["total_s"] / CALLS)
    assert all(v > 0 for v in got.values())
    # the two waits, the idle outside and under vmcu.run itself make up
    # the device's idle share
    rest = 100 * (idle.get("outside", 0) + idle.get("vmcu.run", 0)) \
        / traced["window_s"]
    share = 100 * (1 - traced["busy_s"] / traced["window_s"])
    assert got["host_io_wait"] + got["dispatch_wait"] + rest == \
        pytest.approx(share, abs=0.5)


@pytest.mark.parametrize("name", READERS)
def test_readers_find_nothing_without_the_spans(name):
    read = _reader(name)
    record = {"traced": {"calls": CALLS}}
    assert read(record, None) is None
    assert read(record, tr.reduce(SMALL)) is None
    assert read(record, {**tr.reduce(SMALL), **sp.reduce(SMALL)}) is None


def test_nest_and_overlaps():
    evs = [(10, 40, "run"), (12, 20, "op"), (25, 35, "sync"),
           (50, 60, "run")]
    parent, pieces = sp._nest(evs, 0, 100)
    assert parent == [None, 0, 0, None]
    assert pieces == [(0, 10, None), (10, 12, 0), (12, 20, 1),
                      (20, 25, 0), (25, 35, 2), (35, 40, 0),
                      (40, 50, None), (50, 60, 3), (60, 100, None)]
    got = [(round(sec * 1e9), i)
           for sec, i in sp._overlaps([(5, 15), (30, 55)], pieces)]
    assert got == [(5, None), (2, 0), (3, 1), (5, 2), (5, 0), (10, None),
                   (5, 3)]


def test_a_runs_span_metrics_read_the_merged_trace(traced):
    """The manifest's span metrics, read as a run reads them, find their
    spans; the device idles under them for no more than its idle share."""
    bench = run.Bench()
    record = {"traced": {"calls": CALLS}}
    names = [m["name"] for m in bench.manifest["per_layer"]
             if m["source"] == "program_span" and m["name"].split(".")[0]
             in READERS]
    assert len(names) == 4
    got = {n.split(".")[0]: bench.reader(n, True).read(record, traced)
           for n in names}
    assert all(v is not None and v > 0 for v in got.values())
    idle = bench.reader("idle_share.eval", True).read(record, traced)
    assert got["host_io_wait"] + got["dispatch_wait"] <= idle


def _ring_kernel_seconds():
    """Device seconds of the ``ring_*`` ops inside the window, straight
    from the recorded events."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(SPANS))
    host = data.find_plane_with_name(tr.HOST_PLANE)
    w0, w1 = next((e.start_ns, e.end_ns) for ln in host.lines
                  for e in ln.events if e.name == tr.WINDOW)
    dev = data.find_plane_with_name("/device:TPU:0")
    shift = tr.clock_shift(host, dev)
    ops = next(ln for ln in dev.lines if ln.name == tr.OPS_LINE)
    return sum(min(e.end_ns + shift, w1) - max(e.start_ns + shift, w0)
               for e in ops.events if e.name.lstrip("%").startswith("ring_")
               and min(e.end_ns + shift, w1) > max(e.start_ns + shift, w0)
               ) * 1e-9


def test_kernel_roofline_on_the_trace(traced):
    from chipbench import common
    from chipbench_testlib import NETS

    layers = common.net_layers(NETS["ds-cnn"])
    least = work.least_time(layers, 1, work.peaks("TPU v5 lite"))
    record = {"traced": {"calls": CALLS},
              "least_call_s": sum(t for _, t, _ in least),
              "least_layers": [[n, lr["kind"], t]
                               for (n, t, _), lr in zip(least, layers)]}
    want = 100 * CALLS * sum(t for _, t, _ in least) / _ring_kernel_seconds()
    got = _reader("kernel_roofline")(record, traced)
    assert got == pytest.approx(want, rel=1e-9)
    assert got > _reader("ring_roofline")(record, traced)
    # no ring kernel ran in the small trace, and no trace, no reading
    assert _reader("kernel_roofline")(record, tr.reduce(SMALL)) is None
    assert _reader("kernel_roofline")(record, None) is None


@pytest.mark.parametrize("kinds, share", [(None, 50.0), (("conv",), 25.0),
                                          (("add",), 0.0)])
def test_roofline_share_by_hand(kinds, share):
    # 10 calls x 0.2 ms of least time over 4 ms of ring kernels
    record = {"traced": {"calls": 10},
              "least_layers": [["a", "conv", 1e-4], ["b", "dw", 1e-4]]}
    trace = {"op_s": {"ring_a": 0.002, "ring_b": 0.002, "convert": 1.0}}
    got = work.roofline_share(record, trace, ("ring_",), kinds)
    assert got == pytest.approx(share)
    assert work.roofline_share(record, trace, ("mosaic_",)) is None
