"""Trace reduction, checked on a small trace recorded on one TPU v5e: a
jitted Pallas kernel (``chipbench_double``) and an XLA matmul fusion,
three harness calls 2 ms apart inside one ``chipbench.window``."""
from pathlib import Path

import pytest

from chipbench_testlib import ROOT  # noqa: F401  (import paths)
from chipbench import trace as tr

SMALL = Path(__file__).parent / "data" / "small.xplane.pb"
SPANS = Path(__file__).parent / "data" / "spans.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce(SMALL)


def _device_ops(window):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(SMALL))
    plane = data.find_plane_with_name("/device:TPU:0")
    shift = tr.clock_shift(data.find_plane_with_name("/host:CPU"), plane)
    line = next(ln for ln in plane.lines if ln.name == "XLA Ops")
    return [(e.start_ns + shift, e.end_ns + shift, e.name)
            for e in line.events
            if window[0] <= e.start_ns + shift and
            e.end_ns + shift <= window[1]]


def test_device_clock_is_moved_onto_the_hosts():
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(SMALL))
    shift = tr.clock_shift(data.find_plane_with_name("/host:CPU"),
                           data.find_plane_with_name("/device:TPU:0"))
    assert 1.4e6 < shift < 1.6e6            # about 1.5 ms on this trace


def test_window_busy_and_idle_add_up(reduced):
    assert reduced["devices"] == 1
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    idle = sum(s for _, s in reduced["idle_gaps"])
    assert idle + reduced["busy_s"] == pytest.approx(reduced["window_s"])


def test_busy_is_the_union_of_the_ops_in_the_window(reduced):
    from jax.profiler import ProfileData

    host = ProfileData.from_file(str(SMALL)).find_plane_with_name(
        "/host:CPU")
    win = next((e.start_ns, e.end_ns) for ln in host.lines
               for e in ln.events if e.name == tr.WINDOW)
    ops = _device_ops(win)
    assert len(ops) == 12                   # 4 ops x 3 calls
    covered = set()
    for s, e, _ in ops:                     # 1 ns grid: a plain union
        covered.update(range(int(s), int(e)))
    assert reduced["busy_s"] == pytest.approx(len(covered) * 1e-9, abs=3e-9)


def test_ops_are_named_by_kernel_and_kind(reduced):
    names = [n for n, _ in reduced["device_ops"]]
    assert set(names) == {"chipbench_double", "copy-start", "copy-done",
                          "fusion"}
    assert sum(s for _, s in reduced["device_ops"]) >= reduced["busy_s"]
    assert tr.op_name("%ring_conv_dw_q.1 = s32[8] custom-call(s32[8] %x)") \
        == "ring_conv_dw_q"
    assert tr.op_name("%copy-start = (f32[2]) copy-start(f32[2] %x)") \
        == "copy-start"


@pytest.mark.parametrize("path", [SMALL, SPANS], ids=["small", "spans"])
def test_op_seconds_hold_every_op_and_agree_with_the_top(path):
    got = tr.reduce(path, top=3)
    assert len(got["op_s"]) > 3 and len(got["device_ops"]) == 3
    assert sum(got["op_s"].values()) >= sum(s for _, s in got["device_ops"])
    for name, sec in got["device_ops"]:
        assert got["op_s"][name] == sec
    assert min(s for _, s in got["device_ops"]) >= max(
        s for n, s in got["op_s"].items()
        if n not in dict(got["device_ops"]))


def test_idle_gaps_are_named_by_the_host(reduced):
    gaps = dict(reduced["idle_gaps"])
    assert gaps["$time sleep"] > 0.006      # three 2 ms sleeps
    assert max(gaps, key=gaps.get) == "$time sleep"


def test_union_and_innermost():
    assert tr._union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [[0, 3], [5, 9]]
    host = [(0, 100, "window"), (10, 40, "call"), (12, 20, "dispatch"),
            (50, 90, "call")]
    assert tr._innermost(host, [15, 30, 45, 60, 200]) == [
        "dispatch", "call", "window", "call", "no host event"]
