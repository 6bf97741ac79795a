"""The stream cell on the CPU, and the two readers only it reports:
``stream_roofline.eval`` (the streaming state kernel against its
roofline) and ``session_ms.eval`` (the session's own host time a
step)."""
import json
from pathlib import Path

import pytest

from chipbench_testlib import run_cell, small_bench
from chipbench import run
from chipbench import spans as sp
from chipbench import trace as tr

SPANS = Path(__file__).parent / "data" / "spans.xplane.pb"
CALLS = 4
RECORD = {"traced": {"calls": CALLS},
          "least_layers": [["stem", "conv", 1e-6], ["B0.dw", "dw", 2e-6],
                           ["B0.pw", "conv", 3e-6]]}


def _read(name, record, trace):
    return run.Bench().reader(name, True).read(record, trace)


def test_stream_cell_is_correct_end_to_end_on_the_cpu(tmp_path, monkeypatch):
    # the jnp twin of the stream cell, through the same mode, deployment
    # and check; a streaming compile calibrates on the clip's full windows
    manifest = small_bench(tmp_path, {}, stream_cells=["kws-stream-jnp"])
    man = json.loads(manifest.read_text())
    for m in man["end_to_end"]:
        if m["name"] in ("infer_per_s", "call_p95_ms"):
            m["workloads"].append("kws-stream-jnp")
    manifest.write_text(json.dumps(man))
    out = run_cell(manifest, "kws-stream-jnp", seed=2 ** 33 + 17,
                   seconds=1.0, monkeypatch=monkeypatch)
    checks = out["checks"]
    assert out["attempted"] > 49 and out["failed"] == 0
    assert checks["max_rel_err"]["limit"] == 0.2
    assert 0 < checks["max_rel_err"]["value"] < checks["max_rel_err"]["limit"]
    assert checks["shared_answers"]["value"] == 0
    assert out["correct"] and out["window_compiles"] == 0


def test_stream_roofline_is_the_stems_least_time_over_its_kernel():
    trace = {"op_s": {"ring_conv_stream_q": 4e-4, "ring_conv_dw_q": 1e-3,
                      "convert_element_type": 1.0}}
    # 4 steps x 1 us of the stem's least time over 0.4 ms of its kernel
    assert _read("stream_roofline.eval", RECORD, trace) \
        == pytest.approx(1.0)


@pytest.mark.parametrize("trace", [
    None, {"op_s": {}}, {"op_s": {"ring_conv_dw_q": 1e-3}},
    {"op_s": {"ring_conv_stream_q": 0.0}}])
def test_stream_roofline_finds_nothing_without_its_kernel(trace):
    assert _read("stream_roofline.eval", RECORD, trace) is None


def test_session_ms_is_the_steps_own_time_per_step():
    trace = {"spans": {"vmcu.step": {"count": CALLS, "total_s": 0.02,
                                     "self_s": 0.002},
                       "vmcu.ring": {"count": CALLS, "total_s": 0.01,
                                     "self_s": 0.01}}}
    assert _read("session_ms.eval", RECORD, trace) == pytest.approx(0.5)


@pytest.mark.parametrize("trace", [
    None, {"spans": {}}, {"spans": {"vmcu.run": {"count": 1, "total_s": 1.0,
                                                 "self_s": 1.0}}}])
def test_session_ms_finds_nothing_without_a_step(trace):
    assert _read("session_ms.eval", RECORD, trace) is None


def test_readers_find_nothing_in_a_one_shot_calls_trace():
    """The recorded trace runs one-shot ``CompiledNet.run`` calls: no
    ``vmcu.step`` and no ``ring_conv_stream`` kernel in it."""
    trace = {**tr.reduce(SPANS), **sp.reduce(SPANS)}
    assert "vmcu.run" in trace["spans"]
    assert _read("session_ms.eval", RECORD, trace) is None
    assert _read("stream_roofline.eval", RECORD, trace) is None
