"""The benchmark is data: every name in BENCHMARK.json resolves to a file,
and a configuration, cell, mode or metric is added by files alone."""
import json
import os
import re
import subprocess
import sys

import pytest

from chipbench_testlib import ROOT, eval_cell, run_cell, small_bench
from chipbench import run

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_manifest_keys_and_names():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["paths"] == ["chipbench"]
    assert 1 <= MANIFEST["run_seconds"] <= 51
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in MANIFEST[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert "setup_s" in {m["name"] for m in MANIFEST["end_to_end"]}
    assert all(m["bound"] <= 0.25 for m in MANIFEST["end_to_end"])


def test_every_workload_names_a_config_and_mode():
    bench = run.Bench()
    configs = {c["name"] for c in MANIFEST["configs"]}
    for w in MANIFEST["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        wl = bench.cell(w["name"])
        cfg = bench.config(w["config"])
        assert (ROOT / "chipbench" / "modes" / f"{wl['mode']}.py").exists()
        assert {"net", "target", "dtype", "family", "widths"} <= set(cfg)
    used = {w["config"] for w in MANIFEST["workloads"]}
    assert used == configs


def test_every_workload_file_names_a_config_file_and_mode():
    bench = ROOT / "chipbench"
    files = sorted((bench / "workloads").glob("*.json"))
    assert {f.stem for f in files} >= {w["name"]
                                       for w in MANIFEST["workloads"]}
    for f in files:
        wl = json.loads(f.read_text())
        assert (bench / "configs" / f"{wl['config']}.json").exists()
        assert (bench / "modes" / f"{wl['mode']}.py").exists()
        assert 0 < len(wl["why"]) <= 200 and "\n" not in wl["why"]


def test_stream_cell_runs_end_to_end_on_the_cpu(tmp_path, monkeypatch):
    # Plumbing only: the streaming compile's calibration is an open
    # fault of the program (PERF.md, Open questions), so ``correct`` is
    # not asserted here.  A stream step is one inference: the cell
    # reports the eval cells' metrics.
    manifest = small_bench(tmp_path, {}, stream_cells=["kws-stream-jnp"])
    man = json.loads(manifest.read_text())
    for m in man["end_to_end"]:
        if m["name"] in ("infer_per_s", "call_p95_ms"):
            m["workloads"].append("kws-stream-jnp")
    manifest.write_text(json.dumps(man))
    out = run_cell(manifest, "kws-stream-jnp", seconds=1.0,
                   monkeypatch=monkeypatch)
    assert out["attempted"] > 49 and out["failed"] == 0
    assert set(out["metrics"]) == {"infer_per_s", "call_p95_ms", "setup_s"}
    checks = out["checks"]
    assert checks["shared_answers"]["value"] == 0
    assert 0 < checks["max_rel_err"]["value"] < float("inf")


def test_stream_compile_is_handed_the_whole_calibration_clip(monkeypatch):
    import numpy as np

    import repro

    bench = run.Bench()
    cfg = json.loads((ROOT / "chipbench" / "configs" /
                      "ds-cnn-stream-m4.json").read_text())
    wl = json.loads((ROOT / "chipbench" / "workloads" /
                     "kws-stream-pallas.json").read_text())
    mode = bench.mode(wl["mode"])
    seen = {}

    class Handed(Exception):
        pass

    def compile_(*args, **kw):
        seen.update(kw)
        raise Handed

    monkeypatch.setattr(repro, "compile", compile_)
    with pytest.raises(Handed):
        mode.Cell(cfg, wl, 2 ** 33 + 5, bench.layers(cfg))
    h_win, w, c = cfg["widths"]["input"]
    n = cfg["assumed"]["n_calib"]
    clip = seen["calib"]
    assert seen["streaming"] is True
    np.testing.assert_array_equal(clip, np.random.default_rng(
        cfg["deployment_seed"]).standard_normal((h_win + n - 1, w, c),
                                                np.float32))
    windows = mode.calibration_inputs(cfg, wl)
    assert len(windows) == n
    for t, win in enumerate(windows):       # the window ending in frame t
        np.testing.assert_array_equal(win, clip[t:t + h_win])


def test_a_family_brings_its_own_layer_kind(tmp_path, monkeypatch):
    """New files alone: a family file whose pointwise convs are a kind of
    its own, a configuration that names it, and a cell."""
    manifest = small_bench(tmp_path, {"kws-pw-b2": eval_cell(batch=2)})
    bench_dir = tmp_path / "chipbench"
    (bench_dir / "nets" / "kws_pointwise.py").write_text(FAMILY)
    cfg_path = bench_dir / "configs" / "ds-cnn-m4.json"
    cfg = json.loads(cfg_path.read_text())
    cfg["family"] = "kws_pointwise"
    cfg_path.write_text(json.dumps(cfg))
    out = run_cell(manifest, "kws-pw-b2", monkeypatch=monkeypatch)
    assert out["correct"] and out["failed"] == 0, out["checks"]
    assert set(out["metrics"]) == {"infer_per_s", "setup_s"}

    from chipbench import common, work

    layers = run.Bench(bench_dir, manifest).layers(cfg)
    kinds = [lr["kind"] for lr in layers]
    assert kinds.count("pointwise") == 4 and "conv" in kinds
    cfg["family"] = "ds_cnn"
    plain = common.net_layers(cfg)
    assert [work.macs(lr) for lr in layers] == [work.macs(lr)
                                                for lr in plain]
    assert [work.param_bytes(lr) for lr in layers] == [
        work.param_bytes(lr) for lr in plain]
    for f in (ROOT / "chipbench").rglob("*"):
        rel = f.relative_to(ROOT / "chipbench")
        if f.is_file() and rel.parts[0] != "tests" and \
                "__pycache__" not in rel.parts:
            assert (bench_dir / rel).read_bytes() == f.read_bytes(), rel


FAMILY = '''"""DS-CNN whose pointwise convs are a layer kind of this family."""
import numpy as np

from chipbench.reference import Builder, linear_kind


def _pointwise(x, w, layer):
    h, w_in, c = x.shape
    y = x.reshape(h * w_in, c).astype(np.float64) @ np.asarray(w, np.float64)
    if x.dtype.kind != "f":
        y = np.rint(y).astype(np.int64)
    return y.reshape(h, w_in, -1)


KINDS = {"pointwise": linear_kind(
    _pointwise, ("conv_pw",),
    weight_shape=lambda lr: (lr["c_in"], lr["c_out"]),
    macs=lambda lr: lr["h_out"] * lr["w_out"] * lr["c_in"] * lr["c_out"])}


def layers(widths):
    h, w, c = widths["input"]
    b = Builder(h, w, c)
    stem, ch = widths["stem"], widths["channels"]
    b.conv("stem", ch, k=stem["k"], stride=stem["stride"])
    for i in range(widths["blocks"]):
        b.dw(f"B{i}.dw")
        h, w, c = b.shapes[-1]
        b.push(dict(name=f"B{i}.pw", kind="pointwise", src=b.cur, h=h, w=w,
                    c_in=c, c_out=ch, relu=True, h_out=h, w_out=w),
               (h, w, ch))
    b.head(widths["num_classes"])
    return b.layers
'''


@pytest.mark.parametrize("kind", ["end_to_end", "per_layer"])
def test_every_metric_has_a_reader_and_its_moves_is_reported(kind):
    bench = run.Bench()
    cells = {w["name"] for w in MANIFEST["workloads"]}
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    for m in MANIFEST[kind]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert callable(bench.reader(m["name"], kind == "per_layer").read)
        assert set(m.get("workloads", cells)) <= cells
        if kind == "per_layer":
            target = e2e[m["moves"]]
            assert set(m["workloads"]) <= set(target.get("workloads",
                                                         cells))


def test_each_cell_reports_setup_another_end_to_end_and_a_layer_metric():
    bench = run.Bench()
    for w in MANIFEST["workloads"]:
        e2e = [m["name"] for m in bench.metrics(w["name"], False)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert bench.metrics(w["name"], True)


def test_throwaway_config_workload_and_metric_are_found(tmp_path,
                                                        monkeypatch):
    manifest = small_bench(tmp_path, {"kws-eval-b2": eval_cell(batch=2)})
    man = json.loads(manifest.read_text())
    man["end_to_end"].insert(0, {
        "name": "calls_per_s", "unit": "1/s", "better": "higher",
        "bound": 0.05, "source": "host_clock", "workloads": ["kws-eval-b2"]})
    manifest.write_text(json.dumps(man))
    (tmp_path / "chipbench" / "end_to_end" / "calls_per_s.py").write_text(
        "def read(record, trace=None):\n"
        "    return record['calls'] / record['elapsed_s']\n")
    out = run_cell(manifest, "kws-eval-b2", monkeypatch=monkeypatch)
    assert out["correct"] and out["failed"] == 0
    assert set(out["metrics"]) == {"calls_per_s", "infer_per_s", "setup_s"}
    assert out["metrics"]["infer_per_s"]["value"] == pytest.approx(
        2 * out["metrics"]["calls_per_s"]["value"])
    assert list(out)[-1] == "checks"
    assert out["window_compiles"] == 0


def test_run_off_the_chip_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "chipbench" / "run.py"), "--workload",
         MANIFEST["workloads"][0]["name"], "--seed", str(2 ** 33 + 1),
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "needs 1 TPU" in proc.stderr
