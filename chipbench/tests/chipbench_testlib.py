"""Helpers the chipbench tests share: import paths and a throwaway
benchmark tree with a small CPU cell."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

NETS = json.loads((Path(__file__).parent / "data" / "nets.json").read_text())


def small_bench(tmp: Path, cells: dict, stream_cells=()) -> Path:
    """Copy the benchmark into ``tmp`` and add a ``ds-cnn-m4`` (one-shot)
    configuration with the given eval cells ``{name: workload}``, and
    the manifest entries of the prepared stream cells named in
    ``stream_cells``.  Returns the new manifest's path."""
    shutil.copytree(ROOT / "chipbench", tmp / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((ROOT / "chipbench" / "configs" /
                      "ds-cnn-stream-m4.json").read_text())
    cfg["streaming"] = False
    (tmp / "chipbench" / "configs" / "ds-cnn-m4.json").write_text(
        json.dumps(cfg))
    man["configs"].append({"name": "ds-cnn-m4", "source": "test",
                           "file": "chipbench/configs/ds-cnn-m4.json",
                           "reduced": [], "why": "test"})
    for name, wl in cells.items():
        (tmp / "chipbench" / "workloads" / f"{name}.json").write_text(
            json.dumps({"config": "ds-cnn-m4", **wl}))
        man["workloads"].append({"name": name, "config": "ds-cnn-m4",
                                 "traffic": name, "chips": 1, "why": "test"})
        for m in man["end_to_end"]:
            if "infer_per_s" == m["name"]:
                m["workloads"].append(name)
    if stream_cells and not any(c["name"] == "ds-cnn-stream-m4"
                                for c in man["configs"]):
        man["configs"].append({"name": "ds-cnn-stream-m4", "source": "test",
                               "file": "chipbench/configs/"
                                       "ds-cnn-stream-m4.json",
                               "reduced": [], "why": "test"})
    for name in stream_cells:
        if not any(w["name"] == name for w in man["workloads"]):
            man["workloads"].append({"name": name, "traffic": name,
                                     "config": "ds-cnn-stream-m4",
                                     "chips": 1, "why": "test"})
    path = tmp / "BENCHMARK.json"
    path.write_text(json.dumps(man))
    return path


def eval_cell(batch: int = 4, backend: str = "jnp") -> dict:
    return {"mode": "eval", "batch": batch, "backend": backend,
            "loop": "closed", "bank": 2, "check_samples": 6,
            "limit": 0.2, "why": "test"}


def run_cell(manifest: Path, name: str, seed: int = 3, seconds: float = 0.5,
             monkeypatch=None) -> dict:
    """One run of a cell on the CPU (the chip check skipped, the
    persistent compile cache left off)."""
    import importlib

    from chipbench import run

    cache = importlib.import_module("repro.compile.cache")
    monkeypatch.setattr(cache, "use_compile_cache", lambda: None)
    bench = run.Bench(manifest.parent / "chipbench", manifest)
    return run.main(["--workload", name, "--seed", str(seed), "--seconds",
                     str(seconds)], bench=bench, require_chip=False)
