#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name: the cell in ``BENCHMARK.json`` and
``workloads/<cell>.json``, its configuration in ``configs/``, its traffic
mode in ``modes/<mode>.py``, and each metric's reader in ``end_to_end/``
or ``layer_metrics/`` (``<name>.py``, else the file named by the part of
the name before its last dot).  Adding a cell, configuration, mode or
metric adds files and entries; this file does not change.

A mode file defines ``Cell(cfg, workload, seed, layers)``, which sets the
cell up and has ``batch``, ``passes_s``, ``warm()``, ``step(i)`` (one
timed call; returns the items it completed) and ``check()`` (``({name:
(value, limit)}, failed)`` after the window), and, for
``chipbench/control.py``, ``reference_inputs`` and
``calibration_inputs``.  A reader file defines ``read(record, trace)``
and returns ``None`` where it finds nothing to read; ``trace`` is
:func:`reduce_trace` of the traced window.

A run: check for the chip (off a TPU, or with fewer chips than the cell
asks, it exits 2 and prints no result); turn on JAX's persistent compile
cache; set up the cell (traffic from the seed; the configuration's
deployment: weights made on the device, ``repro.compile``), warm it up; measure a closed loop for ``--seconds``
(``--trace 1`` under the profiler); read the device's peak memory;
compare sampled outputs with the plain reference; print the numbers
compared on standard error and, last on standard output, one JSON line.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


TRACE_SECONDS = 2.0


class Bench:
    """The manifest and the data directories beside this file."""

    def __init__(self, bench_dir: Path = HERE,
                 manifest: Path = ROOT / "BENCHMARK.json"):
        self.dir = Path(bench_dir)
        self.root = self.dir.parent
        self.manifest = json.loads(Path(manifest).read_text())

    def _entry(self, key: str, name: str) -> dict:
        for e in self.manifest[key]:
            if e["name"] == name:
                return e
        raise KeyError(f"{name!r} is not in BENCHMARK.json {key}")

    def cell(self, name: str) -> dict:
        entry = self._entry("workloads", name)
        wl = json.loads((self.dir / "workloads" / f"{name}.json")
                        .read_text())
        if wl["config"] != entry["config"]:
            raise ValueError(f"{name}: workload file names config "
                             f"{wl['config']!r}, BENCHMARK.json "
                             f"{entry['config']!r}")
        return {**wl, "chips": entry["chips"]}

    def config(self, name: str) -> dict:
        return json.loads((self.root / self._entry("configs", name)["file"])
                          .read_text())

    def layers(self, cfg: dict) -> list[dict]:
        from chipbench import common

        return common.net_layers(cfg, self.dir / "nets")

    def mode(self, name: str):
        return load_module(self.dir / "modes" / f"{name}.py")

    def metrics(self, cell: str, trace: bool) -> list[dict]:
        """The cell's metrics for this kind of run, in manifest order."""
        key = "per_layer" if trace else "end_to_end"
        return [m for m in self.manifest[key]
                if cell in m.get("workloads", [cell])]

    def reader(self, metric: str, trace: bool):
        sub = self.dir / ("layer_metrics" if trace else "end_to_end")
        path = sub / f"{metric}.py"
        if not path.exists():
            path = sub / f"{metric.rsplit('.', 1)[0]}.py"
        return load_module(path)


def device_check(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"chipbench: needs {chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform!r} device(s) "
              f"({devs[0].device_kind})", file=sys.stderr)
        raise SystemExit(2)
    return devs


def compile_counter():
    """A list that grows by one for each XLA compile from now on."""
    import jax

    seen: list = []

    def on_event(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(duration)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    return seen


def measure(cell, seconds: float, trace_dir: str | None = None) -> dict:
    """Closed loop: each call starts when the previous one has
    returned; the window ends with the first call that ends after
    ``seconds``.  With ``trace_dir`` the profiler records the calls that
    start in the window's last ``TRACE_SECONDS`` (Python tracing off),
    under the ``chipbench.window`` annotation, and ``traced`` counts
    them."""
    import jax
    from jax.profiler import TraceAnnotation

    durations, items, traced, window = [], 0, None, None
    t0 = time.perf_counter()
    end = t0 + seconds
    t = t0
    while t < end:
        if trace_dir and traced is None and t >= end - TRACE_SECONDS:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            window = TraceAnnotation("chipbench.window")
            window.__enter__()
            traced = {"calls": len(durations), "items": items, "t0": t}
        with TraceAnnotation("chipbench.call"):
            items += cell.step(len(durations))
        now = time.perf_counter()
        durations.append(now - t)
        t = now
    if traced:
        window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        traced = {"calls": len(durations) - traced["calls"],
                  "items": items - traced["items"],
                  "elapsed_s": t - traced["t0"]}
    return {"durations_s": durations, "calls": len(durations),
            "items": items, "elapsed_s": t - t0, "traced": traced}


def reduce_trace(path) -> dict:
    """The device trace (``trace.reduce``) and the program's spans
    (``spans.reduce``) of one ``.xplane.pb``, in one dict."""
    from chipbench import spans
    from chipbench import trace as tr

    return {**tr.reduce(path), **spans.reduce(path)}


def main(argv=None, *, bench: Bench | None = None,
         require_chip: bool = True) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = bench or Bench()
    wl = bench.cell(args.workload)
    cfg = bench.config(wl["config"])

    import jax

    devs = device_check(wl["chips"]) if require_chip else jax.devices()
    from chipbench import work
    from repro.compile.cache import use_compile_cache

    use_compile_cache()
    layers = bench.layers(cfg)
    mode = bench.mode(wl["mode"])
    cell = mode.Cell(cfg, wl, args.seed, layers)
    cell.warm()
    setup_s = time.perf_counter() - T_START

    compiles = compile_counter()
    tmp = tempfile.TemporaryDirectory(prefix="chipbench-trace-") \
        if args.trace else None
    rec = measure(cell, args.seconds, tmp.name if tmp else None)
    window_compiles = len(compiles)
    peak_mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs)
    kind = devs[0].device_kind
    rec.update(setup_s=setup_s, passes_s=cell.passes_s)
    trace = None
    if tmp:
        from chipbench import trace as tr

        trace = reduce_trace(tr.find_xplane(tmp.name))
        tmp.cleanup()
        peak = work.peaks(kind)
        least = work.least_time(layers, cell.batch, peak)
        rec.update(least_call_s=sum(t for _, t, _ in least),
                   least_layers=[[name, layer["kind"], t] for (name, t, _),
                                 layer in zip(least, layers)],
                   macs_per_item=sum(work.macs(lr) for lr in layers),
                   peak_ops_per_s=peak["int8_ops_per_s"])
        bounds = [b for _, _, b in least]
        print(f"least time per call {rec['least_call_s']!r} s; layers "
              f"bound by compute {bounds.count('compute')}, by memory "
              f"{bounds.count('memory')}", file=sys.stderr)

    checks, failed = cell.check()
    correct = failed == 0 and all(v <= lim for v, lim in checks.values())
    metrics = {}
    for m in bench.metrics(args.workload, bool(args.trace)):
        value = bench.reader(m["name"], bool(args.trace)).read(rec, trace)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": peak_mem}
    result = {"correct": correct, "attempted": rec["items"],
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    result["window_compiles"] = window_compiles
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    print(f"calls {rec['calls']} items {rec['items']} in "
          f"{rec['elapsed_s']:.3f} s; setup {setup_s:.3f} s; compiles in "
          f"window {window_compiles}", file=sys.stderr)
    for k, (v, lim) in checks.items():
        print(f"check {k} {v!r} limit {lim!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    main()
