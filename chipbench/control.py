#!/usr/bin/env python3
"""Readings of the plain reference in the program's place, for setting a
cell's limit.

The reference runs in ``--bits`` integer arithmetic on tables from its
own calibration on the deployment's calibration inputs, and is compared
with the float reference on the cell's seeded inputs by the same number
the run checks (``max_rel_err``).  With ``--bits 4`` (the default, the
precision below the configuration's int8) it is the control: a limit
must sit below every reading.  With ``--bits 8`` it reads what a sound
int8 program calibrated the same way should reach: a limit must sit
above every reading.

    python chipbench/control.py --workload <cell> --seeds 1,2,3 \
        [--bits 4] [--manifest BENCHMARK.json]

Prints one JSON line: ``{"workload", "bits", "readings": {seed:
value}}``.  The benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def deployment(bench, workload: str, bits: int = 4):
    """``(layers, host weights, bits-wide tables)`` of the cell's
    deployment."""
    from chipbench import common
    from chipbench import reference as ref

    wl = bench.cell(workload)
    cfg = bench.config(wl["config"])
    layers = bench.layers(cfg)
    weights = common.host_weights(
        common.make_weights(layers, cfg["deployment_seed"]))
    q = ref.calibrate(layers, weights,
                      bench.mode(wl["mode"]).calibration_inputs(cfg, wl),
                      bits=bits)
    return layers, weights, q


def reading(bench, workload: str, seed: int, bits: int = 4) -> float:
    from chipbench import reference as ref

    wl = bench.cell(workload)
    cfg = bench.config(wl["config"])
    layers, weights, q = deployment(bench, workload, bits)
    xs = bench.mode(wl["mode"]).reference_inputs(cfg, wl, seed,
                                                  wl["check_samples"])
    return ref.max_rel_err([ref.int_forward(layers, q, x) for x in xs],
                           [ref.float_forward(layers, weights, x) for x in xs])


def main(argv=None) -> dict:
    from chipbench.run import Bench

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--bits", type=int, default=4)
    ap.add_argument("--manifest", type=Path, default=ROOT / "BENCHMARK.json")
    args = ap.parse_args(argv)
    bench = Bench(manifest=args.manifest)
    seeds = [int(s) for s in args.seeds.split(",")]
    out = {"workload": args.workload, "bits": args.bits,
           "readings": {s: reading(bench, args.workload, s, args.bits)
                        for s in seeds}}
    print(json.dumps(out), flush=True)
    return out


if __name__ == "__main__":
    main()
