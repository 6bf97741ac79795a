#!/usr/bin/env python3
"""Readings of the lower-precision control, for setting a cell's limit.

The control is the plain reference put in the program's place and run
in int4 (the precision below the configuration's int8): tables from its
own calibration on the deployment's calibration inputs, compared with
the float reference on the cell's seeded inputs by the same number the
run checks (``max_rel_err``).  A limit must sit below every reading.

    python chipbench/control.py --workload <cell> --seeds 1,2,3

Prints one JSON line: ``{"workload", "readings": {seed: value}}``.
The benchmark's own runs never run it.
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


def int4_deployment(bench, workload: str):
    """``(layers, host weights, int4 tables)`` of the cell's deployment."""
    from chipbench import common
    from chipbench import reference as ref

    wl = bench.cell(workload)
    cfg = bench.config(wl["config"])
    layers = common.net_layers(cfg)
    weights = common.host_weights(
        common.make_weights(layers, cfg["deployment_seed"]))
    q = ref.calibrate(layers, weights,
                      bench.mode(wl["mode"]).calibration_inputs(cfg, wl),
                      bits=4)
    return layers, weights, q


def reading(bench, workload: str, seed: int) -> float:
    from chipbench import reference as ref

    wl = bench.cell(workload)
    cfg = bench.config(wl["config"])
    layers, weights, q = int4_deployment(bench, workload)
    xs = bench.mode(wl["mode"]).reference_inputs(cfg, wl, seed,
                                                  wl["check_samples"])
    return ref.max_rel_err([ref.int_forward(layers, q, x) for x in xs],
                           [ref.float_forward(layers, weights, x) for x in xs])


def main(argv=None) -> dict:
    from chipbench.run import Bench

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    bench = Bench()
    seeds = [int(s) for s in args.seeds.split(",")]
    out = {"workload": args.workload,
           "readings": {s: reading(bench, args.workload, s) for s in seeds}}
    print(json.dumps(out), flush=True)
    return out


if __name__ == "__main__":
    main()
