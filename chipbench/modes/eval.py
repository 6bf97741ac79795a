"""Closed-loop evaluation: ``CompiledNet.run`` over a bank of seeded
host float32 batches, one call after another.

Workload keys: ``batch`` (1 runs one ``[rows, d]`` image per call,
otherwise a ``[batch, rows, d]`` stack), ``backend``, ``bank`` (distinct
input batches, cycled), ``check_samples`` (outputs compared with the
reference after the window) and ``limit`` (largest relative error of a
sampled output against the float reference).  Besides, the same input
must get the same answer every time (``repeat_mismatch``) and no two
inputs the same answer (``shared_answers``).  The deployment (weights
and calibration images) comes from the configuration's
``deployment_seed``, the same in every run; the run's seed draws the
traffic.
"""
from __future__ import annotations

import numpy as np

import repro
from chipbench import common
from chipbench import reference as ref


def inputs(cfg: dict, wl: dict, seed: int) -> list:
    """The seeded host float32 input batches (the traffic)."""
    h, w, c = cfg["widths"]["input"]
    rng = np.random.default_rng(seed)
    lead = () if wl["batch"] == 1 else (wl["batch"],)
    return [rng.standard_normal(lead + (h * w, c), np.float32)
            for _ in range(wl["bank"])]


def reference_inputs(cfg: dict, wl: dict, seed: int, k: int) -> list:
    """``k`` of the traffic's inputs as ``[h, w, c]`` images."""
    shape = tuple(cfg["widths"]["input"])
    flat = np.concatenate([b.reshape((-1,) + shape)
                           for b in inputs(cfg, wl, seed)])
    rng = np.random.default_rng([seed, 1])
    return [flat[i] for i in common.sample(rng, len(flat), k)]


def calibration_inputs(cfg: dict, wl: dict) -> list:
    """The deployment's calibration images, as ``[h, w, c]``."""
    rng = np.random.default_rng(cfg["deployment_seed"])
    return list(rng.standard_normal(
        (cfg["assumed"]["n_calib"], *cfg["widths"]["input"]), np.float32))


class Cell:
    def __init__(self, cfg: dict, wl: dict, seed: int, layers: list):
        self.layers, self.seed = layers, seed
        self.batch, self.backend = wl["batch"], wl["backend"]
        self.samples, self.limit = wl["check_samples"], wl["limit"]
        self.bank = inputs(cfg, wl, seed)
        self.weights = common.make_weights(layers, cfg["deployment_seed"])
        h, w, c = cfg["widths"]["input"]
        calib = np.stack(calibration_inputs(cfg, wl)).reshape(-1, h * w, c)
        self.cn = repro.compile(cfg["net"], cfg["target"],
                                dtype=cfg["dtype"], params=self.weights,
                                calib=calib)
        common.check_program(self.cn.program, layers)
        self.passes_s = sum(p.seconds for p in self.cn.passes)
        self.outs: list = []

    def warm(self) -> None:
        for _ in range(2):
            self.cn.run(self.bank[0], backend=self.backend).block_until_ready()

    def step(self, i: int) -> int:
        y = self.cn.run(self.bank[i % len(self.bank)], backend=self.backend)
        y.block_until_ready()
        self.outs.append(y)
        return self.batch

    def check(self) -> tuple[dict, int]:
        """``({name: (value, limit)}, failed)`` over the window's outputs."""
        outs = [np.asarray(y).reshape(self.batch, -1) for y in self.outs]
        self.outs = []
        failed = sum(int((~np.isfinite(o)).any(axis=1).sum()) for o in outs)
        n_bank = len(self.bank)
        repeats = sum(int(not np.array_equal(o, outs[i % n_bank]))
                      for i, o in enumerate(outs))
        rng = np.random.default_rng([self.seed, 1])
        picks = common.sample(rng, len(outs) * self.batch, self.samples)
        wts = common.host_weights(self.weights)
        h, w, c = self.layers[0]["h"], self.layers[0]["w"], \
            self.layers[0]["c_in"]
        got, want = [], []
        for p in picks:
            call, row = divmod(int(p), self.batch)
            x = self.bank[call % n_bank].reshape(self.batch, h, w, c)[row]
            got.append(outs[call][row])
            want.append(ref.float_forward(self.layers, wts, x))
        shared = common.shared_answers(
            [row for o in outs for row in o],
            [(i % n_bank, r) for i in range(len(outs))
             for r in range(self.batch)])
        return {"max_rel_err": (ref.max_rel_err(got, want), self.limit),
                "repeat_mismatch": (repeats, 0),
                "shared_answers": (shared, 0)}, failed
