"""Closed-loop stream replay: one ``StreamSession`` stepped through a
seeded sequence of host float32 frames that wraps many times.

Workload keys: ``backend``, ``frames`` (length of the seeded sequence),
``check_samples`` (steps compared with the reference after the window,
drawn from those whose window holds only real frames) and ``limit``
(largest relative error of a sampled step output against the float
reference over that step's window).  No two different windows may get the
same answer (``shared_answers``).  The deployment (weights and the
calibration clip) comes from the configuration's ``deployment_seed``;
the run's seed draws the replayed frames.
"""
from __future__ import annotations

import numpy as np

import repro
from chipbench import common
from chipbench import reference as ref


def inputs(cfg: dict, wl: dict, seed: int) -> np.ndarray:
    """The seeded host float32 frame sequence (the traffic)."""
    _, w, c = cfg["widths"]["input"]
    rng = np.random.default_rng(seed)
    return rng.standard_normal((wl["frames"], w, c), np.float32)


def window(frames: np.ndarray, h_win: int, t: int) -> np.ndarray:
    """The ``[h_win, w, c]`` window a session holds after step ``t``
    (``t >= h_win - 1``) of a replay of ``frames``."""
    return frames[np.arange(t - h_win + 1, t + 1) % len(frames)]


def reference_inputs(cfg: dict, wl: dict, seed: int, k: int) -> list:
    """``k`` full windows of the replay."""
    frames = inputs(cfg, wl, seed)
    h_win = cfg["widths"]["input"][0]
    rng = np.random.default_rng([seed, 1])
    return [window(frames, h_win, h_win + int(t))
            for t in common.sample(rng, len(frames), k)]


def calibration_clip(cfg: dict) -> np.ndarray:
    """The deployment's calibration clip, ``[h_win + n_calib - 1, w,
    c]``: consecutive frames whose ``n_calib`` full windows are the
    calibration.  The program is handed the whole clip."""
    h_win, w, c = cfg["widths"]["input"]
    rng = np.random.default_rng(cfg["deployment_seed"])
    return rng.standard_normal(
        (h_win + cfg["assumed"]["n_calib"] - 1, w, c), np.float32)


def calibration_inputs(cfg: dict, wl: dict) -> list:
    """The clip's full windows, ``[h_win, w, c]`` each: what a reference
    calibrates on."""
    clip, h_win = calibration_clip(cfg), cfg["widths"]["input"][0]
    return [clip[t:t + h_win] for t in range(len(clip) - h_win + 1)]


class Cell:
    def __init__(self, cfg: dict, wl: dict, seed: int, layers: list):
        self.layers, self.seed = layers, seed
        self.batch, self.backend = 1, wl["backend"]
        self.samples, self.limit = wl["check_samples"], wl["limit"]
        self.h_win = cfg["widths"]["input"][0]
        self.frames = inputs(cfg, wl, seed)
        self.weights = common.make_weights(layers, cfg["deployment_seed"])
        self.cn = repro.compile(cfg["net"], cfg["target"],
                                dtype=cfg["dtype"], streaming=True,
                                params=self.weights,
                                calib=calibration_clip(cfg))
        common.check_program(self.cn.program, layers)
        self.passes_s = sum(p.seconds for p in self.cn.passes)
        self.session = self.cn.stream(backend=self.backend)
        self.outs: list = []

    def warm(self) -> None:
        for f in self.frames[:2]:
            self.session.step(f).block_until_ready()
        self.session.reset()

    def step(self, i: int) -> int:
        y = self.session.step(self.frames[i % len(self.frames)])
        y.block_until_ready()
        self.outs.append(y)
        return 1

    def check(self) -> tuple[dict, int]:
        outs = [np.asarray(y).reshape(-1) for y in self.outs]
        self.outs = []
        failed = sum(int(not np.isfinite(o).all()) for o in outs)
        full = max(0, len(outs) - self.h_win)
        if not full:       # no step past the window: nothing to judge
            return {"max_rel_err": (float("inf"), self.limit)}, failed
        rng = np.random.default_rng([self.seed, 1])
        picks = self.h_win + common.sample(rng, full, self.samples)
        wts = common.host_weights(self.weights)
        got = [outs[t] for t in picks]
        want = [ref.float_forward(self.layers, wts,
                                  window(self.frames, self.h_win, int(t)))
                for t in picks]
        n = len(self.frames)
        keys = [t % n if t >= self.h_win - 1 else -1 - t
                for t in range(len(outs))]
        return {"max_rel_err": (ref.max_rel_err(got, want), self.limit),
                "shared_answers": (common.shared_answers(outs, keys), 0)
                }, failed
