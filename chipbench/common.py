"""Pieces the traffic modes share: the network's layers, seeded weights
on the device, and the hand-over of those weights to the program."""
from __future__ import annotations

import importlib
import math

import numpy as np

from chipbench.reference import weight_shape

# program op kind -> reference layer kind
_KINDS = {"conv_pw": "conv", "conv_k2d": "conv", "conv_stream": "conv",
          "conv_dw": "dw", "add": "add", "pool_avg": "avgpool",
          "gemm": "fc"}


def net_layers(cfg: dict) -> list[dict]:
    """The configuration's layers, from ``chipbench/nets/<family>.py``."""
    family = importlib.import_module(f"chipbench.nets.{cfg['family']}")
    return family.layers(cfg["widths"])


def seed_key(seed: int):
    """A PRNG key that keeps every bit of ``seed`` (``PRNGKey`` alone
    keeps the low 32)."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def make_weights(layers: list[dict], seed: int) -> list:
    """Float32 ``(w, b)`` per layer (``None`` for add/pool), made on the
    device in one jitted call from the seed: normal weights scaled by
    ``1/sqrt(fan_in)`` (``sqrt 2`` more before a relu), biases of std
    0.1."""
    import jax

    shapes = [weight_shape(layer) for layer in layers]

    @jax.jit
    def gen(key):
        out = []
        for i, (layer, shp) in enumerate(zip(layers, shapes)):
            if shp is None:
                out.append(None)
                continue
            kw, kb = jax.random.split(jax.random.fold_in(key, i))
            fan_in = math.prod(shp[:-1]) if layer["kind"] != "dw" \
                else shp[0] * shp[1]
            gain = math.sqrt(2.0) if layer.get("relu") else 1.0
            w = jax.random.normal(kw, shp) * (gain / math.sqrt(fan_in))
            b = 0.1 * jax.random.normal(kb, (layer["c_out"],))
            out.append((w, b))
        return out

    return gen(seed_key(seed))


def host_weights(weights: list) -> list:
    return [None if wb is None else tuple(np.asarray(a, np.float64)
                                          for a in wb) for wb in weights]


def check_program(program, layers: list[dict]) -> None:
    """The program runs the configuration's layers, in this order, at
    these widths: the weights were handed over in that order."""
    ops = program.ops
    got = [(_KINDS.get(op.kind, op.kind), op.d_in, op.d_out) for op in ops]
    want = [(lr["kind"], lr["c_in"], lr["c_out"]) for lr in layers]
    if got != want:
        raise RuntimeError(f"program ops {got} do not match the "
                           f"configuration's layers {want}")


def sample(rng: np.random.Generator, n: int, k: int) -> np.ndarray:
    """``min(k, n)`` distinct indices of ``range(n)``, sorted."""
    return np.sort(rng.choice(n, size=min(k, n), replace=False))


def shared_answers(answers, keys) -> int:
    """How many answers are bit for bit an answer given to a different
    input (``keys`` names each answer's input): a substituted or stale
    answer, which the sampled comparison may not draw."""
    seen: dict = {}
    for a, k in zip(answers, keys):
        seen.setdefault(np.ascontiguousarray(a).tobytes(), set()).add(k)
    return sum(len(ks) - 1 for ks in seen.values())
