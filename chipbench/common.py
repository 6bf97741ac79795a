"""Pieces the traffic modes share: the network's layers, seeded weights
on the device, and the hand-over of those weights to the program."""
from __future__ import annotations

import functools
import importlib.util
import math
from pathlib import Path

import numpy as np

from chipbench import reference as ref

NETS = Path(__file__).resolve().parent / "nets"


@functools.cache
def _family(path: Path):
    spec = importlib.util.spec_from_file_location(
        "chipbench_nets_" + path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    ref.add_kinds(getattr(mod, "KINDS", {}))
    return mod


def net_layers(cfg: dict, nets: Path = NETS) -> list[dict]:
    """The configuration's layers, from ``<nets>/<family>.py``; the
    family's own layer kinds (its ``KINDS``) join the reference's
    table."""
    return _family(Path(nets) / f"{cfg['family']}.py").layers(cfg["widths"])


def seed_key(seed: int):
    """A PRNG key that keeps every bit of ``seed`` (``PRNGKey`` alone
    keeps the low 32)."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def make_weights(layers: list[dict], seed: int) -> list:
    """Float32 ``(w, b)`` per layer (``None`` where its kind has no
    weights), made on the device in one jitted call from the seed:
    normal weights scaled by ``1/sqrt(fan_in)`` (``sqrt 2`` more before
    a relu), biases of std 0.1."""
    import jax

    kinds = [ref.kind_of(layer) for layer in layers]
    shapes = [k.weight_shape(layer) for k, layer in zip(kinds, layers)]

    @jax.jit
    def gen(key):
        out = []
        for i, (layer, shp) in enumerate(zip(layers, shapes)):
            if shp is None:
                out.append(None)
                continue
            kw, kb = jax.random.split(jax.random.fold_in(key, i))
            fan_in = kinds[i].fan_in(layer, shp)
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
    got = [(op.kind, op.d_in, op.d_out) for op in program.ops]
    want = [(lr["kind"], lr["c_in"], lr["c_out"]) for lr in layers]
    if len(got) != len(want) or any(
            g[0] not in ref.kind_of(lr).program or g[1:] != w[1:]
            for g, w, lr in zip(got, want, layers)):
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
