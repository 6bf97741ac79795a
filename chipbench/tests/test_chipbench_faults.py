"""A whole run with the timed path broken underneath must come out
``correct: false``; the same run unbroken comes out true."""
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_testlib import eval_cell, run_cell, small_bench

from repro.compile.driver import CompiledNet

_run = CompiledNet.run


def _half_batch(self, x, **kw):
    """The second half of the batch left out: the first half's answers
    stand in for it."""
    y = _run(self, x, **kw)
    half = y.shape[0] // 2
    return jnp.concatenate([y[:half], y[:y.shape[0] - half]])


def _altered(self, x, **kw):
    """Every answer altered where it is produced: its class scores one
    place off."""
    return jnp.roll(_run(self, x, **kw), 1, axis=-1)


def _stale(self, x, **kw):
    """The call returns the previous call's answer (state left unchanged)."""
    y = _run(self, x, **kw)
    prev = getattr(self, "_last_answer", y)
    self._last_answer = y
    return prev


def _int4_control(bench, name):
    """The int4 control in the program's place: every answer is the plain
    reference's, run on int4 tables of the same deployment."""
    from chipbench import control
    from chipbench import reference as ref

    layers, _, q = control.deployment(bench, name)
    shape = (layers[0]["h"], layers[0]["w"], layers[0]["c_in"])

    def run(self, x, **kw):
        y = _run(self, x, **kw)
        ys = [ref.int_forward(layers, q, img.reshape(shape))
              for img in np.asarray(x).reshape((-1,) + shape)]
        return jnp.asarray(np.stack(ys).reshape(y.shape), y.dtype)
    return run


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    return small_bench(tmp_path_factory.mktemp("bench"),
                       {"kws-eval-b4": eval_cell(batch=4)})


def test_sound_run_is_correct(manifest, monkeypatch):
    out = run_cell(manifest, "kws-eval-b4", monkeypatch=monkeypatch)
    assert out["correct"], out["checks"]


# fault name -> the broken ``CompiledNet.run``, given the cell's Bench
FAULTS = {"half_batch": lambda bench: _half_batch,
          "altered": lambda bench: _altered,
          "stale": lambda bench: _stale,
          "int4_control": lambda bench: _int4_control(bench, "kws-eval-b4")}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_broken_timed_path_is_not_correct(manifest, monkeypatch, fault):
    from chipbench import run

    bench = run.Bench(manifest.parent / "chipbench", manifest)
    monkeypatch.setattr(CompiledNet, "run", FAULTS[fault](bench))
    out = run_cell(manifest, "kws-eval-b4", monkeypatch=monkeypatch)
    assert not out["correct"], out["checks"]


def test_int4_control_reads_above_the_limit(manifest):
    from chipbench import control, run

    bench = run.Bench(manifest.parent / "chipbench", manifest)
    limit = bench.cell("kws-eval-b4")["limit"]
    readings = [control.reading(bench, "kws-eval-b4", seed)
                for seed in (3, 4, 5)]
    assert min(readings) > limit, readings
