"""Streaming inference driver: persistent temporal state on the ring.

A :class:`StreamSession` owns ONE pool across invocations.  Each
``step(frame)`` stages only the new frame, executes the compiled
program — whose ``conv_stream`` / ``gru_cell`` ops shift their
ring-resident state and consume the frame — and fetches the step
output.  The state regions live wrap-free above the frame program's
linear extent (``core.program`` placement), so frame traffic can never
alias them; the static verifier certifies exactly that, and the sim
backend re-proves it step by step with live clobber detection.

Backends:

  * ``jnp`` / ``pallas`` — numeric execution on a persistent
    :class:`~repro.core.vpool.VirtualPool` (zero-initialized state ==
    the reference conv's zero padding, so outputs match the one-shot
    net exactly once the window has filled),
  * ``sim`` — the byte oracle: numerics-free, but every step replays
    the schedule through :class:`~repro.core.pool.SegmentPool` with the
    state records still live under their ``("state", i, j)`` owners —
    an N-step run is N independent clobber proofs plus the carried
    state-survival invariant.

``trace=True`` threads a :class:`repro.obs.RingTracer` through every
step (PR-7 observability: per-op wall times + byte traffic per frame);
the artifacts accumulate in :attr:`traces`.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..core.executors import execute, run_program_sim
from ..core.vpool import VirtualPool
from ..obs.spans import span
from ..quant import QParams, dequantize, host_array, quantize


class StreamSession:
    """Reset/step driver over one compiled streaming net.

    Built by :meth:`repro.compile.CompiledNet.stream`; holds the pool
    (the persistent state) between ``step`` calls."""

    def __init__(self, compiled, *, backend: str = "jnp",
                 trace: bool = False):
        self.compiled = compiled
        self.backend = backend
        self.trace = trace
        self.quantized = compiled.quantized
        if self.quantized:
            self.program = compiled.qnet.program
            self.params = compiled.qnet.qparams
            self.in_scale = compiled.qnet.in_scale
            self.out_scale = compiled.qnet.out_scale
        else:
            if compiled.program.quantized:
                from ..compile.driver import CompileError

                raise CompileError(
                    "planner-only int8 compile: no qparams to stream "
                    "with — recompile with quantize=True")
            self.program = compiled.program
            self.params = compiled.ensure_params()
        if not any(op.state_segments for op in self.program.ops):
            raise ValueError(
                f"{compiled.net_name!r} has no stream state — compile "
                "with streaming=True (or a conv_stream/gru_cell graph)")
        self.traces: list = []
        self.reset()

    # -- state lifecycle ---------------------------------------------------
    def reset(self) -> "StreamSession":
        """Zero every state region and restart the step counter.

        Zero state is the semantic origin: a ``conv_stream`` window of
        zeros IS the reference conv's zero padding, so the first
        ``h_win`` steps reproduce a one-shot net seeing the partially
        filled window."""
        self.steps = 0
        if self.backend == "sim":
            self._pool = None      # run_program_sim pre-writes the state
        else:
            dtype = jnp.int8 if self.program.quantized else jnp.float32
            self._pool = VirtualPool.alloc(self.program.spec(dtype))
        return self

    # -- one frame ---------------------------------------------------------
    def step(self, frame=None):
        """Advance one frame.

        ``frame`` is ``[rows_in, d_in]`` (or anything reshapeable to
        it).  Float frames through a quantized net quantize on entry
        and dequantize on exit; an int8 frame is treated as already
        quantized and the raw int8 output is returned (the bitwise
        cross-backend contract).  The ``sim`` backend ignores numerics
        (pass ``frame=None``) and returns the oracle's counters.

        On the array backends a step is one ``vmcu.step`` span (attrs
        ``backend``, ``state_bytes``), with the ``vmcu.*`` spans of its
        layers inside (DESIGN.md §12)."""
        program = self.program
        tracer = None
        if self.trace:
            from ..obs import RingTracer

            tracer = RingTracer()

        if self.backend == "sim":
            sim = run_program_sim(program, pool=self._pool, tracer=tracer)
            # the session consumes the step output; its record must die
            # before the next frame is staged over it
            last = program.ops[-1]
            for j in range(last.out_segments):
                sim.free(last.out_ptr + j, owner=(len(program.ops), j))
            self._pool = sim
            self.steps += 1
            self._finish_trace(tracer)
            return {"reads": sim.reads, "writes": sim.writes,
                    "frees": sim.frees, "peak_live": sim.peak_live,
                    "live": sim.live, "steps": self.steps}

        if frame is None:
            raise ValueError("array backends need a frame per step")
        with span("vmcu.step", backend=self.backend,
                  state_bytes=self.state_bytes):
            return self._step(frame, tracer)

    def _step(self, frame, tracer):
        """One array-backend step, its layers as the ``vmcu.*`` spans of
        a ``CompiledNet.run`` call (DESIGN.md §12)."""
        program = self.program
        shape = (program.ops[0].rows_in, program.in_dim)
        quantize_frame = program.quantized and \
            getattr(frame, "dtype", None) != jnp.int8
        if quantize_frame:
            # the frame's put is the first step of its quantize
            with span("vmcu.quantize", on="host"):
                frame = quantize(jnp.asarray(frame).reshape(shape),
                                 QParams(scale=self.in_scale))
        with span("vmcu.stage"):
            frame = jnp.asarray(frame, self._pool.array.dtype) \
                .reshape(shape)
            pool = self._pool.stage_rows(frame, program.input_ptr)
        with span("vmcu.ring"):
            pool = execute(program, pool, self.params,
                           backend=self.backend, tracer=tracer)
        with span("vmcu.fetch"):
            y = pool.fetch_rows(program.output_ptr, program.out_rows,
                                program.out_dim)
        self._pool = pool
        self.steps += 1
        self._finish_trace(tracer)
        if quantize_frame:
            with span("vmcu.dequantize"):
                y = dequantize(host_array(y, np.float64),
                               QParams(scale=self.out_scale))
        return y

    def run(self, frames):
        """Feed ``frames`` (an iterable of per-step inputs) and return
        the last step's output — the streaming analogue of ``.run`` on
        the full window."""
        y = None
        for f in frames:
            y = self.step(f)
        return y

    # -- observability -----------------------------------------------------
    def _finish_trace(self, tracer) -> None:
        if tracer is None:
            return
        from ..obs import build_trace

        self.traces.append(build_trace(
            self.program, tracer=tracer, backend=self.backend,
            net=self.compiled.net_name, target=self.compiled.target.name))

    @property
    def state_segments(self) -> int:
        """Ring segments held by persistent state (the certified class)."""
        return sum(op.state_segments for op in self.program.ops)

    @property
    def state_bytes(self) -> int:
        return self.state_segments * self.program.seg_width \
            * self.program.elem_bytes
