"""The one-call deployment driver: ``repro.compile(net, target)``.

The paper's value proposition is an end-to-end flow — model in,
segment-ring plan + MCU kernels out.  This driver packages the repo's
previously hand-wired steps (``build_* -> reorder -> plan_net ->
quantize_net -> sim certify -> emit_program``) as a named pass pipeline
over a :class:`repro.compile.targets.Target` descriptor, DORY /
TinyEngine-style:

  ``build``     resolve the net (Graph or registered name) and validate,
  ``schedule``  operator reordering (branch-and-bound over topo orders),
  ``plan``      solve ONE segment ring for the whole net (Eq. 1/2),
  ``budget``    gate the byte-granular bottleneck on the target's SRAM
                (pure arithmetic — runs BEFORE the expensive passes so
                an over-budget net fails in milliseconds),
  ``quantize``  int8 calibration + requant tables (int8 targets),
  ``lint``      budget/consistency findings (``repro.analysis.lint``:
                VMCU3xx/4xx — errors abort, warnings ride in the note),
  ``certify``   prove the plan clobber-free.  ``certify="static"`` runs
                the abstract interpreter (``repro.analysis``) instead of
                replaying the schedule through the SegmentPool sim —
                same certificate, orders of magnitude faster — and falls
                back to the sim replay (recording why) on the rare
                program outside the decidable fragment.

The result is a :class:`CompiledNet`: ``.run(x)`` on any executor
backend, ``.emit_c(dir)`` for the intrinsic-C units, ``.report()`` for
footprint-vs-budget accounting, and ``.save()``/``.load()`` JSON plan
artifacts — deployment never re-runs the scheduler (DESIGN.md §9).

``plan_net`` / ``quantize_net`` remain importable as deprecated shims
over the same internals this driver calls.
"""
from __future__ import annotations

import dataclasses
import functools
import time

import numpy as np

from ..core.codegen import emit_program
from ..core.program import PoolProgram, dtype_itemsize
from ..graph.ir import (Graph, build_ad_autoencoder, build_ds_cnn,
                        build_mcunet, build_mobilenet_v1, build_resnet8)
from ..graph.netplan import NetPlan, _plan_net
from ..graph.run import (QuantizedNet, _quantize_net, certify_net,
                         init_net_params, run_net, run_net_quantized)
from ..graph.schedule import reorder
from ..obs.spans import SpanCollector, collect, span
from . import artifact
from .targets import Target, get_target

PASS_NAMES = ("build", "schedule", "plan", "budget", "partial",
              "quantize", "lint", "certify")

_UNSET = object()


class CompileError(Exception):
    """A pass of the compile pipeline failed."""


class SRAMBudgetError(CompileError):
    """The planned net does not fit the target's SRAM budget."""


# ---------------------------------------------------------------------------
# Net registry — names the CLI / benchmarks compile by.
# ---------------------------------------------------------------------------

def _vww() -> Graph:
    from ..core.graph_planner import MCUNET_5FPS_VWW

    return build_mcunet(MCUNET_5FPS_VWW, "mcunet-5fps-vww", num_classes=2)


def _imagenet() -> Graph:
    from ..core.graph_planner import MCUNET_320KB_IMAGENET

    return build_mcunet(MCUNET_320KB_IMAGENET, "mcunet-320kb-imagenet",
                        num_classes=1000)


def _ds_cnn_stream() -> Graph:
    from ..stream import to_streaming

    return to_streaming(build_ds_cnn())


# MLPerf-Tiny-class model zoo: real k x k spatial convs (conv_k2d)
# through the same one-ring planner as the MCUNet tables, plus the
# FC-heavy ToyADMOS anomaly-detection autoencoder and the per-frame
# streaming form of DS-CNN (persistent window state on the ring).
_NET_BUILDERS = {"mcunet-5fps-vww": _vww, "mcunet-320kb-imagenet": _imagenet,
                 "ds-cnn": build_ds_cnn, "resnet-8": build_resnet8,
                 "mobilenetv1-0.25": build_mobilenet_v1,
                 "ad-toyadmos": build_ad_autoencoder,
                 "ds-cnn-stream": _ds_cnn_stream}
_NET_ALIASES = {"mcunet-vww": "mcunet-5fps-vww",
                "mcunet-imagenet": "mcunet-320kb-imagenet",
                "dscnn": "ds-cnn", "resnet8": "resnet-8",
                "mobilenet-v1": "mobilenetv1-0.25",
                "toyadmos": "ad-toyadmos", "ad-ae": "ad-toyadmos",
                "dscnn-stream": "ds-cnn-stream"}


def available_nets() -> tuple[str, ...]:
    return tuple(sorted(_NET_BUILDERS))


def _resolve_net(net) -> Graph:
    if isinstance(net, Graph):
        return net
    if isinstance(net, str):
        name = _NET_ALIASES.get(net, net)
        try:
            return _NET_BUILDERS[name]()
        except KeyError:
            raise ValueError(f"unknown net {net!r}; known: "
                             f"{available_nets()}") from None
    raise TypeError(f"net must be a Graph or a registered name, got "
                    f"{type(net).__name__}")


# ---------------------------------------------------------------------------
# CompiledNet.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PassRecord:
    name: str
    seconds: float
    note: str = ""


def _nbytes(obj) -> int:
    """Total array bytes in a params/qparams structure (flash estimate)."""
    import numpy as np

    if obj is None or isinstance(obj, (bool, int, float, str)):
        return 0
    if isinstance(obj, (list, tuple)):
        return sum(_nbytes(v) for v in obj)
    return np.asarray(obj).nbytes


def _flash_param_bytes(program: PoolProgram,
                       parents: list[int] | None = None) -> int:
    """Analytic float-parameter storage (4 B/element, the init_net_params
    shapes) — lets ``report()`` account flash without materializing
    parameters on planner-only compiles.  ``parents`` (sliced programs)
    counts each unsliced op's parameters once across its slices."""
    total = 0
    seen: set[int] = set()
    for i, op in enumerate(program.ops):
        if parents is not None:
            if parents[i] in seen:
                continue
            seen.add(parents[i])
        if op.kind in ("gemm", "conv_pw"):
            total += op.d_in * op.d_out
        elif op.kind in ("conv_k2d", "conv_stream"):
            total += op.rs * op.rs * op.d_in * op.d_out
        elif op.kind == "gru_cell":
            total += (op.d_in + op.d_out) * 3 * op.d_out
        elif op.kind == "conv_dw":
            total += op.rs * op.rs * op.d_in
        elif op.kind == "ib_fused":
            total += (op.d_in * op.d_mid + op.rs * op.rs * op.d_mid
                      + op.d_mid * op.d_out)
        elif op.kind == "fused_mlp":
            total += 3 * op.d_in * op.d_ff
    return total * 4


@dataclasses.dataclass
class CompiledNet:
    """A deployed network: one solved ring + everything needed to run,
    emit, report and serialize it.

    ``program`` is the *executed* program (int8-typed for quantized
    targets); ``plan``/``graph`` carry the full NetPlan and IR when the
    net was compiled in-process and are ``None`` after :meth:`load`
    (the artifact is self-contained — ``mcu`` snapshots the
    byte-granular accounting)."""

    net_name: str
    target: Target
    dtype: str
    program: PoolProgram
    params: list | None        # lazily He-initialized (planner-only
                               # compiles never materialize parameters)
    qnet: QuantizedNet | None
    mcu: dict
    certificate: dict | None
    passes: list
    plan: NetPlan | None = None
    graph: Graph | None = None
    init_key: object = None    # PRNG key for lazy parameter init
    spans: list | None = None  # nested timed pipeline spans (obs.spans)
    partial: dict | None = None  # partial-execution accounting + parents

    # -- classification ----------------------------------------------------
    @property
    def quantized(self) -> bool:
        return self.qnet is not None

    @property
    def partial_parents(self) -> list[int] | None:
        """Sliced-op -> unsliced-op index map (``None`` when unsliced)."""
        if self.partial is None:
            return None
        return self.partial.get("parents")

    def ensure_params(self) -> list:
        """Materialize the float parameters on first need (run/save of a
        planner-only compile); quantized compiles already carry them."""
        if self.params is None:
            if self.plan is None:
                raise CompileError("no parameters in this CompiledNet "
                                   "and no plan to initialize them from")
            base = init_net_params(self.plan, self.init_key)
            parents = self.partial_parents
            self.params = (base if parents is None
                           else [base[p] for p in parents])
        return self.params

    # -- footprints --------------------------------------------------------
    @property
    def pool_bytes(self) -> int:
        """The executed ring footprint (bytes of pool state)."""
        return self.program.pool_bytes

    @property
    def mcu_bottleneck_bytes(self) -> int:
        """The byte-granular deployable bottleneck (paper Fig. 9/10)."""
        return self.mcu["mcu_bottleneck_bytes"]

    def _dedup_by_parent(self, entries: list) -> list:
        """Slices of one op share its parameters — count flash once."""
        parents = self.partial_parents
        if parents is None:
            return entries
        seen: set[int] = set()
        kept = []
        for p, e in zip(parents, entries):
            if p not in seen:
                seen.add(p)
                kept.append(e)
        return kept

    @property
    def flash_bytes_used(self) -> int:
        """Parameter storage the target's flash must hold (exact for
        materialized params/qparams, analytic otherwise)."""
        if self.quantized:
            return _nbytes(self._dedup_by_parent(self.qnet.qparams))
        if self.params is not None:
            return _nbytes(self._dedup_by_parent(self.params))
        return _flash_param_bytes(self.program, self.partial_parents)

    def fits(self) -> bool:
        return self.target.fits_sram(self.mcu_bottleneck_bytes)

    # -- execution ---------------------------------------------------------
    def run(self, x, *, backend: str | None = None, trace: bool = False,
            **kwargs):
        """Run the compiled net on ``x`` (float in / float out; int8
        targets quantize on entry and dequantize on exit).

        ``trace=True`` threads a :class:`repro.obs.RingTracer` through
        the executor (per-op synchronized wall times) and returns
        ``(y, TraceArtifact)`` instead of ``y``.  ``trace=False`` is the
        zero-cost path: no tracer reaches the executor and the ``jnp``
        backend keeps its whole-program jit (bit-identical output).

        A leading batch dimension (``x.ndim == 3``) runs every sample
        through the ONE solved plan: vmapped on the ``jnp`` backend
        (one pool per lane, shared program/params), a device loop on
        ``pallas`` (the kernels alias the pool in place per sample).
        An int8 net quantizes an untraced ``jnp`` batch on the device,
        bit-identical to the host quantize of every other call.
        Batched ``trace=True`` traces each sample and returns one
        artifact whose counters are the certificate scaled by exactly
        the batch size (wall times sum across lanes).

        The call is one ``vmcu.run`` span, with the ``vmcu.*`` spans of
        its layers inside (DESIGN.md §12).
        """
        backend = backend or self.target.default_backend
        batch = int(np.shape(x)[0]) if np.ndim(x) == 3 else 1
        with span("vmcu.run", backend=backend, batch=batch):
            return self._run(x, backend, trace, **kwargs)

    def _run(self, x, backend: str, trace: bool, **kwargs):
        import jax
        import jax.numpy as jnp

        # the input's put: the first step of an int8 net's quantize
        on = self._quantize_on(x, backend, trace) if self.quantized else None
        with span("vmcu.quantize", on=on) if on else span("vmcu.stage"):
            xa = jnp.asarray(x)
        if xa.ndim == 3:
            if trace:
                return self._run_batch_traced(xa, backend, **kwargs)
            if backend != "jnp":
                return jnp.stack([self._run(xi, backend, False, **kwargs)
                                  for xi in xa])
            from ..core.executors import run_program

            if self.quantized:
                # quantize and dequantize stay OUTSIDE the vmapped ring
                # run; the quantize runs on the device where it can
                from ..quant import QParams, dequantize, host_array, quantize

                qn = self.qnet
                with span("vmcu.quantize", on=on):
                    if on == "device":
                        xq = self._device_quantize(xa)
                    else:
                        xq = quantize(host_array(xa, np.float64),
                                      QParams(scale=qn.in_scale))
                yq = jax.vmap(lambda s: run_program(
                    qn.program, s, qn.qparams, backend="jnp")[0])(xq)
                with span("vmcu.dequantize"):
                    return dequantize(host_array(yq, np.float64),
                                      QParams(scale=qn.out_scale))
            params = self.ensure_params()
            return jax.vmap(lambda s: run_program(
                self.program, s, params, backend="jnp")[0])(xa)
        tracer = None
        if trace:
            from ..obs import RingTracer

            tracer = kwargs["tracer"] = RingTracer()
        if backend == "pallas":
            # Execution granularity only (rows fused per Pallas grid
            # step) — the plan and its certificates are untouched.
            kwargs.setdefault("kernel_block_rows",
                              self.target.kernel_block_rows)
        if self.quantized:
            y = run_net_quantized(self.qnet, x, backend=backend,
                                  **kwargs)
        elif self.program.quantized:
            raise CompileError(
                "this is a planner-only int8 compile (quantize=False): "
                "the ring geometry exists but no calibrated qparams — "
                "recompile with quantize=True to execute")
        else:
            y = run_net(self.program, x, self.ensure_params(),
                        backend=backend, **kwargs)
        if tracer is None:
            return y
        from ..obs import build_trace

        art = build_trace(self.program, tracer=tracer, backend=backend,
                          net=self.net_name, target=self.target.name,
                          spans=self.spans)
        return y, art

    @staticmethod
    def _quantize_on(x, backend: str, trace: bool) -> str:
        """Where an int8 net's input is quantized: on the device for an
        untraced ``jnp`` batch of a float dtype that widens exactly to
        float32, else on the host (``quant.quantize``)."""
        import jax.numpy as jnp

        if np.ndim(x) != 3 or backend != "jnp" or trace:
            return "host"
        dt = jnp.result_type(x)
        if jnp.issubdtype(dt, jnp.floating) and dt.itemsize <= 4:
            return "device"
        return "host"

    @functools.cached_property
    def _device_quantize(self):
        """The input's device quantize, its edges computed on first use."""
        from ..quant import QParams, device_quantizer

        return device_quantizer(QParams(scale=self.qnet.in_scale))

    def _run_batch_traced(self, xa, backend: str, **kwargs):
        """Batched ``trace=True``: every sample runs through the ONE
        solved plan with its own tracer; wall times sum across lanes
        and the schedule-derived counters scale by exactly the batch —
        the certificate × batch invariant the tests pin.  (The
        occupancy timeline and watermark stay per-sample: each lane
        runs its own pool.)"""
        import jax.numpy as jnp

        from ..obs import RingTracer, build_trace

        agg = RingTracer()
        agg.backend = backend
        ys = []
        for xi in xa:
            t = RingTracer()
            ys.append(self._run(xi, backend, False, tracer=t, **kwargs))
            for i, s in t.wall_s.items():
                agg.wall_s[i] = agg.wall_s.get(i, 0.0) + s
        art = build_trace(self.program, tracer=agg, backend=backend,
                          net=self.net_name, target=self.target.name,
                          spans=self.spans)
        batch = int(xa.shape[0])
        scaled = ("steps", "segs_read", "segs_written", "bytes_loaded",
                  "bytes_stored", "macs", "requants")
        for ev in art.events:
            for k in scaled:
                if k in ev:
                    ev[k] = ev[k] * batch
        for k in scaled:
            if k in art.totals:
                art.totals[k] = art.totals[k] * batch
        art.totals["batch"] = batch
        return jnp.stack(ys), art

    def stream(self, *, backend: str | None = None, trace: bool = False):
        """Open a :class:`repro.stream.StreamSession` on this net — the
        per-frame reset/step driver over the persistent-state ring.
        Requires a streaming compile (``streaming=True`` or a graph
        with ``conv_stream``/``gru_cell`` nodes)."""
        from ..stream import StreamSession

        return StreamSession(
            self, backend=backend or self.target.default_backend,
            trace=trace)

    def profile(self, x=None, *, backend: str | None = None):
        """One traced run on a deterministic input; returns the
        :class:`repro.obs.TraceArtifact` (geometry, per-op byte/MAC
        counters + wall times, occupancy timeline, compile spans).

        Planner-only int8 compiles (no qparams) profile through the sim
        oracle instead — measured segment traffic, no numerics."""
        if self.program.quantized and not self.quantized:
            from ..core.executors import execute
            from ..obs import RingTracer, build_trace

            tracer = RingTracer()
            execute(self.program, backend="sim", tracer=tracer)
            return build_trace(self.program, tracer=tracer,
                               net=self.net_name, target=self.target.name,
                               spans=self.spans)
        if x is None:
            import jax

            x = jax.random.normal(
                jax.random.PRNGKey(0),
                (self.program.in_rows, self.program.in_dim))
        _y, art = self.run(x, backend=backend, trace=True)
        return art

    # -- C emission --------------------------------------------------------
    def emit_c(self, outdir=None, *, name: str | None = None,
               geometry_only: bool = False,
               idiom: str | None = _UNSET) -> dict[str, str]:
        """Emit one intrinsic-C unit per op (``{filename: source}``).

        Quantized nets bake their requant tables in; ``geometry_only``
        emits just the solved ring skeleton (byte-typed pool header, no
        requant constants — the deterministic form the CLI smoke gate
        diffs against goldens).  ``idiom`` defaults to the target's
        requant idiom banner.  ``outdir`` additionally writes the files.
        """
        if idiom is _UNSET:
            idiom = (self.target.requant_idiom
                     if self.target.requant_idiom != "none" else None)
        name = name or self.net_name
        if geometry_only or not self.quantized:
            if not geometry_only and self.program.quantized:
                raise CompileError(
                    "this is a planner-only int8 compile (quantize="
                    "False): no requant tables to bake — recompile with "
                    "quantize=True, or pass geometry_only=True for the "
                    "ring skeleton")
            prog = (self.program.with_dtype("byte") if geometry_only
                    else self.program)
            units = emit_program(prog, name, idiom=idiom)
        else:
            units = emit_program(self.qnet.program, name,
                                 quant=self.qnet.qparams, idiom=idiom)
        if outdir is not None:
            import pathlib

            out = pathlib.Path(outdir)
            out.mkdir(parents=True, exist_ok=True)
            for fname, src in units.items():
                (out / fname).write_text(src)
        return units

    # -- reporting ---------------------------------------------------------
    def report(self) -> dict:
        """Footprint / bottleneck accounting against the target budget."""
        t = self.target
        bot = self.mcu_bottleneck_bytes
        deploy = self.mcu.get("deploy_bytes") or bot
        flash = self.flash_bytes_used
        rep = {
            "net": self.net_name,
            "target": t.name,
            "cpu": t.cpu,
            "dtype": self.dtype,
            "n_ops": len(self.program.ops),
            "pool_bytes": self.pool_bytes,
            "physical_pool_bytes": self.program.physical_pool_bytes,
            "mcu_bottleneck_bytes": bot,
            "tinyengine_bottleneck_bytes":
                self.mcu.get("tinyengine_bottleneck_bytes"),
            "hmcos_bottleneck_bytes":
                self.mcu.get("hmcos_bottleneck_bytes"),
            "reduction_vs_tinyengine":
                self.mcu.get("reduction_vs_tinyengine"),
            "reduction_vs_hmcos": self.mcu.get("reduction_vs_hmcos"),
            "bottleneck_group": self.mcu.get("bottleneck_group"),
            "byte_ring_bytes": self.mcu.get("byte_ring_bytes"),
            "deploy_bytes": self.mcu.get("deploy_bytes"),
            "partial": self.mcu.get("partial"),
            "sram_bytes": t.sram_bytes,
            "sram_margin_bytes": t.sram_margin(deploy),
            "fits_sram": t.fits_sram(deploy),
            "flash_bytes": t.flash_bytes,
            "flash_bytes_used": flash,
            "fits_flash": flash <= t.flash_bytes,
            "certificate": self.certificate,
            "passes": [[p.name, round(p.seconds, 4), p.note]
                       for p in self.passes],
        }
        return rep

    # -- plan artifacts ----------------------------------------------------
    def save(self, path: str) -> str:
        """Write the solved plan + payloads as a JSON artifact.

        Loading it back (:meth:`load`) reproduces ``pool_bytes``, the
        emitted C and bit-identical execution without ever re-running
        the branch-and-bound scheduler."""
        payload = {
            "schema": artifact.SCHEMA,
            "kind": artifact.KIND,
            "net": self.net_name,
            "target": dataclasses.asdict(self.target),
            "dtype": self.dtype,
            "program": self.program.to_json_dict(),
            "params": artifact.encode(self.ensure_params()),
            "quant": None if not self.quantized else {
                "act_scales": list(self.qnet.act_scales),
                "qparams": artifact.encode(self.qnet.qparams),
            },
            "mcu": self.mcu,
            "certificate": self.certificate,
            "passes": [[p.name, p.seconds, p.note] for p in self.passes],
            "spans": self.spans,
            "partial": self.partial,
        }
        artifact.dump(payload, path)
        return path

    @classmethod
    def load(cls, path: str) -> "CompiledNet":
        payload = artifact.load(path)
        target = Target(**payload["target"])
        program = PoolProgram.from_json_dict(payload["program"])
        cert = payload.get("certificate")
        if cert is not None and "program_sha256" in cert:
            have = artifact.program_sha256(program)
            if cert["program_sha256"] != have:
                raise CompileError(
                    f"VMCU403: {path} certificate does not match its "
                    f"program (certified {cert['program_sha256'][:12]}"
                    f"..., stored {have[:12]}...) — the plan changed "
                    "after it was certified")
        params = artifact.decode(payload["params"])
        qnet = None
        if payload["quant"] is not None:
            qnet = QuantizedNet(
                plan=None, program=program, params=params,
                qparams=artifact.decode(payload["quant"]["qparams"]),
                act_scales=tuple(payload["quant"]["act_scales"]))
        return cls(net_name=payload["net"], target=target,
                   dtype=payload["dtype"], program=program, params=params,
                   qnet=qnet, mcu=payload["mcu"],
                   certificate=payload["certificate"],
                   passes=[PassRecord(n, s, note)
                           for n, s, note in payload["passes"]],
                   spans=payload.get("spans"),
                   partial=payload.get("partial"))


def load(path: str) -> CompiledNet:
    """Load a saved plan artifact (module-level alias)."""
    return CompiledNet.load(path)


# ---------------------------------------------------------------------------
# The pipeline.
# ---------------------------------------------------------------------------

def _mcu_summary(plan: NetPlan) -> dict:
    """Snapshot the byte-granular accounting so it survives save/load."""
    return {
        "mcu_bottleneck_bytes": plan.mcu_bottleneck_bytes,
        "tinyengine_bottleneck_bytes": plan.tinyengine_bottleneck_bytes,
        "hmcos_bottleneck_bytes": plan.hmcos_bottleneck_bytes,
        "reduction_vs_tinyengine": plan.reduction_vs_tinyengine,
        "reduction_vs_hmcos": plan.reduction_vs_hmcos,
        "mcu_pool_bytes": plan.mcu_pool_bytes,
        "bottleneck_group": plan.bottleneck_group().name,
        "n_groups": len(plan.groups),
        "groups": [{"name": g.name, "kind": g.group.kind,
                    "fused_exec": g.group.fused_exec,
                    "mcu_bytes": g.group.mcu_bytes,
                    "te_bytes": g.group.te_bytes,
                    "hmcos_bytes": g.group.hmcos_bytes}
                   for g in plan.groups],
    }


def compile(net, target: str | Target = "host-sim", *, dtype=None,
            fused_exec: bool | None = None, seg_width: int | None = None,
            block_rows=_UNSET, order=None, params=None, key=None,
            calib=None, n_calib: int = 2, quantize: bool = True,
            certify: bool | str = True, lint: bool = True,
            check_budget: bool = True, partial: str | int = "off",
            streaming: bool = False) -> CompiledNet:
    """Compile ``net`` for ``target`` — the repo's deployment front door.

    ``net`` is a :class:`repro.graph.Graph` or a registered net name
    (:func:`available_nets`); ``target`` a :class:`Target` or registry
    name.  Every knob defaults from the target descriptor: ``dtype``
    (``target.default_dtype``), ring geometry (``seg_width`` /
    ``block_rows``), and ``fused_exec`` (unfused for int8 — the
    CMSIS-NN deployment form quantization requires).  ``params`` /
    ``key`` seed the float parameters (He-init with PRNGKey(0) when
    omitted — deterministic, and materialized lazily so planner-only
    compiles never pay for init); ``calib``/``n_calib`` feed int8
    calibration.  ``quantize=False`` plans an int8 ring without
    calibrating (planner-only, ``.run`` unavailable); ``certify`` is
    ``True``/``"sim"`` (replay the SegmentPool clobber oracle),
    ``"static"`` (prove it with :func:`repro.analysis.verify_program`,
    sim fallback outside the decidable fragment) or ``False`` (skip);
    ``lint=False`` skips the VMCU3xx/4xx lint pass;
    ``check_budget=False`` records the SRAM verdict without raising
    :class:`SRAMBudgetError`.

    ``partial`` enables partial execution (DESIGN.md §13): ``"auto"``
    slices over-budget fusion groups spatially until the deployable
    ring fits the target SRAM (demoting :class:`SRAMBudgetError` into
    a scheduled latency/memory trade), an ``int`` forces that many
    slices on the ring-pinning group, ``"off"`` (default) keeps the
    hard budget gate.

    ``streaming=True`` converts the resolved feed-forward graph to its
    per-frame streaming form (:func:`repro.stream.to_streaming`) before
    planning, then re-certifies the streaming plan — state liveness
    included.  Run it with :meth:`CompiledNet.stream`.
    """
    if certify not in (True, False, "sim", "static"):
        raise ValueError(f"certify must be True/False/'sim'/'static', "
                         f"got {certify!r}")
    if not (partial in ("off", "auto") or isinstance(partial, int)):
        raise ValueError(f"partial must be 'off', 'auto' or an int "
                         f"slice count, got {partial!r}")
    t = get_target(target)
    dtype = dtype or t.default_dtype
    dtype_itemsize(dtype)  # fail fast on unknown dtypes
    if fused_exec is None:
        # partial execution slices the unfused pw/dw/pw chain — the
        # same deployment form int8 quantization requires
        fused_exec = dtype != "int8" and partial == "off"
    elif fused_exec and dtype == "int8":
        raise CompileError(
            "int8 compilation requires unfused module lowering "
            "(fused_exec=False): quantized execution requantizes "
            "between the pw/dw/pw ops")
    elif fused_exec and partial != "off":
        raise CompileError(
            "partial execution requires unfused module lowering "
            "(fused_exec=False): the slice surgery rewrites the "
            "pw/dw/pw chain ops individually")
    seg_width = t.seg_width if seg_width is None else seg_width
    block_rows = t.block_rows if block_rows is _UNSET else block_rows

    passes: list[PassRecord] = []
    collector = SpanCollector()

    def run_pass(name, fn):
        t0 = time.perf_counter()
        with collect(collector), span(name):
            out, note = fn()
        passes.append(PassRecord(name, time.perf_counter() - t0, note))
        return out

    # build ----------------------------------------------------------------
    def _build():
        g = _resolve_net(net)
        note = ""
        if streaming:
            from ..stream import to_streaming

            g = to_streaming(g)
            note = " (streaming form)"
        g.validate()
        return g, f"{len(g.nodes)} nodes, {len(g.modules)} modules{note}"
    graph = run_pass("build", _build)

    # schedule -------------------------------------------------------------
    def _schedule():
        if order is not None:
            return list(order), f"caller order ({len(order)} nodes)"
        o, peak = reorder(graph)
        return o, f"peak live {peak} B over {len(o)} nodes"
    sched_order = run_pass("schedule", _schedule)

    # plan -----------------------------------------------------------------
    def _plan():
        p = _plan_net(graph, order=sched_order, seg_width=seg_width,
                      block_rows=block_rows, dtype=dtype,
                      fused_exec=fused_exec)
        return p, (f"{len(p.program.ops)} ops in one ring, "
                   f"pool {p.program.pool_bytes} B")
    plan = run_pass("plan", _plan)

    # budget ---------------------------------------------------------------
    # Pure arithmetic on the solved plans: gate BEFORE the expensive
    # quantize/certify passes so an over-budget net fails in ms.  For
    # int8 (the deployment dtype) the gate covers BOTH the analytic
    # per-group bottleneck and the deployable byte ring (seg_width=1 /
    # tight rows — the footprint an MCU build actually allocates), which
    # a merged multi-group ring can exceed the per-group bound on.
    # Float compiles keep the analytic gate: their byte ring is a 4x
    # host-development artifact, not what ships.
    byte_geometry = seg_width == 1 and block_rows is None
    real_mcu = t.sram_bytes < (1 << 38)     # host-sim never gates
    ring_gate = dtype == "int8" or partial != "off"
    byte_plan = None
    if real_mcu and ring_gate and (check_budget or partial != "off") \
            and not byte_geometry:
        def _byte_plan():
            return _plan_net(graph, order=sched_order, dtype=dtype,
                             fused_exec=fused_exec,
                             **t.byte_ring_kwargs)
        try:
            with collect(collector), span("byte_plan"):
                byte_plan = _byte_plan()
        except Exception:
            byte_plan = None        # fall back to the analytic gate only

    def _budget():
        bot = plan.mcu_bottleneck_bytes
        ring = (byte_plan.program.pool_bytes if byte_plan is not None
                else plan.program.pool_bytes
                if byte_geometry and ring_gate else bot)
        deploy = max(bot, ring)
        margin = t.sram_margin(deploy)
        verdict = "fits" if margin >= 0 else "OVER"
        note = (f"bottleneck {bot} B, deployable ring {ring} B vs "
                f"{t.sram_bytes} B SRAM ({verdict}, margin {margin} B)")
        if margin < 0 and partial != "off":
            return (deploy, margin), note + " — deferred to partial pass"
        if check_budget and margin < 0:
            raise SRAMBudgetError(
                f"{graph.name} needs {deploy} B (deployable "
                f"bottleneck) but target {t.name!r} has {t.sram_bytes} "
                f"B SRAM (over by {-margin} B); pass partial='auto' to "
                "slice the over-budget groups, or check_budget=False "
                "to record the verdict without gating")
        return (deploy, margin), note
    run_pass("budget", _budget)

    # partial --------------------------------------------------------------
    # Slice over-budget fusion groups spatially (DESIGN.md §13).  The
    # slicing is CHOSEN on the deployable byte ring (that is the budget
    # being missed) and APPLIED to the executed geometry too.
    partial_plan = None
    exec_parents = None
    exec_program = plan.program
    if partial != "off":
        def _partial():
            nonlocal exec_parents, exec_program
            from ..partial import (PartialPlanError, apply_partial,
                                   plan_partial)

            policy_prog = (byte_plan.program if byte_plan is not None
                           else plan.program)
            policy_groups = (byte_plan.groups if byte_plan is not None
                             else plan.groups)
            ranges = [(gp.op_lo, gp.op_hi) for gp in policy_groups]
            force = partial if isinstance(partial, int) else None
            try:
                pp = plan_partial(policy_prog, ranges, t.sram_bytes,
                                  force=force)
            except PartialPlanError as e:
                raise SRAMBudgetError(
                    f"partial execution cannot fit {graph.name} in "
                    f"{t.sram_bytes} B SRAM on {t.name!r}: {e}") from e
            if pp is None:
                return None, "not needed (deployable ring fits SRAM)"
            exec_program, exec_parents = apply_partial(plan.program,
                                                       pp.choices)
            return pp, (f"{len(pp.groups)} group(s) -> "
                        f"{sum(g['n_slices'] for g in pp.groups)} "
                        f"slices; ring {pp.ring_bytes_before} -> "
                        f"{pp.ring_bytes_after} B, "
                        f"+{pp.mac_overhead:.1%} MACs")
        partial_plan = run_pass("partial", _partial)

    # quantize -------------------------------------------------------------
    # (parameters materialize lazily: planner-only compiles — the
    # benchmark sections — never pay for init_net_params.  Sliced
    # compiles calibrate the UNSLICED plan — the reference forward runs
    # whole tensors — then share each op's qparams across its slices,
    # so requant constants are identical and execution stays bit-exact.)
    qnet = None
    if dtype == "int8" and quantize:
        def _quant():
            nonlocal params
            if params is None:
                with span("init_params", ops=len(plan.program.ops)):
                    params = init_net_params(plan, key)
            q = _quantize_net(plan, params, calib=calib, n_calib=n_calib)
            note = (f"{len(q.qparams)} q-ops, requant tables for "
                    f"{sum(1 for op in q.program.ops if op.kind != 'add')}"
                    " stores")
            if partial_plan is not None:
                from ..partial import apply_partial

                qprog, qpar = apply_partial(q.program,
                                            partial_plan.choices)
                q = QuantizedNet(
                    plan=q.plan, program=qprog,
                    params=[q.params[p] for p in qpar],
                    qparams=[q.qparams[p] for p in qpar],
                    act_scales=q.act_scales)
                note += f"; shared across {len(qpar)} sliced ops"
            return q, note
        qnet = run_pass("quantize", _quant)

    program = qnet.program if qnet is not None else exec_program

    # deployable accounting shared by lint / mcu snapshot / report ---------
    ring_unsliced = (byte_plan.program.pool_bytes
                     if byte_plan is not None
                     else plan.program.pool_bytes
                     if byte_geometry and ring_gate else None)
    deploy_ring = (partial_plan.ring_bytes_after
                   if partial_plan is not None else ring_unsliced)
    deploy_bytes = max(plan.mcu_bottleneck_bytes, deploy_ring or 0)

    # lint -----------------------------------------------------------------
    # (lazy import: repro.analysis is pure inspection, but keep the
    # driver importable without it in minimal deployments)
    if lint:
        def _lint():
            from ..analysis.lint import lint_program

            est = None
            if t.sram_margin(deploy_bytes) < 0 and partial_plan is None:
                # the overflow stood — can partial execution resolve it?
                from ..partial import estimate_slices

                policy = (byte_plan if byte_plan is not None else plan)
                pprog = policy.program
                est = estimate_slices(
                    pprog, [(gp.op_lo, gp.op_hi) for gp in policy.groups],
                    t.sram_bytes // (pprog.seg_width * pprog.elem_bytes))
            diags = lint_program(
                program, t, deploy_bytes=deploy_bytes,
                bottleneck_group=plan.bottleneck_group().name,
                partial_slices=est)
            # check_budget=False means "record, don't gate" — that
            # covers the lint pass's SRAM finding too
            errors = [d for d in diags if d.severity == "error"
                      and (check_budget or d.code != "VMCU301")]
            if errors:
                raise CompileError(f"lint: {errors[0]}")
            if diags:
                return None, (f"{len(diags)} warning(s): "
                              + "; ".join(str(d) for d in diags))
            return None, "clean"
        run_pass("lint", _lint)

    # certify --------------------------------------------------------------
    certificate = None
    if certify:
        def _certify():
            mode = "static" if certify == "static" else "sim"
            note = ""
            if mode == "static":
                from ..analysis import verify_program

                res = verify_program(program)
                if res.safe is False:
                    raise CompileError(f"certify: {res.diagnostics[0]}")
                if res.safe:
                    cert = res.certificate(
                        artifact.program_sha256(program))
                    return cert, (f"static proof: zero clobbers; peak "
                                  f"{cert['peak_live']}/"
                                  f"{program.n_segments} segments live")
                note = f"sim fallback ({res.diagnostics[0].code}); "
            sim = certify_net(program)
            cert = {"clobbers": 0, "peak_live": sim.peak_live,
                    "reads": sim.reads, "writes": sim.writes,
                    "n_segments": program.n_segments,
                    "program_sha256": artifact.program_sha256(program)}
            state_total = sum(op.state_segments for op in program.ops)
            if state_total:
                # the sim observes the end-live invariant the static
                # horizon proof relies on: only the state regions and
                # the final output survive the step
                cert["n_states"] = sum(1 for op in program.ops
                                       if op.state_segments)
                cert["state_segments"] = state_total
                cert["stream_horizon"] = (
                    "unbounded" if sim.live == state_total
                    + program.ops[-1].out_segments else 1)
            return cert, (f"{note}zero clobbers; peak {sim.peak_live}/"
                          f"{program.n_segments} segments live")
        certificate = run_pass("certify", _certify)

    mcu = _mcu_summary(plan)
    mcu["byte_ring_bytes"] = ring_unsliced
    mcu["deploy_bytes"] = deploy_bytes
    partial_info = None
    if partial_plan is not None:
        partial_info = dict(partial_plan.summary())
        partial_info["parents"] = list(exec_parents)
        mcu["partial"] = {k: v for k, v in partial_info.items()
                          if k != "parents"}
        if params is not None:     # re-align materialized float params
            params = [params[p] for p in exec_parents]

    return CompiledNet(net_name=graph.name, target=t, dtype=dtype,
                       program=program, params=params, qnet=qnet,
                       mcu=mcu, certificate=certificate,
                       passes=passes, plan=plan, graph=graph,
                       init_key=key, spans=collector.to_dicts(),
                       partial=partial_info)
