"""The Cappuccino synthesis pipeline (paper §III, Fig. 3).

Inputs (exactly the paper's three):
  1. a :class:`NetworkDescription`          (architecture),
  2. a model file — params dict              (weights/biases),
  3. a validation dataset                    (images, labels).

Stages:
  A. *Primary program synthesis*: plan the program — the planner assigns
     every layer an implementation / thread policy / channel-group width
     via its static cost model (optionally refined by a measured autotune
     pass).  The artifact is an :class:`ExecutionPlan`, not a flag pair.
  B. *Parameter reordering* (compile-time, §IV-B): weights go map-major so
     the vectorized kernels load u operands per access.  Model size is
     unchanged (modulo lane padding), as the paper notes.
  C. *Inexact-computing analysis* (§IV-C): run the mode selector on the
     validation set under the user's accuracy constraint, evaluating under
     the planned implementations (joint mode+impl refinement).
  D. *Software synthesis*: emit the final program — here an XLA-compiled
     callable with the per-layer plan baked in, plus a human-readable
     synthesis report (the analogue of the generated RenderScript source).

Stages A and C are not run once each: because the planner's cost rules are
mode-dependent and Stage C's probes are plan-dependent, ``synthesize`` runs
them as a **fixed-point loop** — plan, probe modes under that plan, re-plan
under the selected modes, re-probe — until the ``(plan.fingerprint(),
modes)`` pair converges (iteration cap + deterministic tie-break; DESIGN.md
§7).  The measured autotune pass runs *inside* the loop, so impl timings
are (re)taken under the modes that actually ship.  After convergence a
**final validation gate** executes the emitted program — the same dispatch
path ``SynthesizedProgram.infer`` / ``for_batch`` serve — on the
calibration set and asserts measured degradation ≤ ``max_degradation``,
demoting modes toward all-PRECISE when the gate fails.  The audit trail is
a :class:`~repro.core.plan.SynthesisReport` on the returned program.

Stages A–C are *plan-time*: they depend on the network, weights, and
validation set but not on the serving batch shape.  Stage D is *shape
specialization*: XLA compiles for one concrete input shape.  The split is
explicit in the artifact — :meth:`SynthesizedProgram.for_batch` re-runs
only Stage D (an AOT compile for ``(batch, C, H, W)``), so a serving layer
can synthesize once per network and specialize per batch bucket (see
serving/program_cache.py and DESIGN.md §6).
"""
from __future__ import annotations

import dataclasses
import hashlib
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..device import DeviceProfile, profile_for_device, resolve_profile
from ..obs import MetricsRegistry, Tracer
from .graph import lower_network
from .layout import LANES, weights_to_map_major
from .mode_selector import ModeSelectionReport, refine_plan
from .network import NetworkDescription, collect_activations, run_network
from .parallelism import Parallelism
from .plan import (ExecutionPlan, IterationRecord, SynthesisReport,
                   ValidationRecord, enforce_precise_xla)
from .planner import PlannerConfig, autotune_plan, plan_network
from .precision import (MODES_FASTEST_FIRST, ComputeMode, QParams,
                        calibrate_act_scale, prepare_weight,
                        weight_channel_axis)

#: Fixed-point iteration cap: plan -> probe -> re-plan rounds before the
#: deterministic tie-break picks among the visited states.
MAX_SYNTHESIS_ITERATIONS = 4

#: Float slack for the validation gate's degradation comparison.
_GATE_EPS = 1e-9


@dataclass
class BatchProgram:
    """One Stage-D artifact: an AOT-compiled executable for a fixed batch.

    This is the closest analogue of the paper's emitted RenderScript source:
    every shape is static, XLA has finished compiling, and ``__call__`` only
    executes.  Produced by :meth:`SynthesizedProgram.for_batch`; cached and
    reused across requests by ``serving.ProgramCache``.
    """
    batch: int
    input_shape: Tuple[int, ...]              # full (B, C, H, W)
    plan_fingerprint: str
    compile_seconds: float
    _compiled: Callable[[jnp.ndarray], jnp.ndarray]

    def hlo_text(self) -> str:
        """The optimized HLO of the compiled executable — what the device
        runs (a compiled Pallas launch shows as a ``tpu_custom_call``)."""
        return self._compiled.as_text()

    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        if tuple(x.shape) != self.input_shape:
            raise ValueError(
                f"BatchProgram compiled for {self.input_shape}, got "
                f"{tuple(x.shape)}; use SynthesizedProgram.for_batch "
                f"({x.shape[0]}) or the serving batcher")
        return self._compiled(x)


@dataclass
class SynthesizedProgram:
    """The plan-time synthesis artifact (Stages A–C baked in) + metadata.

    ``infer`` is the shape-polymorphic entry point (a ``jax.jit`` that
    retraces per input shape — convenient for scripts and tests);
    :meth:`for_batch` is the explicit Stage-D entry point serving uses: it
    AOT-compiles the program for one fixed batch and records the compile in
    ``stage_d_compiles``.
    """
    net: NetworkDescription
    plan: ExecutionPlan
    modes: Dict[str, ComputeMode]
    parallelism: Parallelism
    mode_report: Optional[ModeSelectionReport]
    synthesis_seconds: float
    synthesis_report: Optional[SynthesisReport] = None
    prepared: Dict[str, Dict[str, jnp.ndarray]] = field(repr=False,
                                                        default_factory=dict)
    vector_width: int = LANES
    input_dtype: jnp.dtype = jnp.float32
    stage_d_compiles: int = 0
    #: Cost-model drift (:class:`repro.obs.drift.DriftReport`) — attached
    #: by :func:`repro.obs.measure_drift`; printed by :meth:`report`.
    drift: Optional[object] = field(default=None, repr=False)
    _infer: Optional[Callable[[jnp.ndarray], jnp.ndarray]] = \
        field(default=None, repr=False)
    _params_digest: Optional[str] = field(default=None, repr=False)

    def _forward(self, x: jnp.ndarray) -> jnp.ndarray:
        return run_network(self.net, self.prepared, x, plan=self.plan)

    def params_digest(self) -> str:
        """Content hash of the prepared weights (Stage B's output).

        Cached after the first call — O(model size) once.  Part of
        :meth:`fingerprint` so two programs sharing a network name and plan
        but carrying different weights (a retrain, a different quantization)
        can never share compiled executables."""
        if self._params_digest is None:
            h = hashlib.sha256()
            for name in sorted(self.prepared):
                h.update(name.encode())
                for leaf in jax.tree_util.tree_leaves(self.prepared[name]):
                    arr = np.asarray(leaf)
                    h.update(str(arr.dtype).encode())
                    h.update(str(arr.shape).encode())
                    h.update(arr.tobytes())
            self._params_digest = h.hexdigest()[:16]
        return self._params_digest

    def fingerprint(self) -> str:
        """Program identity for caching: plan dispatch content + weights."""
        return f"{self.plan.fingerprint()}-{self.params_digest()}"

    @property
    def infer(self) -> Callable[[jnp.ndarray], jnp.ndarray]:
        """Jitted forward pass with the plan baked in (retraces per shape)."""
        if self._infer is None:
            self._infer = jax.jit(self._forward)
        return self._infer

    def for_batch(self, batch: int, device=None) -> BatchProgram:
        """Stage D alone: AOT-compile this program for a fixed batch size.

        Stages A–C are already done — this re-specializes the *same* plan
        and prepared weights for a new leading dimension, which is exactly
        what the serving layer's power-of-two buckets need.  ``device``
        (a JAX device) compiles the executable for that device: it runs
        there, with the prepared weights it carries as constants; None
        means the default device.
        """
        if batch < 1:
            raise ValueError(f"batch must be >= 1, got {batch}")
        shape = (batch, *self.net.input_shape)
        sharding = (jax.sharding.SingleDeviceSharding(device)
                    if device is not None else None)
        t0 = time.time()
        compiled = jax.jit(self._forward).lower(
            jax.ShapeDtypeStruct(shape, self.input_dtype,
                                 sharding=sharding)).compile()
        self.stage_d_compiles += 1
        return BatchProgram(batch=batch, input_shape=shape,
                            plan_fingerprint=self.plan.fingerprint(),
                            compile_seconds=time.time() - t0,
                            _compiled=compiled)

    def report(self) -> str:
        lines = [f"== Cappuccino synthesis report: {self.net.name} ==",
                 f"device           : {self.plan.profile.name} "
                 f"[{self.plan.profile.source}] "
                 f"(ridge {self.plan.profile.ridge():.0f} FLOPs/B)",
                 f"parallelism      : {self.parallelism.value} (thread level)"
                 f" + vectorized MAC (intra-thread, u={self.vector_width})",
                 f"layers           : {len(self.net.layers)}"
                 f" ({len(self.net.param_layers)} parametric)",
                 f"plan origin      : {self.plan.origin}",
                 f"synthesis time   : {self.synthesis_seconds:.2f}s",
                 f"dispatch         : "
                 + (f"fused graph ({len(self.plan.graph.groups)} groups / "
                    f"{self.plan.graph.n_layers} layers)"
                    if self.plan.graph is not None else "layer walk"),
                 "execution plan:",
                 "  " + self.plan.table().replace("\n", "\n  "),
                 "layer modes:"]
        for l in self.net.layers:
            if l.is_inexactable:
                lines.append(f"  {l.name:28s} {self.modes[l.name].value}")
        if self.mode_report is not None:
            lines.append("mode selection:")
            lines.append("  " + self.mode_report.summary().replace("\n", "\n  "))
        if self.synthesis_report is not None:
            lines.append("fixed-point synthesis:")
            lines.append("  " + self.synthesis_report.summary()
                         .replace("\n", "\n  "))
        if self.plan.graph is not None:
            lines.append("fusion:")
            lines.append("  " + self.plan.graph.report().replace("\n", "\n  "))
        if self.drift is not None:
            lines.append(self.drift.table())    # carries its own header
        return "\n".join(lines)


def calibrate_activation_qparams(
        net: NetworkDescription, params,
        images: jnp.ndarray) -> Dict[str, QParams]:
    """Int8 activation calibration: static per-tensor symmetric scales.

    Runs the float network once over the calibration set (the same images
    the Stage-C probes and the validation gate use) and records, for every
    parametric layer, ``amax(|input activation|) / 127`` — the scale the
    int8 kernels quantize that layer's activations with at serving time.
    Computed once per synthesis: the scales are *static*, part of the
    layer's plan (and so of the plan fingerprint / ProgramCache identity),
    never recomputed per request.
    """
    acts = collect_activations(net, params, images)
    out: Dict[str, QParams] = {}
    for l in net.param_layers:
        out[l.name] = calibrate_act_scale(acts[l.inputs[0]])
    return out


def _attach_qparams(plan: ExecutionPlan,
                    act_qparams: Optional[Dict[str, QParams]]
                    ) -> ExecutionPlan:
    """Attach calibrated activation qparams to exactly the INT8-mode layers.

    Every other calibrated layer gets ``qparams=None`` — a layer demoted
    out of IMPRECISE_INT8 must also lose its quantization identity, or its
    fingerprint would keep aliasing the quantized program.  Re-planning
    rebuilds LayerPlans from scratch, so this runs after every ``_replan``.
    """
    if not act_qparams:
        return plan
    overlay = {name: (qp if plan.for_layer(name).mode is
                      ComputeMode.IMPRECISE_INT8 else None)
               for name, qp in act_qparams.items()}
    return plan.with_qparams(overlay)


def _accuracy_eval(net, params, images, labels, act_qparams=None):
    """Top-1 accuracy under a candidate plan (modes overlaid per probe).

    Weight-quantizing modes are applied to the probe's weights before
    evaluation — the selector must measure the program Stage B will emit,
    not the raw-weight network (casting-only modes need no preparation:
    the ops cast operands themselves).  With calibrated activation qparams
    the probe attaches them to its INT8-mode layers first, so Stage C
    measures the true int8 datapath the final program would dispatch."""
    def evaluate_plan(p: ExecutionPlan) -> float:
        p = _attach_qparams(p, act_qparams)
        probed = {}
        for l in net.param_layers:
            mode = p.for_layer(l.name).mode
            if mode.quantizes_weights:
                lp = dict(params[l.name])
                lp["w"] = prepare_weight(
                    lp["w"], mode, channel_axis=weight_channel_axis(l.kind))
                probed[l.name] = lp
            else:
                probed[l.name] = params[l.name]
        logits = run_network(net, probed, images, plan=p)
        pred = jnp.argmax(logits, axis=-1)
        return float(jnp.mean((pred == labels).astype(jnp.float32)))
    return evaluate_plan


# ---------------------------------------------------------------------------
# Fixed-point loop + validation-gate helpers.
# ---------------------------------------------------------------------------

def _modes_key(modes: Dict[str, ComputeMode]) -> Tuple[Tuple[str, str], ...]:
    """Hashable, order-independent identity of a mode assignment."""
    return tuple(sorted((n, m.value) for n, m in modes.items()))


def _replan(net: NetworkDescription, base: ExecutionPlan,
            modes: Dict[str, ComputeMode],
            planner_config: Optional[PlannerConfig]) -> ExecutionPlan:
    """Fold a mode assignment into a plan, re-deriving impl routing.

    A static planner plan is *re-planned* under the modes — the cost rules
    are mode-dependent (VMEM envelope dtype, PRECISE's f32-path invariant),
    so a plan drawn at the PRECISE default would mis-route bf16-feasible
    layers.  Measured (autotune) and user/uniform plans keep their impls;
    only modes overlay, with the PRECISE->XLA invariant re-applied
    (:func:`~repro.core.plan.enforce_precise_xla`).  The base plan's graph
    (fused dispatch) is sticky through both paths: re-planning never
    silently changes how the program is grouped.
    """
    if base.origin == "planner":
        return plan_network(net, modes=modes, config=planner_config,
                            graph=base.graph)
    overlaid, _ = enforce_precise_xla(base.with_modes(modes))
    return overlaid


def _prepare_params(net: NetworkDescription, params,
                    modes: Dict[str, ComputeMode]):
    """Stage B: compile-time parameter preparation per chosen mode
    (cast / int8-quantize; map-major reorder happens inside the Pallas
    kernels' operand spec — weights_to_map_major is exposed for them)."""
    prepared = {}
    for l in net.param_layers:
        p = dict(params[l.name])
        p["w"] = prepare_weight(p["w"], modes[l.name],
                                channel_axis=weight_channel_axis(l.kind))
        if "b" in p:
            p["b"] = p["b"].astype(jnp.float32)
        prepared[l.name] = p
    return prepared


def _program_accuracy(program: "SynthesizedProgram", images, labels) -> float:
    """Top-1 accuracy of the *emitted* program — ``program.infer``, the
    exact dispatch path serving's ``for_batch`` specializes (same plan,
    same prepared weights, Pallas routing included)."""
    pred = jnp.argmax(program.infer(images), axis=-1)
    return float(jnp.mean((pred == labels).astype(jnp.float32)))


def _demote_modes(modes: Dict[str, ComputeMode]) -> Dict[str, ComputeMode]:
    """One fallback step: every layer moves one mode toward PRECISE."""
    order = list(MODES_FASTEST_FIRST)            # fastest ... PRECISE
    return {n: order[min(order.index(m) + 1, len(order) - 1)]
            for n, m in modes.items()}


def _dominant_policy(net: NetworkDescription,
                     plan: ExecutionPlan) -> Parallelism:
    """Legacy metadata: the dominant thread policy across parametric layers."""
    policies = {plan.for_layer(l.name).parallelism for l in net.param_layers}
    return policies.pop() if len(policies) == 1 else Parallelism.OLP


def synthesize(net: NetworkDescription,
               params: Dict[str, Dict[str, jnp.ndarray]],
               validation: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
               *,
               max_degradation: float = 0.0,
               allow_int8: bool = False,
               device: "Optional[str | DeviceProfile]" = None,
               plan: Optional[ExecutionPlan] = None,
               planner_config: Optional[PlannerConfig] = None,
               autotune: bool = False,
               autotune_input: Optional[jnp.ndarray] = None,
               max_iterations: int = MAX_SYNTHESIS_ITERATIONS,
               forced_mode: Optional[ComputeMode] = None,
               fuse: bool = True,
               tracer: Optional[Tracer] = None,
               registry: Optional[MetricsRegistry] = None,
               artifact_store: Optional[object] = None
               ) -> SynthesizedProgram:
    """Run the full Cappuccino pipeline and return the synthesized program.

    Stage A emits an :class:`ExecutionPlan`: pass ``plan=`` to supply one,
    or let the planner build it.  ``device=`` selects the synthesis target —
    a :class:`~repro.device.DeviceProfile`, a registry name (``"tpu_v4"``),
    or ``"auto"`` (calibrated/cached profile for this host, deterministic
    builtin fallback off-TPU); with none of ``device=``, ``plan=`` and
    ``planner_config=`` the target is this process's chip, looked up by its
    device kind (:func:`~repro.device.profile_for_device`).  Every cost
    rule and the plan fingerprint are taken under that device.  A uniform
    backend is ``plan=ExecutionPlan.uniform(...)``.

    With a validation set, Stages A and C run as a **fixed-point loop**
    (plan -> probe -> re-plan, ``max_iterations`` cap, deterministic
    tie-break on cycles), and a **final validation gate** measures the
    emitted program — the exact ``infer``/``for_batch`` dispatch path —
    against ``max_degradation``, demoting modes toward all-PRECISE until
    the budget holds.  The returned program's measured degradation on the
    calibration set therefore never exceeds ``max_degradation``; the audit
    trail is ``program.synthesis_report``.

    ``fuse=True`` (the default) first lowers the network through the graph
    pass pipeline (``core/graph.py``: canonicalize, dead-layer
    elimination, conv/dense+bias+ReLU epilogue fusion, pointwise-chain
    fusion) and plans/dispatches *fused groups*: the planner costs each
    group's fused FLOP/byte ratio, Stage-C probes and the validation gate
    measure the fused dispatch path, and the emitted program executes one
    op per group (one Pallas launch for a fused conv group).  Modes remain
    keyed by anchor layer name — every inexactable layer is a group
    anchor, so Stage C's per-layer search *is* the per-group search.  A
    supplied ``plan=`` keeps its own grouping (its ``graph`` field);
    ``fuse=False`` keeps the historical layer walk.

    ``forced_mode`` skips stage C (and the gate — the caller is pinning
    modes deliberately, e.g. to reproduce the paper's 'Parallel' and
    'Imprecise' table columns).  ``autotune=True`` refines the plan with
    per-layer measurements on ``autotune_input`` (or the validation
    images); inside the loop, so timings are (re)taken under the final
    Stage-C modes.

    ``tracer=`` records the pipeline as nested ``synthesis.*`` spans
    (Stage-A planning, each fixed-point iteration with its autotune and
    Stage-C probe, the validation gate and its demotion events);
    ``registry=`` accumulates ``synthesis_*`` counters.  Both default to
    off — synthesis pays nothing unless observed (DESIGN.md §12).

    ``artifact_store=`` (an :class:`~repro.artifacts.ArtifactStore`)
    makes synthesis *restartable*: before Stage A the store is consulted
    under a request key covering every input that determines the result
    (network, raw params, validation set, device identity, all knobs); a
    hit hydrates the converged program — validated report included — with
    **zero fixed-point iterations**, and a miss persists the converged
    result for the next process (DESIGN.md §13).  Bypassed when ``plan=``
    is supplied: a caller pinning the plan is steering synthesis by hand.
    """
    t0 = time.time()
    if max_iterations < 1:
        raise ValueError(f"max_iterations must be >= 1, got {max_iterations}")
    _t = tracer if tracer is not None else Tracer(enabled=False)

    def _count(name: str, amount: float = 1.0, help: str = "") -> None:
        if registry is not None:
            registry.counter(name, help).inc(amount)

    _count("synthesis_runs_total", 1, "synthesize() invocations")
    # Materialized at zero up front: an artifact-store hit returns before
    # the loop, and "zero iterations" must be a reading, not a missing
    # series (the warm-start acceptance assertion reads it).
    _count("synthesis_iterations_total", 0, "Fixed-point plan/probe rounds")

    # Device selection: the target profile flows into the planner config
    # (cost rules) and every plan built here (fingerprint identity).
    if device is not None:
        profile = resolve_profile(device)
        if plan is not None and plan.profile.identity() != profile.identity():
            raise ValueError(
                f"plan= was drawn for device {plan.profile.name!r} but "
                f"device= names {profile.name!r}; re-plan for the target "
                "or drop one of the arguments")
        planner_config = dataclasses.replace(planner_config or PlannerConfig(),
                                             profile=profile)
    elif planner_config is None and plan is None:
        # No target named: plan for the chip this process runs on.
        planner_config = PlannerConfig(profile=profile_for_device())
    elif planner_config is None:
        # Keep the supplied plan's device sticky through re-planning.
        planner_config = PlannerConfig(profile=plan.profile)
    elif (plan is not None and planner_config is not None
          and plan.profile.identity() != planner_config.profile.identity()):
        raise ValueError(
            f"plan= was drawn for device {plan.profile.name!r} but "
            f"planner_config= targets {planner_config.profile.name!r}; "
            "re-planning would silently switch devices — align the two "
            "profiles (dataclasses.replace(planner_config, "
            "profile=plan.profile)) or re-plan for the target")

    # Persistent-artifact consultation (DESIGN.md §13): a previous
    # identical request's converged program hydrates wholesale — Stages
    # A–C skipped, zero fixed-point iterations, the validated report
    # restored from disk.  The request key hashes everything that
    # determines the result, so a hit can only return what this call
    # would have synthesized.  Imported lazily: repro.artifacts depends
    # on this module.
    store_request_key: Optional[str] = None
    if artifact_store is not None and plan is None:
        from ..artifacts.store import synthesis_request_key
        store_request_key = synthesis_request_key(
            net, params, validation=validation,
            device_identity=planner_config.profile.identity(),
            max_degradation=max_degradation, allow_int8=allow_int8,
            forced_mode=forced_mode, fuse=fuse, autotune=autotune,
            max_iterations=max_iterations)
        cached = artifact_store.load_program_for(store_request_key)
        if cached is not None:
            _t.event("synthesis.artifact_hit", net=net.name,
                     fingerprint=cached.fingerprint())
            return cached

    def _store_put(program: SynthesizedProgram) -> None:
        if artifact_store is None or store_request_key is None:
            return
        try:
            artifact_store.put_program(program,
                                       request_key=store_request_key)
        except OSError as e:           # unwritable store never fails synthesis
            _t.event("synthesis.artifact_put_failed", net=net.name,
                     error=str(e))

    # Stage A: primary program synthesis -> ExecutionPlan artifact.
    # Graph lowering happens first (fuse=True): the pass pipeline decides
    # the dispatch groups, then every planning/probing/validation step
    # below operates on the fused program.  A supplied plan= keeps its own
    # grouping.
    if plan is None:
        with _t.span("synthesis.stage_a_plan", net=net.name, fuse=fuse):
            graph = lower_network(net) if fuse else None
            plan = plan_network(net, config=planner_config, graph=graph)
    tune_x = None
    if autotune:
        tune_x = autotune_input if autotune_input is not None else \
            (validation[0] if validation is not None else None)
        if tune_x is None:
            raise ValueError("autotune=True needs autotune_input= or a "
                             "validation set")

    # Int8 activation calibration: when IMPRECISE_INT8 can ship (opt-in via
    # allow_int8, pinned via forced_mode, or present on a supplied plan),
    # compute the static per-tensor activation scales once, up front, over
    # the calibration images.  The scales are attached to exactly the
    # INT8-mode layers after every (re-)planning step below; without
    # calibration images the int8 layers keep the dequant fallback.
    wants_int8 = (allow_int8
                  or forced_mode is ComputeMode.IMPRECISE_INT8
                  or any(lp.mode is ComputeMode.IMPRECISE_INT8
                         for lp in plan.layers.values()))
    calib_x = (validation[0] if validation is not None
               else autotune_input)
    act_qparams: Optional[Dict[str, QParams]] = None
    if wants_int8 and calib_x is not None:
        act_qparams = calibrate_activation_qparams(net, params, calib_x)

    mode_report: Optional[ModeSelectionReport] = None
    if forced_mode is not None or validation is None:
        # Single-pass path: modes are pinned (forced_mode) or defaulted
        # (RELAXED), so there is nothing to iterate and nothing the gate
        # could measure them against.
        modes = {n: forced_mode or ComputeMode.RELAXED
                 for n in net.inexactable_layers}
        plan = _attach_qparams(_replan(net, plan, modes, planner_config),
                               act_qparams)
        if autotune:
            with _t.span("synthesis.autotune", net=net.name):
                plan = autotune_plan(net, params, tune_x, plan)
        synthesis_report = SynthesisReport(
            converged=True, max_iterations=max_iterations,
            gate_skipped_reason=("forced_mode pins Stage C"
                                 if forced_mode is not None
                                 else "no validation set"))
        if act_qparams:
            synthesis_report.act_scales = {
                n: float(qp.act_scale) for n, qp in act_qparams.items()
                if plan.for_layer(n).qparams is not None}
        program = SynthesizedProgram(
            net=net, plan=plan, modes=modes,
            parallelism=_dominant_policy(net, plan),
            mode_report=None, synthesis_seconds=time.time() - t0,
            synthesis_report=synthesis_report,
            prepared=_prepare_params(net, params, modes))
        _count("synthesis_seconds_total", program.synthesis_seconds,
               "Wall seconds spent inside synthesize()")
        _store_put(program)
        return program

    # ---- Fixed-point loop: plan -> mode probe -> re-plan -> re-probe ------
    images, labels = validation
    evaluate_plan = _accuracy_eval(net, params, images, labels, act_qparams)
    layer_names = net.inexactable_layers
    synthesis_report = SynthesisReport(max_iterations=max_iterations)
    seen: Dict[tuple, int] = {}                  # state key -> states index
    states: List[Tuple[ExecutionPlan, Dict[str, ComputeMode],
                       ModeSelectionReport]] = []
    precise_modes = {n: ComputeMode.PRECISE for n in layer_names}
    probe_reference: Optional[float] = None
    probe_reference_fp: Optional[str] = None
    current = _attach_qparams(plan, act_qparams)

    for i in range(1, max_iterations + 1):
      with _t.span("synthesis.iteration", index=i) as it_span:
        _count("synthesis_iterations_total", 1,
               "Fixed-point plan/probe rounds")
        if autotune:
            with _t.span("synthesis.autotune", index=i):
                current = autotune_plan(net, params, tune_x, current)
        # The all-PRECISE reference is mode-independent but *plan*-
        # dependent (probes run under this round's impl routing), so the
        # warm start only holds while the PRECISE-overlay plan — what the
        # reference probe would actually execute — is unchanged.
        ref_fp = current.with_modes(precise_modes).fingerprint()
        if ref_fp != probe_reference_fp:
            probe_reference, probe_reference_fp = None, ref_fp
        with _t.span("synthesis.stage_c_probe", index=i):
            report, probed = refine_plan(current, layer_names, evaluate_plan,
                                         max_degradation=max_degradation,
                                         allow_int8=allow_int8,
                                         reference=probe_reference)
        probe_reference = report.reference_metric
        modes = report.modes
        probed = _attach_qparams(probed, act_qparams)
        next_plan = _attach_qparams(
            _replan(net, probed, modes, planner_config), act_qparams)
        key = (next_plan.fingerprint(), _modes_key(modes))
        if it_span is not None:
            it_span.attrs["fingerprint"] = next_plan.fingerprint()
            it_span.attrs["evaluations"] = report.evaluations
        synthesis_report.iterations.append(IterationRecord(
            index=i, plan_fingerprint=next_plan.fingerprint(),
            modes=dict(modes), probe_metric=report.final_metric,
            evaluations=report.evaluations))
        states.append((next_plan, modes, report))

        # Fixed point.  Without autotune, two equivalent signals:
        # re-planning changed nothing vs what Stage C just measured
        # (ship-what-you-probed), or the (fingerprint, modes) pair matches
        # the previous round.  With autotune the first signal is vacuous —
        # _replan takes the overlay path on an autotuned plan, so next_plan
        # always equals probed — and a genuine fixed point means the pair
        # survived a full re-autotune + re-probe round: only the
        # previous-round match counts, which also guarantees the shipped
        # timings were taken under the shipped modes.
        prev_key = (states[-2][0].fingerprint(), _modes_key(states[-2][1])) \
            if len(states) >= 2 else None
        at_fixed_point = key == prev_key if autotune else (
            next_plan.fingerprint() == probed.fingerprint()
            or key == prev_key)
        if at_fixed_point:
            synthesis_report.converged = True
            current, mode_report = next_plan, report
            break
        if key in seen:
            # Cycle: break it deterministically — among the states forming
            # the cycle, keep the one with the smallest (fingerprint,
            # modes) sort key.  Any member is a state the loop keeps
            # revisiting; the min-key rule just makes the choice stable
            # across runs and platforms.
            cycle = states[seen[key]:-1]
            chosen = min(cycle,
                         key=lambda s: (s[0].fingerprint(),
                                        _modes_key(s[1])))
            synthesis_report.tie_broken = True
            current, modes, mode_report = chosen
            break
        seen[key] = len(states) - 1
        current = next_plan
    else:
        # Cap hit without convergence: same deterministic rule over
        # everything visited.
        chosen = min(states, key=lambda s: (s[0].fingerprint(),
                                            _modes_key(s[1])))
        synthesis_report.tie_broken = True
        current, modes, mode_report = chosen

    # ---- Final validation gate on the emitted dispatch path ---------------
    # Reference: the all-PRECISE program, *emitted* (prepared weights,
    # jitted plan dispatch) — the same path the candidate runs, so the
    # all-PRECISE fallback floor is degradation-free by construction.
    gate_t0 = _t.clock()
    ref_plan = _attach_qparams(
        _replan(net, current, precise_modes, planner_config), act_qparams)
    ref_program = SynthesizedProgram(
        net=net, plan=ref_plan, modes=precise_modes,
        parallelism=_dominant_policy(net, ref_plan),
        mode_report=None, synthesis_seconds=0.0,
        prepared=_prepare_params(net, params, precise_modes))
    ref_acc = _program_accuracy(ref_program, images, labels)
    synthesis_report.reference_accuracy = ref_acc
    acc_memo = {ref_program.fingerprint(): ref_acc}

    cand_plan, cand_modes = current, modes
    while True:
        program = SynthesizedProgram(
            net=net, plan=cand_plan, modes=cand_modes,
            parallelism=_dominant_policy(net, cand_plan),
            mode_report=mode_report, synthesis_seconds=0.0,
            synthesis_report=synthesis_report,
            prepared=_prepare_params(net, params, cand_modes))
        fp = program.fingerprint()
        acc = acc_memo.get(fp)
        if acc is None:
            acc = _program_accuracy(program, images, labels)
            acc_memo[fp] = acc
        degradation = ref_acc - acc
        passed = degradation <= max_degradation + _GATE_EPS
        synthesis_report.validations.append(ValidationRecord(
            plan_fingerprint=cand_plan.fingerprint(), modes=dict(cand_modes),
            accuracy=acc, degradation=degradation, passed=passed))
        if passed:
            break
        if all(m is ComputeMode.PRECISE for m in cand_modes.values()):
            break         # the floor; degradation is 0 here by construction
        demoted = _demote_modes(cand_modes)
        changed = sorted(n for n in cand_modes
                         if demoted[n] is not cand_modes[n])
        synthesis_report.fallbacks.append(
            f"measured degradation {degradation:.4f} > budget "
            f"{max_degradation:.4f}: demoted {', '.join(changed)}")
        _count("synthesis_gate_demotions_total", 1,
               "Validation-gate mode demotion rounds")
        _t.event("synthesis.gate_demotion", degradation=degradation,
                 budget=max_degradation, demoted=", ".join(changed))
        cand_modes = demoted
        cand_plan = _attach_qparams(
            _replan(net, cand_plan, cand_modes, planner_config), act_qparams)

    synthesis_report.validated = passed
    _t.record_span("synthesis.validation_gate", gate_t0, _t.clock(),
                   passed=passed, demotions=len(synthesis_report.fallbacks),
                   accuracy=acc, reference_accuracy=ref_acc)
    if act_qparams:
        synthesis_report.act_scales = {
            n: float(qp.act_scale) for n, qp in act_qparams.items()
            if program.plan.for_layer(n).qparams is not None}
    if synthesis_report.fallbacks and mode_report is not None:
        # Stage C's selection was rejected by the gate: the shipped report
        # must describe the shipped program, not the rejected candidate.
        program.mode_report = dataclasses.replace(
            mode_report, modes=dict(cand_modes), final_metric=acc,
            trace=mode_report.trace + [
                "validation gate: Stage-C selection superseded by fallback; "
                f"shipped modes re-measured at {acc:.4f} on the emitted "
                "path"])
    program.synthesis_seconds = time.time() - t0
    _count("synthesis_seconds_total", program.synthesis_seconds,
           "Wall seconds spent inside synthesize()")
    _store_put(program)
    return program
