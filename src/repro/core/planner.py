"""Execution planner: Stage A's brain (paper §III "primary program
synthesis", generalized per-layer).

Assigns every layer a :class:`~repro.core.plan.LayerPlan` via a *static*
cost model, with an optional *measured* autotune refinement:

  Rule 1 (VMEM envelope)     A conv whose padded input plane exceeds the
                             Pallas kernel's per-block VMEM budget
                             (:func:`fits_vmem`) must take the fused-XLA
                             path — the kernel cannot hold the block.
  Rule 2 (group width u)     Pick the map-major channel-group width: the
                             full 128-lane width when the layer can fill
                             it, else the smallest power of two covering
                             the channel count (avoids lane-padding waste,
                             paper §IV-B).
  Rule 3 (roofline)          Estimate arithmetic intensity and the
                             compute/memory roofline terms (same model as
                             benchmarks/roofline.py, constants from the
                             target :class:`~repro.device.DeviceProfile`).
                             Compute-bound layers with MXU-filling channel
                             counts go to the map-major Pallas kernel;
                             memory-bound or narrow layers stay on XLA,
                             whose fusion wins when loads dominate.
  Thread policy              OLP always — the paper's §IV-A conclusion;
                             KLP/FLP materialize cross-thread partials and
                             exist as measured baselines only.

``autotune_plan`` replaces the static Rule-3 guess with measurements: it
captures each parametric layer's actual input activation, times every
registered candidate implementation on it, and keeps the fastest.

See DESIGN.md §3 for how plans flow through the synthesizer and executor.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import (TYPE_CHECKING, Callable, Dict, List, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp

from ..device import DEFAULT_PROFILE, DeviceProfile
from .layout import LANES
from .network import Layer, NetworkDescription
from .parallelism import Parallelism
from .plan import (IMPL_PALLAS, IMPL_XLA, ExecutionPlan, LayerPlan)
from .precision import ComputeMode

if TYPE_CHECKING:  # pragma: no cover - typing only
    from .graph import GraphProgram

# The historical hard-coded TPU v5e roofline constants (PEAK_FLOPS,
# HBM_BW, RIDGE) lived here as deprecated module aliases until PR 7; the
# numbers live in :data:`repro.device.TPU_V5E` (the default profile), and
# per-device planning reads ``PlannerConfig.profile``.


@dataclass(frozen=True)
class PlannerConfig:
    #: The device the plan targets: every hardware number the cost rules
    #: consume (peak FLOP/s, bandwidth, ridge point, VMEM envelope budget)
    #: comes from here.  Defaults to the builtin tpu_v5e profile — the
    #: historical hard-coded target.
    profile: DeviceProfile = DEFAULT_PROFILE
    u_max: int = LANES
    u_min: int = 8
    #: Minimum min(Cin, Cout) for the MXU to be worth feeding.
    min_channels_for_pallas: int = 16
    #: Fraction of the roofline ridge point above which a conv counts as
    #: compute-bound (1.0 = the exact ridge).
    compute_bound_fraction: float = 1.0
    #: Dense layers route to the map-major matmul above these dims.
    dense_pallas_min_k: int = 256
    dense_pallas_min_n: int = 128
    batch: int = 1
    #: Whether rule 3 may route layers to the Pallas kernels.  None =
    #: decide from the target and the platform: the profile must support
    #: compiled Pallas and only a real TPU compiles it; elsewhere the
    #: kernels run in interpret mode (a simulator), which is never the
    #: fast path, so the planner keeps XLA.  Force True to exercise the
    #: kernels (tests, kernel debugging, cross-device what-if sweeps) or
    #: False to pin everything to XLA.
    allow_pallas: Optional[bool] = None

    @property
    def pallas_enabled(self) -> bool:
        if self.allow_pallas is not None:
            return self.allow_pallas
        return self.profile.supports_pallas and jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# Shape tracing: (C, H, W) / (F,) per layer output, batch excluded.
# ---------------------------------------------------------------------------

def _spatial_out(h: int, k: int, stride: int, padding: str) -> int:
    return -(-h // stride) if padding == "SAME" else (h - k) // stride + 1


def trace_shapes(net: NetworkDescription) -> Dict[str, Tuple[int, ...]]:
    """Static shape inference over the DAG (layers are topologically
    ordered by construction of the builder API)."""
    shapes: Dict[str, Tuple[int, ...]] = {"input": tuple(net.input_shape)}
    for l in net.layers:
        ins = [shapes[i] for i in l.inputs]
        s = ins[0] if ins else None
        if l.kind == "conv":
            c, h, w = s
            shapes[l.name] = (l.out_channels,
                              _spatial_out(h, l.kernel, l.stride, l.padding),
                              _spatial_out(w, l.kernel, l.stride, l.padding))
        elif l.kind in ("maxpool", "avgpool"):
            c, h, w = s
            shapes[l.name] = (c,
                              _spatial_out(h, l.pool_size, l.stride, l.padding),
                              _spatial_out(w, l.pool_size, l.stride, l.padding))
        elif l.kind == "gap":
            shapes[l.name] = (s[0],)
        elif l.kind == "flatten":
            n = 1
            for d in s:
                n *= d
            shapes[l.name] = (n,)
        elif l.kind == "dense":
            shapes[l.name] = (l.out_channels,)
        elif l.kind == "concat":
            shapes[l.name] = (sum(i[0] for i in ins),) + tuple(s[1:])
        else:                    # relu, lrn, softmax: shape-preserving
            shapes[l.name] = tuple(s)
    return shapes


# ---------------------------------------------------------------------------
# Static cost model
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class LayerCost:
    flops: float
    bytes: float
    #: The device whose roofline turns counts into seconds.
    profile: DeviceProfile = DEFAULT_PROFILE
    #: The arithmetic the layer's mode actually runs ("bf16" or "int8") —
    #: selects which peak-FLOP rate and ridge the roofline terms use.
    dtype: str = "bf16"

    @property
    def arithmetic_intensity(self) -> float:
        return self.flops / max(self.bytes, 1.0)

    @property
    def compute_seconds(self) -> float:
        return self.flops / self.profile.peak_flops(self.dtype)

    @property
    def memory_seconds(self) -> float:
        return self.bytes / self.profile.hbm_bandwidth

    @property
    def dominant(self) -> str:
        return ("compute" if self.compute_seconds >= self.memory_seconds
                else "memory")


def mode_cost_dtype(mode: ComputeMode) -> str:
    """Roofline arithmetic class of a mode: the true int8 datapath moves
    1-byte operands at the int8 MXU rate; every other mode is costed as
    bf16 (PRECISE's f32 penalty is folded into the joint XLA invariant)."""
    return "int8" if mode is ComputeMode.IMPRECISE_INT8 else "bf16"


def _mode_bytes_per_el(mode: ComputeMode) -> int:
    return 1 if mode is ComputeMode.IMPRECISE_INT8 else 2


def conv_cost(cin: int, h: int, w: int, layer: Layer, batch: int,
              bytes_per_el: int = 2,
              profile: DeviceProfile = DEFAULT_PROFILE,
              dtype: str = "bf16") -> LayerCost:
    ho = _spatial_out(h, layer.kernel, layer.stride, layer.padding)
    wo = _spatial_out(w, layer.kernel, layer.stride, layer.padding)
    m, k = layer.out_channels, layer.kernel
    flops = 2.0 * batch * cin * k * k * m * ho * wo
    byts = bytes_per_el * (batch * cin * h * w          # input read
                           + m * cin * k * k            # weights read
                           + batch * m * ho * wo)       # output write
    return LayerCost(flops, byts, profile, dtype)


def dense_cost(k: int, n: int, batch: int, bytes_per_el: int = 2,
               profile: DeviceProfile = DEFAULT_PROFILE,
               dtype: str = "bf16") -> LayerCost:
    flops = 2.0 * batch * k * n
    byts = bytes_per_el * (batch * k + k * n + batch * n)
    return LayerCost(flops, byts, profile, dtype)


def _pow2_at_least(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _choose_u(cin: int, cout: int, cfg: PlannerConfig) -> int:
    u_max = min(cfg.u_max, cfg.profile.lane_width)
    widest = max(cin, cout)
    if widest >= u_max // 2:
        return u_max
    return max(cfg.u_min, _pow2_at_least(widest))


def fused_cost(cost: LayerCost, out_elements: float,
               epilogue_ops: int) -> LayerCost:
    """The cost of a fused group: the anchor's cost plus the epilogue's
    FLOPs, with *no* added bytes — the epilogue runs in-register on the
    accumulator, which is exactly why fusion raises arithmetic intensity
    (the intermediate's HBM round-trip disappears from the group)."""
    if epilogue_ops <= 0:
        return cost
    return LayerCost(cost.flops + epilogue_ops * out_elements, cost.bytes,
                     cost.profile, cost.dtype)


def _plan_conv(layer: Layer, cin: int, h: int, w: int,
               cfg: PlannerConfig, mode: ComputeMode,
               epilogue_ops: int = 0) -> LayerPlan:
    # IMPRECISE_INT8 is costed as the true int8 datapath: 1-byte operand
    # traffic against the int8 MXU rate's ridge — routing decisions must
    # reflect the arithmetic the kernel actually runs, not the bf16 rate
    # the old dequantizing path fell back to.
    cost_dtype = mode_cost_dtype(mode)
    cost = conv_cost(cin, h, w, layer, cfg.batch,
                     bytes_per_el=_mode_bytes_per_el(mode),
                     profile=cfg.profile, dtype=cost_dtype)
    ho = _spatial_out(h, layer.kernel, layer.stride, layer.padding)
    wo = _spatial_out(w, layer.kernel, layer.stride, layer.padding)
    cost = fused_cost(cost, cfg.batch * layer.out_channels * ho * wo,
                      epilogue_ops)
    u = _choose_u(cin, layer.out_channels, cfg)
    ai = cost.arithmetic_intensity
    ridge = cfg.profile.ridge(cost_dtype)
    fused_note = f" [fused+{epilogue_ops} epilogue]" if epilogue_ops else ""

    def mk(impl: str, reason: str) -> LayerPlan:
        return LayerPlan(impl=impl, parallelism=Parallelism.OLP, mode=mode,
                         u=u, reason=reason + fused_note,
                         vmem_budget=cfg.profile.vmem_budget)

    from ..kernels.conv_mapmajor.ops import fits_vmem
    if not fits_vmem(h, w, layer.kernel, layer.stride, layer.padding, u, mode,
                     budget=cfg.profile.vmem_budget):
        return mk(IMPL_XLA, f"rule1: {h}x{w} input block over VMEM envelope "
                            f"({cfg.profile.name})")

    if mode is ComputeMode.PRECISE:
        # Joint invariant (mode_selector.refine_plan): the vector-MAC kernel
        # is reserved for inexact modes; PRECISE is XLA's f32 HIGHEST path.
        return mk(IMPL_XLA,
                  "precise: f32 HIGHEST path (vector MAC is inexact-only)")

    if not cfg.pallas_enabled:
        return mk(IMPL_XLA,
                  f"rule3: Pallas interpret-only on {jax.default_backend()}")

    narrow = min(cin, layer.out_channels) < cfg.min_channels_for_pallas
    compute_bound = ai >= cfg.compute_bound_fraction * ridge
    if compute_bound and not narrow:
        return mk(IMPL_PALLAS,
                  f"rule3: compute-bound (AI={ai:.0f} >= {cost_dtype} ridge "
                  f"{ridge:.0f}, {cfg.profile.name})")
    why = (f"rule3: narrow ({min(cin, layer.out_channels)} ch)" if narrow
           else f"rule3: memory-bound (AI={ai:.0f} < {cost_dtype} ridge "
                f"{ridge:.0f}, {cfg.profile.name})")
    return mk(IMPL_XLA, why)


def _plan_dense(layer: Layer, in_features: int, cfg: PlannerConfig,
                mode: ComputeMode, epilogue_ops: int = 0) -> LayerPlan:
    cost = dense_cost(in_features, layer.out_channels, cfg.batch,
                      bytes_per_el=_mode_bytes_per_el(mode),
                      profile=cfg.profile, dtype=mode_cost_dtype(mode))
    cost = fused_cost(cost, cfg.batch * layer.out_channels, epilogue_ops)
    u = _choose_u(in_features, layer.out_channels, cfg)
    fused_note = f" [fused+{epilogue_ops} epilogue]" if epilogue_ops else ""

    def mk(impl: str, reason: str) -> LayerPlan:
        return LayerPlan(impl=impl, parallelism=Parallelism.OLP, mode=mode,
                         u=u, reason=reason + fused_note,
                         vmem_budget=cfg.profile.vmem_budget)

    if (mode is not ComputeMode.PRECISE and cfg.pallas_enabled
            and in_features >= cfg.dense_pallas_min_k
            and layer.out_channels >= cfg.dense_pallas_min_n):
        return mk(IMPL_PALLAS,
                  f"rule3: MXU-filling matmul K={in_features} "
                  f"N={layer.out_channels} (AI={cost.arithmetic_intensity:.1f})")
    if mode is ComputeMode.PRECISE:
        why = "precise: f32 HIGHEST path (vector MAC is inexact-only)"
    elif not cfg.pallas_enabled:
        why = f"rule3: Pallas interpret-only on {jax.default_backend()}"
    else:
        why = f"rule3: small matmul K={in_features} N={layer.out_channels}"
    return mk(IMPL_XLA, why)


def plan_network(net: NetworkDescription, *,
                 modes: Optional[Dict[str, ComputeMode]] = None,
                 config: Optional[PlannerConfig] = None,
                 graph: "Optional[GraphProgram]" = None) -> ExecutionPlan:
    """Assign a :class:`LayerPlan` to every layer via the static cost model.

    With ``graph=`` (a lowered :class:`~repro.core.graph.GraphProgram`)
    the rule-3 roofline decision for each conv/dense anchor is taken on
    the *fused* FLOP/byte ratio — the epilogue's FLOPs at zero added bytes
    — and the returned plan dispatches through the graph (one op per
    group; the plan fingerprint covers the fusion digest).
    """
    cfg = config or PlannerConfig()
    modes = modes or {}
    shapes = trace_shapes(net)
    epilogue_ops: Dict[str, int] = {}
    if graph is not None:
        epilogue_ops = {g.name: len(g.epilogue) for g in graph.groups
                        if g.fused and g.anchor.kind in ("conv", "dense")}
    layers: Dict[str, LayerPlan] = {}
    for l in net.layers:
        mode = modes.get(l.name, ComputeMode.PRECISE)
        if l.kind == "conv":
            cin, h, w = shapes[l.inputs[0]]
            layers[l.name] = _plan_conv(l, cin, h, w, cfg, mode,
                                        epilogue_ops.get(l.name, 0))
        elif l.kind == "dense":
            in_shape = shapes[l.inputs[0]]
            in_features = 1
            for d in in_shape:
                in_features *= d
            layers[l.name] = _plan_dense(l, in_features, cfg, mode,
                                         epilogue_ops.get(l.name, 0))
        else:
            layers[l.name] = LayerPlan(mode=mode, reason="structural")
    return ExecutionPlan(net.name, layers, origin="planner",
                         profile=cfg.profile, graph=graph)


# ---------------------------------------------------------------------------
# Roofline predictions per dispatch group (cost-model drift, DESIGN.md §12)
# ---------------------------------------------------------------------------

def predict_group_seconds(net: NetworkDescription, plan: ExecutionPlan, *,
                          batch: int = 1) -> Dict[str, float]:
    """Predicted roofline latency per parametric dispatch group, in seconds.

    The prediction is ``max(compute_seconds, memory_seconds)`` of the same
    :class:`LayerCost` the Rule-3 routing decision was taken on — the fused
    group cost when the plan carries a graph (epilogue FLOPs at zero added
    bytes), under the layer's planned mode (operand width + peak-FLOP rate)
    and the plan's device profile.  Keys are group/anchor names; structural
    groups (pooling, softmax chains) carry no prediction — the roofline
    model only speaks for MAC-dominated layers.

    This is the "predicted" column of cost-model drift: obs/drift.py times
    the identical dispatch units (``apply_group``) and reports the
    per-group error, closing the loop the paper's cost-driven synthesis
    assumes but never checks.
    """
    shapes = trace_shapes(net)
    profile = plan.profile
    if plan.graph is not None:
        units = [(g.name, g.anchor, len(g.epilogue))
                 for g in plan.graph.groups]
    else:
        units = [(l.name, l, 0) for l in net.layers]
    out: Dict[str, float] = {}
    for name, anchor, n_epilogue in units:
        if anchor.kind not in ("conv", "dense"):
            continue
        lp = plan.for_layer(name)
        dtype = mode_cost_dtype(lp.mode)
        bpe = _mode_bytes_per_el(lp.mode)
        if anchor.kind == "conv":
            cin, h, w = shapes[anchor.inputs[0]]
            cost = conv_cost(cin, h, w, anchor, batch, bytes_per_el=bpe,
                             profile=profile, dtype=dtype)
            ho = _spatial_out(h, anchor.kernel, anchor.stride, anchor.padding)
            wo = _spatial_out(w, anchor.kernel, anchor.stride, anchor.padding)
            cost = fused_cost(cost, batch * anchor.out_channels * ho * wo,
                              n_epilogue)
        else:
            in_features = 1
            for d in shapes[anchor.inputs[0]]:
                in_features *= d
            cost = dense_cost(in_features, anchor.out_channels, batch,
                              bytes_per_el=bpe, profile=profile, dtype=dtype)
            cost = fused_cost(cost, batch * anchor.out_channels, n_epilogue)
        out[name] = max(cost.compute_seconds, cost.memory_seconds)
    return out


# ---------------------------------------------------------------------------
# Measured autotune pass
# ---------------------------------------------------------------------------

def _time_fn(fn: Callable[[], jnp.ndarray], reps: int) -> float:
    fn().block_until_ready()                       # compile + warm up
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn().block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best


def autotune_plan(net: NetworkDescription, params, x: jnp.ndarray,
                  plan: ExecutionPlan, *,
                  candidates: Sequence[str] = (IMPL_XLA, IMPL_PALLAS),
                  reps: int = 3) -> ExecutionPlan:
    """Refine a static plan with measurements on real activations.

    Runs the planned network once, capturing every parametric layer's input,
    then times each candidate implementation in place and keeps the fastest.
    Timings are taken under each layer's *current plan mode* — the
    synthesizer calls this inside its fixed-point loop, so by the last
    round the measurements describe the final Stage-C modes, not the static
    plan's PRECISE defaults.  Two candidates are dropped up front:

    * the Pallas candidate for VMEM-infeasible convs (rule 1, re-checked
      here on actual shapes so non-planner plans are covered too): the
      kernel's own envelope fallback would silently remeasure XLA and
      could record a Pallas plan for a layer that always executes XLA;
    * the Pallas candidate for PRECISE-mode layers (the joint invariant:
      the vector-MAC kernel is inexact-only; timing it under PRECISE would
      let a measurement contradict ``mode_selector.refine_plan``).

    Under a graph-carrying plan, candidates are timed on the *fused group*
    (``apply_group`` with the anchor's candidate plan, epilogue included)
    — the unit the executor actually dispatches — so a kernel with an
    in-kernel epilogue is credited for the dispatch it saves.
    """
    from ..kernels.conv_mapmajor.ops import fits_vmem
    from .layer_ops import apply_group, apply_layer
    from .network import collect_activations
    from .plan import GroupPlan

    groups = {g.name: g for g in plan.graph.groups} \
        if plan.graph is not None else {}
    acts = collect_activations(net, params, x, plan=plan)
    tuned = dict(plan.layers)
    for l in net.layers:
        if not l.has_params:
            continue
        base = plan.for_layer(l.name)
        x_in = acts[l.inputs[0]]
        layer_candidates = list(candidates)
        if base.mode is ComputeMode.PRECISE and IMPL_PALLAS in layer_candidates:
            layer_candidates.remove(IMPL_PALLAS)
        if l.kind == "conv" and IMPL_PALLAS in layer_candidates:
            _, _, h_in, w_in = x_in.shape
            if not fits_vmem(h_in, w_in, l.kernel, l.stride, l.padding,
                             base.u, base.mode,
                             budget=plan.profile.vmem_budget):
                layer_candidates.remove(IMPL_PALLAS)
        group = groups.get(l.name)
        timings: List[Tuple[float, str]] = []
        for impl in layer_candidates:
            cand = LayerPlan(impl=impl, parallelism=base.parallelism,
                             mode=base.mode, u=base.u,
                             vmem_budget=base.vmem_budget,
                             qparams=base.qparams)
            if group is not None:
                gp = GroupPlan(name=group.name, members=group.signature(),
                               plan=cand)
                run = jax.jit(lambda a, g=group, gp=gp: apply_group(
                    g, gp, params, [a]))
            else:
                run = jax.jit(lambda a, l=l, cand=cand: apply_layer(
                    l, cand, params.get(l.name), [a]))
            # A candidate that fails to compile or run raises: a kernel
            # the chip refuses must never turn into a silent XLA pick.
            timings.append((_time_fn(lambda: run(x_in), reps), impl))
        if not timings:
            continue
        t_best, impl_best = min(timings)
        tuned[l.name] = LayerPlan(
            impl=impl_best, parallelism=base.parallelism, mode=base.mode,
            u=base.u, vmem_budget=base.vmem_budget, qparams=base.qparams,
            reason=f"autotune: {t_best * 1e6:.0f}us best of "
                   f"{len(timings)}")
    return ExecutionPlan(net.name, tuned, origin="autotune",
                         profile=plan.profile, graph=plan.graph)
