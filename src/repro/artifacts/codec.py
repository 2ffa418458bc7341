"""(De)serialization of synthesis artifacts (DESIGN.md §13).

Everything a :class:`~repro.core.synthesizer.SynthesizedProgram` carries is
lowered to plain JSON documents plus two binary blobs:

  program document   the network description, the converged
                     :class:`~repro.core.plan.ExecutionPlan` (per-layer
                     plans, the :class:`~repro.device.DeviceProfile` via its
                     own versioned JSON, the fused
                     :class:`~repro.core.graph.GraphProgram`), the shipped
                     modes, and the full audit trail
                     (:class:`~repro.core.plan.SynthesisReport`,
                     :class:`~repro.core.mode_selector.ModeSelectionReport`);
  weights blob       Stage B's prepared parameters as raw little-endian
                     bytes, described by a sidecar manifest of
                     (layer, param, dtype, shape, nbytes) entries — numpy's
                     ``npz`` is avoided because prepared weights may be
                     ``bfloat16``/``int8`` (ml_dtypes extension dtypes) and
                     the raw-bytes encoding round-trips them exactly, which
                     the recomputed ``params_digest`` depends on;
  executable blobs   one ``jax.export`` serialization per Stage-D batch
                     bucket, stamped with the producing jaxlib version and
                     lowering platforms so a consumer can refuse to
                     deserialize foreign executables *before* handing bytes
                     to the runtime (the plan-only fallback).

Decoding is self-validating where it matters: the caller recomputes the
loaded program's fingerprint (plan dispatch content + prepared-weights
digest) and compares it against the artifact's claimed identity, so a
tampered weight or a hand-edited plan can never hydrate silently.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.graph import FusedGroup, GraphProgram
from ..core.mode_selector import ModeSelectionReport
from ..core.network import Layer, NetworkDescription
from ..core.parallelism import Parallelism
from ..core.plan import (ExecutionPlan, IterationRecord, LayerPlan,
                         SynthesisReport, ValidationRecord)
from ..core.precision import ComputeMode, QParams
from ..core.synthesizer import BatchProgram, SynthesizedProgram
from ..device.profile import DeviceProfile


class ArtifactCodecError(ValueError):
    """An artifact document is malformed or cannot be reconstructed."""


# ---------------------------------------------------------------------------
# Network / graph structure
# ---------------------------------------------------------------------------

_LAYER_FIELDS = ("name", "kind", "inputs", "out_channels", "kernel",
                 "stride", "padding", "use_bias", "pool_size", "lrn_size",
                 "lrn_alpha", "lrn_beta")


def encode_layer(layer: Layer) -> Dict[str, Any]:
    doc = {f: getattr(layer, f) for f in _LAYER_FIELDS}
    doc["inputs"] = list(layer.inputs)
    return doc


def decode_layer(doc: Dict[str, Any]) -> Layer:
    try:
        kwargs = {f: doc[f] for f in _LAYER_FIELDS}
    except KeyError as e:
        raise ArtifactCodecError(f"layer document missing field {e}") from None
    kwargs["inputs"] = tuple(kwargs["inputs"])
    return Layer(**kwargs)


def encode_network(net: NetworkDescription) -> Dict[str, Any]:
    return {"name": net.name,
            "input_shape": list(net.input_shape),
            "layers": [encode_layer(l) for l in net.layers]}


def decode_network(doc: Dict[str, Any]) -> NetworkDescription:
    return NetworkDescription(
        name=doc["name"], input_shape=tuple(doc["input_shape"]),
        layers=[decode_layer(l) for l in doc["layers"]])


def encode_graph(graph: Optional[GraphProgram]) -> Optional[Dict[str, Any]]:
    if graph is None:
        return None
    return {"net_name": graph.net_name,
            "output": graph.output,
            "trace": list(graph.trace),
            "groups": [{"name": g.name,
                        "inputs": list(g.inputs),
                        "layers": [encode_layer(l) for l in g.layers]}
                       for g in graph.groups]}


def decode_graph(doc: Optional[Dict[str, Any]]) -> Optional[GraphProgram]:
    if doc is None:
        return None
    groups = tuple(FusedGroup(name=g["name"],
                              layers=tuple(decode_layer(l)
                                           for l in g["layers"]),
                              inputs=tuple(g["inputs"]))
                   for g in doc["groups"])
    return GraphProgram(net_name=doc["net_name"], groups=groups,
                        output=doc["output"], trace=tuple(doc["trace"]))


# ---------------------------------------------------------------------------
# Plans
# ---------------------------------------------------------------------------

def encode_layer_plan(lp: LayerPlan) -> Dict[str, Any]:
    return {"impl": lp.impl,
            "parallelism": lp.parallelism.value,
            "mode": lp.mode.value,
            "u": lp.u,
            "reason": lp.reason,
            "vmem_budget": lp.vmem_budget,
            "qparams": (None if lp.qparams is None else
                        {"act_scale": float(lp.qparams.act_scale),
                         "zero_point": int(lp.qparams.zero_point)})}


def decode_layer_plan(doc: Dict[str, Any]) -> LayerPlan:
    qp = doc.get("qparams")
    return LayerPlan(impl=doc["impl"],
                     parallelism=Parallelism(doc["parallelism"]),
                     mode=ComputeMode(doc["mode"]),
                     u=int(doc["u"]),
                     reason=doc.get("reason", ""),
                     vmem_budget=doc.get("vmem_budget"),
                     qparams=(None if qp is None else
                              QParams(act_scale=qp["act_scale"],
                                      zero_point=qp["zero_point"])))


def encode_plan(plan: ExecutionPlan) -> Dict[str, Any]:
    return {"net_name": plan.net_name,
            "origin": plan.origin,
            "profile": plan.profile.to_json_dict(),
            "graph": encode_graph(plan.graph),
            "layers": {name: encode_layer_plan(lp)
                       for name, lp in plan.layers.items()}}


def decode_plan(doc: Dict[str, Any]) -> ExecutionPlan:
    try:
        profile = DeviceProfile.from_json_dict(doc["profile"])
    except ValueError as e:
        raise ArtifactCodecError(f"embedded device profile invalid: {e}") \
            from None
    return ExecutionPlan(
        net_name=doc["net_name"],
        layers={name: decode_layer_plan(lp)
                for name, lp in doc["layers"].items()},
        origin=doc.get("origin", "planner"),
        profile=profile,
        graph=decode_graph(doc.get("graph")))


# ---------------------------------------------------------------------------
# Reports (the audit trail a store hit must restore intact)
# ---------------------------------------------------------------------------

def _encode_modes(modes: Dict[str, ComputeMode]) -> Dict[str, str]:
    return {n: m.value for n, m in modes.items()}


def _decode_modes(doc: Dict[str, str]) -> Dict[str, ComputeMode]:
    return {n: ComputeMode(v) for n, v in doc.items()}


def encode_synthesis_report(r: Optional[SynthesisReport]
                            ) -> Optional[Dict[str, Any]]:
    if r is None:
        return None
    return {
        "iterations": [{"index": it.index,
                        "plan_fingerprint": it.plan_fingerprint,
                        "modes": _encode_modes(it.modes),
                        "probe_metric": it.probe_metric,
                        "evaluations": it.evaluations}
                       for it in r.iterations],
        "converged": r.converged,
        "tie_broken": r.tie_broken,
        "max_iterations": r.max_iterations,
        "reference_accuracy": r.reference_accuracy,
        "validations": [{"plan_fingerprint": v.plan_fingerprint,
                         "modes": _encode_modes(v.modes),
                         "accuracy": v.accuracy,
                         "degradation": v.degradation,
                         "passed": v.passed}
                        for v in r.validations],
        "fallbacks": list(r.fallbacks),
        "validated": r.validated,
        "gate_skipped_reason": r.gate_skipped_reason,
        "act_scales": dict(r.act_scales),
    }


def decode_synthesis_report(doc: Optional[Dict[str, Any]]
                            ) -> Optional[SynthesisReport]:
    if doc is None:
        return None
    return SynthesisReport(
        iterations=[IterationRecord(
            index=it["index"], plan_fingerprint=it["plan_fingerprint"],
            modes=_decode_modes(it["modes"]),
            probe_metric=it["probe_metric"],
            evaluations=it["evaluations"]) for it in doc["iterations"]],
        converged=doc["converged"],
        tie_broken=doc["tie_broken"],
        max_iterations=doc["max_iterations"],
        reference_accuracy=doc.get("reference_accuracy"),
        validations=[ValidationRecord(
            plan_fingerprint=v["plan_fingerprint"],
            modes=_decode_modes(v["modes"]), accuracy=v["accuracy"],
            degradation=v["degradation"], passed=v["passed"])
            for v in doc["validations"]],
        fallbacks=list(doc["fallbacks"]),
        validated=doc["validated"],
        gate_skipped_reason=doc.get("gate_skipped_reason"),
        act_scales=dict(doc.get("act_scales", {})))


def encode_mode_report(r: Optional[ModeSelectionReport]
                       ) -> Optional[Dict[str, Any]]:
    if r is None:
        return None
    return {"reference_metric": r.reference_metric,
            "final_metric": r.final_metric,
            "modes": _encode_modes(r.modes),
            "evaluations": r.evaluations,
            "trace": list(r.trace)}


def decode_mode_report(doc: Optional[Dict[str, Any]]
                       ) -> Optional[ModeSelectionReport]:
    if doc is None:
        return None
    return ModeSelectionReport(
        reference_metric=doc["reference_metric"],
        final_metric=doc["final_metric"],
        modes=_decode_modes(doc["modes"]),
        evaluations=doc["evaluations"],
        trace=list(doc["trace"]))


# ---------------------------------------------------------------------------
# Prepared weights: raw bytes + manifest (exact round-trip, all dtypes)
# ---------------------------------------------------------------------------

def _dtype_from_name(name: str) -> np.dtype:
    """Resolve a dtype name, including jax's ml_dtypes extensions
    (``bfloat16``) numpy alone cannot parse."""
    try:
        return np.dtype(name)
    except TypeError:
        pass
    ext = getattr(jnp, name, None)
    if ext is None:
        raise ArtifactCodecError(f"unknown weight dtype {name!r}")
    return np.dtype(ext)


def encode_weights(prepared: Dict[str, Dict[str, jnp.ndarray]]
                   ) -> Tuple[List[Dict[str, Any]], bytes]:
    """Prepared params -> (entry manifest, concatenated raw bytes).

    Deterministic order (layer name, then param name) so identical
    programs always produce identical blobs — concurrent writers racing
    on one fingerprint write the same content.
    """
    entries: List[Dict[str, Any]] = []
    chunks: List[bytes] = []
    for lname in sorted(prepared):
        for pname in sorted(prepared[lname]):
            arr = np.asarray(prepared[lname][pname])
            raw = arr.tobytes()
            entries.append({"layer": lname, "param": pname,
                            "dtype": str(arr.dtype),
                            "shape": list(arr.shape),
                            "nbytes": len(raw)})
            chunks.append(raw)
    return entries, b"".join(chunks)


def decode_weights(entries: List[Dict[str, Any]], blob: bytes
                   ) -> Dict[str, Dict[str, jnp.ndarray]]:
    prepared: Dict[str, Dict[str, jnp.ndarray]] = {}
    offset = 0
    for e in entries:
        n = int(e["nbytes"])
        raw = blob[offset:offset + n]
        if len(raw) != n:
            raise ArtifactCodecError(
                f"weights blob truncated at {e['layer']}/{e['param']}: "
                f"wanted {n} bytes, {len(raw)} left")
        arr = np.frombuffer(raw, dtype=_dtype_from_name(e["dtype"]))
        arr = arr.reshape(tuple(e["shape"]))
        prepared.setdefault(e["layer"], {})[e["param"]] = jnp.asarray(arr)
        offset += n
    if offset != len(blob):
        raise ArtifactCodecError(
            f"weights blob has {len(blob) - offset} trailing bytes")
    return prepared


# ---------------------------------------------------------------------------
# Whole-program document
# ---------------------------------------------------------------------------

def encode_program(program: SynthesizedProgram) -> Dict[str, Any]:
    """The JSON half of a program artifact (weights travel separately)."""
    return {
        "fingerprint": program.fingerprint(),
        "net": encode_network(program.net),
        "plan": encode_plan(program.plan),
        "modes": _encode_modes(program.modes),
        "parallelism": program.parallelism.value,
        "mode_report": encode_mode_report(program.mode_report),
        "synthesis_report": encode_synthesis_report(program.synthesis_report),
        "synthesis_seconds": program.synthesis_seconds,
        "vector_width": program.vector_width,
        "input_dtype": str(np.dtype(program.input_dtype)),
    }


def decode_program(doc: Dict[str, Any],
                   prepared: Dict[str, Dict[str, jnp.ndarray]]
                   ) -> SynthesizedProgram:
    """Rebuild the program; the caller verifies the recomputed fingerprint
    against the artifact's claimed identity (store.py does)."""
    try:
        return SynthesizedProgram(
            net=decode_network(doc["net"]),
            plan=decode_plan(doc["plan"]),
            modes=_decode_modes(doc["modes"]),
            parallelism=Parallelism(doc["parallelism"]),
            mode_report=decode_mode_report(doc.get("mode_report")),
            synthesis_seconds=float(doc.get("synthesis_seconds", 0.0)),
            synthesis_report=decode_synthesis_report(
                doc.get("synthesis_report")),
            prepared=prepared,
            vector_width=int(doc["vector_width"]),
            input_dtype=_dtype_from_name(doc["input_dtype"]))
    except (KeyError, TypeError, ValueError) as e:
        if isinstance(e, ArtifactCodecError):
            raise
        raise ArtifactCodecError(f"program document invalid: {e}") from None


# ---------------------------------------------------------------------------
# Stage-D executables via jax.export (the zero-recompile path)
# ---------------------------------------------------------------------------

def executable_stamp() -> Dict[str, Any]:
    """The environment identity an exported executable is only valid under.

    ``jax.export`` blobs embed lowered StableHLO for specific platforms;
    deserializing under a different jaxlib or backend is at best a compile
    error and at worst silent misbehavior, so the stamp is checked *before*
    bytes reach the runtime and a mismatch downgrades to the plan-only
    path (Stage D recompiles).
    """
    import jaxlib

    return {"jaxlib": getattr(jaxlib, "__version__", "unknown"),
            "backend": jax.default_backend()}


def export_executable(program: SynthesizedProgram,
                      batch: int) -> Tuple[bytes, Dict[str, Any]]:
    """Serialize the Stage-D computation for one batch bucket.

    Raises :class:`ArtifactCodecError` when the program cannot be exported
    (a lowering jax.export does not support) — the caller degrades to a
    plan-only artifact.
    """
    from jax import export as jax_export

    shape = (batch, *program.net.input_shape)
    try:
        exp = jax_export.export(jax.jit(program._forward))(
            jax.ShapeDtypeStruct(shape, program.input_dtype))
        blob = exp.serialize()
        platforms = list(exp.platforms)
    except Exception as e:  # jax.export raises a zoo of types
        raise ArtifactCodecError(
            f"jax.export cannot serialize Stage D for batch {batch}: "
            f"{type(e).__name__}: {e}") from None
    meta = {"batch": batch, "input_shape": list(shape),
            "platforms": platforms, **executable_stamp()}
    return bytes(blob), meta


def hydrate_executable(program: SynthesizedProgram, batch: int,
                       blob: bytes, meta: Dict[str, Any]) -> BatchProgram:
    """Deserialize an exported Stage-D blob into a servable BatchProgram.

    The stamp must already have been checked by the caller; deserialization
    failures still raise :class:`ArtifactCodecError` (corrupt blob).  The
    hydrated program records ``compile_seconds=0.0`` — no Stage-D compile
    was paid — and the deserialization wall time is the store's
    ``artifact_hydrate_seconds_total`` business, not this function's.
    """
    from jax import export as jax_export

    shape = (batch, *program.net.input_shape)
    if tuple(meta.get("input_shape", shape)) != shape:
        raise ArtifactCodecError(
            f"executable was exported for shape {meta.get('input_shape')}, "
            f"program wants {list(shape)}")
    try:
        exp = jax_export.deserialize(bytearray(blob))
    except Exception as e:
        raise ArtifactCodecError(
            f"cannot deserialize Stage-D executable for batch {batch}: "
            f"{type(e).__name__}: {e}") from None
    return BatchProgram(batch=batch, input_shape=shape,
                        plan_fingerprint=program.plan.fingerprint(),
                        compile_seconds=0.0,
                        _compiled=exp.call)


def stamp_matches(meta: Dict[str, Any]) -> Tuple[bool, str]:
    """Does this host match an executable's producing environment?"""
    stamp = executable_stamp()
    if meta.get("jaxlib") != stamp["jaxlib"]:
        return False, (f"jaxlib {meta.get('jaxlib')!r} != "
                       f"{stamp['jaxlib']!r}")
    if stamp["backend"] not in meta.get("platforms", ()):
        return False, (f"backend {stamp['backend']!r} not in exported "
                       f"platforms {meta.get('platforms')!r}")
    return True, ""


__all__ = [
    "ArtifactCodecError",
    "decode_graph", "decode_layer", "decode_layer_plan", "decode_mode_report",
    "decode_network", "decode_plan", "decode_program",
    "decode_synthesis_report", "decode_weights",
    "encode_graph", "encode_layer", "encode_layer_plan", "encode_mode_report",
    "encode_network", "encode_plan", "encode_program",
    "encode_synthesis_report", "encode_weights",
    "executable_stamp", "export_executable",
    "hydrate_executable", "stamp_matches",
]
