"""Device profiles: the single source of hardware truth for synthesis.

Cappuccino's headline experiment runs one synthesis flow against *three*
mobile SoCs — the device is an input to synthesis, not an ambient constant.
This module is the TPU-generation analogue: a :class:`DeviceProfile` carries
every hardware number the pipeline consumes (per-dtype peak FLOP/s, HBM
bandwidth, the per-block VMEM budget behind the planner's rule-1 envelope,
the vector lane width behind map-major grouping, and the derived roofline
ridge point), and everything downstream — the planner's cost rules, the
VMEM envelope, ``benchmarks/roofline.py``, the plan fingerprint the serving
``ProgramCache`` keys on — reads from a profile instead of redeclaring
constants.

Three builtin targets mirror the paper's three devices:

  ``tpu_v5e``       the historical default; its numbers are byte-for-byte
                    the constants the planner and roofline benchmark used
                    to hard-code.
  ``tpu_v4``        a second real accelerator generation: more FLOP/s *and*
                    more bandwidth, with a different ridge point — plans
                    legitimately diverge from v5e.
  ``cpu_interpret`` the CI fallback: Pallas kernels only interpret here, so
                    the profile disables Pallas routing and carries a small
                    cache-resident "VMEM" budget.

Profiles serialize to versioned JSON (``save``/``load``); unknown schema
versions are rejected loudly so a stale on-disk calibration can never be
silently misread.  ``identity()`` is the content digest folded into
``ExecutionPlan.fingerprint()`` — two plans synthesized for different
devices can never alias in any cache.  Measured (calibrated) profiles come
from :mod:`repro.device.calibrate`.

Validate a profile JSON from the command line:

    PYTHONPATH=src python -m repro.device.profile profile.json
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
from dataclasses import dataclass
from typing import Any, Dict, Tuple

#: Version tag written into every serialized profile; bump on field changes.
PROFILE_SCHEMA_VERSION = 1

#: TPU VPU lane width / MXU minor dimension — the natural map-major ``u``.
#: The single declaration; ``repro.core.layout.LANES`` re-exports it.
LANE_WIDTH = 128


class ProfileSchemaError(ValueError):
    """A profile document is malformed or from an unknown schema version."""


@dataclass(frozen=True)
class DeviceProfile:
    """One device's resource characteristics, as synthesis consumes them.

    Frozen: profiles are values.  A calibrated profile is a *new* value
    (``source="calibrated"``) with its own :meth:`identity`.
    """
    name: str
    #: Peak MAC throughput per operand dtype, FLOP/s.
    peak_flops_f32: float
    peak_flops_bf16: float
    peak_flops_int8: float
    #: Main-memory streaming bandwidth, bytes/s.
    hbm_bandwidth: float
    #: Per-block on-chip scratch budget (bytes) the map-major conv kernel
    #: may spend on one input block — the planner's rule-1 envelope.
    vmem_budget: int
    #: Vector lane width (map-major channel-group ``u`` ceiling).
    lane_width: int = LANE_WIDTH
    #: Inter-chip link bandwidth, bytes/s per link (0 = single-chip target).
    link_bandwidth: float = 0.0
    #: Whether the Pallas kernels *compile* on this target (False = they
    #: only interpret, so the planner must never route to them for speed).
    supports_pallas: bool = True
    #: "builtin" | "calibrated" | "file" — provenance, not identity.
    source: str = "builtin"
    description: str = ""

    def __post_init__(self):
        if not self.name:
            raise ValueError("profile name must be non-empty")
        for field in ("peak_flops_f32", "peak_flops_bf16", "peak_flops_int8",
                      "hbm_bandwidth"):
            if getattr(self, field) <= 0:
                raise ValueError(f"{field} must be positive")
        if self.vmem_budget <= 0 or self.lane_width <= 0:
            raise ValueError("vmem_budget and lane_width must be positive")

    # -- derived roofline quantities ----------------------------------------
    def peak_flops(self, dtype: str = "bf16") -> float:
        try:
            return {"f32": self.peak_flops_f32,
                    "float32": self.peak_flops_f32,
                    "bf16": self.peak_flops_bf16,
                    "bfloat16": self.peak_flops_bf16,
                    "int8": self.peak_flops_int8}[dtype]
        except KeyError:
            raise KeyError(f"no peak FLOP/s entry for dtype {dtype!r}") \
                from None

    def ridge(self, dtype: str = "bf16") -> float:
        """Arithmetic intensity (FLOPs/byte) where compute time equals
        memory time — the roofline ridge point for ``dtype`` operands."""
        return self.peak_flops(dtype) / self.hbm_bandwidth

    # -- identity -----------------------------------------------------------
    def identity(self) -> str:
        """Content digest of everything that changes a synthesis decision.

        Covers the name and every hardware number; excludes ``source`` and
        ``description`` (provenance/prose — a builtin v5e profile and a file
        reload of it are the *same* device).  Folded into
        ``ExecutionPlan.fingerprint()`` so the serving ``ProgramCache``
        never serves a plan synthesized for a different device.
        """
        h = hashlib.sha256()
        h.update(self.name.encode())
        for v in (self.peak_flops_f32, self.peak_flops_bf16,
                  self.peak_flops_int8, self.hbm_bandwidth, self.vmem_budget,
                  self.lane_width, self.link_bandwidth, self.supports_pallas):
            h.update(f"|{v!r}".encode())
        return h.hexdigest()[:12]

    # -- versioned JSON (de)serialization -----------------------------------
    def to_json_dict(self) -> Dict[str, Any]:
        doc = dataclasses.asdict(self)
        doc["schema_version"] = PROFILE_SCHEMA_VERSION
        doc["identity"] = self.identity()
        return doc

    @classmethod
    def from_json_dict(cls, doc: Any) -> "DeviceProfile":
        if not isinstance(doc, dict):
            raise ProfileSchemaError("profile document must be a JSON object")
        version = doc.get("schema_version")
        if version != PROFILE_SCHEMA_VERSION:
            raise ProfileSchemaError(
                f"unknown profile schema_version {version!r} "
                f"(this build reads version {PROFILE_SCHEMA_VERSION}); "
                "refusing to guess at field meanings")
        fields = {f.name for f in dataclasses.fields(cls)}
        missing = {"name", "peak_flops_f32", "peak_flops_bf16",
                   "peak_flops_int8", "hbm_bandwidth", "vmem_budget"} \
            - set(doc)
        if missing:
            raise ProfileSchemaError(f"profile missing fields: "
                                     f"{', '.join(sorted(missing))}")
        kwargs = {k: v for k, v in doc.items() if k in fields}
        profile = cls(**kwargs)
        declared = doc.get("identity")
        if declared is not None and declared != profile.identity():
            raise ProfileSchemaError(
                f"profile identity mismatch: file says {declared}, fields "
                f"hash to {profile.identity()} (corrupt or hand-edited)")
        return profile

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_json_dict(), f, indent=2, sort_keys=True)
            f.write("\n")

    @classmethod
    def load(cls, path: str) -> "DeviceProfile":
        with open(path) as f:
            try:
                doc = json.load(f)
            except json.JSONDecodeError as e:
                raise ProfileSchemaError(f"{path}: not valid JSON ({e})") \
                    from None
        return cls.from_json_dict(doc)

    def summary(self) -> str:
        return (f"{self.name} [{self.source}]: "
                f"bf16 {self.peak_flops_bf16 / 1e12:.1f} TFLOP/s, "
                f"f32 {self.peak_flops_f32 / 1e12:.1f} TFLOP/s, "
                f"HBM {self.hbm_bandwidth / 1e9:.0f} GB/s, "
                f"ridge {self.ridge():.0f} FLOPs/B, "
                f"VMEM block {self.vmem_budget // (1024 * 1024)} MB, "
                f"u<= {self.lane_width}, "
                f"pallas={'yes' if self.supports_pallas else 'interpret-only'}")


# ---------------------------------------------------------------------------
# Builtin registry: the repo's three devices (paper Table I has three SoCs).
# ---------------------------------------------------------------------------

#: The historical defaults: exactly the constants core/planner.py and
#: benchmarks/roofline.py used to declare by hand.
TPU_V5E = DeviceProfile(
    name="tpu_v5e",
    peak_flops_f32=49.25e12,          # bf16 peak / 4 (MXU f32 passes)
    peak_flops_bf16=197e12,
    peak_flops_int8=394e12,
    hbm_bandwidth=819e9,
    vmem_budget=24 * 1024 * 1024,
    lane_width=LANE_WIDTH,
    link_bandwidth=50e9,              # per ICI link
    description="TPU v5e per chip: 197 TFLOP/s bf16, 819 GB/s HBM")

TPU_V4 = DeviceProfile(
    name="tpu_v4",
    peak_flops_f32=68.75e12,
    peak_flops_bf16=275e12,
    peak_flops_int8=275e12,           # v4 has no int8 doubling
    hbm_bandwidth=1228e9,
    vmem_budget=32 * 1024 * 1024,
    lane_width=LANE_WIDTH,
    link_bandwidth=50e9,
    description="TPU v4 per chip: 275 TFLOP/s bf16, 1228 GB/s HBM")

CPU_INTERPRET = DeviceProfile(
    name="cpu_interpret",
    peak_flops_f32=200e9,
    peak_flops_bf16=100e9,            # emulated bf16 is slower than f32
    peak_flops_int8=400e9,
    hbm_bandwidth=40e9,
    vmem_budget=2 * 1024 * 1024,      # L2-slice-sized block budget
    lane_width=LANE_WIDTH,            # map-major layout kept TPU-shaped
    link_bandwidth=0.0,
    supports_pallas=False,            # Pallas TPU kernels only interpret here
    description="CPU host (CI): XLA-only, Pallas in interpret mode")

#: What the pipeline assumes when no device is named off-TPU — the
#: historical hard-coded target, so default plans and fingerprints on a
#: test host stay v5e-shaped (Pallas only interprets there, so the planner
#: keeps XLA).  On a TPU the chip itself is the target:
#: :func:`profile_for_device`.
DEFAULT_PROFILE = TPU_V5E

#: The builtin profile of each TPU generation, keyed by ``device_kind`` as
#: JAX reports it.
TPU_PROFILES_BY_KIND: Dict[str, DeviceProfile] = {
    "TPU v5 lite": TPU_V5E,
    "TPU v4": TPU_V4,
}


def profile_for_device(device: Any = None) -> DeviceProfile:
    """The builtin profile for a JAX device (default: ``jax.devices()[0]``).

    A TPU is looked up by its ``device_kind`` in
    :data:`TPU_PROFILES_BY_KIND`; a TPU kind missing from the table is an
    error, never silently planned as a v5e.  Any other platform plans for
    :data:`DEFAULT_PROFILE`.
    """
    if device is None:
        import jax
        device = jax.devices()[0]
    if device.platform != "tpu":
        return DEFAULT_PROFILE
    try:
        return TPU_PROFILES_BY_KIND[device.device_kind]
    except KeyError:
        raise KeyError(
            f"no device profile for TPU kind {device.device_kind!r}; known "
            f"kinds: {', '.join(sorted(TPU_PROFILES_BY_KIND))}") from None

_REGISTRY: Dict[str, DeviceProfile] = {}


def register_profile(profile: DeviceProfile, *,
                     allow_replace: bool = False) -> DeviceProfile:
    """Add a profile to the registry (e.g. a calibrated measurement)."""
    if profile.name in _REGISTRY and not allow_replace:
        raise ValueError(f"profile {profile.name!r} already registered; "
                         "pass allow_replace=True to overwrite")
    _REGISTRY[profile.name] = profile
    return profile


for _p in (TPU_V5E, TPU_V4, CPU_INTERPRET):
    register_profile(_p)


def get_profile(name: str) -> DeviceProfile:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown device profile {name!r}; registered: "
                       f"{', '.join(sorted(_REGISTRY))}") from None


def registered_profiles() -> Tuple[DeviceProfile, ...]:
    """All registered profiles, sorted by name (deterministic sweeps)."""
    return tuple(_REGISTRY[n] for n in sorted(_REGISTRY))


def main(argv) -> int:
    """Validate profile JSON files: round-trip each and print a summary."""
    if not argv:
        print("usage: python -m repro.device.profile PROFILE.json [...]")
        return 2
    bad = 0
    for path in argv:
        try:
            p = DeviceProfile.load(path)
            print(f"{path}: ok — {p.summary()}")
        except (OSError, ProfileSchemaError, ValueError, TypeError) as e:
            print(f"{path}: INVALID — {e}")
            bad += 1
    return 1 if bad else 0


if __name__ == "__main__":
    import sys
    sys.exit(main(sys.argv[1:]))
