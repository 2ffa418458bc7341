"""Microbenchmark calibration: measure a :class:`DeviceProfile` in place.

The paper's cost model is only as good as its hardware numbers, and
datasheet numbers are only as good as the software stack's ability to reach
them — Lu et al. (PAPERS.md) model mobile CNN resource requirements by
*measuring* per-device characteristics rather than trusting specs.  This
module does the same for the synthesis pipeline:

  * :func:`measure_matmul_flops` — an MXU FLOP-rate sweep: square matmuls
    of increasing size, best sustained rate wins (small sizes are launch-
    bound, so the sweep's max approximates peak).
  * :func:`measure_stream_bandwidth` — a streaming probe: a saxpy-shaped
    read+write over buffers too large to cache, best sustained byte rate.
  * :func:`calibrate` — runs both and returns a new profile
    (``source="calibrated"``) with the measured numbers folded in.

Every timing loop takes an injectable ``clock`` so calibration is
deterministic under test (a stubbed clock yields exact, repeatable rates).

**Profile cache and fallback.**  Calibration is seconds of device time, so
:func:`resolve_profile` persists measurements to an on-disk cache keyed by
``(backend, device kind)`` and reloads them on later runs.  When
measurement is unavailable — any non-TPU backend, i.e. CPU CI, where
timing the interpreter would calibrate the *simulator* — it falls back to
the builtin registry deterministically instead (``cpu_interpret`` off-TPU,
the chip's builtin by device kind otherwise).

CLI (used by CI to produce and validate a profile artifact):

    PYTHONPATH=src python -m repro.device.calibrate --out profile.json
"""
from __future__ import annotations

import argparse
import os
import re
import time
from dataclasses import replace
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .profile import (CPU_INTERPRET, DeviceProfile, ProfileSchemaError,
                      get_profile, profile_for_device)

Clock = Callable[[], float]

#: Square matmul sizes for the FLOP-rate sweep.
MATMUL_SWEEP: Tuple[int, ...] = (256, 512, 1024, 2048)
#: Streaming-probe buffer sizes (elements of f32).
STREAM_SWEEP: Tuple[int, ...] = (1 << 22, 1 << 24)


def _best_seconds(fn: Callable[[], jax.Array], reps: int,
                  clock: Clock) -> float:
    """Best-of-``reps`` wall time of ``fn`` (first call warms up/compiles)."""
    fn().block_until_ready()
    best = float("inf")
    for _ in range(reps):
        t0 = clock()
        fn().block_until_ready()
        best = min(best, clock() - t0)
    return max(best, 1e-12)            # a stubbed clock may tick 0


def measure_matmul_flops(dtype=jnp.bfloat16, *,
                         sizes: Sequence[int] = MATMUL_SWEEP,
                         reps: int = 3, clock: Clock = time.perf_counter,
                         seed: int = 0) -> float:
    """Best sustained matmul FLOP/s over a size sweep (2*n^3 per call).

    Integer dtypes (the int8 datapath sweep) use uniform int8-range
    operands and ``preferred_element_type=int32`` — the same MXU
    configuration the int8 kernels request — so the measured rate is the
    rate IMPRECISE_INT8 groups are costed against."""
    integer = jnp.issubdtype(jnp.dtype(dtype), jnp.integer)
    acc = jnp.int32 if integer else jnp.float32
    best_rate = 0.0
    for n in sizes:
        key = jax.random.PRNGKey(seed)
        if integer:
            a = jax.random.randint(key, (n, n), -127, 128, jnp.int32).astype(dtype)
            b = jax.random.randint(key, (n, n), -127, 128, jnp.int32).astype(dtype)
        else:
            a = jax.random.normal(key, (n, n), dtype=jnp.float32).astype(dtype)
            b = jax.random.normal(key, (n, n), dtype=jnp.float32).astype(dtype)
        f = jax.jit(lambda x, y: jnp.dot(x, y, preferred_element_type=acc))
        t = _best_seconds(lambda: f(a, b), reps, clock)
        best_rate = max(best_rate, 2.0 * n ** 3 / t)
    return best_rate


def measure_stream_bandwidth(*, sizes: Sequence[int] = STREAM_SWEEP,
                             reps: int = 3,
                             clock: Clock = time.perf_counter,
                             seed: int = 0) -> float:
    """Best sustained streaming bytes/s: y = a*x + c reads x, writes y."""
    best_rate = 0.0
    for n in sizes:
        x = jax.random.normal(jax.random.PRNGKey(seed), (n,),
                              dtype=jnp.float32)
        f = jax.jit(lambda v: 2.5 * v + 1.0)
        t = _best_seconds(lambda: f(x), reps, clock)
        moved = 2 * n * 4              # one f32 read + one f32 write
        best_rate = max(best_rate, moved / t)
    return best_rate


def calibrate(base: Optional[DeviceProfile] = None, *,
              sizes: Sequence[int] = MATMUL_SWEEP,
              stream_sizes: Sequence[int] = STREAM_SWEEP,
              reps: int = 3, clock: Clock = time.perf_counter,
              seed: int = 0) -> DeviceProfile:
    """Measure this host's backend and return a calibrated profile.

    ``base`` supplies the fields microbenchmarks cannot see (VMEM budget,
    lane width, link bandwidth, Pallas support); defaults to the builtin
    matching this backend.  int8 peak is *measured* with its own sweep —
    int8 x int8 -> int32 matmuls, the exact MXU configuration the
    IMPRECISE_INT8 kernels run — so the planner's int8 ridge reflects this
    host's real integer throughput rather than a datasheet ratio.
    """
    if base is None:
        base = _builtin_for_host()
    bf16 = measure_matmul_flops(jnp.bfloat16, sizes=sizes, reps=reps,
                                clock=clock, seed=seed)
    f32 = measure_matmul_flops(jnp.float32, sizes=sizes, reps=reps,
                               clock=clock, seed=seed)
    int8 = measure_matmul_flops(jnp.int8, sizes=sizes, reps=reps,
                                clock=clock, seed=seed)
    bw = measure_stream_bandwidth(sizes=stream_sizes, reps=reps, clock=clock,
                                  seed=seed)
    return replace(
        base,
        peak_flops_bf16=bf16,
        peak_flops_f32=f32,
        peak_flops_int8=int8,
        hbm_bandwidth=bw,
        source="calibrated",
        description=(f"calibrated on backend={jax.default_backend()} "
                     f"device_kind={_device_kind()} (base {base.name})"))


# ---------------------------------------------------------------------------
# On-disk profile cache + deterministic resolution
# ---------------------------------------------------------------------------

def _device_kind() -> str:
    devs = jax.devices()
    return devs[0].device_kind if devs else "unknown"


def _sanitize(s: str) -> str:
    return re.sub(r"[^A-Za-z0-9_.-]+", "_", s).strip("_") or "unknown"


def default_cache_dir() -> str:
    """Where calibrated profiles persist between runs (env-overridable)."""
    env = os.environ.get("REPRO_DEVICE_PROFILE_CACHE")
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "repro",
                        "device_profiles")


def cache_key(backend: Optional[str] = None,
              device_kind: Optional[str] = None) -> str:
    """Cache filename stem for the current (backend, device kind) pair."""
    backend = backend or jax.default_backend()
    device_kind = device_kind or _device_kind()
    return f"{_sanitize(backend)}__{_sanitize(device_kind)}"


def _cache_path(cache_dir: str, key: str) -> str:
    return os.path.join(cache_dir, key + ".json")


def load_cached_profile(cache_dir: Optional[str] = None,
                        key: Optional[str] = None
                        ) -> Optional[DeviceProfile]:
    """The cached calibration for this device, or None on miss/corruption
    (an unreadable or wrong-version entry counts as a miss — it will be
    re-measured and overwritten, never trusted)."""
    path = _cache_path(cache_dir or default_cache_dir(), key or cache_key())
    if not os.path.exists(path):
        return None
    try:
        return DeviceProfile.load(path)
    except (ProfileSchemaError, OSError):
        return None


def store_cached_profile(profile: DeviceProfile,
                         cache_dir: Optional[str] = None,
                         key: Optional[str] = None) -> str:
    cache_dir = cache_dir or default_cache_dir()
    os.makedirs(cache_dir, exist_ok=True)
    path = _cache_path(cache_dir, key or cache_key())
    tmp = path + ".tmp"
    profile.save(tmp)
    os.replace(tmp, path)              # atomic: readers never see a partial
    return path


def measurement_available() -> bool:
    """True when microbenchmarks measure real hardware.  Off-TPU the Pallas
    stack interprets and CI machines are noisy/shared — calibrating there
    would bake scheduler jitter into plans, so we fall back instead."""
    return jax.default_backend() == "tpu"


def resolve_profile(device: "str | DeviceProfile | None" = None, *,
                    allow_calibration: bool = True,
                    use_cache: bool = True,
                    cache_dir: Optional[str] = None,
                    clock: Clock = time.perf_counter) -> DeviceProfile:
    """Turn a device spec into a profile — the synthesis entry point.

      * a :class:`DeviceProfile` passes through untouched;
      * a registry name ("tpu_v4") returns that builtin;
      * ``None`` / ``"auto"`` means *this host*: cached calibration if
        present, fresh calibration (persisted) when measurement is
        available, else the deterministic builtin fallback.
    """
    if isinstance(device, DeviceProfile):
        return device
    if device is not None and device != "auto":
        return get_profile(device)
    if use_cache:
        cached = load_cached_profile(cache_dir)
        if cached is not None:
            return cached
    if allow_calibration and measurement_available():
        profile = calibrate(clock=clock)
        if use_cache:
            store_cached_profile(profile, cache_dir)
        return profile
    return _builtin_for_host()


def _builtin_for_host() -> DeviceProfile:
    """This host's builtin: the chip's profile by device kind on a TPU,
    ``cpu_interpret`` elsewhere."""
    if jax.default_backend() == "tpu":
        return profile_for_device()
    return CPU_INTERPRET


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="device_profile.json",
                    help="where to write the resolved profile JSON")
    ap.add_argument("--device", default="auto",
                    help="registry name, or 'auto' to calibrate/fall back")
    ap.add_argument("--force-measure", action="store_true",
                    help="run the microbenchmarks even off-TPU (numbers "
                         "describe this host, not a deployment target)")
    ap.add_argument("--no-cache", action="store_true",
                    help="skip the on-disk profile cache entirely")
    args = ap.parse_args()

    if args.force_measure:
        base = None if args.device == "auto" else get_profile(args.device)
        profile = calibrate(base)
    else:
        profile = resolve_profile(args.device, use_cache=not args.no_cache)
    profile.save(args.out)
    print(f"wrote {args.out}: {profile.summary()}")
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
