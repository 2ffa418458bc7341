"""Device-profile subsystem: per-device cost models for synthesis.

The device is an *input* to synthesis (the paper runs one flow on three
SoCs).  :mod:`profile` defines the frozen :class:`DeviceProfile` value, its
versioned JSON form, and the builtin registry; :mod:`calibrate` measures a
profile on the current backend (with an on-disk cache and a deterministic
CI fallback).  Everything downstream — planner cost rules, the VMEM
envelope, the roofline benchmark, plan fingerprints — reads hardware
numbers from here and only here.  See DESIGN.md §8.
"""
from .calibrate import (cache_key, calibrate, default_cache_dir,
                        load_cached_profile, measure_matmul_flops,
                        measure_stream_bandwidth, measurement_available,
                        resolve_profile, store_cached_profile)
from .profile import (CPU_INTERPRET, DEFAULT_PROFILE, LANE_WIDTH,
                      PROFILE_SCHEMA_VERSION, TPU_PROFILES_BY_KIND, TPU_V4,
                      TPU_V5E, DeviceProfile, ProfileSchemaError, get_profile,
                      profile_for_device, register_profile,
                      registered_profiles)

__all__ = [
    "CPU_INTERPRET", "DEFAULT_PROFILE", "LANE_WIDTH",
    "PROFILE_SCHEMA_VERSION", "TPU_PROFILES_BY_KIND", "TPU_V4", "TPU_V5E",
    "DeviceProfile", "ProfileSchemaError", "get_profile",
    "profile_for_device", "register_profile", "registered_profiles",
    "cache_key", "calibrate", "default_cache_dir", "load_cached_profile",
    "measure_matmul_flops", "measure_stream_bandwidth",
    "measurement_available", "resolve_profile", "store_cached_profile",
]
