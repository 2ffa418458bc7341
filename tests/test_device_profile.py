"""Device-profile subsystem: serialization, calibration, the on-disk
profile cache, profile-aware planning, and device-keyed program identity."""
import dataclasses
import json
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from repro.cnn import alexnet, init_network_params
from repro.core import (ComputeMode, IMPL_PALLAS, IMPL_XLA, NetworkDescription,
                        PlannerConfig, plan_network, synthesize)
from repro.device import (CPU_INTERPRET, DEFAULT_PROFILE,
                          PROFILE_SCHEMA_VERSION, TPU_PROFILES_BY_KIND,
                          TPU_V4, TPU_V5E, DeviceProfile, ProfileSchemaError,
                          calibrate, get_profile, load_cached_profile,
                          profile_for_device, registered_profiles,
                          resolve_profile, store_cached_profile)
from repro.serving import ProgramCache

jax.config.update("jax_platform_name", "cpu")


# ------------------------------------------------------ JSON round-trip ----
def test_profile_json_round_trip(tmp_path):
    path = str(tmp_path / "v4.json")
    TPU_V4.save(path)
    loaded = DeviceProfile.load(path)
    assert loaded == TPU_V4
    assert loaded.identity() == TPU_V4.identity()


def test_profile_rejects_unknown_schema_version(tmp_path):
    doc = TPU_V5E.to_json_dict()
    doc["schema_version"] = PROFILE_SCHEMA_VERSION + 1
    path = tmp_path / "future.json"
    path.write_text(json.dumps(doc))
    with pytest.raises(ProfileSchemaError, match="schema_version"):
        DeviceProfile.load(str(path))


def test_profile_rejects_missing_fields_and_bad_json(tmp_path):
    doc = TPU_V5E.to_json_dict()
    del doc["hbm_bandwidth"]
    path = tmp_path / "partial.json"
    path.write_text(json.dumps(doc))
    with pytest.raises(ProfileSchemaError, match="hbm_bandwidth"):
        DeviceProfile.load(str(path))
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    with pytest.raises(ProfileSchemaError, match="JSON"):
        DeviceProfile.load(str(bad))


def test_profile_rejects_tampered_identity(tmp_path):
    doc = TPU_V5E.to_json_dict()
    doc["hbm_bandwidth"] = doc["hbm_bandwidth"] * 2  # numbers edited...
    path = tmp_path / "tampered.json"
    path.write_text(json.dumps(doc))
    with pytest.raises(ProfileSchemaError, match="identity"):
        DeviceProfile.load(str(path))


def test_profile_validates_fields():
    with pytest.raises(ValueError):
        dataclasses.replace(TPU_V5E, hbm_bandwidth=0.0)
    with pytest.raises(ValueError):
        dataclasses.replace(TPU_V5E, vmem_budget=-1)


# ------------------------------------------------------ registry -----------
def test_registry_has_three_builtin_targets():
    names = {p.name for p in registered_profiles()}
    assert {"tpu_v5e", "tpu_v4", "cpu_interpret"} <= names
    assert get_profile("tpu_v5e") is TPU_V5E
    with pytest.raises(KeyError, match="unknown device profile"):
        get_profile("snapdragon_801")            # paper SoC, not a TPU


def test_profile_identities_distinct():
    ids = [p.identity() for p in registered_profiles()]
    assert len(set(ids)) == len(ids)


# ------------------------------------------------------ calibration --------
class StubClock:
    """Deterministic clock: every (start, stop) pair spans exactly tick."""

    def __init__(self, tick: float = 1e-3):
        self.now, self.tick = 0.0, tick

    def __call__(self) -> float:
        self.now += self.tick
        return self.now


SMALL = dict(sizes=(32,), stream_sizes=(1024,), reps=2)


def test_calibration_deterministic_under_stubbed_clock():
    a = calibrate(CPU_INTERPRET, clock=StubClock(), **SMALL)
    b = calibrate(CPU_INTERPRET, clock=StubClock(), **SMALL)
    assert a == b
    assert a.identity() == b.identity()
    assert a.source == "calibrated"
    # rates are exactly work/tick for the stubbed 1ms best-of window
    assert a.peak_flops_bf16 == pytest.approx(2.0 * 32 ** 3 / 1e-3)
    assert a.hbm_bandwidth == pytest.approx(2 * 1024 * 4 / 1e-3)


def test_calibration_preserves_base_structure_fields():
    cal = calibrate(CPU_INTERPRET, clock=StubClock(), **SMALL)
    assert cal.vmem_budget == CPU_INTERPRET.vmem_budget
    assert cal.lane_width == CPU_INTERPRET.lane_width
    assert cal.supports_pallas == CPU_INTERPRET.supports_pallas


def test_calibration_measures_int8_peak():
    """int8 peak comes from its own int8 x int8 -> int32 sweep, not the base
    profile's datasheet ratio: under the stubbed 1ms window the measured
    rate is exactly work/tick, same as bf16's."""
    cal = calibrate(CPU_INTERPRET, clock=StubClock(), **SMALL)
    assert cal.peak_flops_int8 == pytest.approx(2.0 * 32 ** 3 / 1e-3)
    again = calibrate(CPU_INTERPRET, clock=StubClock(), **SMALL)
    assert again.peak_flops_int8 == cal.peak_flops_int8  # deterministic


def test_measure_matmul_flops_int8_dtype_runs():
    """The int8 sweep path (randint data, int32 accumulator) measures a
    positive rate under a stubbed clock."""
    from repro.device.calibrate import measure_matmul_flops
    rate = measure_matmul_flops(jnp.int8, sizes=(32,), reps=2,
                                clock=StubClock())
    assert rate == pytest.approx(2.0 * 32 ** 3 / 1e-3)


# ------------------------------------------------------ profile cache ------
def test_profile_cache_miss_then_hit(tmp_path):
    cache_dir = str(tmp_path / "profiles")
    assert load_cached_profile(cache_dir) is None            # cold miss
    cal = calibrate(CPU_INTERPRET, clock=StubClock(), **SMALL)
    store_cached_profile(cal, cache_dir)
    hit = load_cached_profile(cache_dir)
    assert hit == cal                                        # warm hit


def test_profile_cache_corrupt_entry_is_a_miss(tmp_path):
    cache_dir = tmp_path / "profiles"
    cal = calibrate(CPU_INTERPRET, clock=StubClock(), **SMALL)
    path = store_cached_profile(cal, str(cache_dir))
    with open(path, "w") as f:
        f.write("{broken")
    assert load_cached_profile(str(cache_dir)) is None


def test_resolve_profile_prefers_cached_calibration(tmp_path):
    cache_dir = str(tmp_path / "profiles")
    cal = calibrate(CPU_INTERPRET, clock=StubClock(), **SMALL)
    store_cached_profile(cal, cache_dir)
    assert resolve_profile("auto", cache_dir=cache_dir) == cal


def test_resolve_profile_deterministic_fallback_off_tpu(tmp_path):
    """CPU CI: measurement unavailable -> the builtin fallback, every time."""
    cache_dir = str(tmp_path / "empty")
    assert jax.default_backend() != "tpu"
    got = resolve_profile("auto", cache_dir=cache_dir)
    assert got is CPU_INTERPRET
    assert resolve_profile(None, cache_dir=cache_dir) is CPU_INTERPRET
    assert load_cached_profile(cache_dir) is None   # fallback never cached


def test_resolve_profile_passthrough_and_names():
    assert resolve_profile(TPU_V4) is TPU_V4
    assert resolve_profile("tpu_v4") is TPU_V4


# ------------------------------------------------ device kind -> profile ----
def _device(platform, kind):
    return SimpleNamespace(platform=platform, device_kind=kind)


@pytest.mark.parametrize("kind,want", [("TPU v5 lite", TPU_V5E),
                                       ("TPU v4", TPU_V4)])
def test_profile_for_device_by_tpu_kind(kind, want):
    assert TPU_PROFILES_BY_KIND[kind] is want
    assert profile_for_device(_device("tpu", kind)) is want


def test_profile_for_device_rejects_unknown_tpu_kind():
    with pytest.raises(KeyError, match="TPU v7x"):
        profile_for_device(_device("tpu", "TPU v7x"))


def test_profile_for_device_off_tpu_is_the_default():
    assert profile_for_device(_device("cpu", "cpu")) is DEFAULT_PROFILE
    assert profile_for_device() is DEFAULT_PROFILE      # this CPU host


def test_synthesize_without_a_target_plans_for_this_device():
    net = NetworkDescription("tiny", (3, 8, 8))
    net.conv("c", 4, 3, stride=1, padding="SAME", inputs=("input",))
    prog = synthesize(net, init_network_params(net, jax.random.PRNGKey(0)),
                      forced_mode=ComputeMode.RELAXED)
    assert prog.plan.profile is profile_for_device()


# ------------------------------------------------------ planner routing ----
def _wide_conv_net():
    # 64x64 keeps one grid step of the kernel (double-buffered blocks,
    # accumulator, temporaries) inside the v5e budget; 128x128 does not.
    net = NetworkDescription("wide", (128, 64, 64))
    net.conv("cwide", 128, 3, stride=1, padding="SAME", inputs=("input",))
    return net


def test_vmem_budget_routes_same_conv_differently():
    """Two profiles that differ only in VMEM budget must route the same
    compute-bound conv to different implementations (rule 1 vs rule 3)."""
    tiny_vmem = dataclasses.replace(TPU_V5E, name="tiny_vmem",
                                    vmem_budget=1024 * 1024)
    net = _wide_conv_net()
    modes = {"cwide": ComputeMode.RELAXED}

    roomy = plan_network(net, modes=modes, config=PlannerConfig(
        profile=TPU_V5E, allow_pallas=True)).for_layer("cwide")
    cramped = plan_network(net, modes=modes, config=PlannerConfig(
        profile=tiny_vmem, allow_pallas=True)).for_layer("cwide")

    assert roomy.impl == IMPL_PALLAS
    assert cramped.impl == IMPL_XLA
    assert cramped.reason.startswith("rule1"), cramped.reason


def test_ridge_moves_the_compute_bound_frontier():
    """A hypothetical high-bandwidth device lowers the ridge, flipping a
    memory-bound-on-v5e conv to compute-bound (same conv, same modes)."""
    fat_pipe = dataclasses.replace(TPU_V5E, name="fat_pipe",
                                   hbm_bandwidth=TPU_V5E.hbm_bandwidth * 10)
    net = NetworkDescription("mid", (32, 64, 64))
    net.conv("c", 32, 3, stride=1, padding="SAME", inputs=("input",))
    modes = {"c": ComputeMode.RELAXED}

    on_v5e = plan_network(net, modes=modes, config=PlannerConfig(
        profile=TPU_V5E, allow_pallas=True)).for_layer("c")
    on_fat = plan_network(net, modes=modes, config=PlannerConfig(
        profile=fat_pipe, allow_pallas=True)).for_layer("c")

    assert on_v5e.impl == IMPL_XLA and "memory-bound" in on_v5e.reason
    assert on_fat.impl == IMPL_PALLAS


def test_interpret_only_profile_never_routes_to_pallas():
    net = _wide_conv_net()
    plan = plan_network(net, modes={"cwide": ComputeMode.RELAXED},
                        config=PlannerConfig(profile=CPU_INTERPRET))
    assert plan.for_layer("cwide").impl == IMPL_XLA


# ------------------------------------------------- device-keyed identity ---
def test_plan_fingerprint_covers_device_profile():
    net = _wide_conv_net()
    fp5 = plan_network(net, config=PlannerConfig(profile=TPU_V5E)).fingerprint()
    fp4 = plan_network(net, config=PlannerConfig(profile=TPU_V4)).fingerprint()
    assert fp5 != fp4


def test_program_cache_keeps_per_device_entries():
    """Acceptance: synthesizing the same network under two profiles yields
    two distinct ProgramCache entries — a plan synthesized for one device
    is never served for another."""
    net = alexnet(scale=0.1, num_classes=10, input_hw=67)
    params = init_network_params(net, jax.random.PRNGKey(0))
    cache = ProgramCache()
    programs = {}
    for profile in (TPU_V5E, TPU_V4):
        prog = synthesize(net, params, device=profile,
                          forced_mode=ComputeMode.RELAXED)
        assert prog.plan.profile is profile
        programs[profile.name] = prog
        cache.admit(prog)
    fps = {name: p.fingerprint() for name, p in programs.items()}
    assert fps["tpu_v5e"] != fps["tpu_v4"]
    assert cache.programs == 2
    for p in programs.values():
        cache.get_or_build(p, 1)
    assert len(cache) == 2                      # one compile per device
    assert cache.stats.stage_d_compiles == 2


def test_synthesize_device_name_and_mismatch_guard():
    net = alexnet(scale=0.1, num_classes=10, input_hw=67)
    params = init_network_params(net, jax.random.PRNGKey(0))
    prog = synthesize(net, params, device="tpu_v4",
                      forced_mode=ComputeMode.RELAXED)
    assert prog.plan.profile is TPU_V4
    assert "tpu_v4" in prog.report()
    v5e_plan = plan_network(net, config=PlannerConfig(profile=TPU_V5E))
    with pytest.raises(ValueError, match="drawn for device"):
        synthesize(net, params, device="tpu_v4", plan=v5e_plan,
                   forced_mode=ComputeMode.RELAXED)


def test_synthesize_rejects_plan_config_device_mismatch():
    """plan= and planner_config= naming different devices must fail loudly
    instead of silently re-planning the supplied plan for the config's
    device (a fingerprint-visible device flip)."""
    net = alexnet(scale=0.1, num_classes=10, input_hw=67)
    params = init_network_params(net, jax.random.PRNGKey(0))
    v4_plan = plan_network(net, config=PlannerConfig(profile=TPU_V4))
    with pytest.raises(ValueError, match="drawn for device"):
        synthesize(net, params, plan=v4_plan,
                   planner_config=PlannerConfig(profile=TPU_V5E),
                   forced_mode=ComputeMode.RELAXED)


def test_runtime_envelope_honors_plans_device_budget(monkeypatch):
    """The dispatch-time VMEM guard must use the budget the plan was drawn
    under, not the default profile's: a block over the plan's (smaller)
    budget takes the XLA fallback even though it fits the v5e default."""
    from repro.kernels.conv_mapmajor import ops as conv_ops
    from repro.kernels.conv_mapmajor.ops import conv2d_mapmajor

    def boom(*a, **k):
        raise AssertionError("Pallas path entered above the plan's budget")
    monkeypatch.setattr(conv_ops, "_conv2d_mapmajor_pallas", boom)

    x = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 32, 32))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 4, 3, 3)) * 0.1
    # 34*34*8*2B ≈ 18 KB: inside the 24 MB default, over a 1 KB budget.
    out = conv2d_mapmajor(x, w, stride=1, padding="SAME",
                          mode=ComputeMode.RELAXED, u=8, vmem_budget=1024)
    assert out.shape == (1, 4, 32, 32)


def test_budget_only_plan_difference_never_aliases():
    """Two plans identical except a layer's vmem_budget compile different
    programs (the dispatch guard branches on the budget), so they must not
    share a fingerprint — while None and an explicit default budget, which
    dispatch identically, must."""
    from repro.core import IMPL_PALLAS as P, LayerPlan

    net = _wide_conv_net()
    base = plan_network(net, modes={"cwide": ComputeMode.RELAXED},
                        config=PlannerConfig(profile=TPU_V5E,
                                             allow_pallas=True))
    lp = base.for_layer("cwide")
    assert lp.impl == P
    squeezed = base.with_layer("cwide",
                               dataclasses.replace(lp, vmem_budget=1024))
    assert squeezed.fingerprint() != base.fingerprint()
    defaulted = base.with_layer(
        "cwide", dataclasses.replace(lp, vmem_budget=None))
    explicit = base.with_layer(
        "cwide", dataclasses.replace(lp, vmem_budget=TPU_V5E.vmem_budget))
    assert defaulted.fingerprint() == explicit.fingerprint()


def test_planned_layers_carry_their_devices_budget():
    tiny_vmem = dataclasses.replace(TPU_V5E, name="tiny_vmem",
                                    vmem_budget=1024 * 1024)
    net = _wide_conv_net()
    plan = plan_network(net, config=PlannerConfig(profile=tiny_vmem))
    assert plan.for_layer("cwide").vmem_budget == 1024 * 1024


def test_replan_keeps_supplied_plans_device():
    """A plan drawn for a non-default device must keep that device through
    the synthesizer's re-planning (no silent fall-back to v5e)."""
    net = alexnet(scale=0.1, num_classes=10, input_hw=67)
    params = init_network_params(net, jax.random.PRNGKey(0))
    plan = plan_network(net, config=PlannerConfig(profile=TPU_V4))
    prog = synthesize(net, params, plan=plan,
                      forced_mode=ComputeMode.RELAXED)
    assert prog.plan.profile is TPU_V4


# ------------------------------------------- single source of constants ----
def test_roofline_reads_the_default_profile():
    """Regression for the old sync-by-comment: the roofline benchmark's
    constants must be *reads* of the default DeviceProfile object
    (import-level agreement, no hand sync).  The planner-side aliases were
    retired in PR 7 (tests/test_deprecated_shims.py pins the removal) —
    the profile itself is the single source now."""
    import benchmarks.roofline as roofline

    assert roofline.PROFILE is TPU_V5E
    assert roofline.PEAK_FLOPS == TPU_V5E.peak_flops_bf16
    assert roofline.HBM_BW == TPU_V5E.hbm_bandwidth
    assert roofline.LINK_BW == TPU_V5E.link_bandwidth


def test_kernel_vmem_budget_and_lanes_come_from_device():
    from repro.core.layout import LANES
    from repro.device.profile import LANE_WIDTH
    from repro.kernels.conv_mapmajor import ops

    assert ops.VMEM_INPUT_BUDGET == TPU_V5E.vmem_budget
    assert LANES == LANE_WIDTH == TPU_V5E.lane_width
