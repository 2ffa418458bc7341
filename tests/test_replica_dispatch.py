"""Tests for the data-parallel serving tier (DESIGN.md §11).

Three acceptance criteria from the PR-7 issue are pinned here:

  * deterministic dispatch-policy behavior — least-loaded placement and
    round-robin + work-stealing are exactly predictable given queue
    depths, so the tests assert placements, not distributions;
  * bounded queues under overload — a threaded open-loop burst against a
    slow program must keep every per-replica queue at or below
    ``max_queue_depth``, shed the excess with a typed
    :class:`LoadShedError`, and still complete every *admitted* request
    with finite latency;
  * bitwise parity — a 2-replica tier returns the same outputs as a
    single replica (and as direct ``program.for_batch`` calls) for the
    same requests.

The policy/overload tests run against a duck-typed FakeProgram (no
synthesis, no XLA) so they are fast and fully deterministic; the parity
and device-mesh tests use real synthesized programs.
"""
import threading
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cnn import init_network_params, squeezenet
from repro.core import ComputeMode, synthesize
from repro.serving import (DISPATCH_POLICIES, LeastLoadedPolicy,
                           LoadShedError, ReplicaSet, ServingConfig,
                           WorkStealingPolicy, resolve_dispatch_policy,
                           warm_replicas)

jax.config.update("jax_platform_name", "cpu")


# ------------------------------------------------------------ fake program --
class _FakeBatch:
    """Stage-D stand-in: multiplies by 2, optionally slowly."""

    compile_seconds = 0.0

    def __init__(self, delay_s: float = 0.0):
        self.delay_s = delay_s

    def __call__(self, x):
        if self.delay_s:
            time.sleep(self.delay_s)
        return jnp.asarray(x) * 2.0


class FakeProgram:
    """Duck-typed SynthesizedProgram: everything the serving tier touches
    (net identity, fingerprint, input dtype, Stage-D factory, device name)
    with no synthesis and no XLA compile behind it."""

    def __init__(self, name="fakenet", fp="fake-fp", delay_s=0.0,
                 device="fake_dev"):
        self.net = SimpleNamespace(name=name, input_shape=(3,))
        self.plan = SimpleNamespace(profile=SimpleNamespace(name=device))
        self.input_dtype = jnp.float32
        self._fp = fp
        self._delay_s = delay_s

    def fingerprint(self):
        return self._fp

    def for_batch(self, batch, device=None):
        return _FakeBatch(self._delay_s)


def _fake_tier(*, replicas=2, dispatch="least_loaded", max_batch=2,
               max_queue_depth=0, delay_s=0.0, max_delay_s=60.0):
    config = ServingConfig(max_batch=max_batch, max_delay_s=max_delay_s,
                           replicas=replicas, dispatch=dispatch,
                           max_queue_depth=max_queue_depth)
    return ReplicaSet(FakeProgram(delay_s=delay_s), config=config)


def _img(v):
    return np.full(3, float(v), np.float32)


# ----------------------------------------------------------- policy units ---
def test_least_loaded_policy_is_deterministic():
    p = LeastLoadedPolicy()
    assert p.select([3, 1, 2], rr=0) == 1
    assert p.select([2, 2, 2], rr=5) == 0        # lowest index on ties
    assert p.select([0, 0], rr=99) == 0          # rr is ignored
    assert not p.steals


def test_work_stealing_policy_is_round_robin():
    p = WorkStealingPolicy()
    assert [p.select([9, 0, 0], rr=r) for r in range(5)] == [0, 1, 2, 0, 1]
    assert p.steals                               # depths are ignored


def test_resolve_dispatch_policy():
    assert isinstance(resolve_dispatch_policy("least_loaded"),
                      LeastLoadedPolicy)
    inst = WorkStealingPolicy()
    assert resolve_dispatch_policy(inst) is inst
    with pytest.raises(ValueError, match="unknown dispatch policy"):
        resolve_dispatch_policy("random")
    assert set(DISPATCH_POLICIES) == {"least_loaded", "work_stealing"}


def test_serving_config_validation():
    with pytest.raises(ValueError):
        ServingConfig(max_batch=6)                # FlushPolicy invariant
    with pytest.raises(ValueError):
        ServingConfig(replicas=0)
    with pytest.raises(ValueError):
        ServingConfig(cache_entries=0)
    with pytest.raises(ValueError):
        ServingConfig(dispatch="random")
    with pytest.raises(ValueError):
        ServingConfig(max_queue_depth=-1)
    cfg = ServingConfig(max_batch=4, replicas=3)
    assert cfg.with_replicas(1) == ServingConfig(max_batch=4, replicas=1)
    assert cfg.flush_policy().max_batch == 4


# ------------------------------------------------------- placement (fake) ---
def test_least_loaded_placement_balances_queues():
    tier = _fake_tier(replicas=2, dispatch="least_loaded")
    for i in range(5):
        tier.submit(_img(i))
    # (0,0)->r0, (1,0)->r1, (1,1)->r0, (2,1)->r1, (2,2)->r0
    assert [r.depth for r in tier.replicas] == [3, 2]
    assert [r.peak_depth for r in tier.replicas] == [3, 2]
    assert tier.stats()["submitted"] == 5 and tier.stats()["shed_requests"] == 0


def test_work_stealing_placement_is_round_robin():
    tier = _fake_tier(replicas=3, dispatch="work_stealing")
    for i in range(7):
        tier.submit(_img(i))
    assert [r.depth for r in tier.replicas] == [3, 2, 2]


def test_idle_replica_steals_overflow_from_deepest_peer():
    tier = _fake_tier(replicas=2, dispatch="work_stealing", max_batch=2)
    futs = [tier.submit(_img(i)) for i in range(8)]   # rr: r0 even, r1 odd
    assert [r.depth for r in tier.replicas] == [4, 4]

    # drain replica 1's own queue: two full buckets of 2
    assert tier.pump(replica=1, force=True) == 2
    assert tier.pump(replica=1, force=True) == 2
    assert [r.depth for r in tier.replicas] == [4, 0]

    # idle replica 1 now steals replica 0's overflow: depth 4 exceeds one
    # full bucket (max_batch=2) by 2, so exactly 2 come off the tail
    assert tier.pump(replica=1) == 2
    assert [r.depth for r in tier.replicas] == [2, 0]
    assert tier.replicas[1].stolen_requests == 2
    assert tier.stats()["stolen_requests"] == 2

    # depth 2 == one full bucket: nothing left to steal
    assert tier.pump(replica=1) == 0
    assert tier.drain() == 2
    # every request — owned or stolen — still gets its own row, bitwise
    for i, f in enumerate(futs):
        np.testing.assert_array_equal(f.result(timeout=5.0), _img(i) * 2.0)


def test_least_loaded_never_steals():
    tier = _fake_tier(replicas=2, dispatch="least_loaded", max_batch=2)
    for i in range(6):
        tier.submit(_img(i))
    tier.pump(replica=1, force=True)
    tier.pump(replica=1, force=True)
    assert [r.depth for r in tier.replicas] == [3, 0]
    assert tier.pump(replica=1) == 0              # idle but no stealing
    assert tier.stats()["stolen_requests"] == 0
    tier.drain()


# ------------------------------------------------- admission control (fake) --
def test_admission_bound_sheds_with_typed_error():
    tier = _fake_tier(replicas=2, dispatch="least_loaded", max_queue_depth=3)
    futs = [tier.submit(_img(i)) for i in range(6)]   # fills both to 3
    assert [r.depth for r in tier.replicas] == [3, 3]
    with pytest.raises(LoadShedError) as exc:
        tier.submit(_img(99))
    assert exc.value.depths == (3, 3) and exc.value.max_queue_depth == 3
    stats = tier.stats()
    assert stats["shed_requests"] == 1 and stats["submitted"] == 6
    assert stats["peak_depth"] == 3               # the bound held exactly
    tier.drain()
    assert all(f.done() for f in futs)            # admitted requests complete


def test_round_robin_falls_over_to_shallowest_before_shedding():
    tier = _fake_tier(replicas=2, dispatch="work_stealing", max_batch=2,
                      max_queue_depth=2)
    for i in range(4):
        tier.submit(_img(i))                      # rr fills both to the bound
    tier.pump(replica=0, force=True)              # r0 drains one bucket
    assert [r.depth for r in tier.replicas] == [0, 2]
    tier._rr = 1                                  # force rr to pick full r1
    tier.submit(_img(5))
    assert [r.depth for r in tier.replicas] == [1, 2]   # fell over, no shed
    assert tier.stats()["shed_requests"] == 0
    tier.drain()


def test_unbounded_queue_never_sheds():
    tier = _fake_tier(replicas=1, max_queue_depth=0)
    for i in range(100):
        tier.submit(_img(i))
    assert tier.replicas[0].depth == 100 and tier.shed_requests == 0
    tier.drain()


# ------------------------------------------------- threaded overload (fake) --
def test_threaded_overload_bounds_queues_and_sheds():
    """Open-loop burst against a slow tier: queues stay at or below the
    admission bound, the excess is shed (and counted), and every admitted
    request completes with finite latency — overload degrades by shedding,
    not by unbounded queueing."""
    bound = 4
    tier = _fake_tier(replicas=2, dispatch="least_loaded", max_batch=4,
                      max_queue_depth=bound, delay_s=0.02, max_delay_s=0.001)
    n, shed = 300, 0
    futs = []
    with tier:
        for i in range(n):                        # back-to-back arrivals
            try:
                futs.append(tier.submit(_img(i)))
            except LoadShedError:
                shed += 1
        for f in futs:
            f.result(timeout=60.0)

    stats = tier.stats()
    assert shed > 0 and stats["shed_requests"] == shed
    assert stats["submitted"] == len(futs) == n - shed
    assert stats["peak_depth"] <= bound           # the bound held throughout
    for r in stats["replicas"]:
        assert r["peak_depth"] <= bound
    assert sum(r["completed"] for r in stats["replicas"]) == len(futs)
    for f in futs:
        assert f.latency_s is not None and np.isfinite(f.latency_s)


def test_threaded_submitters_race_admission_without_overshoot():
    """Concurrent submitters cannot overshoot the bound: admission holds
    one lock across observe-depths + enqueue."""
    bound = 3
    tier = _fake_tier(replicas=2, dispatch="least_loaded", max_batch=4,
                      max_queue_depth=bound)      # no dispatch threads at all
    shed_counts = [0] * 4

    def client(t):
        for i in range(50):
            try:
                tier.submit(_img(t * 50 + i))
            except LoadShedError:
                shed_counts[t] += 1

    threads = [threading.Thread(target=client, args=(t,)) for t in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=30.0)

    # nothing dispatched, so exactly 2 * bound requests can be in queues
    assert [r.depth for r in tier.replicas] == [bound, bound]
    assert sum(shed_counts) == 200 - 2 * bound == tier.shed_requests
    assert tier.stats()["peak_depth"] == bound
    tier.drain()


# --------------------------------------------------- tier construction ------
def test_replica_set_rejects_mismatched_shapes_and_counts():
    with pytest.raises(ValueError, match="at least one"):
        ReplicaSet([])
    with pytest.raises(ValueError, match="replicas=3"):
        ReplicaSet([FakeProgram(), FakeProgram()],
                   config=ServingConfig(replicas=3))
    with pytest.raises(ValueError, match="same network"):
        ReplicaSet([FakeProgram(name="a"), FakeProgram(name="b")])
    # a bare sequence infers its width
    tier = ReplicaSet([FakeProgram(), FakeProgram(), FakeProgram()])
    assert tier.config.replicas == len(tier.replicas) == 3


def test_warm_replicas_shares_compiles_through_the_cache():
    tier = _fake_tier(replicas=2, max_batch=4)
    seconds = warm_replicas(tier)
    assert len(seconds) == 2
    assert [r.warm_seconds for r in tier.replicas] == seconds
    # identical fingerprints: replica 0 pays the 3 bucket compiles
    # (1, 2, 4), replica 1 lands 3 hits
    assert tier.cache.stats.stage_d_compiles == 3
    assert tier.cache.stats.hits == 3
    assert all("warm_seconds" in r for r in tier.stats()["replicas"])


# ------------------------------------------------- parity (real programs) ---
@pytest.fixture(scope="module")
def small_net():
    net = squeezenet(scale=0.08, num_classes=10, input_hw=64)
    params = init_network_params(net, jax.random.PRNGKey(0))
    return net, params


@pytest.fixture(scope="module")
def program(small_net):
    net, params = small_net
    return synthesize(net, params, forced_mode=ComputeMode.RELAXED)


def _serve_through(tier, imgs):
    futs = [tier.submit(imgs[i]) for i in range(len(imgs))]
    tier.drain()
    return np.stack([f.result(timeout=30.0) for f in futs])


def test_two_replica_tier_is_bitwise_identical_to_one(program):
    """The ISSUE parity criterion: the same requests through a 2-replica
    tier, a 1-replica tier, and direct program calls agree bitwise."""
    n = 12
    rng = np.random.default_rng(21)
    imgs = rng.standard_normal(
        (n, *program.net.input_shape)).astype(np.float32)
    direct = np.asarray(program.for_batch(n)(jnp.asarray(imgs)))

    config = ServingConfig(max_batch=8, max_delay_s=60.0)
    one = _serve_through(
        ReplicaSet(program, config=config.with_replicas(1)), imgs)
    two = _serve_through(
        ReplicaSet(program, config=config.with_replicas(2)), imgs)

    np.testing.assert_array_equal(one, direct)
    np.testing.assert_array_equal(two, direct)


def test_identical_replicas_share_stage_d_compiles(program):
    config = ServingConfig(max_batch=4, max_delay_s=60.0, replicas=2)
    tier = ReplicaSet(program, config=config)
    warm_replicas(tier)
    # one program fingerprint: buckets 1/2/4 compile once, replica 1 hits
    assert tier.cache.stats.stage_d_compiles == 3
    assert tier.cache.stats.hits == 3
    assert tier.replicas[0].warm_seconds > tier.replicas[1].warm_seconds


def test_device_mesh_replicas_never_alias_in_the_shared_cache(small_net):
    """Device-distinct replicas (PR 4 fingerprints cover the profile
    identity) each get their own Stage-D entries in the shared cache."""
    net, params = small_net
    tier = ReplicaSet.for_devices(
        net, params, ["tpu_v5e", "tpu_v4"],
        config=ServingConfig(max_batch=2, max_delay_s=60.0, replicas=2),
        forced_mode=ComputeMode.RELAXED)
    assert [r.profile for r in tier.replicas] == ["tpu_v5e", "tpu_v4"]
    fps = {r.program.fingerprint() for r in tier.replicas}
    assert len(fps) == 2                          # profiles keep them apart

    warm_replicas(tier)
    # no aliasing: every bucket compiles once *per device* (2 buckets x 2)
    assert tier.cache.stats.stage_d_compiles == 4
    assert tier.cache.stats.hits == 0

    imgs = np.random.default_rng(3).standard_normal(
        (4, *net.input_shape)).astype(np.float32)
    outs = _serve_through(tier, imgs)
    assert outs.shape == (4, 10)
    assert np.isfinite(outs).all()


# ----------------------------------------- one replica per device (child) ---
_PLACEMENT_SCRIPT = r"""
import json
import jax
import numpy as np
from repro.cnn import init_network_params, squeezenet
from repro.core import ComputeMode, synthesize
from repro.serving import ReplicaSet, ServingConfig

net = squeezenet(scale=0.08, num_classes=10, input_hw=64)
program = synthesize(net, init_network_params(net, jax.random.PRNGKey(0)),
                     forced_mode=ComputeMode.RELAXED)
tier = ReplicaSet(program, config=ServingConfig(max_batch=2,
                                                max_delay_s=60.0,
                                                replicas=4))
imgs = np.random.default_rng(0).standard_normal(
    (8, *net.input_shape)).astype(np.float32)
futs = [tier.submit(x) for x in imgs]
tier.drain()
four = np.stack([f.result(timeout=30.0) for f in futs])
one = np.asarray(program.for_batch(8)(imgs))
print("RESULT " + json.dumps({
    "devices": [str(d) for d in jax.devices()],
    "replica_devices": [str(r.device) for r in tier.replicas],
    "output_devices": [r["output_devices"]
                       for r in tier.stats()["replicas"]],
    "compiles": tier.cache.stats.stage_d_compiles,
    "equal": bool(np.array_equal(four, one)),
}))
"""


def test_replica_i_runs_on_device_i():
    """On a host with four devices, replica i compiles for, and serves
    from, ``jax.devices()[i]`` — a CPU child with four host devices stands
    in for a 2x2 TPU host."""
    import json
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    proc = subprocess.run([sys.executable, "-c", _PLACEMENT_SCRIPT],
                          capture_output=True, text=True, timeout=300,
                          env=env)
    assert proc.returncode == 0, proc.stderr
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT ")]
    got = json.loads(line[-1][len("RESULT "):])
    assert len(got["devices"]) == 4
    assert got["replica_devices"] == got["devices"]
    # least-loaded admission deals two requests to each replica: one
    # full bucket each, computed on that replica's own device only
    assert got["output_devices"] == [{d: 2} for d in got["devices"]]
    assert got["compiles"] == 4           # one batch-2 executable per device
    assert got["equal"]
