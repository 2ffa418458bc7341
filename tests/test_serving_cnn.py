"""Tests for the CNN serving subsystem: batcher, ProgramCache, server.

The load-bearing acceptance test is the round trip: N single requests
through the dynamic batcher must produce bitwise-identical outputs to
direct SynthesizedProgram calls, with at most ceil(log2(N)) + 1 Stage-D
compiles recorded by the ProgramCache.
"""
import math
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cnn import init_network_params, squeezenet
from repro.core import (ComputeMode, ExecutionPlan, LayerPlan, Parallelism,
                        plan_network, synthesize)
from repro.serving import (DynamicBatcher, FlushPolicy, ProgramCache,
                           ServingConfig, SynthesisServer, pow2_bucket)

jax.config.update("jax_platform_name", "cpu")


# ---------------------------------------------------------------- batcher ---
def test_pow2_bucket():
    assert [pow2_bucket(n) for n in (1, 2, 3, 4, 5, 8, 9)] == \
        [1, 2, 4, 4, 8, 8, 16]
    with pytest.raises(ValueError):
        pow2_bucket(0)


def test_flush_policy_validation():
    with pytest.raises(ValueError):
        FlushPolicy(max_batch=6)          # not a power of two
    with pytest.raises(ValueError):
        FlushPolicy(max_batch=4, flush_depth=5)
    assert FlushPolicy(max_batch=4).depth_trigger == 4
    assert FlushPolicy(max_batch=8, flush_depth=3).depth_trigger == 3


def test_batcher_depth_trigger_and_split():
    b = DynamicBatcher(config=ServingConfig(max_batch=4, max_delay_s=60.0))
    for i in range(6):
        b.submit(i)
    # depth 6 >= trigger 4: one full bucket comes out...
    bucket = b.take()
    assert bucket is not None and bucket.batch == 4 and bucket.padding == 0
    assert [r.image for r in bucket.requests] == [0, 1, 2, 3]  # FIFO
    # ...the 2 leftovers are below the trigger and far from their deadline
    assert b.take() is None
    assert b.depth == 2
    # force drains them into the pow-2 bucket above their count
    tail = b.take(force=True)
    assert tail.batch == 2 and tail.padding == 0
    assert b.depth == 0 and b.take(force=True) is None


def test_batcher_deadline_trigger():
    b = DynamicBatcher(config=ServingConfig(max_batch=8, max_delay_s=0.01))
    b.submit("x")
    now = time.perf_counter()
    assert not b.ready(now)                      # too fresh
    assert b.take(now) is None
    late = now + 0.02
    assert b.ready(late)                         # oldest aged out
    bucket = b.take(late)
    assert bucket.batch == 1 and len(bucket.requests) == 1


def test_batcher_pads_to_pow2():
    b = DynamicBatcher(config=ServingConfig(max_batch=8, flush_depth=3,
                                            max_delay_s=60.0))
    for i in range(3):
        b.submit(i)
    bucket = b.take()
    assert bucket.batch == 4 and bucket.padding == 1


# ------------------------------------------------------------ fingerprint ---
@pytest.fixture(scope="module")
def small_net():
    net = squeezenet(scale=0.08, num_classes=10, input_hw=64)
    params = init_network_params(net, jax.random.PRNGKey(0))
    return net, params


def test_plan_fingerprint_stable_and_discriminating(small_net):
    net, _ = small_net
    p1 = plan_network(net)
    p2 = plan_network(net)
    assert p1.fingerprint() == p2.fingerprint()          # deterministic
    # reasons/origin are cosmetic: a uniform plan with identical dispatch
    # must share the fingerprint with an equivalent planner plan
    relabeled = ExecutionPlan(
        p1.net_name,
        {n: LayerPlan(impl=lp.impl, parallelism=lp.parallelism, mode=lp.mode,
                      u=lp.u, reason="hand-written")
         for n, lp in p1.layers.items()},
        origin="uniform")
    assert relabeled.fingerprint() == p1.fingerprint()
    # any dispatch change moves it
    first = net.param_layers[0].name
    changed = p1.with_modes({first: ComputeMode.IMPRECISE})
    assert changed.fingerprint() != p1.fingerprint()
    other_par = p1.with_layer(first, LayerPlan(parallelism=Parallelism.FLP))
    assert other_par.fingerprint() != p1.fingerprint()


# ----------------------------------------------------------- ProgramCache ---
@pytest.fixture(scope="module")
def program(small_net):
    net, params = small_net
    return synthesize(net, params, forced_mode=ComputeMode.RELAXED)


def test_program_cache_hits_and_compiles(program):
    cache = ProgramCache()
    cache.admit(program)
    base = program.stage_d_compiles
    a = cache.get_or_build(program, 2)
    b = cache.get_or_build(program, 2)
    assert a is b                                # second call is a hit
    assert cache.stats.hits == 1 and cache.stats.misses == 1
    assert cache.stats.stage_d_compiles == 1
    assert program.stage_d_compiles == base + 1  # program-side counter agrees
    c = cache.get_or_build(program, 4)
    assert c is not a and cache.stats.stage_d_compiles == 2


def test_program_cache_distinguishes_weights():
    """Same network, same plan, different weights: no executable sharing —
    compiled programs close over their weights.

    The network is wide enough to carry a signal (the 0.08-width net
    squeezes to single channels whose ReLUs can die for every input, which
    makes every class score equal), and the input is seeded noise, so the
    comparison below is between outputs that demonstrably vary."""
    net = squeezenet(scale=0.25, num_classes=10, input_hw=64)
    programs = [synthesize(net,
                           init_network_params(net, jax.random.PRNGKey(s)),
                           forced_mode=ComputeMode.RELAXED) for s in (0, 99)]
    assert programs[0].plan.fingerprint() == programs[1].plan.fingerprint()
    assert programs[0].fingerprint() != programs[1].fingerprint()

    cache = ProgramCache()
    for p in programs:
        cache.admit(p)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, *net.input_shape))
    out1, out2 = (np.asarray(cache.get_or_build(p, 1)(x)) for p in programs)
    assert cache.stats.stage_d_compiles == 2 and cache.stats.hits == 0
    for out in (out1, out2):
        assert np.ptp(out, axis=-1).min() > 1e-3        # logits vary
    assert not np.array_equal(out1, out2)


def test_program_cache_requires_admit(program):
    with pytest.raises(KeyError):
        ProgramCache().get_or_build(program, 1)


def test_program_cache_lru_eviction(program):
    cache = ProgramCache(config=ServingConfig(cache_entries=2))
    cache.admit(program)
    a1 = cache.get_or_build(program, 1)
    cache.get_or_build(program, 2)
    cache.get_or_build(program, 4)                        # evicts bucket 1
    assert cache.stats.evictions == 1 and len(cache) == 2
    assert cache.get_or_build(program, 1) is not a1       # recompiled
    assert cache.stats.stage_d_compiles == 4


def test_batch_program_rejects_wrong_shape(program):
    bp = program.for_batch(2)
    good = jnp.zeros((2, *program.net.input_shape))
    assert bp(good).shape[0] == 2
    with pytest.raises(ValueError):
        bp(jnp.zeros((3, *program.net.input_shape)))


# ------------------------------------------------------------- round trip ---
def test_server_round_trip_bitwise_and_compile_bound(program):
    """N single requests == direct program calls, with a logarithmic
    Stage-D compile bound (the ISSUE acceptance criterion)."""
    n = 11
    rng = np.random.default_rng(42)
    imgs = rng.standard_normal(
        (n, *program.net.input_shape)).astype(np.float32)
    direct = np.asarray(program.for_batch(n)(jnp.asarray(imgs)))

    server = SynthesisServer(
        program, config=ServingConfig(max_batch=8, max_delay_s=60.0))
    futures = [server.submit(imgs[i]) for i in range(n)]
    assert server.drain() == n
    outs = np.stack([f.result(timeout=5.0) for f in futures])

    np.testing.assert_array_equal(outs, direct)  # bitwise
    assert server.cache.stats.stage_d_compiles <= math.ceil(math.log2(n)) + 1
    assert server.stats.completed == n and server.stats.failed == 0
    # 11 -> one full 8-bucket + 3 padded into a 4-bucket
    assert server.stats.bucket_counts == {8: 1, 4: 1}
    assert server.stats.padded_slots == 1


def test_server_threaded_round_trip(program):
    n = 10
    rng = np.random.default_rng(7)
    imgs = rng.standard_normal(
        (n, *program.net.input_shape)).astype(np.float32)
    direct = np.asarray(program.for_batch(n)(jnp.asarray(imgs)))

    with SynthesisServer(program,
                         config=ServingConfig(max_batch=4,
                                              max_delay_s=0.005)) as server:
        futures = [server.submit(imgs[i]) for i in range(n)]
        outs = np.stack([f.result(timeout=60.0) for f in futures])
    np.testing.assert_array_equal(outs, direct)
    assert server.stats.completed == n
    assert all(f.latency_s is not None and f.latency_s >= 0 for f in futures)


def test_server_infer_one_and_shape_check(program):
    server = SynthesisServer(program)
    img = np.zeros(program.net.input_shape, np.float32)
    out = server.infer_one(img)
    assert out.shape == (10,)
    with pytest.raises(ValueError):              # batched input rejected
        server.submit(np.zeros((2, *program.net.input_shape), np.float32))


def test_servers_share_cache_across_replicas(program):
    cache = ProgramCache()
    s1 = SynthesisServer(program, cache=cache)
    s2 = SynthesisServer(program, cache=cache)
    img = np.zeros(program.net.input_shape, np.float32)
    s1.infer_one(img)
    s2.infer_one(img)                            # replica reuses the compile
    assert cache.stats.stage_d_compiles == 1 and cache.stats.hits == 1


def test_server_concurrent_submitters(program):
    """Requests from several client threads all complete and stay intact."""
    n_threads, per_thread = 4, 6
    rng = np.random.default_rng(3)
    imgs = rng.standard_normal(
        (n_threads, per_thread, *program.net.input_shape)).astype(np.float32)
    direct = np.asarray(program.for_batch(n_threads * per_thread)(
        jnp.asarray(imgs.reshape(-1, *program.net.input_shape))))

    results = {}
    with SynthesisServer(program,
                         config=ServingConfig(max_batch=8,
                                              max_delay_s=0.002)) as server:
        def client(t):
            futs = [server.submit(imgs[t, i]) for i in range(per_thread)]
            results[t] = np.stack([f.result(timeout=60.0) for f in futs])

        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120.0)

    assert sorted(results) == list(range(n_threads))
    for t in range(n_threads):
        np.testing.assert_array_equal(
            results[t], direct[t * per_thread:(t + 1) * per_thread])
