"""Concurrency contract of serving.ProgramCache.get_or_build.

Two properties, pinned separately because they pull in opposite
directions:

1. *Exactly-once per key*: N threads racing on the same (network, bucket)
   trigger exactly one Stage-D compile; every caller gets the same
   BatchProgram object and the CacheStats ledger stays consistent
   (hits + misses == calls, compiles == distinct buckets built).
2. *Concurrency across keys*: threads building *different* buckets must
   not serialize on each other — compiles run under per-key in-flight
   locks, not the cache-wide lock (the replica warm-up perf fix), proven
   here by making the builds rendezvous inside ``for_batch``.
"""
import threading

import jax
import numpy as np
import pytest

from repro.cnn import init_network_params
from repro.core import ComputeMode, NetworkDescription, synthesize
from repro.serving import ProgramCache

jax.config.update("jax_platform_name", "cpu")


@pytest.fixture(scope="module")
def program():
    net = NetworkDescription("cache_tiny", (3, 8, 8))
    net.conv("c1", 4, 3, padding="SAME", inputs=("input",))
    net.relu("r1")
    net.flatten("f")
    net.dense("d1", 4)
    params = init_network_params(net, jax.random.PRNGKey(0))
    return synthesize(net, params, forced_mode=ComputeMode.RELAXED)


def _hammer(cache, program, buckets, n_threads):
    """Race n_threads through get_or_build; returns results per thread."""
    barrier = threading.Barrier(n_threads)
    results, errors = [None] * n_threads, []

    def worker(i):
        try:
            barrier.wait(timeout=30.0)
            results[i] = cache.get_or_build(program, buckets[i])
        except Exception as e:                    # surface, don't deadlock
            errors.append((i, e))

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120.0)
    assert not errors, errors
    assert not any(t.is_alive() for t in threads)
    return results


def test_same_bucket_compiles_exactly_once(program):
    n = 8
    cache = ProgramCache()
    cache.admit(program)
    results = _hammer(cache, program, [4] * n, n)

    first = results[0]
    assert all(r is first for r in results)       # one object, shared
    assert cache.stats.stage_d_compiles == 1      # exactly one build
    assert cache.stats.misses == 1
    assert cache.stats.hits == n - 1
    assert cache.stats.requests == n
    assert len(cache) == 1
    assert program.stage_d_compiles == 1          # program-side ledger agrees


def test_mixed_buckets_compile_once_each(program):
    buckets = [1, 2, 4] * 4                       # 12 calls over 3 buckets
    cache = ProgramCache()
    cache.admit(program)
    results = _hammer(cache, program, buckets, len(buckets))

    by_bucket = {}
    for b, r in zip(buckets, results):
        by_bucket.setdefault(b, set()).add(id(r))
        assert r.batch == b
    assert all(len(ids) == 1 for ids in by_bucket.values())
    assert cache.stats.stage_d_compiles == 3
    assert cache.stats.misses == 3
    assert cache.stats.hits == len(buckets) - 3
    assert len(cache) == 3

    # results stay functionally correct after the race
    x = np.zeros((4, *program.net.input_shape), np.float32)
    out = cache.get_or_build(program, 4)(x)
    assert out.shape == (4, 4)


class _RendezvousProgram:
    """Program stub whose ``for_batch`` blocks until ``expected`` builders
    are inside it simultaneously.  Under the per-key-lock design, distinct
    buckets build concurrently and the barrier releases; under a
    compile-under-the-cache-lock design the builders would serialize and
    the barrier would time out — making this a structural regression test,
    not a timing-dependent one."""

    class _Net:
        name = "rendezvous"
        input_shape = (3, 8, 8)

    def __init__(self, expected: int):
        self.net = self._Net()
        self.barrier = threading.Barrier(expected)
        self.concurrent_builds = 0
        self.stage_d_compiles = 0
        self._lock = threading.Lock()

    def fingerprint(self) -> str:
        return "rendezvous-fp"

    def for_batch(self, batch: int, device=None):
        self.barrier.wait(timeout=30.0)          # all builders inside at once
        with self._lock:
            self.concurrent_builds += 1
            self.stage_d_compiles += 1

        class _Built:
            def __init__(self, b):
                self.batch = b
                self.input_shape = (b, 3, 8, 8)
                self.plan_fingerprint = "rendezvous-fp"
                self.compile_seconds = 0.0
        return _Built(batch)


def test_distinct_buckets_build_concurrently():
    """Builders for different buckets rendezvous inside for_batch — they
    cannot be holding one shared lock."""
    n_buckets = 3
    prog = _RendezvousProgram(expected=n_buckets)
    cache = ProgramCache()
    cache.admit(prog)
    results = _hammer(cache, prog, [1, 2, 4], n_buckets)

    assert prog.concurrent_builds == n_buckets
    assert sorted(r.batch for r in results) == [1, 2, 4]
    assert cache.stats.stage_d_compiles == n_buckets
    assert cache.stats.misses == n_buckets
    assert len(cache) == n_buckets


def test_distinct_buckets_concurrent_same_key_still_once():
    """Both properties at once: 2 distinct buckets build concurrently
    (rendezvous) while 3 extra callers pile onto each bucket and must not
    build a second time."""
    prog = _RendezvousProgram(expected=2)
    cache = ProgramCache()
    cache.admit(prog)
    buckets = [1, 2] + [1, 2] * 3                # 8 calls over 2 buckets
    results = _hammer(cache, prog, buckets, len(buckets))

    assert prog.concurrent_builds == 2           # one build per bucket...
    by_bucket = {}
    for b, r in zip(buckets, results):
        by_bucket.setdefault(b, set()).add(id(r))
    assert all(len(ids) == 1 for ids in by_bucket.values())  # ...shared by all
    assert cache.stats.misses == 2
    assert cache.stats.hits == len(buckets) - 2


def test_get_alias_is_retired(program):
    """The migration window closed in PR 7: the deprecated ``get`` alias
    is gone, and get_or_build is the only entry point."""
    cache = ProgramCache()
    cache.admit(program)
    a = cache.get_or_build(program, 2)
    with pytest.raises(AttributeError):
        cache.get(program, 2)
    assert cache.get_or_build(program, 2) is a
    assert cache.stats.stage_d_compiles == 1 and cache.stats.hits == 1
