"""The spans a traced dispatch records (DESIGN.md §12).

* ``serve.dispatch`` holds six back-to-back phase spans, in order, each
  with the dispatch thread's CPU seconds, covering their parent;
* every request gets a ``serve.request`` span whose ``queue_s`` is its
  wait from enqueue to the bucket's release and whose ``dispatch`` names
  the span that served it;
* untraced, the dispatch records nothing, reads no extra clock, and
  returns bitwise what the traced dispatch returns;
* with ``Tracer(annotate=jax.profiler.TraceAnnotation)`` the spans land
  in a profiler capture, nested on the dispatch thread's line;
* ``assemble`` writes the server's staging buffer where it can (``staged``
  on the span, ``serving_staging_buffers_total`` in the registry): pad
  rows stay zero, served rows stay as they were when the buffer is
  written again, a failed dispatch gives the buffer up, and a dispatch
  racing the holder allocates its own.
"""
import collections
import glob
import statistics
import sys
import threading
import time

import jax
import numpy as np
import pytest

from repro.cnn import init_network_params, squeezenet
from repro.core import ComputeMode, synthesize
from repro.obs import MetricsRegistry, Tracer
from repro.serving import ServingConfig, ServingFuture, SynthesisServer
from repro.serving.batcher import Bucket, Request

jax.config.update("jax_platform_name", "cpu")

PHASES = ["serve.dispatch." + p for p in
          ("lookup", "assemble", "transfer", "execute", "copy_out",
           "complete")]


@pytest.fixture(scope="module")
def program():
    net = squeezenet(scale=0.08, num_classes=10, input_hw=64)
    params = init_network_params(net, jax.random.PRNGKey(0))
    return synthesize(net, params, forced_mode=ComputeMode.RELAXED)


@pytest.fixture(scope="module")
def images(program):
    return np.random.default_rng(3).standard_normal(
        (12, *program.net.input_shape)).astype(np.float32)


def _server(program, **kw):
    return SynthesisServer(
        program, config=ServingConfig(max_batch=4, max_delay_s=60.0), **kw)


def _serve(server, images):
    futs = [server.submit(im) for im in images]
    assert server.drain() == len(images)
    return np.stack([f.result(timeout=30.0) for f in futs])


def _children(tracer, parent):
    return sorted((s for s in tracer.finished()
                   if s.parent_id == parent.span_id),
                  key=lambda s: s.t_start)


def test_dispatch_has_six_phases_in_order_covering_it(program, images):
    tracer = Tracer()
    server = _server(program, tracer=tracer, labels={"replica": 0})
    _serve(server, images)
    dispatches = tracer.by_name("serve.dispatch")
    assert len(dispatches) == 3
    for d in dispatches:
        kids = _children(tracer, d)
        assert [k.name for k in kids] == PHASES
        assert sum(k.duration_s for k in kids) >= 0.97 * d.duration_s
        for k in kids:
            assert 0.0 <= k.attrs["cpu_s"] <= k.duration_s + 1e-3
            assert k.attrs["replica"] == d.attrs["replica"] == "0"
            assert d.t_start <= k.t_start and k.t_end <= d.t_end
        assert d.attrs["batch"] == 4 and d.attrs["requests"] == 4


def test_request_spans_carry_queue_wait_and_serving_dispatch(program,
                                                             images):
    tracer = Tracer()
    server = _server(program, tracer=tracer, labels={"replica": 1})
    futs = [server.submit(im) for im in images[:3]]
    enqueued = [r.enqueue_time for r in server.batcher._queue]
    release = enqueued[-1] + 0.25
    bucket = server.batcher.take(now=release, force=True)
    assert bucket.released == release
    server.dispatch_bucket(bucket)

    (dispatch,) = tracer.by_name("serve.dispatch")
    reqs = tracer.by_name("serve.request")
    assert len(reqs) == 3
    for span, t_enq, fut in zip(reqs, enqueued, futs):
        assert span.attrs["dispatch"] == dispatch.span_id
        assert span.attrs["queue_s"] == release - t_enq
        assert span.attrs["replica"] == "1"
        assert span.t_start == t_enq and span.t_end == fut.complete_time
        assert span.parent_id is None
    # serve.batch_wait stays per bucket
    (wait,) = tracer.by_name("serve.batch_wait")
    assert wait.t_start == enqueued[0] and wait.t_end == release


def test_untraced_dispatch_records_nothing_and_matches(program, images,
                                                       monkeypatch):
    import repro.serving.server as server_mod

    traced = _serve(_server(program, tracer=Tracer()), images)

    reads = []

    def clock():
        reads.append(1)
        return float(len(reads))

    def no_thread_time():
        raise AssertionError("untraced dispatch read the thread clock")

    monkeypatch.setattr(server_mod.time, "thread_time", no_thread_time)
    monkeypatch.setattr(server_mod, "_Phases", None)
    server = _server(program, registry=MetricsRegistry(clock=clock))
    assert server.tracer is None and server.batcher.tracer is None
    futs = [server.submit(im) for im in images]
    n_before = len(reads)
    while server.pump(force=True):
        pass
    # Two registry clock reads per bucket, as before the phases existed.
    assert len(reads) - n_before == 2 * 3
    untraced = np.stack([f.result(timeout=30.0) for f in futs])
    np.testing.assert_array_equal(untraced, traced)


def test_profiler_capture_holds_mirrored_dispatch_phases(program, images,
                                                         tmp_path):
    from jax.profiler import ProfileData

    tracer = Tracer(annotate=jax.profiler.TraceAnnotation)
    server = _server(program, tracer=tracer)
    _serve(server, images[:4])               # first dispatch outside
    n0 = len(tracer.finished())
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(4):
            _serve(server, images)
    mine = [s for s in tracer.finished()[n0:]
            if s.name.startswith("serve.dispatch")]

    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    lines = [(line.name, [e for e in line.events
                          if e.name.startswith("serve.dispatch")])
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines]
    lines = [(name, evs) for name, evs in lines if evs]
    assert len(lines) == 1                   # one thread's line
    events = sorted(lines[0][1],
                    key=lambda e: (e.start_ns, e.name != "serve.dispatch"))
    assert len(events) == len(mine) == 12 * 7

    parents = [e for e in events if e.name == "serve.dispatch"]
    for p in parents:
        kids = [e for e in events if e.name != "serve.dispatch"
                and p.start_ns <= e.start_ns
                and e.start_ns + e.duration_ns
                <= p.start_ns + p.duration_ns]
        assert [k.name for k in kids] == PHASES

    # Matched in order: the Tracer's span and its mirror last as long,
    # per span name, to within 100 us.
    mine.sort(key=lambda s: (s.t_start, s.name != "serve.dispatch"))
    diffs = collections.defaultdict(list)
    for e, s in zip(events, mine):
        assert e.name == s.name
        diffs[e.name].append(abs(e.duration_ns / 1e9 - s.duration_s))
    for name, d in diffs.items():
        assert statistics.median(d) < 100e-6, name


# ---------------------------------------------------------- staging buffer ---
@pytest.fixture(scope="module")
def alone(program, images):
    """Each image run through the program by itself, a bucket of 1."""
    one = program.for_batch(1)
    return np.stack([np.asarray(one(im[None]))[0] for im in images])


def _bucket(images, batch):
    """A bucket of ``batch`` slots holding one request per image."""
    now = time.perf_counter()
    return Bucket(requests=[Request(im, ServingFuture(), now)
                            for im in images], batch=batch, released=now)


def _rows(bucket):
    return np.stack([r.future.result(timeout=30.0) for r in bucket.requests])


def _staging(server, **labels):
    total = server.registry.get("serving_staging_buffers_total")
    return {o: total.value(outcome=o, **labels)
            for o in ("reused", "allocated")}


def _aligned(shape, dtype):
    """Zeros on a page boundary, so the CPU's ``device_put`` may alias
    them rather than copy."""
    size = int(np.prod(shape)) * np.dtype(dtype).itemsize
    raw = np.zeros(size + 4096, np.uint8)
    start = -raw.ctypes.data % 4096
    return raw[start:start + size].view(dtype).reshape(shape)


@pytest.mark.parametrize("counts, staged", [
    ((4, 4, 4), [0, 1, 1]),        # one buffer for every full bucket
    ((1, 4, 2), [0, 0, 1]),        # grows for the 4, then reused
    ((4, 3, 2), [0, 1, 1]),        # smaller buckets pad inside it
    ((4, 1, 4), [0, 0, 1]),        # a bucket of one takes a fresh buffer
])
def test_staging_counts_and_span_attribute(program, images, alone, counts,
                                           staged):
    tracer = Tracer()
    server = _server(program, tracer=tracer, labels={"replica": 2})
    start = 0
    for n in counts:
        bucket = _bucket(images[start:start + n], 1 << (n - 1).bit_length())
        server.dispatch_bucket(bucket)
        np.testing.assert_array_equal(_rows(bucket),
                                      alone[start:start + n])
        start += n
    spans = tracer.by_name("serve.dispatch.assemble")
    assert [s.attrs["staged"] for s in spans] == staged
    assert _staging(server, replica=2) == {
        "reused": sum(staged), "allocated": len(staged) - sum(staged)}


def test_staging_pads_with_zeros_after_a_larger_bucket(program, images,
                                                       alone, monkeypatch):
    import repro.serving.server as server_mod

    sent = []
    put = server_mod.jax.device_put

    def spy(x, device=None):
        sent.append(np.array(x))
        return put(x, device)

    monkeypatch.setattr(server_mod.jax, "device_put", spy)
    server = _server(program)
    full, one = _bucket(images[:4], 4), _bucket(images[4:5], 4)
    server.dispatch_bucket(full)
    server.dispatch_bucket(one)
    assert _staging(server) == {"reused": 1, "allocated": 1}
    np.testing.assert_array_equal(sent[1][0], images[4])
    assert not sent[1][1:].any()               # the old rows were zeroed
    np.testing.assert_array_equal(_rows(full), alone[:4])
    np.testing.assert_array_equal(_rows(one), alone[4:5])


def test_served_rows_survive_the_next_bucket_in_an_aliased_buffer(
        program, images, alone, monkeypatch):
    import repro.serving.server as server_mod

    monkeypatch.setattr(server_mod, "_staging_buffer", _aligned)
    server = _server(program)
    buckets = [_bucket(images[i:i + 4], 4) for i in (0, 4, 8)]
    server.dispatch_bucket(buckets[0])
    assert server._staging.ctypes.data % 4096 == 0
    first = _rows(buckets[0]).copy()
    for b in buckets[1:]:
        server.dispatch_bucket(b)
    assert _staging(server) == {"reused": 2, "allocated": 1}
    np.testing.assert_array_equal(_rows(buckets[0]), first)
    np.testing.assert_array_equal(
        np.concatenate([_rows(b) for b in buckets]), alone)


@pytest.mark.parametrize("where", ["assemble", "execute"])
def test_failed_dispatch_gives_the_staging_buffer_up(program, images, alone,
                                                     monkeypatch, where):
    server = _server(program)
    server.dispatch_bucket(_bucket(images[:4], 4))
    if where == "assemble":                     # a row that cannot be copied
        bad = _bucket([images[4], images[5][:, :8]], 2)
    else:
        def lost(*args):
            def call(x):
                raise RuntimeError("device lost")
            return call

        monkeypatch.setattr(server.cache, "get_or_build", lost)
        bad = _bucket(images[4:6], 2)
    server.dispatch_bucket(bad)
    monkeypatch.undo()
    for r in bad.requests:
        with pytest.raises(Exception):
            r.future.result(timeout=30.0)
    good = _bucket(images[6:10], 4)
    server.dispatch_bucket(good)
    np.testing.assert_array_equal(_rows(good), alone[6:10])
    # the buffer went with the failure: the next bucket allocated anew
    assert _staging(server) == {"reused": 1, "allocated": 2}
    assert server.stats.failed == 2


def test_racing_dispatch_allocates_its_own_buffer(program, images, alone,
                                                  monkeypatch):
    server = _server(program)
    server.dispatch_bucket(_bucket(images[:4], 4))
    build = server.cache.get_or_build
    both = threading.Barrier(2, timeout=30.0)

    def meeting(*args):
        compiled = build(*args)

        def call(x):
            both.wait()         # each executes while the other holds a buffer
            return compiled(x)
        return call

    monkeypatch.setattr(server.cache, "get_or_build", meeting)
    buckets = [_bucket(images[i:i + 4], 4) for i in (4, 8)]
    threads = [threading.Thread(target=server.dispatch_bucket, args=(b,))
               for b in buckets]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60.0)
    assert not any(t.is_alive() for t in threads)
    assert _staging(server) == {"reused": 1, "allocated": 2}
    np.testing.assert_array_equal(
        np.concatenate([_rows(b) for b in buckets]), alone[4:12])


def test_staging_under_many_dispatching_threads(program, images, alone):
    server = _server(program)
    jobs = [(i, n) for i in range(8) for n in (1, 3, 4, 2)]
    buckets = [_bucket(images[(i * 3) % 8:(i * 3) % 8 + n],
                       1 << (n - 1).bit_length()) for i, n in jobs]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(
            target=lambda k=k: [server.dispatch_bucket(b)
                                for b in buckets[k::8]]) for k in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120.0)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    for (i, n), b in zip(jobs, buckets):
        np.testing.assert_array_equal(
            _rows(b), alone[(i * 3) % 8:(i * 3) % 8 + n])
    counts = _staging(server)
    assert counts["reused"] + counts["allocated"] == len(buckets)
    assert server.stats.completed == sum(n for _, n in jobs)
