"""The spans a traced dispatch records (DESIGN.md §12).

* ``serve.dispatch`` holds six back-to-back phase spans, in order, each
  with the dispatch thread's CPU seconds, covering their parent;
* every request gets a ``serve.request`` span whose ``queue_s`` is its
  wait from enqueue to the bucket's release and whose ``dispatch`` names
  the span that served it;
* untraced, the dispatch records nothing, reads no extra clock, and
  returns bitwise what the traced dispatch returns;
* with ``Tracer(annotate=jax.profiler.TraceAnnotation)`` the spans land
  in a profiler capture, nested on the dispatch thread's line.
"""
import collections
import glob
import statistics

import jax
import numpy as np
import pytest

from repro.cnn import init_network_params, squeezenet
from repro.core import ComputeMode, synthesize
from repro.obs import MetricsRegistry, Tracer
from repro.serving import ServingConfig, SynthesisServer

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
