"""The serving thread's two-deep dispatch pipeline (DESIGN.md §6.3).

A stub program stands in for the device: its call returns at once, and
its output is ready only a fixed delay after the device, which runs one
bucket at a time, gets to it.  It reads its input when the output becomes
ready, as the device's copy reads the host buffer, so a staging buffer
written again too early shows as a wrong row.  ``device_put`` hands the
host buffer through unchanged for the same reason.

* at most two buckets of one server are in flight, and the pipeline
  does engage: buckets are launched behind another;
* buckets complete in launch order;
* every row served is its own request's, with the two slots' staging
  buffers apart;
* a lone bucket, taken with nothing in flight or queued, is dispatched on
  the serving thread itself, with ``overlapped`` 0;
* ``stop()`` finishes every bucket in flight;
* a bucket that fails in flight fails its own requests only, gives its
  buffer up, and the pipeline goes on;
* a pipelined bucket's six phases stay back to back under its
  ``serve.dispatch`` span.
"""
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

import repro.serving.server as server_mod
from repro.obs import Tracer
from repro.serving import ReplicaSet, ServingConfig, SynthesisServer

DELAY_S = 0.03
PHASES = ["serve.dispatch." + p for p in
          ("lookup", "assemble", "transfer", "execute", "copy_out",
           "complete")]
POISON = -1.0


class _Output:
    """A bucket's output: ready ``DELAY_S`` after the device gets to it;
    twice its input, read from the host buffer at that moment."""

    def __init__(self, device, x):
        self.device, self.x = device, x
        self.ready_at = device.start(self)

    def block_until_ready(self):
        time.sleep(max(0.0, self.ready_at - time.perf_counter()))
        if (self.x[:, 0] == POISON).any():
            raise RuntimeError("device fault")
        self.value = 2.0 * np.array(self.x)
        return self

    def devices(self):
        return {"stub"}

    def __array__(self, dtype=None, copy=None):
        self.device.done(self)
        return self.value


class _Device:
    """Runs one bucket at a time; counts the buckets between their launch
    and their copy-out, and keeps the order of both."""

    def __init__(self):
        self.lock = threading.Lock()
        self.free_at = 0.0
        self.in_flight = 0
        self.most_in_flight = 0
        self.launched, self.copied, self.outputs = [], [], []

    def start(self, out):
        with self.lock:
            self.in_flight += 1
            self.most_in_flight = max(self.most_in_flight, self.in_flight)
            self.launched.append(float(out.x[0, 0]))
            self.outputs.append(out)
            self.free_at = max(self.free_at, time.perf_counter()) + DELAY_S
            return self.free_at

    def done(self, out):
        with self.lock:
            self.in_flight -= 1
            self.copied.append(float(out.x[0, 0]))


class StubProgram:
    """Duck-typed SynthesizedProgram whose executables run on one
    :class:`_Device`."""

    def __init__(self):
        self.net = SimpleNamespace(name="stubnet", input_shape=(3,))
        self.plan = SimpleNamespace(profile=SimpleNamespace(name="stub"))
        self.input_dtype = np.float32
        self.device = _Device()

    def fingerprint(self):
        return "stub-fp"

    def for_batch(self, batch, device=None):
        return _Executable(self.device)


class _Executable:
    compile_seconds = 0.0

    def __init__(self, device):
        self.device = device

    def __call__(self, x):
        return _Output(self.device, x)


@pytest.fixture(autouse=True)
def host_buffer_is_device_input(monkeypatch):
    monkeypatch.setattr(server_mod.jax, "device_put",
                        lambda x, device=None: x)


def _img(v):
    return np.full(3, float(v), np.float32)


def _server(tracer=None, max_batch=4, max_delay_s=0.05):
    return SynthesisServer(
        StubProgram(),
        config=ServingConfig(max_batch=max_batch, max_delay_s=max_delay_s),
        tracer=tracer)


def _overlaps(server, **labels):
    total = server.registry.get("serving_dispatch_overlap_total")
    return {o: total.value(overlapped=o, **labels) for o in ("1", "0")}


def _run(server, values):
    """Queue one request per value, then serve them all and stop."""
    futs = [server.submit(_img(v)) for v in values]
    server.start()
    try:
        for f in futs:
            try:
                f.result(timeout=30.0)
            except RuntimeError:          # a request of a failed bucket
                pass
    finally:
        server.stop()
    return futs


def _staging(server):
    total = server.registry.get("serving_staging_buffers_total")
    return {o: total.value(outcome=o) for o in ("reused", "allocated")}


def test_at_most_two_buckets_in_flight_and_pipeline_engages():
    server = _server()
    futs = _run(server, range(1, 41))
    device = server.program.device
    assert device.most_in_flight == 2
    assert all(f.done() for f in futs)
    overlaps = _overlaps(server)
    assert overlaps["1"] + overlaps["0"] == 10
    assert overlaps["1"] >= 8


def test_buckets_complete_in_launch_order():
    server = _server()
    futs = _run(server, range(1, 41))
    device = server.program.device
    assert device.copied == device.launched
    done = [f.complete_time for f in futs]
    assert done == sorted(done)          # within a bucket, in row order


def test_rows_are_their_own_with_the_slots_buffers_apart():
    server = _server()
    futs = _run(server, range(1, 41))
    for v, f in zip(range(1, 41), futs):
        np.testing.assert_array_equal(f.result(0), 2.0 * _img(v))
    buffers = {id(out.x.base) for out in server.program.device.outputs}
    assert len(buffers) == 2
    assert _staging(server) == {"reused": 8, "allocated": 2}


def test_lone_bucket_finishes_on_the_serving_thread():
    tracer = Tracer()
    server = _server(tracer=tracer, max_delay_s=0.002)
    server.start()
    try:
        for v in (1, 2, 3):
            np.testing.assert_array_equal(
                server.submit(_img(v)).result(timeout=30.0), 2.0 * _img(v))
    finally:
        server.stop()
    spans = tracer.by_name("serve.dispatch")
    assert [s.attrs["overlapped"] for s in spans] == [0, 0, 0]
    assert {s.thread for s in spans} == {"synthesis-server"}
    assert _overlaps(server) == {"1": 0, "0": 3}


def test_stop_finishes_every_bucket_in_flight():
    server = _server()
    device = server.program.device
    futs = [server.submit(_img(v)) for v in range(1, 41)]
    server.start()
    deadline = time.perf_counter() + 30.0
    while device.in_flight < 2 and time.perf_counter() < deadline:
        time.sleep(1e-3)
    server.stop()
    assert device.most_in_flight == 2
    assert all(f.done() for f in futs)
    for v, f in zip(range(1, 41), futs):
        np.testing.assert_array_equal(f.result(0), 2.0 * _img(v))
    assert device.in_flight == 0
    assert not [t for t in threading.enumerate()
                if t.name.startswith("synthesis-server")]


def test_bucket_failing_in_flight_fails_only_its_requests():
    server = _server()
    values = list(range(1, 41))
    values[12] = POISON                   # in the fourth bucket
    futs = _run(server, values)
    for i, (v, f) in enumerate(zip(values, futs)):
        if 12 <= i < 16:
            with pytest.raises(RuntimeError, match="device fault"):
                f.result(0)
        else:
            np.testing.assert_array_equal(f.result(0), 2.0 * _img(v))
    assert server.stats.failed == 4 and server.stats.completed == 36
    outputs = server.program.device.outputs
    bad = outputs[3]
    assert bad.x[0, 0] == POISON
    # its buffer went with the failure: no later bucket wrote it
    assert id(bad.x.base) not in {id(o.x.base) for o in outputs[4:]}
    assert _staging(server) == {"reused": 7, "allocated": 3}


def test_pipelined_phases_back_to_back_under_their_dispatch():
    tracer = Tracer()
    server = _server(tracer=tracer)
    _run(server, range(1, 41))
    dispatches = tracer.by_name("serve.dispatch")
    assert len(dispatches) == 10
    pipelined = [d for d in dispatches if d.attrs["overlapped"] == 1]
    assert len(pipelined) >= 8
    for d in pipelined:
        kids = sorted((s for s in tracer.finished()
                       if s.parent_id == d.span_id), key=lambda s: s.t_start)
        assert [k.name for k in kids] == PHASES
        assert kids[0].t_start == d.t_start and kids[-1].t_end == d.t_end
        for a, b in zip(kids, kids[1:]):
            assert a.t_end == b.t_start
        for k in kids:
            assert k.attrs["cpu_s"] >= 0.0
        # the device's delay is inside execute, which waits for it
        assert kids[3].duration_s >= 0.5 * DELAY_S
    reqs = tracer.by_name("serve.request")
    assert len(reqs) == 40
    assert {r.attrs["dispatch"] for r in reqs} == \
        {d.span_id for d in dispatches}


def test_replica_tier_pipelines_each_replica():
    program = StubProgram()
    tier = ReplicaSet(program, config=ServingConfig(
        max_batch=4, max_delay_s=0.05, replicas=2))
    futs = [tier.submit(_img(v)) for v in range(1, 81)]
    tier.start()
    for f in futs:
        f.result(timeout=30.0)
    tier.stop()
    for v, f in zip(range(1, 81), futs):
        np.testing.assert_array_equal(f.result(0), 2.0 * _img(v))
    for r in tier.replicas:
        overlaps = _overlaps(r.server, replica=r.index)
        assert overlaps["1"] + overlaps["0"] == 10
        assert overlaps["1"] >= 1
    assert program.device.most_in_flight <= 4
