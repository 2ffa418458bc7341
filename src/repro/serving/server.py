"""SynthesisServer: batched serving of synthesized CNN programs.

The end of the Cappuccino pipeline meets traffic here (DESIGN.md §6):
single-image requests are coalesced by a :class:`~repro.serving.batcher.
DynamicBatcher` into power-of-two buckets, each bucket is padded and
dispatched through a :class:`~repro.serving.program_cache.ProgramCache`-
held :class:`~repro.core.synthesizer.BatchProgram` (Stage D compiled once
per bucket), and per-request rows are scattered back to their futures.

Batching is semantically transparent: a request's output is bitwise
identical to running its image through the program alone — padding rows
are zeros and are sliced off, and row i of an XLA batch does not read row
j.  The round-trip test in tests/test_serving_cnn.py pins this.

Two dispatch modes share all logic:

  ``start()``/``stop()``   a background thread waits on the batcher's
                           flush triggers and keeps up to two buckets in
                           flight (:meth:`SynthesisServer.serve`) — the
                           serving configuration;
  ``pump()``               synchronously dispatch at most one bucket —
                           deterministic, for tests and simulations.
"""
from __future__ import annotations

import queue
import threading
import time
import warnings
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from ..core.synthesizer import SynthesizedProgram
from ..obs import MetricsRegistry, Span, Tracer
from .batcher import Bucket, DynamicBatcher, FlushPolicy, ServingFuture
from .config import ServingConfig
from .program_cache import ProgramCache

#: Buckets one server keeps in flight: one the device runs, and the next,
#: launched behind it while the host assembles it.
PIPELINE_DEPTH = 2


def _staging_buffer(shape, dtype) -> np.ndarray:
    """A new host buffer of zeros with every page already faulted in.

    ``np.zeros`` of a buffer this size maps pages that fault and zero on
    first write; ``fill`` pays that once, here, and not in a dispatch.
    """
    buf = np.empty(shape, dtype)
    buf.fill(0)
    return buf


class _Slot:
    """One pipeline slot's staging buffer, and the lock that the dispatch
    writing it holds until the device no longer reads it."""

    __slots__ = ("lock", "buf", "rows")

    def __init__(self):
        self.lock = threading.Lock()
        self.buf: Optional[np.ndarray] = None
        self.rows = 0          # rows the last bucket wrote; past them, zeros


class _Phases:
    """The phase spans of one traced dispatch, back to back, kept until
    the dispatch finishes and then recorded under its ``serve.dispatch``.

    Each phase starts where the one before it ended (the first where the
    dispatch began), so what runs between two phases, tracing's own
    bookkeeping included, is charged to the later one, and the phases
    cover their parent.  Each carries ``cpu_s``, the CPU seconds of the
    thread that ran it; the rest of its wall time went to waiting: for
    the GIL, a lock, the device or the scheduler.

    A pipelined dispatch is launched on one thread and finished on
    another: :meth:`hand_off` leaves the running phase open, keeping its
    CPU seconds so far, and :meth:`resume` takes it up on the finishing
    thread.  With the tracer's ``annotate``, the dispatch and each phase
    are mirrored on the thread that runs them, so a handed-off dispatch
    shows as one ``serve.dispatch`` on each of the two threads.
    """

    __slots__ = ("_tracer", "_labels", "_marks", "_name", "_attrs", "_t",
                 "_cpu", "_carried", "_outer", "_inner", "t_start")

    def __init__(self, tracer: Tracer, labels: Dict[str, str]):
        self._tracer = tracer
        self._labels = labels
        self._marks: List[Tuple[str, float, float, Dict[str, object]]] = []
        self._name: Optional[str] = None
        self._attrs: Dict[str, object] = {}
        self._carried = 0.0
        self._inner = None
        self._outer = self._mirror("serve.dispatch")
        self.t_start = self._t = tracer.clock()
        self._cpu = time.thread_time()

    def _mirror(self, name: str):
        if self._tracer.annotate is None:
            return None
        scope = self._tracer.annotate(name)
        scope.__enter__()
        return scope

    def _close(self, error: bool = False) -> None:
        if self._name is None:
            return
        t, cpu = self._tracer.clock(), time.thread_time()
        attrs = self._attrs
        attrs["cpu_s"] = self._carried + cpu - self._cpu
        if error:
            attrs["error"] = True
        self._marks.append((self._name, self._t, t, attrs))
        self._name, self._t, self._cpu, self._carried = None, t, cpu, 0.0
        if self._inner is not None:
            self._inner.__exit__(None, None, None)
            self._inner = None

    def next(self, name: str) -> None:
        """End the running phase, if any, and start ``name``."""
        self._close()
        self._inner = self._mirror(name)
        self._name, self._attrs = name, {}

    def note(self, **attrs) -> None:
        """Attach attributes to the running phase."""
        self._attrs.update(attrs)

    def hand_off(self) -> None:
        """Leave the running phase open for the thread that finishes."""
        self._carried += time.thread_time() - self._cpu
        for scope in (self._inner, self._outer):
            if scope is not None:
                scope.__exit__(None, None, None)

    def resume(self) -> None:
        """Take a handed-off dispatch up on this thread."""
        self._outer = self._mirror("serve.dispatch")
        self._inner = self._mirror(self._name)
        self._cpu = time.thread_time()

    def end(self, error: bool = False) -> None:
        """End the running phase, and the dispatch."""
        self._close(error)
        if self._outer is not None:
            self._outer.__exit__(None, None, None)
            self._outer = None

    def record(self, **attrs) -> Span:
        """Record ``serve.dispatch`` with ``attrs``, then its phases."""
        tracer = self._tracer
        span = tracer.record_span("serve.dispatch", self.t_start, self._t,
                                  **attrs)
        for name, t0, t1, phase_attrs in self._marks:
            tracer.record_span(name, t0, t1, parent=span.span_id,
                               **self._labels, **phase_attrs)
        return span


class _Untraced:
    """The phases of an untraced dispatch: nothing is read or kept."""

    __slots__ = ()

    def next(self, name: str) -> None:
        pass

    def note(self, **attrs) -> None:
        pass

    def hand_off(self) -> None:
        pass

    def resume(self) -> None:
        pass

    def end(self, error: bool = False) -> None:
        pass


_UNTRACED = _Untraced()


@dataclass
class _InFlight:
    """A launched bucket, from :meth:`SynthesisServer._launch` to
    :meth:`SynthesisServer._finish`."""
    bucket: Bucket
    t0: float                        # registry clock at launch
    phases: Any                      # _Phases, or _UNTRACED
    slot: Optional[_Slot]            # the staging slot held, if any
    overlapped: bool                 # launched behind another bucket
    y: Any = None                    # the program's output, maybe not ready
    error: Optional[Exception] = None


@dataclass
class ServerStats:
    requests: int = 0
    completed: int = 0
    failed: int = 0
    batches: int = 0
    padded_slots: int = 0
    bucket_counts: Dict[int, int] = field(default_factory=dict)
    #: Completed requests per device their output was computed on.
    output_devices: Dict[str, int] = field(default_factory=dict)

    @property
    def dispatched_slots(self) -> int:
        return sum(b * n for b, n in self.bucket_counts.items())

    @property
    def padding_fraction(self) -> float:
        slots = self.dispatched_slots
        return self.padded_slots / slots if slots else 0.0

    def as_dict(self) -> Dict[str, object]:
        return {"requests": self.requests, "completed": self.completed,
                "failed": self.failed, "batches": self.batches,
                "padded_slots": self.padded_slots,
                "padding_fraction": round(self.padding_fraction, 4),
                "bucket_counts": {str(k): v for k, v
                                  in sorted(self.bucket_counts.items())},
                "output_devices": dict(sorted(self.output_devices.items()))}


class SynthesisServer:
    """Serve one synthesized program under a dynamic batching policy.

    ``config`` is the consolidated :class:`~repro.serving.config.
    ServingConfig` — bucket policy and cache budget both come from it
    (``policy=`` is the deprecated pre-config spelling).  ``program``
    carries Stages A–C (plan + prepared weights); the server only ever
    triggers Stage D, through the shared ``cache`` — pass one
    ``ProgramCache`` to several servers to share compiled buckets across
    replicas of the same network/plan (what ``ReplicaSet`` does).
    ``device`` binds the server to one JAX device: its executables are
    compiled for it and every bucket is placed on it (None = the default
    device).
    """

    def __init__(self, program: SynthesizedProgram, *,
                 config: Optional[ServingConfig] = None,
                 cache: Optional[ProgramCache] = None,
                 policy: Optional[FlushPolicy] = None,
                 registry: Optional[MetricsRegistry] = None,
                 tracer: Optional[Tracer] = None,
                 labels: Optional[Dict[str, object]] = None,
                 device=None):
        if policy is not None:
            if config is not None:
                raise ValueError("pass either config= or the deprecated "
                                 "policy= FlushPolicy, not both")
            warnings.warn(
                "SynthesisServer(policy=FlushPolicy(...)) is deprecated; "
                "pass config=ServingConfig(...) — the consolidated serving "
                "configuration", DeprecationWarning, stacklevel=2)
            config = ServingConfig.from_flush_policy(policy)
        self.config = config or ServingConfig()
        self.program = program
        self.device = device
        self.cache = cache if cache is not None else \
            ProgramCache(config=self.config, registry=registry, tracer=tracer)
        self.policy = self.config.flush_policy()
        self.cache.admit(program)
        # One registry per serving tier: an explicit registry= wins,
        # otherwise the cache's — so a server sharing a ProgramCache with
        # its peers (ReplicaSet) lands cache, batcher, and dispatch series
        # in the same snapshot without any extra plumbing.
        self.registry = registry if registry is not None else \
            self.cache.registry
        self.tracer = tracer if tracer is not None else self.cache.tracer
        self._labels = {k: str(v) for k, v in (labels or {}).items()}
        self.batcher = DynamicBatcher(config=self.config,
                                      registry=self.registry,
                                      tracer=self.tracer, labels=self._labels)
        self._dispatch_seconds = self.registry.histogram(
            "serving_dispatch_seconds",
            "Wall time of one bucket dispatch (pad + execute + scatter)",
            tuple(sorted(self._labels)))
        self._staging_total = self.registry.counter(
            "serving_staging_buffers_total",
            "Bucket host buffers: the server's staging buffer reused, or "
            "a buffer allocated", tuple(sorted(self._labels)) + ("outcome",))
        for outcome in ("reused", "allocated"):
            self._staging_total.inc(0, outcome=outcome, **self._labels)
        self._overlap_total = self.registry.counter(
            "serving_dispatch_overlap_total",
            "Buckets launched while another bucket of the same server was "
            "in flight (1), or with none (0)",
            tuple(sorted(self._labels)) + ("overlapped",))
        for overlapped in ("1", "0"):
            self._overlap_total.inc(0, overlapped=overlapped, **self._labels)
        # One staging buffer per pipeline slot, each as many rows as the
        # largest bucket it has staged.
        self._slots = tuple(_Slot() for _ in range(PIPELINE_DEPTH))
        self.stats = ServerStats()
        self._stats_lock = threading.Lock()   # submit() races the loop
        self._thread: Optional[threading.Thread] = None
        self._stopping = threading.Event()

    # -- request side -------------------------------------------------------
    def submit(self, image) -> ServingFuture:
        """Enqueue one (C, H, W) image; returns its completion future."""
        expect = tuple(self.program.net.input_shape)
        if tuple(np.shape(image)) != expect:
            raise ValueError(f"expected a single image of shape {expect}, "
                             f"got {tuple(np.shape(image))}")
        with self._stats_lock:
            self.stats.requests += 1
        return self.batcher.submit(image)

    def infer_one(self, image, timeout: Optional[float] = 30.0):
        """Synchronous convenience wrapper: submit and wait.

        With no background thread running, the request is flushed
        immediately (a forced bucket of one) instead of waiting out the
        batching deadline against nobody.
        """
        fut = self.submit(image)
        if self._thread is None:
            self.pump(force=True)
        return fut.result(timeout)

    # -- dispatch side ------------------------------------------------------
    @property
    def _staging(self) -> Optional[np.ndarray]:
        """The first slot's staging buffer: the one a dispatch with
        nothing else in flight writes."""
        return self._slots[0].buf

    def dispatch_bucket(self, bucket: Bucket) -> None:
        """Pad, execute, and scatter one released bucket, on this thread.

        Public because the replica tier dispatches buckets it took (or
        stole) itself; the bucket need not come from this server's own
        batcher — work stealing dispatches a peer's requests here.  It is
        :meth:`_launch` then :meth:`_finish`; the serving thread
        (:meth:`serve`) runs the two apart, on two threads.

        A bucket of more than one image is copied into the staging buffer
        of a free pipeline slot (:meth:`_assemble`), which is written
        again only once the dispatch holding it has its output ready, so
        the device no longer reads it; ``x_dev``, which may alias it on
        the CPU, is not read after that.  A dispatch that finds both
        slots held (a ``pump()`` racing the serving thread), or that must
        grow the buffer, allocates one, as does a bucket of one; a
        dispatch that raises gives its slot's buffer up.

        Traced, the ``serve.dispatch`` span (``batch``, ``requests``,
        ``overlapped``: 1 where the bucket was launched while another of
        this server's was in flight) holds six back-to-back child spans
        ``serve.dispatch.<phase>``, each with ``cpu_s``: ``lookup`` (the
        cache, fingerprint included), ``assemble`` (the copy into the
        host buffer; ``staged`` is 1 where a slot's buffer was reused, 0
        where a buffer was allocated), ``transfer`` (``device_put`` as
        the host sees it), ``execute`` (the compiled call to the output
        being ready, with whatever of the copy to the device is still in
        flight and, pipelined, the wait behind the other slot's bucket),
        ``copy_out`` (``devices()`` and ``np.asarray``) and ``complete``
        (histogram, stats, futures).  Then each request gets a
        ``serve.request`` span, enqueue to result, with ``queue_s``
        (enqueue to release) and ``dispatch`` (the ``span_id`` of the
        span that served it).
        """
        self._finish(self._launch(bucket))

    def _claim_slot(self, batch: int) -> Optional[_Slot]:
        """A free slot, its lock now held; None for a bucket of one or
        where both are held.  Never waits: a racing ``pump()`` allocates
        its own buffer.  A bucket of one stays on a fresh buffer: on a
        v5e host the staging buffer there lengthened one-image latency
        (PERF.md §6) to save ~0.05 ms."""
        if batch > 1:
            for slot in self._slots:
                if slot.lock.acquire(blocking=False):
                    return slot
        return None

    def _launch(self, bucket: Bucket, overlapped: bool = False) -> _InFlight:
        """Look up, assemble, transfer and call the program on one bucket;
        JAX runs the call asynchronously.  A step that raises is kept in
        the record, for :meth:`_finish` to fail the bucket's requests."""
        tracer = self.tracer
        flight = _InFlight(
            bucket, self.registry.clock(),
            _UNTRACED if tracer is None or not tracer.enabled else
            _Phases(tracer, self._labels),
            self._claim_slot(bucket.batch), overlapped)
        self._overlap_total.inc(overlapped=str(int(overlapped)),
                                **self._labels)
        phases = flight.phases
        try:
            phases.next("serve.dispatch.lookup")
            compiled = self.cache.get_or_build(
                self.program, bucket.batch, self.device)
            phases.next("serve.dispatch.assemble")
            x, staged = self._assemble(bucket, flight.slot)
            phases.note(staged=int(staged))
            phases.next("serve.dispatch.transfer")
            x_dev = jax.device_put(x, self.device)
            phases.next("serve.dispatch.execute")
            flight.y = compiled(x_dev)
        except Exception as exc:
            flight.error = exc
        return flight

    def _finish(self, flight: _InFlight) -> None:
        """Wait for a launched bucket's output, release its slot, and
        scatter its rows to the requests' futures (or its error)."""
        bucket, phases, slot = flight.bucket, flight.phases, flight.slot
        try:
            if flight.error is not None:
                raise flight.error
            y = jax.block_until_ready(flight.y)
            if slot is not None:       # the device is done reading it
                slot.lock.release()
                slot = None
            phases.next("serve.dispatch.copy_out")
            where = ",".join(sorted(str(d) for d in y.devices()))
            out = np.asarray(y)
            phases.next("serve.dispatch.complete")
            self._dispatch_seconds.observe(
                self.registry.clock() - flight.t0, **self._labels)
            with self._stats_lock:
                self.stats.batches += 1
                self.stats.padded_slots += bucket.padding
                self.stats.bucket_counts[bucket.batch] = \
                    self.stats.bucket_counts.get(bucket.batch, 0) + 1
                self.stats.output_devices[where] = \
                    self.stats.output_devices.get(where, 0) \
                    + len(bucket.requests)
            for i, req in enumerate(bucket.requests):
                req.future.set_result(out[i])
                with self._stats_lock:
                    self.stats.completed += 1
            phases.end()
            failed = False
        except Exception as exc:  # surface the failure on every request
            phases.end(error=True)
            if slot is not None:  # the device may still read it
                slot.buf = None
            for req in bucket.requests:
                req.future.set_exception(exc)
                with self._stats_lock:
                    self.stats.failed += 1
            failed = True
        finally:
            if slot is not None:
                slot.lock.release()
        if phases is _UNTRACED:
            return
        attrs = {"error": True} if failed else {}
        span = phases.record(batch=bucket.batch,
                             requests=len(bucket.requests),
                             overlapped=int(flight.overlapped),
                             **self._labels, **attrs)
        for req in bucket.requests:
            self.tracer.record_span(
                "serve.request", req.enqueue_time, req.future.complete_time,
                queue_s=bucket.released - req.enqueue_time,
                dispatch=span.span_id, **self._labels)

    def _assemble(self, bucket: Bucket, slot: Optional[_Slot]
                  ) -> Tuple[np.ndarray, bool]:
        """The bucket's host input, its images then zero rows, and
        whether it is a slot's reused staging buffer.

        ``slot``: the slot this dispatch holds, if any.  Only rows the
        slot's last bucket wrote past this bucket's requests are zeroed,
        so a full bucket writes its rows and nothing else.
        """
        n, batch = len(bucket.requests), bucket.batch
        buf = slot.buf if slot is not None else None
        staged = buf is not None and len(buf) >= batch
        if staged:
            buf[n:slot.rows] = 0
        elif slot is not None:
            buf = slot.buf = _staging_buffer(
                (batch, *self.program.net.input_shape),
                self.program.input_dtype)
        else:
            buf = np.zeros((batch, *self.program.net.input_shape),
                           self.program.input_dtype)
        self._staging_total.inc(outcome="reused" if staged else "allocated",
                                **self._labels)
        for i, r in enumerate(bucket.requests):
            buf[i] = r.image
        if slot is not None:
            slot.rows = n
        return buf[:batch], staged

    def pump(self, force: bool = False) -> int:
        """Dispatch at most one bucket now; returns requests served."""
        bucket = self.batcher.take(force=force)
        if bucket is None:
            return 0
        self.dispatch_bucket(bucket)
        return len(bucket.requests)

    def drain(self) -> int:
        """Dispatch until the queue is empty; returns requests served."""
        served = 0
        while True:
            n = self.pump(force=True)
            if n == 0:
                return served
            served += n

    # -- background loop ----------------------------------------------------
    def wait_for_trigger(self, stopping: threading.Event,
                         poll: float) -> None:
        """Sleep until a flush trigger may have fired: a submit (which can
        complete a full bucket), the oldest request's deadline, or at most
        ``poll`` seconds.  The trigger is checked under the batcher's lock
        that ``submit`` notifies under, so no wakeup is lost."""
        batcher = self.batcher
        with batcher.not_empty:
            if stopping.is_set() or batcher.ready():
                return
            deadline = batcher.next_deadline()
            timeout = poll if deadline is None else \
                max(0.0, min(deadline - time.perf_counter(), poll))
            batcher.not_empty.wait(timeout=timeout)

    def serve(self, take: Callable[[], Optional[Bucket]],
              stopping: threading.Event) -> None:
        """Dispatch the buckets ``take`` releases until ``stopping`` is
        set: the body of a serving thread, this server's own
        (:meth:`start`, ``take`` its batcher's) or a replica's in a
        ``ReplicaSet`` (whose ``take`` may steal from a peer).

        Up to :data:`PIPELINE_DEPTH` buckets are in flight: this thread
        launches each (:meth:`_launch`), so the next bucket is assembled,
        copied and called while the device runs the one before it, and a
        completer thread finishes them (:meth:`_finish`) in launch order,
        so requests complete in the order their buckets were taken.  A
        bucket taken with nothing in flight and nothing queued behind it
        is dispatched on this thread with no hand-off (the idle-server
        rule): a lone request waits on no other thread.  Before this
        returns, the completer finishes every bucket in flight.
        """
        poll = max(self.policy.max_delay_s, 1e-4)
        launched: "queue.SimpleQueue[Optional[_InFlight]]" = \
            queue.SimpleQueue()
        room = threading.Condition()
        in_flight = 0

        def complete() -> None:
            nonlocal in_flight
            while (flight := launched.get()) is not None:
                try:
                    flight.phases.resume()
                    self._finish(flight)
                finally:
                    with room:
                        in_flight -= 1
                        room.notify()

        completer = threading.Thread(
            target=complete, daemon=True,
            name=threading.current_thread().name + "-complete")
        completer.start()
        try:
            while not stopping.is_set():
                with room:
                    room.wait_for(lambda: in_flight < PIPELINE_DEPTH)
                bucket = take()
                if bucket is None:
                    self.wait_for_trigger(stopping, poll)
                    continue
                with room:
                    busy = in_flight
                if not busy and not self.batcher.depth:
                    self.dispatch_bucket(bucket)
                    continue
                flight = self._launch(bucket, overlapped=busy > 0)
                flight.phases.hand_off()
                with room:
                    in_flight += 1
                launched.put(flight)
        finally:
            launched.put(None)
            completer.join()

    def start(self) -> "SynthesisServer":
        if self._thread is not None:
            raise RuntimeError("server already started")
        self._stopping.clear()
        self._thread = threading.Thread(
            target=self.serve, args=(self.batcher.take, self._stopping),
            name="synthesis-server", daemon=True)
        self._thread.start()
        return self

    def stop(self, drain: bool = True) -> None:
        """Stop the dispatch thread once every bucket in flight has
        finished; by default then drain queued requests."""
        if self._thread is None:
            return
        self._stopping.set()
        with self.batcher.not_empty:
            self.batcher.not_empty.notify_all()
        self._thread.join(timeout=30.0)
        self._thread = None
        if drain:
            self.drain()

    def __enter__(self) -> "SynthesisServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()
