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
                           flush triggers — the serving configuration;
  ``pump()``               synchronously dispatch at most one bucket —
                           deterministic, for tests and simulations.
"""
from __future__ import annotations

import threading
import time
import warnings
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

import jax
import numpy as np

from ..core.synthesizer import SynthesizedProgram
from ..obs import MetricsRegistry, Tracer
from .batcher import Bucket, DynamicBatcher, FlushPolicy, ServingFuture
from .config import ServingConfig
from .program_cache import ProgramCache

#: A phase (or the dispatch span) with no tracer attached: one reusable
#: no-op context.
_UNTRACED = nullcontext()


def _no_phase(name: str) -> nullcontext:
    return _UNTRACED


def _staging_buffer(shape, dtype) -> np.ndarray:
    """A new host buffer of zeros with every page already faulted in.

    ``np.zeros`` of a buffer this size maps pages that fault and zero on
    first write; ``fill`` pays that once, here, and not in a dispatch.
    """
    buf = np.empty(shape, dtype)
    buf.fill(0)
    return buf


class _Phases:
    """The phase spans of one traced dispatch, back to back.

    Each phase starts where the one before it ended (the first where the
    dispatch began), so what runs between two phases, tracing's own
    bookkeeping included, is charged to the later one, and the phases
    cover their parent.  Each carries ``cpu_s``, the dispatch thread's
    CPU seconds over the same stretch; the rest of its wall time went to
    waiting: for the GIL, a lock, the device or the scheduler.
    """

    __slots__ = ("_tracer", "_labels", "_t", "_cpu", "_scope", "_span")

    def __init__(self, tracer: Tracer, parent, labels: Dict[str, str]):
        self._tracer = tracer
        self._labels = labels
        self._t = parent.t_start
        self._cpu = time.thread_time()

    def __call__(self, name: str) -> "_Phases":
        self._scope = self._tracer.span(name, **self._labels)
        return self

    def __enter__(self):
        self._span = self._scope.__enter__()
        self._span.t_start = self._t
        return self._span

    def __exit__(self, *exc) -> bool:
        cpu = time.thread_time()
        self._span.attrs["cpu_s"] = cpu - self._cpu
        self._cpu = cpu
        done = self._scope.__exit__(*exc)
        self._t = self._span.t_end
        return done


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
        # The staging buffer: one host buffer per server, as many rows as
        # the largest bucket so far, written by the dispatch that holds
        # the lock.  Rows past ``_staged_rows`` are zeros.
        self._staging: Optional[np.ndarray] = None
        self._staged_rows = 0
        self._staging_lock = threading.Lock()
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
    def dispatch_bucket(self, bucket: Bucket) -> None:
        """Pad, execute, and scatter one released bucket.

        Public because the replica tier dispatches buckets it took (or
        stole) itself; the bucket need not come from this server's own
        batcher — work stealing dispatches a peer's requests here.

        A bucket of more than one image is copied into the server's
        staging buffer (:meth:`_assemble`), which is written again only
        once the previous dispatch's output is ready, so the device no
        longer reads it; ``x_dev``, which may alias it on the CPU, is not
        read after that.  A dispatch that finds the buffer held by
        another thread, or that must grow it, allocates one, as does a
        bucket of one; a dispatch that raises gives the buffer up.

        Traced, the ``serve.dispatch`` span holds six back-to-back child
        spans ``serve.dispatch.<phase>``, each with ``cpu_s``: ``lookup``
        (the cache, fingerprint included), ``assemble`` (the copy into
        the host buffer; ``staged`` is 1 where the staging buffer was
        reused, 0 where a buffer was allocated), ``transfer``
        (``device_put`` as the host sees it), ``execute`` (the call to
        ``block_until_ready``, with whatever of the copy to the device
        is still in flight), ``copy_out`` (``devices()`` and
        ``np.asarray``) and ``complete`` (histogram, stats, futures).
        Then each request gets a ``serve.request`` span, enqueue to
        result, with ``queue_s`` (enqueue to release) and ``dispatch``
        (the ``span_id`` of the span that served it).
        """
        t0 = self.registry.clock()
        tracer = self.tracer
        with (_UNTRACED if tracer is None else
              tracer.span("serve.dispatch", batch=bucket.batch,
                          requests=len(bucket.requests),
                          **self._labels)) as span:
            phase = _no_phase if span is None else \
                _Phases(tracer, span, self._labels)
            # Never wait for the buffer: a racing pump() allocates its own.
            # A bucket of one stays on a fresh buffer: on a v5e host the
            # staging buffer there lengthened one-image latency (PERF.md
            # §6) to save ~0.05 ms.
            held = bucket.batch > 1 and \
                self._staging_lock.acquire(blocking=False)
            try:
                with phase("serve.dispatch.lookup"):
                    compiled = self.cache.get_or_build(
                        self.program, bucket.batch, self.device)
                with phase("serve.dispatch.assemble") as assemble:
                    x, staged = self._assemble(bucket, held)
                    if assemble is not None:
                        assemble.attrs["staged"] = int(staged)
                with phase("serve.dispatch.transfer"):
                    x_dev = jax.device_put(x, self.device)
                with phase("serve.dispatch.execute"):
                    y = jax.block_until_ready(compiled(x_dev))
                with phase("serve.dispatch.copy_out"):
                    where = ",".join(sorted(str(d) for d in y.devices()))
                    out = np.asarray(y)
                with phase("serve.dispatch.complete"):
                    self._dispatch_seconds.observe(
                        self.registry.clock() - t0, **self._labels)
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
            except Exception as exc:  # surface the failure on every request
                if held:              # the device may still read it
                    self._staging = None
                if span is not None:
                    span.attrs["error"] = True
                for req in bucket.requests:
                    req.future.set_exception(exc)
                    with self._stats_lock:
                        self.stats.failed += 1
            finally:
                if held:
                    self._staging_lock.release()
        if span is not None:
            for req in bucket.requests:
                tracer.record_span(
                    "serve.request", req.enqueue_time,
                    req.future.complete_time,
                    queue_s=bucket.released - req.enqueue_time,
                    dispatch=span.span_id, **self._labels)

    def _assemble(self, bucket: Bucket, held: bool
                  ) -> Tuple[np.ndarray, bool]:
        """The bucket's host input, its images then zero rows, and
        whether it is the reused staging buffer.

        ``held``: this dispatch holds the staging lock.  Only rows the
        last bucket wrote past this bucket's requests are zeroed, so a
        full bucket writes its rows and nothing else.
        """
        n, batch = len(bucket.requests), bucket.batch
        buf = self._staging if held else None
        staged = buf is not None and len(buf) >= batch
        if staged:
            buf[n:self._staged_rows] = 0
        elif held:
            buf = self._staging = _staging_buffer(
                (batch, *self.program.net.input_shape),
                self.program.input_dtype)
        else:
            buf = np.zeros((batch, *self.program.net.input_shape),
                           self.program.input_dtype)
        self._staging_total.inc(outcome="reused" if staged else "allocated",
                                **self._labels)
        for i, r in enumerate(bucket.requests):
            buf[i] = r.image
        if held:
            self._staged_rows = n
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

    def _loop(self) -> None:
        poll = max(self.policy.max_delay_s, 1e-4)
        while not self._stopping.is_set():
            bucket = self.batcher.take()
            if bucket is not None:
                self.dispatch_bucket(bucket)
                continue
            self.wait_for_trigger(self._stopping, poll)

    def start(self) -> "SynthesisServer":
        if self._thread is not None:
            raise RuntimeError("server already started")
        self._stopping.clear()
        self._thread = threading.Thread(target=self._loop,
                                        name="synthesis-server", daemon=True)
        self._thread.start()
        return self

    def stop(self, drain: bool = True) -> None:
        """Stop the dispatch thread; by default drain queued requests."""
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
