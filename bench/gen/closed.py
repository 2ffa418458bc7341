"""Closed loop: a fixed number of single-image requests always in flight.

Each user sends its next request as soon as its previous one comes back,
so a slower system receives less load.  One client thread plays all the
users: it keeps the outstanding requests in send order, waits on the
oldest, and replaces every one that has completed.  Within a replica
requests complete in send order, so the oldest is the next to complete;
across replicas each has at least two buckets queued at the cell's
depths, which hides the few microseconds a completion may wait to be
noticed.

Traffic file keys: ``outstanding`` (requests in flight), ``pool``
(images in the seeded pool), ``ramp_s`` (seconds the loop runs before
the window opens, so it is at steady state by then).
"""
from __future__ import annotations

import collections
import contextlib
import time
from dataclasses import dataclass, field
from typing import Any, Callable, List, Optional

import numpy as np


@dataclass
class Sent:
    """One request: which pool image it carried, when it was sent, and how
    it ended."""
    pool_idx: int
    t_send: float
    future: Any = field(repr=False)
    t_done: Optional[float] = None
    ok: bool = False


def buckets(traffic: dict, max_batch: int, replicas: int) -> List[int]:
    """Every bucket the batcher can release under this traffic: a queue
    never holds more than ``outstanding`` requests."""
    most = min(int(traffic["outstanding"]), max_batch)
    out, b = [], 1
    while b < most:
        out.append(b)
        b *= 2
    return out + [b]


def _wait(fut, timeout: float) -> None:
    try:
        fut.result(timeout)
    except Exception:       # not done in time, or failed: settled later
        pass


def _settle(s: Sent) -> None:
    fut = s.future
    s.t_done = fut.complete_time
    try:
        fut.result(0)
        s.ok = True
    except Exception:
        s.ok = False


def run(submit: Callable[[np.ndarray], Any], pool: np.ndarray,
        traffic: dict, rng: np.random.Generator, seconds: float, *,
        on_ramped: Callable[[], None] = lambda: None,
        on_open: Callable[[], float] = time.perf_counter,
        on_close: Callable[[], float] = time.perf_counter,
        annotate: Callable[[str], Any] = lambda name: contextlib.nullcontext(),
        late_s: float = 60.0):
    """Drive the loop; returns (sent requests, window open, window close).

    ``on_ramped`` runs once the ramp is over (it may take a while, as
    starting a profiler does; the loop then settles again for ``ramp_s``
    before the window opens).  ``on_open``/``on_close`` return the
    window's boundaries on ``time.perf_counter``'s clock.  After the
    close no request is sent, and the outstanding ones are awaited for up
    to ``late_s`` seconds in all.
    """
    n_out = int(traffic["outstanding"])
    ramp = float(traffic["ramp_s"])
    n_pool = len(pool)
    sent: List[Sent] = []
    outstanding: "collections.deque[Sent]" = collections.deque()

    def send() -> None:
        idx = int(rng.integers(n_pool))
        with annotate("bench.submit"):
            t = time.perf_counter()
            try:
                fut = submit(pool[idx])
            except Exception as exc:         # a refused request has failed
                fut = _Failed(exc)
        s = Sent(idx, t, fut)
        sent.append(s)
        outstanding.append(s)

    def loop_until(t_end: float) -> None:
        while time.perf_counter() < t_end:
            head = outstanding[0]
            with annotate("bench.wait"):
                _wait(head.future, max(0.0, t_end - time.perf_counter()))
            while outstanding and outstanding[0].future.done():
                _settle(outstanding.popleft())
                send()
            # Requests of another replica may finish before the oldest.
            for s in list(outstanding):
                if s.future.done():
                    outstanding.remove(s)
                    _settle(s)
                    send()

    for _ in range(n_out):
        send()
    loop_until(time.perf_counter() + ramp)
    on_ramped()
    loop_until(time.perf_counter() + ramp)
    t_open = on_open()
    loop_until(t_open + seconds)
    t_close = on_close()
    deadline = time.perf_counter() + late_s
    for s in outstanding:
        _wait(s.future, max(0.0, deadline - time.perf_counter()))
        if s.future.done():
            _settle(s)
    return sent, t_open, t_close


class _Failed:
    """Stands for the future of a request the tier refused to admit."""

    def __init__(self, exc: BaseException):
        self.exception = exc
        self.complete_time = time.perf_counter()

    def done(self) -> bool:
        return True

    def result(self, timeout=None):
        raise self.exception
