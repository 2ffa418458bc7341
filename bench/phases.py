"""Readers of the program's dispatch-phase and per-request spans.

A traced dispatch (``serving/server.py``) records ``serve.dispatch`` with
six back-to-back children ``serve.dispatch.<phase>``, each carrying
``cpu_s``, the dispatch thread's CPU seconds in the phase; every request
served records ``serve.request`` with ``queue_s``, its wait from enqueue
to its bucket's release.  A program that records none of these reads
None.
"""
from __future__ import annotations

from typing import Optional

PHASES = ("lookup", "assemble", "transfer", "execute", "copy_out",
          "complete")


def phase_ms(ctx, phase: str) -> Optional[float]:
    """Mean duration of the ``serve.dispatch.<phase>`` spans, in ms."""
    spans = ctx.spans_named("serve.dispatch." + phase)
    if not spans:
        return None
    return 1e3 * sum(s.t_end - s.t_start for s in spans) / len(spans)


def offcpu_share(ctx) -> Optional[float]:
    """1 - CPU seconds / wall seconds of the dispatch thread, summed over
    every phase but ``execute`` (which waits on the device by design):
    the share of the host's dispatch work spent waiting for the GIL, a
    lock or the scheduler."""
    wall = cpu = 0.0
    for phase in PHASES:
        if phase == "execute":
            continue
        for s in ctx.spans_named("serve.dispatch." + phase):
            wall += s.t_end - s.t_start
            cpu += s.attrs["cpu_s"]
    return 1.0 - cpu / wall if wall > 0 else None


def queue_wait_ms(ctx) -> Optional[float]:
    """Mean ``queue_s`` of the ``serve.request`` spans, in ms."""
    spans = ctx.spans_named("serve.request")
    if not spans:
        return None
    return 1e3 * sum(s.attrs["queue_s"] for s in spans) / len(spans)
