"""From a profiler trace to device busy time, kernel time and idle gaps.

``capture`` records a JAX profiler trace (host threads plus every
device's operations).  ``load`` reads the ``.xplane.pb`` it wrote into a
plain :class:`Trace`: the operations each chip ran, and the host events
(the runtime's own, and the ``TraceAnnotation``s the benchmark places
around its calls).  ``reduce`` turns a trace and a window into numbers:

* busy: the union of a chip's operation intervals inside the window;
  idle share is 1 - busy / window;
* kernel time: the summed durations of each kernel's launches, by the
  launch's name;
* the breakdown: device operations by total time, and the window's idle
  time split by what the host was doing meanwhile.

A trace saved with :func:`save` and read with :func:`read` round-trips,
which is how a recorded trace is kept small enough for a test.
"""
from __future__ import annotations

import collections
import glob
import gzip
import json
import os
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

#: The line of a device plane that holds one event per operation run.
DEVICE_OPS_LINE = "XLA Ops"
#: A device gap shorter than this is a bubble between two operations of
#: one program, not time the host kept the chip waiting.
OP_BUBBLE_NS = 10_000.0


@dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float
    stats: Dict[str, object] = field(default_factory=dict)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Trace:
    """Device operations per chip, and host events with their thread."""
    device_ops: Dict[str, List[Event]]
    host: List[Event]

    def chips(self) -> List[str]:
        return sorted(self.device_ops)


def capture_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # no per-call Python events
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    return opts


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _stats(ev) -> Dict[str, object]:
    out = {}
    for k, v in ev.stats:
        out[k] = v if isinstance(v, (int, float, str)) else str(v)
    return out


def load(path: str) -> Trace:
    """Read an ``.xplane.pb``: device planes are ``/device:<kind>:<n>``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_ops: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:") and \
                not plane.name.startswith("/device:CUSTOM"):
            for line in plane.lines:
                if line.name == DEVICE_OPS_LINE:
                    chip = plane.name[len("/device:"):]
                    device_ops[chip] = [
                        Event(e.name, float(e.start_ns), float(e.duration_ns),
                              _stats(e)) for e in line.events]
        elif plane.name.startswith("/host:") and plane.name != "/host:metadata":
            for line in plane.lines:
                for e in line.events:
                    host.append(Event(e.name, float(e.start_ns),
                                      float(e.duration_ns),
                                      {"thread": line.name}))
    host.sort(key=lambda e: e.start_ns)
    for ops in device_ops.values():
        ops.sort(key=lambda e: e.start_ns)
    return Trace(device_ops, host)


def save(trace: Trace, path: str) -> None:
    """Write a trace as gzipped JSON (what :func:`read` reads)."""
    def ev(e: Event):
        return [e.name, e.start_ns, e.dur_ns, e.stats]
    doc = {"device_ops": {c: [ev(e) for e in evs]
                          for c, evs in trace.device_ops.items()},
           "host": [ev(e) for e in trace.host]}
    with gzip.open(path, "wt") as f:
        json.dump(doc, f, separators=(",", ":"))


def read(path: str) -> Trace:
    with gzip.open(path, "rt") as f:
        doc = json.load(f)
    return Trace({c: [Event(*e) for e in evs]
                  for c, evs in doc["device_ops"].items()},
                 [Event(*e) for e in doc["host"]])


def annotation(trace: Trace, name: str) -> Event:
    """The one host event called ``name`` (the benchmark's window)."""
    hits = [e for e in trace.host if e.name == name]
    if len(hits) != 1:
        raise ValueError(f"expected one host event {name!r}, found {len(hits)}")
    return hits[0]


# -- reduction ---------------------------------------------------------------

def merge(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Union of intervals, as sorted disjoint intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Iterable[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def gaps(busy: Sequence[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """The idle intervals of [lo, hi] around disjoint sorted ``busy``."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out


def op_name(ev: Event) -> str:
    """An operation's HLO instruction name without its number: the event
    ``%fusion.12 = bf16[...] fusion(...)`` is a ``fusion``."""
    head = ev.name.split(" = ", 1)[0].lstrip("%")
    base, dot, num = head.rpartition(".")
    return base if dot and num.isdigit() else head


def launch_of(ev: Event) -> Optional[str]:
    """A kernel launch's name (the ``tpu_custom_call`` operation's
    instruction name, which the program's jitted kernel wrapper gives
    it), or None for any other operation."""
    if 'custom_call_target="tpu_custom_call"' not in ev.name:
        return None
    return op_name(ev)


@dataclass
class Reduced:
    window_s: float
    busy_s: Dict[str, float]              # per chip
    launch_s: Dict[str, float]            # per kernel launch name, all chips
    launches: Dict[str, int]
    top_ops: List[Tuple[str, float]]      # by op name, summed over chips
    idle_by_host: List[Tuple[str, float]]  # idle seconds by host activity

    @property
    def mean_busy_s(self) -> float:
        if not self.busy_s:
            raise ValueError("the trace holds no device operations")
        return sum(self.busy_s.values()) / len(self.busy_s)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.mean_busy_s / self.window_s


#: What the host may be doing while a chip waits, most specific first: a
#: stretch of a gap goes to the first group with an event covering it.
#: The runtime's events (copying the input to the device, the result back,
#: launching the program) come before the program's spans, and those
#: before the client's annotations.  A dispatch in progress outranks a
#: bucket waiting in the batcher: while the chip's dispatch thread is
#: busy, the chip waits on that thread, not on the batcher.
HOST_GROUPS: Tuple[Tuple[str, Tuple[str, ...]], ...] = (
    ("python gc", ("PythonRefManager::CollectGarbage",)),
    ("host->device copy", ("XlaLinearize", "Linearize", "H2D Dispatch",
                           "tpu::System::TransferToDevice",
                           "DevicePutWithSharding", "shard_args")),
    ("device->host copy", ("XlaDelinearize", "Delinearize",
                           "DelinearizeUsingTranspose", "D2H Dispatch",
                           "tpu::System::TransferFromDevice",
                           "CommonPjRtBuffer::ToLiteral",
                           "np.asarray(jax.Array)")),
    ("launch", ("PJRT_LoadedExecutable_Execute",
                "CommonPjRtLoadedExecutable::Execute",
                "CommonPjRtLoadedExecutable::ExecutePrepare",
                "CommonPjRtLoadedExecutable::ExecuteHelperOnSingleDevice")),
    ("serve.dispatch, other host work", ("serve.dispatch",)),
    ("serve.batch_wait", ("serve.batch_wait",)),
    ("client", ("bench.submit", "bench.wait")),
)
_GROUP_OF = {name: (rank, label)
             for rank, (label, names) in enumerate(HOST_GROUPS)
             for name in names}


def attribute_gaps(idle: Sequence[Tuple[float, float]],
                   host: Sequence[Event]) -> Dict[str, float]:
    """Idle nanoseconds by what the host was doing: each stretch of a gap
    goes to the most specific group of :data:`HOST_GROUPS` with an event
    covering it; gaps under :data:`OP_BUBBLE_NS` are bubbles between
    operations; what no event covers is ``unattributed``."""
    import bisect

    tagged = sorted(((e.start_ns, e.end_ns) + _GROUP_OF[e.name]
                     for e in host if e.name in _GROUP_OF and e.dur_ns > 0))
    starts = [t[0] for t in tagged]
    longest = max((t[1] - t[0] for t in tagged), default=0.0)
    out: Dict[str, float] = collections.defaultdict(float)
    for a, b in idle:
        if b - a < OP_BUBBLE_NS:
            out["bubble between ops"] += b - a
            continue
        lo = bisect.bisect_left(starts, a - longest)
        hi = bisect.bisect_right(starts, b)
        cover = [t for t in tagged[lo:hi] if t[1] > a]
        cuts = sorted({a, b} | {min(max(t[0], a), b) for t in cover}
                      | {min(max(t[1], a), b) for t in cover})
        for p, q in zip(cuts, cuts[1:]):
            mid = (p + q) / 2
            ranks = [(t[2], t[3]) for t in cover if t[0] <= mid < t[1]]
            out[min(ranks)[1] if ranks else "unattributed"] += q - p
    return dict(out)


def reduce(trace: Trace, lo_ns: float, hi_ns: float,
           extra_host: Sequence[Event] = ()) -> Reduced:
    """Reduce the part of ``trace`` inside [lo_ns, hi_ns].

    ``extra_host`` adds host intervals the profiler did not record (the
    program's own spans, moved onto the profiler's clock); one whose
    stats name a ``chip`` is charged only to that chip's gaps."""
    busy: Dict[str, float] = {}
    launch_ns: Dict[str, float] = collections.defaultdict(float)
    launches: Dict[str, int] = collections.defaultdict(int)
    by_cat: Dict[str, float] = collections.defaultdict(float)
    idle_ns: Dict[str, float] = collections.defaultdict(float)
    host = sorted(list(trace.host) + list(extra_host),
                  key=lambda e: e.start_ns)
    for chip, ops in trace.device_ops.items():
        # The program's spans name the chip they ran for.
        chip_host = [e for e in host if e.stats.get("chip", chip) == chip]
        inside = [e for e in ops if e.end_ns > lo_ns and e.start_ns < hi_ns]
        merged = merge(clip(((e.start_ns, e.end_ns) for e in inside),
                            lo_ns, hi_ns))
        busy[chip] = sum(b - a for a, b in merged) / 1e9
        for e in inside:
            dur = min(e.end_ns, hi_ns) - max(e.start_ns, lo_ns)
            by_cat[op_name(e)] += dur
            k = launch_of(e)
            if k is not None:
                launch_ns[k] += dur
                launches[k] += 1
        for label, ns in attribute_gaps(gaps(merged, lo_ns, hi_ns),
                                        chip_host).items():
            idle_ns[label] += ns
    top = sorted(by_cat.items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(idle_ns.items(), key=lambda kv: -kv[1])[:10]
    return Reduced(window_s=(hi_ns - lo_ns) / 1e9, busy_s=busy,
                   launch_s={k: v / 1e9 for k, v in launch_ns.items()},
                   launches=dict(launches),
                   top_ops=[(k, v / 1e9) for k, v in top],
                   idle_by_host=[(k, v / 1e9) for k, v in idle])
