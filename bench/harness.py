"""One run of one cell: set up, drive the window, measure, compare.

The cell's configuration, traffic mix and metrics are read from
``BENCHMARK.json`` and the files it names; whatever belongs to one
configuration, one traffic kind or one metric sits in a file of its own
and is found by name:

* ``bench/configs/<config>.json`` -- the network, its sizes, compute mode,
  weights seed and serving settings;
* ``bench/traffic/<traffic>.json`` -- a traffic mix, whose ``kind`` names
  a generator ``bench/gen/<kind>.py``;
* ``bench/metrics/<metric>.py`` -- one reader per metric, end-to-end and
  per-layer alike, each a ``read(ctx)`` returning a number or None.

The program under ``src/`` is the system under test: its network
builders, ``synthesize``, ``ServingConfig``, ``ReplicaSet`` and the
spans and counters the tier exposes.  Nothing else of it is used.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import math
import json
import os
import sys
import tempfile
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import numpy as np

from . import check, flops, peaks, reference, trace, weights

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
#: JAX's persistent compilation cache: a fixed directory of the checkout,
#: so every run of a cell after the first finds its programs there.
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
#: Events JAX records when it traces or compiles a program; none may
#: happen inside the measured window.
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_hits")
REFERENCE_BLOCK = 32


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell needs."""


@dataclass
class Cell:
    workload: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """Import one file as a module (names may hold ``.`` and ``-``)."""
    name = "bench_" + os.path.relpath(path, BENCH_DIR).replace(os.sep, "_") \
        .replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str, root: str = ROOT) -> Cell:
    """The cell named ``workload`` in ``root/BENCHMARK.json``."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return Cell(workload=workload, chips=int(w["chips"]),
                config=_load_json(os.path.join(root, cfg_entry["file"])),
                traffic=_load_json(os.path.join(
                    BENCH_DIR, "traffic", w["traffic"] + ".json")),
                end_to_end=e2e, per_layer=per_layer)


class CompileCounter:
    """Counts JAX's trace and compile events while ``active``.

    JAX's listeners are process-wide and cannot be removed, so one counter
    is registered per process (:func:`compile_counter`)."""

    def __init__(self):
        import jax

        self.active = False
        self.count = 0

        def listener(event: str, *args, **kw) -> None:
            if self.active and event in COMPILE_EVENTS:
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(listener)
        jax.monitoring.register_event_listener(listener)


_COUNTER: List[CompileCounter] = []


def compile_counter() -> CompileCounter:
    if not _COUNTER:
        _COUNTER.append(CompileCounter())
    return _COUNTER[0]


def configure_jax() -> None:
    """Every program of a run goes to the checkout's compilation cache,
    however quick its compile and however large its entry (an AlexNet
    bucket executable, weights baked in, serializes to ~290 MB), so the
    second run of a cell compiles nothing."""
    import jax

    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def pallas_groups(program) -> Dict[str, List[str]]:
    """Anchor layer kind -> the fused groups the plan routed to Pallas."""
    from repro.core.plan import IMPL_PALLAS

    out: Dict[str, List[str]] = {}
    for g in program.plan.graph.groups:
        if program.plan.for_layer(g.name).impl == IMPL_PALLAS:
            out.setdefault(g.anchor.kind, []).append(g.name)
    return out


def _stats_of(tier) -> Dict[str, Any]:
    """The tier's dispatch counters, summed over replicas."""
    agg = {"batches": 0, "padded_slots": 0, "completed": 0,
           "bucket_counts": {}}
    for r in tier.replicas:
        s = r.server.stats
        for _ in range(100):           # a dispatch thread may add a bucket
            try:
                counts = dict(s.bucket_counts)
                break
            except RuntimeError:
                continue
        agg["batches"] += s.batches
        agg["padded_slots"] += s.padded_slots
        agg["completed"] += s.completed
        for b, n in counts.items():
            agg["bucket_counts"][b] = agg["bucket_counts"].get(b, 0) + n
    return agg


def _delta(a: Dict[str, Any], b: Dict[str, Any]) -> Dict[str, Any]:
    counts = {k: v - a["bucket_counts"].get(k, 0)
              for k, v in b["bucket_counts"].items()}
    counts = {k: v for k, v in counts.items() if v}
    slots = sum(k * v for k, v in counts.items())
    padded = b["padded_slots"] - a["padded_slots"]
    return {"batches": b["batches"] - a["batches"], "padded_slots": padded,
            "dispatched_slots": slots, "real_rows": slots - padded,
            "bucket_counts": counts,
            "completed": b["completed"] - a["completed"]}


@dataclass
class Context:
    """What a metric reader may read about one run."""
    workload: str
    chips: int
    net: Any
    mode: str
    seconds: float                        # window length, host clock
    setup_s: float
    requests: List[Any]                   # every request sent
    t_open: float
    t_close: float
    stats: Dict[str, Any]                 # tier counters over the window
    pallas_groups: Dict[str, List[str]]   # anchor kind -> groups
    peaks: Optional[peaks.Peaks]
    spans: Optional[List[Any]] = None     # program spans in the window
    reduced: Optional[trace.Reduced] = None

    @property
    def in_window(self) -> List[Any]:
        """Requests sent inside the window."""
        return [s for s in self.requests
                if self.t_open <= s.t_send < self.t_close]

    def latencies_ms(self) -> List[float]:
        """Send to result of each request sent in the window; a failed
        request counts as missing (infinitely late)."""
        return [(s.t_done - s.t_send) * 1e3 if s.ok else float("inf")
                for s in self.in_window]

    def spans_named(self, name: str) -> List[Any]:
        return [s for s in (self.spans or []) if s.name == name]

    @property
    def operand(self) -> str:
        return "int8" if self.mode == "imprecise_int8" else "bf16"

    def group_cost(self, group: str, batch: int) -> flops.Cost:
        width = 1 if self.operand == "int8" else 2
        return flops.layer_cost(self.net, group, batch, operand_bytes=width,
                                out_bytes=2)


def warm(tier, program, buckets: List[int]) -> None:
    """Load (or compile) and run twice every bucket executable the traffic
    can release, on every replica's chip, so nothing compiles or warms up
    inside the window."""
    import jax

    for r in tier.replicas:
        for b in buckets:
            exe = tier.cache.get_or_build(program, b, r.device)
            x = jax.device_put(
                np.zeros((b, *program.net.input_shape), np.float32), r.device)
            for _ in range(2):
                jax.block_until_ready(exe(x))


def read_metrics(entries: List[Dict[str, Any]], ctx: Context
                 ) -> Dict[str, Dict[str, Any]]:
    """Each metric from its own reader, ``bench/metrics/<name>.py``; a
    reader that finds nothing to read leaves its metric out."""
    out: Dict[str, Dict[str, Any]] = {}
    for m in entries:
        reader = load_module(os.path.join(BENCH_DIR, "metrics",
                                          m["name"] + ".py"))
        value = reader.read(ctx)
        if value is not None and math.isfinite(value):
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool, *,
             t_process: float, require_tpu: bool = True,
             trace_dir: Optional[str] = None) -> Dict[str, Any]:
    """One run: ``seconds`` of the cell's traffic after a warm set-up.
    Returns the result line; its last key, ``checks``, holds each number
    the correctness comparison read beside its limit."""
    import jax
    import jax.numpy as jnp

    configure_jax()
    counter = compile_counter()
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU: its first device is "
                     f"{devices[0].platform} ({devices[0].device_kind})")
    if len(devices) < cell.chips:
        raise NoChip(f"the cell needs {cell.chips} chips, JAX finds "
                     f"{len(devices)}")
    used = devices[:cell.chips]
    kind = used[0].device_kind
    chip_peaks = peaks.peaks_for(kind) if used[0].platform == "tpu" else None

    from repro.cnn import WORKLOADS
    from repro.core import synthesize
    from repro.core.precision import ComputeMode
    from repro.obs import MetricsRegistry, Tracer
    from repro.serving import ReplicaSet, ServingConfig

    cfg = cell.config
    net = WORKLOADS[cfg["network"]](**cfg["net"])
    params = weights.make(net, int(cfg["weights_seed"]))
    mode = ComputeMode(cfg["mode"])
    extra = {}
    if mode.quantizes_weights:
        # The int8 path calibrates its activation scales on images of the
        # model file's own (seeded like the weights, not like the traffic).
        calib = np.random.default_rng(int(cfg["weights_seed"])) \
            .standard_normal((REFERENCE_BLOCK, *net.input_shape),
                             dtype=np.float32)
        extra["autotune_input"] = jnp.asarray(calib)
    program = synthesize(net, params, forced_mode=mode, **extra)
    groups = pallas_groups(program)

    seed %= 1 << 64                    # numpy seeds are non-negative
    traffic = cell.traffic
    gen = load_module(os.path.join(BENCH_DIR, "gen", traffic["kind"] + ".py"))
    pool = np.random.default_rng([seed, 0]).standard_normal(
        (int(traffic["pool"]), *net.input_shape), dtype=np.float32)
    order = np.random.default_rng([seed, 1])

    scfg = ServingConfig(replicas=cell.chips, **cfg["serving"])
    registry = MetricsRegistry()
    tracer = Tracer(clock=time.perf_counter) if traced else None
    tier = ReplicaSet(program, config=scfg, registry=registry, tracer=tracer)
    warm(tier, program, gen.buckets(traffic, scfg.max_batch, cell.chips))

    state: Dict[str, Any] = {}
    log_dir = trace_dir or (tempfile.mkdtemp(prefix="bench_trace_")
                            if traced else None)

    def on_ramped() -> None:
        if traced:
            jax.profiler.start_trace(log_dir,
                                     profiler_options=trace.capture_options())

    def on_open() -> float:
        state["s0"] = _stats_of(tier)
        state["compiles0"] = tier.cache.stats.stage_d_compiles
        state["events0"] = counter.count
        counter.active = True
        if traced:
            state["ann"] = jax.profiler.TraceAnnotation("bench.window")
            state["ann"].__enter__()
        return time.perf_counter()

    def on_close() -> float:
        t = time.perf_counter()
        if traced:
            state["ann"].__exit__(None, None, None)
        counter.active = False
        state["s1"] = _stats_of(tier)
        state["compiles1"] = tier.cache.stats.stage_d_compiles
        return t

    def annotate(name: str):
        return jax.profiler.TraceAnnotation(name)

    # Trace planes are named /device:TPU:<id>; spans carry their replica.
    chip_of = {str(r.index): f"{r.device.platform.upper()}:{r.device.id}"
               for r in tier.replicas}
    tier.start()
    try:
        sent, t_open, t_close = gen.run(
            tier.submit, pool, traffic, order, seconds,
            on_ramped=on_ramped, on_open=on_open, on_close=on_close,
            annotate=annotate if traced else
            (lambda name: contextlib.nullcontext()),
            late_s=check.LATE_S)
    finally:
        tier.stop()
        if traced:
            jax.profiler.stop_trace()
    setup_s = t_open - t_process

    memory_peak = 0
    for d in used:
        ms = d.memory_stats() or {}
        memory_peak = max(memory_peak, int(ms.get("peak_bytes_in_use", 0)))

    ctx = Context(workload=cell.workload, chips=cell.chips, net=net,
                  mode=cfg["mode"], seconds=t_close - t_open,
                  setup_s=setup_s, requests=sent, t_open=t_open,
                  t_close=t_close,
                  stats=_delta(state["s0"], state["s1"]),
                  pallas_groups=groups, peaks=chip_peaks)
    window_compiles = (counter.count - state["events0"]
                       + state["compiles1"] - state["compiles0"])

    device: Dict[str, Any] = {"platform": used[0].platform, "kind": kind,
                              "count": len(used),
                              "memory_peak_bytes": memory_peak}
    breakdown = None
    if traced:
        ctx.spans = [s for s in tracer.finished()
                     if t_open <= s.t_start < t_close]
        tr = trace.load(trace.find_xplane(log_dir))
        win = trace.annotation(tr, "bench.window")
        offset = win.start_ns - t_open * 1e9
        moved = [trace.Event(s.name, s.t_start * 1e9 + offset,
                             (s.t_end - s.t_start) * 1e9,
                             {"chip": chip_of[str(s.attrs["replica"])]}
                             if "replica" in s.attrs else {})
                 for s in tracer.finished()]
        ctx.reduced = trace.reduce(tr, win.start_ns, win.end_ns, moved)
        if tr.device_ops or require_tpu:
            device["busy_s"] = ctx.reduced.mean_busy_s
        device["window_s"] = ctx.reduced.window_s
        breakdown = {"device_ops": [[k, v] for k, v in ctx.reduced.top_ops],
                     "idle_gaps": [[k, v] for k, v in
                                   ctx.reduced.idle_by_host]}
        if trace_dir is None:
            import shutil
            shutil.rmtree(log_dir, ignore_errors=True)

    metrics = read_metrics(cell.per_layer if traced else cell.end_to_end,
                           ctx)

    # -- correctness: every request sent in the window --------------------
    due = ctx.in_window
    attempted = len(due)
    failed = [s for s in due if not s.ok]
    done = [s for s in due if s.ok]
    if len(done) > check.MAX_COMPARED:
        pick = np.random.default_rng([seed, 2]).choice(
            len(done), check.MAX_COMPARED, replace=False)
        done = [done[i] for i in sorted(pick)]
    outputs = np.stack([np.asarray(s.future.result(0)) for s in done]) \
        if done else np.zeros((0, 1))
    pool_idx = np.array([s.pool_idx for s in done], np.int64)
    foreign = 0
    for r in tier.replicas:
        own = str(r.device)
        foreign += sum(n for where, n in r.server.stats.output_devices.items()
                       if where != own)
    # Free the program's state before the reference runs on the chip.
    del tier, program, sent, due, done, ctx
    gc.collect()
    uniq = np.unique(pool_idx)
    ref_fn = reference.make(net, params, REFERENCE_BLOCK)
    ref_rows = dict(zip(uniq.tolist(), ref_fn(pool[uniq]))) if len(uniq) \
        else {}
    checks = {
        "row_err_max": {"value": check.compare(outputs, pool_idx, ref_rows),
                        "limit": check.ROW_ERR_LIMIT},
        "failed_requests": {"value": len(failed), "limit": 0},
        "window_compiles": {"value": window_compiles, "limit": 0},
        "foreign_rows": {"value": foreign, "limit": 0},
    }
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    for c in checks.values():          # JSON has no infinity
        if not math.isfinite(c["value"]):
            c["value"] = float.fromhex("0x1.fffffffffffffp+1023")
    line: Dict[str, Any] = {"correct": correct,
                            "attempted": attempted,
                            "failed": len(failed), "metrics": metrics,
                            "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    return line
