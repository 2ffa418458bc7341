"""Serving benchmark: sustained throughput + scaling vs replica count.

Single-shot latency (tables 1-3) and sustained-load behavior diverge on
real systems — this suite measures the latter: it synthesizes a CNN once,
then drives the data-parallel :class:`~repro.serving.ReplicaSet` through
:func:`repro.serving.run_offered_load` (open-loop arrivals, every replica's
batch buckets pre-warmed so no XLA compile lands in the measured window)
at each replica count from 1 to ``--replicas``, and reports sustained
img/s per count, the scaling efficiency of the widest tier
(``sustained_N / (N * sustained_1)``), shed/stolen request counts, and
per-replica cold-start (warm-up) seconds.  Output is a schema-validated
``BENCH_serving.json`` (benchmarks/bench_schema.py) that CI uploads as
the perf-trajectory artifact.

  PYTHONPATH=src python -m benchmarks.serving_throughput --replicas 2 --dry-run
  PYTHONPATH=src python -m benchmarks.serving_throughput \
      --net squeezenet --requests 256 --rate 100 --max-batch 8 --replicas 4
"""
from __future__ import annotations

import argparse
from typing import Dict

import jax

from repro.cnn import WORKLOADS, init_network_params
from repro.core import ComputeMode, synthesize
from repro.obs import (MetricsRegistry, Tracer, measure_drift, render_table,
                       write_metrics_json)
from repro.serving import DISPATCH_POLICIES, ServingConfig, run_offered_load
from repro.launch.compile_cache import enable_compile_cache

from .bench_schema import SCHEMA_VERSION, write_bench


def run(net_name: str = "squeezenet", *, scale: float = 0.08,
        input_hw: int = 64, num_classes: int = 10, requests: int = 128,
        rate: float = 0.0, max_batch: int = 8, max_delay_ms: float = 2.0,
        replicas: int = 2, dispatch: str = "least_loaded",
        max_queue_depth: int = 64,
        mode: ComputeMode = ComputeMode.RELAXED, seed: int = 0,
        drift_reps: int = 2) -> Dict:
    """Run the offered-load experiment at 1..replicas and return the
    BENCH document.  ``doc["obs"]`` carries the widest tier's
    :class:`~repro.obs.MetricsRegistry`, :class:`~repro.obs.Tracer`, and
    :class:`~repro.obs.DriftReport` (stripped before ``write_bench``)."""
    net = WORKLOADS[net_name](scale=scale, num_classes=num_classes,
                              input_hw=input_hw)
    params = init_network_params(net, jax.random.PRNGKey(seed))
    # One registry/tracer covers synthesis, the *widest* serving tier run
    # (the headline), and the drift probe; the narrower warm-up tiers get
    # their own registries so their series don't sum into the headline's.
    registry = MetricsRegistry()
    tracer = Tracer(clock=registry.clock)
    program = synthesize(net, params, forced_mode=mode,
                         registry=registry, tracer=tracer)

    config = ServingConfig(max_batch=max_batch,
                           max_delay_s=max_delay_ms / 1e3,
                           dispatch=dispatch,
                           max_queue_depth=max_queue_depth)
    reports = {}
    for r in range(1, replicas + 1):
        headline = r == replicas
        reports[r] = run_offered_load(
            program, requests=requests, rate=rate,
            config=config.with_replicas(r), seed=seed,
            registry=registry if headline else None,
            tracer=tracer if headline else None)

    drift = measure_drift(program, batch=max_batch, reps=drift_reps,
                          registry=registry, tracer=tracer)

    top = reports[replicas]                  # the widest tier is the headline
    base = reports[1]
    scaling_efficiency = (
        top.sustained_per_s / (replicas * base.sustained_per_s)
        if replicas > 1 else 1.0)

    cache, srv, tier = top.cache_stats, top.server_stats, top.tier_stats
    rows = [{"name": f"sustained_replicas_{r}",
             "value": rep.sustained_per_s} for r, rep in reports.items()]
    rows += [{"name": f"replica_{i}_warm_seconds", "value": s}
             for i, s in enumerate(top.warm_seconds)]
    rows += [{"name": f"bucket_{b}_batches", "value": n}
             for b, n in sorted(top.bucket_counts.items())]
    rows += [{"name": f"drift_{g.group}_error_pct", "value": g.error_pct}
             for g in drift.groups]
    return {
        "benchmark": "serving_throughput",
        "schema_version": SCHEMA_VERSION,
        "config": {
            "net": net.name, "scale": scale, "input_hw": input_hw,
            "requests": requests, "offered_rate_rps": rate,
            "max_batch": max_batch, "max_delay_ms": max_delay_ms,
            "replicas": replicas, "dispatch": dispatch,
            "max_queue_depth": max_queue_depth,
            "mode": mode.value, "backend": jax.default_backend(),
            "program_fingerprint": program.fingerprint(),
        },
        "metrics": {
            "sustained_imgs_per_s": top.sustained_per_s,
            "sustained_imgs_per_s_1r": base.sustained_per_s,
            "scaling_efficiency": scaling_efficiency,
            "replica_count": top.replica_count,
            "shed_requests": top.shed_requests,
            "stolen_requests": tier["stolen_requests"],
            "peak_queue_depth": tier["peak_depth"],
            "latency_p50_ms": top.latency_ms(50),
            "latency_p95_ms": top.latency_ms(95),
            "latency_p99_ms": top.latency_ms(99),
            "latency_mean_ms": top.latency_mean_ms,
            "latency_max_ms": top.latencies_ms[-1],
            "wall_seconds": top.wall_seconds,
            "batches": srv["batches"],
            "padding_fraction": srv["padding_fraction"],
            "stage_d_compiles": cache["stage_d_compiles"],
            "stage_d_seconds": cache["stage_d_seconds"],
            "cache_hit_rate": cache["hit_rate"],
            "warm_seconds_total": sum(top.warm_seconds),
            "synthesis_seconds": program.synthesis_seconds,
            "drift_mean_abs_error_pct": drift.mean_abs_error_pct,
            "drift_groups": len(drift.groups),
        },
        "rows": rows,
        "obs": {"registry": registry, "tracer": tracer, "drift": drift},
    }


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", "--dry-run", dest="smoke", action="store_true",
                    help="tiny fast configuration for CI")
    ap.add_argument("--net", default="squeezenet", choices=sorted(WORKLOADS))
    ap.add_argument("--scale", type=float, default=0.08)
    ap.add_argument("--input-hw", type=int, default=64)
    ap.add_argument("--requests", type=int, default=128)
    ap.add_argument("--rate", type=float, default=0.0)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-delay-ms", type=float, default=2.0)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--dispatch", default="least_loaded",
                    choices=sorted(DISPATCH_POLICIES))
    ap.add_argument("--max-queue-depth", type=int, default=64)
    ap.add_argument("--mode", default="relaxed",
                    choices=[m.value for m in ComputeMode])
    ap.add_argument("--out", default="BENCH_serving.json")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the tier's JSON metrics snapshot here")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the tier's trace spans as JSONL here")
    args = ap.parse_args()

    if args.smoke:
        args.requests = min(args.requests, 48)
        args.max_batch = min(args.max_batch, 4)

    doc = run(args.net, scale=args.scale, input_hw=args.input_hw,
              requests=args.requests, rate=args.rate,
              max_batch=args.max_batch, max_delay_ms=args.max_delay_ms,
              replicas=args.replicas, dispatch=args.dispatch,
              max_queue_depth=args.max_queue_depth,
              mode=ComputeMode(args.mode))
    obs = doc.pop("obs")
    write_bench(args.out, doc)
    m = doc["metrics"]
    print(f"wrote {args.out}: {m['sustained_imgs_per_s']:.1f} img/s at "
          f"{m['replica_count']:.0f} replicas "
          f"({m['sustained_imgs_per_s_1r']:.1f} img/s at 1, scaling "
          f"efficiency {m['scaling_efficiency']:.2f}), "
          f"p50 {m['latency_p50_ms']:.2f} ms, p95 {m['latency_p95_ms']:.2f} ms,"
          f" {m['shed_requests']:.0f} shed,"
          f" {m['stage_d_compiles']:.0f} Stage-D compiles")
    print("\nmetrics snapshot (widest tier):")
    print(render_table(obs["registry"]))
    print("\ncost-model drift (predicted vs measured per group):")
    print(obs["drift"].table())
    if args.metrics_out:
        write_metrics_json(args.metrics_out, obs["registry"],
                           meta={"benchmark": "serving_throughput",
                                 "net": args.net, "replicas": args.replicas})
        print(f"\nmetrics snapshot -> {args.metrics_out}")
    if args.trace_out:
        obs["tracer"].export_jsonl(args.trace_out)
        print(f"trace spans -> {args.trace_out}")


if __name__ == "__main__":
    main()
