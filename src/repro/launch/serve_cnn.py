"""CNN serving launcher: synthesize once, serve a stream of single images.

  PYTHONPATH=src python -m repro.launch.serve_cnn --net squeezenet \
      --scale 0.08 --input-hw 64 --requests 64 --max-batch 8 \
      --max-delay-ms 2 --rate 200 --replicas 2 --dispatch least_loaded

Synthesizes the network (Stages A–C once), builds a
:class:`~repro.serving.ServingConfig` from the flags, and drives the
data-parallel :class:`~repro.serving.ReplicaSet` with an open-loop stream
of ``--requests`` single images at ``--rate`` req/s (0 = back-to-back)
via :func:`repro.serving.run_offered_load`.  Prints sustained throughput,
latency percentiles, per-replica warm-up (cold start) times, shed count,
and a metrics snapshot rendered from the tier's registry
(``repro.obs``).  ``--metrics-out``/``--trace-out`` export the snapshot
(JSON) and the trace spans (JSONL) for offline analysis.

``--profile-out DIR`` captures a JAX profiler trace of the serving run
(``DIR/plugins/profile/<time>/*.xplane.pb``, for TensorBoard, Perfetto or
``jax.profiler.ProfileData``) with the tracer's spans mirrored into it
through ``jax.profiler.TraceAnnotation``: each ``serve.dispatch`` and its
``serve.dispatch.<phase>`` children sit on the line of the thread that ran
them (a pipelined bucket's on the serving thread's and its completer's),
on the same clock as the device's operations.

``--artifact-dir PATH`` attaches a persistent
:class:`~repro.artifacts.ArtifactStore` (DESIGN.md §13): the first launch
synthesizes and compiles cold while persisting every artifact; subsequent
launches against the same directory hydrate the converged program (zero
synthesis iterations) and the serialized Stage-D executables (zero
compiles) — the banner reports how many compiles the warm start avoided,
and the ``artifact_*`` hit/miss/hydrate counters appear in the snapshot
table alongside the cache series.
"""
from __future__ import annotations

import argparse
import contextlib
from typing import Optional, Tuple

import jax

from repro.cnn import WORKLOADS, init_network_params
from repro.core import ComputeMode, NetworkDescription, synthesize
from repro.core.synthesizer import SynthesizedProgram
from repro.launch.compile_cache import enable_compile_cache
from repro.obs import MetricsRegistry, Tracer, render_table, write_metrics_json
from repro.serving import (DISPATCH_POLICIES, LoadReport, ServingConfig,
                           run_offered_load)


def serve(net: NetworkDescription, params, *, mode: ComputeMode,
          config: ServingConfig, requests: int, rate: float = 0.0,
          seed: int = 0, registry: Optional[MetricsRegistry] = None,
          tracer: Optional[Tracer] = None, store=None,
          profile_dir: Optional[str] = None
          ) -> Tuple[SynthesizedProgram, LoadReport]:
    """Synthesize ``net`` once with every layer pinned to ``mode``, then
    drive the replica tier with ``requests`` single images at ``rate``
    req/s (0 = back-to-back).  ``store`` is an optional
    :class:`~repro.artifacts.ArtifactStore` to hydrate the program from;
    ``profile_dir`` captures a profiler trace of the serving run there."""
    program = synthesize(net, params, forced_mode=mode, registry=registry,
                         tracer=tracer, artifact_store=store)
    with (jax.profiler.trace(profile_dir) if profile_dir
          else contextlib.nullcontext()):
        report = run_offered_load(program, requests=requests, rate=rate,
                                  config=config, seed=seed,
                                  registry=registry, tracer=tracer)
    return program, report


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--net", default="squeezenet", choices=sorted(WORKLOADS))
    ap.add_argument("--scale", type=float, default=0.08)
    ap.add_argument("--input-hw", type=int, default=64)
    ap.add_argument("--classes", type=int, default=10)
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--rate", type=float, default=0.0,
                    help="offered load in req/s; 0 = back-to-back")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-delay-ms", type=float, default=2.0)
    ap.add_argument("--replicas", type=int, default=1,
                    help="data-parallel replica count")
    ap.add_argument("--dispatch", default="least_loaded",
                    choices=sorted(DISPATCH_POLICIES))
    ap.add_argument("--max-queue-depth", type=int, default=64,
                    help="per-replica admission bound; 0 = unbounded")
    ap.add_argument("--mode", default="relaxed",
                    choices=[m.value for m in ComputeMode])
    ap.add_argument("--artifact-dir", default=None, metavar="PATH",
                    help="persistent artifact store: synthesize/compile "
                         "cold once, start warm forever after")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write a JSON metrics snapshot here")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write trace spans as JSONL here")
    ap.add_argument("--profile-out", default=None, metavar="DIR",
                    help="capture a profiler trace of the serving run "
                         "here, with the trace spans mirrored into it")
    args = ap.parse_args()

    net = WORKLOADS[args.net](scale=args.scale, num_classes=args.classes,
                              input_hw=args.input_hw)
    params = init_network_params(net, jax.random.PRNGKey(args.seed))
    print(f"synthesizing {net.name} ({len(net.layers)} layers)...")
    registry = MetricsRegistry()
    tracer = Tracer(clock=registry.clock,
                    annotate=jax.profiler.TraceAnnotation
                    if args.profile_out else None)
    store = None
    if args.artifact_dir:
        from repro.artifacts import ArtifactStore
        store = ArtifactStore(args.artifact_dir, registry=registry,
                              tracer=tracer)
    config = ServingConfig(max_batch=args.max_batch,
                           max_delay_s=args.max_delay_ms / 1e3,
                           replicas=args.replicas,
                           dispatch=args.dispatch,
                           max_queue_depth=args.max_queue_depth,
                           artifact_dir=args.artifact_dir)
    program, report = serve(net, params, mode=ComputeMode(args.mode),
                            config=config, requests=args.requests,
                            rate=args.rate, seed=args.seed,
                            registry=registry, tracer=tracer, store=store,
                            profile_dir=args.profile_out)
    if store is not None and store.hits:
        print(f"  program hydrated from {args.artifact_dir} "
              "(zero synthesis iterations), "
              f"program {program.fingerprint()}")
    else:
        print(f"  stages A-C in {program.synthesis_seconds:.2f}s, "
              f"program {program.fingerprint()}")

    srv, tier = report.server_stats, report.tier_stats
    print(f"served {report.admitted}/{report.requests} requests "
          f"({report.shed_requests} shed) across {report.replica_count} "
          f"replica(s) in {report.wall_seconds:.3f}s "
          f"({report.sustained_per_s:.1f} img/s sustained)")
    print(f"latency ms: p50 {report.latency_ms(50):.2f}  "
          f"p95 {report.latency_ms(95):.2f}  max {report.latencies_ms[-1]:.2f}")
    print(f"batches: {srv['batches']}  buckets {srv['bucket_counts']}  "
          f"padding {srv['padding_fraction']:.1%}  "
          f"stolen {tier['stolen_requests']}  peak depth {tier['peak_depth']}")
    warm = ", ".join(f"r{i}={s:.2f}s" for i, s in enumerate(report.warm_seconds))
    print(f"cold start (warm-up): {warm}")
    if args.artifact_dir:
        hits = report.registry.get("artifact_hits_total")
        avoided = int(hits.value(kind="executable")) if hits else 0
        print(f"warm start: {avoided} compile(s) avoided via "
              f"{args.artifact_dir}" if avoided else
              f"cold start: artifacts persisted to {args.artifact_dir} "
              "(next launch starts warm)")
    print("\nmetrics snapshot:")
    print(render_table(report.registry))

    if args.metrics_out:
        write_metrics_json(args.metrics_out, report.registry,
                           meta={"net": net.name, "requests": args.requests,
                                 "replicas": args.replicas})
        print(f"\nmetrics snapshot -> {args.metrics_out}")
    if args.trace_out:
        (report.tracer or tracer).export_jsonl(args.trace_out)
        print(f"trace spans -> {args.trace_out}")
    if args.profile_out:
        print(f"profiler trace -> {args.profile_out}")


if __name__ == "__main__":
    main()
