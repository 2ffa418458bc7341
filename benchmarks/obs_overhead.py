"""Observability overhead: serving latency with instrumentation on vs off.

The obs layer (DESIGN.md §12) promises to be cheap enough to leave on:
every call site pays at most a registry-locked increment or a span
append.  This suite pins that promise with an A/B through the *identical*
code path — two single-tier :class:`~repro.serving.ReplicaSet`\\ s over
the same synthesized program, one with an enabled
``MetricsRegistry``/``Tracer``, one with both disabled (mutations become
early returns, spans no-ops).  Reps interleave the arms so clock drift
and thermal state hit both equally; the headline ``overhead_pct`` is the
min-of-reps wall-time ratio (min is robust to scheduler noise).

Emits ``BENCH_obs.json`` (schema: benchmarks/bench_schema.py) and — the
CI artifacts — the enabled arm's metrics snapshot (``--metrics-out``)
and trace spans (``--trace-out``).

  PYTHONPATH=src python -m benchmarks.obs_overhead --dry-run
  PYTHONPATH=src python -m benchmarks.obs_overhead --requests 64 --reps 5
"""
from __future__ import annotations

import argparse
import time
from typing import Dict

import jax
import numpy as np

from repro.cnn import WORKLOADS, init_network_params
from repro.core import ComputeMode, synthesize
from repro.obs import (MetricsRegistry, Tracer, measure_drift, render_table,
                       write_metrics_json)
from repro.serving import ReplicaSet, ServingConfig
from repro.serving.loadgen import warm_replicas
from repro.launch.compile_cache import enable_compile_cache

from .bench_schema import SCHEMA_VERSION, write_bench


def _build_arm(program, config: ServingConfig, enabled: bool) -> ReplicaSet:
    registry = MetricsRegistry(enabled=enabled)
    tracer = Tracer(clock=registry.clock, enabled=enabled)
    tier = ReplicaSet(program, config=config, registry=registry,
                      tracer=tracer)
    warm_replicas(tier)
    return tier


def run(net_name: str = "squeezenet", *, scale: float = 0.08,
        input_hw: int = 64, num_classes: int = 10, requests: int = 64,
        reps: int = 5, max_batch: int = 8, max_delay_ms: float = 2.0,
        replicas: int = 1, mode: ComputeMode = ComputeMode.RELAXED,
        seed: int = 0, drift_reps: int = 2) -> Dict:
    """A/B the serving path and return the BENCH document.  ``doc["obs"]``
    carries the enabled arm's registry/tracer (stripped before
    ``write_bench``)."""
    net = WORKLOADS[net_name](scale=scale, num_classes=num_classes,
                              input_hw=input_hw)
    params = init_network_params(net, jax.random.PRNGKey(seed))
    program = synthesize(net, params, forced_mode=mode)

    # Unbounded queues: a shed in one arm and not the other would make
    # the walls incomparable.
    config = ServingConfig(max_batch=max_batch,
                           max_delay_s=max_delay_ms / 1e3,
                           replicas=replicas, max_queue_depth=0)
    tier_on = _build_arm(program, config, enabled=True)
    tier_off = _build_arm(program, config, enabled=False)

    rng = np.random.default_rng(seed)
    images = rng.standard_normal(
        (requests, *net.input_shape)).astype(np.float32)

    walls: Dict[str, list] = {"enabled": [], "disabled": []}
    with tier_on, tier_off:
        for rep in range(reps):
            # Interleave, alternating which arm goes first each rep.
            arms = [("enabled", tier_on), ("disabled", tier_off)]
            if rep % 2:
                arms.reverse()
            for name, tier in arms:
                t0 = time.perf_counter()
                futures = [tier.submit(images[i]) for i in range(requests)]
                for f in futures:
                    f.result(timeout=300.0)
                walls[name].append(time.perf_counter() - t0)

    on, off = min(walls["enabled"]), min(walls["disabled"])
    overhead_pct = (on - off) / off * 100.0
    drift = measure_drift(program, batch=max_batch, reps=drift_reps,
                          registry=tier_on.registry, tracer=tier_on.tracer)

    return {
        "benchmark": "obs_overhead",
        "schema_version": SCHEMA_VERSION,
        "config": {
            "net": net.name, "scale": scale, "input_hw": input_hw,
            "requests": requests, "reps": reps, "max_batch": max_batch,
            "max_delay_ms": max_delay_ms, "replicas": replicas,
            "mode": mode.value, "backend": jax.default_backend(),
            "program_fingerprint": program.fingerprint(),
        },
        "metrics": {
            "overhead_pct": overhead_pct,
            "enabled_wall_s": on,
            "disabled_wall_s": off,
            "enabled_ms_per_request": on / requests * 1e3,
            "disabled_ms_per_request": off / requests * 1e3,
            "requests": requests,
            "reps": reps,
            "spans_recorded": len(tier_on.tracer.finished()),
            "drift_mean_abs_error_pct": drift.mean_abs_error_pct,
            "drift_groups": len(drift.groups),
        },
        "rows": ([{"name": f"enabled_rep_{i}_wall_s", "value": w}
                  for i, w in enumerate(walls["enabled"])]
                 + [{"name": f"disabled_rep_{i}_wall_s", "value": w}
                    for i, w in enumerate(walls["disabled"])]),
        "obs": {"registry": tier_on.registry, "tracer": tier_on.tracer,
                "drift": drift},
    }


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", "--dry-run", dest="smoke", action="store_true",
                    help="tiny fast configuration for CI")
    ap.add_argument("--net", default="squeezenet", choices=sorted(WORKLOADS))
    ap.add_argument("--scale", type=float, default=0.08)
    ap.add_argument("--input-hw", type=int, default=64)
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-delay-ms", type=float, default=2.0)
    ap.add_argument("--replicas", type=int, default=1)
    ap.add_argument("--mode", default="relaxed",
                    choices=[m.value for m in ComputeMode])
    ap.add_argument("--out", default="BENCH_obs.json")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the enabled arm's metrics snapshot here")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the enabled arm's trace spans here")
    args = ap.parse_args()

    if args.smoke:
        args.requests = min(args.requests, 32)
        args.reps = min(args.reps, 3)
        args.max_batch = min(args.max_batch, 4)

    doc = run(args.net, scale=args.scale, input_hw=args.input_hw,
              requests=args.requests, reps=args.reps,
              max_batch=args.max_batch, max_delay_ms=args.max_delay_ms,
              replicas=args.replicas, mode=ComputeMode(args.mode))
    obs = doc.pop("obs")
    write_bench(args.out, doc)
    m = doc["metrics"]
    print(f"wrote {args.out}: obs overhead {m['overhead_pct']:+.2f}% "
          f"({m['enabled_ms_per_request']:.3f} vs "
          f"{m['disabled_ms_per_request']:.3f} ms/request, "
          f"{m['spans_recorded']:.0f} spans, "
          f"drift mean |err| {m['drift_mean_abs_error_pct']:.0f}%)")
    print("\nenabled-arm metrics snapshot:")
    print(render_table(obs["registry"]))
    if args.metrics_out:
        write_metrics_json(args.metrics_out, obs["registry"],
                           meta={"benchmark": "obs_overhead",
                                 "net": args.net,
                                 "overhead_pct": m["overhead_pct"]})
        print(f"\nmetrics snapshot -> {args.metrics_out}")
    if args.trace_out:
        obs["tracer"].export_jsonl(args.trace_out)
        print(f"trace spans -> {args.trace_out}")


if __name__ == "__main__":
    main()
