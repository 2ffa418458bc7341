"""Fusion benchmark: fused-group dispatch vs. the unfused layer walk.

For each reference CNN this suite lowers the network through the graph
pass pipeline (core/graph.py) and reports:

  * **dispatch counts** — executor-level op launches per forward pass:
    one per layer unfused vs. one per fused group (the paper's
    dispatch-overhead claim, Wang et al.: dispatch dominates small-layer
    latency on mobile parts).  Counted exactly, via
    :class:`~repro.core.graph.DispatchStats`.
  * **latency** — jitted end-to-end forward time under the *identical*
    per-layer plan (the unfused baseline is the fused plan with its graph
    stripped, so routing differences cannot masquerade as fusion wins).
    On this CPU/XLA host the compiler already fuses most of the gap away,
    so treat the dispatch counts (exact) as the headline and the latency
    ratio as corroboration; on TPU the fused conv groups additionally
    collapse to single Pallas launches.

The suite *enforces* the PR's acceptance criterion: GoogLeNet's fused
dispatch count must be strictly lower than unfused, or it exits non-zero
(CI runs it with --dry-run).

Emits schema-validated ``BENCH_fusion.json``:

  PYTHONPATH=src python -m benchmarks.fusion_speedup --dry-run
"""
from __future__ import annotations

import argparse
from typing import Dict, List

import jax
import jax.numpy as jnp

from repro.cnn import WORKLOADS, init_network_params
from repro.core import (ComputeMode, DispatchStats, execute_graph,
                        lower_network, mode_tolerance, plan_network,
                        run_network)
from repro.launch.compile_cache import enable_compile_cache

from .bench_schema import SCHEMA_VERSION, write_bench
from .common import bench, csv_row

DRY_SCALES = {"alexnet": (0.1, 67), "squeezenet": (0.08, 64),
              "googlenet": (0.1, 64)}
FULL_SCALES = {"alexnet": (0.25, 115), "squeezenet": (0.25, 128),
               "googlenet": (0.125, 112)}


def measure_net(name: str, builder, *, scale: float, hw: int,
                reps: int) -> Dict[str, float]:
    net = builder(scale=scale, num_classes=10, input_hw=hw)
    graph = lower_network(net)
    params = init_network_params(net, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 3, hw, hw))
    modes = {n: ComputeMode.RELAXED for n in net.inexactable_layers}

    fused_plan = plan_network(net, modes=modes, graph=graph)
    # The unfused baseline is the *same* per-layer plan dispatched through
    # the layer walk — not an independent re-plan, which could route
    # layers differently under unfused costs and conflate fusion with
    # re-routing.  This isolates exactly the grouping.
    unfused_plan = fused_plan.with_graph(None)

    # Exact dispatch accounting: trace the fused executor once.
    stats = DispatchStats()
    execute_graph(graph, fused_plan, params, x, stats=stats)
    assert stats.layers == graph.n_layers

    f_unfused = jax.jit(lambda xx: run_network(net, params, xx,
                                               plan=unfused_plan))
    f_fused = jax.jit(lambda xx: run_network(net, params, xx,
                                             plan=fused_plan))
    t_unfused = bench(f_unfused, x, reps=reps)
    t_fused = bench(f_fused, x, reps=reps)

    # Parity guard: the two programs must agree within the RELAXED
    # tolerance — a fused path that silently drops its epilogue must fail
    # the benchmark, not just log a number.
    want = f_unfused(x).astype(jnp.float32)
    diff = float(jnp.max(jnp.abs(f_fused(x).astype(jnp.float32) - want)))
    tol = mode_tolerance(ComputeMode.RELAXED) \
        * max(float(jnp.max(jnp.abs(want))), 1.0)
    if diff > tol:
        raise RuntimeError(
            f"{name}: fused/unfused parity violated: max abs diff {diff:.4g}"
            f" > tolerance {tol:.4g}")

    return {
        "dispatches_unfused": len(net.layers),
        "dispatches_fused": stats.dispatches,
        "fused_groups": stats.fused_groups,
        "layers_fused_away": stats.fused_away,
        "latency_unfused_us": t_unfused * 1e6,
        "latency_fused_us": t_fused * 1e6,
        "latency_speedup": t_unfused / t_fused,
        "max_abs_diff": diff,
    }


def sweep(scales: Dict[str, tuple], reps: int) -> Dict[str, Dict[str, float]]:
    results = {}
    for name, builder in WORKLOADS.items():
        scale, hw = scales[name]
        results[name] = measure_net(name, builder, scale=scale, hw=hw,
                                    reps=reps)
    return results


def check_acceptance(results: Dict[str, Dict[str, float]]) -> None:
    """Raises RuntimeError (a plain Exception, so benchmarks/run.py's
    keep-going harness can record the failure and finish the other suites;
    as a script the non-zero exit still fails CI)."""
    g = results["googlenet"]
    if not g["dispatches_fused"] < g["dispatches_unfused"]:
        raise RuntimeError(
            f"acceptance violated: googlenet fused dispatch count "
            f"{g['dispatches_fused']} is not strictly lower than unfused "
            f"{g['dispatches_unfused']}")


def to_bench_doc(results: Dict[str, Dict[str, float]], *, reps: int,
                 scales: Dict[str, tuple]) -> dict:
    rows: List[dict] = []
    for net, r in sorted(results.items()):
        for k, v in sorted(r.items()):
            rows.append({"name": f"{net}.{k}", "value": float(v)})
    g = results["googlenet"]
    return {
        "benchmark": "fusion_speedup",
        "schema_version": SCHEMA_VERSION,
        "config": {"reps": reps, "backend": jax.default_backend(),
                   "scales": {n: list(s) for n, s in scales.items()},
                   "mode": "relaxed"},
        "metrics": {
            "nets": len(results),
            "googlenet_dispatches_unfused": g["dispatches_unfused"],
            "googlenet_dispatches_fused": g["dispatches_fused"],
            "googlenet_dispatch_reduction":
                1.0 - g["dispatches_fused"] / g["dispatches_unfused"],
            "googlenet_latency_speedup": g["latency_speedup"],
            "total_layers_fused_away":
                sum(r["layers_fused_away"] for r in results.values()),
        },
        "rows": rows,
    }


def run(reps: int = 4) -> List[str]:
    """CSV rows for benchmarks.run."""
    results = sweep(DRY_SCALES, reps)
    check_acceptance(results)
    out = []
    for net, r in sorted(results.items()):
        out.append(csv_row(
            f"fusion.{net}.fused", r["latency_fused_us"],
            f"dispatches={r['dispatches_fused']}/{r['dispatches_unfused']} "
            f"speedup={r['latency_speedup']:.2f}X"))
    return out


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--dry-run", action="store_true",
                    help="small networks + minimal reps: validates the "
                         "pipeline + schema, numbers indicative only")
    ap.add_argument("--reps", type=int, default=12)
    ap.add_argument("--out", default="BENCH_fusion.json")
    args = ap.parse_args()
    reps = 2 if args.dry_run else args.reps
    scales = DRY_SCALES if args.dry_run else FULL_SCALES

    results = sweep(scales, reps)
    for net, r in sorted(results.items()):
        print(f"{net:12s} dispatches {r['dispatches_unfused']:3.0f} -> "
              f"{r['dispatches_fused']:3.0f} "
              f"({r['fused_groups']:.0f} fused groups, "
              f"{r['layers_fused_away']:.0f} layers fused away)  "
              f"latency {r['latency_unfused_us']:.0f} -> "
              f"{r['latency_fused_us']:.0f} us "
              f"({r['latency_speedup']:.2f}X)")
    check_acceptance(results)
    write_bench(args.out, to_bench_doc(results, reps=reps, scales=scales))
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
