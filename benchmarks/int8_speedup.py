"""Int8 datapath benchmark: IMPRECISE_INT8 vs. RELAXED on the fused path.

For each reference CNN this suite synthesizes the program twice through the
real pipeline (``synthesize(forced_mode=...)``, fused graph dispatch,
Stage-B prepared weights, calibrated activation qparams) and reports:

  * **dispatch counts** — executor-level launches per forward pass under
    each mode, counted exactly via
    :class:`~repro.core.graph.DispatchStats`.  A quantized fused
    conv+bias+ReLU group stays *one* launch: the int8 kernels fold the
    dequant into the same flush epilogue bias+ReLU already use.
  * **int8 coverage** — how many layers carry calibrated qparams, i.e.
    actually run int8 x int8 -> int32 (uncalibrated layers would silently
    take the dequant fallback; the acceptance check forbids that here).
  * **latency** — jitted end-to-end forward time.  On this CPU host the
    Pallas kernels run interpreted and XLA emulates int8 matmuls, so treat
    coverage and dispatch counts (exact) as the headline and the latency
    ratio as corroboration; on TPU the int8 ridge is what the planner
    costs against (``profile.ridge("int8")``).
  * **parity** — max abs difference int8 vs. RELAXED logits, enforced
    within ``mode_tolerance(IMPRECISE_INT8)``.

Emits schema-validated ``BENCH_int8.json``:

  PYTHONPATH=src python -m benchmarks.int8_speedup --dry-run
"""
from __future__ import annotations

import argparse
from typing import Dict, List

import jax
import jax.numpy as jnp

from repro.cnn import WORKLOADS, init_network_params
from repro.core import (ComputeMode, DispatchStats, execute_graph,
                        mode_tolerance, synthesize)
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
    params = init_network_params(net, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 3, hw, hw))

    # Both programs come out of the real pipeline: fused graph, Stage-B
    # prepared weights, and — for int8 — activation calibration over the
    # same input the latency loop uses (autotune_input doubles as the
    # calibration set on the forced-mode path).
    prog_relaxed = synthesize(net, params,
                              forced_mode=ComputeMode.RELAXED)
    prog_int8 = synthesize(net, params,
                           forced_mode=ComputeMode.IMPRECISE_INT8,
                           autotune_input=x)

    int8_layers = sum(1 for lp in prog_int8.plan.layers.values()
                     if lp.qparams is not None)

    stats_i8, stats_rel = DispatchStats(), DispatchStats()
    execute_graph(prog_int8.plan.graph, prog_int8.plan, prog_int8.prepared,
                  x, stats=stats_i8)
    execute_graph(prog_relaxed.plan.graph, prog_relaxed.plan,
                  prog_relaxed.prepared, x, stats=stats_rel)

    t_rel = bench(prog_relaxed.infer, x, reps=reps)
    t_i8 = bench(prog_int8.infer, x, reps=reps)

    # Parity guard: quantized logits must track the RELAXED program within
    # the INT8 mode tolerance — a kernel that drops its dequant epilogue
    # must fail the benchmark, not just log a number.
    want = prog_relaxed.infer(x).astype(jnp.float32)
    diff = float(jnp.max(jnp.abs(prog_int8.infer(x).astype(jnp.float32)
                                 - want)))
    tol = mode_tolerance(ComputeMode.IMPRECISE_INT8) \
        * max(float(jnp.max(jnp.abs(want))), 1.0)
    if diff > tol:
        raise RuntimeError(
            f"{name}: int8/relaxed parity violated: max abs diff {diff:.4g}"
            f" > tolerance {tol:.4g}")

    return {
        "dispatches_int8": stats_i8.dispatches,
        "dispatches_relaxed": stats_rel.dispatches,
        "int8_layers": int8_layers,
        "param_layers": len(net.param_layers),
        "latency_relaxed_us": t_rel * 1e6,
        "latency_int8_us": t_i8 * 1e6,
        "latency_speedup": t_rel / t_i8,
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
    """Every parametric layer must carry calibrated qparams (true int8
    datapath, no silent dequant fallback), and the quantized fused program
    must not dispatch more ops than the RELAXED one — the dequant epilogue
    rides the existing flush, it never costs an extra launch."""
    for name, r in results.items():
        if r["int8_layers"] != r["param_layers"]:
            raise RuntimeError(
                f"acceptance violated: {name} calibrated only "
                f"{r['int8_layers']}/{r['param_layers']} layers — the rest "
                "would take the dequant fallback")
        if r["dispatches_int8"] > r["dispatches_relaxed"]:
            raise RuntimeError(
                f"acceptance violated: {name} int8 dispatches "
                f"{r['dispatches_int8']} exceed relaxed "
                f"{r['dispatches_relaxed']} — quantization must not break "
                "epilogue fusion")


def to_bench_doc(results: Dict[str, Dict[str, float]], *, reps: int,
                 scales: Dict[str, tuple]) -> dict:
    rows: List[dict] = []
    for net, r in sorted(results.items()):
        for k, v in sorted(r.items()):
            rows.append({"name": f"{net}.{k}", "value": float(v)})
    g = results["googlenet"]
    return {
        "benchmark": "int8_speedup",
        "schema_version": SCHEMA_VERSION,
        "config": {"reps": reps, "backend": jax.default_backend(),
                   "scales": {n: list(s) for n, s in scales.items()},
                   "modes": ["imprecise_int8", "relaxed"]},
        "metrics": {
            "nets": len(results),
            "total_int8_layers":
                sum(r["int8_layers"] for r in results.values()),
            "googlenet_dispatches_int8": g["dispatches_int8"],
            "googlenet_dispatches_relaxed": g["dispatches_relaxed"],
            "googlenet_latency_speedup": g["latency_speedup"],
            "max_parity_diff":
                max(r["max_abs_diff"] for r in results.values()),
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
            f"int8.{net}", r["latency_int8_us"],
            f"int8_layers={r['int8_layers']}/{r['param_layers']} "
            f"dispatches={r['dispatches_int8']} "
            f"speedup={r['latency_speedup']:.2f}X"))
    return out


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--dry-run", action="store_true",
                    help="small networks + minimal reps: validates the "
                         "pipeline + schema, numbers indicative only")
    ap.add_argument("--reps", type=int, default=12)
    ap.add_argument("--out", default="BENCH_int8.json")
    args = ap.parse_args()
    reps = 2 if args.dry_run else args.reps
    scales = DRY_SCALES if args.dry_run else FULL_SCALES

    results = sweep(scales, reps)
    for net, r in sorted(results.items()):
        print(f"{net:12s} int8 layers {r['int8_layers']:2.0f}/"
              f"{r['param_layers']:2.0f}  dispatches "
              f"{r['dispatches_int8']:3.0f} (relaxed "
              f"{r['dispatches_relaxed']:3.0f})  latency "
              f"{r['latency_relaxed_us']:.0f} -> {r['latency_int8_us']:.0f}"
              f" us ({r['latency_speedup']:.2f}X)  "
              f"parity diff {r['max_abs_diff']:.3g}")
    check_acceptance(results)
    write_bench(args.out, to_bench_doc(results, reps=reps, scales=scales))
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
