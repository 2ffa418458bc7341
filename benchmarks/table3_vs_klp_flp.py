"""Table III analogue: Cappuccino (OLP) vs CNNDroid-style parallelization.

CNNDroid [10] parallelizes with kernel/filter-level decomposition and
explicit cross-thread reductions; the paper reports Cappuccino 1.38X faster
exact and 11.47X faster imprecise, on AlexNet.  Our stand-ins: FLP and KLP
implementations (materialized partial tensors + reduction — the cost OLP
avoids) vs OLP, exact and imprecise, per representative conv layer and on
the scaled AlexNet.

As a module (from benchmarks.run) it prints CSV rows; as a script it also
emits a schema-validated BENCH document:

  PYTHONPATH=src python -m benchmarks.table3_vs_klp_flp --dry-run
"""
from __future__ import annotations

import argparse
from typing import List, Tuple

import jax

import jax.numpy as jnp

from repro.cnn import alexnet, init_network_params
from repro.core import (ComputeMode, ExecutionPlan, Parallelism, plan_network,
                        run_network, synthesize)
from repro.launch.compile_cache import enable_compile_cache

from .bench_schema import SCHEMA_VERSION, write_bench
from .common import bench, csv_row

# representative conv layer geometries (scaled AlexNet conv2/conv3)
LAYERS = [
    ("conv2_like", (1, 24, 27, 27), (64, 24, 5, 5), 1),
    ("conv3_like", (1, 64, 13, 13), (96, 64, 3, 3), 1),
]


def measure(reps: int = 8, *, scale: float = 0.25,
            input_hw: int = 115) -> Tuple[List[Tuple[str, float]], dict]:
    """All Table-III timings as (name, us_per_call) pairs, plus the
    synthesis summary (validated accuracy numbers — not latencies, so they
    ride outside the timing rows)."""
    out: List[Tuple[str, float]] = []
    from repro.core.parallelism import conv_policy
    for lname, xshape, wshape, stride in LAYERS:
        x = jax.random.normal(jax.random.PRNGKey(0), xshape)
        w = jax.random.normal(jax.random.PRNGKey(1), wshape) * 0.1
        for par in (Parallelism.OLP, Parallelism.FLP, Parallelism.KLP):
            f = jax.jit(lambda xx, ww, par=par: conv_policy(
                xx, ww, stride=stride, padding="SAME", mode=ComputeMode.RELAXED,
                parallelism=par))
            t = bench(f, x, w, reps=reps)
            out.append((f"table3.layer.{lname}.{par.value}", t * 1e6))

    # whole-network: OLP vs FLP (the CNNDroid-style policy), exact + imprecise,
    # each policy expressed as a uniform execution plan.
    net = alexnet(scale=scale, num_classes=100, input_hw=input_hw)
    params = init_network_params(net, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 3, input_hw, input_hw))
    for par in (Parallelism.OLP, Parallelism.FLP):
        for mode in (ComputeMode.PRECISE, ComputeMode.IMPRECISE):
            modes = {n: mode for n in net.inexactable_layers}
            plan = ExecutionPlan.uniform(net, backend="xla", parallelism=par,
                                         modes=modes)
            f = jax.jit(lambda xx, plan=plan: run_network(
                net, params, xx, plan=plan))
            t = bench(f, x, reps=reps)
            out.append((f"table3.alexnet.{par.value}.{mode.value}", t * 1e6))

    # the planner's own per-layer assignment, for comparison with the
    # uniform policies above
    for mode in (ComputeMode.PRECISE, ComputeMode.IMPRECISE):
        modes = {n: mode for n in net.inexactable_layers}
        plan = plan_network(net, modes=modes)
        f = jax.jit(lambda xx, plan=plan: run_network(net, params, xx,
                                                      plan=plan))
        t = bench(f, x, reps=reps)
        out.append((f"table3.alexnet.planned.{mode.value}", t * 1e6))

    # the program the synthesizer actually ships: fixed-point loop +
    # final validation gate on the emitted dispatch path.  The timing row
    # is the converged program; the synthesis rows are the validated
    # accuracy numbers (not probe-path estimates) the table should quote.
    cal_x = jax.random.normal(jax.random.PRNGKey(3),
                              (8, 3, input_hw, input_hw))
    cal_labels = jnp.argmax(run_network(net, params, cal_x), -1)
    prog = synthesize(net, params, validation=(cal_x, cal_labels),
                      max_degradation=0.0)
    t = bench(prog.infer, x, reps=reps)
    out.append(("table3.alexnet.synthesized_validated", t * 1e6))
    srep = prog.synthesis_report
    synthesis = {
        "fixed_point_iterations": len(srep.iterations),
        "validated_acc": srep.final_validation.accuracy,
        "validated_degradation": srep.final_validation.degradation,
        "gate_fallbacks": len(srep.fallbacks),
    }
    return out, synthesis


def _synthesis_row(synthesis: dict) -> str:
    return csv_row(
        "table3.synthesis.validated", 0.0,
        f"acc={synthesis['validated_acc']:.4f} "
        f"deg={synthesis['validated_degradation']:.4f} "
        f"iters={synthesis['fixed_point_iterations']} "
        f"fallbacks={synthesis['gate_fallbacks']}")


def run(reps: int = 8) -> List[str]:
    pairs, synthesis = measure(reps)
    return [csv_row(name, us) for name, us in pairs] \
        + [_synthesis_row(synthesis)]


def to_bench_doc(pairs: List[Tuple[str, float]], synthesis: dict,
                 reps: int) -> dict:
    us = dict(pairs)
    olp = us["table3.alexnet.olp.precise"]
    flp = us["table3.alexnet.flp.precise"]
    olp_i = us["table3.alexnet.olp.imprecise"]
    flp_i = us["table3.alexnet.flp.imprecise"]
    return {
        "benchmark": "table3_vs_klp_flp",
        "schema_version": SCHEMA_VERSION,
        "config": {"reps": reps, "backend": jax.default_backend()},
        "metrics": {
            "olp_over_flp_speedup": flp / olp,
            "olp_over_flp_speedup_imprecise": flp_i / olp_i,
            "alexnet_olp_precise_us": olp,
            "alexnet_olp_imprecise_us": olp_i,
            "alexnet_synthesized_validated_us":
                us["table3.alexnet.synthesized_validated"],
            "validated_acc": synthesis["validated_acc"],
            "validated_degradation": synthesis["validated_degradation"],
            "fixed_point_iterations": synthesis["fixed_point_iterations"],
        },
        "rows": [{"name": n, "value": v} for n, v in pairs],
    }


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--dry-run", action="store_true",
                    help="minimal reps: validates the pipeline + schema, "
                         "numbers are indicative only")
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--out", default="BENCH_table3.json")
    args = ap.parse_args()
    reps = 2 if args.dry_run else args.reps

    pairs, synthesis = measure(reps)
    for name, us in pairs:
        print(csv_row(name, us))
    print(_synthesis_row(synthesis))
    write_bench(args.out, to_bench_doc(pairs, synthesis, reps))
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
