"""Table I analogue: Baseline vs Parallel vs Imprecise on the three CNNs.

Paper: single-threaded Java baseline vs Cappuccino-parallel (exact) vs
Cappuccino-imprecise, on Nexus 5 / 6P / Galaxy S7.  Here: sequential
scalar-loop baseline vs OLP-parallel PRECISE vs OLP IMPRECISE, on this
container's CPU via XLA.  Absolute numbers differ from phones; the paper's
*orderings* (imprecise <= parallel << baseline) are the reproduced claims.

CNNs are channel-scaled to finish in CPU time; layer structure is intact.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.cnn import WORKLOADS, init_network_params
from repro.core import ComputeMode, ExecutionPlan, run_network, synthesize
from repro.launch.compile_cache import enable_compile_cache

from .common import bench, csv_row

SCALES = {"alexnet": (0.25, 115), "squeezenet": (0.25, 128),
          "googlenet": (0.125, 112)}


def run(reps: int = 8):
    rows = []
    for name, fn in WORKLOADS.items():
        scale, hw = SCALES[name]
        net = fn(scale=scale, num_classes=100, input_hw=hw)
        params = init_network_params(net, jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 3, hw, hw))

        seq = ExecutionPlan.uniform(net, backend="sequential")
        baseline = jax.jit(lambda xx, net=net, p=params, plan=seq: run_network(
            net, p, xx, plan=plan))
        parallel = synthesize(net, params, forced_mode=ComputeMode.PRECISE).infer
        imprecise = synthesize(net, params, forced_mode=ComputeMode.IMPRECISE).infer

        t_base = bench(baseline, x, reps=reps)
        t_par = bench(parallel, x, reps=reps)
        t_imp = bench(imprecise, x, reps=reps)
        speedup = t_base / t_imp
        rows.append(csv_row(f"table1.{name}.baseline", t_base * 1e6))
        rows.append(csv_row(f"table1.{name}.parallel", t_par * 1e6,
                            f"vs_baseline={t_base / t_par:.2f}X"))
        rows.append(csv_row(f"table1.{name}.imprecise", t_imp * 1e6,
                            f"speedup={speedup:.2f}X"))
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    print("\n".join(run()))
