"""Table II analogue: energy for SqueezeNet, baseline vs synthesized.

The container has no power rail; the paper's 7.81X energy ratio came from
runtime reduction dominating the higher instantaneous power of parallel
execution.  We report the measurable component — the runtime ratio — twice
(two independent 'first 1000 / second 1000'-style batches, paper §V-B-4) to
reproduce the repeatability protocol, and flag the proxy explicitly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.cnn import squeezenet, init_network_params
from repro.core import ComputeMode, ExecutionPlan, run_network, synthesize
from repro.launch.compile_cache import enable_compile_cache

from .common import bench, csv_row


def run(reps: int = 8):
    net = squeezenet(scale=0.25, num_classes=100, input_hw=128)
    params = init_network_params(net, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 3, 128, 128))
    seq = ExecutionPlan.uniform(net, backend="sequential")
    baseline = jax.jit(lambda xx: run_network(net, params, xx, plan=seq))
    synthesized = synthesize(net, params,
                             forced_mode=ComputeMode.IMPRECISE).infer
    rows = []
    ratios = []
    for batch in ("first", "second"):
        t_base = bench(baseline, x, reps=reps)
        t_syn = bench(synthesized, x, reps=reps)
        ratios.append(t_base / t_syn)
        rows.append(csv_row(f"table2.squeezenet.baseline.{batch}", t_base * 1e6))
        rows.append(csv_row(f"table2.squeezenet.synthesized.{batch}", t_syn * 1e6,
                            f"runtime_ratio={t_base / t_syn:.2f}X(energy proxy)"))
    rows.append(csv_row("table2.squeezenet.avg_ratio",
                        0.0, f"avg={sum(ratios) / len(ratios):.2f}X"))
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    print("\n".join(run()))
