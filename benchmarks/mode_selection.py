"""§V-B-2 analogue: per-layer inexact-mode analysis on a validation set.

The paper found imprecise-mode classification accuracy identical to exact on
5000 ILSVRC-2012 images, so Cappuccino recommended imprecise everywhere.  We
reproduce the *analysis* on a synthetic-but-nontrivial validation set (the
data pipeline's pseudo-ImageNet): the report records reference accuracy,
per-mode accuracy, and the selector's recommendation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.cnn import squeezenet, init_network_params
from repro.core import IMPL_DEFAULT, ComputeMode, run_network, synthesize
from repro.data.synthetic import imagenet_like
from repro.launch.compile_cache import enable_compile_cache

from .common import csv_row


def run(n_val: int = 64):
    net = squeezenet(scale=0.125, num_classes=10, input_hw=64)
    params = init_network_params(net, jax.random.PRNGKey(0))
    images, _ = imagenet_like(jax.random.PRNGKey(1), n_val, hw=64)
    # labels from the PRECISE model = ground truth proxy (accuracy 1.0 ref)
    labels = jnp.argmax(run_network(net, params, images), -1)

    prog = synthesize(net, params, validation=(images, labels),
                      max_degradation=0.0, allow_int8=False)
    rep = prog.mode_report
    rows = [csv_row("mode_selection.reference_acc", 0.0,
                    f"acc={rep.reference_metric:.4f}"),
            csv_row("mode_selection.final_acc", 0.0,
                    f"acc={rep.final_metric:.4f}"),
            csv_row("mode_selection.evaluations", float(rep.evaluations))]
    n_imprecise = sum(1 for m in prog.modes.values()
                      if m is ComputeMode.IMPRECISE)
    rows.append(csv_row("mode_selection.imprecise_layers", float(n_imprecise),
                        f"of={len(prog.modes)}"))
    # The numbers that actually ship: the fixed-point loop's convergence and
    # the final gate's measurement of the *emitted* program (not the probe
    # path) — these are the paper-table accuracies to quote.
    srep = prog.synthesis_report
    val = srep.final_validation
    rows += [csv_row("mode_selection.fixed_point_iterations",
                     float(len(srep.iterations)),
                     f"converged={srep.converged}"),
             csv_row("mode_selection.validated_acc", 0.0,
                     f"acc={val.accuracy:.4f}"),
             csv_row("mode_selection.validated_degradation", 0.0,
                     f"deg={val.degradation:.4f} budget=0.0"),
             csv_row("mode_selection.gate_fallbacks",
                     float(len(srep.fallbacks)),
                     f"validated={srep.validated}")]
    # Stage A plan artifact: how the planner assigned implementations
    impls = [p.impl for _, p in prog.plan if p.impl != IMPL_DEFAULT]
    for impl in sorted(set(impls)):
        rows.append(csv_row(f"mode_selection.plan.{impl}",
                            float(impls.count(impl)),
                            f"origin={prog.plan.origin}"))
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    print("\n".join(run()))
