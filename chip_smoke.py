#!/usr/bin/env python3
"""Smoke check of the main path on a TPU.

SqueezeNet v1.0 at its published size (width 1.0, 224x224 input, 1000
classes, random weights from a seed) goes through the same entry point
as ``repro.launch.serve_cnn``: ``synthesize(forced_mode=RELAXED)``, a
``ReplicaSet``, and ``run_offered_load`` with single-image requests.

  python3 chip_smoke.py                # one chip
  python3 chip_smoke.py --four-chips   # 4-replica tier on a 2x2 host
                                       # against a 1-replica tier
  python3 chip_smoke.py --rehearse [--four-chips]
                                       # a tiny network on any backend
                                       # (prints no ok line)

One chip: every served row is compared with a float32 reference of the
same weights (``run_network`` under an all-PRECISE plan at ``highest``
matmul precision), and at least one fused group must run as a compiled
Pallas kernel.  Four chips: the same requests through four replicas and
through one must give equal outputs, each replica's from its own chip.

Exits nonzero and prints no ok line when JAX finds no TPU, when a request
fails, or when a check fails.  Otherwise the last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.cnn import init_network_params, squeezenet  # noqa: E402
from repro.core import ComputeMode, run_network  # noqa: E402
from repro.core.plan import IMPL_PALLAS  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve_cnn import serve  # noqa: E402
from repro.obs import MetricsRegistry, Tracer  # noqa: E402
from repro.serving import (ReplicaSet, ServingConfig,  # noqa: E402
                           run_offered_load)

SEED = 0
MAX_BATCH = 8
REQUESTS = 48
#: Largest relative error admitted between a RELAXED served row and the
#: float32 reference: the RMS over classes of the difference of the two
#: log-probability rows, each centred (softmax ignores a shift), over the
#: reference row's standard deviation.  RELAXED rounds every conv operand
#: to bf16 (relative error <= 2**-9) and accumulates in f32; over
#: SqueezeNet's 26 convs those roundings compound to about 1% of the logit
#: scale (0.9% on a 64 px, width-1.0 SqueezeNet through XLA alone, on a
#: CPU).  The logits of this network span ~25 units, so that 1% alone
#: moves the largest single log-probability by ~0.25: an absolute bound
#: would have to be loose where the scale is small.  0.05 leaves 5x room;
#: a wrong kernel (lost channel group, wrong tap, wrong row of a bucket)
#: moves a row by a large fraction of its scale.
RELAXED_REL_TOL = 0.05
#: A row whose log-probabilities span less than this is constant: with
#: dead ReLUs every class gets the same score.
MIN_LOGP_SPREAD = 1e-3


def fail(msg: str) -> None:
    print(f"FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def network(rehearse: bool):
    if rehearse:
        return squeezenet(scale=0.25, num_classes=100, input_hw=64)
    return squeezenet()


def log_probs(probs: np.ndarray) -> np.ndarray:
    return np.log(np.maximum(np.asarray(probs, np.float64), 1e-30))


def check_not_constant(logp: np.ndarray) -> None:
    spread = logp.max(axis=1) - logp.min(axis=1)
    if spread.min() < MIN_LOGP_SPREAD:
        fail(f"constant logits across classes (row spread {spread.min():.2e})")
    across = np.abs(logp - logp[:1]).max()
    if len(logp) > 1 and across < MIN_LOGP_SPREAD:
        fail(f"identical logits across images (max diff {across:.2e})")


def one_chip(net, params, rehearse: bool) -> None:
    registry = MetricsRegistry()
    tracer = Tracer(clock=registry.clock)
    config = ServingConfig(max_batch=MAX_BATCH, max_delay_s=0.002)
    program, report = serve(net, params, mode=ComputeMode.RELAXED,
                            config=config, requests=REQUESTS, seed=SEED,
                            registry=registry, tracer=tracer)
    print(f"synthesis seconds: {program.synthesis_seconds}")
    for span in sorted(tracer.by_name("synthesis.stage_d_compile"),
                       key=lambda s: s.attrs["batch"]):
        print(f"stage-D compile bucket {span.attrs['batch']}: "
              f"{span.attrs['compile_seconds']} s")

    groups = program.plan.graph.groups
    pallas = [g.name for g in groups
              if program.plan.for_layer(g.name).impl == IMPL_PALLAS]
    tier = report.tier
    launches = {}
    for b in sorted(report.bucket_counts):
        hlo = tier.cache.get_or_build(program, b,
                                      tier.replicas[0].device).hlo_text()
        launches[b] = hlo.count('custom_call_target="tpu_custom_call"')
    print(f"pallas groups: {len(pallas)} of {len(groups)} planned "
          f"({', '.join(pallas)}); compiled kernel launches per bucket "
          f"executable: {launches}")
    if not rehearse:
        if not pallas:
            fail("no group was routed to a Pallas kernel")
        if any(n != len(pallas) for n in launches.values()):
            fail(f"{len(pallas)} Pallas groups planned but the executables "
                 f"hold {launches} compiled kernel launches")

    srv = report.server_stats
    print(f"requests: {report.requests} submitted, {srv['completed']} "
          f"served, {srv['failed']} failed, {report.shed_requests} shed")
    if srv["failed"] or srv["completed"] != REQUESTS:
        fail("not every request was served")
    print(f"throughput: {report.sustained_per_s} img/s "
          f"(buckets {report.bucket_counts}, not a benchmark)")

    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda x: run_network(net, params, x))(report.images)
    served, want = log_probs(report.outputs), log_probs(ref)
    if not np.isfinite(served).all():
        fail("non-finite served outputs")
    check_not_constant(served)
    diff = served - want
    diff -= diff.mean(axis=1, keepdims=True)
    rel = np.sqrt((diff ** 2).mean(axis=1)) / want.std(axis=1)
    print(f"largest |log p - log p_ref| over {served.shape}: "
          f"{float(np.abs(served - want).max())}")
    print(f"largest relative error of a row: {float(rel.max())} "
          f"(tolerance {RELAXED_REL_TOL})")
    if rel.max() > RELAXED_REL_TOL:
        fail("served logits disagree with the float32 reference")


def four_chips(net, params) -> None:
    devices = jax.devices()
    if len(devices) != 4:
        fail(f"--four-chips needs 4 devices, JAX finds {len(devices)}")
    # Requests arrive back-to-back and the deadline is far away, so each
    # tier releases only full buckets of MAX_BATCH: the least-loaded
    # admission deals requests round-robin to the four replicas, and both
    # tiers run the same batch-8 executable on every row.
    requests = 4 * MAX_BATCH
    config = ServingConfig(max_batch=MAX_BATCH, max_delay_s=30.0)
    program, four = serve(net, params, mode=ComputeMode.RELAXED,
                          config=config.with_replicas(4), requests=requests,
                          seed=SEED)
    one = run_offered_load(ReplicaSet(program, config=config,
                                      cache=four.tier.cache),
                           requests=requests, seed=SEED, warm=False)
    for r in four.tier.stats()["replicas"]:
        print(f"replica {r['replica']} on {r['device']}: buckets "
              f"{r['bucket_counts']}, outputs from {r['output_devices']}")
        if set(r["output_devices"]) != {r["device"]}:
            fail(f"replica {r['replica']} served outputs from "
                 f"{r['output_devices']}, not from its own {r['device']}")
    if four.server_stats["failed"] or one.server_stats["failed"]:
        fail("a request failed")
    check_not_constant(log_probs(four.outputs))
    diff = float(np.abs(four.outputs.astype(np.float64)
                        - one.outputs.astype(np.float64)).max())
    print(f"4-replica vs 1-replica outputs over {four.outputs.shape}: "
          f"max |diff| {diff}")
    if diff != 0.0:
        fail("4-replica and 1-replica tiers served different outputs")
    print(f"throughput: 4 replicas {four.sustained_per_s} img/s, 1 replica "
          f"{one.sustained_per_s} img/s (cold, not a benchmark)")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only the 4-replica vs 1-replica comparison")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny network on any backend; prints no ok line")
    args = ap.parse_args()

    enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        fail(f"no TPU: JAX's first device is {dev.platform} "
             f"({dev.device_kind})")
    net = network(args.rehearse)
    params = init_network_params(net, jax.random.PRNGKey(SEED))
    widths = [l.out_channels for l in net.param_layers]
    print(f"network: {net.name} input {net.input_shape}, "
          f"{len(net.param_layers)} convs, widths {widths}")
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(net, params)
    else:
        one_chip(net, params, args.rehearse)
    print(f"smoke seconds: {time.perf_counter() - t0}")
    if args.rehearse:
        print("rehearsal passed (no ok line off the chip)")
        return
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
