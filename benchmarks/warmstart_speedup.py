"""Warm-start benchmark: cold synthesis+compile vs artifact-store hydrate.

Measures what the persistent artifact store (DESIGN.md §13) buys at
process start.  Two *separate subprocesses* run the identical start
sequence — synthesize (fixed-point loop + validation gate) then warm
every serving bucket — against one shared artifact directory:

  cold   empty store: pays the full fixed-point loop, the validation
         gate, and a Stage-D AOT compile per bucket, persisting every
         artifact as it goes;
  warm   populated store: hydrates the converged program (zero synthesis
         iterations) and the serialized Stage-D executables (zero
         compiles where ``jax.export`` supports the platform).

Separate processes are load-bearing, not ceremony: XLA caches compiled
executables in-process, so a cold-then-warm sequence inside one process
would hand the warm phase compile results through memory and measure
nothing.  A child process reports its phase through a marker line on
stdout; the parent computes the speedup and emits schema-validated
``BENCH_warmstart.json``:

  cold_start_seconds     synthesis + bucket warm-up, empty store
  warm_start_seconds     same sequence, populated store
  warm_stage_d_compiles  0 on the executable-serialization path; >0 only
                         under the plan-only fallback (see ``plan_only``)
  speedup                cold_start_seconds / warm_start_seconds

  PYTHONPATH=src python -m benchmarks.warmstart_speedup --dry-run
  PYTHONPATH=src python -m benchmarks.warmstart_speedup \
      --net squeezenet --input-hw 64 --max-batch 8 --replicas 2
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time
from typing import Dict

from repro.launch.compile_cache import enable_compile_cache

from .bench_schema import SCHEMA_VERSION, write_bench

#: stdout marker a phase child prints its result JSON behind.
_MARKER = "WARMSTART_PHASE_RESULT "


def run_phase(artifact_dir: str, *, net_name: str, scale: float,
              input_hw: int, num_classes: int, max_batch: int,
              replicas: int, calib: int, seed: int) -> Dict:
    """One process start against ``artifact_dir``: synthesize, build the
    tier, warm every bucket.  Returns the phase measurements."""
    import jax
    import jax.numpy as jnp

    from repro.artifacts import ArtifactStore
    from repro.cnn import WORKLOADS, init_network_params
    from repro.core import run_network, synthesize
    from repro.obs import MetricsRegistry
    from repro.serving import ReplicaSet, ServingConfig
    from repro.serving.loadgen import warm_replicas

    net = WORKLOADS[net_name](scale=scale, num_classes=num_classes,
                              input_hw=input_hw)
    params = init_network_params(net, jax.random.PRNGKey(seed))
    x = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (calib, *net.input_shape))
    labels = jnp.argmax(run_network(net, params, x), -1)

    registry = MetricsRegistry()
    store = ArtifactStore(artifact_dir, registry=registry)
    t0 = time.perf_counter()
    program = synthesize(net, params, validation=(x, labels),
                         max_degradation=0.25, registry=registry,
                         artifact_store=store)
    synthesis_seconds = time.perf_counter() - t0

    config = ServingConfig(max_batch=max_batch, replicas=replicas,
                           artifact_dir=artifact_dir)
    tier = ReplicaSet(program, config=config, registry=registry)
    warm_replicas(tier)
    start_seconds = time.perf_counter() - t0

    def count(name: str, **labels) -> float:
        c = registry.get(name)
        return float(c.value(**labels)) if c is not None else 0.0

    return {
        "start_seconds": start_seconds,
        "synthesis_seconds": synthesis_seconds,
        "synthesis_iterations": count("synthesis_iterations_total"),
        "stage_d_compiles": tier.cache.stats.stage_d_compiles,
        "stage_d_seconds": tier.cache.stats.stage_d_seconds,
        "artifact_hits_program": count("artifact_hits_total",
                                       kind="program"),
        "artifact_hits_executable": count("artifact_hits_total",
                                          kind="executable"),
        "artifact_writes": count("artifact_writes_total", kind="program")
        + count("artifact_writes_total", kind="executable"),
        "artifact_invalid": count("artifact_invalid_total", kind="program")
        + count("artifact_invalid_total", kind="executable"),
        "fingerprint": program.fingerprint(),
        "backend": jax.default_backend(),
    }


def _spawn_phase(phase: str, artifact_dir: str, args) -> Dict:
    """Run one phase in a fresh interpreter and parse its marker line."""
    cmd = [sys.executable, "-m", "benchmarks.warmstart_speedup",
           "--phase", phase, "--artifact-dir", artifact_dir,
           "--net", args.net, "--scale", str(args.scale),
           "--input-hw", str(args.input_hw),
           "--classes", str(args.classes),
           "--max-batch", str(args.max_batch),
           "--replicas", str(args.replicas),
           "--calib", str(args.calib), "--seed", str(args.seed)]
    proc = subprocess.run(cmd, capture_output=True, text=True,
                          env=dict(os.environ))
    if proc.returncode != 0:
        raise RuntimeError(
            f"{phase} phase failed (exit {proc.returncode}):\n"
            f"{proc.stdout}\n{proc.stderr}")
    for line in proc.stdout.splitlines():
        if line.startswith(_MARKER):
            return json.loads(line[len(_MARKER):])
    raise RuntimeError(f"{phase} phase emitted no result marker:\n"
                       f"{proc.stdout}\n{proc.stderr}")


def run(args) -> Dict:
    """Cold-then-warm in two subprocesses; returns the BENCH document."""
    artifact_dir = args.artifact_dir or tempfile.mkdtemp(
        prefix="warmstart_store_")
    cold = _spawn_phase("cold", artifact_dir, args)
    warm = _spawn_phase("warm", artifact_dir, args)

    if warm["fingerprint"] != cold["fingerprint"]:
        raise RuntimeError(
            f"warm phase hydrated fingerprint {warm['fingerprint']} but "
            f"cold converged to {cold['fingerprint']} — the store returned "
            "a different program")

    plan_only = int(warm["stage_d_compiles"] > 0)
    return {
        "benchmark": "warmstart_speedup",
        "schema_version": SCHEMA_VERSION,
        "config": {
            "net": args.net, "scale": args.scale,
            "input_hw": args.input_hw, "max_batch": args.max_batch,
            "replicas": args.replicas, "calib": args.calib,
            "seed": args.seed, "artifact_dir": artifact_dir,
            "backend": cold["backend"],
            "program_fingerprint": cold["fingerprint"],
            "fallback": ("plan-only: Stage-D executables recompiled "
                         "(serialization unavailable on this platform)"
                         if plan_only else "none"),
        },
        "metrics": {
            "cold_start_seconds": cold["start_seconds"],
            "warm_start_seconds": warm["start_seconds"],
            "speedup": cold["start_seconds"] / warm["start_seconds"],
            "cold_synthesis_seconds": cold["synthesis_seconds"],
            "warm_synthesis_seconds": warm["synthesis_seconds"],
            "cold_synthesis_iterations": cold["synthesis_iterations"],
            "warm_synthesis_iterations": warm["synthesis_iterations"],
            "cold_stage_d_compiles": cold["stage_d_compiles"],
            "warm_stage_d_compiles": warm["stage_d_compiles"],
            "cold_stage_d_seconds": cold["stage_d_seconds"],
            "warm_artifact_hits_program": warm["artifact_hits_program"],
            "warm_artifact_hits_executable":
                warm["artifact_hits_executable"],
            "artifact_invalid": cold["artifact_invalid"]
            + warm["artifact_invalid"],
            "plan_only_fallback": plan_only,
        },
        "rows": [
            {"name": "cold_artifact_writes", "value": cold["artifact_writes"]},
            {"name": "warm_artifact_writes", "value": warm["artifact_writes"]},
        ],
    }


def rows(out: str = "BENCH_warmstart.json"):
    """CSV rows for ``benchmarks.run``: the smoke two-process experiment.

    Writes the schema-validated BENCH document as a side effect so the
    ``dryrun_summary`` rollup picks it up like every other suite.
    """
    args = argparse.Namespace(net="squeezenet", scale=0.08, input_hw=64,
                              classes=10, max_batch=4, replicas=1, calib=8,
                              artifact_dir=None, seed=0)
    doc = run(args)
    write_bench(out, doc)
    for name, value in sorted(doc["metrics"].items()):
        yield f"warmstart.{name},{value},"


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", "--dry-run", dest="smoke", action="store_true",
                    help="tiny fast configuration for CI")
    ap.add_argument("--phase", choices=("cold", "warm"), default=None,
                    help=argparse.SUPPRESS)   # internal: child-process mode
    ap.add_argument("--net", default="squeezenet")
    ap.add_argument("--scale", type=float, default=0.08)
    ap.add_argument("--input-hw", type=int, default=64)
    ap.add_argument("--classes", type=int, default=10)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--replicas", type=int, default=1)
    ap.add_argument("--calib", type=int, default=8,
                    help="calibration/validation images for synthesis")
    ap.add_argument("--artifact-dir", default=None, metavar="PATH",
                    help="store root (default: fresh temp dir)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_warmstart.json")
    args = ap.parse_args()

    if args.smoke:
        args.input_hw = min(args.input_hw, 64)
        args.max_batch = min(args.max_batch, 4)
        args.calib = min(args.calib, 8)

    if args.phase:
        if not args.artifact_dir:
            ap.error("--phase requires --artifact-dir")
        result = run_phase(args.artifact_dir, net_name=args.net,
                           scale=args.scale, input_hw=args.input_hw,
                           num_classes=args.classes,
                           max_batch=args.max_batch,
                           replicas=args.replicas, calib=args.calib,
                           seed=args.seed)
        print(_MARKER + json.dumps(result))
        return

    doc = run(args)
    write_bench(args.out, doc)
    m = doc["metrics"]
    print(f"wrote {args.out}: cold {m['cold_start_seconds']:.2f}s -> warm "
          f"{m['warm_start_seconds']:.2f}s ({m['speedup']:.1f}x), "
          f"warm iterations {m['warm_synthesis_iterations']:.0f}, "
          f"warm Stage-D compiles {m['warm_stage_d_compiles']:.0f}"
          + (" [plan-only fallback]" if m["plan_only_fallback"] else ""))


if __name__ == "__main__":
    main()
