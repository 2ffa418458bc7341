"""Cross-device synthesis sweep: one network, every registered device.

The paper's Table I runs the same synthesis flow on three mobile SoCs and
shows the *chosen programs differ per device*.  This benchmark is our
analogue: it synthesizes the reference CNN against every profile in the
device registry (``tpu_v5e``, ``tpu_v4``, ``cpu_interpret``, plus anything
registered at runtime) and reports where the chosen plans diverge.

Two views per device:

  * **target-native plan** — the static planner run *as if deploying to
    that device* (``allow_pallas`` from the profile, every cost rule on the
    profile's numbers).  This is what diverges: ridge points move the
    rule-3 boundary, VMEM budgets move the rule-1 envelope, and
    interpret-only targets get no Pallas at all.  The per-layer
    (impl, u, mode) choices feed the divergence rows.
  * **synthesized program** — the full ``synthesize(..., device=...)``
    pipeline (fixed-point loop + validation gate) on this host, proving the
    device threads end to end and that per-device fingerprints are
    distinct: the same network admitted under every profile yields one
    ProgramCache entry per device.

Emits schema-validated ``BENCH_device_sweep.json``:

  PYTHONPATH=src python -m benchmarks.device_sweep --dry-run
"""
from __future__ import annotations

import argparse
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro.cnn import alexnet, init_network_params
from repro.core import (ComputeMode, IMPL_PALLAS, PlannerConfig, plan_network,
                        run_network, synthesize)
from repro.device import DeviceProfile, registered_profiles
from repro.serving import ProgramCache
from repro.launch.compile_cache import enable_compile_cache

from .bench_schema import SCHEMA_VERSION, write_bench
from .common import csv_row

PlanChoice = Tuple[str, int, str]        # (impl, u, mode) per layer


def target_native_plans(net, profiles) -> Dict[str, Dict[str, PlanChoice]]:
    """profile name -> layer -> (impl, u, mode) under target-native rules."""
    relaxed = {n: ComputeMode.RELAXED for n in net.inexactable_layers}
    out: Dict[str, Dict[str, PlanChoice]] = {}
    for p in profiles:
        cfg = PlannerConfig(profile=p, allow_pallas=p.supports_pallas)
        plan = plan_network(net, modes=relaxed, config=cfg)
        out[p.name] = {
            l.name: (plan.for_layer(l.name).impl, plan.for_layer(l.name).u,
                     plan.for_layer(l.name).mode.value)
            for l in net.param_layers}
    return out


def divergence(per_device: Dict[str, Dict[str, PlanChoice]]
               ) -> Dict[str, int]:
    """layer -> number of distinct (impl, u, mode) choices across devices."""
    layers = next(iter(per_device.values())).keys()
    return {layer: len({choices[layer] for choices in per_device.values()})
            for layer in layers}


def sweep(profiles: "List[DeviceProfile]", *, scale: float, input_hw: int,
          calibration: int, seed: int = 0) -> dict:
    net = alexnet(scale=scale, num_classes=10, input_hw=input_hw)
    params = init_network_params(net, jax.random.PRNGKey(seed))
    cal_x = jax.random.normal(jax.random.PRNGKey(seed + 1),
                              (calibration, 3, input_hw, input_hw))
    cal_labels = jnp.argmax(run_network(net, params, cal_x), -1)

    native = target_native_plans(net, profiles)
    div = divergence(native)

    cache = ProgramCache()
    fingerprints: Dict[str, str] = {}
    validated_acc: Dict[str, float] = {}
    for p in profiles:
        prog = synthesize(net, params, validation=(cal_x, cal_labels),
                          max_degradation=0.0, device=p)
        fingerprints[p.name] = prog.fingerprint()
        final = prog.synthesis_report.final_validation
        validated_acc[p.name] = final.accuracy if final is not None else 0.0
        cache.admit(prog)

    baseline = profiles[0].name
    return {
        "net": net.name,
        "profiles": [p.name for p in profiles],
        "native": native,
        "divergence": div,
        "fingerprints": fingerprints,
        "validated_acc": validated_acc,
        "cache_entries": cache.programs,
        "baseline": baseline,
    }


def to_bench_doc(r: dict, *, scale: float, input_hw: int,
                 calibration: int) -> dict:
    native, div = r["native"], r["divergence"]
    baseline = r["baseline"]
    rows: List[dict] = []
    for layer, distinct in sorted(div.items()):
        rows.append({"name": f"divergence.{layer}", "value": distinct})
    for name in r["profiles"]:
        choices = native[name]
        pallas = sum(1 for c in choices.values() if c[0] == IMPL_PALLAS)
        differs = sum(1 for layer in choices
                      if choices[layer] != native[baseline][layer])
        rows.append({"name": f"{name}.pallas_layers", "value": pallas})
        rows.append({"name": f"{name}.layers_diverging_from_{baseline}",
                     "value": differs})
        rows.append({"name": f"{name}.validated_acc",
                     "value": r["validated_acc"][name]})
    return {
        "benchmark": "device_sweep",
        "schema_version": SCHEMA_VERSION,
        "config": {"net": r["net"], "scale": scale, "input_hw": input_hw,
                   "calibration": calibration,
                   "backend": jax.default_backend(),
                   "profiles": r["profiles"],
                   "fingerprints": r["fingerprints"]},
        "metrics": {
            "profiles": len(r["profiles"]),
            "layers_compared": len(div),
            "divergent_layers": sum(1 for v in div.values() if v > 1),
            "distinct_fingerprints": len(set(r["fingerprints"].values())),
            "cache_entries": r["cache_entries"],
        },
        "rows": rows,
    }


def run(reps: int = 0) -> List[str]:
    """CSV rows for benchmarks.run (reps unused: planning is static)."""
    r = sweep(list(registered_profiles()), scale=0.1, input_hw=67,
              calibration=8)
    out = []
    for layer, distinct in sorted(r["divergence"].items()):
        out.append(csv_row(f"device_sweep.divergence.{layer}", 0.0,
                           f"distinct={distinct}"))
    out.append(csv_row("device_sweep.fingerprints", 0.0,
                       f"distinct={len(set(r['fingerprints'].values()))}"
                       f"/{len(r['profiles'])}"))
    return out


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--dry-run", action="store_true",
                    help="small network + tiny calibration set: validates "
                         "the pipeline + schema, numbers indicative only")
    ap.add_argument("--scale", type=float, default=0.25)
    ap.add_argument("--input-hw", type=int, default=115)
    ap.add_argument("--calibration", type=int, default=32)
    ap.add_argument("--out", default="BENCH_device_sweep.json")
    args = ap.parse_args()
    scale = 0.1 if args.dry_run else args.scale
    input_hw = 67 if args.dry_run else args.input_hw
    calibration = 8 if args.dry_run else args.calibration

    profiles = list(registered_profiles())
    r = sweep(profiles, scale=scale, input_hw=input_hw,
              calibration=calibration)

    print(f"device sweep: {r['net']} across {', '.join(r['profiles'])}")
    for layer, distinct in sorted(r["divergence"].items()):
        marks = "  ".join(f"{n}={'/'.join(map(str, r['native'][n][layer]))}"
                          for n in r["profiles"])
        flag = " <- diverges" if distinct > 1 else ""
        print(f"  {layer:24s} {marks}{flag}")
    print(f"fingerprints: {r['fingerprints']}")
    print(f"program cache entries: {r['cache_entries']} "
          f"(one per device, never aliased)")

    write_bench(args.out, to_bench_doc(r, scale=scale, input_hw=input_hw,
                                       calibration=calibration))
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
