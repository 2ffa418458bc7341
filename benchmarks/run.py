"""Benchmark entry point: one function per paper table + roofline summary.

``PYTHONPATH=src python -m benchmarks.run [--fast]``
prints ``name,us_per_call,derived`` CSV rows.

Each suite runs in a child process of its own and the parent never imports
JAX: a chip belongs to one process at a time, and ``warmstart_speedup``
itself starts two children that need it.
"""
from __future__ import annotations

import subprocess
import sys
import traceback

SUITES = ("table1_speedup", "table2_energy_proxy", "table3_vs_klp_flp",
          "mode_selection", "device_sweep", "fusion_speedup", "int8_speedup",
          "warmstart_speedup", "roofline", "dryrun_summary")


def run_suite(name: str, reps: int) -> None:
    """Child mode: print one suite's CSV rows."""
    import importlib

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    mod = importlib.import_module(f"benchmarks.{name}")
    if name == "warmstart_speedup":
        rows = mod.rows()
    elif name in ("mode_selection", "roofline", "dryrun_summary"):
        rows = mod.run()
    else:
        rows = mod.run(reps=reps)
    for row in rows:
        print(row, flush=True)


def main() -> None:
    reps = 4 if "--fast" in sys.argv else 8
    if "--suite" in sys.argv:
        try:
            run_suite(sys.argv[sys.argv.index("--suite") + 1], reps)
        except Exception:
            traceback.print_exc()
            sys.exit(1)
        return
    print("name,us_per_call,derived", flush=True)
    failed = [name for name in SUITES
              if subprocess.run([sys.executable, "-m", "benchmarks.run",
                                 "--suite", name, *sys.argv[1:]]).returncode]
    if failed:
        print(f"# FAILED suites: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
