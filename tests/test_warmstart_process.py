"""Acceptance: warm start across *processes* — zero synthesis, zero compiles.

The in-memory ProgramCache and XLA's own in-process caching make a
single-process cold/warm comparison meaningless, so this test does what
the warm-start benchmark does: two separate interpreters share one
artifact directory.  The first (cold) pays the fixed-point loop and a
Stage-D compile per bucket; the second (warm) must report

  * ``synthesis_iterations_total`` == 0  (zero-synthesis start), and
  * ``serving_cache_stage_d_compiles_total`` == 0 with one
    ``artifact_hits_total{kind=executable}`` per bucket (zero-recompile
    start) — plan-only platforms skip the compile assertion but still
    must hydrate the program,

via the registry counters of its own process, plus a bitwise-identical
output digest against the cold process.
"""
import json
import os
import subprocess
import sys

import pytest

_PHASE_SCRIPT = r"""
import json, sys
import jax
import jax.numpy as jnp
import numpy as np

jax.config.update("jax_platform_name", "cpu")

from repro.artifacts import ArtifactStore
from repro.cnn import init_network_params
from repro.core import NetworkDescription, run_network, synthesize
from repro.obs import MetricsRegistry
from repro.serving import ReplicaSet, ServingConfig
from repro.serving.loadgen import warm_replicas

artifact_dir = sys.argv[1]

net = NetworkDescription("warmstart_tiny", (3, 8, 8))
net.conv("c1", 8, 3, padding="SAME", inputs=("input",))
net.relu("r1")
net.flatten("f")
net.dense("d1", 4)
params = init_network_params(net, jax.random.PRNGKey(0))
x = jax.random.normal(jax.random.PRNGKey(1), (8, 3, 8, 8))
labels = jnp.argmax(run_network(net, params, x), -1)

registry = MetricsRegistry()
store = ArtifactStore(artifact_dir, registry=registry)
program = synthesize(net, params, validation=(x, labels),
                     max_degradation=0.25, registry=registry,
                     artifact_store=store)
tier = ReplicaSet(program,
                  config=ServingConfig(max_batch=4,
                                       artifact_dir=artifact_dir),
                  registry=registry)
warm_replicas(tier)
out = np.asarray(tier.infer_one(np.asarray(x[0])))

def count(name, **labels):
    c = registry.get(name)
    return float(c.value(**labels)) if c is not None else 0.0

print("PHASE_RESULT " + json.dumps({
    "synthesis_iterations": count("synthesis_iterations_total"),
    "stage_d_compiles": tier.cache.stats.stage_d_compiles,
    "artifact_hits_program": count("artifact_hits_total", kind="program"),
    "artifact_hits_executable": count("artifact_hits_total",
                                      kind="executable"),
    "artifact_invalid": count("artifact_invalid_total", kind="program")
    + count("artifact_invalid_total", kind="executable"),
    "fingerprint": program.fingerprint(),
    "output_digest": __import__("hashlib").sha256(out.tobytes()).hexdigest(),
    "validated": int(program.synthesis_report.validated),
}))
"""


def _run_phase(artifact_dir: str) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORM_NAME"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", _PHASE_SCRIPT, artifact_dir],
                          capture_output=True, text=True, timeout=600,
                          env=env)
    assert proc.returncode == 0, f"phase failed:\n{proc.stdout}\n{proc.stderr}"
    for line in proc.stdout.splitlines():
        if line.startswith("PHASE_RESULT "):
            return json.loads(line[len("PHASE_RESULT "):])
    pytest.fail(f"no result marker in phase output:\n{proc.stdout}")


def test_two_process_warm_start(tmp_path):
    store_dir = str(tmp_path / "store")
    cold = _run_phase(store_dir)
    warm = _run_phase(store_dir)

    # Cold start did real work and persisted it.
    assert cold["synthesis_iterations"] >= 1
    assert cold["stage_d_compiles"] == 3            # buckets 1, 2, 4
    assert cold["validated"] == 1

    # Warm start: zero synthesis iterations, program hydrated from disk.
    assert warm["synthesis_iterations"] == 0
    assert warm["artifact_hits_program"] >= 1
    assert warm["fingerprint"] == cold["fingerprint"]
    assert warm["validated"] == 1                   # audit trail restored

    # Zero Stage-D compiles: every bucket's executable is hydrated.
    assert warm["stage_d_compiles"] == 0
    assert warm["artifact_hits_executable"] == 3
    assert cold["artifact_invalid"] == 0 and warm["artifact_invalid"] == 0

    # Same program, same bits.
    assert warm["output_digest"] == cold["output_digest"]
