"""GoogLeNet's configuration and the four-replica SqueezeNet cell, on the
CPU at small sizes.

GoogLeNet is checked as the other networks are: the PRECISE program
agrees with the float32 reference, RELAXED stays inside the limit, and
the whole timed path of ``googlenet.closed64``, shrunk, comes out
correct, and not correct once its buckets' rows come back scattered.
``squeezenet.x4.closed256`` runs in a child process that sees four host
devices, one per replica, as the cell's four chips.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import check, harness, reference, weights
from repro.cnn import WORKLOADS
from repro.core import synthesize
from repro.core.precision import ComputeMode
from repro.serving.program_cache import ProgramCache

SMALL_NET = {"scale": 0.25, "num_classes": 100, "input_hw": 64}
SEED = 2**31 + 23


@pytest.fixture(scope="module")
def small():
    net = WORKLOADS["googlenet"](**SMALL_NET)
    params = weights.make(net, 7)          # bfloat16, as served
    x = np.random.default_rng(7).standard_normal(
        (4, *net.input_shape), dtype=np.float32)
    ref = reference.make(net, params, block=4)(x)
    return net, params, x, ref


def test_reference_matches_the_precise_program(small):
    net, params, x, ref = small
    program = synthesize(net, params, forced_mode=ComputeMode.PRECISE)
    out = np.asarray(program.infer(x))
    assert check.row_errors(out, ref).max() < 1e-4
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-7)


def test_relaxed_program_within_the_limit(small):
    net, params, x, ref = small
    program = synthesize(net, params, forced_mode=ComputeMode.RELAXED)
    err = check.row_errors(np.asarray(program.infer(x)), ref)
    assert 0 < err.max() < check.ROW_ERR_LIMIT
    swapped = ref[::-1].copy()
    assert check.row_errors(swapped, ref).min() > 3 * check.ROW_ERR_LIMIT


def small_cell():
    cell = harness.load_cell("googlenet.closed64")
    cell.config = dict(cell.config, net=SMALL_NET,
                       serving=dict(cell.config["serving"], max_batch=4))
    cell.traffic = dict(cell.traffic, outstanding=8, pool=16, ramp_s=0.2)
    return cell


def run(cell):
    return harness.run_cell(cell, SEED, 0.3, False, t_process=0.0,
                            require_tpu=False)


@pytest.fixture
def no_persistent_cache(monkeypatch):
    monkeypatch.setattr(harness, "configure_jax", lambda: None)


def test_googlenet_cell_is_correct(no_persistent_cache):
    cell = harness.load_cell("googlenet.closed64")
    assert cell.chips == 1
    assert cell.config["net"] == {"scale": 1.0, "num_classes": 1000,
                                  "input_hw": 224}
    assert cell.traffic["outstanding"] == 64
    line = run(small_cell())
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0
    assert 0 < line["checks"]["row_err_max"]["value"] < check.ROW_ERR_LIMIT


def test_googlenet_scattered_rows_are_not_correct(no_persistent_cache,
                                                  monkeypatch):
    orig = ProgramCache.get_or_build

    def scattered(self, program, batch, device=None):
        exe = orig(self, program, batch, device)
        return lambda x: exe(x)[::-1]

    monkeypatch.setattr(ProgramCache, "get_or_build", scattered)
    line = run(small_cell())
    assert not line["correct"]
    assert line["checks"]["row_err_max"]["value"] > check.ROW_ERR_LIMIT


#: Runs a shrunk ``squeezenet.x4.closed256`` and prints the result line
#: with the buckets each replica dispatched.
_X4_CHILD = """
import json, sys
sys.path[:0] = [{root!r}, {src!r}]
from bench import harness
from repro.serving import ReplicaSet

batches = []
stop = ReplicaSet.stop

def recording_stop(self, *a, **kw):
    stop(self, *a, **kw)
    batches[:] = [r.server.stats.batches for r in self.replicas]

ReplicaSet.stop = recording_stop
harness.configure_jax = lambda: None
cell = harness.load_cell("squeezenet.x4.closed256")
cell.config = dict(cell.config,
                   net={{"scale": 0.25, "num_classes": 100, "input_hw": 64}},
                   serving=dict(cell.config["serving"], max_batch=4))
cell.traffic = dict(cell.traffic, outstanding=32, pool=16, ramp_s=0.2)
line = harness.run_cell(cell, {seed}, 0.5, False, t_process=0.0,
                        require_tpu=False)
print(json.dumps({{"line": line, "batches": batches}}))
"""


def test_four_replica_cell_serves_from_every_chip():
    cell = harness.load_cell("squeezenet.x4.closed256")
    assert cell.chips == 4
    assert cell.traffic["outstanding"] == 256
    assert cell.config["serving"]["dispatch"] == "least_loaded"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = _X4_CHILD.format(root=harness.ROOT,
                            src=os.path.join(harness.ROOT, "src"), seed=SEED)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=600, env=env, cwd=harness.ROOT)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    line = out["line"]
    assert line["correct"], line["checks"]
    assert line["device"]["count"] == 4
    assert line["checks"]["foreign_rows"]["value"] == 0
    assert len(out["batches"]) == 4 and min(out["batches"]) >= 1, out
    assert line["metrics"]["throughput"]["value"] > 0
