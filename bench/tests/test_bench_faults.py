"""A run whose timed path is broken underneath comes out not correct.

Drives the whole of ``harness.run_cell`` on the CPU at a small size,
skipping only the look for a chip.  Each fault is planted in the Stage-D
executables the tier dispatches, where the answers are produced:

* ``altered``: one answer of every bucket changed (classes rolled by one);
* ``half_batch``: half of every bucket's rows left out (zeros);
* ``scattered``: the rows of every bucket handed back in reverse order.

A cell of this benchmark has no step state and no exchange between chips
(its replicas serve independently), so those faults do not apply.
"""
import jax.numpy as jnp
import pytest

from bench import check, harness
from repro.serving.program_cache import ProgramCache

FAULTS = {
    "altered": lambda y: y.at[0].set(jnp.roll(y[0], 1)),
    "half_batch": lambda y: y.at[y.shape[0] // 2:].set(0.0),
    "scattered": lambda y: y[::-1],
}


def small_cell(mode="relaxed"):
    cell = harness.load_cell("squeezenet.closed64")
    cell.config = dict(cell.config, mode=mode,
                       net={"scale": 0.25, "num_classes": 100,
                            "input_hw": 64},
                       serving=dict(cell.config["serving"], max_batch=4))
    cell.traffic = dict(cell.traffic, outstanding=8, pool=16, ramp_s=0.2)
    return cell


def run(cell, seed=2**31 + 11):
    return harness.run_cell(cell, seed, 0.3, False, t_process=0.0,
                            require_tpu=False)


class _Broken:
    def __init__(self, exe, fault):
        self.exe, self.fault = exe, fault

    def __call__(self, x):
        return self.fault(self.exe(x))


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    monkeypatch.setattr(harness, "configure_jax", lambda: None)


def test_sound_run_is_correct():
    line = run(small_cell())
    assert line["correct"], line["checks"]
    assert line["failed"] == 0
    assert line["attempted"] > 0
    assert 0 < line["checks"]["row_err_max"]["value"] < check.ROW_ERR_LIMIT
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(monkeypatch, fault):
    orig = ProgramCache.get_or_build

    def broken(self, program, batch, device=None):
        return _Broken(orig(self, program, batch, device), FAULTS[fault])

    monkeypatch.setattr(ProgramCache, "get_or_build", broken)
    line = run(small_cell())
    assert not line["correct"]
    assert line["checks"]["row_err_max"]["value"] > check.ROW_ERR_LIMIT


def test_control_is_not_correct():
    """The program's own int8 path, the precision below the
    configuration's bfloat16, reads above the limit."""
    line = run(small_cell(mode="imprecise_int8"))
    assert not line["correct"]
    assert line["checks"]["row_err_max"]["value"] > check.ROW_ERR_LIMIT
