"""At a small width the float32 reference agrees with the program."""
import numpy as np
import pytest

from bench import check, reference, weights
from repro.cnn import WORKLOADS
from repro.core import synthesize
from repro.core.precision import ComputeMode

SMALL = {"squeezenet": {"scale": 0.25, "num_classes": 100, "input_hw": 64},
         "alexnet": {"scale": 0.125, "num_classes": 100, "input_hw": 67}}


@pytest.fixture(scope="module", params=sorted(SMALL))
def small(request):
    net = WORKLOADS[request.param](**SMALL[request.param])
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


def test_row_error_catches_a_row_of_another_image(small):
    net, params, x, ref = small
    swapped = ref[::-1].copy()
    assert check.row_errors(swapped, ref).min() > 3 * check.ROW_ERR_LIMIT
