"""Exact percentiles, and FLOP/byte counts against hand-worked values."""
import math

import pytest

from bench import flops, stats
from repro.core.network import NetworkDescription


@pytest.mark.parametrize("q,rank", [(50, 5), (99, 10), (10, 1), (100, 10),
                                    (51, 6)])
def test_percentile_is_an_order_statistic(q, rank):
    sample = [7.0, 1.0, 9.0, 3.0, 5.0, 2.0, 8.0, 4.0, 10.0, 6.0]
    assert stats.percentile(sample, q) == sorted(sample)[rank - 1]
    assert stats.percentile(sample, q) in sample


def test_percentile_counts_missing_requests_as_late():
    sample = [1.0] * 98 + [math.inf] * 2
    assert stats.percentile(sample, 99) == math.inf
    assert stats.percentile(sample, 50) == 1.0


def _net():
    net = NetworkDescription("t", (3, 8, 8))
    net.conv("c", 4, 3, stride=1, padding="SAME", inputs=("input",))
    net.relu("r")
    net.flatten("f")
    net.dense("d", 10)
    return net


def test_conv_layer_cost_by_hand():
    # 8x8 SAME 3x3 conv, 3 -> 4 channels, batch 2: per image
    # 4*8*8 outputs x 3*3*3 taps = 6912 MACs.
    c = flops.layer_cost(_net(), "c", 2)
    assert c.flops == 2 * 2 * 6912
    # bf16 input 2*3*64, weights 4*3*9, output 2*4*64; f32 bias 4.
    assert c.bytes == 2 * (2 * 3 * 64) + 2 * (4 * 3 * 9) + 4 * 4 \
        + 2 * (2 * 4 * 64)


def test_dense_layer_cost_by_hand():
    # 256 -> 10 at batch 3: 2560 MACs per image.
    d = flops.layer_cost(_net(), "d", 3)
    assert d.flops == 2 * 3 * 2560
    assert d.bytes == 2 * 3 * 256 + 2 * 2560 + 4 * 10 + 2 * 3 * 10
    assert d.ideal_seconds(1e12, 1e9) == pytest.approx(d.bytes / 1e9)


def test_model_flops_sum_conv_and_dense():
    assert flops.model_flops_per_image(_net()) == 2 * (6912 + 2560)
