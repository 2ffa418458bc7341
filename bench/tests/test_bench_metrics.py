"""Metric readers on a hand-made context, against values worked by hand."""
import pytest

from bench import flops, harness, peaks, trace
from bench.gen.closed import Sent
from repro.core.network import NetworkDescription


def _ctx(**kw):
    net = NetworkDescription("t", (3, 8, 8))
    net.conv("c", 4, 3, padding="SAME", inputs=("input",))
    net.relu("r")
    net.flatten("f")
    net.dense("d", 10)
    reqs = [Sent(0, 1.0 + 0.1 * i, None, t_done=1.05 + 0.1 * i, ok=True)
            for i in range(10)]
    reduced = trace.Reduced(
        window_s=1.0, busy_s={"TPU:0": 0.25},
        launch_s={"_conv2d_mapmajor_pallas": 2e-6}, launches={},
        top_ops=[], idle_by_host=[])
    base = dict(workload="w", chips=1, net=net, mode="relaxed", seconds=1.0,
                setup_s=3.0, requests=reqs, t_open=1.0, t_close=2.0,
                stats={"batches": 5, "padded_slots": 2, "dispatched_slots": 10,
                       "real_rows": 8, "bucket_counts": {2: 5},
                       "completed": 8},
                pallas_groups={"conv": ["c"]},
                peaks=peaks.peaks_for("TPU v5 lite"), reduced=reduced)
    base.update(kw)
    return harness.Context(**base)


def _read(name, ctx):
    return harness.read_metrics([{"name": name, "unit": "u"}],
                                ctx).get(name, {}).get("value")


def test_conv_roofline_by_hand():
    ctx = _ctx()
    cost = flops.layer_cost(ctx.net, "c", 2)
    ideal = max(cost.flops / 197e12, cost.bytes / 819e9)
    assert _read("conv_mapmajor_roofline", ctx) == pytest.approx(
        100 * 5 * ideal / 2e-6)
    # no launch in the trace, or no group on the kernel: nothing to read
    assert _read("matmul_mapmajor_roofline", ctx) is None


def test_throughput_latency_and_shares():
    ctx = _ctx()
    # t_done 1.05 .. 1.95 all inside [1, 2]
    assert _read("throughput", ctx) == pytest.approx(10.0)
    assert _read("latency_p50_ms", ctx) == pytest.approx(50.0)
    assert _read("bucket_fill.tput", ctx) == pytest.approx(0.8)
    assert _read("device_idle_share.tput", ctx) == pytest.approx(0.75)
    assert _read("device_ms_per_bucket.tput", ctx) == pytest.approx(50.0)
    assert _read("setup_s", ctx) == 3.0
    work = flops.model_flops_per_image(ctx.net) * 10
    assert _read("mfu", ctx) == pytest.approx(100 * work / 197e12)
