"""The reduction from a profiler trace to busy time, kernel time and idle
gaps: on a hand-made trace with known answers, and on a small recorded
TPU v5e trace."""
import os

import pytest

from bench import trace
from bench.trace import Event

DATA = os.path.join(os.path.dirname(__file__), "data")


US = 1e3     # the hand-made trace counts in microseconds


def _hand_trace():
    ops = [Event("%fusion.1 = bf16[8] fusion()", 0 * US, 100 * US),
           Event("%fusion.2 = bf16[8] fusion()", 50 * US, 100 * US),
           Event('%_conv2d_mapmajor_pallas.3 = bf16[32,2,26,26,128] '
                 'custom-call(), custom_call_target="tpu_custom_call"',
                 300 * US, 200 * US),
           Event("%fusion.4 = bf16[8] fusion()", 900 * US, 200 * US)]
    host = [Event("tpu::System::TransferToDevice", 160 * US, 100 * US),
            Event("serve.dispatch", 100 * US, 900 * US),
            Event("bench.wait", 600 * US, 200 * US),
            Event("bench.submit", 1005 * US, 2 * US)]
    return trace.Trace({"TPU:0": ops}, host)


def test_busy_is_the_union_of_op_intervals():
    r = trace.reduce(_hand_trace(), 0, 1000 * US)
    # [0,150] + [300,500] + [900,1000] inside a 1000 us window
    assert r.busy_s["TPU:0"] == pytest.approx(450e-6)
    assert r.idle_share == pytest.approx(0.55)
    assert r.window_s == pytest.approx(1e-3)


def test_launch_time_and_op_names():
    r = trace.reduce(_hand_trace(), 0, 1000 * US)
    assert r.launch_s == {"_conv2d_mapmajor_pallas": pytest.approx(200e-6)}
    assert r.launches == {"_conv2d_mapmajor_pallas": 1}
    cats = dict(r.top_ops)
    assert cats["fusion"] == pytest.approx(300e-6)   # 100 + 100 + 100 clipped
    assert cats["_conv2d_mapmajor_pallas"] == pytest.approx(200e-6)


def test_idle_gaps_go_to_the_most_specific_host_activity():
    r = trace.reduce(_hand_trace(), 0, 1000 * US)
    idle = dict(r.idle_by_host)
    # gap [150,300]: the transfer covers [160,260], the dispatch span the
    # rest; gap [500,900]: the dispatch span outranks the client's wait
    assert idle["host->device copy"] == pytest.approx(100e-6)
    assert idle["serve.dispatch, other host work"] == pytest.approx(450e-6)
    assert "client" not in idle
    assert sum(idle.values()) == pytest.approx(550e-6)


def test_short_gaps_are_bubbles_and_uncovered_gaps_unattributed():
    r = trace.reduce(_hand_trace(), 0, 1200 * US)
    idle = dict(r.idle_by_host)
    # [1100,1200]: no host event covers it
    assert idle["unattributed"] == pytest.approx(100e-6)
    t = trace.Trace({"TPU:0": [Event("%a.1 = x", 0, 10),
                               Event("%b.2 = x", 20, 10)]}, [])
    assert dict(trace.reduce(t, 0, 30).idle_by_host) == {
        "bubble between ops": pytest.approx(10e-9)}


def test_saved_trace_round_trips(tmp_path):
    t = _hand_trace()
    path = str(tmp_path / "t.json.gz")
    trace.save(t, path)
    back = trace.read(path)
    assert trace.reduce(back, 0, 1000 * US) == trace.reduce(t, 0, 1000 * US)


def test_recorded_v5e_trace():
    """120 ms of an `alexnet.closed64` window on one v5e (bucket 32),
    device operations and the host events the reduction reads."""
    t = trace.read(os.path.join(DATA, "v5e_alexnet_closed64.json.gz"))
    assert t.chips() == ["TPU:0"]
    r = trace.reduce(t, 0, 120e6)
    assert r.busy_s["TPU:0"] == pytest.approx(0.01994821)
    assert r.idle_share == pytest.approx(1 - 0.01994821 / 0.12)
    # conv2 is one conv_mapmajor launch a bucket, fc6-fc8 three
    # matmul_mapmajor launches; 5 whole buckets and 6 of fc launches fall
    # in this slice of the window
    assert r.launches == {"_conv2d_mapmajor_pallas": 5, "_matmul_padded": 18}
    assert r.launch_s["_conv2d_mapmajor_pallas"] == pytest.approx(0.002563633)
    assert r.launch_s["_matmul_padded"] == pytest.approx(0.001585636)
    cats = dict(r.top_ops)
    assert set(cats) >= {"fusion", "reduce_window_max", "copy",
                         "_conv2d_mapmajor_pallas", "_matmul_padded"}
    idle = dict(r.idle_by_host)
    assert sum(idle.values()) == pytest.approx(0.12 - 0.01994821)
    assert idle["host->device copy"] > idle["device->host copy"] > 0
