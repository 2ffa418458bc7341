"""Readers of the dispatch-phase and per-request spans, on hand-made
spans, against values worked by hand; None where the spans are absent,
as they are in a program that does not record them."""
import pytest

from bench.phases import PHASES
from bench.tests.test_bench_metrics import _ctx, _read
from repro.obs.trace import Span

#: (wall ms, cpu ms) of each phase in the two hand-made dispatches.
WORK = {"lookup": [(0.2, 0.2), (0.4, 0.3)],
        "assemble": [(3.0, 2.0), (5.0, 4.0)],
        "transfer": [(1.0, 1.0), (1.0, 0.5)],
        "execute": [(2.0, 0.1), (2.0, 0.1)],
        "copy_out": [(0.5, 0.5), (0.5, 0.5)],
        "complete": [(2.0, 0.5), (4.0, 1.5)]}


def _spans():
    spans, sid = [], 0
    for d in range(2):
        t = 1.0 + 0.5 * d
        sid += 1
        parent = sid
        total = sum(w[d][0] for w in WORK.values()) / 1e3
        spans.append(Span("serve.dispatch", parent, None, t, t + total,
                          attrs={"batch": 4, "requests": 4}))
        for phase in PHASES:
            wall, cpu = WORK[phase][d]
            sid += 1
            spans.append(Span("serve.dispatch." + phase, sid, parent, t,
                              t + wall / 1e3, attrs={"cpu_s": cpu / 1e3}))
            t += wall / 1e3
    for i, q in enumerate((0.001, 0.002, 0.003, 0.010)):
        sid += 1
        spans.append(Span("serve.request", sid, None, 1.0 + 0.01 * i,
                          1.1, attrs={"queue_s": q, "dispatch": 1}))
    return spans


@pytest.mark.parametrize("suffix", ["tput", "lat"])
@pytest.mark.parametrize("phase", PHASES)
def test_phase_ms_by_hand(phase, suffix):
    name = f"dispatch_{phase}_ms.{suffix}"
    want = sum(w for w, _ in WORK[phase]) / 2
    assert _read(name, _ctx(spans=_spans())) == pytest.approx(want)
    assert _read(name, _ctx(spans=[])) is None
    assert _read(name, _ctx(spans=None)) is None


@pytest.mark.parametrize("suffix", ["tput", "lat"])
def test_offcpu_share_by_hand(suffix):
    name = f"dispatch_offcpu_share.{suffix}"
    # execute left out: wall 0.2+0.4+3+5+1+1+0.5+0.5+2+4 = 17.6 ms,
    # cpu 0.2+0.3+2+4+1+0.5+0.5+0.5+0.5+1.5 = 11.0 ms
    assert _read(name, _ctx(spans=_spans())) == pytest.approx(
        1 - 11.0 / 17.6)
    assert _read(name, _ctx(spans=[])) is None


def test_queue_wait_by_hand():
    assert _read("queue_wait_ms.tput", _ctx(spans=_spans())) == \
        pytest.approx(4.0)
    assert _read("queue_wait_ms.tput", _ctx(spans=[])) is None


def test_phases_sum_to_dispatch_ms():
    ctx = _ctx(spans=_spans())
    total = sum(_read(f"dispatch_{p}_ms.lat", ctx) for p in PHASES)
    assert total == pytest.approx(_read("dispatch_ms.lat", ctx))
