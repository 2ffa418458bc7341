"""The dispatch-overlap reader on hand-made ``serve.dispatch`` spans,
against values worked by hand; None where no span carries
``overlapped``, as in a program that dispatches one bucket at a time."""
import pytest

from bench.tests.test_bench_metrics import _ctx, _read
from repro.obs.trace import Span

NAME = "dispatch_overlap_share.tput"


def _dispatches(overlapped):
    return [Span("serve.dispatch", i + 1, None, 1.0 + i, 1.5 + i,
                 attrs={"batch": 32} if o is None else
                 {"batch": 32, "overlapped": o})
            for i, o in enumerate(overlapped)]


@pytest.mark.parametrize("overlapped, want", [
    ([1, 1, 1, 1], 1.0),
    ([0, 1, 1, 1], 0.75),
    ([0, 0, 1], 1 / 3),
    ([0, 0], 0.0),
    ([None, None], None),      # spans without the attribute
    ([], None),
])
def test_dispatch_overlap_share_by_hand(overlapped, want):
    got = _read(NAME, _ctx(spans=_dispatches(overlapped)))
    assert got == (None if want is None else pytest.approx(want))


def test_dispatch_overlap_share_reads_only_dispatch_spans():
    spans = _dispatches([1, 0]) + [
        Span("serve.dispatch.execute", 9, 1, 1.0, 1.4,
             attrs={"cpu_s": 0.1, "overlapped": 0})]
    assert _read(NAME, _ctx(spans=spans)) == pytest.approx(0.5)


def test_dispatch_overlap_share_without_spans():
    assert _read(NAME, _ctx(spans=None)) is None
