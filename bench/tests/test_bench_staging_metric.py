"""The staging-buffer reader on hand-made ``serve.dispatch.assemble``
spans, against values worked by hand; None where no span carries
``staged``, as in a program without a staging buffer."""
import pytest

from bench.tests.test_bench_metrics import _ctx, _read
from repro.obs.trace import Span

NAME = "staging_reuse_share.tput"


def _assemble(staged):
    return [Span("serve.dispatch.assemble", i + 1, None, 1.0 + i, 1.5 + i,
                 attrs={"cpu_s": 0.1} if s is None else
                 {"cpu_s": 0.1, "staged": s})
            for i, s in enumerate(staged)]


@pytest.mark.parametrize("staged, want", [
    ([1, 1, 1, 1], 1.0),
    ([0, 1, 1, 1], 0.75),
    ([0, 0], 0.0),
    ([None, None], None),      # spans without the attribute
    ([], None),
])
def test_staging_reuse_share_by_hand(staged, want):
    got = _read(NAME, _ctx(spans=_assemble(staged)))
    assert got == (None if want is None else pytest.approx(want))


def test_staging_reuse_share_without_spans():
    assert _read(NAME, _ctx(spans=None)) is None
