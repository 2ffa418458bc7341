"""The comparison that decides ``correct``.

Every request sent in the measured window is due: it must come back (a
minute past the window's close at the latest), must not fail, and its
row must agree with the float32 reference of the pool image it carried.
A row is compared as log-probabilities: both rows are centred (softmax
ignores a shift) and the RMS of their difference is divided by the
reference row's standard deviation.  That is scale-free, so one limit
holds for networks whose logits span 5 units or 50, and a row served to
the wrong request, or computed from a wrong tap, channel group or bucket
row, reads a large fraction of 1.

The limit sits between two readings taken on the chip (PERF.md gives
them): above the largest error of sound RELAXED runs over a dozen seeds
or more, and below the smallest error of the program's own int8 path
(IMPRECISE_INT8, the next precision down) on the same traffic.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

#: Largest relative row error admitted (see the module docstring, and
#: PERF.md for the readings it was set from).
ROW_ERR_LIMIT = 0.011
#: Most requests compared in one run; a larger window compares a sample
#: drawn from the seed.
MAX_COMPARED = 16384
#: Seconds past the window's close that a due request may still come.
LATE_S = 60.0


def log_probs(p: np.ndarray) -> np.ndarray:
    return np.log(np.maximum(np.asarray(p, np.float64), 1e-30))


def row_errors(served: np.ndarray, reference: np.ndarray) -> np.ndarray:
    """Relative error of each served row against its reference row."""
    s, r = log_probs(served), log_probs(reference)
    d = s - r
    d -= d.mean(axis=1, keepdims=True)
    # A constant reference row (every class scored alike) admits only a
    # constant served row.
    err = np.sqrt((d ** 2).mean(axis=1)) / np.maximum(r.std(axis=1), 1e-12)
    return np.where(np.isfinite(err), err, np.inf)


def compare(outputs: np.ndarray, pool_idx: np.ndarray,
            reference_rows: Dict[int, np.ndarray]) -> float:
    """Largest relative row error of ``outputs`` against the reference row
    of each one's pool image."""
    if len(outputs) == 0:
        return float("inf")
    ref = np.stack([reference_rows[int(i)] for i in pool_idx])
    return float(row_errors(outputs, ref).max())


def checks_line(checks: Dict[str, Dict[str, float]]) -> List[str]:
    """One line per compared number, with its limit."""
    return [f"check {name}: {c['value']} (limit {c['limit']})"
            for name, c in checks.items()]
