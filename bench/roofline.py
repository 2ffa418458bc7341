"""A kernel's share of its roofline over a traced window.

The least time the chip could take for the kernel's launches in the
window -- for each bucket dispatched, and each fused group the plan
routed to a Pallas kernel with an anchor of the kernel's kind, the
larger of its operations over the peak rate and its bytes over HBM
bandwidth (``flops.py``) -- over the summed device time of the kernel's
launches in the trace, found by their names.  The window's mix of
buckets comes from the tier's counters over the same window.
"""
from __future__ import annotations

from typing import Optional, Sequence


def kernel_roofline(ctx, anchor_kind: str,
                    launch_names: Sequence[str]) -> Optional[float]:
    """Percent of the roofline reached by the Pallas groups whose anchor
    is a ``anchor_kind`` layer, launched under ``launch_names``; None
    where the plan routes no such group or the trace holds no launch."""
    groups = ctx.pallas_groups.get(anchor_kind, [])
    if ctx.peaks is None or ctx.reduced is None or not groups:
        return None
    measured = sum(ctx.reduced.launch_s.get(n, 0.0) for n in launch_names)
    if measured <= 0:
        return None
    peak = ctx.peaks.flops_for(ctx.operand)
    bw = ctx.peaks.hbm_bytes_per_s
    ideal = sum(n * ctx.group_cost(g, batch).ideal_seconds(peak, bw)
                for batch, n in ctx.stats["bucket_counts"].items()
                for g in groups)
    return 100.0 * ideal / measured

