"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

The yardstick keeps its own table: a roofline or utilization is measured
against what the vendor publishes, not against the program's cost model.
A device kind missing from the table is an error, never a default.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    bf16_flops: float         # FLOP/s, dense bfloat16 matrix units
    int8_ops: float           # OP/s, int8
    hbm_bytes_per_s: float    # bytes/s
    source: str

    def flops_for(self, dtype: str) -> float:
        """Peak rate for an operand type: ``"bf16"`` or ``"int8"``."""
        return {"bf16": self.bf16_flops, "int8": self.int8_ops}[dtype]


PEAKS = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12, int8_ops=393e12, hbm_bytes_per_s=819e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "393 TOP/s int8, 16 GB HBM at 819 GB/s per chip"),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
