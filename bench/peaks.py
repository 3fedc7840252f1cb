"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.  A kind that is not here is an error, not a default."""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, object]] = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "flops_per_s": 197e12,      # bf16 on the MXU
        "source": "Google Cloud TPU v5e documentation: 16 GB of HBM at "
                  "819 GB/s, 197 TFLOP/s bf16 per chip",
    },
}


def peaks(kind: str) -> Dict[str, object]:
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r}; add "
                       f"them to bench/peaks.py with their source")
    return PEAKS[kind]
