"""Published peaks of the chips the benchmark runs on, keyed by JAX's
`device_kind`.  A device that is not in the table is an error, never a
default: a share of an unknown peak is no number at all.
"""
from __future__ import annotations

from typing import Dict

_V5E = {
    "bf16_flops_per_s": 197e12,
    "hbm_bytes_per_s": 819e9,
    "hbm_bytes": 16e9,
    "source": "Google Cloud documentation, \"TPU v5e\" (per chip)",
}

PEAKS: Dict[str, Dict[str, object]] = {
    "TPU v5 lite": _V5E,   # what JAX 0.9 reports for a v5e chip
    "TPU v5e": _V5E,
}


def peaks_for(device_kind: str) -> Dict[str, object]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
