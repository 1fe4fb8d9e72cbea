"""Published peaks per accelerator, keyed by JAX's ``device_kind``.

A device that is not in this table is an error, never a default.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, object]] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": 'Google Cloud documentation, "TPU v5e"',
    },
}


SUMMED = ("bf16_flops_per_s", "hbm_bytes_per_s", "hbm_bytes")  # a cell's chips add these


def peaks_for(device_kind: str, chips: int = 1) -> Dict[str, object]:
    """The peaks of ``chips`` chips of ``device_kind`` together: one chip's
    times ``chips``, so that a share of them is a share of every chip a cell
    holds."""
    try:
        one = PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None
    return dict(one, **{k: chips * one[k] for k in SUMMED})
