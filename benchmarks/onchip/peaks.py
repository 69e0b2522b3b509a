"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` that JAX reports.  Only published figures are here; a
device that is not in the table is an error, never a default."""

from __future__ import annotations

V5E_SOURCE = ("Google Cloud TPU documentation, 'TPU v5e': 197 TFLOP/s "
              "bf16, 16 GB HBM2 at 819 GB/s per chip")

_V5E = {"flops": 197e12, "hbm_Bps": 819e9, "hbm_bytes": 16e9,
        "source": V5E_SOURCE}

PEAKS = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def lookup(device_kind: str) -> dict:
    """The peaks of one chip of ``device_kind``; KeyError if unknown."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
