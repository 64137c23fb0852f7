"""Published peaks of each accelerator the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A kind that is not here is an error, never a
default: a roofline or utilization against a guessed peak means nothing.

Source for TPU v5e: Google Cloud documentation, "TPU v5e" (system
architecture, chip specifications): 197 TFLOP/s bf16, 393 TOP/s int8,
16 GB HBM2 at 819 GB/s per chip.
"""
from __future__ import annotations

_V5E = {
    "bf16_flops_per_s": 197e12,
    "int8_ops_per_s": 393e12,
    "hbm_bytes": 16e9,
    "hbm_bytes_per_s": 819e9,
    "source": "Google Cloud documentation, TPU v5e",
}

# JAX reports a v5e chip as "TPU v5 lite"
PEAKS = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; raises ``UnknownDevice`` otherwise."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None
