"""Published peaks of one chip, keyed by JAX's ``device_kind``.

A kind that is not listed is an error: a roofline or a utilisation taken
against a guessed peak is not a measurement.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 2**30,
        "source": "Google Cloud documentation, 'TPU v5e': per-chip peaks",
    },
}


class UnknownDevice(KeyError):
    """The device kind has no entry in :data:`PEAKS`."""


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(f"no published peaks for device kind {device_kind!r}; "
                            f"known: {sorted(PEAKS)}") from None
