"""Published peaks per device kind, keyed by ``jax.Device.device_kind``.

An unknown kind is an error, never a default: a share of a peak that was
guessed is not a measurement."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e' (per chip)",
    },
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak row for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
