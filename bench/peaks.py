"""Published peaks of the chips the benchmark runs on, by ``device_kind``.

A device missing from the table is an error, never a default."""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "source": "Google Cloud TPU v5e documentation",
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
