"""Published peaks of the chips the benchmark has run on, by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" system architecture page (one
chip: 197 TFLOP/s bf16, 394 TOP/s int8, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s
of inter-chip interconnect), as quoted in the on-chip-measurement guide; not
re-read from the network. A device that is not here is an error, not a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add it to "
            "benchmarks/harness/peaks.py with its source"
        ) from None
