"""Published peaks of the chips the benchmark may run on, keyed by the
`device_kind` jax reports. Source: Google Cloud documentation, "TPU v5e"
system architecture page: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at
819 GB/s, 1,600 Gbit/s inter-chip interconnect per chip. A device that is
not in the table is an error, never a default (bench.py's 202.7 TFLOP/s,
read off one r03 trace plane, is retired)."""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
        "source": "Google Cloud docs, TPU v5e system architecture",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak for device_kind {device_kind!r}; add it to "
            "perfbench/peaks.py with its source"
        ) from None
