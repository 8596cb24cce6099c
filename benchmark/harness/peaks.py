"""Published peaks of the chips the benchmark runs on, keyed by
``device_kind`` as JAX reports it. A device that is not here is an
error, never a default: a roofline share against the wrong peak is a
wrong number under a right name."""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,      # FLOP/s
        "hbm_bytes_per_s": 819e9,  # bytes/s
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
        "source": "Google Cloud documentation, \"TPU v5e\": 197 TFLOP/s bf16, "
                  "16 GB HBM2e at 819 GB/s, 1,600 Gbit/s interchip interconnect",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} in "
            f"benchmark/harness/peaks.py (known: {', '.join(PEAKS)})") from None
