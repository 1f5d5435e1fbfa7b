"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports it.
A kind that is not here is an error, never a default."""
from __future__ import annotations

#: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
#: 197 TFLOP/s bf16, HBM2e at 819 GB/s per chip.
#: ``bench.py:56`` PEAK_FLOPS has the same bf16 figure; bandwidth is added here.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "Google Cloud docs, TPU v5e system architecture"},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind {device_kind!r}: add it to "
                       "benchmarks/harness/peaks.py with its source") from None
