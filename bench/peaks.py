"""Published peaks of each chip the benchmark may run on, keyed by the
``device_kind`` JAX reports.  A kind that is not here is an error, not a
default: a roofline share against the wrong peak is a wrong number."""
from __future__ import annotations

#: device_kind -> peaks.  Source for "TPU v5 lite": Google Cloud
#: documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at 819 GB/s).
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    """The peaks of ``device_kind``; raises ``KeyError`` for a kind the
    table does not hold."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """Roofline: the least time the chip could take for the work, the
    larger of operations over peak FLOP/s and bytes over peak bytes/s."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
