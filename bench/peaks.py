"""Published peaks of each accelerator, keyed by JAX's `device_kind`.

A device that is not in the table is an error, never a default."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e' (per chip: "
                  "197 TFLOP/s bf16, 16 GB HBM at 819 GB/s)",
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def roofline_s(flops: float, nbytes: float, device_kind: str) -> tuple[float, str]:
    """Least time the chip could take for this work, and which bound sets
    it ("compute" or "bytes")."""
    p = peaks(device_kind)
    t_c = flops / p["bf16_flops_per_s"]
    t_b = nbytes / p["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_b else (t_b, "bytes")
