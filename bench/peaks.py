"""Published peaks of the chips the benchmark runs on, keyed by JAX's
`device_kind`. An unknown kind is an error, never a default.

TPU v5e (Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
393 TOP/s int8, 16 GB HBM at 819 GB/s). No float32 peak is published; the
benchmark's float32 work is read against the bf16 peak, so an f32 share
is at most a fraction of what bf16 work could reach (float32 matmuls at
'highest' precision take six bf16 passes on the MXU).
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to bench/peaks.py with "
                       f"their source") from None


def roofline_share(flops: float, nbytes: float, seconds: float,
                   device_kind: str):
    """(share in %, bound) of work that took `seconds`: the least time the
    chip could take (the larger of flops / peak FLOP/s and bytes / peak
    bytes/s) over the time taken. None when nothing was timed."""
    if not seconds or seconds <= 0:
        return None
    pk = peaks(device_kind)
    t_flops = flops / pk["flops_per_s"]
    t_bytes = nbytes / pk["hbm_bytes_per_s"]
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound


def flops_share(flops: float, seconds: float, device_kind: str):
    """Achieved FLOP/s over the peak, in %. None when nothing was timed."""
    if not seconds or seconds <= 0:
        return None
    return 100.0 * flops / seconds / peaks(device_kind)["flops_per_s"]
