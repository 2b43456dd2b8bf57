"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports
it.  A device that is not here is an error, never a default."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM
    # at 819 GB/s
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind):
    if device_kind not in PEAKS:
        raise SystemExit(f"no published peaks for device {device_kind!r}")
    return PEAKS[device_kind]
