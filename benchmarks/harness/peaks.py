"""Published peaks of the chips the benchmark has run on, keyed by the
``device_kind`` jax reports.  A device that is not here is an error, not
a default: a roofline share against a guessed peak means nothing."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e" system architecture: 197
    # TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip
    "TPU v5 lite": {
        "flops_bf16_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e, per chip)",
    },
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; add a "
            f"sourced row to benchmarks/harness/peaks.py")
    return PEAKS[device_kind]
