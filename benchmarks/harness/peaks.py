"""Published peaks of the chips the benchmark knows, keyed by JAX's
`device_kind`. A device that is not in the table is an error, not a default.

Source for "TPU v5 lite": Google Cloud documentation, "TPU v5e" (system
architecture): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM2e at 819 GB/s,
1,600 Gbit/s of chip-to-chip interconnect.
"""

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9,
    },
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; known: "
            f"{sorted(PEAKS)} (add the chip with its source to "
            "benchmarks/harness/peaks.py)")
    return PEAKS[device_kind]
