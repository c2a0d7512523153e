"""Peak rates of the chips the benchmark may run on, keyed by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" system architecture page — one
chip: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s.
A device that is not in the table is an error, never a default, and nothing in
the environment overrides an entry.
"""

PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "hbm_bytes_per_s": 819e9,
    },
}


def peak(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f"benchmark: no peak table entry for device_kind {device_kind!r}; "
            f"add it to benchmark/harness/peaks.py with its source") from None
