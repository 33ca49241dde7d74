"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports it.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s. A device that
is not in the table is an error, never a default: add its published row.
The benchmark keeps this copy because the program's own table
(``dlrover_tpu/utils/profiler.py`` ``PEAKS``) may change under it.
"""

_V5E = {"bf16_flops": 197e12, "int8_ops": 393e12,
        "hbm_bytes": 16e9, "hbm_bytes_per_s": 819e9}
PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"device kind {device_kind!r} is not in the "
                         f"benchmark's peaks table {sorted(PEAKS)}") from None
