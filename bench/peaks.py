"""Published peaks of the chips the benchmark runs on, by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s).  A device that is not in
the table is an error, never a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(PEAKS)}") from None


def least_time(p: dict, *, int8_ops: float = 0.0, float_ops: float = 0.0,
               bytes_moved: float = 0.0) -> tuple[float, str]:
    """The least seconds the chip could take for this work, and which
    bound binds: int8 operations at the int8 peak plus float operations
    at the bf16 peak, against the bytes at the HBM bandwidth."""
    compute = int8_ops / p["int8_ops"] + float_ops / p["bf16_flops"]
    memory = bytes_moved / p["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
