"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``. A kind that is not in the table is an error, not a default.
"""
from __future__ import annotations

#: Google Cloud documentation, "TPU v5e" (Cloud TPU system architecture):
#: per chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s,
#: 1,600 Gbit/s inter-chip interconnect.
PEAKS: dict[str, dict] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
        "source": "Google Cloud documentation, 'TPU v5e'",
    },
}


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device_kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None
