"""Published peaks of each accelerator the benchmark may run on.

Keyed by ``jax.Device.device_kind``. A kind that is not in the table is
an error: a share of a peak against a guessed peak means nothing.
"""
from __future__ import annotations

#: device_kind -> peaks. Source for TPU v5e (which JAX reports as
#: "TPU v5 lite"): Google Cloud documentation, "TPU v5e"
#: (https://cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16,
#: 393 TOP/s int8, 16 GB of HBM at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {
        "int8_ops_per_s": 393e12,
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "https://cloud.google.com/tpu/docs/v5e",
    },
}


class UnknownDeviceError(KeyError):
    """The device kind has no row in :data:`PEAKS`."""


def peaks_for(device_kind: str) -> dict:
    """The peaks row of ``device_kind``; raises for an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDeviceError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None
