from repro.roofline.hlo import collective_bytes, op_histogram
from repro.roofline.model import (
    DEVICE_PEAKS,
    V5E,
    DevicePeaks,
    Roofline,
    device_peaks,
    model_flops,
    param_counts,
)

__all__ = [
    "DEVICE_PEAKS", "V5E", "DevicePeaks", "device_peaks",
    "Roofline", "model_flops", "param_counts",
    "collective_bytes", "op_histogram",
]
