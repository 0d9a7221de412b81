"""Roofline-backed profiling: achieved vs attainable per dispatched kernel.

Bridges the static HLO cost model (``repro.roofline.hlo_cost`` — exact
FLOP/byte counts off the post-SPMD optimized HLO) and measured wall-clock:

    achieved    = analyzed FLOPs (bytes) / measured seconds
    attainable  = min(peak FLOP/s, HBM bytes/s * arithmetic_intensity)

The peaks are the running device's, looked up by ``device_kind`` in
``repro.roofline.model.DEVICE_PEAKS``. A device that is not in that table
(a CPU, say) gets no roofline: ``attainable_flops_per_s``, ``bound`` and
``achieved_fraction`` are None, never another chip's numbers. ``bound``
names the binding resource at this intensity ("compute" above the ridge
point, "memory" below).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax

from repro.roofline import hlo_cost
from repro.roofline.model import DevicePeaks, device_peaks


def analyze_jitted(jitted, *args, **kwargs) -> Dict[str, Any]:
    """HLO cost of one jitted callable at these (abstract) args: AOT lower,
    compile, analyze — the computation itself never runs."""
    return hlo_cost.analyze(jitted.lower(*args, **kwargs).compile().as_text())


def attainable_flops_per_s(cost: Dict[str, Any], peaks: DevicePeaks) -> float:
    """The roofline ceiling at this kernel's arithmetic intensity."""
    flops = float(cost.get("flops", 0.0))
    nbytes = float(cost.get("bytes", 0.0))
    if nbytes <= 0.0:
        return peaks.flops_bf16
    return min(peaks.flops_bf16, peaks.hbm_bw * (flops / nbytes))


def roofline_record(
    label: str, cost: Dict[str, Any], seconds: Optional[float],
    calls: int = 1, device_kind: Optional[str] = None,
) -> Dict[str, Any]:
    """One achieved-vs-attainable row. ``seconds`` is the measured duration
    of ``calls`` executions (None when only the static cost is known — the
    achieved fields are then null, never fabricated). ``device_kind``
    defaults to the first local device's."""
    if device_kind is None:
        device_kind = jax.devices()[0].device_kind
    peaks = device_peaks(device_kind)
    flops = float(cost.get("flops", 0.0))
    nbytes = float(cost.get("bytes", 0.0))
    attainable = None if peaks is None else attainable_flops_per_s(cost, peaks)
    rec: Dict[str, Any] = {
        "label": label,
        "device_kind": device_kind,
        "flops": flops,
        "bytes": nbytes,
        "collective_bytes": float(cost.get("collective_bytes", 0.0)),
        "arithmetic_intensity": flops / nbytes if nbytes > 0 else None,
        "attainable_flops_per_s": attainable,
        "bound": None if peaks is None
        else "compute" if attainable >= peaks.flops_bf16 else "memory",
        "unknown_loops": int(cost.get("unknown_loops", 0)),
        "seconds_per_call": None,
        "achieved_flops_per_s": None,
        "achieved_bytes_per_s": None,
        "achieved_fraction": None,
    }
    if seconds is not None and seconds > 0.0:
        per_call = seconds / max(1, calls)
        rec["seconds_per_call"] = per_call
        rec["achieved_flops_per_s"] = flops / per_call
        rec["achieved_bytes_per_s"] = nbytes / per_call
        if attainable is not None:
            rec["achieved_fraction"] = (flops / per_call) / attainable
    return rec
