"""The matfree HVP kernel's share of its roofline: the least time of its
calls in a round (``work/glm_hvp.py``, the larger of FLOPs over the bf16
peak and bytes over HBM bandwidth, per chip) over their measured device
time per round. The solve calls the kernel once per CG iteration, so a
round holds ``cg_iters`` calls (the trace counts no calls of it)."""

import registry

UNIT = "%"
LAYER = "matfree HVP kernel"
MOVES = "round_ms"


def read(rec):
    ms = registry.metric("glm_hvp_ms").read(rec)
    if ms is None:
        return None
    flops, nbytes = rec.work("glm_hvp")
    least = max(flops / rec.peaks.flops_bf16, nbytes / rec.peaks.hbm_bw)
    calls = rec.cell.hparams["cg_iters"]
    return 100.0 * calls * least / rec.chips / (ms * 1e-3)
