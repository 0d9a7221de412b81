"""The least FLOPs of one round (``work/fednew_round.py``) over the
measured round time and the chips' bf16 peak."""

UNIT = "%"
LAYER = "solver step"
MOVES = "round_ms"


def read(rec):
    flops, _ = rec.work("fednew_round")
    return 100.0 * flops / (rec.round_ms * 1e-3 * rec.peaks.flops_bf16 * rec.chips)
