"""The least bytes of one matrix-free round (``work/fednew_round.py``: one
read of the features per dependent CG sweep) over the measured round time
and the chips' HBM bandwidth."""

UNIT = "%"
LAYER = "matfree solve"
MOVES = "round_ms"


def read(rec):
    _, nbytes = rec.work("fednew_round")
    if nbytes is None:
        return None
    return 100.0 * nbytes / (rec.round_ms * 1e-3 * rec.peaks.hbm_bw * rec.chips)
