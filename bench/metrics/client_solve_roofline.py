"""The eq. 9 Pallas kernel's share of its roofline: the least time of one
call (``work/client_solve.py``, the larger of FLOPs over the bf16 peak and
bytes over HBM bandwidth) over its measured device time per call."""

UNIT = "%"
LAYER = "eq. 9 kernel"
MOVES = "round_ms"


def read(rec):
    return rec.kernel_roofline("client_solve")
