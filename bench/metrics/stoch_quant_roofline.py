"""The quantizer Pallas kernel's share of its roofline: the least time of
one call (``work/stoch_quant.py``) over its measured device time per
call."""

UNIT = "%"
LAYER = "uplink codec"
MOVES = "round_ms"


def read(rec):
    return rec.kernel_roofline("stoch_quant")
