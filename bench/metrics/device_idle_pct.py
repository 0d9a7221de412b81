"""Share of the traced steady window in which no operation ran on the
device, averaged over the cell's chips (trace: busy is the union of the
device's op intervals)."""

UNIT = "%"
LAYER = "device"
MOVES = "round_ms"


def read(rec):
    if rec.trace is None or rec.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - rec.trace.busy_s / rec.trace.window_s)
