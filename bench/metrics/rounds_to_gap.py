"""Rounds the window's job took to bring the relative gap
(f(x_k) - f*) / (f(x_0) - f*) under the configuration's target."""

UNIT = "rounds"
LAYER = "solver step"
MOVES = "time_to_gap_s"


def read(rec):
    return None if rec.rounds_to_gap is None else float(rec.rounds_to_gap)
