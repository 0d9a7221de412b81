"""Device self time per round under the named scope ``fednew.eq9``: the
client solve with its padding and layout scatter (the Pallas kernel on the
dense path, CG on HVPs on the matrix-free one), per chip, from the trace
(``bench/scopes.py``)."""

import scopes

UNIT = "ms"
LAYER = "eq. 9 solve"
MOVES = "round_ms"


def read(rec):
    return scopes.per_round_ms(rec, "fednew.eq9")
