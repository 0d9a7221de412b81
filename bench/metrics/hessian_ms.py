"""Device self time per round under the named scope ``fednew.hessian``:
the curvature refresh, on the dense path the per-client Hessians (the
``cond`` branch that forms them, with the sigmoid weights it needs), per
chip, from the trace (``bench/scopes.py``)."""

import scopes

UNIT = "ms"
LAYER = "Hessian formation"
MOVES = "round_ms"


def read(rec):
    return scopes.per_round_ms(rec, "fednew.hessian")
