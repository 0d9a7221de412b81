"""Device self time per round under the named scope ``fednew.eval``: the
round's ``StepMetrics`` (global loss and gradient norm at the new model,
dual-sum residual, direction norm), per chip, from the trace
(``bench/scopes.py``)."""

import scopes

UNIT = "ms"
LAYER = "round evaluation"
MOVES = "round_ms"


def read(rec):
    return scopes.per_round_ms(rec, "fednew.eval")
