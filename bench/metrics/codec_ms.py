"""Device self time per round under the named scope ``fednew.codec``: the
uplink encode (the quantizer kernel and its uniforms), decode and codec
state, per chip, from the trace (``bench/scopes.py``)."""

import scopes

UNIT = "ms"
LAYER = "uplink codec"
MOVES = "round_ms"


def read(rec):
    return scopes.per_round_ms(rec, "fednew.codec")
