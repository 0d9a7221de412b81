"""Device time per round of the eq. 9 Pallas kernel, the custom call named
after its jitted wrapper (``client_solve.N``), averaged over the chips,
from the trace. The wrapper's padding and layout copies are not in it:
the trace cannot tell them from other ops until the program names them."""

UNIT = "ms"
LAYER = "eq. 9 kernel"
MOVES = "round_ms"


def read(rec):
    if rec.trace is None or not rec.trace.calls("client_solve"):
        return None
    return 1e3 * rec.trace.time("client_solve") / rec.trace.rounds
