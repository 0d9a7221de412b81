"""Device time per round of the cross-chip exchange: the ``all-reduce``
ops of the trace (eq. 13's mean and the step's metric sums over the
client mesh), per chip. None where the trace holds no collective, as on
one chip."""

UNIT = "ms"
LAYER = "client exchange"
MOVES = "round_ms"


def read(rec):
    if rec.trace is None or not rec.trace.calls("allreduce"):
        return None
    return 1e3 * rec.trace.time("allreduce") / rec.trace.rounds
