"""Device time per round of the uplink quantizer's Pallas kernel, the
custom call named after its jitted wrapper (``quantize_with_keys.N``),
averaged over the chips, from the trace. The wrapper's uniforms and
ranges are not in it: the trace cannot tell them apart until the program
names them."""

UNIT = "ms"
LAYER = "uplink codec"
MOVES = "round_ms"


def read(rec):
    if rec.trace is None or not rec.trace.calls("stoch_quant"):
        return None
    return 1e3 * rec.trace.time("stoch_quant") / rec.trace.rounds
