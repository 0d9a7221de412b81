"""Device time per round of the matfree HVP kernel, the custom call named
after its jitted wrapper (``glm_hvp.N``), averaged over the chips, from the
trace's per-op self times. None where the kernel did not run (the
reference path, or a program without it)."""

UNIT = "ms"
LAYER = "matfree HVP kernel"
MOVES = "round_ms"
KERNEL = "glm_hvp"


def seconds(trace) -> float:
    """The kernel's device seconds over the window, per chip."""
    return sum(s for name, s in trace.top_ops if name.split(".")[0] == KERNEL)


def read(rec):
    if rec.trace is None or seconds(rec.trace) <= 0:
        return None
    return 1e3 * seconds(rec.trace) / rec.trace.rounds
