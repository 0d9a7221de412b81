"""95th percentile of the host-clock time of one dispatched scan block,
each blocked to completion, over the window's blocks after the first (the
first holds the job's trace). Catches stalls a median hides."""

import statistics

UNIT = "ms"
LAYER = "schedule"
MOVES = "round_ms"


def read(rec):
    blocks = rec.block_s
    if len(blocks) < 20:
        return None
    return 1e3 * statistics.quantiles(blocks, n=20)[18]
