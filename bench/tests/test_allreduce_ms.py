"""The cross-chip exchange's device time, read from a hand-built trace:
per round and per chip, and nothing where no collective ran."""

import pytest

import devtrace
import harness
import registry
from devtrace import Op

SPANS = [("bench.dispatch", 0, 100), ("bench.dispatch", 100, 200),
         ("bench.dispatch", 200, 300)]


def _record(devices, rounds_per_block=4):
    summary = devtrace.reduce(SPANS, devices, rounds_per_block)
    return harness.Record(cell=harness.load_cell("e2006-matfree.full"), peaks=None,
                          chips=summary.chips, round_ms=1.0, rounds_to_gap=None,
                          block_s=[], trace=summary)


def test_reads_collective_time_per_round_and_per_chip():
    # Two chips over a window of two 4-round blocks (100 to 300 ns): chip 0
    # spends 10 + 6 ns in all-reduces, chip 1 spends 20 + 4; the fusion and
    # the op before the window do not count.
    chip0 = [Op(110, 150, "fusion.3", "fusion", True),
             Op(150, 160, "all-reduce.2", "all-reduce", True),
             Op(250, 256, "psum.57", "all-reduce", True),
             Op(50, 90, "all-reduce.2", "all-reduce", True)]
    chip1 = [Op(150, 170, "all-reduce.2", "all-reduce", True),
             Op(250, 252, "all-reduce-start.1", "all-reduce-start", False),
             Op(260, 262, "all-reduce-done.1", "all-reduce-done", True)]
    rec = _record({"/device:TPU:0": chip0, "/device:TPU:1": chip1})
    per_chip_ns = (10 + 6 + 20 + 2 + 2) / 2
    assert registry.metric("allreduce_ms").read(rec) == pytest.approx(
        1e3 * per_chip_ns * 1e-9 / 8)


def test_reads_nothing_without_collectives():
    rec = _record({"/device:TPU:0": [Op(110, 150, "fusion.3", "fusion", True)]})
    assert registry.metric("allreduce_ms").read(rec) is None
    rec.trace = None
    assert registry.metric("allreduce_ms").read(rec) is None
