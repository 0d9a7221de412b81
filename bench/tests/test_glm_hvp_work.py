"""The matfree HVP kernel's least work, and its two readers on a
hand-built trace: device time per round and chip, and the roofline share
with the least time divided over the chips."""

import pytest

import devtrace
import harness
import peaks
import registry
from devtrace import Op

RCV1 = {"n_clients": 20, "samples_per_client": 1012, "dim": 47236, "dtype": "float32"}
SPANS = [("bench.dispatch", 0, 100), ("bench.dispatch", 100, 200),
         ("bench.dispatch", 200, 300)]


def test_kernel_reads_the_bf16_features_once_per_call():
    flops, nbytes = registry.work("glm_hvp").work(RCV1, {"operand_bytes": 2})
    features = 20 * 1012 * 47236  # 956,056,640
    assert flops == 4 * features
    assert nbytes == 2 * features == 1_912_113_280


def _record(cell, devices, rounds_per_block=4):
    summary = devtrace.reduce(SPANS, devices, rounds_per_block)
    return harness.Record(cell=harness.load_cell(cell), peaks=peaks.peaks("TPU v5 lite"),
                          chips=summary.chips, round_ms=1.0, rounds_to_gap=None,
                          block_s=[], trace=summary)


@pytest.mark.parametrize("cell,chips", [("rcv1-matfree.full", 1), ("e2006-matfree.full", 4)])
def test_readers_divide_per_round_and_per_chip(cell, chips):
    # Each chip runs the kernel twice in the window of two 4-round blocks
    # (8 rounds), 30 ns a call; ops before the window and other ops do not count.
    ops = [Op(110, 140, "glm_hvp.4", "custom-call", True),
           Op(210, 240, "glm_hvp.4", "custom-call", True),
           Op(150, 190, "fusion.27", "fusion", True),
           Op(10, 90, "glm_hvp.4", "custom-call", True)]
    rec = _record(cell, {f"/device:TPU:{c}": ops for c in range(chips)})
    ms = registry.metric("glm_hvp_ms").read(rec)
    assert ms == pytest.approx(1e3 * 60e-9 / 8)

    flops, nbytes = rec.work("glm_hvp")
    least = max(flops / rec.peaks.flops_bf16, nbytes / rec.peaks.hbm_bw)
    assert nbytes / rec.peaks.hbm_bw > flops / rec.peaks.flops_bf16  # bytes bound it
    per_round = rec.cell.hparams["cg_iters"] * least / chips
    assert registry.metric("glm_hvp_roofline").read(rec) == pytest.approx(
        100.0 * per_round / (ms * 1e-3))


def test_readers_read_nothing_where_the_kernel_did_not_run():
    rec = _record("rcv1-matfree.full",
                  {"/device:TPU:0": [Op(110, 150, "fusion.55", "fusion", True)]})
    assert registry.metric("glm_hvp_ms").read(rec) is None
    assert registry.metric("glm_hvp_roofline").read(rec) is None
    rec.trace = None
    assert registry.metric("glm_hvp_ms").read(rec) is None
    assert registry.metric("glm_hvp_roofline").read(rec) is None
