"""The reduction from a device trace to busy time, kernel times and gaps."""

import gzip
import os

import pytest

import devtrace
from devtrace import Op

# A profiler trace of two 16-round scan blocks of w8a-dense.full on a
# TPU v5e, as bench/harness.py records it (bench.* host spans included).
RECORDED = os.path.join(os.path.dirname(__file__), "data", "w8a_dense_2blocks.xplane.pb.gz")


def test_parse_hlo_reads_name_and_opcode_past_tuple_shapes():
    assert devtrace.parse_hlo(
        "%client_solve.9 = f32[60,1,384]{2,1,0:T(1,128)S(1)} custom-call(f32[60,384,384] %copy.30)"
    ) == ("client_solve.9", "custom-call")
    assert devtrace.parse_hlo(
        "%quantize_with_keys.9 = (s32[60,267]{1,0:T(8,128)}, f32[60,267]{1,0}) custom-call(%a)"
    ) == ("quantize_with_keys.9", "custom-call")
    assert devtrace.parse_hlo(
        "%while.4 = (s32[]{:T(128)}, f32[267]{0:T(512)}) while((s32[]) %tuple.50), condition=%c"
    ) == ("while.4", "while")


def test_union_and_gaps():
    iv = [(0, 10), (5, 20), (30, 40), (38, 45)]
    assert devtrace.union_length(iv) == 20 + 15
    assert devtrace.idle_gaps(iv, 0, 50) == [(20, 30), (45, 50)]
    assert devtrace.idle_gaps(iv, 12, 35) == [(20, 30)]


def test_categories_by_kernel_custom_call_and_collective():
    op = lambda name, code: Op(0, 1, name, code, True)
    assert devtrace.categories(op("client_solve.9", "custom-call")) == ["client_solve"]
    assert devtrace.categories(op("quantize_with_keys.3", "custom-call")) == ["stoch_quant"]
    assert devtrace.categories(op("client_solve.2", "fusion")) == []
    assert devtrace.categories(op("all-reduce.1", "all-reduce")) == ["allreduce"]
    assert devtrace.categories(op("fusion.48", "fusion")) == []


def test_reduce_on_a_hand_built_trace():
    # Three blocks; the window is from the first block's end (100) to the
    # last's (300). One chip: a while op covering each block's body.
    spans = [("bench.init", 0, 10), ("bench.dispatch", 10, 100),
             ("bench.dispatch", 120, 200), ("bench.dispatch", 210, 300)]
    ops = [Op(20, 95, "while.1", "while", True), Op(30, 60, "client_solve.1", "custom-call", True),
           Op(130, 195, "while.1", "while", True), Op(140, 170, "client_solve.1", "custom-call", True),
           Op(220, 290, "while.1", "while", True), Op(230, 260, "client_solve.1", "custom-call", True),
           Op(150, 160, "copy-start", "copy-start", False)]
    s = devtrace.reduce(spans, {"/device:TPU:0": ops}, rounds_per_block=4)
    assert s.window_s == pytest.approx(200e-9)
    assert s.busy_s == pytest.approx((65 + 70) * 1e-9)
    assert s.rounds == 8
    assert s.calls("client_solve") == 2
    assert s.time("client_solve") == pytest.approx(60e-9)
    # self time: the while ops are containers and stay out of the top ops
    assert [n for n, _ in s.top_ops] == ["client_solve.1"]
    gaps = dict(s.gaps)
    # 100-130 and 195-220 fall between dispatch spans, 290-300 inside one
    assert gaps["between bench spans"] == pytest.approx(55e-9)
    assert gaps["bench.dispatch"] == pytest.approx(10e-9)
    assert sum(gaps.values()) == pytest.approx(s.window_s - s.busy_s)


def test_reduce_on_a_recorded_tpu_trace(tmp_path):
    path = tmp_path / "trace.xplane.pb"
    with gzip.open(RECORDED, "rb") as f:
        path.write_bytes(f.read())
    spans, devices = devtrace.load(str(path))
    assert list(devices) == ["/device:TPU:0"]
    s = devtrace.reduce(spans, devices, rounds_per_block=16)
    assert 0 < s.busy_s <= s.window_s
    assert s.calls("client_solve") == s.rounds  # one kernel call per round
    assert 0 < s.time("client_solve") < s.busy_s
    assert s.calls("allreduce") == 0
    assert s.top_ops[0][0].startswith("client_solve")
