"""Device time by named scope: the map from HLO text, the readers, and the
block rebuilt from a cell's shapes."""

import gzip
import os

import pytest

import devtrace
import harness
import registry
import scopes
import tiny

DATA = os.path.join(os.path.dirname(__file__), "data")
SCOPED = ("eq9_ms", "hessian_ms", "codec_ms", "eval_ms")

HLO = """HloModule jit_block, entry_computation_layout={()->()}

%fused_computation.3 (p: f32[60,829,267]) -> f32[60,829] {
  %p = f32[60,829,267]{1,2,0} parameter(0)
  ROOT %r = f32[60,829]{1,0} reduce(%p), metadata={op_name="jit(block)/while/body/closed_call/fednew.hessian/cond/branch_1_fun/vmap()/dot_general"}
}

ENTRY %main {
  %multiply_reduce_fusion.12 = f32[60,829]{1,0:T(8,128)} fusion(f32[60,829,267]{1,2,0} %a), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(block)/while/body/closed_call/fednew.hessian/cond/jit(block)/while/body/closed_call/fednew.hessian/cond/branch_1_fun/vmap()/dot_general"}
  %pad.21.clone.2 = f32[60,384,384]{2,1,0} pad(%x, %c), padding=0_0x0_117x0_117, metadata={op_name="jit(block)/while/body/closed_call/fednew.hessian/cond/jit(block)/while/body/closed_call/fednew.eq9/jit(client_solve)/jit(_pad)/pad"}
  %client_solve.9 = f32[60,1,384]{2,1,0} custom-call(%copy.30, %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(block)/while/body/closed_call/fednew.eq9/jit(client_solve)/pallas_call" source_file="x.py" source_line=3}
  %copy.29 = f32[60,267,267]{0,2,1} copy(%fusion.48)
  %fusion.46 = f32[60,829]{1,0} fusion(%a), kind=kLoop, calls=%fused_computation.3
  ROOT %fusion.54 = f32[] fusion(%y), kind=kLoop, calls=%fused_computation.9, metadata={op_name="jit(block)/while/body/closed_call/fednew.eval/vmap()/dot_general"}
}
"""


def test_scope_map_takes_the_innermost_fednew_scope():
    smap = scopes.scope_map(HLO)
    assert smap["multiply_reduce_fusion.12"] == "fednew.hessian"
    assert smap["r"] == "fednew.hessian"
    assert smap["pad.21.clone.2"] == "fednew.eq9"  # moved into the cond branch
    assert smap["client_solve.9"] == "fednew.eq9"
    assert smap["copy.29"] is None  # a layout copy the compiler inserted
    assert smap["fusion.46"] == "fednew.hessian"  # from the ops fused into it
    assert smap["fusion.54"] == "fednew.eval"


def test_scope_seconds_sums_by_scope_and_refuses_another_program():
    ops = [("client_solve.9", 6.0), ("pad.21.clone.2", 1.0), ("copy.29", 0.5),
           ("multiply_reduce_fusion.12", 2.0), ("fusion.54", 0.5)]
    by = scopes.scope_seconds(ops, HLO)
    assert by == {"fednew.eq9": 7.0, None: 0.5, "fednew.hessian": 2.0, "fednew.eval": 0.5}
    # names the program does not hold: not this program's trace
    assert scopes.scope_seconds(ops + [("fusion.77", 10.0)], HLO) is None
    assert scopes.scope_seconds([], HLO) is None


def _record(top_ops, rounds, cell="w8a-dense.q3"):
    summary = devtrace.Summary(window_s=1.0, busy_s=1.0, rounds=rounds, chips=1,
                               cat_s={}, cat_n={}, top_ops=top_ops, gaps=[])
    return harness.Record(cell=harness.load_cell(cell), peaks=None, chips=1,
                          round_ms=1.0, rounds_to_gap=None, block_s=[], trace=summary)


def test_readers_on_a_hand_built_record(monkeypatch):
    monkeypatch.setattr(scopes, "block_text", lambda cell: HLO)
    rec = _record([("client_solve.9", 6e-3), ("pad.21.clone.2", 1e-3),
                   ("multiply_reduce_fusion.12", 2e-3), ("fusion.54", 5e-4)], rounds=4)
    read = {m: registry.metric(m).read(rec) for m in SCOPED}
    assert read["eq9_ms"] == pytest.approx(1.75)
    assert read["hessian_ms"] == pytest.approx(0.5)
    assert read["eval_ms"] == pytest.approx(0.125)
    assert read["codec_ms"] is None  # no op of the scope ran
    rec.trace = None
    assert all(registry.metric(m).read(rec) is None for m in SCOPED)


def test_readers_say_nothing_for_a_program_without_scopes(monkeypatch):
    """The program before the scopes existed: no ``compile_block``."""
    from repro.core import engine

    monkeypatch.delattr(engine, "compile_block")
    monkeypatch.setattr(scopes, "_TEXTS", {})
    rec = _record([("client_solve.9", 6e-3)], rounds=4)
    assert all(registry.metric(m).read(rec) is None for m in SCOPED)


def test_metric_modules_agree_with_benchmark_json():
    entries = {m["name"]: m for m in registry.benchmark()["per_layer"]}
    for name in SCOPED:
        mod, m = registry.metric(name), entries[name]
        assert (mod.UNIT, mod.LAYER, mod.MOVES) == (m["unit"], m["layer"], m["moves"])


@pytest.mark.parametrize("cell,want", [
    ("w8a-dense.q3", {"fednew.hessian", "fednew.grad", "fednew.eq9", "fednew.codec",
                      "fednew.aggregate", "fednew.eval"}),
    ("rcv1-matfree.full", {"fednew.hessian", "fednew.grad", "fednew.eq9",
                           "fednew.aggregate", "fednew.eval"}),
])
def test_block_rebuilt_from_a_cells_shapes_names_its_scopes(monkeypatch, cell, want):
    monkeypatch.setattr(scopes, "_TEXTS", {})
    with tiny.harness_on_cpu():
        text = scopes.block_text(harness.load_cell(cell))
    assert set(scopes.scope_map(text).values()) - {None} == want


def test_scoped_readers_on_a_recorded_tpu_trace(tmp_path, monkeypatch):
    """A traced two-block w8a-dense.q3 job and the HLO text of the block it
    ran, recorded on a TPU v5e by ``record_scoped_trace.py``."""
    path = tmp_path / "trace.xplane.pb"
    with gzip.open(os.path.join(DATA, "w8a_q3_2blocks.xplane.pb.gz"), "rb") as f:
        path.write_bytes(f.read())
    with gzip.open(os.path.join(DATA, "w8a_q3_2blocks.hlo.txt.gz"), "rt") as f:
        text = f.read()
    summary = devtrace.reduce(*devtrace.load(str(path)), rounds_per_block=16)
    monkeypatch.setattr(scopes, "block_text", lambda cell: text)
    rec = _record(summary.top_ops, summary.rounds)
    rec.trace = summary
    read = {m: registry.metric(m).read(rec) for m in SCOPED}
    assert all(v is not None and v > 0 for v in read.values()), read
    client_solve_ms = registry.metric("client_solve_ms").read(rec)
    assert read["eq9_ms"] >= client_solve_ms  # the kernel and its wrapper
    by = scopes.scope_seconds(summary.top_ops, text)
    assert sum(v for k, v in by.items() if k) >= 0.9 * summary.busy_s
