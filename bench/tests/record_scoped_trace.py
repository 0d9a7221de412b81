"""Record the scoped-trace fixture on a TPU: a profiler trace of a two-block
``w8a-dense.q3`` job, as ``bench/harness.py`` runs it, and the optimized
HLO text of the block program that job dispatched.

    python3 bench/tests/record_scoped_trace.py <out_dir>

writes ``w8a_q3_2blocks.xplane.pb.gz`` and ``w8a_q3_2blocks.hlo.txt.gz``
(copy both to ``bench/tests/data/``) and prints, as JSON, whether the block
rebuilt from shapes by ``repro.core.engine.compile_block`` gives every
instruction the same scope, and the per-scope readings.
"""

import glob
import gzip
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import jax  # noqa: E402

import devtrace  # noqa: E402
import harness  # noqa: E402
import scopes  # noqa: E402

CELL = "w8a-dense.q3"
SEED = 2**33 + 13
NAME = "w8a_q3_2blocks"


class ProgramClock(harness.BlockClock):
    """The harness's hook, keeping each program the engine builds."""

    def __init__(self):
        super().__init__()
        self.programs = {}

    def compiled(self, label, compiled):
        self.programs[label] = compiled


def main(out_dir: str) -> None:
    from repro.core import engine

    cell = harness.load_cell(CELL)
    devs = harness.devices_for(1)
    harness.enable_compile_cache()
    p = harness.prepare(cell, SEED, devs)

    def job():
        clock = ProgramClock()
        engine.run(p.solver, p.obj, p.data, 2 * cell.block, key=p.run_key,
                   mode="scan", block_size=cell.block, participation=p.part,
                   timings=[], tracer=clock)
        return clock

    job()  # warm-up: compile or load every program once
    trace_dir = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(trace_dir, profiler_options=opts):
        clock = job()
    (xplane,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    (program,) = clock.programs.values()
    text = program.as_text()

    os.makedirs(out_dir, exist_ok=True)
    with open(xplane, "rb") as src, gzip.open(os.path.join(out_dir, f"{NAME}.xplane.pb.gz"), "wb") as dst:
        shutil.copyfileobj(src, dst)
    with gzip.open(os.path.join(out_dir, f"{NAME}.hlo.txt.gz"), "wt") as f:
        f.write(text)

    summary = devtrace.reduce(*devtrace.load(xplane), rounds_per_block=cell.block)
    by_scope = scopes.scope_seconds(summary.top_ops, text)
    print(json.dumps({
        "rebuilt_matches": scopes.scope_map(scopes.block_text(cell)) == scopes.scope_map(text),
        "busy_s": summary.busy_s, "window_s": summary.window_s, "rounds": summary.rounds,
        "scope_s": {str(k): v for k, v in (by_scope or {}).items()},
    }))


if __name__ == "__main__":
    main(sys.argv[1])
