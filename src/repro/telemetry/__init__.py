"""repro.telemetry — tracing, metrics, and roofline profiling.

The observability layer of the runtime (docs/telemetry.md):

  * :mod:`repro.telemetry.trace` — :class:`TraceRecorder` (Chrome-trace
    JSON, host + simulated clock domains) and :class:`EngineTracer` (the
    duck-typed hook ``engine.run`` / ``events.run_events`` accept).
  * :mod:`repro.telemetry.metrics` — the JSONL diagnostics stream.
  * :mod:`repro.telemetry.diagnostics` — the ``diag_`` metric-field
    convention, the runner-side split, and the solver-agnostic
    :func:`instrument` wrapper.
  * :mod:`repro.telemetry.profile` — achieved-vs-attainable roofline
    records off the HLO cost model.
  * ``python -m repro.telemetry`` — ``summarize`` / ``validate`` CLI over
    traces, streams, RunResults, and dry-run caches.

Hard contract: telemetry off is the byte-identical lowering (the PR-5 hex
goldens ride on it), telemetry on runs the identical trajectory with
bounded, host-side-only overhead. Both are pinned in
tests/test_telemetry.py.
"""

from repro.telemetry.diagnostics import (
    DIAG_PREFIX,
    generic_extras,
    instrument,
    split_metric_lists,
)
from repro.telemetry.metrics import read_stream, stream_rows
from repro.telemetry.profile import analyze_jitted, roofline_record
from repro.telemetry.trace import (
    HOST_PID,
    SIM_PID,
    EngineTracer,
    TraceRecorder,
)

__all__ = [
    "DIAG_PREFIX",
    "HOST_PID",
    "SIM_PID",
    "EngineTracer",
    "TraceRecorder",
    "analyze_jitted",
    "generic_extras",
    "instrument",
    "read_stream",
    "roofline_record",
    "split_metric_lists",
    "stream_rows",
]
