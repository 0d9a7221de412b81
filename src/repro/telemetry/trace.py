"""Chrome-trace recording with two clock domains.

A :class:`TraceRecorder` collects `Trace Event Format`_ events and saves
them as one JSON object Perfetto / ``chrome://tracing`` loads directly.
Events live in one of two *clock domains*, rendered as two separate
processes in the viewer:

  * **host** (``pid == HOST_PID``) — wall-clock spans around the phases the
    engine actually executes on this machine: ``init``, per program
    ``trace`` / ``lower`` / ``compile``, each ``dispatch`` (one program
    call, holding ``launch`` and ``wait``), ``eval``, ``hlo-analyze``.
    Timestamps are ``time.perf_counter`` deltas from recorder creation.
    Host spans are *observations*; they never feed back into a trajectory
    (the fedlint ``nondeterminism`` rule exempts exactly this package —
    and nothing else — from its wall-clock ban; see docs/analysis.md).

  * **simulated** (``pid == SIM_PID``) — spans on the *simulated* timeline
    of the event heap / netsim: per-client download / compute / upload
    bars (one thread row per client), server-step instants. Timestamps are
    simulated seconds, so the same seed always produces the byte-identical
    simulated sub-trace (pinned in tests/test_telemetry.py).

Timestamps are microseconds (floats — the trace format allows fractional
``ts``). ``displayTimeUnit`` is milliseconds.

.. _Trace Event Format:
   https://docs.google.com/document/d/1CvAClvFfyA5R-PhYUmn5OOQtYMH4h6I0nSsKchNAySU
"""

from __future__ import annotations

import contextlib
import json
import os
import time
from typing import Any, Dict, List, Optional

HOST_PID = 1  # wall-clock domain
SIM_PID = 2  # simulated-clock domain

_PROCESS_NAMES = {
    HOST_PID: "host (wall clock)",
    SIM_PID: "simulated (event clock)",
}


def _us(seconds: float) -> float:
    return seconds * 1e6


class TraceRecorder:
    """Collects Chrome-trace events; the one mutable telemetry sink.

    All methods are cheap appends — the recorder never synchronizes devices
    or touches traced values (callers hand it host floats/ints only).
    """

    def __init__(self) -> None:
        self._events: List[Dict[str, Any]] = []
        self._named: set = set()
        self._t0 = time.perf_counter()
        #: free-form payload saved under ``otherData`` (roofline records,
        #: run identifiers, ...)
        self.other_data: Dict[str, Any] = {}

    # -- metadata -----------------------------------------------------------

    def _ensure_process(self, pid: int) -> None:
        if ("process", pid) in self._named:
            return
        self._named.add(("process", pid))
        self._events.append({
            "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": _PROCESS_NAMES.get(pid, f"pid {pid}")},
        })

    def name_thread(self, pid: int, tid: int, name: str) -> None:
        """Label one row of the viewer (e.g. ``client 17``)."""
        if ("thread", pid, tid) in self._named:
            return
        self._named.add(("thread", pid, tid))
        self._ensure_process(pid)
        self._events.append({
            "name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
            "args": {"name": name},
        })

    # -- host clock domain --------------------------------------------------

    @contextlib.contextmanager
    def host_span(self, name: str, cat: str = "host", **args):
        """A wall-clock complete event around the ``with`` body."""
        self._ensure_process(HOST_PID)
        t0 = time.perf_counter()
        try:
            yield self
        finally:
            t1 = time.perf_counter()
            ev: Dict[str, Any] = {
                "name": name, "ph": "X", "cat": cat,
                "pid": HOST_PID, "tid": 0,
                "ts": _us(t0 - self._t0), "dur": _us(t1 - t0),
            }
            if args:
                ev["args"] = args
            self._events.append(ev)

    def host_instant(self, name: str, cat: str = "host", **args) -> None:
        self._ensure_process(HOST_PID)
        ev: Dict[str, Any] = {
            "name": name, "ph": "i", "cat": cat, "s": "g",
            "pid": HOST_PID, "tid": 0,
            "ts": _us(time.perf_counter() - self._t0),
        }
        if args:
            ev["args"] = args
        self._events.append(ev)

    # -- simulated clock domain --------------------------------------------

    def sim_span(
        self, name: str, t0_s: float, t1_s: float, *,
        tid: int = 0, cat: str = "sim", **args,
    ) -> None:
        """A complete event on the simulated timeline (seconds in)."""
        self._ensure_process(SIM_PID)
        ev: Dict[str, Any] = {
            "name": name, "ph": "X", "cat": cat,
            "pid": SIM_PID, "tid": tid,
            "ts": _us(t0_s), "dur": _us(max(0.0, t1_s - t0_s)),
        }
        if args:
            ev["args"] = args
        self._events.append(ev)

    def sim_instant(
        self, name: str, t_s: float, *, tid: int = 0, cat: str = "sim",
        **args,
    ) -> None:
        self._ensure_process(SIM_PID)
        ev: Dict[str, Any] = {
            "name": name, "ph": "i", "cat": cat, "s": "t",
            "pid": SIM_PID, "tid": tid, "ts": _us(t_s),
        }
        if args:
            ev["args"] = args
        self._events.append(ev)

    def client_segments(
        self, cid: int, t0_s: float, *, down_s: float, compute_s: float,
        up_s: float, **args,
    ) -> float:
        """The canonical per-client bar triple — download, compute, upload —
        starting at simulated ``t0_s`` on thread row ``cid + 1`` (row 0 is
        the server). Returns the end time. Used by both the event heap and
        the netsim replay so straggler rounds render identically."""
        tid = int(cid) + 1
        self.name_thread(SIM_PID, tid, f"client {int(cid)}")
        t1 = t0_s + down_s
        t2 = t1 + compute_s
        t3 = t2 + up_s
        self.sim_span("download", t0_s, t1, tid=tid, **args)
        if compute_s > 0.0:
            self.sim_span("compute", t1, t2, tid=tid, **args)
        self.sim_span("upload", t2, t3, tid=tid, **args)
        return t3

    # -- output -------------------------------------------------------------

    @property
    def events(self) -> List[Dict[str, Any]]:
        return list(self._events)

    def sim_events(self) -> List[Dict[str, Any]]:
        """The simulated-domain sub-trace (metadata excluded) — the part
        that is a pure function of the run's seeds."""
        return [
            e for e in self._events
            if e.get("pid") == SIM_PID and e.get("ph") != "M"
        ]

    def to_dict(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "traceEvents": self._events,
            "displayTimeUnit": "ms",
        }
        if self.other_data:
            out["otherData"] = self.other_data
        return out

    def save(self, path: str) -> str:
        parent = os.path.dirname(os.path.abspath(path))
        os.makedirs(parent, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.to_dict(), f)
        return path


class EngineTracer:
    """What ``engine.run(tracer=...)`` / ``run_events(tracer=...)`` accept:
    host spans plus optional per-program HLO cost capture.

    The engine stays ignorant of this module (duck-typed hook) — it calls
    ``span(name, **args)`` around each phase (``init``; ``trace``,
    ``lower``, ``compile`` per program; ``dispatch`` holding ``launch`` and
    ``wait`` per block) and ``compiled(label, compiled)`` once per program
    it builds. Each span is also a ``jax.profiler.TraceAnnotation`` named
    ``engine.<name>``, so a profiler trace of the run shows the phases on
    the device trace's clock. With ``profile=True`` the optimized HLO of
    each program the run dispatches is analysed: the program itself, never
    a second lowering.
    """

    def __init__(
        self, recorder: Optional[TraceRecorder] = None, profile: bool = False
    ) -> None:
        self.recorder = recorder
        self.profile = profile
        #: per-dispatch (label, rounds, seconds) in call order
        self.dispatches: List[tuple] = []
        #: label -> hlo_cost.analyze dict
        self.costs: Dict[str, Dict[str, Any]] = {}

    def _host_span(self, name: str, **args):
        if self.recorder is None:
            return contextlib.nullcontext()
        return self.recorder.host_span(name, cat="engine", **args)

    @contextlib.contextmanager
    def span(self, name: str, **args):
        import jax

        t0 = time.perf_counter()
        with self._host_span(name, **args), \
                jax.profiler.TraceAnnotation(f"engine.{name}"):
            yield
        if name == "dispatch":
            self.dispatches.append(
                (args.get("label", name), args.get("rounds", 0),
                 time.perf_counter() - t0)
            )

    def compiled(self, label: str, compiled) -> None:
        """Analyse one built program's optimized HLO and remember the cost
        under ``label`` (the first program of a label is kept)."""
        if not self.profile or label in self.costs:
            return
        from repro.roofline import hlo_cost

        with self._host_span("hlo-analyze", label=label):
            self.costs[label] = hlo_cost.analyze(compiled.as_text())

    def roofline_records(self) -> List[Dict[str, Any]]:
        """Achieved-vs-attainable per profiled dispatch label, using the
        fastest observed call as the steady-state estimate (the first call
        of each label carries trace+compile time)."""
        from repro.telemetry import profile as profile_lib

        by_label: Dict[str, List[tuple]] = {}
        for label, rounds, seconds in self.dispatches:
            by_label.setdefault(label, []).append((rounds, seconds))
        records = []
        for label, cost in self.costs.items():
            calls = by_label.get(label, [])
            seconds = min((s for _, s in calls), default=None)
            records.append(
                profile_lib.roofline_record(label, cost, seconds)
            )
        return records
