"""``repro.api.run``: execute one :class:`ExperimentSpec`, return a
:class:`RunResult`.

The runner owns everything around the engine call: building the problem from
the spec, threading the participation law, stacking metrics into plain
Python lists, the *exact* cumulative uplink-bit ledger (Python-int
arithmetic via the PR-2 accounting helpers — the traced per-round metric is
float-typed under partial participation, the ledger never is), wall-clock,
and JSON persistence.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from repro.api import build
from repro.api.specs import ExperimentSpec
from repro.core import engine, participation as participation_lib
from repro.core.quantization import word_bits


class LedgerJSONEncoder(json.JSONEncoder):
    """Strict encoder for RunResult payloads: numpy integers serialize as
    JSON ints (the exact uplink ledger must never round through a float —
    lossy past 2^53), numpy floats as floats, and anything else json can't
    already handle raises instead of silently degrading."""

    def default(self, o):
        if isinstance(o, np.integer):
            return int(o)
        if isinstance(o, np.floating):
            return float(o)
        raise TypeError(
            f"RunResult JSON refuses to guess a representation for "
            f"{type(o).__name__!r} (exact-ledger fields must stay ints); "
            f"convert it explicitly before saving"
        )


@dataclasses.dataclass
class RunResult:
    """Everything one experiment produced, JSON-able as-is.

    metrics                          per-round engine metrics, each a
                                     (rounds,) list of floats (includes
                                     ``gap`` when f(x*) was computed).
    sampled_clients                  per-round participating-client counts
                                     (always n under full participation).
    uplink_bits_total                exact per-round uplink bits summed over
                                     the sampled clients (Python ints — the
                                     PR-2 accounting, no float rounding).
    cumulative_uplink_bits_total     running sum of the above.
    cumulative_uplink_bits_per_client  the paper's x-axis: cumulative mean
                                     uplink bits per client (floats; exact
                                     division of the int ledger).
    downlink_bits_total              exact per-round downlink bits (the PS
                                     broadcasts x^k to each sampled client
                                     at the transmitted word size), summed
                                     over the sampled clients — Python ints,
                                     same contract as the uplink ledger.
    cumulative_downlink_bits_total   running sum of the above.
    simulated_round_s / simulated_time_s
                                     ``repro.comm.netsim`` synchronous-round
                                     wall-clock (max over sampled clients of
                                     broadcast + upload + 2·latency) driven
                                     by the exact ledgers; present only when
                                     the spec carries a ``network`` section.
    wall_clock_s                     total run wall clock (= compile_s +
                                     steady_wall_clock_s).
    compile_s / compile_rounds       wall clock and round count of the
                                     FIRST dispatched block/step —
                                     dominated by trace + compile time.
    steady_wall_clock_s / steady_rounds  wall clock and round count of
                                     every subsequent dispatch: per-round
                                     steady cost is steady_wall_clock_s /
                                     steady_rounds — never divide by the
                                     spec's total rounds, the compile
                                     block's rounds are not in the steady
                                     window. (A distinct tail block adds
                                     its own smaller compile here; size
                                     blocks to divide rounds when that
                                     matters.)
    """

    spec: Dict[str, Any]
    solver: str
    rounds: int
    n_clients: int
    dim: int
    metrics: Dict[str, List[float]]
    sampled_clients: List[int]
    uplink_bits_total: List[int]
    cumulative_uplink_bits_total: List[int]
    cumulative_uplink_bits_per_client: List[float]
    wall_clock_s: float
    compile_s: float = 0.0
    steady_wall_clock_s: float = 0.0
    compile_rounds: int = 0
    steady_rounds: int = 0
    f_star: Optional[float] = None
    downlink_bits_total: List[int] = dataclasses.field(default_factory=list)
    cumulative_downlink_bits_total: List[int] = dataclasses.field(
        default_factory=list
    )
    simulated_round_s: Optional[List[float]] = None
    simulated_time_s: Optional[float] = None
    # Events-mode extras (``ScheduleSpec(mode="events")`` — repro.events):
    # the audited resident-state high-water mark of the streamed-cohort
    # executor, and how many dispatches the dropout law ate. None for the
    # synchronous schedules.
    peak_state_bytes: Optional[int] = None
    n_dropped: Optional[int] = None
    # Per-round solver internals recorded when the spec sets
    # ``telemetry.diagnostics`` (``diag_``-prefixed metric fields, prefix
    # stripped — see repro.telemetry.diagnostics). Empty when off.
    diagnostics: Dict[str, List[float]] = dataclasses.field(
        default_factory=dict
    )

    @property
    def final_loss(self) -> float:
        return self.metrics["loss"][-1]

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    def save_json(self, path: str) -> str:
        parent = os.path.dirname(os.path.abspath(path))
        os.makedirs(parent, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.to_dict(), f, indent=2, cls=LedgerJSONEncoder)
        return path


def _run_ledger(spec: ExperimentSpec) -> engine.SolverLedger:
    """The solver's exact bit-accounting object, built from the SAME merged
    hparams as the solver that runs (``CompressionSpec`` folded into the
    ``codec`` hparam) — the registry is the one accounting authority, so
    the ledger and the step's traced metric cannot drift. Adding a solver
    to ``engine._registry`` with a ``ledger`` factory is all it takes for
    this runner to account it."""
    return engine.solver_ledger(
        spec.solver.name,
        **build._merged_solver_hparams(spec.solver, spec.compression),
    )


def _per_round_payload_bits(
    spec: ExperimentSpec, leaf_words, rounds: int
) -> List[int]:
    """Exact bits ONE sampled client uploads in each round, as Python ints
    (mirrors each step's metric expression; pinned against the traced
    metric in tests/test_api.py and the conformance suite). ``leaf_words``
    is the wire layout: ``[(size, word_bits), ...]`` — one entry for a flat
    d-vector run, one per param leaf for a pytree run (codecs apply
    per-leaf, so per-round bits are the sum of per-leaf payloads)."""
    uplink = _run_ledger(spec).uplink
    return [
        sum(uplink(s, w, r) for s, w in leaf_words) for r in range(rounds)
    ]


def _per_round_downlink_bits(
    spec: ExperimentSpec, leaf_words, rounds: int
) -> List[int]:
    """Exact bits the PS sends ONE sampled client per round — per-solver
    (most broadcast the iterate; fagh also downlinks the momentum
    direction its phase-2 HVP probes), summed over the wire leaves."""
    downlink = _run_ledger(spec).downlink
    return [
        sum(downlink(s, w, r) for s, w in leaf_words) for r in range(rounds)
    ]


def _transmitted_word_bits(data) -> int:
    """Word size of the vectors on the wire: the solvers build their state
    (and transmit) in the dataset's float dtype (non-float features fall
    back to float32, mirroring ``fednew.init``)."""
    dt = data.features.dtype
    if dt not in (np.dtype("float32"), np.dtype("float64")):
        return 32
    return word_bits(dt)


def _wire_layout(data, x0):
    """``(dim, leaf_words)`` of the transmitted state: per-leaf
    ``(size, word_bits)`` pairs for a pytree run (dim = total param count),
    the single ``(d, word)`` entry for flat-vector runs."""
    if x0 is not None:
        leaves = jax.tree_util.tree_leaves(x0)
        leaf_words = [(int(l.size), word_bits(l.dtype)) for l in leaves]
        return sum(s for s, _ in leaf_words), leaf_words
    return data.dim, [(data.dim, _transmitted_word_bits(data))]


def _running_sum(values: List[int]) -> List[int]:
    out, acc = [], 0
    for v in values:
        acc += v
        out.append(acc)
    return out


# Solvers whose step computes diagnostics natively (collective-aware, so
# they are correct under shard_map too). Everything else gets the generic
# state-delta wrapper, which is scan/host-only.
_INSTEP_DIAG_SOLVERS = ("fednew", "q-fednew")


def _telemetry_hooks(spec: ExperimentSpec):
    """(recorder, tracer) from the spec's telemetry section. (None, None)
    when ``trace_path`` is unset — the engine then keeps its historical
    zero-overhead path (no telemetry import at all)."""
    tspec = spec.telemetry
    if not tspec.trace_path:
        return None, None
    from repro import telemetry

    rec = telemetry.TraceRecorder()
    if spec.name:
        rec.other_data["run"] = spec.name
    if tspec.tag:
        rec.other_data["tag"] = tspec.tag
    return rec, telemetry.EngineTracer(recorder=rec, profile=tspec.profile)


def _finish_telemetry(spec: ExperimentSpec, rec, tracer) -> None:
    """Attach roofline records (when profiling) and write the trace file."""
    if rec is None:
        return
    if tracer is not None and tracer.profile:
        rec.other_data["roofline"] = tracer.roofline_records()
    rec.save(spec.telemetry.trace_path)


def _stream_result(spec: ExperimentSpec, metrics, diagnostics) -> None:
    """One JSONL row per round: ``{"round": r, <metrics...>, <diag_...>}``."""
    if not spec.telemetry.stream_path:
        return
    from repro import telemetry

    rounds = len(next(iter(metrics.values()), []))
    rows = []
    for r in range(rounds):
        row: Dict[str, Any] = {"round": r}
        for name, vals in metrics.items():
            row[name] = vals[r]
        for name, vals in diagnostics.items():
            # run-level diagnostics (events cache counters) are one-element
            # series — they ride in RunResult, not in every row
            if len(vals) == rounds:
                row[telemetry.DIAG_PREFIX + name] = vals[r]
        rows.append(row)
    telemetry.stream_rows(spec.telemetry.stream_path, rows)


# Per-client simulated bars are replayed for at most this many client ids
# (matches repro.events.runtime._MAX_TRACED_CLIENTS — traces must not scale
# with the fleet).
_MAX_TRACED_CLIENTS = 256


def _replay_netsim_trace(
    rec, links, payloads, down_payloads, masks, round_s
) -> None:
    """Rebuild the synchronous netsim timeline as simulated-clock spans:
    per-client download/upload bars (no compute model on this path) and a
    ``server_step`` instant at each straggler barrier. Pure function of the
    exact ledgers + the replayed masks, so the sub-trace is deterministic
    per seed regardless of scan/shard_map/host execution."""
    n = len(links.uplink_bps)
    t = 0.0
    for r, dt in enumerate(round_s):
        active = (
            range(min(n, _MAX_TRACED_CLIENTS)) if masks is None
            else [c for c in np.nonzero(masks[r])[0]
                  if c < _MAX_TRACED_CLIENTS]
        )
        for cid in active:
            rec.client_segments(
                int(cid),
                t,
                down_s=down_payloads[r] / float(links.downlink_bps[cid])
                + float(links.latency_s[cid]),
                compute_s=0.0,
                up_s=payloads[r] / float(links.uplink_bps[cid])
                + float(links.latency_s[cid]),
                round=r,
            )
        t += dt
        rec.sim_instant("server_step", t, round=r)


def _run_events(spec: ExperimentSpec) -> RunResult:
    """The ``mode="events"`` runner: event-driven FedNew through
    ``repro.events.runtime.run_events``. Per-server-step series replace the
    per-round ones — ``simulated_round_s`` entries are the (variable)
    simulated seconds between consecutive server steps, and ``rounds`` is
    the number of steps the event loop actually completed (an arrival trace
    can exhaust early)."""
    from repro.api.specs import ArrivalSpec
    from repro.events import arrivals as arrivals_lib
    from repro.events import fedbuff, runtime as events_runtime
    from repro.events import sim as events_sim

    obj, data = build.build_problem(spec)
    n = data.n_clients
    aspec = spec.arrival if spec.arrival is not None else ArrivalSpec()
    net = spec.network

    cfg = fedbuff.FedNewAsyncConfig(
        **build._merged_solver_hparams(spec.solver, spec.compression)
    )
    fleet = events_sim.build_fleet(
        n,
        uplink_mbps=net.uplink_mbps,
        downlink_mbps=net.downlink_mbps,
        latency_s=net.latency_s,
        compute_s=aspec.compute_s,
        heterogeneity=net.heterogeneity,
        sigma=net.sigma,
        seed=net.seed,
    )
    if aspec.kind == "poisson":
        trace = arrivals_lib.poisson_trace(
            n, aspec.rate_per_s, aspec.horizon_s, aspec.seed
        )
    elif aspec.kind == "trace":
        trace = arrivals_lib.load_trace(aspec.trace_path, n)
    else:
        trace = None

    rec, tracer = _telemetry_hooks(spec)
    t0 = time.perf_counter()
    res = events_runtime.run_events(
        cfg, obj, data, fleet,
        server_steps=spec.schedule.rounds,
        # the spec default (64) should work on any fleet; a cohort can never
        # exceed it anyway
        cohort=min(aspec.cohort, n),
        key=jax.random.PRNGKey(spec.seed),
        arrival_trace=trace,
        dropout_prob=aspec.dropout_prob,
        seed=aspec.seed,
        cache_capacity=aspec.cache_capacity,
        checkpoint_dir=aspec.checkpoint_dir,
        eval_cohort=aspec.eval_cohort,
        tracer=tracer,
    )
    wall = time.perf_counter() - t0

    metric_lists = dict(res.metrics)
    diagnostics: Dict[str, List[float]] = {}
    if spec.telemetry.diagnostics:
        # Events-mode internals: the staleness series (async only — it IS
        # already a per-step law there) plus the cohort-cache audit. The
        # run-level cache/dropout counters become one-element series so the
        # diagnostics container stays uniformly Dict[str, List[float]].
        for k in ("staleness_mean", "staleness_max"):
            if k in metric_lists:
                diagnostics[k] = list(metric_lists[k])
        diagnostics["cache_spills"] = [float(res.n_spills)]
        diagnostics["cache_restores"] = [float(res.n_restores)]
        diagnostics["dropped_dispatches"] = [float(res.n_dropped)]
    f_star = None
    if spec.telemetry.f_star_newton_iters > 0:
        from repro.core import baselines

        _, fs = baselines.reference_optimum(
            obj, data, iters=spec.telemetry.f_star_newton_iters
        )
        f_star = float(fs)
        metric_lists["gap"] = [l - f_star for l in metric_lists["loss"]]

    cumulative = _running_sum(res.uplink_bits_total)
    result = RunResult(
        spec=spec.to_dict(),
        solver=spec.solver.name,
        rounds=res.n_server_steps,
        n_clients=n,
        dim=data.dim,
        metrics=metric_lists,
        sampled_clients=res.contributors,
        uplink_bits_total=res.uplink_bits_total,
        cumulative_uplink_bits_total=cumulative,
        cumulative_uplink_bits_per_client=[c / n for c in cumulative],
        wall_clock_s=wall,
        f_star=f_star,
        downlink_bits_total=res.downlink_bits_total,
        cumulative_downlink_bits_total=_running_sum(res.downlink_bits_total),
        simulated_round_s=res.round_time_s,
        simulated_time_s=res.simulated_time_s,
        peak_state_bytes=res.peak_state_bytes,
        n_dropped=res.n_dropped,
        diagnostics=diagnostics,
    )
    _finish_telemetry(spec, rec, tracer)
    _stream_result(spec, metric_lists, diagnostics)
    if spec.telemetry.save_path:
        result.save_json(spec.telemetry.save_path)
    return result


def run(spec: ExperimentSpec) -> RunResult:
    """Build everything the spec describes, run it through the engine, and
    assemble the result. Deterministic per the spec's three seeds (dataset /
    run / participation)."""
    if spec.schedule.mode == "events":
        return _run_events(spec)
    obj, data = build.build_problem(spec)
    build.check_solver_objective(spec, obj)
    mesh = build.build_mesh(spec.schedule, data.n_clients)
    if spec.telemetry.diagnostics and spec.solver.name in _INSTEP_DIAG_SOLVERS:
        merged = build._merged_solver_hparams(spec.solver, spec.compression)
        merged["diagnostics"] = True
        solver = engine.get_solver(spec.solver.name, **merged)
    elif spec.telemetry.diagnostics:
        if mesh is not None:
            raise ValueError(
                f"telemetry.diagnostics for solver {spec.solver.name!r} uses "
                "the generic state-delta wrapper, whose norms would be "
                "shard-local under a mesh; only "
                f"{'/'.join(_INSTEP_DIAG_SOLVERS)} compute diagnostics "
                "inside the step (collective-aware)"
            )
        from repro import telemetry

        solver = telemetry.instrument(
            build.build_solver(spec.solver, spec.compression)
        )
    else:
        solver = build.build_solver(spec.solver, spec.compression)
    part = build.build_participation(spec)
    x0 = build.build_x0(spec)
    sched = spec.schedule
    rec, tracer = _telemetry_hooks(spec)

    timings: List = []
    t0 = time.perf_counter()
    state, metrics = engine.run(
        solver, obj, data, sched.rounds,
        key=jax.random.PRNGKey(spec.seed),
        x0=x0,
        mode=sched.mode,
        block_size=sched.block_size,
        mesh=mesh,
        participation=part,
        timings=timings,
        tracer=tracer,
    )
    jax.block_until_ready(metrics)
    wall = time.perf_counter() - t0
    # First dispatch carries trace+compile; the rest is steady-state. The
    # round counts ride along so consumers can form per-round figures
    # (compile covers block_size rounds under scan, 1 under host). See the
    # RunResult docstring for the tail-block caveat.
    compile_s = timings[0][1] if timings else 0.0
    compile_rounds = timings[0][0] if timings else 0
    steady_s = sum(t for _, t in timings[1:])
    steady_rounds = sum(r for r, _ in timings[1:])

    metric_lists = {
        name: [float(v) for v in np.asarray(vals)]
        for name, vals in zip(metrics._fields, metrics)
    }
    diagnostics: Dict[str, List[float]] = {}
    if spec.telemetry.diagnostics:
        from repro import telemetry

        metric_lists, diagnostics = telemetry.split_metric_lists(metric_lists)

    f_star = None
    if spec.telemetry.f_star_newton_iters > 0:
        from repro.core import baselines

        _, fs = baselines.reference_optimum(
            obj, data, iters=spec.telemetry.f_star_newton_iters
        )
        f_star = float(fs)
        metric_lists["gap"] = [l - f_star for l in metric_lists["loss"]]

    # Exact integer uplink + downlink ledgers: per-message payloads (Python
    # ints) times the per-round sampled-client counts replayed from the mask
    # schedule.
    n = data.n_clients
    dim, leaf_words = _wire_layout(data, x0)
    counts = participation_lib.sampled_counts(part, sched.rounds, n)
    payloads = _per_round_payload_bits(spec, leaf_words, sched.rounds)
    down_payloads = _per_round_downlink_bits(spec, leaf_words, sched.rounds)
    totals = [p * c for p, c in zip(payloads, counts)]
    down_totals = [p * c for p, c in zip(down_payloads, counts)]

    cumulative = _running_sum(totals)

    # Simulated synchronous-round wall-clock under the spec's link model,
    # driven by the exact per-message ledgers and the replayed masks.
    sim_round_s = sim_total_s = None
    if spec.network is not None:
        from repro.comm import netsim

        links = spec.network.build_links(n)
        masks = (
            participation_lib.round_masks(part, sched.rounds, n)
            if part is not None else None
        )
        sim_round_s, sim_total_s = netsim.simulate_rounds(
            links, payloads, down_payloads, masks
        )
        if rec is not None:
            _replay_netsim_trace(
                rec, links, payloads, down_payloads, masks, sim_round_s
            )

    result = RunResult(
        spec=spec.to_dict(),
        solver=solver.name,
        rounds=sched.rounds,
        n_clients=n,
        dim=dim,
        metrics=metric_lists,
        sampled_clients=counts,
        uplink_bits_total=totals,
        cumulative_uplink_bits_total=cumulative,
        cumulative_uplink_bits_per_client=[c / n for c in cumulative],
        wall_clock_s=wall,
        compile_s=compile_s,
        steady_wall_clock_s=steady_s,
        compile_rounds=compile_rounds,
        steady_rounds=steady_rounds,
        f_star=f_star,
        downlink_bits_total=down_totals,
        cumulative_downlink_bits_total=_running_sum(down_totals),
        simulated_round_s=sim_round_s,
        simulated_time_s=sim_total_s,
        diagnostics=diagnostics,
    )
    _finish_telemetry(spec, rec, tracer)
    _stream_result(spec, metric_lists, diagnostics)
    if spec.telemetry.save_path:
        result.save_json(spec.telemetry.save_path)
    return result


def run_components(
    solver_name: str,
    obj,
    data,
    rounds: int,
    *,
    key=None,
    mesh=None,
    block_size=None,
    mode: str = "scan",
    participation=None,
    **hparams,
):
    """Imperative escape hatch: run a registry solver on prebuilt
    objective/data (the pre-spec surface benchmarks used). Returns the raw
    engine ``(final_state, stacked_metrics)``. Prefer :func:`run` with an
    :class:`ExperimentSpec` for anything new."""
    sol = engine.get_solver(solver_name, **hparams)
    return engine.run(
        sol, obj, data, rounds,
        key=key, mesh=mesh, block_size=block_size, mode=mode,
        participation=participation,
    )
