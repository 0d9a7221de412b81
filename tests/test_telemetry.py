"""repro.telemetry tests: the observability layer's three hard contracts.

1. **Off = byte-identical, on = same trajectory.** Telemetry disabled is the
   historical zero-overhead path (the PR-5 hex goldens ride the existing
   freeze tests untouched); telemetry enabled — tracer, profiling, in-step
   diagnostics, the instrument() wrapper — must reproduce the identical
   trajectory, pinned bit for bit here.
2. **Simulated-clock determinism.** The sim-domain sub-trace is a pure
   function of the run's seeds: identical across reruns and across
   scan/shard_map execution (the netsim replay consumes the replayed
   host-side masks, never traced state), and identical across reruns of the
   event heap.
3. **Diagnostics are schedule-invariant.** Every conformance-suite solver
   produces the same diagnostics under scan and host scheduling.

Plus the units: typed metrics (exact-int counters), trace/stream formats,
the CLI validator/summarizer, and the roofline profile records.
"""

import json
import os
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).parent))
import conformance as conf  # noqa: E402

import repro.api as api  # noqa: E402
from repro import telemetry  # noqa: E402
from repro.core import engine, hvp  # noqa: E402
from repro.telemetry import cli as telemetry_cli  # noqa: E402

CASE_IDS = [c.label for c in conf.CASES]

_INSTEP = ("fednew", "q-fednew")


def _diag_solver(case):
    """The case's solver with diagnostics enabled: in-step for the FedNew
    family (static config flag), the generic wrapper for everything else."""
    if case.solver in _INSTEP:
        return engine.get_solver(case.solver, diagnostics=True,
                                 **case.hparams)
    return telemetry.instrument(case.build())


def _run_diag(case, mode):
    obj, data = conf.problem()
    return engine.run(
        _diag_solver(case), obj, data, 4,
        key=jax.random.PRNGKey(1), mode=mode, block_size=2,
    )


def _sim_events(trace_path):
    payload = json.load(open(trace_path))
    return [e for e in payload["traceEvents"]
            if e.get("pid") == telemetry.SIM_PID and e.get("ph") != "M"]


def _traced_spec(tmp_path, tag, *, mode="scan", mesh_devices=None,
                 diagnostics=True, profile=False, stream=False,
                 solver=None, network=True):
    solver = solver or api.SolverSpec(
        "fednew",
        {"rho": 0.1, "alpha": 0.03, "hessian_period": 1,
         "hessian_repr": "matfree", "cg_iters": 12},
    )
    return api.ExperimentSpec(
        partition=api.PartitionSpec(dataset="custom", n_clients=8,
                                    samples_per_client=16, dim=24, seed=0),
        solver=solver,
        schedule=api.ScheduleSpec(rounds=4, block_size=2, mode=mode,
                                  mesh_devices=mesh_devices),
        telemetry=api.TelemetrySpec(
            trace_path=str(tmp_path / f"{tag}_trace.json"),
            diagnostics=diagnostics,
            stream_path=(str(tmp_path / f"{tag}_stream.jsonl")
                         if stream else None),
            profile=profile,
        ),
        network=(api.NetworkSpec(uplink_mbps=5.0, downlink_mbps=50.0,
                                 latency_s=0.01, heterogeneity="lognormal",
                                 sigma=0.8, seed=7) if network else None),
        name=tag,
    )


def _events_spec(tmp_path, tag, *, seed=0):
    return api.ExperimentSpec(
        partition=api.PartitionSpec(dataset="custom", n_clients=8,
                                    samples_per_client=16, dim=24, seed=0),
        solver=api.SolverSpec(
            "fednew-async",
            {"rho": 0.1, "alpha": 0.03, "hessian_period": 1,
             "buffer_size": 3, "staleness_power": 0.5},
        ),
        schedule=api.ScheduleSpec(rounds=4, mode="events"),
        telemetry=api.TelemetrySpec(
            trace_path=str(tmp_path / f"{tag}_trace.json"),
            diagnostics=True,
        ),
        network=api.NetworkSpec(uplink_mbps=5.0, downlink_mbps=50.0,
                                latency_s=0.01, heterogeneity="lognormal",
                                sigma=0.8, seed=7),
        arrival=api.ArrivalSpec(cohort=6, compute_s=0.05, seed=seed),
        name=tag,
    )


# ---------------------------------------------------------------------------
# contract 1: telemetry on reproduces the bare trajectory
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("case", conf.CASES, ids=CASE_IDS)
def test_diagnostics_do_not_change_trajectory(case):
    """In-step diagnostics and the instrument() wrapper both add outputs,
    never math: final state and the base metric fields are bit-identical to
    the undiagnosed run."""
    obj, data = conf.problem()
    state0, m0 = engine.run(case.build(), obj, data, 4,
                            key=jax.random.PRNGKey(1), mode="scan",
                            block_size=2)
    state1, m1 = _run_diag(case, "scan")
    conf.assert_tree_equal(state0, state1, err=f"{case.label}: state drift")
    for name in m0._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(m0, name)), np.asarray(getattr(m1, name)),
            err_msg=f"{case.label}: metric {name} drift",
        )
    assert any(f.startswith(telemetry.DIAG_PREFIX) for f in m1._fields)


def test_tracer_and_profile_do_not_change_trajectory():
    """Host spans + AOT HLO profiling wrap the dispatches; the computed
    rounds stay bit-identical."""
    case = conf.CASES[1]  # fednew-matfree
    obj, data = conf.problem()
    _, m0 = engine.run(case.build(), obj, data, 4,
                       key=jax.random.PRNGKey(1), mode="scan", block_size=2)
    tracer = telemetry.EngineTracer(
        recorder=telemetry.TraceRecorder(), profile=True
    )
    _, m1 = engine.run(case.build(), obj, data, 4,
                       key=jax.random.PRNGKey(1), mode="scan", block_size=2,
                       tracer=tracer)
    conf.assert_tree_equal(m0, m1, err="traced run diverged")
    names = {e["name"] for e in tracer.recorder.events if e["ph"] == "X"}
    assert {"init", "dispatch", "hlo-analyze"} <= names


def test_cg_track_iters_solution_bit_identical():
    """The opt-in live-count carry must not perturb the CG iterates."""
    key = jax.random.PRNGKey(0)
    kA, kb = jax.random.split(key)
    M = jax.random.normal(kA, (6, 12, 12))
    A = jnp.einsum("nij,nkj->nik", M, M) / 12.0
    rhs = jax.random.normal(kb, (6, 12))
    matvec = lambda p: jnp.einsum("nij,nj->ni", A, p)
    base = hvp.cg_solve_clients(matvec, rhs, damping=0.5, iters=20, tol=1e-6)
    tracked = hvp.cg_solve_clients(matvec, rhs, damping=0.5, iters=20,
                                   tol=1e-6, track_iters=True)
    np.testing.assert_array_equal(np.asarray(base.x), np.asarray(tracked.x))
    iters = np.asarray(tracked.iterations)
    assert iters.shape == (6,)
    assert iters.dtype == np.int32
    assert (iters >= 1).all() and (iters <= 20).all()
    # the damped 12-d systems converge well before 20 iterations
    assert (iters < 20).all()


def test_runresult_diagnostics_off_is_empty(tmp_path):
    spec = _traced_spec(tmp_path, "plain", diagnostics=False)
    res = api.run(spec)
    assert res.diagnostics == {}
    assert not any(k.startswith(telemetry.DIAG_PREFIX) for k in res.metrics)


# ---------------------------------------------------------------------------
# contract 2: the simulated sub-trace is deterministic per seed
# ---------------------------------------------------------------------------


def test_sim_trace_identical_across_reruns_and_schedules(tmp_path):
    """scan rerun, and scan vs shard_map: the simulated-clock events agree
    exactly (they derive from the exact ledgers + replayed masks)."""
    spec_a = _traced_spec(tmp_path, "a")
    spec_b = _traced_spec(tmp_path, "b")
    api.run(spec_a)
    api.run(spec_b)
    ev_a = _sim_events(spec_a.telemetry.trace_path)
    ev_b = _sim_events(spec_b.telemetry.trace_path)
    assert ev_a == ev_b
    api.run(_traced_spec(tmp_path, "m", mesh_devices="auto"))
    ev_m = _sim_events(str(tmp_path / "m_trace.json"))
    assert ev_m == ev_a
    assert any(e["name"] == "download" for e in ev_a)
    assert any(e["name"] == "upload" for e in ev_a)
    assert any(e["name"] == "server_step" for e in ev_a)


def test_events_sim_trace_deterministic(tmp_path):
    api.run(_events_spec(tmp_path, "e1"))
    api.run(_events_spec(tmp_path, "e2"))
    ev1 = _sim_events(str(tmp_path / "e1_trace.json"))
    ev2 = _sim_events(str(tmp_path / "e2_trace.json"))
    assert ev1 == ev2
    # per-client bars on the simulated timeline + compute segments (the
    # events fleet has a compute model, unlike the netsim replay)
    assert any(e["name"] == "compute" for e in ev1)
    tids = {e["tid"] for e in ev1 if e["name"] in ("download", "upload")}
    assert len(tids) > 1  # one thread row per client
    payload = json.load(open(str(tmp_path / "e1_trace.json")))
    pids = {e["pid"] for e in payload["traceEvents"]}
    assert pids == {telemetry.HOST_PID, telemetry.SIM_PID}


def test_events_diagnostics_and_metrics(tmp_path):
    res = api.run(_events_spec(tmp_path, "ed"))
    assert "staleness_mean" in res.diagnostics
    assert "cache_spills" in res.diagnostics
    assert "dropped_dispatches" in res.diagnostics


# ---------------------------------------------------------------------------
# contract 3: diagnostics are schedule-invariant
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("case", conf.CASES, ids=CASE_IDS)
def test_diagnostics_scan_vs_host(case):
    _, m_scan = _run_diag(case, "scan")
    _, m_host = _run_diag(case, "host")
    assert m_scan._fields == m_host._fields
    diag_fields = [f for f in m_scan._fields
                   if f.startswith(telemetry.DIAG_PREFIX)]
    assert diag_fields
    for name in diag_fields:
        a = np.asarray(getattr(m_scan, name))
        b = np.asarray(getattr(m_host, name))
        if case.host_exact:
            np.testing.assert_array_equal(
                a, b, err_msg=f"{case.label}: {name}")
        else:
            np.testing.assert_allclose(
                a, b, rtol=case.rtol, atol=1e-6,
                err_msg=f"{case.label}: {name}")


def test_fednew_diagnostics_catalogue(tmp_path):
    """The matfree acceptance point: ADMM residuals, CG iterations-to-tol,
    codec error all present with per-round length."""
    spec = _traced_spec(tmp_path, "cat", stream=True)
    res = api.run(spec)
    for key in ("admm_primal_residual", "admm_dual_residual", "cg_iters",
                "cg_residual", "codec_error", "anchor_staleness"):
        assert key in res.diagnostics, key
        assert len(res.diagnostics[key]) == 4
    assert all(1.0 <= v <= 12.0 for v in res.diagnostics["cg_iters"])
    assert all(v >= 0.0 for v in res.diagnostics["admm_primal_residual"])
    # uncompressed run: decode(encode(u)) == u
    assert res.diagnostics["codec_error"] == [0.0] * 4
    rows = telemetry.read_stream(spec.telemetry.stream_path)
    assert [r["round"] for r in rows] == [0, 1, 2, 3]
    assert rows[0]["loss"] == res.metrics["loss"][0]
    assert rows[0]["diag_cg_iters"] == res.diagnostics["cg_iters"][0]


def test_qfednew_codec_error_positive():
    """3-bit quantization must report a strictly positive compression
    error."""
    case = next(c for c in conf.CASES if c.label == "q-fednew")
    _, m = _run_diag(case, "scan")
    err = np.asarray(m.diag_codec_error)
    assert (err > 0).all()


# ---------------------------------------------------------------------------
# units: stream, spec, CLI, roofline
# ---------------------------------------------------------------------------


def test_stream_roundtrip(tmp_path):
    path = str(tmp_path / "s.jsonl")
    rows = [{"round": 0, "loss": 1.0}, {"round": 1, "loss": 0.5}]
    telemetry.stream_rows(path, rows)
    assert telemetry.read_stream(path) == rows


def test_split_metric_lists():
    metrics, diag = telemetry.split_metric_lists(
        {"loss": [1.0], "diag_cg_iters": [3.0]}
    )
    assert metrics == {"loss": [1.0]}
    assert diag == {"cg_iters": [3.0]}


def test_telemetry_spec_validation_and_roundtrip(tmp_path):
    with pytest.raises(ValueError):
        api.TelemetrySpec(profile=True)
    spec = _traced_spec(tmp_path, "rt", profile=True, stream=True)
    again = api.ExperimentSpec.from_dict(spec.to_dict())
    assert again == spec
    assert again.telemetry.diagnostics is True
    assert again.telemetry.profile is True


def test_cli_validate_and_summarize(tmp_path, capsys):
    spec = _traced_spec(tmp_path, "cli", profile=True, stream=True)
    spec = api.ExperimentSpec.from_dict({
        **spec.to_dict(),
        "telemetry": {**spec.to_dict()["telemetry"],
                      "save_path": str(tmp_path / "cli_result.json")},
    })
    api.run(spec)
    trace = spec.telemetry.trace_path
    stream = spec.telemetry.stream_path
    assert telemetry_cli.main(
        ["validate", trace, "--expect-domain", "host",
         "--expect-domain", "sim", "--stream", stream]
    ) == 0
    assert telemetry_cli.main(["summarize", trace]) == 0
    assert telemetry_cli.main(
        ["summarize", str(tmp_path / "cli_result.json")]
    ) == 0
    assert telemetry_cli.main(["summarize", stream]) == 0
    out = capsys.readouterr().out
    assert "roofline" in out

    bad = str(tmp_path / "bad_trace.json")
    json.dump({"traceEvents": [{"ph": "X"}]}, open(bad, "w"))
    with pytest.raises(SystemExit):
        telemetry_cli.main(["validate", bad])
    # a host-only trace must fail the sim-domain expectation
    host_only = str(tmp_path / "host_only.json")
    rec = telemetry.TraceRecorder()
    with rec.host_span("x"):
        pass
    rec.save(host_only)
    with pytest.raises(SystemExit):
        telemetry_cli.main(["validate", host_only, "--expect-domain", "sim"])


def test_roofline_records(tmp_path):
    case = conf.CASES[0]
    obj, data = conf.problem()
    tracer = telemetry.EngineTracer(profile=True)
    engine.run(case.build(), obj, data, 4, key=jax.random.PRNGKey(1),
               mode="scan", block_size=2, tracer=tracer)
    records = tracer.roofline_records()
    assert records
    rec = records[0]
    assert rec["label"].startswith("scan_block")
    assert rec["device_kind"] == jax.devices()[0].device_kind
    assert rec["flops"] > 0
    # a CPU has no published peaks: no ceiling, no bound, no share
    assert rec["attainable_flops_per_s"] is None
    assert rec["bound"] is None
    assert rec["achieved_fraction"] is None
    assert rec["seconds_per_call"] > 0
    assert rec["achieved_flops_per_s"] == pytest.approx(
        rec["flops"] / rec["seconds_per_call"]
    )


def test_roofline_record_uses_the_device_kinds_peaks():
    from repro.roofline.model import DEVICE_PEAKS, V5E

    cost = {"flops": 4e9, "bytes": 1e9}
    peaks = DEVICE_PEAKS[V5E]
    rec = telemetry.roofline_record("k", cost, 0.01, device_kind=V5E)
    assert rec["attainable_flops_per_s"] == pytest.approx(4 * peaks.hbm_bw)
    assert rec["bound"] == "memory"
    assert rec["achieved_fraction"] == pytest.approx(
        (4e9 / 0.01) / (4 * peaks.hbm_bw)
    )
    other = telemetry.roofline_record("k", cost, 0.01, device_kind="cpu")
    assert other["attainable_flops_per_s"] is None
    assert other["achieved_fraction"] is None
    assert other["achieved_flops_per_s"] == pytest.approx(4e11)


def test_trace_file_loads_as_chrome_trace(tmp_path):
    spec = _traced_spec(tmp_path, "fmt", profile=True)
    api.run(spec)
    payload = json.load(open(spec.telemetry.trace_path))
    assert isinstance(payload["traceEvents"], list)
    assert payload["displayTimeUnit"] == "ms"
    for e in payload["traceEvents"]:
        assert {"name", "ph", "pid"} <= set(e)
        if e["ph"] == "X":
            assert e["ts"] >= 0 and e["dur"] >= 0
    assert payload["otherData"]["roofline"]


def test_generic_instrument_under_mesh_rejected(tmp_path):
    spec = _traced_spec(
        tmp_path, "meshdiag", mesh_devices="auto",
        solver=api.SolverSpec("fednl", {}),
    )
    with pytest.raises(ValueError, match="shard-local"):
        api.run(spec)
