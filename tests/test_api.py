"""Declarative experiment API tests (repro.api) + partial participation.

The acceptance contract of the api_redesign PR:

  * an ``ExperimentSpec`` with ``ParticipationSpec(fraction=1.0)`` reproduces
    the pre-API engine trajectories BIT-EXACTLY, under both the scan and the
    shard_map schedule (the engine detects full participation and takes the
    legacy code path verbatim);
  * a ``fraction < 1.0`` run is deterministic per seed, its per-round uplink
    bits are charged only to the sampled clients (traced metric AND the
    exact integer ledger), and the mask schedule replayed on the host
    (``participation.round_masks``) matches what the compiled scan drew;
  * specs round-trip through dict/JSON losslessly and reject unknown
    fields/values with errors that name the valid choices.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro import api
from repro.core import engine, participation as pl
from repro.core.quantization import exact_payload_bits, payload_bits
from repro.launch.mesh import make_client_mesh

ROUNDS = 6
FEDNEW_HP = {"rho": 0.1, "alpha": 0.03, "hessian_period": 1}


def a1a_spec(**overrides) -> api.ExperimentSpec:
    kw = dict(
        objective=api.ObjectiveSpec(kind="logreg", mu=1e-3),
        partition=api.PartitionSpec(dataset="a1a", seed=0),
        solver=api.SolverSpec("fednew", FEDNEW_HP),
        schedule=api.ScheduleSpec(rounds=ROUNDS, block_size=4),
    )
    kw.update(overrides)
    return api.ExperimentSpec(**kw)


def _metrics_dict_exact(result: api.RunResult, ref_metrics) -> None:
    """RunResult metric lists == raw engine stacked metrics, bit for bit
    (``float()`` of a float32 is exact; so is the round-trip back)."""
    for name, vals in zip(ref_metrics._fields, ref_metrics):
        np.testing.assert_array_equal(
            np.asarray(vals, dtype=np.float64),
            np.asarray(result.metrics[name], dtype=np.float64),
            err_msg=f"metric {name}",
        )


# ---------------------------------------------------------------------------
# acceptance: full participation == pre-API engine, bit-exact
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mesh_devices", [None, 1], ids=["scan", "shard_map"])
@pytest.mark.parametrize("solver,hp", [
    ("fednew", FEDNEW_HP),
    ("q-fednew", {**FEDNEW_HP, "bits": 3}),
], ids=["fednew", "q-fednew"])
def test_full_participation_bit_exact(mesh_devices, solver, hp):
    spec = a1a_spec(
        solver=api.SolverSpec(solver, hp),
        schedule=api.ScheduleSpec(
            rounds=ROUNDS, block_size=4, mesh_devices=mesh_devices
        ),
        participation=api.ParticipationSpec(fraction=1.0),
    )
    obj, data = api.build_problem(spec)
    sol = engine.get_solver(solver, **hp)
    mesh = make_client_mesh(1) if mesh_devices else None
    _, m_ref = engine.run(
        sol, obj, data, ROUNDS,
        key=jax.random.PRNGKey(spec.seed), block_size=4, mesh=mesh,
    )
    res = api.run(spec)
    _metrics_dict_exact(res, m_ref)
    # full participation: every client charged every round, exact ints
    payload = (payload_bits(3, data.dim) if solver == "q-fednew"
               else exact_payload_bits(data.dim, 32))
    assert res.sampled_clients == [data.n_clients] * ROUNDS
    assert res.uplink_bits_total == [payload * data.n_clients] * ROUNDS
    assert res.cumulative_uplink_bits_per_client[-1] == payload * ROUNDS


# ---------------------------------------------------------------------------
# acceptance: partial participation — deterministic, bits only for sampled
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("solver,hp,payload_fn", [
    ("fednew", FEDNEW_HP, lambda d: exact_payload_bits(d, 32)),
    ("q-fednew", {**FEDNEW_HP, "bits": 2}, lambda d: payload_bits(2, d)),
], ids=["fednew", "q-fednew"])
def test_partial_participation_deterministic_bits(solver, hp, payload_fn):
    part = api.ParticipationSpec(fraction=0.4, kind="bernoulli", seed=11)
    spec = a1a_spec(
        solver=api.SolverSpec(solver, hp),
        schedule=api.ScheduleSpec(rounds=8, block_size=3),
        participation=part,
    )
    res1 = api.run(spec)
    res2 = api.run(spec)
    assert res1.metrics == res2.metrics  # deterministic per seed, exactly

    # the host replay of the mask schedule matches what the scan drew
    masks = pl.round_masks(part.to_runtime(), 8, res1.n_clients)
    counts = [int(m.sum()) for m in masks]
    assert res1.sampled_clients == counts
    assert min(counts) < res1.n_clients  # genuinely partial at this seed
    assert len(set(counts)) > 1  # bernoulli: counts vary round to round

    # traced metric: payload x sampled fraction, only sampled clients pay
    payload = payload_fn(res1.dim)
    expect = [payload * c / res1.n_clients for c in counts]
    np.testing.assert_allclose(
        res1.metrics["uplink_bits_per_client"], expect, rtol=1e-6
    )
    # exact integer ledger
    assert res1.uplink_bits_total == [payload * c for c in counts]
    assert res1.cumulative_uplink_bits_total[-1] == payload * sum(counts)


def test_partial_participation_same_across_schedules():
    """host / scan / shard_map draw identical masks and produce the same
    trajectories (float tolerance — schedules reorder float reductions)."""
    part = api.ParticipationSpec(fraction=0.5, kind="fixed", seed=3)
    specs = {
        "host": a1a_spec(schedule=api.ScheduleSpec(rounds=ROUNDS, mode="host"),
                         participation=part),
        "scan": a1a_spec(schedule=api.ScheduleSpec(rounds=ROUNDS, block_size=2),
                         participation=part),
        "shard": a1a_spec(schedule=api.ScheduleSpec(rounds=ROUNDS,
                                                    mesh_devices=1),
                          participation=part),
    }
    runs = {k: api.run(s) for k, s in specs.items()}
    # fixed law: exactly round(0.5 * 10) clients every round, every schedule
    for res in runs.values():
        assert res.sampled_clients == [5] * ROUNDS
    ref = np.asarray(runs["host"].metrics["loss"])
    for k in ("scan", "shard"):
        np.testing.assert_allclose(
            ref, np.asarray(runs[k].metrics["loss"]), rtol=1e-4, atol=1e-6,
            err_msg=k,
        )


def test_partial_participation_baselines_and_empty_rounds():
    """Baselines honor the mask through the Objective aggregates, and a
    bernoulli round that samples nobody is a no-op (x unchanged), not NaN."""
    for solver, hp in [("fedgd", {"lr": 2.0}), ("newton-zero", {}),
                       ("newton", {})]:
        res = api.run(a1a_spec(
            solver=api.SolverSpec(solver, hp),
            schedule=api.ScheduleSpec(rounds=4),
            participation=api.ParticipationSpec(fraction=0.5, kind="fixed",
                                                seed=1),
        ))
        assert all(np.isfinite(res.metrics["loss"])), solver
        assert res.sampled_clients == [5] * 4
    # tiny fraction: some rounds sample zero clients — every solver must
    # degrade to a no-op round (x unchanged), including exact Newton, whose
    # masked Hessian would otherwise be the singular all-zero matrix
    tiny = api.ParticipationSpec(fraction=0.05, seed=0)
    for solver, hp in [("fednew", FEDNEW_HP), ("newton", {}),
                       ("fedgd", {"lr": 2.0})]:
        res = api.run(a1a_spec(
            solver=api.SolverSpec(solver, hp),
            schedule=api.ScheduleSpec(rounds=10),
            participation=tiny,
        ))
        assert 0 in res.sampled_clients, solver
        assert all(np.isfinite(res.metrics["loss"])), solver
        # an empty round transmits nothing
        empty = res.sampled_clients.index(0)
        assert res.uplink_bits_total[empty] == 0
        assert res.metrics["uplink_bits_per_client"][empty] == 0.0


def test_dual_sum_invariant_under_participation():
    """Masked dual updates preserve sum_i lam_i = 0 (eq. 13's premise)."""
    res = api.run(a1a_spec(
        schedule=api.ScheduleSpec(rounds=8),
        participation=api.ParticipationSpec(fraction=0.5, kind="bernoulli",
                                            seed=7),
    ))
    assert res.metrics["dual_sum_residual"][-1] < 1e-3


# ---------------------------------------------------------------------------
# fixed-count participation: ceil semantics (regression)
# ---------------------------------------------------------------------------


def test_fixed_count_ceil_never_undersamples():
    """``fixed`` samples ceil(fraction*n): banker's rounding used to turn
    "25% of 10 clients" into 2 (int(round(2.5))), under-sampling the spec'd
    fraction. Half-way cases are the regression surface."""
    cases = {
        (0.25, 10): 3,  # the bug: round(2.5) == 2
        (0.5, 10): 5,
        (0.75, 10): 8,  # round(7.5) == 8 by luck; ceil by definition
        (0.15, 10): 2,
        (0.25, 2): 1,
        (0.1, 30): 3,   # 0.1*30 == 3.0000000000000004: no float over-ceil
        (0.05, 10): 1,
        (1.0, 7): 7,
    }
    for (f, n), want in cases.items():
        got = pl.Participation(fraction=f, kind="fixed").fixed_count(n)
        assert got == want, (f, n, got, want)
        assert got >= f * n - 1e-6  # never fewer than the asked-for fraction


@pytest.mark.parametrize("mesh_devices", [None, 1], ids=["scan", "shard_map"])
def test_fixed_count_host_replay_matches_scan(mesh_devices):
    """At the half-way case the in-scan mask (round_mask inside lax.scan)
    and the host replay (round_masks -> sampled_counts, the exact-ledger
    basis) must agree on ceil counts under every schedule."""
    part = api.ParticipationSpec(fraction=0.25, kind="fixed", seed=2)
    res = api.run(a1a_spec(
        schedule=api.ScheduleSpec(rounds=4, mesh_devices=mesh_devices),
        participation=part,
    ))
    assert res.sampled_clients == [3] * 4  # ceil(0.25 * 10), not round
    payload = exact_payload_bits(res.dim, 32)
    np.testing.assert_allclose(
        res.metrics["uplink_bits_per_client"],
        [payload * 3 / 10] * 4, rtol=1e-6,
    )
    assert res.uplink_bits_total == [payload * 3] * 4


# ---------------------------------------------------------------------------
# forced-empty round: end-to-end freeze through both schedules (regression)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mesh_devices", [None, 1], ids=["scan", "shard_map"])
@pytest.mark.parametrize("solver,hp", [
    ("fednew", FEDNEW_HP),
    ("q-fednew", {**FEDNEW_HP, "bits": 3}),
], ids=["fednew", "q-fednew"])
def test_empty_round_freezes_state_end_to_end(mesh_devices, solver, hp):
    """An all-zero Bernoulli round must be a frozen no-op all the way
    through the engine on the a1a problem: finite metrics, x unchanged,
    lam/comm/curv untouched, 0 bits charged — under scan AND shard_map.
    (The engine-level contract for EVERY registry solver lives in
    tests/test_solver_conformance.py; this keeps the api-built-problem +
    explicit-mesh path covered through the shared helpers.)"""
    import conformance as conf

    n = 10
    part, empty_r = conf.empty_round_participation(rounds=6, n=n)

    spec = a1a_spec()
    obj, data = api.build_problem(spec)
    sol = engine.get_solver(solver, **hp)
    mesh = make_client_mesh(1) if mesh_devices else None

    def run_rounds(r):
        return engine.run(
            sol, obj, data, r, key=jax.random.PRNGKey(0), mesh=mesh,
            participation=part,
        )

    before, _ = run_rounds(empty_r)          # ends just before the empty round
    after, metrics = run_rounds(empty_r + 1)  # includes it
    # host replay confirms the round really was empty
    assert pl.sampled_counts(part, empty_r + 1, n)[empty_r] == 0

    for field in type(before)._fields:
        if field in conf.FREEZE_EXEMPT:
            continue
        np.testing.assert_array_equal(
            np.asarray(getattr(before, field)),
            np.asarray(getattr(after, field)),
            err_msg=f"{field} changed across an empty round",
        )
    for name, vals in zip(metrics._fields, metrics):
        assert np.all(np.isfinite(np.asarray(vals))), name
    assert float(metrics.uplink_bits_per_client[empty_r]) == 0.0
    assert float(metrics.direction_norm[empty_r]) == 0.0


# ---------------------------------------------------------------------------
# RunResult: exact-int JSON ledger + compile/steady wall-clock split
# ---------------------------------------------------------------------------


def test_save_json_keeps_ledger_ints_exact(tmp_path):
    """numpy integers leaking into the ledger must serialize as JSON ints
    (the old ``default=float`` silently rounded past 2^53); unknown types
    must raise instead of degrading."""
    res = api.run(a1a_spec(schedule=api.ScheduleSpec(rounds=2)))
    big = 2**60 + 1  # not representable as a float64
    res.uplink_bits_total = [np.int64(b) for b in res.uplink_bits_total]
    res.cumulative_uplink_bits_total = [
        np.int64(res.cumulative_uplink_bits_total[0]), np.int64(big)
    ]
    path = tmp_path / "result.json"
    res.save_json(str(path))
    payload = json.loads(path.read_text())
    for got, want in zip(
        payload["cumulative_uplink_bits_total"],
        res.cumulative_uplink_bits_total,
    ):
        assert isinstance(got, int), type(got)
        assert got == int(want)
    assert payload["cumulative_uplink_bits_total"][-1] == big
    for got in payload["uplink_bits_total"]:
        assert isinstance(got, int)

    res.spec["not_json"] = object()
    with pytest.raises(TypeError, match="refuses"):
        res.save_json(str(tmp_path / "bad.json"))


def test_wall_clock_split_compile_vs_steady():
    """First dispatched block carries trace+compile; later blocks are
    steady-state. The split fields must cover the total and the compile
    block must dominate a tiny CPU problem."""
    res = api.run(a1a_spec(
        schedule=api.ScheduleSpec(rounds=6, block_size=2)  # 3 equal blocks
    ))
    assert res.compile_s > 0.0
    assert res.steady_wall_clock_s > 0.0
    assert res.compile_s + res.steady_wall_clock_s <= res.wall_clock_s + 1e-3
    # 2 steady blocks re-run a compiled function: far cheaper than block 1
    assert res.steady_wall_clock_s < res.compile_s
    assert {"compile_s", "steady_wall_clock_s"} <= res.to_dict().keys()
    # the round counts each window covers ride along (per-round figures
    # must divide by these, not by the spec's total rounds)
    assert res.compile_rounds == 2
    assert res.steady_rounds == 4


# ---------------------------------------------------------------------------
# spec serialization
# ---------------------------------------------------------------------------


def test_spec_json_round_trip():
    spec = api.ExperimentSpec(
        name="rt",
        objective=api.ObjectiveSpec(kind="logreg", mu=1e-2),
        partition=api.PartitionSpec(dataset="w8a", scheme="dirichlet",
                                    alpha=0.3, seed=42, dtype="float32"),
        solver=api.SolverSpec("q-fednew", {"rho": 0.1, "alpha": 0.03,
                                           "bits": 3}),
        schedule=api.ScheduleSpec(rounds=150, block_size=64,
                                  mesh_devices="auto"),
        participation=api.ParticipationSpec(fraction=0.5, kind="fixed",
                                            seed=1),
        telemetry=api.TelemetrySpec(f_star_newton_iters=30, tag="t"),
        seed=9,
    )
    assert api.ExperimentSpec.from_json(spec.to_json()) == spec
    assert spec.to_dict()["schema_version"] == api.SCHEMA_VERSION
    # the optional comm sections round-trip too (null and populated)
    assert spec.to_dict()["compression"] is None
    comm_spec = api.ExperimentSpec(
        solver=api.SolverSpec("fednew", {"rho": 0.1, "alpha": 0.03}),
        compression=api.CompressionSpec(
            codec="topk", params={"fraction": 0.1, "value_bits": 32}
        ),
        network=api.NetworkSpec(heterogeneity="lognormal", sigma=0.5),
    )
    assert api.ExperimentSpec.from_json(comm_spec.to_json()) == comm_spec


@settings(max_examples=25, deadline=None)
@given(
    dataset=st.sampled_from(["a1a", "w7a", "w8a", "phishing"]),
    scheme=st.sampled_from(["iid", "dirichlet"]),
    alpha=st.floats(0.01, 100.0, allow_nan=False),
    rounds=st.integers(1, 10_000),
    block=st.one_of(st.none(), st.integers(1, 512)),
    mode=st.sampled_from(["scan", "host"]),
    solver=st.sampled_from(["fednew", "fedgd", "newton"]),
    fraction=st.floats(0.01, 1.0, allow_nan=False),
    kind=st.sampled_from(["bernoulli", "fixed"]),
    seed=st.integers(0, 2**31 - 1),
)
def test_spec_round_trip_property(dataset, scheme, alpha, rounds, block,
                                  mode, solver, fraction, kind, seed):
    hp = {"rho": 0.5, "alpha": 0.1} if solver == "fednew" else {}
    spec = api.ExperimentSpec(
        partition=api.PartitionSpec(dataset=dataset, scheme=scheme,
                                    alpha=alpha, seed=seed),
        solver=api.SolverSpec(solver, hp),
        schedule=api.ScheduleSpec(rounds=rounds, block_size=block, mode=mode),
        participation=api.ParticipationSpec(fraction=fraction, kind=kind,
                                            seed=seed),
        seed=seed,
    )
    assert api.ExperimentSpec.from_json(spec.to_json()) == spec
    assert api.ExperimentSpec.from_dict(spec.to_dict()) == spec


def test_spec_validation_errors_name_valid_choices():
    with pytest.raises(ValueError, match="registered solvers"):
        api.SolverSpec("sgd")
    with pytest.raises(ValueError, match="valid hparams"):
        api.SolverSpec("fednew", {"rhoo": 1.0})
    with pytest.raises(ValueError, match="bits"):
        api.SolverSpec("q-fednew", {"rho": 0.1})
    with pytest.raises(ValueError, match="unknown spec key"):
        api.ExperimentSpec.from_dict({"solvr": {}})
    with pytest.raises(ValueError, match="unknown field"):
        api.ExperimentSpec.from_dict({"schedule": {"round": 5}})
    with pytest.raises(ValueError, match="fraction"):
        api.ParticipationSpec(fraction=1.5)
    with pytest.raises(ValueError, match="custom"):
        api.PartitionSpec(dataset="custom")
    with pytest.raises(ValueError, match="scan-compiled"):
        api.ScheduleSpec(mode="host", mesh_devices=1)
    with pytest.raises(ValueError, match="quadratic"):
        api.ExperimentSpec(
            objective=api.ObjectiveSpec(kind="quadratic"),
            partition=api.PartitionSpec(dataset="a1a", scheme="dirichlet"),
        )


def test_quadratic_objective_spec_runs():
    res = api.run(api.ExperimentSpec(
        objective=api.ObjectiveSpec(kind="quadratic"),
        partition=api.PartitionSpec(dataset="custom", n_clients=4,
                                    samples_per_client=1, dim=8, cond=5.0),
        solver=api.SolverSpec("fednew", {"rho": 0.5, "alpha": 0.1}),
        schedule=api.ScheduleSpec(rounds=5),
    ))
    assert len(res.metrics["loss"]) == 5
    assert all(np.isfinite(res.metrics["loss"]))


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def test_cli_runs_quickstart_spec(tmp_path):
    repo = Path(__file__).resolve().parent.parent
    out = tmp_path / "result.json"
    proc = subprocess.run(
        [sys.executable, "-m", "repro.api",
         str(repo / "examples" / "specs" / "quickstart.json"),
         "--out", str(out)],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": str(repo / "src")},
    )
    assert proc.returncode == 0, proc.stderr
    assert "final loss" in proc.stdout
    payload = json.loads(out.read_text())
    assert payload["rounds"] == 20
    assert len(payload["metrics"]["loss"]) == 20
    assert payload["metrics"]["gap"][-1] < payload["metrics"]["gap"][0]
    assert (payload["cumulative_uplink_bits_total"][-1]
            == payload["n_clients"] * 32 * payload["dim"] * 20)


def test_cli_template_round_trips():
    repo = Path(__file__).resolve().parent.parent
    proc = subprocess.run(
        [sys.executable, "-m", "repro.api", "--template"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "PYTHONPATH": str(repo / "src")},
    )
    assert proc.returncode == 0, proc.stderr
    spec = api.ExperimentSpec.from_json(proc.stdout)
    assert spec.name == "template"


@pytest.mark.parametrize("env_set", [False, True], ids=["default", "env"])
def test_compile_cache_dir(tmp_path, env_set):
    """The entry points' compile cache: where JAX_COMPILATION_CACHE_DIR is
    set, JAX's own setting stands; otherwise the fixed <repo>/.jax_cache."""
    repo = Path(__file__).resolve().parent.parent
    env = {**os.environ, "PYTHONPATH": str(repo / "src")}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = repo / ".jax_cache"
    if env_set:
        want = tmp_path / "cache"
        env["JAX_COMPILATION_CACHE_DIR"] = str(want)
    proc = subprocess.run(
        [sys.executable, "-c",
         "import jax\n"
         "from repro.launch.compile_cache import enable_compile_cache\n"
         "print(enable_compile_cache())\n"
         "print(jax.config.jax_compilation_cache_dir)\n"],
        capture_output=True, text=True, timeout=300, env=env,
    )
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split() == [str(want), str(want)]
