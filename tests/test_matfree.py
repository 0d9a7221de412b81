"""Matrix-free FedNew (hessian_repr="matfree"): CG-on-HVP eq. 9 solve path.

Acceptance contract of the matfree PR:

  * the closed-form ``Objective.local_hvp`` oracles agree with the dense
    ``local_hessian`` contraction;
  * ``cg_solve_clients`` solves n independent damped systems (per-client
    Krylov recurrences, not one coupled block system);
  * a matfree run matches the dense FedNew trajectory to <= 1e-5 relative
    loss gap at the paper's d=267, under BOTH the scan and the shard_map
    schedule (CG run to convergence on the well-damped system);
  * a d=1e5 logreg round runs on CPU without materializing any (n, d, d)
    array — per-client state is O(d) (the curv cache holds anchor points);
  * the dense default stays the default and the new knobs round-trip
    through the declarative spec layer.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import engine, fednew, hvp
from repro.core.objectives import (
    ClientDataset,
    Objective,
    logistic_regression,
    quadratic,
    quadratic_optimum,
)
from repro.data import synthetic
from repro.launch.mesh import make_client_mesh

KEY = jax.random.PRNGKey(0)
D = 267  # the paper's w8a dimension — the acceptance point


@pytest.fixture(scope="module")
def logreg_267():
    spec = synthetic.DatasetSpec(
        "custom", n_clients=8, samples_per_client=64, dim=D, sparse=True
    )
    return logistic_regression(1e-3), synthetic.make_dataset(spec, KEY)


# ---------------------------------------------------------------------------
# oracles
# ---------------------------------------------------------------------------


def test_logreg_hvp_matches_dense_hessian(logreg_267):
    obj, data = logreg_267
    n = data.n_clients
    x = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (D,))
    v = jax.random.normal(jax.random.PRNGKey(2), (n, D))
    dense = jnp.einsum("nij,nj->ni", obj.local_hessian(x, data), v)
    free = obj.local_hvp(jnp.broadcast_to(x, (n, D)), data, v)
    np.testing.assert_allclose(free, dense, rtol=1e-4, atol=1e-5)


def test_logreg_hvp_honors_per_client_anchors(logreg_267):
    """Each client differentiates at its OWN anchor (stale-curvature
    semantics under partial participation / hessian_period > 1)."""
    obj, data = logreg_267
    n = data.n_clients
    anchors = 0.1 * jax.random.normal(jax.random.PRNGKey(3), (n, D))
    v = jax.random.normal(jax.random.PRNGKey(4), (n, D))
    per_client = jnp.stack([
        obj.local_hessian(anchors[i], data)[i] @ v[i] for i in range(n)
    ])
    free = obj.local_hvp(anchors, data, v)
    np.testing.assert_allclose(free, per_client, rtol=1e-4, atol=1e-5)


def test_logreg_curvature_split_is_its_hvp(logreg_267):
    """local_curvature's weights rebuild local_hvp: A^T (w * (A v)) / m +
    l2 v at per-client anchors; the objectives that have no such split
    say so."""
    obj, data = logreg_267
    n, m = data.n_clients, data.features.shape[1]
    x = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (n, D))
    v = jax.random.normal(jax.random.PRNGKey(2), (n, D))
    w = obj.local_curvature(x, data)
    assert w.shape == (n, m) and obj.l2 == 1e-3
    A = data.features
    split = jnp.einsum("nmd,nm->nd", A, w * jnp.einsum("nmd,nd->nm", A, v)) / m + obj.l2 * v
    np.testing.assert_allclose(split, obj.local_hvp(x, data, v), rtol=1e-5, atol=1e-6)
    assert obj.has_curvature and not quadratic().has_curvature


def test_quadratic_hvp_is_P_apply():
    data = synthetic.make_quadratic_dataset(KEY, n_clients=3, dim=12, cond=4.0)
    obj = quadratic()
    v = jax.random.normal(jax.random.PRNGKey(5), (3, 12))
    np.testing.assert_allclose(
        obj.local_hvp(jnp.zeros((3, 12)), data, v),
        jnp.einsum("nij,nj->ni", data.features, v),
        rtol=1e-6,
    )


# ---------------------------------------------------------------------------
# batched per-client CG
# ---------------------------------------------------------------------------


def test_cg_solve_clients_matches_direct_solve():
    n, d, damping = 5, 24, 0.7
    data = synthetic.make_quadratic_dataset(
        jax.random.PRNGKey(6), n_clients=n, dim=d, cond=20.0
    )
    P, rhs = data.features, jax.random.normal(jax.random.PRNGKey(7), (n, d))
    res = hvp.cg_solve_clients(
        lambda v: jnp.einsum("nij,nj->ni", P, v), rhs,
        damping=damping, iters=200, tol=1e-8,
    )
    eye = jnp.eye(d)
    direct = jnp.stack(
        [jnp.linalg.solve(P[i] + damping * eye, rhs[i]) for i in range(n)]
    )
    np.testing.assert_allclose(res.x, direct, rtol=1e-4, atol=1e-5)
    assert res.residual_norm.shape == (n,)


def test_cg_solve_clients_recurrences_are_independent():
    """Scaling one client's system must not change another client's
    iterates (the stacked-system pitfall: a single global inner product
    couples every client's step sizes)."""
    n, d = 3, 10
    data = synthetic.make_quadratic_dataset(
        jax.random.PRNGKey(8), n_clients=n, dim=d, cond=8.0
    )
    P = data.features
    rhs = jax.random.normal(jax.random.PRNGKey(9), (n, d))

    def solve(P, iters):
        return hvp.cg_solve_clients(
            lambda v: jnp.einsum("nij,nj->ni", P, v), rhs,
            damping=0.5, iters=iters,
        ).x

    few = 3  # deliberately unconverged: iterates, not the fixed point
    base = solve(P, few)
    # blow up client 2's spectrum by 100x; clients 0 and 1 must not move
    P_scaled = P.at[2].multiply(100.0)
    scaled = solve(P_scaled, few)
    np.testing.assert_allclose(scaled[:2], base[:2], rtol=1e-5)
    assert not np.allclose(scaled[2], base[2])


# ---------------------------------------------------------------------------
# trajectory: matfree vs dense at d=267 (acceptance)
# ---------------------------------------------------------------------------

MATFREE_HP = {"rho": 0.1, "alpha": 0.03, "hessian_period": 1,
              "hessian_repr": "matfree", "cg_iters": 200, "cg_tol": 1e-7}
DENSE_HP = {"rho": 0.1, "alpha": 0.03, "hessian_period": 1}


@pytest.mark.parametrize("mesh_devices", [None, 1], ids=["scan", "shard_map"])
def test_matfree_matches_dense_trajectory_d267(logreg_267, mesh_devices):
    obj, data = logreg_267
    rounds = 6
    mesh = make_client_mesh(mesh_devices) if mesh_devices else None
    losses = {}
    for label, hp in [("dense", DENSE_HP), ("matfree", MATFREE_HP)]:
        _, m = engine.run(
            engine.get_solver("fednew", **hp), obj, data, rounds,
            key=jax.random.PRNGKey(0), mesh=mesh,
        )
        losses[label] = np.asarray(m.loss)
    rel = np.max(
        np.abs(losses["dense"] - losses["matfree"]) / np.abs(losses["dense"])
    )
    assert rel <= 1e-5, f"relative loss gap {rel:.2e} > 1e-5"


@pytest.mark.parametrize("mesh_devices", [None, 1], ids=["scan", "shard_map"])
@pytest.mark.parametrize("d", [D, 300])
def test_matfree_glm_kernel_matches_reference(mesh_devices, d):
    """solve_backend="pallas" (the glm_hvp kernel, interpreted here) runs
    the same matfree job as "reference" (local_hvp): losses and the model
    agree within the dense-trajectory tolerance, at w8a's d and at a d
    with a ragged last lane group."""
    spec = synthetic.DatasetSpec(
        "custom", n_clients=4, samples_per_client=37, dim=d, sparse=True
    )
    obj, data = logistic_regression(1e-3), synthetic.make_dataset(spec, KEY)
    mesh = make_client_mesh(mesh_devices) if mesh_devices else None
    runs = {}
    for backend in ("reference", "pallas"):
        hp = dict(MATFREE_HP, cg_iters=50, solve_backend=backend)
        st, m = engine.run(
            engine.get_solver("fednew", **hp), obj, data, 4,
            key=jax.random.PRNGKey(0), mesh=mesh,
        )
        runs[backend] = (np.asarray(m.loss), np.asarray(st.x))
    (ref_loss, ref_x), (got_loss, got_x) = runs["reference"], runs["pallas"]
    assert np.max(np.abs(got_loss - ref_loss) / np.abs(ref_loss)) <= 1e-5
    assert np.linalg.norm(got_x - ref_x) <= 1e-5 * np.linalg.norm(ref_x)


def test_matfree_auto_on_cpu_lowers_local_hvp(logreg_267):
    """backend="auto" on the CPU keeps the local_hvp path, lowered exactly
    as "reference" lowers it: no glm_hvp kernel in the program."""
    obj, data = logreg_267

    def lowered(**kw):
        cfg = fednew.FedNewConfig(**{**MATFREE_HP, "cg_iters": 4}, **kw)
        state = fednew.init(obj, data, cfg, KEY)
        step = jax.jit(lambda s: fednew.step(s, obj, data, cfg))
        return step.lower(state).as_text()

    auto = lowered()
    assert "glm_hvp" not in auto
    assert auto == lowered(solve_backend="reference")
    assert "glm_hvp" in lowered(solve_backend="pallas")


def test_matfree_qfednew_and_hessian_period(logreg_267):
    """Q-FedNew composes with matfree, and hessian_period=0 freezes the
    anchor at x^0 (the r=0 zeroth-Hessian variant, now O(n d) state)."""
    obj, data = logreg_267
    cfg = fednew.FedNewConfig(
        rho=0.1, alpha=0.03, bits=3, hessian_repr="matfree",
        cg_iters=100, cg_tol=1e-7, hessian_period=0,
    )
    state = fednew.init(obj, data, cfg, KEY)
    assert state.curv.shape == (data.n_clients, D)  # anchors, not factors
    anchor0 = state.curv
    for _ in range(3):
        state, m = jax.jit(
            lambda s: fednew.step(s, obj, data, cfg)
        )(state)
    assert jnp.array_equal(state.curv, anchor0)
    assert np.isfinite(float(m.loss))


def test_matfree_quadratic_reaches_optimum():
    data = synthetic.make_quadratic_dataset(
        jax.random.PRNGKey(3), n_clients=4, dim=16, cond=5.0
    )
    obj = quadratic()
    cfg = fednew.FedNewConfig(
        rho=0.5, alpha=0.1, hessian_repr="matfree", cg_iters=64, cg_tol=1e-8
    )
    st, _ = engine.run(fednew.solver(cfg), obj, data, 40, key=KEY)
    assert float(jnp.linalg.norm(st.x - quadratic_optimum(data))) < 1e-2


def test_matfree_partial_participation_freezes_anchors(logreg_267):
    """Unsampled clients keep their stale curvature anchor — mirroring the
    dense path's stale-factor semantics."""
    obj, data = logreg_267
    cfg = fednew.FedNewConfig(**{**MATFREE_HP, "cg_iters": 50})
    state = fednew.init(obj, data, cfg, KEY)
    mask = jnp.zeros((data.n_clients,)).at[0].set(1.0)
    new_state, m = jax.jit(
        lambda s: fednew.step(s, obj, data, cfg, mask=mask)
    )(state)
    # sampled client 0 re-anchored at x^0 (= same x), others frozen at init
    np.testing.assert_array_equal(
        np.asarray(new_state.curv[1:]), np.asarray(state.curv[1:])
    )
    assert np.isfinite(float(m.loss))


# ---------------------------------------------------------------------------
# large d: the only path that survives (acceptance)
# ---------------------------------------------------------------------------


def test_matfree_runs_d_1e5_without_dense_hessians(tmp_path):
    """The shipped large-d example spec: d=1e5 logreg rounds on CPU. The
    dense path would need n * d^2 * 4B = 160 GB of Hessian cache; matfree
    state is (n, d). Runs through the full declarative stack."""
    with open("examples/specs/matfree_large_d.json") as f:
        spec = api.ExperimentSpec.from_dict(json.load(f))
    assert spec.partition.dim == 100_000
    obj, data = api.build_problem(spec)
    sol = api.build_solver(spec.solver)
    state, metrics = engine.run(
        sol, obj, data, spec.schedule.rounds,
        key=jax.random.PRNGKey(spec.seed),
        block_size=spec.schedule.block_size,
    )
    assert state.curv.shape == (4, 100_000)  # O(n d): anchors, no factors
    assert all(np.isfinite(np.asarray(metrics.loss)))
    # and the loss actually moves — these are real Newton-type rounds
    assert metrics.loss[-1] < metrics.loss[0]


# ---------------------------------------------------------------------------
# config/spec plumbing
# ---------------------------------------------------------------------------


def test_config_validation():
    with pytest.raises(ValueError, match="hessian_repr"):
        fednew.FedNewConfig(hessian_repr="sparse")
    with pytest.raises(ValueError, match="cg_iters"):
        fednew.FedNewConfig(hessian_repr="matfree", cg_iters=0)
    with pytest.raises(ValueError, match="cg_tol"):
        fednew.FedNewConfig(hessian_repr="matfree", cg_tol=-1.0)
    with pytest.raises(ValueError, match="matfree"):
        fednew.FedNewConfig(hessian_repr="matfree", use_kernel=True)
    # solve_backend="pallas" on matfree routes the HVPs to the glm_hvp kernel
    cfg = fednew.FedNewConfig(hessian_repr="matfree", solve_backend="pallas")
    assert not cfg.solve_uses_kernel  # no dense Hessians are cached
    obj = logistic_regression(1e-3)
    curv, data = jnp.zeros((2, 3)), ClientDataset(jnp.ones((2, 4, 3)), jnp.ones((2, 4)))
    text = str(jax.make_jaxpr(fednew._matfree_matvec(curv, cfg, obj, data))(curv))
    assert "glm_hvp" in text and "pallas_call" in text


def test_matfree_requires_hvp_oracle(logreg_267):
    obj, data = logreg_267
    blind = Objective(
        local_loss=obj.local_loss,
        local_grad=obj.local_grad,
        local_hessian=obj.local_hessian,
    )
    assert not blind.has_hvp
    cfg = fednew.FedNewConfig(hessian_repr="matfree")
    with pytest.raises(ValueError, match="local_hvp"):
        fednew.init(blind, data, cfg, KEY)


def test_solver_spec_accepts_and_round_trips_matfree_hparams():
    spec = api.ExperimentSpec(
        solver=api.SolverSpec("fednew", {
            "rho": 0.1, "alpha": 0.03,
            "hessian_repr": "matfree", "cg_iters": 64, "cg_tol": 1e-6,
        }),
    )
    assert api.ExperimentSpec.from_json(spec.to_json()) == spec
    # registry exposes the new knobs for validation/error messages
    for knob in ("hessian_repr", "cg_iters", "cg_tol"):
        assert knob in engine.solver_hparam_names("fednew")
    # bad values fail at spec-build time with the valid choices named
    with pytest.raises(ValueError, match="hessian_repr"):
        api.SolverSpec("fednew", {"hessian_repr": "wavelet"})


def test_api_run_matfree_spec_end_to_end():
    res = api.run(api.ExperimentSpec(
        partition=api.PartitionSpec(
            dataset="custom", n_clients=6, samples_per_client=32, dim=40
        ),
        solver=api.SolverSpec("fednew", {
            "rho": 0.5, "alpha": 0.1,
            "hessian_repr": "matfree", "cg_iters": 80, "cg_tol": 1e-7,
        }),
        schedule=api.ScheduleSpec(rounds=5, block_size=2),
    ))
    assert all(np.isfinite(res.metrics["loss"]))
    assert res.metrics["loss"][-1] < res.metrics["loss"][0]
    # uplink accounting is repr-independent: still the full-precision y_i
    assert res.uplink_bits_total == [32 * 40 * 6] * 5
