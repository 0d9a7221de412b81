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
    array — per-client state is O(d) (the curv cache holds each client's
    GLM weights, O(m));
  * the state carries every client's gradient and GLM weights at x, made
    by the evaluation of the round that produced x, so a round reads the
    f32 features twice outside the CG loop (A x and A^T r at x^{k+1});
  * the dense default stays the default and the new knobs round-trip
    through the declarative spec layer.
"""

import json
import os
import re
import subprocess
import sys

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
    """local_curvature's weights at x rebuild local_hvp at anchors x:
    A^T (w * (A v)) / m + l2 v; the objectives that have no such split
    say so."""
    obj, data = logreg_267
    n, m = data.n_clients, data.features.shape[1]
    x = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (D,))
    v = jax.random.normal(jax.random.PRNGKey(2), (n, D))
    w = obj.local_curvature(x, data)
    assert w.shape == (n, m) and obj.l2 == 1e-3
    A = data.features
    split = jnp.einsum("nmd,nm->nd", A, w * jnp.einsum("nmd,nd->nm", A, v)) / m + obj.l2 * v
    anchors = jnp.broadcast_to(x, (n, D))
    np.testing.assert_allclose(split, obj.local_hvp(anchors, data, v), rtol=1e-5, atol=1e-6)
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


def test_matfree_auto_on_cpu_lowers_reference(logreg_267):
    """backend="auto" on the CPU keeps the jnp HVP (the glm_hvp reference,
    local_hvp's expression on the cached weights), lowered exactly as
    "reference" lowers it: no glm_hvp kernel in the program."""
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
    rows = data.features.shape[1]
    assert state.curv.shape == (data.n_clients, rows)  # GLM weights, not factors
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


def test_matfree_masked_refresh_keeps_weights_at_last_anchor(logreg_267):
    """Partial participation over several rounds with x moving: a sampled
    client's row of the GLM cache is the previous state's curv_x (its
    weights at the x it saw), and every row is local_curvature at the x of
    the last round its client was sampled in (x^0 before its first)."""
    obj, data = logreg_267
    n = data.n_clients
    cfg = fednew.FedNewConfig(**{**MATFREE_HP, "cg_iters": 8})
    step = jax.jit(lambda s, mk: fednew.step(s, obj, data, cfg, mask=mk))
    state = init = fednew.init(obj, data, cfg, KEY)
    anchor = [state.x] * n
    masks = [[1, 0, 0, 1, 0, 0, 1, 0], [0, 1, 0, 0, 1, 0, 0, 0],
             [1, 1, 0, 0, 0, 0, 0, 1], [0, 0, 0, 1, 1, 0, 0, 0]]
    for mk in masks:
        new, _ = step(state, jnp.asarray(mk, jnp.float32))
        assert not np.allclose(np.asarray(new.x), np.asarray(state.x))
        for i in range(n):
            if mk[i]:
                np.testing.assert_array_equal(np.asarray(new.curv[i]),
                                              np.asarray(state.curv_x[i]))
                anchor[i] = state.x
            want = np.asarray(obj.local_curvature(anchor[i], data)[i])
            got = np.asarray(new.curv[i])
            assert np.max(np.abs(got - want)) <= 1e-5 * np.max(np.abs(want))
        state = new
    # clients 2 and 5 are never sampled: their rows are init's, bit for bit
    for i in (2, 5):
        np.testing.assert_array_equal(np.asarray(state.curv[i]),
                                      np.asarray(init.curv[i]))


# ---------------------------------------------------------------------------
# the carry: gradients and GLM weights at x, made once per iterate
# ---------------------------------------------------------------------------


def _carry_errors(obj, data, hp, mode, mesh=None):
    """Worst relative error, over rounds 0..3, of the carried grad_x /
    curv_x against the oracles at the state's x, and of the cache against
    local_curvature at the anchor it was refreshed at (x of the round
    before, hessian_period = 1)."""
    solver = engine.get_solver("fednew", **hp)
    states = [solver.init(obj, data, KEY)]
    for rounds in (1, 2, 3):
        st, _ = engine.run(solver, obj, data, rounds, key=KEY, mode=mode,
                           mesh=mesh, block_size=2)
        states.append(st)

    def rel(got, want):
        got, want = np.asarray(got), np.asarray(want)
        assert got.shape == want.shape
        return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))

    errs = {"grad_x": 0.0, "curv_x": 0.0, "curv": 0.0}
    for prev, st in zip([states[0]] + states, states):
        errs["grad_x"] = max(errs["grad_x"],
                             rel(st.grad_x, obj.local_grad(st.x, data)))
        errs["curv_x"] = max(errs["curv_x"],
                             rel(st.curv_x, obj.local_curvature(st.x, data)))
        errs["curv"] = max(errs["curv"],
                           rel(st.curv, obj.local_curvature(prev.x, data)))
    return errs


@pytest.mark.parametrize("mode", ["scan", "host"])
def test_matfree_carry_matches_oracles(logreg_267, mode):
    """GLM matfree: after every round the state's grad_x and curv_x are the
    oracles at its x, and curv holds local_curvature at the refreshed
    anchors."""
    obj, data = logreg_267
    errs = _carry_errors(obj, data, dict(MATFREE_HP, cg_iters=8), mode)
    assert max(errs.values()) <= 1e-5, errs


SHARDED_CARRY = """
import json
import jax
import test_matfree as tm
from repro.core.objectives import logistic_regression
from repro.data import synthetic
from repro.launch.mesh import make_client_mesh

spec = synthetic.DatasetSpec("custom", n_clients=8, samples_per_client=64,
                             dim=tm.D, sparse=True)
data = synthetic.make_dataset(spec, tm.KEY)
obj = logistic_regression(1e-3)
mesh = make_client_mesh(8)
hp = dict(tm.MATFREE_HP, cg_iters=8)
errs = tm._carry_errors(obj, data, hp, "scan", mesh=mesh)
dense = tm.engine.get_solver("fednew", **tm.DENSE_HP)
st, _ = tm.engine.run(dense, obj, data, 3, key=tm.KEY, mesh=mesh, block_size=2)
g = obj.local_grad(st.x, data)
errs["dense_grad_x"] = float(abs(st.grad_x - g).max() / abs(g).max())
print(json.dumps({"devices": len(jax.devices()), **errs}))
"""


def test_matfree_carry_under_8_device_shard_map():
    """The carry under the client-mesh driver on eight virtual devices (a
    process of its own: the device count is fixed when JAX starts): each
    device's rows of grad_x / curv_x / curv are its own clients' oracles,
    for the GLM matfree and the dense path."""
    tests = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(tests), "src"), tests]),
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    p = subprocess.run([sys.executable, "-c", SHARDED_CARRY], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out.pop("devices") == 8
    assert max(out.values()) <= 1e-5, out


def _feature_consumers(hlo_text: str) -> list:
    """Instructions of the ENTRY computation that take the features
    parameter as an operand, leaving out the tuple that hands it to a
    loop: the reads of the features outside every loop body."""
    entry = hlo_text[hlo_text.index("\nENTRY") + 1:]
    lines = entry.splitlines()[1:]
    param = next(
        l.split("=")[0].strip().lstrip("%") for l in lines
        if 'op_name="d.features"' in l and " parameter(" in l
    )
    ref = re.compile(r"%" + re.escape(param) + r"\b")
    out = []
    for l in lines:
        if "=" not in l:
            continue
        name, rhs = l.split("=", 1)
        opcode = re.search(r"\b([a-z][\w\-]*)\(", rhs).group(1)
        if ref.search(rhs) and opcode not in ("tuple", "parameter"):
            out.append(name.strip().lstrip("%"))
    return out


def test_matfree_round_reads_features_twice_outside_cg():
    """The compiled matfree round on the reference backend reads the f32
    features outside the CG while loop exactly twice: A x^{k+1} (the loss,
    the gradient and the GLM weights share it) and A^T r^{k+1}. The
    gradient and weights at x^k come from the state, not from two more
    sweeps at a point the last round already evaluated."""
    spec = synthetic.DatasetSpec(
        "custom", n_clients=4, samples_per_client=37, dim=300, sparse=True
    )
    obj, data = logistic_regression(1e-3), synthetic.make_dataset(spec, KEY)
    cfg = fednew.FedNewConfig(rho=1.0, alpha=1.0, hessian_repr="matfree",
                              cg_iters=4, solve_backend="reference")
    state = fednew.init(obj, data, cfg, KEY)
    step = jax.jit(lambda s, d: fednew.step(s, obj, d, cfg))
    text = step.lower(state, data).compile().as_text()
    assert " while(" in text
    assert len(_feature_consumers(text)) == 2, _feature_consumers(text)


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
    # O(n d): gradients and GLM weights, no factors
    assert state.grad_x.shape == (4, 100_000)
    assert state.curv.shape == state.curv_x.shape == data.features.shape[:2]
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
