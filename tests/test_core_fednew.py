"""Behaviour tests for the paper-faithful FedNew core (Algorithm 1)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import conformance as conf
from repro.core import admm, baselines, engine, fednew, objectives, participation
from repro.core.objectives import logistic_regression, quadratic, quadratic_optimum
from repro.data.synthetic import PAPER_DATASETS, make_dataset, make_quadratic_dataset

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def logreg_problem():
    data = make_dataset(PAPER_DATASETS["phishing"], KEY)
    return logistic_regression(mu=1e-3), data


@pytest.fixture(scope="module")
def quad_problem():
    data = make_quadratic_dataset(KEY, n_clients=8, dim=24, cond=20.0)
    return quadratic(), data


def test_fednew_converges_on_quadratic(quad_problem):
    """On a quadratic, x^k must approach the closed-form optimum."""
    obj, data = quad_problem
    cfg = fednew.FedNewConfig(rho=2.0, alpha=0.5, hessian_period=1)
    state, hist = fednew.run(obj, data, cfg, rounds=120)
    x_star = quadratic_optimum(data)
    assert jnp.linalg.norm(state.x - x_star) / jnp.linalg.norm(x_star) < 1e-3
    # Loss must decrease toward the optimal value.
    f_star = obj.global_loss(x_star, data)
    assert hist.loss[-1] - f_star < 0.05 * (hist.loss[0] - f_star)


def test_fednew_converges_on_logreg(logreg_problem):
    obj, data = logreg_problem
    cfg = fednew.FedNewConfig(rho=0.1, alpha=0.05, hessian_period=1)
    state, hist = fednew.run(obj, data, cfg, rounds=60)
    _, f_star = baselines.reference_optimum(obj, data)
    gap = hist.loss - f_star
    assert gap[-1] < 1e-4
    assert hist.grad_norm[-1] < 1e-3


def test_dual_sum_invariant(logreg_problem):
    """sum_i lam_i^k = 0 for all k — the identity behind eq. 13."""
    obj, data = logreg_problem
    cfg = fednew.FedNewConfig(rho=1.0, alpha=0.5)
    _, hist = fednew.run(obj, data, cfg, rounds=20)
    assert jnp.all(hist.dual_sum_residual < 1e-3)


def test_hessian_period_zero_never_refactorizes(logreg_problem):
    """r=0: the factor must stay the x^0 factor (Newton-Zero-like compute)."""
    obj, data = logreg_problem
    cfg = fednew.FedNewConfig(rho=0.1, alpha=0.05, hessian_period=0)
    state = fednew.init(obj, data, cfg, KEY)
    curv0 = state.curv
    for _ in range(3):
        state, _ = fednew.step(state, obj, data, cfg)
    assert jnp.array_equal(state.curv, curv0)
    # and it still converges (paper: r=0 tracks Newton-Zero)
    state2, hist = fednew.run(obj, data, cfg, rounds=80)
    assert hist.grad_norm[-1] < 1e-2


def test_refresh_rate_ordering(logreg_problem):
    """Paper Fig. 1: r=1 converges in fewer rounds than r=0."""
    obj, data = logreg_problem
    rounds = 40
    _, h1 = fednew.run(obj, data, fednew.FedNewConfig(rho=0.1, alpha=0.05, hessian_period=1), rounds)
    _, h0 = fednew.run(obj, data, fednew.FedNewConfig(rho=0.1, alpha=0.05, hessian_period=0), rounds)
    _, f_star = baselines.reference_optimum(obj, data)
    assert h1.loss[-1] - f_star <= h0.loss[-1] - f_star + 1e-7


def test_communication_is_O_d(logreg_problem):
    """FedNew uplink is exactly 32 d bits every round, including the first."""
    obj, data = logreg_problem
    cfg = fednew.FedNewConfig()
    _, hist = fednew.run(obj, data, cfg, rounds=5)
    assert jnp.all(hist.uplink_bits_per_client == 32 * data.dim)


def test_qfednew_bits_and_convergence(logreg_problem):
    obj, data = logreg_problem
    cfg = fednew.FedNewConfig(rho=0.1, alpha=0.05, bits=3)
    _, hist = fednew.run(obj, data, cfg, rounds=80)
    assert jnp.all(hist.uplink_bits_per_client == 3 * data.dim + 32)
    _, f_star = baselines.reference_optimum(obj, data)
    assert hist.loss[-1] - f_star < 1e-3


def test_newton_zero_first_round_bits(logreg_problem):
    obj, data = logreg_problem
    _, hist = baselines.run_simple(
        baselines.newton_zero_init, baselines.newton_zero_step, obj, data,
        baselines.NewtonZeroConfig(), rounds=3,
    )
    d = data.dim
    assert int(hist.uplink_bits_per_client[0]) == 32 * d * d + 32 * d
    assert int(hist.uplink_bits_per_client[1]) == 32 * d


def test_fedgd_slower_than_fednew(logreg_problem):
    """Paper Fig. 1 ordering: FedGD needs far more rounds."""
    obj, data = logreg_problem
    rounds = 40
    _, hgd = baselines.run_simple(
        baselines.fedgd_init, baselines.fedgd_step, obj, data,
        baselines.FedGDConfig(lr=2.0), rounds,
    )
    _, hfn = fednew.run(obj, data, fednew.FedNewConfig(rho=0.1, alpha=0.05), rounds)
    _, f_star = baselines.reference_optimum(obj, data)
    assert hfn.loss[-1] - f_star < hgd.loss[-1] - f_star


def test_admm_helpers_pytree():
    """admm helpers must be pytree-generic (used by FedNew-HF on params)."""
    lam = {"w": jnp.ones((4, 3)), "b": jnp.zeros((4, 2))}
    y_i = {"w": jnp.arange(12.0).reshape(4, 3), "b": jnp.ones((4, 2))}
    y = admm.tree_mean_clients(y_i)
    lam2 = admm.dual_update(lam, y_i, jax.tree.map(lambda g, yi: jnp.broadcast_to(g, yi.shape), y, y_i), rho=1.0)
    assert admm.dual_sum_residual(jax.tree.map(lambda a, b: a - b, lam2, lam)) < 1e-5


# ---------------------------------------------------------------------------
# the state's carry: every client's gradient at x, made by the round that
# produced x (its evaluation) and read by the next round's eq. 9 rhs
# ---------------------------------------------------------------------------


def _tree_problem():
    """A param-tree job: logistic loss over dict params, autodiff oracles."""
    n, m, d = 4, 12, 6
    k1, k2 = jax.random.split(jax.random.PRNGKey(5))

    def loss_fn(p, b):
        z = b["A"] @ p["w"] + p["b"][0]
        reg = 0.5e-3 * (jnp.sum(p["w"] ** 2) + jnp.sum(p["b"] ** 2))
        return jnp.mean(jnp.logaddexp(0.0, -b["y"] * z)) + reg

    batch = {"A": jax.random.normal(k1, (n, m, d)),
             "y": jnp.sign(jax.random.normal(k2, (n, m)))}
    x0 = {"w": jnp.zeros((d,)), "b": jnp.zeros((2,))}
    hp = {"rho": 0.5, "alpha": 0.1, "hessian_repr": "matfree", "cg_iters": 16}
    return (objectives.from_loss_fn(loss_fn),
            objectives.TokenDataset(batch=batch), x0, hp)


def _flat_problem():
    obj, data = conf.problem()
    return obj, data, None, conf.FEDNEW_HP


@pytest.mark.parametrize("mode", ["scan", "host"])
@pytest.mark.parametrize("layout", ["flat", "tree"])
def test_carried_gradient_is_local_grad_at_x(layout, mode):
    """After every round (and at init) ``grad_x`` is every client's
    gradient at the state's own x: the next round's eq. 9 rhs reads the
    gradient at the point it solves at, not at a stale one."""
    obj, data, x0, hp = (_flat_problem if layout == "flat" else _tree_problem)()
    solver = engine.get_solver("fednew", **hp)
    states = [solver.init(obj, data, KEY, x0)]
    for rounds in (1, 2, 3):
        st, _ = engine.run(solver, obj, data, rounds, key=KEY, x0=x0,
                           mode=mode, block_size=2)
        states.append(st)
    for st in states:
        want = obj.local_grad(st.x, data)
        assert (jax.tree.structure(st.grad_x) == jax.tree.structure(want))
        for got, ref in zip(jax.tree.leaves(st.grad_x), jax.tree.leaves(want)):
            assert got.shape == ref.shape and got.shape[0] == data.n_clients
            np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)
        # no GLM split on either layout here: the weights' slot is empty
        assert st.curv_x.shape == (data.n_clients, 0)
    assert not np.allclose(np.asarray(jax.tree.leaves(states[-1].grad_x)[0]),
                           np.asarray(jax.tree.leaves(states[0].grad_x)[0]))


@pytest.mark.parametrize("repr_", ["dense", "matfree"])
def test_async_state_carries_gradient_at_x(repr_):
    """The async buffer state carries the gradients (and matfree GLM
    weights) at its x as FedNewState does, so it runs the same client half:
    after every round, flushed or held, they are the oracles at the state's
    own x. About half the clients submit a round into a buffer of n / 2,
    so some rounds flush and move x and others hold it."""
    obj, data = conf.problem()
    hp = dict(conf.FEDNEW_HP, hessian_repr=repr_, cg_iters=16)
    solver = engine.get_solver("fednew-async",
                               buffer_size=data.n_clients // 2, **hp)
    part = participation.Participation(fraction=0.5, seed=2)
    for rounds in (0, 1, 2, 3, 4, 5):
        if rounds == 0:
            st = solver.init(obj, data, KEY)
        else:
            st, m = engine.run(solver, obj, data, rounds, key=KEY,
                               block_size=2, participation=part)
        np.testing.assert_allclose(st.grad_x, obj.local_grad(st.x, data),
                                   rtol=1e-5, atol=1e-6)
        if repr_ == "matfree":
            np.testing.assert_allclose(
                st.curv_x, obj.local_curvature(st.x, data),
                rtol=1e-5, atol=1e-6)
        else:
            assert st.curv_x.shape == (data.n_clients, 0)
    assert set(np.asarray(m.flushed).tolist()) == {0.0, 1.0}, m.flushed
