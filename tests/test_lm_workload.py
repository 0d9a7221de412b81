"""The LM fine-tuning workload: pytree-native oracles, model specs, and
per-leaf ledgers.

Four contracts pinned here:

  * ``objectives.from_loss_fn`` / ``logistic_regression_autodiff`` derive
    oracles that agree with the closed forms to machine precision (grad,
    Hessian, jvp-over-grad HVP) — across dtypes and under both the scan and
    shard_map trajectories (satellite: autodiff-vs-closed-form agreement);
  * a ``kind='model'`` spec runs matrix-free FedNew and FAGH end-to-end over
    a registry arch's param pytree through ``repro.api.run`` with decreasing
    loss;
  * the RunResult's exact Python-int ledgers equal the traced in-step
    metric AND the hand-computed per-leaf payload sums — for identity and
    quantizing codecs;
  * capability mismatches raise errors that name the spec field (and
    registry arch) to change.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api, comm
from repro.core import engine, objectives
from repro.core.quantization import word_bits
from repro.data import synthetic
from repro.launch.mesh import make_client_mesh

KEY = jax.random.PRNGKey(0)
D = 40


@pytest.fixture(scope="module")
def logreg_pair():
    spec = synthetic.DatasetSpec(
        "custom", n_clients=4, samples_per_client=32, dim=D, sparse=False
    )
    data = synthetic.make_dataset(spec, KEY)
    return (
        objectives.logistic_regression(1e-3),
        objectives.logistic_regression_autodiff(1e-3),
        data,
    )


def tiny_model_spec(solver="fednew", hparams=None, **over):
    base = {
        "objective": {"kind": "model", "arch": "gemma3-4b",
                      "seq_len": 8, "layers": 1, "d_model": 16},
        "partition": {"dataset": "tokens", "n_clients": 2,
                      "samples_per_client": 2, "seed": 0},
        "solver": {"name": solver, "hparams": hparams if hparams is not None
                   else {"hessian_repr": "matfree", "cg_iters": 2,
                         "alpha": 8.0, "rho": 1.0}},
        "schedule": {"rounds": 2, "mode": "host"},
        "seed": 1,
    }
    base.update(over)
    return api.ExperimentSpec.from_dict(base)


# ---------------------------------------------------------------------------
# satellite: autodiff oracles vs closed forms
# ---------------------------------------------------------------------------


def _point(data, dtype):
    x = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (D,), dtype)
    n = data.n_clients
    anchors = jnp.broadcast_to(x, (n, D)) + 0.01 * jax.random.normal(
        jax.random.PRNGKey(2), (n, D), dtype
    )
    v = jax.random.normal(jax.random.PRNGKey(3), (n, D), dtype)
    return x, anchors, v


def _agreement(closed, auto, data, tol):
    x, anchors, v = _point(data, data.features.dtype)
    np.testing.assert_allclose(
        auto.local_loss(x, data), closed.local_loss(x, data), rtol=tol
    )
    np.testing.assert_allclose(
        auto.local_grad(x, data), closed.local_grad(x, data),
        rtol=tol, atol=tol,
    )
    np.testing.assert_allclose(
        auto.local_hessian(x, data), closed.local_hessian(x, data),
        rtol=tol, atol=tol,
    )
    # per-client anchors: the Hessian-refresh staleness contract
    np.testing.assert_allclose(
        auto.local_hvp(anchors, data, v), closed.local_hvp(anchors, data, v),
        rtol=tol, atol=tol,
    )


def test_autodiff_matches_closed_form_f32(logreg_pair):
    closed, auto, data = logreg_pair
    # machine precision at f32: both derivations contract the same A/b
    _agreement(closed, auto, data, 1e-5)


def test_autodiff_matches_closed_form_f64():
    with jax.enable_x64(True):
        spec = synthetic.DatasetSpec(
            "custom", n_clients=4, samples_per_client=32, dim=D, sparse=False
        )
        data = synthetic.make_dataset(spec, KEY, dtype=jnp.float64)
        assert data.features.dtype == jnp.float64
        _agreement(
            objectives.logistic_regression(1e-3),
            objectives.logistic_regression_autodiff(1e-3),
            data,
            1e-12,
        )


@pytest.mark.parametrize("mesh_devices", [None, 1], ids=["scan", "shard_map"])
def test_autodiff_matches_closed_form_trajectory(logreg_pair, mesh_devices):
    """Matrix-free FedNew driven by the autodiff oracles reproduces the
    closed-form trajectory under both schedules."""
    closed, auto, data = logreg_pair
    mesh = make_client_mesh(mesh_devices) if mesh_devices else None

    def traj(obj):
        _, m = api.run_components(
            "fednew", obj, data, 5,
            key=jax.random.PRNGKey(0), mesh=mesh, mode="scan",
            hessian_repr="matfree", cg_iters=8, rho=0.1, alpha=0.1,
        )
        return np.asarray(m.loss)

    np.testing.assert_allclose(traj(auto), traj(closed), rtol=1e-5)


def test_from_loss_fn_hvp_on_pytree_params():
    """jvp-over-grad on a dict pytree equals the analytic HVP of a toy
    quadratic-in-params loss (per-client batches, per-client anchors)."""
    n = 3

    def loss_fn(p, b):
        r = b["A"] @ p["w"] - b["y"]
        return 0.5 * jnp.sum(r * r) + 0.5 * jnp.sum(p["b"] ** 2)

    obj = objectives.from_loss_fn(loss_fn)
    k1, k2, k3, k4 = jax.random.split(KEY, 4)
    batch = {"A": jax.random.normal(k1, (n, 5, 4)),
             "y": jax.random.normal(k2, (n, 5))}
    data = objectives.TokenDataset(batch=batch)
    assert data.n_clients == n
    anchors = {"w": jax.random.normal(k3, (n, 4)),
               "b": jnp.zeros((n, 2))}
    v = {"w": jax.random.normal(k4, (n, 4)), "b": jnp.ones((n, 2))}
    out = obj.local_hvp(anchors, data, v)
    # analytic: H_w = A^T A (anchor-independent), H_b = I
    want_w = jnp.einsum("nij,nj->ni", jnp.einsum(
        "nki,nkj->nij", batch["A"], batch["A"]), v["w"])
    np.testing.assert_allclose(out["w"], want_w, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(out["b"], v["b"], rtol=1e-6)
    assert not obj.has_hessian
    with pytest.raises(ValueError, match="no local_hessian oracle"):
        obj.global_hessian(anchors, data)


# ---------------------------------------------------------------------------
# satellite: the Gauss-Newton curvature option
# ---------------------------------------------------------------------------


def _tree_dot(a, b):
    return sum(
        jnp.sum(x * y, axis=tuple(range(1, x.ndim)))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


def test_gauss_newton_equals_exact_hessian_for_glm():
    """Ground truth for the GN derivation: with a LINEAR backbone cut
    (z = A w) and a convex head, J^T H_pred J is the exact Hessian — the
    GN and Pearlmutter oracles must agree to machine precision."""
    loss_fn = lambda p, b: jnp.mean(
        jnp.logaddexp(0.0, -b["y"] * (b["A"] @ p["w"]))
    )
    exact = objectives.from_loss_fn(loss_fn)
    gn = objectives.from_loss_fn(
        loss_fn,
        hvp="gauss_newton",
        predict_fn=lambda p, b: b["A"] @ p["w"],
        pred_loss_fn=lambda p, z, b: jnp.mean(
            jnp.logaddexp(0.0, -b["y"] * z)
        ),
    )
    n = 3
    k1, k2, k3, k4 = jax.random.split(KEY, 4)
    batch = {"A": jax.random.normal(k1, (n, 16, 6)),
             "y": jnp.sign(jax.random.normal(k2, (n, 16)))}
    data = objectives.TokenDataset(batch=batch)
    anchors = {"w": 0.3 * jax.random.normal(k3, (n, 6))}
    v = {"w": jax.random.normal(k4, (n, 6))}
    np.testing.assert_allclose(
        gn.local_hvp(anchors, data, v)["w"],
        exact.local_hvp(anchors, data, v)["w"],
        rtol=1e-5, atol=1e-6,
    )


def test_gauss_newton_model_hvp_is_psd():
    """The satellite's acceptance pin: the GN oracle on a real registry
    backbone (nonlinear, where the exact Hessian is indefinite) stays PSD —
    v^T (GN) v >= 0 for random probes — and symmetric."""
    from repro.models import lm

    ospec = api.ObjectiveSpec(kind="model", arch="gemma3-4b", seq_len=8,
                              layers=1, d_model=16, hvp="gauss_newton")
    obj = api.build_objective(ospec)
    pspec = api.PartitionSpec(dataset="tokens", n_clients=2,
                              samples_per_client=2, seed=0)
    data = api.build_dataset(ospec, pspec)
    cfg = api.build_model_config(ospec)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    n = data.n_clients
    anchors = jax.tree.map(
        lambda l: jnp.broadcast_to(l[None], (n,) + l.shape), params
    )
    leaves, treedef = jax.tree.flatten(anchors)
    for probe in range(3):
        ks = jax.random.split(jax.random.fold_in(KEY, probe), len(leaves))
        v = jax.tree.unflatten(treedef, [
            jax.random.normal(k, l.shape, l.dtype)
            for k, l in zip(ks, leaves)
        ])
        hv = obj.local_hvp(anchors, data, v)
        q = np.asarray(_tree_dot(v, hv))
        assert np.all(q >= -1e-6 * np.abs(q).max()), f"probe {probe}: {q}"
    # symmetry: u^T H v == v^T H u
    ks = jax.random.split(jax.random.fold_in(KEY, 99), len(leaves))
    u = jax.tree.unflatten(treedef, [
        jax.random.normal(k, l.shape, l.dtype) for k, l in zip(ks, leaves)
    ])
    hu = obj.local_hvp(anchors, data, u)
    np.testing.assert_allclose(
        np.asarray(_tree_dot(u, hv)), np.asarray(_tree_dot(v, hu)),
        rtol=1e-3, atol=1e-4,
    )


def test_gauss_newton_spec_runs_end_to_end():
    """kind='model' + hvp='gauss_newton' through repro.api.run: the GN
    curvature drives matrix-free FedNew with finite, decreasing loss."""
    spec = tiny_model_spec(
        objective={"kind": "model", "arch": "gemma3-4b", "seq_len": 8,
                   "layers": 1, "d_model": 16, "hvp": "gauss_newton"},
    )
    res = api.run(spec)
    losses = res.metrics["loss"]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_from_loss_fn_rejects_bad_hvp_options():
    loss_fn = lambda p, b: jnp.sum(p["w"] ** 2)
    with pytest.raises(ValueError, match="gauss_newton"):
        objectives.from_loss_fn(loss_fn, hvp="fisher")
    with pytest.raises(ValueError, match="predict_fn"):
        objectives.from_loss_fn(loss_fn, hvp="gauss_newton")
    with pytest.raises(ValueError, match="hvp"):
        api.ObjectiveSpec(kind="model", arch="gemma3-4b",
                          hvp="fisher")
    with pytest.raises(ValueError, match="model"):
        api.ObjectiveSpec(kind="logreg", hvp="gauss_newton")


# ---------------------------------------------------------------------------
# model specs end-to-end
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def fednew_model_run():
    return api.run(tiny_model_spec())


def test_model_run_loss_decreases(fednew_model_run):
    losses = fednew_model_run.metrics["loss"]
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


def test_model_run_dim_is_param_count(fednew_model_run):
    spec = api.ExperimentSpec.from_dict(fednew_model_run.spec)
    x0 = api.build_x0(spec)
    n_params = sum(int(l.size) for l in jax.tree.leaves(x0))
    assert fednew_model_run.dim == n_params


def test_model_run_ledger_matches_traced_metric(fednew_model_run):
    res = fednew_model_run
    per_client = [t / res.n_clients for t in res.uplink_bits_total]
    np.testing.assert_array_equal(
        per_client, res.metrics["uplink_bits_per_client"]
    )


def test_model_run_ledger_is_per_leaf_sum(fednew_model_run):
    """Identity codec: uplink = sum over param leaves of size * word_bits,
    per sampled client — computed here by hand, per leaf, in Python ints."""
    res = fednew_model_run
    spec = api.ExperimentSpec.from_dict(res.spec)
    x0 = api.build_x0(spec)
    per_leaf = sum(
        int(l.size) * word_bits(l.dtype) for l in jax.tree.leaves(x0)
    )
    assert res.uplink_bits_total[0] == per_leaf * res.n_clients


def test_model_run_quantized_per_leaf_ledger():
    """stoch_quant applies per leaf: bits*size + one 32-bit range word per
    (client, leaf) — the ledger must count every leaf's range word."""
    spec = tiny_model_spec(
        compression={"codec": "stoch_quant", "params": {"bits": 3}}
    )
    res = api.run(spec)
    x0 = api.build_x0(spec)
    leaves = jax.tree.leaves(x0)
    want = sum(3 * int(l.size) + 32 for l in leaves) * res.n_clients
    assert res.uplink_bits_total[0] == want
    np.testing.assert_array_equal(
        [t / res.n_clients for t in res.uplink_bits_total],
        res.metrics["uplink_bits_per_client"],
    )
    assert all(np.isfinite(res.metrics["loss"]))


def test_model_run_fagh():
    res = api.run(tiny_model_spec("fagh", {"lr": 0.5, "damping": 1.0}))
    losses = res.metrics["loss"]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    # fagh wire: y^k down + grad up, u down + HVP up => 2d words each way
    assert res.uplink_bits_total[0] == 2 * res.dim * 32 * res.n_clients
    assert res.downlink_bits_total[0] == res.uplink_bits_total[0]


def test_model_spec_json_round_trip():
    spec = tiny_model_spec()
    again = api.ExperimentSpec.from_dict(json.loads(json.dumps(spec.to_dict())))
    assert again == spec


# ---------------------------------------------------------------------------
# capability errors name the spec field + arch
# ---------------------------------------------------------------------------


def test_model_dense_fednew_names_field_and_arch():
    spec = tiny_model_spec(
        solver="fednew", hparams={"rho": 0.1, "alpha": 0.1}
    )
    with pytest.raises(ValueError, match=r"gemma3-4b.*hessian_repr"):
        api.run(spec)


def test_model_unsupported_solver_names_solver():
    spec = tiny_model_spec(solver="fednl", hparams={})
    with pytest.raises(ValueError, match=r"solver\.name='fednl'.*pytree"):
        api.run(spec)


def test_model_rejects_shard_map_schedule():
    with pytest.raises(ValueError, match="mesh_devices"):
        tiny_model_spec(
            schedule={"rounds": 2, "mode": "host", "mesh_devices": 1}
        )


def test_model_rejects_f_star():
    with pytest.raises(ValueError, match="f_star"):
        tiny_model_spec(telemetry={"f_star_newton_iters": 5})


def test_model_requires_tokens_partition():
    with pytest.raises(ValueError, match="tokens"):
        tiny_model_spec(
            partition={"dataset": "custom", "n_clients": 2,
                       "samples_per_client": 2, "dim": 10}
        )


def test_tokens_partition_requires_model_objective():
    with pytest.raises(ValueError, match="tokens"):
        tiny_model_spec(objective={"kind": "logreg"})


def test_model_spec_requires_known_arch():
    with pytest.raises(ValueError, match="arch"):
        tiny_model_spec(
            objective={"kind": "model", "arch": "not-an-arch", "seq_len": 8}
        )


# ---------------------------------------------------------------------------
# per-leaf comm helpers
# ---------------------------------------------------------------------------


def test_tree_payload_bits_per_leaf():
    codec = comm.build_codec({"name": "stoch_quant", "bits": 4})
    tree = {"a": jnp.zeros((3, 2)), "b": jnp.zeros((5,))}
    want = (4 * 6 + 32) + (4 * 5 + 32)
    assert comm.tree_payload_bits(codec, tree) == want
    traced = comm.tree_payload_bits_metric(codec, tree, jnp.zeros((), jnp.int32))
    assert int(traced) == want
