"""Federated execution engine tests (repro.core.engine).

The contract under test: the scan-compiled driver and the shard_map-sharded
driver are *schedules*, not algorithms — on the paper_logreg workload they
must reproduce the legacy host-loop metrics to float32 tolerance for FedNew
and Q-FedNew, and the solver registry must serve every method behind the one
FederatedSolver protocol."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_logreg import CONFIG as LOGREG_CONFIG
from repro.core import baselines, engine, fednew
from repro.core.objectives import logistic_regression
from repro.data.synthetic import PAPER_DATASETS, make_dataset
from repro.launch.mesh import make_client_mesh

KEY = jax.random.PRNGKey(7)
ROUNDS = 10
RHO, ALPHA = LOGREG_CONFIG.fed.rho, LOGREG_CONFIG.fed.alpha


@pytest.fixture(scope="module")
def problem():
    # w8a geometry = the paper_logreg config's d_model=267 workload
    data = make_dataset(PAPER_DATASETS["w8a"], jax.random.PRNGKey(0))
    return logistic_regression(mu=1e-3), data


def _assert_metrics_close(a, b, rtol=1e-4, atol=1e-6):
    for name, va, vb in zip(a._fields, a, b):
        np.testing.assert_allclose(
            np.asarray(va, dtype=np.float64), np.asarray(vb, dtype=np.float64),
            rtol=rtol, atol=atol, err_msg=f"metric {name}",
        )


@pytest.mark.parametrize("bits", [None, 3], ids=["fednew", "q-fednew"])
def test_scan_driver_matches_legacy_host_loop(problem, bits):
    """Acceptance: scan-compiled rounds == legacy run() to f32 tolerance."""
    obj, data = problem
    cfg = fednew.FedNewConfig(rho=RHO, alpha=ALPHA, hessian_period=1, bits=bits)
    _, m_host = fednew.run(obj, data, cfg, ROUNDS, key=KEY)  # legacy wrapper
    _, m_scan = engine.run(
        fednew.solver(cfg), obj, data, ROUNDS, key=KEY, block_size=4
    )  # 4-round blocks + a 2-round tail block
    _assert_metrics_close(m_host, m_scan)


@pytest.mark.parametrize("bits", [None, 3], ids=["fednew", "q-fednew"])
def test_shard_map_driver_smoke(problem, bits):
    """1-device client mesh: the shard_map manual region (size-1 client
    axis) must reproduce the host-loop trajectory."""
    obj, data = problem
    cfg = fednew.FedNewConfig(rho=RHO, alpha=ALPHA, hessian_period=1, bits=bits)
    _, m_host = fednew.run(obj, data, cfg, ROUNDS, key=KEY)
    mesh = make_client_mesh(1)
    assert mesh.axis_names == ("clients",)
    _, m_shard = engine.run(
        fednew.solver(cfg), obj, data, ROUNDS, key=KEY, mesh=mesh, block_size=5
    )
    _assert_metrics_close(m_host, m_shard)
    # the dual-sum invariant survives the sharded schedule
    assert float(m_shard.dual_sum_residual[-1]) < 1e-3


def test_engine_runs_baselines_behind_one_protocol(problem):
    obj, data = problem
    for name, kw in [("fedgd", {"lr": 2.0}), ("newton-zero", {}), ("newton", {})]:
        sol = engine.get_solver(name, **kw)
        _, m_legacy = baselines.run_simple(
            getattr(baselines, name.replace("-", "_") + "_init"),
            getattr(baselines, name.replace("-", "_") + "_step"),
            obj, data,
            {"fedgd": baselines.FedGDConfig(lr=2.0),
             "newton-zero": baselines.NewtonZeroConfig(),
             "newton": None}[name],
            rounds=4,
        )
        _, m_scan = engine.run(sol, obj, data, 4)
        _assert_metrics_close(m_legacy, m_scan)


def test_registry_rejects_unknown_and_unparameterized():
    with pytest.raises(KeyError):
        engine.get_solver("sgd")
    with pytest.raises(ValueError):
        engine.get_solver("q-fednew")  # bits is mandatory


def test_registry_errors_name_solver_and_keys():
    """Unknown hparams fail with the solver, the bad key, and the valid keys
    in the message (not an opaque dataclass TypeError); the unknown-solver
    KeyError enumerates the registry."""
    with pytest.raises(TypeError, match=r"fednew.*rhoo.*valid hparams.*rho"):
        engine.get_solver("fednew", rhoo=0.1)
    with pytest.raises(TypeError, match=r"fedgd.*momentum.*lr"):
        engine.get_solver("fedgd", momentum=0.9)
    with pytest.raises(TypeError, match="newton"):
        engine.get_solver("newton", lr=1.0)  # config-less solver: no hparams
    with pytest.raises(KeyError) as ei:
        engine.get_solver("sgd")
    for name in engine.solver_names():
        assert name in str(ei.value)
    assert engine.solver_hparam_names("fedgd") == ("lr",)
    assert engine.solver_hparam_names("newton") == ()


def test_block_plan_covers_rounds_exactly():
    assert engine._block_plan(10, 4) == [4, 4, 2]
    assert engine._block_plan(8, 4) == [4, 4]
    assert engine._block_plan(3, None) == [3]
    assert sum(engine._block_plan(1000, 64)) == 1000


def test_block_plan_edge_cases():
    # block_size > rounds clamps to one full block
    assert engine._block_plan(3, 64) == [3]
    # block_size=1: one block per round
    assert engine._block_plan(4, 1) == [1, 1, 1, 1]
    # rounds=1 under any block size
    assert engine._block_plan(1, None) == [1]
    assert engine._block_plan(1, 64) == [1]
    # degenerate block sizes are clamped, never zero/negative blocks
    assert engine._block_plan(5, 0) == [5]


@pytest.mark.parametrize("mode", ["scan", "host"])
def test_dataset_is_an_argument_not_a_constant(problem, mode):
    """The compiled rounds take the dataset as an argument: a closed-over
    dataset is embedded in the program as a constant, which at a few GB of
    client data exhausts host memory while compiling."""
    import warnings

    obj, data = problem
    prev = jax.config.jax_captured_constants_warn_bytes
    jax.config.update("jax_captured_constants_warn_bytes", 1 << 20)
    try:
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "error", message=".*constants were captured.*"
            )
            engine.run(engine.get_solver("fednew", rho=RHO, alpha=ALPHA),
                       obj, data, 2, key=KEY, mode=mode)
    finally:
        jax.config.update("jax_captured_constants_warn_bytes", prev)


@pytest.mark.parametrize("rounds,block", [(1, None), (4, 1), (3, 64)],
                         ids=["rounds=1", "block=1", "block>rounds"])
def test_run_edge_blocks_match_host(problem, rounds, block):
    """Scan scheduling edge cases (single round, per-round blocks, oversized
    block) reproduce the host loop on a cheap baseline."""
    obj, data = problem
    sol = engine.get_solver("fedgd", lr=2.0)
    _, m_host = engine.run(sol, obj, data, rounds, key=KEY, mode="host")
    _, m_scan = engine.run(sol, obj, data, rounds, key=KEY, block_size=block)
    assert m_scan.loss.shape == (rounds,)
    _assert_metrics_close(m_host, m_scan)


def test_run_rejects_bad_rounds_and_mode(problem):
    obj, data = problem
    sol = engine.get_solver("fedgd", lr=2.0)
    with pytest.raises(ValueError, match="rounds"):
        engine.run(sol, obj, data, 0)
    with pytest.raises(ValueError, match="mode"):
        engine.run(sol, obj, data, 1, mode="vmap")


def test_sharded_driver_rejects_uneven_client_split(problem):
    obj, data = problem  # w8a: 60 clients
    bad = jax.tree.map(lambda x: x[:59], data)  # 59 clients, 7-way axis
    with pytest.raises(ValueError, match="divide"):
        engine._run_sharded(
            fednew.solver(fednew.FedNewConfig()), obj, bad, 1,
            _FakeMesh(7), key=KEY, x0=None, block_size=None,
            axis_name=None, donate=True,
        )


class _FakeMesh:
    axis_names = ("clients",)

    def __init__(self, n):
        import numpy as _np

        self.devices = _np.empty((n,), dtype=object)


def test_quantized_sharded_keys_match_vmap(problem):
    """Q-FedNew under sharding derives the SAME per-client PRNG keys as the
    single-device run (full split + shard slice), so levels match exactly in
    round 1 before float drift can accumulate."""
    obj, data = problem
    cfg = fednew.FedNewConfig(rho=RHO, alpha=ALPHA, bits=2)
    _, m_host = fednew.run(obj, data, cfg, 1, key=KEY)
    _, m_shard = engine.run(
        fednew.solver(cfg), obj, data, 1, key=KEY, mesh=make_client_mesh(1)
    )
    _assert_metrics_close(m_host, m_shard, rtol=1e-6, atol=1e-7)
