"""Kernel dispatch layer (repro.kernels.dispatch): backend resolution,
cross-path PRNG/bit-exactness contracts, and the engine-level promotion of
the Pallas kernels to the dispatched hot path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine, fednew
from repro.core import quantization as Q
from repro.core.objectives import logistic_regression
from repro.data.synthetic import PAPER_DATASETS, make_dataset
from repro.kernels import dispatch
from repro.kernels.glm_hvp import ops as glm_ops
from repro.kernels.glm_hvp import ref as glm_ref
from repro.launch.mesh import make_client_mesh

KEY = jax.random.PRNGKey(7)


@pytest.fixture(scope="module")
def problem():
    data = make_dataset(PAPER_DATASETS["w8a"], jax.random.PRNGKey(0))
    return logistic_regression(mu=1e-3), data


# ---------------------------------------------------------------------------
# backend resolution
# ---------------------------------------------------------------------------


def test_resolved_backend_on_cpu(monkeypatch):
    """The silent-interpret bug, fixed: on CPU 'auto' never picks the
    interpreter (reference instead), and forcing 'pallas' resolves to the
    interpreter *explicitly* — the resolved name says so."""
    monkeypatch.delenv(dispatch.ENV_BACKEND, raising=False)
    assert dispatch.platform() == "cpu"  # CI runs on CPU
    assert dispatch.resolve_backend("auto") == "reference"
    assert dispatch.resolve_backend("pallas") == "pallas-interpret"
    assert dispatch.resolve_backend("reference") == "reference"
    # on TPU both 'auto' and 'pallas' compile
    assert dispatch.resolve_backend("auto", plat="tpu") == "pallas"
    assert dispatch.resolve_backend("pallas", plat="tpu") == "pallas"
    assert dispatch.interpret_flag("pallas-interpret") is True
    assert dispatch.interpret_flag("pallas") is False
    assert dispatch.default_interpret() is True  # CPU


def test_env_override_resolves_auto(monkeypatch):
    monkeypatch.setenv(dispatch.ENV_BACKEND, "pallas")
    assert dispatch.resolve_backend("auto") == "pallas-interpret"
    monkeypatch.setenv(dispatch.ENV_BACKEND, "reference")
    assert dispatch.resolve_backend("auto") == "reference"
    # explicit (non-auto) backends ignore the env
    assert dispatch.resolve_backend("pallas") == "pallas-interpret"


def test_matmul_operand_dtype_follows_the_default_precision(monkeypatch):
    """A kernel may stream f32 operands as bf16 only where the jnp path it
    replaces would round them so: a TPU at JAX's default precision."""
    assert dispatch.matmul_operand_dtype(jnp.float32) == jnp.float32  # CPU
    monkeypatch.setattr(dispatch, "platform", lambda: "tpu")
    assert dispatch.matmul_operand_dtype(jnp.float32) == jnp.bfloat16
    assert dispatch.matmul_operand_dtype(jnp.bfloat16) == jnp.bfloat16
    with jax.default_matmul_precision("highest"):
        assert dispatch.matmul_operand_dtype(jnp.float32) == jnp.float32


def test_unknown_backend_rejected():
    with pytest.raises(ValueError, match="unknown kernel backend"):
        dispatch.resolve_backend("cuda")
    with pytest.raises(ValueError, match="unknown kernel backend"):
        fednew.FedNewConfig(backend="fastest")


def test_registry_serves_both_hot_loops():
    assert set(dispatch.registered_kernels()) >= {
        "client_solve", "glm_hvp", "stoch_quant", "stoch_quant.quantize"
    }
    impl = dispatch.get_impl("stoch_quant", backend="reference")
    assert impl is Q.quantize_with_keys
    assert dispatch.get_impl("glm_hvp", backend="reference") is glm_ref.glm_hvp_ref
    assert dispatch.get_impl("glm_hvp", backend="pallas") is glm_ops.glm_hvp
    with pytest.raises(KeyError):
        dispatch.get_impl("flash_attention_v9")


def test_registry_degrades_to_reference_on_import_error():
    """An unimportable kernel raises: the reference never stands in for a
    kernel the backend asked for (it would hide the device path), while the
    reference flavor of the same entry still resolves."""
    dispatch.register_kernel(
        "broken_kernel",
        pallas="repro.kernels.nonexistent_module:fn",
        reference="repro.core.quantization:quantize_with_keys",
    )
    try:
        with pytest.raises(ImportError, match="nonexistent_module"):
            dispatch.resolve_impl("broken_kernel", backend="pallas")
        fn, resolved = dispatch.resolve_impl("broken_kernel", backend="reference")
        assert fn is Q.quantize_with_keys
        assert resolved == "reference"
    finally:
        dispatch._REGISTRY.pop("broken_kernel", None)


# ---------------------------------------------------------------------------
# cross-path PRNG / bit-exactness (the satellite-3 contract)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("N", [77, 1000, 1024, 3000])
def test_same_key_same_levels_across_paths(N):
    """Same key => same randomness => identical levels AND identical
    dequantized vector on the reference and kernel paths (float32). The old
    wrapper drew padded float32 uniforms and silently diverged."""
    key = jax.random.PRNGKey(N)
    ky, kp = jax.random.split(key)
    y = jax.random.normal(ky, (N,), jnp.float32)
    prev = jax.random.normal(kp, (N,), jnp.float32) * 0.1
    r = jax.jit(lambda: Q.quantize(key, y, prev, 3))()
    k = dispatch.quantize(key, y, prev, 3, backend="pallas")
    np.testing.assert_array_equal(
        np.asarray(k.levels), np.asarray(r.levels)
    )
    np.testing.assert_array_equal(np.asarray(k.y_hat), np.asarray(r.y_hat))
    assert int(k.payload_bits) == int(r.payload_bits) == 3 * N + 32


def test_batched_same_keys_same_levels():
    keys = jax.random.split(jax.random.PRNGKey(3), 6)
    y = jax.random.normal(jax.random.PRNGKey(1), (6, 999), jnp.float32)
    prev = jax.random.normal(jax.random.PRNGKey(2), (6, 999), jnp.float32) * 0.2
    r = jax.jit(lambda: Q.quantize_with_keys(keys, y, prev, 4))()
    k = dispatch.quantize_with_keys(keys, y, prev, 4, backend="pallas")
    np.testing.assert_array_equal(
        np.asarray(k.levels), np.asarray(r.levels)
    )
    np.testing.assert_array_equal(np.asarray(k.y_hat), np.asarray(r.y_hat))
    np.testing.assert_array_equal(np.asarray(k.delta), np.asarray(r.delta))


def test_reference_backend_is_the_reference():
    key = jax.random.PRNGKey(0)
    y = jax.random.normal(key, (64,), jnp.float32)
    r = Q.quantize(key, y, jnp.zeros_like(y), 3)
    k = dispatch.quantize(key, y, jnp.zeros_like(y), 3, backend="reference")
    np.testing.assert_array_equal(np.asarray(k.y_hat), np.asarray(r.y_hat))


# ---------------------------------------------------------------------------
# engine promotion: Q-FedNew through the dispatched kernels
# ---------------------------------------------------------------------------


def _metrics_bitwise(a, b):
    for name, va, vb in zip(a._fields, a, b):
        np.testing.assert_array_equal(
            np.asarray(va), np.asarray(vb), err_msg=f"metric {name}"
        )


@pytest.mark.parametrize("sharded", [False, True], ids=["scan", "shard_map"])
def test_qfednew_pallas_quant_bit_exact_vs_reference(problem, sharded):
    """Acceptance: Q-FedNew via engine.run with the quantizer on the Pallas
    (interpret) path reproduces the reference path bit for bit under the
    same schedule — sharded and unsharded."""
    obj, data = problem
    mk = lambda b: fednew.FedNewConfig(rho=0.1, alpha=0.05, bits=3, quant_backend=b)
    kw = dict(key=KEY, mesh=make_client_mesh(1)) if sharded else dict(key=KEY)
    _, m_ref = engine.run(fednew.solver(mk("reference")), obj, data, 5, **kw)
    s_pal, m_pal = engine.run(fednew.solver(mk("pallas")), obj, data, 5, **kw)
    _metrics_bitwise(m_ref, m_pal)
    assert jnp.all(m_pal.uplink_bits_per_client == 3 * data.dim + 32)


def test_qfednew_full_pallas_backend_tracks_reference(problem):
    """backend='pallas' routes BOTH hot loops through kernels; the CG solve
    is not bitwise-identical to Cholesky, so the whole trajectory matches to
    solver tolerance while the quantizer stays bit-exact per round."""
    obj, data = problem
    cfg_ref = fednew.FedNewConfig(rho=0.1, alpha=0.05, bits=3, backend="reference")
    cfg_pal = fednew.FedNewConfig(rho=0.1, alpha=0.05, bits=3, backend="pallas")
    _, m_ref = engine.run(fednew.solver(cfg_ref), obj, data, 6, key=KEY)
    _, m_pal = engine.run(fednew.solver(cfg_pal), obj, data, 6, key=KEY)
    np.testing.assert_allclose(
        np.asarray(m_pal.loss), np.asarray(m_ref.loss), rtol=1e-4, atol=1e-5
    )
    np.testing.assert_array_equal(
        np.asarray(m_pal.uplink_bits_per_client),
        np.asarray(m_ref.uplink_bits_per_client),
    )


def test_get_solver_accepts_backend(problem):
    obj, data = problem
    sol = engine.get_solver("q-fednew", bits=2, rho=0.1, alpha=0.05,
                            quant_backend="pallas")
    _, m = engine.run(sol, obj, data, 2, key=KEY)
    assert jnp.all(m.uplink_bits_per_client == 2 * data.dim + 32)


def test_legacy_use_kernel_maps_to_pallas_solve(monkeypatch):
    monkeypatch.delenv(dispatch.ENV_BACKEND, raising=False)
    cfg = fednew.FedNewConfig(use_kernel=True)
    assert cfg.resolved_solve_backend == "pallas"
    assert cfg.solve_uses_kernel  # interpret on CPU, compiled on TPU
    # explicit backend beats the legacy flag
    cfg2 = fednew.FedNewConfig(use_kernel=True, backend="reference")
    assert cfg2.resolved_solve_backend == "reference"
    assert not cfg2.solve_uses_kernel
    # default on CPU: auto -> reference (no silent interpreter)
    assert not fednew.FedNewConfig().solve_uses_kernel


# ---------------------------------------------------------------------------
# fednew_hf leaf-wise kernel route
# ---------------------------------------------------------------------------


def test_fednew_hf_leafwise_kernel_route_bit_exact():
    """The leaf-wise quantize route fednew_hf's step builders call
    (``comm.encode_decode_tree`` with the backend-dispatched stoch_quant
    codec) must be bit-exact across backends."""
    from repro import comm

    key = jax.random.PRNGKey(11)
    tree = {
        "w": jax.random.normal(key, (4, 8, 33), jnp.float32),
        "b": jax.random.normal(jax.random.fold_in(key, 1), (4, 17), jnp.float32),
    }
    prev = jax.tree.map(jnp.zeros_like, tree)

    def route(backend):
        codec = comm.build_codec(
            {"name": "stoch_quant", "bits": 3}, backend=backend
        )
        return comm.encode_decode_tree(codec, key, tree, prev)[0]

    # jit both routes, as the train step does: the bit-exactness contract is
    # between compiled programs (eager op-by-op rounding can differ by ulps
    # from XLA's folded constants on either path)
    ref = jax.jit(lambda: route("reference"))()
    ker = jax.jit(lambda: route("pallas"))()
    for leaf_r, leaf_k in zip(jax.tree.leaves(ref), jax.tree.leaves(ker)):
        np.testing.assert_array_equal(np.asarray(leaf_r), np.asarray(leaf_k))
