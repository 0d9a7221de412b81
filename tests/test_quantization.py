"""Property tests for the Q-FedNew stochastic quantizer (paper eqs. 25-30)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.quantization import quantize, quantize_batch


def _vec(data, n):
    return np.array(data.draw(st.lists(
        st.floats(-100.0, 100.0, allow_nan=False, width=32), min_size=n, max_size=n
    )), dtype=np.float32)


@settings(max_examples=50, deadline=None)
@given(data=st.data(), bits=st.integers(1, 8), n=st.integers(1, 32), seed=st.integers(0, 2**31 - 1))
def test_error_within_one_level(data, bits, n, seed):
    """|y_hat - y| <= Delta elementwise (rounding never skips a level)."""
    y = _vec(data, n)
    prev = _vec(data, n)
    q = quantize(jax.random.PRNGKey(seed), jnp.asarray(y), jnp.asarray(prev), bits)
    delta = float(q.delta)
    assert np.all(np.abs(np.asarray(q.y_hat) - y) <= delta + 1e-4 * (1 + delta))


@settings(max_examples=25, deadline=None)
@given(data=st.data(), bits=st.integers(1, 6), n=st.integers(1, 16))
def test_levels_within_range(data, bits, n):
    y = _vec(data, n)
    prev = _vec(data, n)
    q = quantize(jax.random.PRNGKey(0), jnp.asarray(y), jnp.asarray(prev), bits)
    lv = np.asarray(q.levels)
    assert np.all(lv >= 0) and np.all(lv <= (1 << bits) - 1)


def test_unbiasedness_statistical():
    """E[y_hat] = y (eq. 27): average over many independent keys."""
    key = jax.random.PRNGKey(7)
    y = jax.random.normal(key, (64,))
    prev = jnp.zeros((64,))
    keys = jax.random.split(jax.random.PRNGKey(1), 4096)
    hats = jax.vmap(lambda k: quantize(k, y, prev, 3).y_hat)(keys)
    q0 = quantize(keys[0], y, prev, 3)
    # standard error of the mean ~ delta/2/sqrt(K); allow 5 sigma
    tol = 5 * float(q0.delta) / 2 / np.sqrt(4096)
    assert float(jnp.max(jnp.abs(hats.mean(0) - y))) < tol


def test_zero_diff_is_exact():
    """If y == y_hat_prev the reconstruction must be exactly y (guarded /0)."""
    y = jnp.ones((8,)) * 3.25
    q = quantize(jax.random.PRNGKey(0), y, y, 3)
    np.testing.assert_allclose(np.asarray(q.y_hat), np.asarray(y), rtol=0, atol=0)


def test_payload_accounting():
    y = jnp.zeros((100,))
    q = quantize(jax.random.PRNGKey(0), y, y, 3)
    assert int(q.payload_bits) == 3 * 100 + 32


def test_payload_bits_exact_at_lm_scale():
    """Regression: the uplink-bit count must be exact (no int32 wraparound,
    which kicked in past d ≈ 2.7e8 at 8 bits — numpy 2.x raised
    OverflowError there) up to d = 1e9."""
    from repro.core import quantization as Q

    d = 1_000_000_000
    assert Q.payload_bits(8, d) == 8 * d + 32  # exact Python int, any scale
    assert Q.exact_payload_bits(d) == 32 * d
    # traced form: int64 (bit-exact) under x64 ...
    with jax.enable_x64(True):
        arr = Q.payload_bits_array(Q.payload_bits(8, d))
        assert arr.dtype == jnp.int64
        assert int(arr) == 8 * d + 32
    # ... and float32 (positive, 2^-24-relative) without — never negative.
    # (Explicitly disabled so the assertion holds under CI's x64 leg too.)
    with jax.enable_x64(False):
        arr32 = Q.payload_bits_array(Q.payload_bits(8, d))
        assert arr32.dtype == jnp.float32
        assert float(arr32) > 0
        assert abs(float(arr32) - (8 * d + 32)) <= (8 * d + 32) * 2**-24


def test_payload_bits_dtype_aware():
    """Baselines must count the transmitted dtype's width, not 32."""
    from repro.core import quantization as Q

    assert Q.word_bits(jnp.zeros((3,), jnp.float32)) == 32
    assert Q.word_bits(jnp.zeros((3,), jnp.bfloat16)) == 16
    assert Q.word_bits(jnp.dtype(jnp.float16)) == 16
    assert Q.exact_payload_bits(100, Q.word_bits(jnp.zeros((), jnp.bfloat16))) == 1600


def test_fedgd_payload_tracks_float64_state():
    """End-to-end satellite check: a float64 run reports 64·d uplink."""
    from repro.core import baselines
    from repro.core.objectives import ClientDataset, logistic_regression

    with jax.enable_x64(True):
        key = jax.random.PRNGKey(0)
        feats = jax.random.normal(key, (4, 16, 10), jnp.float64)
        labels = jnp.sign(jax.random.normal(jax.random.fold_in(key, 1), (4, 16)))
        data = ClientDataset(features=feats, labels=labels.astype(jnp.float64))
        obj = logistic_regression(mu=1e-3)
        state = baselines.fedgd_init(obj, data, baselines.FedGDConfig())
        _, m = baselines.fedgd_step(state, obj, data, baselines.FedGDConfig())
        assert int(m.uplink_bits_per_client) == 64 * data.dim


def test_batch_matches_per_client():
    """quantize_batch must equal per-client quantize with split keys."""
    key = jax.random.PRNGKey(3)
    y = jax.random.normal(key, (5, 17))
    prev = jnp.zeros_like(y)
    qb = quantize_batch(key, y, prev, 4)
    keys = jax.random.split(key, 5)
    for i in range(5):
        qi = quantize(keys[i], y[i], prev[i], 4)
        np.testing.assert_allclose(np.asarray(qb.y_hat[i]), np.asarray(qi.y_hat))


@pytest.mark.parametrize("bits", [1, 3, 8])
def test_error_shrinks_with_bits_on_average(bits):
    key = jax.random.PRNGKey(11)
    y = jax.random.normal(key, (256,))
    prev = jnp.zeros_like(y)
    q = quantize(jax.random.PRNGKey(5), y, prev, bits)
    # Variance bound: E[eps^2] <= Delta^2/4 per element (Reisizadeh et al.)
    mse = float(jnp.mean((q.y_hat - y) ** 2))
    assert mse <= float(q.delta) ** 2  # loose (4x) deterministic-sample bound
