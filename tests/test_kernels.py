"""Pallas kernel validation (interpret=True on CPU) against jnp oracles.

Per the harness contract: every kernel sweeps shapes/dtypes and
assert_allclose's against its ref.py."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.client_solve import ops as cs_ops
from repro.kernels.client_solve.ref import client_solve_ref
from repro.kernels.glm_hvp import ops as glm_ops
from repro.kernels.glm_hvp.glm_hvp import glm_hvp_pallas
from repro.kernels.glm_hvp.ref import glm_hvp_ref
from repro.kernels.stoch_quant import ops as sq_ops
from repro.kernels.stoch_quant.ref import stoch_quant_ref
from repro.kernels.stoch_quant.stoch_quant import stoch_quant
from repro.kernels.swa_attention import ops as swa_ops
from repro.kernels.swa_attention.ref import swa_attention_ref


# ---------------------------------------------------------------------------
# swa_attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("S,window,q_blk", [(256, 64, 64), (256, 100, 64), (512, 128, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_swa_kernel_matches_ref(S, window, q_blk, dtype):
    B, H, Hkv, Dh = 2, 4, 2, 64
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (B, S, H, Dh), jnp.float32).astype(dtype)
    k = jax.random.normal(kk, (B, S, Hkv, Dh), jnp.float32).astype(dtype)
    v = jax.random.normal(kv, (B, S, Hkv, Dh), jnp.float32).astype(dtype)
    got = swa_ops.swa_attention(q, k, v, window=window, q_blk=q_blk, interpret=True)
    G = H // Hkv
    q2 = q.transpose(0, 2, 1, 3).reshape(B * H, S, Dh)
    k2 = k.transpose(0, 2, 1, 3).reshape(B * Hkv, S, Dh)
    v2 = v.transpose(0, 2, 1, 3).reshape(B * Hkv, S, Dh)
    ref = swa_attention_ref(q2, k2, v2, window=window, groups=G)
    ref = ref.reshape(B, H, S, Dh).transpose(0, 2, 1, 3)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32), atol=tol, rtol=tol
    )


def test_swa_kernel_softcap():
    B, S, H, Dh, window = 1, 128, 2, 64, 32
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (jax.random.normal(kk, (B, S, H, Dh), jnp.float32) for kk in ks)
    got = swa_ops.swa_attention(q, k, v, window=window, q_blk=64, cap=20.0, interpret=True)
    q2 = q.transpose(0, 2, 1, 3).reshape(B * H, S, Dh)
    k2 = k.transpose(0, 2, 1, 3).reshape(B * H, S, Dh)
    v2 = v.transpose(0, 2, 1, 3).reshape(B * H, S, Dh)
    ref = swa_attention_ref(q2, k2, v2, window=window, cap=20.0)
    ref = ref.reshape(B, H, S, Dh).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_swa_kernel_vs_model_attention():
    """The kernel must agree with the model's jnp sliding-window path."""
    import dataclasses

    from repro.configs.registry import get_config
    from repro.models.attention import causal_attention

    cfg = dataclasses.replace(
        get_config("mixtral-8x7b").reduced(), attn_q_chunk=64, attn_kv_chunk=64
    )
    B, S, H, Hkv, Dh, window = 2, 256, 4, 2, 64, 64
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, S, H, Dh), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, Dh), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, Dh), jnp.float32)
    model_out = causal_attention(q, k, v, cfg, window=window, cap=None)
    kern_out = swa_ops.swa_attention(q, k, v, window=window, q_blk=64, interpret=True)
    np.testing.assert_allclose(
        np.asarray(kern_out), np.asarray(model_out), atol=3e-5, rtol=3e-5
    )


# ---------------------------------------------------------------------------
# glm_hvp
# ---------------------------------------------------------------------------


def _glm_inputs(n, m, d, dtype, seed=0):
    ka, kw, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    a = jax.random.normal(ka, (n, m, d), jnp.float32).astype(dtype)
    w = 0.25 * jax.random.uniform(kw, (n, m), jnp.float32)
    v = jax.random.normal(kv, (n, d), jnp.float32)
    return a, w, v


@pytest.mark.parametrize(
    "n,m,d,rows,dtype",
    [
        (1, 37, 267, 16, jnp.float32),  # one client, ragged m and d
        (3, 37, 267, 16, jnp.float32),
        (3, 1012, 131, None, jnp.float32),  # rcv1's m: one 1,016-row tile
        (2, 45, 1300, 16, jnp.bfloat16),  # d past one lane chunk, ragged tail
        (3, 21, 2048, 8, jnp.float32),  # d a whole number of chunks
        (1, 64, 128, 16, jnp.float32),  # m a whole number of tiles
        (2, 29, 24, 16, jnp.float32),  # d under one lane group
    ],
)
def test_glm_hvp_matches_ref(n, m, d, rows, dtype):
    """``rows`` forces small row tiles (None: the wrapper's choice)."""
    a, w, v = _glm_inputs(n, m, d, dtype)
    if rows is None:
        got = glm_ops.glm_hvp(a, w, v, mu=1e-3, interpret=True)
    else:
        got = glm_hvp_pallas(a, w, v, mu=1e-3, rows_per_step=rows, interpret=True)
    ref = glm_hvp_ref(a, w, v, mu=1e-3)
    scale = float(jnp.max(jnp.abs(ref)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5,
                               atol=1e-5 * scale)


def test_glm_hvp_masks_the_rows_past_m():
    """The rows of the last tile past m hold whatever the DMA left there;
    the interpreter fills them with NaN (checked first), and NaN * 0 is
    NaN, so a result without NaN shows the kernel masks those rows of A
    itself and not only their weights."""
    from jax.experimental import pallas as pl

    def count_nan(x_ref, o_ref):
        o_ref[...] = jnp.sum(jnp.isnan(x_ref[...]).astype(jnp.float32),
                             axis=0, keepdims=True)

    nans = pl.pallas_call(
        count_nan, grid=(1,), in_specs=[pl.BlockSpec((8, 128), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((1, 128), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, 128), jnp.float32), interpret=True,
    )(jnp.zeros((5, 128), jnp.float32))
    assert np.all(np.asarray(nans) == 3)

    a, w, v = _glm_inputs(2, 37, 300, jnp.float32, seed=3)
    got = np.asarray(glm_hvp_pallas(a, w, v, mu=1e-3, rows_per_step=16, interpret=True))
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, np.asarray(glm_hvp_ref(a, w, v, mu=1e-3)),
                               rtol=1e-5, atol=1e-5 * np.max(np.abs(got)))


def test_glm_hvp_rows_per_step():
    """A bf16 tile of 16-row multiples near TILE_BYTES and of at least
    MIN_ROWS rows, covering m in as few tiles as that allows: 13 tiles of
    80 rows at rcv1, 16 of 64 at E2006; f32 tiles are 8-row multiples."""
    assert glm_ops.rows_per_step(1012, 47236, 2) == 80
    assert glm_ops.rows_per_step(969, 150360, 2) == 64
    assert glm_ops.rows_per_step(37, 267, 4) == 40
    for m, d, size in [(1012, 47236, 2), (969, 150360, 2), (829, 267, 4), (5, 10**6, 2)]:
        tm = glm_ops.rows_per_step(m, d, size)
        pack = 8 * (4 // size)
        assert tm % pack == 0
        assert (tm <= glm_ops.MIN_ROWS
                or tm * -(-d // 128) * 128 * size <= glm_ops.TILE_BYTES)
        tiles = -(-m // tm)
        assert (tiles - 1) * tm < m <= tiles * tm


# ---------------------------------------------------------------------------
# client_solve
# ---------------------------------------------------------------------------


def _spd(key, n, d, cond=50.0):
    Q = jnp.linalg.qr(jax.random.normal(key, (n, d, d)))[0]
    eigs = jnp.logspace(0, np.log10(cond), d)[None]
    return jnp.einsum("nij,nj,nkj->nik", Q, jnp.broadcast_to(eigs, (n, d)), Q)


@pytest.mark.parametrize(
    "n,d",
    [(4, 40), (4, 99), (4, 128), (4, 263), (1, 40), (7, 99), (15, 267), (4, 267)],
)
@pytest.mark.parametrize("damping", [0.5, 2.0])
def test_client_solve_matches_direct(n, d, damping):
    """n = 15 leaves a ragged last block of 7 clients; n = 1 and 7 make a
    block of the whole batch."""
    kA, kb = jax.random.split(jax.random.PRNGKey(d))
    A = _spd(kA, n, d)
    b = jax.random.normal(kb, (n, d), jnp.float32)
    got = cs_ops.client_solve(A, b, damping=damping, iters=96, interpret=True)
    ref = client_solve_ref(A, b, damping=damping)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-4, rtol=2e-3)


def test_client_solve_padding_exact_zero():
    """An unaligned d (70) solves correctly with no pad: the Hessians reach
    the kernel as they are, with no pad or scatter in the wrapper."""
    n, d = 2, 70
    kA, kb = jax.random.split(jax.random.PRNGKey(7))
    A = _spd(kA, n, d, cond=10.0)
    b = jax.random.normal(kb, (n, d), jnp.float32)
    solve = lambda A, b: cs_ops.client_solve(A, b, damping=1.0, iters=96, interpret=True)
    program = str(jax.make_jaxpr(solve)(A, b))
    assert "pallas_call[" in program
    assert "pad[" not in program and "scatter[" not in program
    got = solve(A, b)
    ref = client_solve_ref(A, b, damping=1.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-4, rtol=2e-3)


def test_clients_per_step_fits_vmem_budget():
    """8 clients a step at w8a's d, 1 at MAX_DIM, never more than n, and
    the chosen double-buffered A tiles within the budget MAX_DIM sets."""
    assert cs_ops.clients_per_step(60, 267) == 8
    assert cs_ops.clients_per_step(15, 267) == 8
    assert cs_ops.clients_per_step(3, 267) == 3
    assert cs_ops.clients_per_step(2, cs_ops.MAX_DIM) == 1

    def tile_bytes(d):  # one client's f32 (d, d) tile as VMEM lays it out
        return -(-d // 8) * 8 * (-(-d // 128) * 128) * 4

    budget = 2 * tile_bytes(cs_ops.MAX_DIM)
    for d in (40, 267, 640, 1280):
        c = cs_ops.clients_per_step(60, d)
        assert 1 <= c <= 8
        assert 2 * c * tile_bytes(d) <= budget
        assert c == 8 or 2 * (c + 1) * tile_bytes(d) > budget


def test_fednew_with_kernel_path_matches_cholesky():
    """End-to-end: FedNew rounds with use_kernel=True track the faithful path."""
    from repro.core import fednew
    from repro.core.objectives import logistic_regression
    from repro.data.synthetic import PAPER_DATASETS, make_dataset

    data = make_dataset(PAPER_DATASETS["phishing"], jax.random.PRNGKey(0))
    obj = logistic_regression(mu=1e-3)
    cfg_ref = fednew.FedNewConfig(rho=1.0, alpha=1.0, hessian_period=1)
    cfg_ker = fednew.FedNewConfig(rho=1.0, alpha=1.0, hessian_period=1, use_kernel=True)
    _, m_ref = fednew.run(obj, data, cfg_ref, rounds=8)
    _, m_ker = fednew.run(obj, data, cfg_ker, rounds=8)
    np.testing.assert_allclose(
        np.asarray(m_ker.loss), np.asarray(m_ref.loss), rtol=1e-4, atol=1e-5
    )


# ---------------------------------------------------------------------------
# stoch_quant
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("N", [1024, 4096])
@pytest.mark.parametrize("bits", [1, 3, 8])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_stoch_quant_bit_exact_vs_ref(N, bits, dtype):
    ky, kp, ku = jax.random.split(jax.random.PRNGKey(bits * 7 + N), 3)
    y = jax.random.normal(ky, (N,), jnp.float32).astype(dtype)
    prev = (jax.random.normal(kp, (N,), jnp.float32) * 0.1).astype(dtype)
    u = jax.random.uniform(ku, (N,), jnp.float32)
    R = jnp.max(jnp.abs(y.astype(jnp.float32) - prev.astype(jnp.float32)))
    q_k, yh_k = stoch_quant(y, prev, u, R, bits=bits, interpret=True)
    q_r, yh_r = stoch_quant_ref(y, prev, u, R, bits=bits)
    np.testing.assert_array_equal(np.asarray(q_k), np.asarray(q_r))
    # integer levels are bit-exact; the dequantized value may differ by one
    # output-dtype ulp (cast rounding order), so the tolerance is dtype-aware
    rtol = 2 ** -7 if dtype == jnp.bfloat16 else 1e-6
    np.testing.assert_allclose(
        np.asarray(yh_k, np.float32), np.asarray(yh_r, np.float32), rtol=rtol, atol=1e-6
    )


def test_stoch_quant_ops_error_bound():
    """|ŷ - y| <= Δ elementwise (paper's one-level error bound)."""
    key = jax.random.PRNGKey(3)
    y = jax.random.normal(key, (3000,), jnp.float32)
    prev = jnp.zeros((3000,), jnp.float32)
    res = sq_ops.quantize(jax.random.PRNGKey(4), y, prev, bits=3, interpret=True)
    err = np.abs(np.asarray(res.y_hat - y))
    assert err.max() <= float(res.delta) * (1 + 1e-6)


@pytest.mark.parametrize("n,N,block", [
    (3, 1000, 256),   # tail block per row
    (4, 1024, 256),   # exact fit
    (2, 77, 256),     # single partial block
    (5, 1300, 512),   # tail with a bigger tile
])
def test_stoch_quant_2d_grid_tail_masking(n, N, block):
    """The batched (clients, blocks) grid with in-kernel tail masking must
    match the oracle for any N, with NO host-side padding (the old kernel
    asserted N % block == 0)."""
    ky, kp, ku = jax.random.split(jax.random.PRNGKey(n * N), 3)
    y = jax.random.normal(ky, (n, N), jnp.float32)
    prev = jax.random.normal(kp, (n, N), jnp.float32) * 0.1
    u = jax.random.uniform(ku, (n, N), jnp.float32)
    R = jnp.max(jnp.abs(y - prev), axis=1)
    q_k, yh_k = stoch_quant(y, prev, u, R, bits=3, block=block, interpret=True)
    q_r, yh_r = stoch_quant_ref(y, prev, u, R, bits=3)
    np.testing.assert_array_equal(np.asarray(q_k), np.asarray(q_r))
    np.testing.assert_allclose(np.asarray(yh_k), np.asarray(yh_r), rtol=1e-6, atol=1e-6)


def test_stoch_quant_2d_zero_diff_row():
    """A client whose diff is exactly zero (R = 0) must reconstruct itself
    exactly — the guarded division, per row of the 2-D grid."""
    n, N = 3, 500
    y = jax.random.normal(jax.random.PRNGKey(0), (n, N), jnp.float32)
    prev = y.at[1].set(0.0)  # row 1 has diff; rows 0 and 2 are zero-diff
    prev = prev.at[0].set(y[0]).at[2].set(y[2])
    u = jax.random.uniform(jax.random.PRNGKey(1), (n, N), jnp.float32)
    R = jnp.max(jnp.abs(y - prev), axis=1)
    q_k, yh_k = stoch_quant(y, prev, u, R, bits=4, interpret=True)
    np.testing.assert_array_equal(np.asarray(yh_k[0]), np.asarray(y[0]))
    np.testing.assert_array_equal(np.asarray(yh_k[2]), np.asarray(y[2]))
    np.testing.assert_array_equal(np.asarray(q_k[0]), np.zeros(N, np.int32))
    q_r, yh_r = stoch_quant_ref(y, prev, u, R, bits=4)
    np.testing.assert_array_equal(np.asarray(q_k), np.asarray(q_r))


def test_stoch_quant_ops_batched_matches_reference_quantize():
    """ops.quantize_with_keys (one 2-D grid) == vmapped reference quantize,
    levels bit for bit and ŷ bit for bit (same keys, float32)."""
    from repro.core.quantization import quantize_with_keys as ref_qwk

    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    y = jax.random.normal(jax.random.PRNGKey(6), (4, 1111), jnp.float32)
    prev = jax.random.normal(jax.random.PRNGKey(8), (4, 1111), jnp.float32) * 0.3
    res_k = sq_ops.quantize_with_keys(keys, y, prev, 3, interpret=True)
    res_r = jax.jit(lambda: ref_qwk(keys, y, prev, 3))()
    np.testing.assert_array_equal(
        np.asarray(res_k.levels), np.asarray(res_r.levels)
    )
    np.testing.assert_array_equal(np.asarray(res_k.y_hat), np.asarray(res_r.y_hat))


# ---------------------------------------------------------------------------
# slstm_scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("S,t_blk", [(64, 16), (96, 32), (128, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_slstm_scan_matches_ref(S, t_blk, dtype):
    from repro.kernels.slstm_scan import slstm_scan, slstm_scan_ref

    B, D, H = 2, 64, 4
    w = D // H
    ks = jax.random.split(jax.random.PRNGKey(S + t_blk), 4)
    x4 = (jax.random.normal(ks[0], (B, S, 4 * D), jnp.float32)).astype(dtype)
    r = (jax.random.normal(ks[1], (H, w, 4 * w), jnp.float32) * 0.3).astype(dtype)
    bias = jnp.zeros((4 * D,), jnp.float32)
    state = tuple(jnp.zeros((B, D), jnp.float32) for _ in range(4))
    hs_k, fin_k = slstm_scan(x4, r, bias, state, t_blk=t_blk, interpret=True)
    hs_r, fin_r = slstm_scan_ref(x4, r, bias, state)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(hs_k), np.asarray(hs_r), atol=tol, rtol=tol)
    for a, b in zip(fin_k, fin_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=tol, rtol=tol)


def test_slstm_scan_matches_model_layer():
    """Kernel output must match models.xlstm.slstm_apply's recurrence."""
    import dataclasses

    from repro.configs.registry import get_config
    from repro.kernels.slstm_scan import slstm_scan
    from repro.models import xlstm as xl
    from repro.models.layers import dense

    cfg = dataclasses.replace(get_config("xlstm-350m").reduced())
    params = xl.slstm_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    B, S, D = 2, 32, cfg.d_model
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, D), jnp.float32) * 0.5
    y_ref, _ = xl.slstm_apply(params, cfg, x)
    x4 = dense(params["wx"], x)
    state = tuple(jnp.zeros((B, D), jnp.float32) for _ in range(4))
    hs, _ = slstm_scan(x4, params["r"], params["bias"], state, t_blk=16, interpret=True)
    from repro.models.layers import rmsnorm

    y_kern = dense(params["down"], rmsnorm(params["hnorm"], hs.astype(x.dtype), cfg.norm_eps))
    np.testing.assert_allclose(np.asarray(y_kern), np.asarray(y_ref), atol=2e-5, rtol=2e-5)
