"""The plain reference: FedNew / Q-FedNew rounds and f(x*) in ``jax.numpy``.

Written from the paper (Elgabli et al., ICML 2022, Algorithm 1, eqs. 9,
12-14 and 25-30) and regularized logistic regression (eqs. 31-32), with no
import from the program under test and nothing taken from its run but the
client data, which is the benchmark's own. It computes in the dtype it is
given: float32 under ``default_matmul_precision("highest")`` is the
reference; bfloat16 is the control that must come out as not correct.

One round, for clients i = 1..n holding (A_i, b_i):

    g_i = grad f_i(x),  H_i = hess f_i(anchor_i)
    y_i = (H_i + (alpha + rho) I)^{-1} (g_i - lam_i + rho y)     eq. 9
    y_i' = codec(y_i)                       identity, or eqs. 25-30
    y = mean_i y_i'                                               eq. 13
    lam_i += rho (y_i' - y)                                       eq. 12
    x -= y                                                        eq. 14

The dense configuration solves eq. 9 exactly (Cholesky); the matrix-free
one runs the configured number of conjugate-gradient iterations on
Hessian-vector products, because that inexact solve is part of what the
configuration states.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _loss_1(x, A, b, mu):
    z = b * (A @ x)
    return jnp.mean(jnp.logaddexp(0.0, -z)) + 0.5 * mu * jnp.vdot(x, x)


def _grad_1(x, A, b, mu):
    z = b * (A @ x)
    return A.T @ (-jax.nn.sigmoid(-z) * b) / A.shape[0] + mu * x


def _curv_1(x, A, b):
    """Per-sample Hessian weights s(1 - s) at x (b^2 = 1)."""
    s = jax.nn.sigmoid(b * (A @ x))
    return s * (1.0 - s)


def _hess_1(x, A, b, mu):
    w = _curv_1(x, A, b)
    return (A.T * w) @ A / A.shape[0] + mu * jnp.eye(A.shape[1], dtype=A.dtype)


def _hvp_1(w, v, A, mu):
    return A.T @ (w * (A @ v)) / A.shape[0] + mu * v


def global_loss(x, A, b, mu):
    return jnp.mean(jax.vmap(_loss_1, (None, 0, 0, None))(x, A, b, mu))


def _cg(mv, rhs, iters):
    """``iters`` plain CG iterations from 0 on a batch of SPD systems."""
    dot = lambda u, v: jnp.sum(u * v, axis=-1, keepdims=True)
    x = jnp.zeros_like(rhs)
    r, p = rhs, rhs
    rs = dot(r, r)

    def body(_, c):
        x, r, p, rs = c
        ap = mv(p)
        den = dot(p, ap)
        a = jnp.where(den > 0, rs / jnp.maximum(den, 1e-30), 0.0)
        x, r = x + a * p, r - a * ap
        rs_new = dot(r, r)
        p = r + jnp.where(rs > 0, rs_new / jnp.maximum(rs, 1e-30), 0.0) * p
        return x, r, p, rs_new

    return jax.lax.fori_loop(0, iters, body, (x, r, p, rs))[0]


def _quantize(keys, y, prev, bits):
    """Eqs. 25-30 per client: unbiased stochastic rounding of y - prev onto
    2^bits levels spanning [-R, R]; returns the reconstruction."""
    n_levels = (1 << bits) - 1
    u = jax.vmap(lambda k: jax.random.uniform(k, (y.shape[-1],), y.dtype))(keys)
    R = jnp.max(jnp.abs(y - prev), axis=-1, keepdims=True)
    delta = 2.0 * R / n_levels
    c = (y - prev + R) / jnp.where(delta > 0, delta, 1.0)
    lo = jnp.floor(c)
    q = jnp.clip(lo + (u < c - lo).astype(y.dtype), 0, n_levels)
    return prev + delta * q - R


def uplink_bits(codec: dict, d: int) -> int:
    """Exact bits one client sends per round: 32·d for float32 directions,
    bits·d + 32 (the range R at float32) for the stochastic quantizer."""
    if codec["name"] == "identity":
        return 32 * d
    if codec["name"] == "stoch_quant":
        return codec["bits"] * d + 32
    raise ValueError(f"no reference for codec {codec['name']!r}")


@functools.partial(jax.jit, static_argnames=("hp", "codec", "rounds", "dtype"))
def fednew_rounds(A, b, key, *, hp, codec, rounds, dtype):
    """The first ``rounds`` rounds from x = 0. ``hp`` and ``codec`` are
    hashable tuples of (key, value) pairs. Returns per-round global loss
    and direction norm, and the model after the last round."""
    hp, codec = dict(hp), dict(codec)
    A, b = A.astype(dtype), b.astype(dtype)
    n, _, d = A.shape
    mu, rho, alpha = hp["mu"], hp["rho"], hp["alpha"]
    damp = alpha + rho
    period = hp["hessian_period"]
    matfree = hp["hessian_repr"] == "matfree"
    grad = jax.vmap(_grad_1, (None, 0, 0, None))

    def curvature(x):
        if matfree:
            return jax.vmap(_curv_1, (None, 0, 0))(x, A, b)  # (n, m)
        H = jax.vmap(_hess_1, (None, 0, 0, None))(x, A, b, mu)
        return jax.vmap(jnp.linalg.cholesky)(
            (H + damp * jnp.eye(d, dtype=dtype)).astype(jnp.float32)
        )

    def solve(curv, rhs):
        if matfree:
            mv = lambda v: jax.vmap(_hvp_1, (0, 0, 0, None))(curv, v, A, mu) + damp * v
            return _cg(mv, rhs, hp["cg_iters"])
        sol = jax.vmap(lambda L, r: jax.scipy.linalg.cho_solve((L, True), r))(
            curv, rhs.astype(jnp.float32)
        )
        return sol.astype(dtype)

    def one(carry, _):
        x, y, lam, prev, curv, key, k = carry
        curv = jax.lax.cond(
            (k % period) == 0 if period > 0 else k == 0,
            lambda: curvature(x), lambda: curv,
        )
        rhs = grad(x, A, b, mu) - lam + rho * y
        y_i = solve(curv, rhs)
        if codec["name"] == "stoch_quant":
            key, sub = jax.random.split(key)
            y_i = _quantize(jax.random.split(sub, n), y_i, prev, codec["bits"])
            prev = y_i
        y = jnp.mean(y_i, axis=0)
        lam = lam + rho * (y_i - y)
        x = x - y
        return (x, y, lam, prev, curv, key, k + 1), (
            global_loss(x, A, b, mu), jnp.linalg.norm(y)
        )

    x0 = jnp.zeros((d,), dtype)
    zeros = jnp.zeros((n, d), dtype)
    carry = (x0, x0, zeros, zeros, curvature(x0), key, jnp.zeros((), jnp.int32))
    carry, (loss, dnorm) = jax.lax.scan(one, carry, None, length=rounds)
    return loss.astype(jnp.float32), dnorm.astype(jnp.float32), carry[0].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("mu", "steps"))
def newton_dense(A, b, *, mu, steps):
    """f(x*) as the paper takes it: ``steps`` exact Newton steps on the
    global objective from 0 (d small enough for a (d, d) solve). Returns
    the last iterate, its loss and gradient norm."""
    d = A.shape[-1]
    hess = jax.vmap(_hess_1, (None, 0, 0, None))
    grad = jax.vmap(_grad_1, (None, 0, 0, None))

    def step(x, _):
        H = jnp.mean(hess(x, A, b, mu), axis=0)
        g = jnp.mean(grad(x, A, b, mu), axis=0)
        return x - jnp.linalg.solve(H, g), None

    x, _ = jax.lax.scan(step, jnp.zeros((d,), A.dtype), None, length=steps)
    g = jnp.mean(grad(x, A, b, mu), axis=0)
    return x, global_loss(x, A, b, mu), jnp.linalg.norm(g)


@functools.partial(jax.jit, static_argnames=("mu", "steps", "cg_iters"))
def newton_cg(A, b, *, mu, steps, cg_iters):
    """f(x*) where no (d, d) matrix fits: ``steps`` Newton steps, each
    solved by ``cg_iters`` CG iterations on exact global Hessian-vector
    products. Returns the last iterate, its loss and gradient norm."""
    n, _, d = A.shape
    grad = jax.vmap(_grad_1, (None, 0, 0, None))

    def step(x, _):
        g = jnp.mean(grad(x, A, b, mu), axis=0)
        w = jax.vmap(_curv_1, (None, 0, 0))(x, A, b)
        mv = lambda v: jnp.mean(
            jax.vmap(_hvp_1, (0, None, 0, None))(w, v[0], A, mu), axis=0
        )[None]
        return x - _cg(mv, g[None], cg_iters)[0], None

    x, _ = jax.lax.scan(step, jnp.zeros((d,), A.dtype), None, length=steps)
    g = jnp.mean(grad(x, A, b, mu), axis=0)
    return x, global_loss(x, A, b, mu), jnp.linalg.norm(g)
