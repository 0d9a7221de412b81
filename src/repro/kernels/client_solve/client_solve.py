"""Batched damped-SPD solve kernel for FedNew's client sub-problem (eq. 9).

Each FL client must apply (H_i + (alpha+rho) I)^{-1} to its ADMM right-hand
side every round. At paper scale (d ≤ 267) the whole damped Hessian tile fits
VMEM with room to spare, so the TPU-native design keeps A_i resident in VMEM
and runs a fixed-iteration conjugate-gradient loop whose matvec is a
(1 × d)·(d × d) MXU contraction at full f32 precision — no HBM traffic
inside the loop, one grid step per client.

Block layout: the client dim is squeezed out of every block (``None``), so
the kernel sees an A tile of ``(d, d)`` and rhs/solution tiles of ``(1, d)``
taken from ``(n, 1, d)`` arrays — each block's last two dims span the whole
array dims, which is what the TPU compiler requires of a block that is not
(8, 128)-aligned. A is double-buffered across grid steps, so VMEM holds
2·d²·4 bytes of it: ``ops.MAX_DIM`` is the largest padded d that fits the
default scoped VMEM.

The damping (alpha + rho) bounds the condition number, so a modest fixed
iteration count reaches float32 solve accuracy (tests sweep d, dtype, and
iteration count against ``ref.py``'s direct solve).

Shapes are padded to the 128-lane MXU tile by ``ops.py``; padding rows carry
an identity diagonal and zero rhs so they solve to exactly zero.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(a_ref, b_ref, x_ref, *, iters: int, damping: float):
    b = b_ref[...].astype(jnp.float32)  # (1, d)

    def matvec(p):  # (1,d) @ (d,d) on the MXU; A is symmetric
        A = a_ref[...].astype(jnp.float32)  # (d, d), read from VMEM
        return jax.lax.dot_general(
            p, A, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        ) + damping * p

    def dot(u, v):  # (1, 1): keeps every value 2-D for the TPU lowering
        return jnp.sum(u * v, axis=1, keepdims=True)

    x = jnp.zeros_like(b)
    r = b
    p = r
    rs = dot(r, r)

    def body(_, carry):
        x, r, p, rs = carry
        ap = matvec(p)
        denom = dot(p, ap)
        alpha = jnp.where(denom > 0, rs / jnp.maximum(denom, 1e-30), 0.0)
        x = x + alpha * p
        r = r - alpha * ap
        rs_new = dot(r, r)
        beta = jnp.where(rs > 0, rs_new / jnp.maximum(rs, 1e-30), 0.0)
        p = r + beta * p
        return x, r, p, rs_new

    x, r, p, rs = jax.lax.fori_loop(0, iters, body, (x, r, p, rs))
    x_ref[...] = x.astype(x_ref.dtype)


def client_solve_cg(
    A: jax.Array,  # (n, d, d) — local Hessians, WITHOUT damping
    b: jax.Array,  # (n, d) — ADMM rhs g_i - lam_i + rho y
    *,
    damping: float,
    iters: int = 32,
    interpret: bool = False,
) -> jax.Array:
    """(n, d) solutions of (A_i + damping·I) x = b_i, one grid step/client."""
    n, d, _ = A.shape
    kernel = functools.partial(_kernel, iters=iters, damping=damping)
    row = pl.BlockSpec((None, 1, d), lambda i: (i, 0, 0))
    x = pl.pallas_call(
        kernel,
        grid=(n,),
        in_specs=[pl.BlockSpec((None, d, d), lambda i: (i, 0, 0)), row],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((n, 1, d), b.dtype),
        interpret=interpret,
    )(A, b[:, None, :])
    return x[:, 0, :]
