"""Batched damped-SPD solve kernel for FedNew's client sub-problem (eq. 9).

Each FL client must apply (H_i + (alpha+rho) I)^{-1} to its ADMM right-hand
side every round. At paper scale (d ≤ 267) a client's Hessian fits VMEM
many times over, so the kernel keeps the Hessians of a block of C clients
resident in VMEM and runs a fixed-iteration conjugate-gradient loop on all
C systems at once — no HBM traffic inside the loop, one grid step per block
of clients.

Block layout: an A block is ``(C, d, d)`` and the rhs/solution blocks are
``(C, 1, d)`` taken from ``(n, 1, d)`` arrays. The last two dims of every
block span the whole array dims, which the TPU compiler accepts at any d,
so the Hessians reach the kernel unpadded. The grid is ``cdiv(n, C)``; the
clients past n in a ragged last block read whatever the DMA leaves there,
are solved independently of the real ones, and are never written back.
Inside a step the CG vectors are ``(C, d)``, one client a sublane, and the
dot products are lane reductions, one per client.

The matvec runs on the vector unit in exact f32. A one-row matvec would
use 1/128 of the matrix unit's systolic array, and at HIGHEST precision
pay several bf16 passes for it; as elementwise multiply-adds it is f32
products and f32 sums with no rounding of any operand. A is symmetric, so
``(A p)_j = sum_i A_ij p_i``: the step transposes the C clients' p once to
``(d, C)``, broadcasts client k's column across the lanes of its (d, d)
tile, and reduces the product over the sublane axis, which leaves the
result lane-major like the CG vectors. The C clients' dependent CG chains
interleave in one loop body.

The damping (alpha + rho) bounds the condition number, so a modest fixed
iteration count reaches float32 solve accuracy (tests sweep n, d and the
damping against ``ref.py``'s direct solve). ``ops.py`` picks C
from the shapes and calls this with the Hessians as they are.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(a_ref, b_ref, x_ref, *, iters: int, damping: float):
    c = a_ref.shape[0]
    b = b_ref[:, 0, :].astype(jnp.float32)  # (C, d): one client a sublane

    def matvec(p):  # per client (A p)_j = sum_i A_ij p_i on the VPU
        p_t = p.T  # (d, C): client k's p is column k
        rows = [
            jnp.sum(a_ref[k].astype(jnp.float32) * p_t[:, k:k + 1],
                    axis=0, keepdims=True)
            for k in range(c)
        ]
        return jnp.concatenate(rows, axis=0) + damping * p

    def dot(u, v):  # (C, 1): one dot product per client
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
    x_ref[:, 0, :] = x.astype(x_ref.dtype)


def client_solve_cg(
    A: jax.Array,  # (n, d, d) — local Hessians, WITHOUT damping
    b: jax.Array,  # (n, d) — ADMM rhs g_i - lam_i + rho y
    *,
    damping: float,
    clients_per_step: int,
    iters: int = 32,
    interpret: bool = False,
) -> jax.Array:
    """(n, d) solutions of (A_i + damping·I) x = b_i, C clients a grid step."""
    n, d, _ = A.shape
    c = clients_per_step
    kernel = functools.partial(_kernel, iters=iters, damping=damping)
    row = pl.BlockSpec((c, 1, d), lambda i: (i, 0, 0))
    x = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(n, c),),
        in_specs=[pl.BlockSpec((c, d, d), lambda i: (i, 0, 0)), row],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((n, 1, d), b.dtype),
        interpret=interpret,
    )(A, b[:, None, :])
    return x[:, 0, :]
