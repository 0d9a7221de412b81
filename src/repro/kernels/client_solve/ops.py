"""Jit wrappers for the client_solve kernel: padding + the FedNew hook.

``client_solve(A, b, damping)`` pads d up to the 128-lane tile (identity
diagonal + zero rhs on the pad, so padded coordinates solve to exactly 0 and
never feed back into the CG recurrences), calls the Pallas kernel, and strips
the pad. ``repro.core.fednew`` routes eq. 9 through here (via
``repro.kernels.dispatch``) when the config's solve backend resolves to the
Pallas path. ``interpret=None`` means "ask the dispatch layer": compiled on
TPU, interpreter elsewhere — never the interpreter silently on TPU.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.client_solve.client_solve import client_solve_cg

LANE = 128
# Largest padded d whose double-buffered (d, d) f32 A tile fits the default
# scoped VMEM of a TPU v5e (2·1280²·4 B ≈ 13 MB; 1408 is refused by the
# compiler). Larger problems solve eq. 9 with backend="reference" or
# hessian_repr="matfree".
MAX_DIM = 1280


def _pad_up(d: int) -> int:
    return -(-d // LANE) * LANE


@partial(jax.jit, static_argnames=("damping", "iters", "interpret"))
def client_solve(
    A: jax.Array, b: jax.Array, *, damping: float, iters: int = 32,
    interpret: bool | None = None,
) -> jax.Array:
    if interpret is None:
        from repro.kernels import dispatch

        interpret = dispatch.default_interpret()
    n, d, _ = A.shape
    dp = _pad_up(d)
    if dp > MAX_DIM and not interpret:
        raise ValueError(
            f"client_solve keeps a ({dp}, {dp}) f32 tile in VMEM, over the "
            f"{MAX_DIM} limit; solve eq. 9 with backend='reference' or "
            "hessian_repr='matfree' at this d"
        )
    if dp != d:
        pad = dp - d
        A = jnp.pad(A, ((0, 0), (0, pad), (0, pad)))
        # identity on the padded diagonal keeps the system SPD; with zero rhs
        # the padded solution coordinates are exactly zero.
        diag = jnp.arange(d, dp)
        A = A.at[:, diag, diag].set(1.0)
        b = jnp.pad(b, ((0, 0), (0, pad)))
    x = client_solve_cg(A, b, damping=damping, iters=iters, interpret=interpret)
    return x[:, :d]


def client_solve_from_chol(chol: jax.Array, rhs: jax.Array) -> jax.Array:
    """Back-compat hook for the faithful Cholesky path (repro.core.fednew):
    reconstruct A = L L^T - damping I is wasteful, so this simply runs the
    triangular solves — the CG kernel is exposed via ``client_solve`` and is
    exercised by the fednew step when configs carry raw Hessians."""
    import jax.scipy.linalg as jsl

    return jax.vmap(lambda L, r: jsl.cho_solve((L, True), r))(chol, rhs)
