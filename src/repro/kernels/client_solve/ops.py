"""Jit wrapper for the client_solve kernel: the block choice + the FedNew hook.

``client_solve(A, b, damping)`` picks how many clients share a grid step
(``clients_per_step``) from the shapes and calls the Pallas kernel on the
Hessians as they are: no padding, no copy. ``repro.core.fednew`` routes
eq. 9 through here (via ``repro.kernels.dispatch``) when the config's solve
backend resolves to the Pallas path. ``interpret=None`` means "ask the
dispatch layer": compiled on TPU, interpreter elsewhere — never the
interpreter silently on TPU.
"""

from __future__ import annotations

from functools import partial

import jax

from repro.kernels.client_solve.client_solve import client_solve_cg

SUBLANE, LANE = 8, 128
# Largest d whose double-buffered f32 A tile of one client, (d rounded up to
# 8) x (d rounded up to 128), fits the default scoped VMEM of a TPU v5e
# (2·1280²·4 B ≈ 13 MB; 1408 is refused by the compiler). Larger problems
# solve eq. 9 with backend="reference" or hessian_repr="matfree".
MAX_DIM = 1280
# Clients per grid step at most. Their CG chains interleave in one loop body:
# on a TPU v5e at 60 x 267, 8 a step solve in 0.36 ms where 4 take 0.48.
MAX_CLIENTS_PER_STEP = 8


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _tile_elems(d: int) -> int:
    """f32 elements one client's (d, d) A tile takes in VMEM."""
    return _round_up(d, SUBLANE) * _round_up(d, LANE)


def clients_per_step(n: int, d: int) -> int:
    """Clients a grid step solves: the largest C ≤ 8 (and ≤ n) whose
    double-buffered A tiles fit the VMEM budget that ``MAX_DIM`` sets."""
    fit = _tile_elems(MAX_DIM) // _tile_elems(d)
    return max(1, min(n, MAX_CLIENTS_PER_STEP, fit))


@partial(jax.jit, static_argnames=("damping", "iters", "interpret"))
def client_solve(
    A: jax.Array, b: jax.Array, *, damping: float, iters: int = 32,
    interpret: bool | None = None,
) -> jax.Array:
    if interpret is None:
        from repro.kernels import dispatch

        interpret = dispatch.default_interpret()
    n, d, _ = A.shape
    if d > MAX_DIM and not interpret:
        raise ValueError(
            f"client_solve keeps a ({d}, {d}) f32 tile in VMEM, over the "
            f"{MAX_DIM} limit; solve eq. 9 with backend='reference' or "
            "hessian_repr='matfree' at this d"
        )
    return client_solve_cg(
        A, b, damping=damping, clients_per_step=clients_per_step(n, d),
        iters=iters, interpret=interpret,
    )
