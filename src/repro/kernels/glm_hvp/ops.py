"""Jit wrapper for the glm_hvp kernel: the row-tile choice + the FedNew hook.

``glm_hvp(a, w, v, mu=)`` picks how many rows of a client a grid step reads
(``rows_per_step``) from the shapes and calls the Pallas kernel.
``repro.core.fednew`` reaches it through ``repro.kernels.dispatch`` once per
CG iteration of the matrix-free eq. 9 solve, when the objective exposes its
GLM curvature split and the solve backend resolves to the Pallas path. The
wrapper's name is what the device trace calls the kernel (``glm_hvp.N``).
``interpret=None`` means "ask the dispatch layer": compiled on TPU,
interpreter elsewhere.
"""

from __future__ import annotations

from functools import partial

import jax

from repro.kernels.glm_hvp.glm_hvp import LANE, SUBLANE, glm_hvp_pallas

# Bytes of one A tile in VMEM: large enough that a grid step's DMA dwarfs
# its fixed cost, small enough that two tiles and the (8, d) vectors fit
# VMEM at E2006's d = 150,360.
TILE_BYTES = 8 << 20
# Rows a step reads at least. Short tiles read slower than their DMA: on a
# TPU v5e at E2006's 5 x 969 x 150,360 bf16, a call in a loop of eight took
# 2.91 ms at 32 rows, 2.22 at 48, 2.17 at 64 and 2.21 at 96.
MIN_ROWS = 64


def rows_per_step(m: int, d: int, itemsize: int) -> int:
    """Rows of one client a grid step reads: a multiple of the dtype's
    sublane packing (16 rows of bf16, 8 of f32), at least ``MIN_ROWS``,
    with the tile near ``TILE_BYTES``, spread so the row tiles cover m
    with the fewest rows past it."""
    pack = SUBLANE * max(1, 4 // itemsize)
    row = -(-d // LANE) * LANE * itemsize
    cap = max(MIN_ROWS, TILE_BYTES // row // pack * pack)
    tiles = -(-m // cap)
    return -(-(-(-m // tiles)) // pack) * pack


@partial(jax.jit, static_argnames=("mu", "interpret"))
def glm_hvp(
    a: jax.Array, w: jax.Array, v: jax.Array, *, mu: float,
    interpret: bool | None = None,
) -> jax.Array:
    """(n, d): every client's A_i^T (w_i * (A_i v_i)) / m + mu v_i, reading
    each client's features ``a`` (n, m, d) once."""
    if interpret is None:
        from repro.kernels import dispatch

        interpret = dispatch.default_interpret()
    _, m, d = a.shape
    return glm_hvp_pallas(a, w, v, mu=mu,
                          rows_per_step=rows_per_step(m, d, a.dtype.itemsize),
                          interpret=interpret)
