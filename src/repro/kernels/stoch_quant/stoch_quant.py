"""Stochastic quantizer kernel for Q-FedNew (paper eqs. 25-30).

Elementwise map over a *batch* of client direction vectors: given the
previous quantized vectors, per-client scalar ranges R_i (computed by a
cheap jnp row-max outside — one reduction; the elementwise pass is the
byte-moving hot loop), and pre-drawn uniforms, emit the integer levels and
the dequantized vectors.

Grid: 2-D ``(client blocks, column blocks)`` over ``(rows, cols)`` tiles of
the ``(n, N)`` batch — the shape the sharded engine hands each device inside
its ``shard_map`` region (``(n_clients/n_devices, d)``). ``rows`` is the
8-sublane tile (or all of n when n < 8) and ``cols`` a multiple of the
128-lane tile (or all of N when N fits one block): the TPU compiler accepts
a block only when its last two dims are multiples of (8, 128) or span the
whole array. Every tile loads (y, ŷ_prev, u) into VMEM together with its
clients' ``(rows, 1)`` ranges, computes

    c  = (y - ŷ + R) / Δ,   Δ = 2R / (2^bits - 1)
    q  = floor(c) + [u < frac(c)]          (unbiased, eqs. 26-28)
    ŷ' = ŷ + Δ·q - R                        (eq. 30)

entirely in registers/VMEM, and writes (q, ŷ') back. The trailing column
tile of a row whose N is not a multiple of ``cols`` is masked *in-kernel*
(column iota vs the true N), so callers never pad: out-of-range lanes
produce q = 0, ŷ' = ŷ_prev deterministically and Pallas drops the
out-of-bounds writes (trailing client rows likewise). The uniforms are
taken as an input (rather than seeding in-kernel) so the kernel is
bit-exact against ``ref.py`` under any PRNG.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROWS = 8  # clients per tile: the f32 sublane count


def _kernel(y_ref, prev_ref, u_ref, r_ref, q_ref, out_ref, *, bits: int,
            n_cols: int, cols: int):
    j = pl.program_id(1)
    y = y_ref[...].astype(jnp.float32)  # (rows, cols)
    prev = prev_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)
    R = r_ref[...]  # (rows, 1): one range per client row
    n_levels = float((1 << bits) - 1)
    delta = 2.0 * R / n_levels
    safe_delta = jnp.where(delta > 0, delta, 1.0)
    c = (y - prev + R) / safe_delta
    lo = jnp.floor(c)
    q = lo + (u < (c - lo)).astype(jnp.float32)
    q = jnp.clip(q, 0.0, n_levels)
    # In-kernel tail mask: lanes past the true row length carry whatever
    # Pallas padded in (garbage/NaN); force them to a defined (0, ŷ_prev)
    # before the store so interpret and compiled modes agree exactly.
    col = j * cols + jax.lax.broadcasted_iota(jnp.int32, y.shape, 1)
    valid = col < n_cols
    q = jnp.where(valid, q, 0.0)
    y_hat = jnp.where(valid, prev + delta * q - R, prev)
    q_ref[...] = q.astype(q_ref.dtype)
    out_ref[...] = y_hat.astype(out_ref.dtype)


def stoch_quant(
    y: jax.Array,  # (n, N) batched directions, or (N,) single vector
    y_hat_prev: jax.Array,  # same shape as y
    u: jax.Array,  # same shape as y, uniforms in [0, 1)
    R: jax.Array,  # (n,) per-client ranges max|y_i - ŷ_i| (or scalar for 1-D)
    *,
    bits: int,
    block: int = 1024,
    interpret: bool = False,
):
    """Returns (levels int32, y_hat) with y's shape. N need not divide
    ``block`` (a multiple of 128) — the trailing tile is masked in-kernel."""
    squeeze = y.ndim == 1
    if squeeze:
        y, y_hat_prev, u = y[None], y_hat_prev[None], u[None]
    n, N = y.shape
    rows = min(n, ROWS)
    cols = N if N <= block else block
    R2 = jnp.broadcast_to(jnp.asarray(R, jnp.float32).reshape(-1, 1), (n, 1))
    grid = (pl.cdiv(n, rows), pl.cdiv(N, cols))
    kernel = functools.partial(_kernel, bits=bits, n_cols=N, cols=cols)
    tile = pl.BlockSpec((rows, cols), lambda i, j: (i, j))
    q, y_hat = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[tile, tile, tile, pl.BlockSpec((rows, 1), lambda i, j: (i, 0))],
        out_specs=[tile, tile],
        out_shape=[
            jax.ShapeDtypeStruct((n, N), jnp.int32),
            jax.ShapeDtypeStruct((n, N), y.dtype),
        ],
        interpret=interpret,
    )(y, y_hat_prev, u, R2)
    if squeeze:
        return q[0], y_hat[0]
    return q, y_hat
