"""Fused GLM Hessian-vector product for the matrix-free eq. 9 solve.

A generalized linear model's client Hessian at its anchor splits as

    H_i = A_i^T diag(w_i) A_i / m + mu I

(``Objective.local_curvature`` gives the weights w_i, logistic regression's
s(1 - s)). Each CG iteration of ``hessian_repr="matfree"`` applies it to one
vector per client. Written as two matvecs, ``A^T (w * (A v))``, that reads
the features twice; here each row tile of A is read from HBM once and both
products are taken from the same VMEM tile:

    t = A_t v_i,   u = w_t * t,   acc += A_t^T u,   out_i = acc / m + mu v_i.

Grid: ``(clients, row tiles)``. A step DMAs one ``(1, tm, d)`` tile with the
whole d as its last dimension, which the TPU compiler accepts at any d.
``v_i`` and the output block are indexed by the client alone, so ``v_i`` is
fetched once per client and the output stays resident while the row-tile
axis accumulates. The accumulator is an ``(8, d)`` f32 scratch: each tile's
row groups of 8 are added into its sublanes, and the last step folds them.

Inside a step the lanes are walked in chunks of ``CHUNK`` (a loop), then
the d mod ``CHUNK`` lanes left over, the last 128-lane group narrower where
d is not a multiple of 128. The first pass keeps ``A_t v`` as a
``(tm, 128)`` partial sum and reduces across lanes once; the second reads
and writes each chunk of the accumulator once.

Rows past m in the last tile hold whatever the DMA left there (NaN in
interpret mode), and NaN * 0 is NaN. So ``u`` is zeroed there, and the
second pass selects zero for those rows of A itself before multiplying.

Precision: A is read at its own dtype (the bf16 copy the solver hands the
kernel where the default matmul precision would round it to bf16 anyway)
and widened to f32; v, t, u and the sums stay f32, multiplied and added on
the vector unit. No operand is rounded below A's own dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
SUBLANE = 8
CHUNK = 1024  # lanes per loop iteration: 8 lane groups of 128


def _fold8(p):
    """(tm, w) -> (8, w): the sum of the tile's row groups of 8."""
    out = p[:SUBLANE]
    for r in range(SUBLANE, p.shape[0], SUBLANE):
        out = out + p[r:r + SUBLANE]
    return out


def _kernel(a_ref, w_ref, v_ref, o_ref, acc_ref, *, m: int, mu: float):
    j = pl.program_id(1)
    last = pl.num_programs(1) - 1
    tm, d = a_ref.shape[1], a_ref.shape[2]
    n_chunks, tail = divmod(d, CHUNK)

    def chunked(fn, carry):
        """``carry = fn(offset, width, carry)`` over the lane chunks: a loop
        over the whole ones, then the tail of d mod CHUNK lanes."""
        if n_chunks:
            carry = jax.lax.fori_loop(
                0, n_chunks,
                lambda c, carry: fn(pl.multiple_of(c * CHUNK, CHUNK), CHUNK, carry),
                carry)
        if tail:
            carry = fn(n_chunks * CHUNK, tail, carry)
        return carry

    def a_tile(off, width):
        return a_ref[0, :, pl.ds(off, width)].astype(jnp.float32)

    def v_row(off, width):
        return v_ref[0, :, pl.ds(off, width)]

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # -- pass 1: t = A_t v, kept as (tm, 128) lane-parallel partial sums --
    def dot(off, width, carry):
        full, ragged = carry
        prod = a_tile(off, width) * v_row(off, width)
        whole = width // LANE * LANE
        for g in range(0, whole, LANE):
            full = full + prod[:, g:g + LANE]
        if whole < width:
            ragged = ragged + jnp.sum(prod[:, whole:], axis=1, keepdims=True)
        return full, ragged

    full, ragged = chunked(
        dot, (jnp.zeros((tm, LANE), jnp.float32), jnp.zeros((tm, 1), jnp.float32)))
    t = jnp.sum(full, axis=1, keepdims=True) + ragged  # (tm, 1)
    live = j * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0) < m
    u = jnp.where(live, w_ref[0] * t, 0.0)  # (tm, 1)

    # -- pass 2: acc += A_t^T u, from the same VMEM tile; rows past m of A
    # are zeroed, not just their u (NaN * 0 is NaN) ----------------------
    def accumulate(off, width, carry):
        a = jnp.where(live, a_tile(off, width), 0.0)
        acc_ref[:, pl.ds(off, width)] += _fold8(a * u)
        return carry

    chunked(accumulate, 0)

    # -- last tile: fold the sublanes, scale, add mu v --------------------
    @pl.when(j == last)
    def _():
        def out(off, width, carry):
            s = jnp.sum(acc_ref[:, pl.ds(off, width)], axis=0, keepdims=True)
            o_ref[0, :, pl.ds(off, width)] = (
                s / m + mu * v_row(off, width)).astype(o_ref.dtype)
            return carry

        chunked(out, 0)


def _vmem_bytes(tm: int, d: int, itemsize: int) -> int:
    """VMEM the kernel's buffers take: two A tiles, two each of the v and
    output blocks (one row padded to 8 sublanes), the accumulator and the
    weight blocks (one lane padded to 128)."""
    dp = -(-d // LANE) * LANE
    return (2 * tm * dp * itemsize + 5 * SUBLANE * dp * 4
            + 2 * (-(-tm // SUBLANE) * SUBLANE) * LANE * 4)


def glm_hvp_pallas(
    a: jax.Array,  # (n, m, d) features (bf16 or f32)
    w: jax.Array,  # (n, m) curvature weights
    v: jax.Array,  # (n, d) one vector per client
    *,
    mu: float,
    rows_per_step: int,
    interpret: bool = False,
) -> jax.Array:
    """(n, d): A_i^T (w_i * (A_i v_i)) / m + mu v_i, tm = ``rows_per_step``
    (a multiple of 8) rows of one client a grid step."""
    n, m, d = a.shape
    tm = rows_per_step
    if tm % SUBLANE:
        raise ValueError(f"rows_per_step must be a multiple of 8, got {tm}")
    kernel = functools.partial(_kernel, m=m, mu=mu)
    vec = pl.BlockSpec((1, 1, d), lambda i, j: (i, 0, 0))
    limit = _vmem_bytes(tm, d, a.dtype.itemsize) + (8 << 20)
    out = pl.pallas_call(
        kernel,
        grid=(n, pl.cdiv(m, tm)),
        in_specs=[
            pl.BlockSpec((1, tm, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, tm, 1), lambda i, j: (i, j, 0)),
            vec,
        ],
        out_specs=vec,
        out_shape=jax.ShapeDtypeStruct((n, 1, d), v.dtype),
        scratch_shapes=[pltpu.VMEM((SUBLANE, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=limit,
        ),
        interpret=interpret,
    )(a, w.astype(jnp.float32)[:, :, None], v[:, None, :])
    return out[:, 0, :]
