"""jnp oracle for the glm_hvp kernel: the two-matvec expression of
``objectives._logreg_hvp_1`` with the weights given."""

from __future__ import annotations

import jax


def glm_hvp_ref(a, w, v, *, mu: float):
    """(n, m, d), (n, m), (n, d) -> A_i^T (w_i * (A_i v_i)) / m + mu v_i."""
    def one(A, wi, vi):
        return A.T @ (wi * (A @ vi)) / A.shape[0] + mu * vi

    return jax.vmap(one)(a, w, v)
