"""Client objectives: the pytree-native oracle contract of the solver zoo.

Two parameter layouts share ONE :class:`Objective` interface:

  * the paper-faithful flat layout — ``x`` is a single ``(d,)`` array,
    per-client quantities are ``(n, d)`` / ``(n, d, d)`` stacks, oracles are
    closed-form (logreg eq. 31-32, quadratics);
  * arbitrary param *pytrees* — ``x`` is a model's parameter tree (e.g.
    ``models.lm.init_params``), per-client quantities carry a leading client
    axis on every leaf, and the oracles come from autodiff over a loss
    function (:func:`from_loss_fn`): gradients by ``jax.grad``, HVPs by
    ``jax.jvp``-over-``grad`` (Pearlmutter), both ``vmap``-batched over the
    client axis.

The flat layout is literally the single-leaf special case — every consumer
(``admm``, ``hvp.cg_solve_clients``, the engine) is tree-generic, and the
solvers branch on :func:`is_param_tree` so the flat code paths (and their
bit-exactness pins) are untouched.

The paper evaluates regularized logistic regression (eq. 31-32):

    f(x) = (1/n) sum_i f_i(x),
    f_i(x) = (1/m) sum_j log(1 + exp(-b_ij a_ij^T x)) + (mu/2) ||x||^2

The l2 regularizer is folded into every client's local loss so that the
global objective is exactly the mean of the local ones (the consensus
reformulation in eq. 6 requires separability).

All client-level quantities carry a leading client axis ``n`` and are
produced by ``vmap`` so the same code runs single-host or sharded (the
distributed path shards the client axis of ``ClientDataset``).

A quadratic objective is provided as a second family: FedNew on a quadratic
is *exact* Newton after the inner ADMM converges, which gives tests a
closed-form optimum to compare against.
``logistic_regression_autodiff`` derives the logreg oracles by autodiff
instead of the closed forms — the executable cross-check that the two
derivations agree to machine precision (pinned in tests).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp

# Treedef of a bare leaf: the flat (d,)-vector layout. Comparing treedefs is
# trace-safe (an isinstance check on jax.Array would also match tracers of
# pytree leaves and is wrong under vmap/scan).
_LEAF_TREEDEF = jax.tree.structure(0)


def is_param_tree(x) -> bool:
    """True when ``x`` is a structured parameter pytree rather than the flat
    paper-scale ``(d,)`` vector (a single bare array)."""
    return jax.tree.structure(x) != _LEAF_TREEDEF


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ClientDataset:
    """Per-client supervised data: features (n, m, d), labels (n, m) in {-1,+1}."""

    features: jax.Array
    labels: jax.Array

    @property
    def n_clients(self) -> int:
        return self.features.shape[0]

    @property
    def dim(self) -> int:
        return self.features.shape[-1]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class TokenDataset:
    """Per-client LM training data: a batch pytree (``data/tokens.py``
    layout — tokens/targets/loss_mask plus any modality stubs) whose leaves
    all carry a leading client axis. The model-objective counterpart of
    :class:`ClientDataset`; it has no ``dim`` — the parameter dimension
    belongs to the param pytree, not the data."""

    batch: Any

    @property
    def n_clients(self) -> int:
        return jax.tree.leaves(self.batch)[0].shape[0]


@dataclasses.dataclass(frozen=True)
class Objective:
    """Bundle of per-client oracles. Every fn maps over the client axis.

    Flat layout (x a (d,) array) / pytree layout (x a param pytree):

    local_loss(x, data)    -> (n,)
    local_grad(x, data)    -> (n, d)       / per-leaf (n, ...) pytree
    local_hessian(x, data) -> (n, d, d)    [optional — flat layout only]
    local_hvp(x, data, v)  -> (n, d)       / per-leaf (n, ...) pytree
                                           [optional]
    local_curvature(x, data) -> (n, m)     [optional — GLM objectives]

    ``local_hvp`` is the matrix-free counterpart of ``local_hessian``: it
    applies every client's Hessian to a per-client vector batch without ever
    materializing a ``(d, d)`` block. Unlike the other oracles it takes a
    *per-client* anchor batch ``x: (n, d)`` (pytree layout: every leaf gains
    a leading client axis) — FedNew's Hessian-refresh rate means
    offline/stale clients keep curvature anchored at an older iterate, so
    each client may differentiate at its own point. Solvers that need it
    (``hessian_repr="matfree"``, fagh) check :attr:`has_hvp` and fail loudly
    when an objective doesn't provide one.

    ``local_curvature`` is the split a generalized linear model's HVP has
    (flat layout only): every client's per-row weights ``w`` at one point
    ``x``, such that ``local_hvp(anchors, data, v)_i == A_i^T (w_i * (A_i
    v_i)) / m + l2 * v_i`` where every anchor is ``x``, with ``A =
    data.features`` of ``m`` rows a client. It takes ``x`` as
    ``local_grad`` does, so its margins ``A x`` are the product the loss
    and the gradient at ``x`` form, and a program that evaluates all three
    reads the features once. A solver that finds the split may cache each
    client's weights at its anchor and apply every client's Hessian in one
    read of the features (the ``glm_hvp`` kernel) instead of calling
    ``local_hvp``.

    ``local_hessian`` is optional: autodiff model objectives
    (:func:`from_loss_fn`) cannot materialize (d, d) blocks, so solvers on
    the dense path check :attr:`has_hessian` first (``repro.api.build``
    raises the capability error with the spec field and model named).

    ``axis_name`` makes the ``global_*`` aggregates mesh-aware: inside a
    ``shard_map`` manual region where ``data`` holds only this shard's
    clients, the local client mean is followed by a ``pmean`` across the
    client mesh axis (shards hold equal client counts, so mean-of-means is
    exact). Outside shard_map leave it None (the default) and the leading
    client axis is reduced locally. Use :meth:`with_axis` to derive the
    shard-aware view the engine passes into the manual region.
    """

    local_loss: Callable
    local_grad: Callable
    local_hessian: Callable | None = None
    local_hvp: Callable | None = None
    axis_name: str | None = None
    local_curvature: Callable | None = None
    l2: float = 0.0  # the ridge term of the local_curvature split

    @property
    def has_hvp(self) -> bool:
        """True when the matrix-free ``local_hvp`` oracle is available."""
        return self.local_hvp is not None

    @property
    def has_curvature(self) -> bool:
        """True when the GLM split ``local_curvature`` is available."""
        return self.local_curvature is not None

    @property
    def has_hessian(self) -> bool:
        """True when the dense ``local_hessian`` oracle is available."""
        return self.local_hessian is not None

    def with_axis(self, axis_name: str | None) -> "Objective":
        """Shard-aware view of the same oracles (see class docstring)."""
        return dataclasses.replace(self, axis_name=axis_name)

    def _agg(self, v, weights: jax.Array | None = None):
        if weights is None:
            # tree.map over a bare array applies the fn directly, so the flat
            # (single-array) layout lowers exactly as it always did.
            v = jax.tree.map(lambda l: jnp.mean(l, axis=0), v)
            if self.axis_name is not None:
                v = jax.tree.map(
                    lambda l: jax.lax.pmean(l, self.axis_name), v
                )
            return v
        # Weighted (participation-masked) aggregate: one definition of the
        # masked mean for the whole repo — solver aggregation (eq. 13) and
        # the objective oracles must never drift apart.
        from repro.core import admm

        return admm.tree_mean_clients(v, self.axis_name, weights=weights)

    def global_loss(self, x, data: ClientDataset, weights=None) -> jax.Array:
        return self._agg(self.local_loss(x, data), weights)

    def global_grad(self, x, data: ClientDataset, weights=None) -> jax.Array:
        return self._agg(self.local_grad(x, data), weights)

    def global_hessian(self, x, data: ClientDataset, weights=None) -> jax.Array:
        if not self.has_hessian:
            raise ValueError(
                "this objective has no local_hessian oracle (autodiff model "
                "objectives never materialize (d, d) blocks); use the "
                "matrix-free local_hvp path instead"
            )
        return self._agg(self.local_hessian(x, data), weights)


# ---------------------------------------------------------------------------
# Regularized logistic regression (paper eq. 31-32)
# ---------------------------------------------------------------------------


def _logreg_loss_1(x, A, b, mu):
    z = b * (A @ x)
    # log(1 + exp(-z)) computed stably.
    return jnp.mean(jnp.logaddexp(0.0, -z)) + 0.5 * mu * jnp.vdot(x, x)


def _logreg_grad_1(x, A, b, mu):
    z = b * (A @ x)
    # d/dz log(1+e^{-z}) = -sigmoid(-z)
    w = -jax.nn.sigmoid(-z) * b  # (m,)
    return A.T @ w / A.shape[0] + mu * x


def _logreg_hessian_1(x, A, b, mu):
    z = b * (A @ x)
    s = jax.nn.sigmoid(z)
    w = s * (1.0 - s)  # (m,) ; b^2 == 1
    H = (A.T * w) @ A / A.shape[0]
    return H + mu * jnp.eye(A.shape[1], dtype=A.dtype)


def _logreg_curvature_1(x, A, b):
    """The diagonal D of H(x) = A^T D A / m + mu I: s(1 - s) per row."""
    z = b * (A @ x)
    s = jax.nn.sigmoid(z)
    return s * (1.0 - s)  # (m,) ; b^2 == 1


def _logreg_hvp_1(x, v, A, b, mu):
    """H(x) v = A^T (D (A v)) / m + mu v — two matvecs and a diagonal scale,
    O(m d) time and memory; the (d, d) Hessian never exists."""
    w = _logreg_curvature_1(x, A, b)
    return A.T @ (w * (A @ v)) / A.shape[0] + mu * v


def logistic_regression(mu: float = 1e-3) -> Objective:
    loss = jax.vmap(partial(_logreg_loss_1, mu=mu), in_axes=(None, 0, 0))
    grad = jax.vmap(partial(_logreg_grad_1, mu=mu), in_axes=(None, 0, 0))
    hess = jax.vmap(partial(_logreg_hessian_1, mu=mu), in_axes=(None, 0, 0))
    # hvp maps per-client anchors AND per-client vectors (see Objective doc)
    hvp = jax.vmap(partial(_logreg_hvp_1, mu=mu), in_axes=(0, 0, 0, 0))
    curvature = jax.vmap(_logreg_curvature_1, in_axes=(None, 0, 0))
    return Objective(
        local_loss=lambda x, d: loss(x, d.features, d.labels),
        local_grad=lambda x, d: grad(x, d.features, d.labels),
        local_hessian=lambda x, d: hess(x, d.features, d.labels),
        local_hvp=lambda x, d, v: hvp(x, v, d.features, d.labels),
        local_curvature=lambda x, d: curvature(x, d.features, d.labels),
        l2=mu,
    )


# ---------------------------------------------------------------------------
# Autodiff oracles over arbitrary param pytrees
# ---------------------------------------------------------------------------


def from_loss_fn(
    loss_fn: Callable,
    *,
    hvp: str = "exact",
    predict_fn: Callable | None = None,
    pred_loss_fn: Callable | None = None,
) -> Objective:
    """Autodiff oracle bundle for an arbitrary param pytree.

    ``loss_fn(params, batch) -> scalar`` is ONE client's loss on ONE
    client's batch (a pytree slice without the client axis — e.g.
    ``lambda p, b: models.lm.train_loss(p, cfg, b)``). The oracles ``vmap``
    it over the leading client axis of ``data.batch`` (:class:`TokenDataset`
    or any container exposing a ``batch`` pytree):

      local_loss(x, data)         -> (n,)
      local_grad(x, data)         -> params tree, per-leaf leading n
      local_hvp(anchors, data, v) -> params tree, per-leaf leading n

    ``hvp`` selects the curvature oracle:

      * ``"exact"`` (default) — the Pearlmutter product, ``jax.jvp`` over
        ``jax.grad`` (forward-over-reverse): the true Hessian, which for a
        non-convex backbone is indefinite.
      * ``"gauss_newton"`` — the generalized Gauss-Newton product through a
        declared cut ``loss = pred_loss_fn(params, predict_fn(params, b), b)``:
        ``J^T H_pred J v`` where ``J`` is the backbone Jacobian at the cut and
        ``H_pred`` the Hessian of the (convex) head in the prediction. PSD by
        construction whenever the head is convex in the prediction — FedNew's
        regularized subproblem ``(H + (alpha+rho)I)^{-1}`` stays SPD at any
        iterate (PSD pinned in tests/test_lm_workload.py). Requires both
        ``predict_fn(params, batch) -> z`` (any pytree of predictions) and
        ``pred_loss_fn(params, z, batch) -> scalar`` (``params`` enters only
        through pieces GN treats as constant, e.g. a tied readout).

    ``anchors`` is a *per-client* param pytree (leading client axis on every
    leaf): the Hessian-refresh staleness contract of the flat layout,
    verbatim.

    No ``local_hessian`` is provided — a (d, d) block cannot exist at model
    scale; dense-path solvers must check :attr:`Objective.has_hessian`.
    """
    if hvp not in ("exact", "gauss_newton"):
        raise ValueError(
            f"hvp must be 'exact' or 'gauss_newton', got {hvp!r}"
        )
    if hvp == "gauss_newton" and (predict_fn is None or pred_loss_fn is None):
        raise ValueError(
            "hvp='gauss_newton' requires both predict_fn (the backbone cut) "
            "and pred_loss_fn (the convex head)"
        )
    grad1 = jax.grad(loss_fn)

    def local_loss(x, data):
        return jax.vmap(lambda b: loss_fn(x, b))(data.batch)

    def local_grad(x, data):
        return jax.vmap(lambda b: grad1(x, b))(data.batch)

    if hvp == "gauss_newton":

        def one_hvp(anchor, b, vi):
            f = lambda p: predict_fn(p, b)
            # Forward: predictions z and the Jacobian push-forward J v.
            z, Jv = jax.jvp(f, (anchor,), (vi,))
            # Head curvature in the prediction: H_pred (J v), via jvp of
            # the head's prediction-gradient (params held at the anchor).
            gz = jax.grad(lambda zz: pred_loss_fn(anchor, zz, b))
            _, HJv = jax.jvp(gz, (z,), (Jv,))
            # Pull back through the backbone: J^T (H_pred J v).
            _, pullback = jax.vjp(f, anchor)
            return pullback(HJv)[0]

    else:

        def one_hvp(anchor, b, vi):
            _, tangent = jax.jvp(lambda p: grad1(p, b), (anchor,), (vi,))
            return tangent

    def local_hvp(anchors, data, v):
        return jax.vmap(one_hvp)(anchors, data.batch, v)

    return Objective(
        local_loss=local_loss, local_grad=local_grad, local_hvp=local_hvp
    )


def logistic_regression_autodiff(mu: float = 1e-3) -> Objective:
    """The logreg oracles derived by autodiff — the single-(implicit-)leaf
    cross-check of :func:`from_loss_fn`'s derivation strategy against
    :func:`logistic_regression`'s closed forms (grad by ``jax.grad``, HVP by
    jvp-over-grad, Hessian by ``jax.hessian``). Agreement to machine
    precision is pinned in tests/test_lm_workload.py."""
    loss1 = partial(_logreg_loss_1, mu=mu)
    grad1 = jax.grad(loss1)

    def hvp1(x, v, A, b):
        _, tangent = jax.jvp(lambda p: grad1(p, A, b), (x,), (v,))
        return tangent

    loss = jax.vmap(loss1, in_axes=(None, 0, 0))
    grad = jax.vmap(grad1, in_axes=(None, 0, 0))
    hess = jax.vmap(jax.hessian(loss1), in_axes=(None, 0, 0))
    hvp = jax.vmap(hvp1, in_axes=(0, 0, 0, 0))
    return Objective(
        local_loss=lambda x, d: loss(x, d.features, d.labels),
        local_grad=lambda x, d: grad(x, d.features, d.labels),
        local_hessian=lambda x, d: hess(x, d.features, d.labels),
        local_hvp=lambda x, d, v: hvp(x, v, d.features, d.labels),
    )


# ---------------------------------------------------------------------------
# Quadratic objective: f_i(x) = 1/2 x^T P_i x - q_i^T x
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class QuadraticData:
    """P: (n, d, d) SPD, q: (n, d). Stored in ClientDataset fields:
    features := P, labels := q."""


def quadratic() -> Objective:
    def loss(x, d):
        P, q = d.features, d.labels
        return 0.5 * jnp.einsum("i,nij,j->n", x, P, x) - q @ x

    def grad(x, d):
        P, q = d.features, d.labels
        return jnp.einsum("nij,j->ni", P, x) - q

    def hess(x, d):
        return d.features

    def hvp(x, d, v):
        # The quadratic's Hessian IS the stored P_i, so "matrix-free" here
        # just means applying it without the dense-solve factorization path.
        return jnp.einsum("nij,nj->ni", d.features, v)

    return Objective(
        local_loss=loss, local_grad=grad, local_hessian=hess, local_hvp=hvp
    )


def quadratic_optimum(data: ClientDataset) -> jax.Array:
    """Closed-form argmin of the mean quadratic."""
    P = jnp.mean(data.features, axis=0)
    q = jnp.mean(data.labels, axis=0)
    return jnp.linalg.solve(P, q)
