"""Buffered-asynchronous FedNew (FedBuff-style) — the ``fednew-async``
registry solver plus the per-client-iterate update math the event-driven
runtime (``events/runtime.py``) flushes with.

Semantics (the scan-schedulable approximation of the event-driven mode):
every sampled client computes its eq. 9 direction at the CURRENT iterate and
deposits the codec-decoded reconstruction into a server-side buffer; the
server applies the outer Newton step (eqs. 12-14) only when ``buffer_size``
updates are buffered, weighting each buffered direction by its staleness

    w_i = (1 + s_i) ** (-staleness_power),    s_i = server steps since submit

(exactly 1.0 at s_i = 0, so a same-round flush reproduces the synchronous
weights). The dual update runs with the SAME weights, which keeps the
eq. 13 invariant sum_i lam_i ~ 0 whenever sum_i w_i >= 1: the increment is
rho * (sum w y_i - sum w * y_bar) = 0 by construction of the weighted mean.
Rounds that do not flush leave x / lam / y untouched — the buffer is the
only thing that moves.

``buffer_size=0`` (the default) means "flush every round": the factory
returns **literally** ``fednew.solver`` on the shared config, so the
synchronous degeneracy is bit-exact by construction, not by tolerance.

The event-driven runtime does not call :func:`step` (one traced round is a
schedule, and events have none); it calls :func:`client_update_rows` /
:func:`flush` below, which generalize the same math to per-client dispatch
iterates (each buffered client solved eq. 9 against the x of the server
version it was dispatched at).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
import jax.scipy.linalg as jsl

from repro.core import admm, fednew, hvp
from repro.core.objectives import ClientDataset, Objective, is_param_tree


@dataclasses.dataclass(frozen=True)
class FedNewAsyncConfig:
    """FedNew hparams + the buffered-asynchronous aggregation knobs.

    buffer_size       server step fires once this many client updates are
                      buffered; 0 = flush every round (bit-exact synchronous
                      FedNew — the factory returns ``fednew.solver``).
    staleness_power   p in ``w_i = (1 + s_i)^-p``; 0 disables staleness
                      down-weighting (FedBuff's uniform buffer mean).
    """

    rho: float = 1.0
    alpha: float = 1.0
    hessian_period: int = 1
    bits: Optional[int] = None
    backend: str = "auto"
    solve_backend: Optional[str] = None
    quant_backend: Optional[str] = None
    hessian_repr: str = "dense"
    cg_iters: int = 32
    cg_tol: float = 0.0
    codec: Union[None, str, Mapping[str, Any]] = None
    buffer_size: int = 0
    staleness_power: float = 0.5

    def __post_init__(self):
        if self.buffer_size < 0:
            raise ValueError(
                f"buffer_size must be >= 0 (0 = flush every round), got "
                f"{self.buffer_size}"
            )
        if self.staleness_power < 0:
            raise ValueError(
                f"staleness_power must be >= 0, got {self.staleness_power}"
            )
        # Shared-field validation is fednew's: build (and discard) the inner
        # config so bad values fail here with fednew's own messages.
        self.fednew_config()

    def fednew_config(self) -> fednew.FedNewConfig:
        """The synchronous config this one embeds (shared fields only)."""
        return fednew.FedNewConfig(
            rho=self.rho,
            alpha=self.alpha,
            hessian_period=self.hessian_period,
            bits=self.bits,
            backend=self.backend,
            solve_backend=self.solve_backend,
            quant_backend=self.quant_backend,
            hessian_repr=self.hessian_repr,
            cg_iters=self.cg_iters,
            cg_tol=self.cg_tol,
            codec=self.codec,
        )


class FedBuffState(NamedTuple):
    x: jax.Array
    y: jax.Array  # last FLUSHED direction (rhs anchor, like fednew's y)
    lam: jax.Array  # (n, d) duals
    curv: jax.Array  # per-client curvature cache (fednew layouts)
    comm: jax.Array  # (n, w) per-client codec state
    pending: jax.Array  # (n, d) buffered decoded directions
    pending_mask: jax.Array  # (n,) {0,1} "client has an update buffered"
    submit_step: jax.Array  # (n,) int32 server step each buffer entry saw
    key: jax.Array
    step: jax.Array
    grad_x: jax.Array  # (n, d) per-client local gradients at x
    curv_x: jax.Array  # per-client GLM weights at x (fednew layouts)


class AsyncStepMetrics(NamedTuple):
    loss: jax.Array
    grad_norm: jax.Array
    uplink_bits_per_client: jax.Array
    dual_sum_residual: jax.Array
    direction_norm: jax.Array
    buffered: jax.Array  # buffer occupancy AFTER this round (0 post-flush)
    flushed: jax.Array  # 1.0 when this round applied a server step


def staleness_weights(staleness, power: float):
    """``(1 + s)^-p`` per buffered update — exactly 1.0 at s = 0."""
    s = staleness.astype(jnp.float32)
    return (1.0 + s) ** (-power)


def init(
    obj: Objective,
    data: ClientDataset,
    cfg: FedNewAsyncConfig,
    key: jax.Array,
    x0=None,
) -> FedBuffState:
    if x0 is not None and is_param_tree(x0):
        raise ValueError(
            "fednew-async carries flat (n, d) buffer state only; pytree "
            "(model) objectives run the synchronous fednew/fagh paths "
            "(async LM fine-tuning is a ROADMAP follow-up)"
        )
    base = fednew.init(obj, data, cfg.fednew_config(), key, x0)
    n = base.lam.shape[0]
    return FedBuffState(
        x=base.x,
        y=base.y,
        lam=base.lam,
        curv=base.curv,
        comm=base.comm,
        pending=jnp.zeros_like(base.lam),
        pending_mask=jnp.zeros((n,), jnp.float32),
        submit_step=jnp.zeros((n,), jnp.int32),
        key=base.key,
        step=base.step,
        grad_x=base.grad_x,
        curv_x=base.curv_x,
    )


def step(
    state: FedBuffState,
    obj: Objective,
    data: ClientDataset,
    cfg: FedNewAsyncConfig,
    *,
    axis_name: Optional[str] = None,
    n_global_clients: Optional[int] = None,
    mask: Optional[jax.Array] = None,
):
    """One buffered round: sampled clients submit eq. 9 directions into the
    buffer; the server flushes (staleness-weighted eqs. 12-14) iff the
    buffer holds >= ``buffer_size`` updates afterwards. An empty round
    (nobody sampled, buffer below K) is a frozen no-op on every carried
    field but the clocks — the conformance freeze contract."""
    fcfg = cfg.fednew_config()
    if axis_name is not None:
        obj = obj.with_axis(axis_name)
    n_local = state.lam.shape[0]

    # -- client submit phase: fednew's client half (eq. 9 and the codec) ----
    curv, _, y_i_tx, comm_state, key, _ = fednew.client_half(
        state, obj, data, fcfg, axis_name=axis_name,
        n_global_clients=n_global_clients, mask=mask,
    )

    # -- deposit into the buffer (re-submitting overwrites the stale entry) --
    submit = (
        jnp.ones((n_local,), jnp.float32) if mask is None
        else (mask > 0).astype(jnp.float32)
    )
    pending = admm.mask_client_rows(submit, y_i_tx, state.pending)
    pending_mask = jnp.maximum(state.pending_mask, submit)
    submit_step = jnp.where(
        submit > 0, jnp.broadcast_to(state.step, (n_local,)), state.submit_step
    ).astype(jnp.int32)

    count = jnp.sum(pending_mask)
    if axis_name is not None:
        count = jax.lax.psum(count, axis_name)
    do_flush = count >= (cfg.buffer_size - 0.5)

    # -- flush: staleness-weighted eqs. 13 + 12 + 14 over the buffer --------
    def flushed():
        stale = (state.step - submit_step).astype(jnp.float32)
        w = pending_mask * staleness_weights(stale, cfg.staleness_power)
        y_bar = admm.tree_mean_clients(pending, axis_name, weights=w)
        lam = admm.dual_update(
            state.lam, pending, jnp.broadcast_to(y_bar, pending.shape),
            fcfg.rho, weights=w,
        )
        return (
            state.x - y_bar,  # eq. 14 with the buffered direction
            y_bar,
            lam,
            jnp.zeros_like(pending),
            jnp.zeros_like(pending_mask),
            jnp.zeros_like(submit_step),
            y_bar,
        )

    def held():
        return (
            state.x, state.y, state.lam, pending, pending_mask, submit_step,
            jnp.zeros_like(state.y),
        )

    x, y, lam, pending, pending_mask, submit_step, applied = jax.lax.cond(
        do_flush, flushed, held
    )

    # -- exact uplink accounting (submission is the transmission) -----------
    bits = fcfg.build_codec().payload_bits_metric(
        data.dim, fednew.word_bits(y_i_tx), state.step
    )
    if mask is not None:
        from repro.core import participation

        bits = participation.masked_bits_metric(bits, mask, axis_name)

    # The gradients and GLM weights at the (possibly unmoved) iterate serve
    # the metrics and the next round's client half, as in fednew.step.
    grad_x, curv_x = fednew._carry(obj, x, data, fednew._glm_split(obj, fcfg))
    new_state = FedBuffState(
        x=x, y=y, lam=lam, curv=curv, comm=comm_state, pending=pending,
        pending_mask=pending_mask, submit_step=submit_step, key=key,
        step=state.step + 1, grad_x=grad_x, curv_x=curv_x,
    )
    occupancy = jnp.sum(pending_mask)
    if axis_name is not None:
        occupancy = jax.lax.psum(occupancy, axis_name)
    metrics = AsyncStepMetrics(
        loss=obj.global_loss(x, data),
        grad_norm=jnp.linalg.norm(obj._agg(grad_x)),
        uplink_bits_per_client=bits,
        dual_sum_residual=admm.dual_sum_residual(lam, axis_name),
        direction_norm=jnp.linalg.norm(applied),
        buffered=occupancy,
        flushed=do_flush.astype(jnp.float32),
    )
    return new_state, metrics


def solver(cfg: FedNewAsyncConfig):
    """``fednew-async`` as an engine :class:`FederatedSolver`.

    ``buffer_size=0`` returns **the fednew solver itself** on the shared
    config — flush-every-round IS synchronous FedNew, and returning the same
    functions (not a re-implementation) makes the degeneracy bit-exact by
    construction (pinned in tests/test_events.py)."""
    from repro.core import engine

    if cfg.buffer_size == 0:
        inner = fednew.solver(cfg.fednew_config())
        return dataclasses.replace(inner, name="fednew-async(sync)")
    return engine.FederatedSolver(
        name=f"fednew-async(K={cfg.buffer_size})",
        init=lambda obj, data, key, x0=None: init(obj, data, cfg, key, x0),
        step=lambda state, obj, data, **axis_kw: step(
            state, obj, data, cfg, **axis_kw
        ),
        client_fields=(
            "lam", "curv", "comm", "pending", "pending_mask", "submit_step",
            "grad_x", "curv_x",
        ),
    )


def ledger(cfg: FedNewAsyncConfig):
    """Bit-for-bit fednew accounting: a sampled client uplinks its codec
    payload in the round it SUBMITS (whether or not that round flushes), and
    downlinks the ``word*d`` iterate when dispatched."""
    return fednew.ledger(cfg.fednew_config())


# ---------------------------------------------------------------------------
# per-client-iterate update math (the event-driven runtime's flush kernel)
# ---------------------------------------------------------------------------


def _rowwise(oracle, x_rows, data, *extra):
    """Apply a per-client oracle with PER-CLIENT iterates: each client's row
    of ``x_rows`` is its own evaluation point (async clients were dispatched
    at different server versions). Works for any Objective — the client axis
    is peeled one row at a time under vmap."""
    expanded = jax.tree.map(lambda a: a[:, None], data)

    def one(xr, dr, *er):
        return oracle(xr, dr, *er)[0]

    return jax.vmap(one)(x_rows, expanded, *extra)


def client_update_rows(
    cfg: FedNewAsyncConfig,
    obj: Objective,
    data: ClientDataset,
    x_rows: jax.Array,
    y_rows: jax.Array,
    lam: jax.Array,
    comm_state: jax.Array,
    keys: Optional[jax.Array],
    step,
):
    """Eq. 9 + uplink codec for a batch of clients whose dispatch iterates
    differ per row: client i anchors its curvature at ``x_rows[i]`` (the
    stateless re-derivation contract — anchor == the iterate of the server
    version it was dispatched at) and uses ``y_rows[i]`` as the eq. 9 rhs
    anchor. Returns ``(y_i_tx, new_comm_state)``."""
    fcfg = cfg.fednew_config()
    g_i = _rowwise(obj.local_grad, x_rows, data)
    rhs = admm.admm_rhs(g_i, lam, y_rows, fcfg.rho)
    if fcfg.matfree:
        y_i = hvp.cg_solve_clients(
            lambda v: obj.local_hvp(x_rows, data, v),
            rhs,
            damping=fcfg.damping,
            iters=fcfg.cg_iters,
            tol=fcfg.cg_tol,
        ).x
    else:
        H = _rowwise(obj.local_hessian, x_rows, data)
        damped = H + fcfg.damping * jnp.eye(H.shape[-1], dtype=H.dtype)
        L = jax.vmap(lambda M: jsl.cholesky(M, lower=True))(damped)
        y_i = jax.vmap(lambda Lf, r: jsl.cho_solve((Lf, True), r))(L, rhs)
    codec = fcfg.build_codec()
    wire = codec.encode(keys, y_i, comm_state, step)
    y_i_tx = codec.decode(wire, comm_state, step)
    new_comm = codec.update_state(y_i_tx, y_i, comm_state, step)
    return y_i_tx, new_comm


def flush(
    cfg: FedNewAsyncConfig,
    x: jax.Array,
    lam: jax.Array,
    y_i_tx: jax.Array,
    staleness: jax.Array,
):
    """The server's buffered step over K decoded directions: staleness
    weights, weighted eq. 13 mean, weighted eq. 12 duals, eq. 14 iterate.
    Returns ``(new_x, y_bar, new_lam)``."""
    w = staleness_weights(staleness, cfg.staleness_power)
    y_bar = admm.tree_mean_clients(y_i_tx, None, weights=w)
    lam = admm.dual_update(
        lam, y_i_tx, jnp.broadcast_to(y_bar, y_i_tx.shape), cfg.rho,
        weights=w,
    )
    return x - y_bar, y_bar, lam
