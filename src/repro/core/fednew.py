"""FedNew and Q-FedNew (paper Algorithm 1 + Sec. 5), faithful implementation.

State layout mirrors Algorithm 1:
  x      (d,)      global model at the PS (broadcast each round)
  y      (d,)      previous global direction y^{k-1}
  lam    (n, d)    per-client dual variables
  curv   per-client curvature cache; representation depends on the config:
           hessian_repr="dense"   (n, d, d) cached Cholesky factors of
                                  (H_i + (alpha+rho) I) (reference solve),
                                  or the raw H_i (Pallas CG kernel)
           hessian_repr="matfree" (n, d) per-client Hessian *anchor points* —
                                  the iterate each client's curvature is
                                  evaluated at; no d x d array ever exists
  comm   (n, w)    per-client compression-codec state (``repro.comm``): the
                   previously-quantized vector for stoch_quant (Q-FedNew's
                   ŷ, historically the ``y_hat`` field), the error-feedback
                   residual for topk, width 0 for the identity codec

Pytree layout: when ``init`` receives a param *pytree* as ``x0`` (a model
objective — ``objectives.from_loss_fn``), every field generalizes leaf-wise:
x/y are param trees, lam/curv stack a leading client axis onto every leaf
(curv holds per-client anchor trees; matfree is mandatory), comm holds one
``(n, width)`` codec-state array per leaf and the uplink applies the codec
per (client, leaf) via ``comm.encode_decode_tree``. The flat path below is
dispatched away from (``objectives.is_param_tree``) and stays bit-exact.

The Hessian refresh rate r from the experiments maps to ``hessian_period``:
r=1 -> 1, r=0.1 -> 10, r=0 -> 0 (never refresh; factor from x^0 is kept —
the computation-efficient "zeroth Hessian" variant, one factorization ever).

``hessian_repr`` selects how the eq. 9 client sub-problem
``(H_i + (alpha+rho) I) y_i = rhs_i`` is solved:

  "dense"   (default) materialize H_i once per refresh and cache a Cholesky
            factor (or the raw Hessian on the Pallas kernel path) — exact,
            O(n d^2) memory / O(n d^3) refresh compute; the paper-scale path,
            bit-identical to builds that predate ``hessian_repr``.
  "matfree" never build H_i: solve with damped conjugate gradients
            (``hvp.cg_solve_clients``) where each matvec is the objective's
            closed-form batched HVP (``Objective.local_hvp``) at the cached
            per-client anchor, or, for a GLM objective on the Pallas solve
            backend, the ``glm_hvp`` kernel (one read of the features per
            matvec). O(n d) state, O(cg_iters n m d) compute — the
            only path that survives d ~ 1e5+. ``cg_iters``/``cg_tol`` bound
            the inner iteration; run to convergence (tol ~ 1e-7, generous
            iters) the trajectory matches "dense" to solver tolerance.

What crosses the uplink is owned by a ``repro.comm`` codec: ``codec=None``
with ``bits=None`` is the identity codec (plain FedNew), ``bits=b`` is sugar
for the ``stoch_quant`` codec (Q-FedNew — the historical path, bit for bit),
and ``codec={"name": "topk", "fraction": 0.05}`` (or any registered codec
spec) swaps the compressor without touching the ADMM math. Each round the
step encodes the per-client directions, aggregates the *decoded* (PS-side)
reconstructions in eq. 13, and carries the codec's per-client state in
``FedNewState.comm``.

Each layer of a round runs under a ``jax.named_scope``, which reaches the
``op_name`` of its ops in the optimized HLO and changes nothing else:
``fednew.hessian`` (the curvature refresh), ``fednew.grad`` (local
gradients, the eq. 9 right side), ``fednew.eq9`` (the client solve),
``fednew.codec`` (uplink encode, decode, codec state), ``fednew.aggregate``
(eqs. 13, 12, 14 and the bit metric) and ``fednew.eval`` (StepMetrics).

Communication accounting follows the paper: the metric of record is uplink
bits per client per round — w·d for FedNew (w = word bits of the transmitted
dtype, 32 for float32), ``bits``·d + 32 for Q-FedNew, the codec's exact
``payload_bits`` in general. FedNew never transmits Hessians, so refresh
rounds cost no extra bits. Counts are exact Python ints lowered via
``quantization.payload_bits_array`` (no int32 wraparound at LM scale).

Both hot loops — the eq. 9 client solve (dense, or matfree HVPs) and the
eqs. 25-30 quantizer — are reached through ``repro.kernels.dispatch``:
``FedNewConfig.backend`` selects ``auto`` (compiled Pallas on TPU, jnp
reference elsewhere), ``pallas`` (kernel everywhere; interpreter off-TPU),
or ``reference``, with per-loop overrides ``solve_backend``/
``quant_backend``. The legacy ``use_kernel`` flag remains as an alias for
``solve_backend="pallas"``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
import jax.scipy.linalg as jsl

from repro import comm
from repro.core import admm, hvp
from repro.core.objectives import ClientDataset, Objective, is_param_tree
from repro.core.quantization import word_bits
from repro.kernels import dispatch


HESSIAN_REPRS = ("dense", "matfree")


@dataclasses.dataclass(frozen=True)
class FedNewConfig:
    rho: float = 1.0
    alpha: float = 1.0
    hessian_period: int = 1  # 0 => never refresh (r = 0)
    bits: Optional[int] = None  # sugar for codec={"name":"stoch_quant","bits":b}
    use_kernel: bool = False  # legacy alias for solve_backend="pallas"
    backend: str = "auto"  # "auto" | "pallas" | "reference" (both hot loops)
    solve_backend: Optional[str] = None  # per-loop override, eq. 9
    quant_backend: Optional[str] = None  # per-loop override, eqs. 25-30
    hessian_repr: str = "dense"  # "dense" | "matfree" (see module docstring)
    cg_iters: int = 32  # matfree: CG iterations for the eq. 9 solve
    cg_tol: float = 0.0  # matfree: per-client residual-norm early exit (0 = off)
    codec: Union[None, str, Mapping[str, Any]] = None  # repro.comm codec spec
    # Static trace-time flag: True extends the step's metrics with the
    # ``diag_*`` catalogue (ADMM residuals, CG iterations-to-tolerance,
    # codec error, anchor staleness — see docs/telemetry.md), computed
    # read-only from in-step intermediates. False (default) is the
    # byte-identical historical lowering.
    diagnostics: bool = False

    def __post_init__(self):
        for b in (self.backend, self.solve_backend, self.quant_backend):
            if b is not None:
                dispatch.validate_backend(b)
        if self.codec is not None:
            if self.bits is not None:
                raise ValueError(
                    "bits= is sugar for the stoch_quant codec; set either "
                    "bits or codec, not both"
                )
            object.__setattr__(self, "codec", comm.normalize_spec(self.codec))
        # Build (and discard) the codec so bad specs fail here, at config
        # construction — the same place every other hparam is validated.
        self.build_codec()
        if self.hessian_repr not in HESSIAN_REPRS:
            raise ValueError(
                f"unknown hessian_repr {self.hessian_repr!r}; "
                f"expected one of {HESSIAN_REPRS}"
            )
        if self.cg_iters < 1:
            raise ValueError(f"cg_iters must be >= 1, got {self.cg_iters}")
        if self.cg_tol < 0:
            raise ValueError(f"cg_tol must be >= 0, got {self.cg_tol}")
        if self.hessian_repr == "matfree" and self.use_kernel:
            raise ValueError(
                "hessian_repr='matfree' solves eq. 9 with CG on HVPs and "
                "never builds the (n, d, d) Hessians the Pallas client_solve "
                "kernel consumes; drop use_kernel (solve_backend='pallas' "
                "routes the matfree HVPs to the glm_hvp kernel)"
            )

    @property
    def damping(self) -> float:
        return self.alpha + self.rho

    @property
    def resolved_solve_backend(self) -> str:
        if self.solve_backend is not None:
            return self.solve_backend
        if self.backend == "auto" and self.use_kernel:
            return "pallas"
        return self.backend

    @property
    def resolved_quant_backend(self) -> str:
        return self.quant_backend if self.quant_backend is not None else self.backend

    @property
    def matfree(self) -> bool:
        return self.hessian_repr == "matfree"

    @property
    def codec_spec(self) -> Mapping[str, Any]:
        """Canonical ``repro.comm`` codec spec this config resolves to."""
        if self.codec is not None:
            return dict(self.codec)
        if self.bits is not None:
            return {"name": "stoch_quant", "bits": self.bits}
        return {"name": "identity"}

    def build_codec(self) -> comm.Codec:
        return comm.build_codec(
            self.codec_spec, backend=self.resolved_quant_backend
        )

    @property
    def solve_uses_kernel(self) -> bool:
        """Static (trace-time) routing decision for the dense eq. 9 solve;
        also decides whether state.curv caches Cholesky factors (reference)
        or raw Hessians (the CG kernel applies the damping itself). Matfree
        mode never takes the dense kernel; its HVPs route in
        ``_matfree_matvec``."""
        if self.matfree:
            return False
        return dispatch.use_pallas(
            dispatch.resolve_backend(self.resolved_solve_backend)
        )


class FedNewState(NamedTuple):
    x: jax.Array
    y: jax.Array
    lam: jax.Array
    curv: jax.Array  # per-client curvature cache; layout per FedNewConfig
    comm: jax.Array  # per-client codec state (ŷ / EF residual / width 0)
    key: jax.Array
    step: jax.Array


class StepMetrics(NamedTuple):
    loss: jax.Array
    grad_norm: jax.Array
    uplink_bits_per_client: jax.Array
    dual_sum_residual: jax.Array
    direction_norm: jax.Array


class StepMetricsDiag(NamedTuple):
    """StepMetrics + the per-round diagnostics catalogue (the ``diag_``
    prefix is the ``repro.telemetry`` split convention: the runner peels
    these into ``RunResult.diagnostics``). Returned only under
    ``FedNewConfig(diagnostics=True)``; every extra is a pure read of
    in-step intermediates — no PRNG use, no state change — aggregated over
    the sampled clients (collectives over ``axis_name`` when sharded).

    admm_primal_residual  mean_i ||y_i_tx - ȳ|| — eq. 11's consensus gap
                          on the transmitted directions
    admm_dual_residual    rho * ||ȳ^k - ȳ^{k-1}|| — the dual residual of
                          the one-pass ADMM step
    cg_iters              matfree: mean iterations-to-tolerance of the
                          eq. 9 CG solve (== cg_iters when tol never trips);
                          0 on the dense paths
    cg_residual           matfree: mean final per-client CG residual norm;
                          0 on the dense paths
    codec_error           mean_i ||decode(encode(y_i)) - y_i|| / ||y_i||
                          (exact compression error of the uplink codec)
    anchor_staleness      matfree: mean_i ||anchor_i - x^k|| (drift of the
                          cached curvature anchors); dense: rounds since
                          this round's Hessian refresh
    """

    loss: jax.Array
    grad_norm: jax.Array
    uplink_bits_per_client: jax.Array
    dual_sum_residual: jax.Array
    direction_norm: jax.Array
    diag_admm_primal_residual: jax.Array
    diag_admm_dual_residual: jax.Array
    diag_cg_iters: jax.Array
    diag_cg_residual: jax.Array
    diag_codec_error: jax.Array
    diag_anchor_staleness: jax.Array


def _diag_mean(values, mask, axis_name):
    """Mean of a per-client (n_local,) series over the sampled clients,
    replicated across the client mesh axis when sharded."""
    w = jnp.ones_like(values) if mask is None else mask.astype(values.dtype)
    total = jnp.sum(values * w)
    count = jnp.sum(w)
    if axis_name is not None:
        total = jax.lax.psum(total, axis_name)
        count = jax.lax.psum(count, axis_name)
    return total / jnp.maximum(count, 1.0)


def _anchor_staleness(state, curv, cfg: FedNewConfig, mask, axis_name):
    """Hessian-anchor staleness: matfree measures the anchors' actual drift
    from the current iterate; dense reports rounds since the refresh that
    produced this round's factors (a host-free re-derivation of the
    ``step % hessian_period`` schedule)."""
    if cfg.matfree:
        bcast = jax.tree.map(
            lambda xl, cl: cl - jnp.broadcast_to(xl, cl.shape), state.x, curv
        )
        return _diag_mean(hvp.client_norms(bcast), mask, axis_name)
    age = (
        state.step % cfg.hessian_period
        if cfg.hessian_period > 0 else state.step
    )
    return age.astype(jnp.float32)


def _diag_metrics(
    state: FedNewState,
    cfg: FedNewConfig,
    base: StepMetrics,
    *,
    y_i,
    y_i_tx,
    y,
    curv,
    cg_info,
    mask,
    axis_name,
) -> StepMetricsDiag:
    """The ``diag_*`` catalogue from one round's intermediates — shared by
    the flat and pytree step paths (every expression is tree-generic: a flat
    ``(n, d)`` stack is just a one-leaf tree)."""
    primal = _diag_mean(
        hvp.client_norms(jax.tree.map(
            lambda t, yl: t - jnp.broadcast_to(yl, t.shape), y_i_tx, y
        )),
        mask, axis_name,
    )
    dual = cfg.rho * hvp.tree_norm(
        jax.tree.map(lambda a, b: a - b, y, state.y)
    )
    codec_err = _diag_mean(
        hvp.client_norms(jax.tree.map(lambda a, b: a - b, y_i_tx, y_i))
        / jnp.maximum(hvp.client_norms(y_i), 1e-30),
        mask, axis_name,
    )
    if cg_info is not None:
        cg_iters = _diag_mean(
            cg_info.iterations.astype(jnp.float32), mask, axis_name
        )
        cg_residual = _diag_mean(cg_info.residual_norm, mask, axis_name)
    else:
        cg_iters = jnp.zeros((), jnp.float32)
        cg_residual = jnp.zeros((), jnp.float32)
    return StepMetricsDiag(
        *base,
        diag_admm_primal_residual=primal,
        diag_admm_dual_residual=dual,
        diag_cg_iters=cg_iters,
        diag_cg_residual=cg_residual,
        diag_codec_error=codec_err,
        diag_anchor_staleness=_anchor_staleness(
            state, curv, cfg, mask, axis_name
        ),
    )


def _factorize(obj: Objective, x, data, cfg: FedNewConfig):
    H = obj.local_hessian(x, data)  # (n, d, d)
    if cfg.solve_uses_kernel:
        # Pallas path keeps the raw Hessian; the in-VMEM CG kernel applies
        # the (alpha+rho) damping itself (no host-side factorization at all).
        return H
    damped = H + cfg.damping * jnp.eye(H.shape[-1], dtype=H.dtype)
    return jax.vmap(lambda M: jsl.cholesky(M, lower=True))(damped)


def _check_matfree(obj: Objective, cfg: FedNewConfig) -> None:
    if cfg.matfree and not obj.has_hvp:
        raise ValueError(
            "hessian_repr='matfree' needs an Objective with a local_hvp "
            "oracle (objectives.logistic_regression / objectives.quadratic "
            "provide closed-form ones; objectives.from_loss_fn derives one "
            "by autodiff); this objective has none"
        )


def _fresh_curv(obj: Objective, x, data, cfg: FedNewConfig, n_local: int):
    """The curvature cache a client that saw iterate ``x`` would hold:
    factors/Hessians in dense mode, the anchor point itself in matfree."""
    if cfg.matfree:
        return jnp.broadcast_to(x, (n_local,) + x.shape)
    return _factorize(obj, x, data, cfg)


def _check_tree_mode(cfg: FedNewConfig, axis_name=None) -> None:
    if not cfg.matfree:
        raise ValueError(
            "pytree parameters need hessian_repr='matfree': the dense path "
            "factorizes (n, d, d) Hessian blocks, which cannot exist for "
            "model-scale param pytrees"
        )
    if axis_name is not None:
        raise ValueError(
            "pytree FedNew states run on the scan/host schedules only; the "
            "client mesh still assumes flat (n, d) state (ROADMAP: 2-D mesh "
            "sharding clients x model is the follow-up)"
        )


def _init_tree(
    obj: Objective, data, cfg: FedNewConfig, key: jax.Array, x0
) -> FedNewState:
    """Pytree-layout init: x0 IS the model's param pytree (required — zeros
    can't be conjured without the tree structure); per-client state stacks a
    client axis onto every leaf, the codec state is per-leaf."""
    _check_tree_mode(cfg)
    n = data.n_clients
    return FedNewState(
        x=x0,
        y=jax.tree.map(jnp.zeros_like, x0),
        lam=admm.stack_zeros(x0, n),
        curv=admm.bcast_clients(x0, n),
        comm=comm.init_state_tree(cfg.build_codec(), n, x0),
        key=key,
        step=jnp.zeros((), jnp.int32),
    )


def init(
    obj: Objective, data: ClientDataset, cfg: FedNewConfig, key: jax.Array, x0=None
) -> FedNewState:
    _check_matfree(obj, cfg)
    if x0 is not None and is_param_tree(x0):
        return _init_tree(obj, data, cfg, key, x0)
    d = data.dim
    n = data.n_clients
    dtype = data.features.dtype if data.features.dtype in (jnp.float32, jnp.float64) else jnp.float32
    x = jnp.zeros((d,), dtype) if x0 is None else jnp.asarray(x0, dtype)
    return FedNewState(
        x=x,
        y=jnp.zeros((d,), dtype),
        lam=jnp.zeros((n, d), dtype),
        curv=_fresh_curv(obj, x, data, cfg, n),
        comm=cfg.build_codec().init_state(n, d, dtype),
        key=key,
        step=jnp.zeros((), jnp.int32),
    )


def _matfree_matvec(curv, cfg: FedNewConfig, obj: Objective, data):
    """The matfree CG's matvec: every client's H_i at its anchor in ``curv``.

    An objective that exposes its GLM split (``local_curvature``) on a solve
    backend that resolves to Pallas gets the ``glm_hvp`` kernel, which reads
    the features once per call where ``local_hvp``'s two matvecs read them
    twice. The kernel's copy of the features is made here, outside the CG
    loop (XLA hoists it out of a scan block too, as the features never
    change); it is bf16 only where the default matmul precision would hand
    the chip bf16 operands anyway (``dispatch.matmul_operand_dtype``).
    Every other case calls ``local_hvp``, lowered as it always was."""
    backend = cfg.resolved_solve_backend
    if not (obj.has_curvature
            and dispatch.use_pallas(dispatch.resolve_backend(backend))):
        return lambda v: obj.local_hvp(curv, data, v)
    feats = data.features
    a = feats.astype(dispatch.matmul_operand_dtype(feats.dtype))

    def matvec(v):
        # The weights are formed per call, as local_hvp forms them: XLA
        # hoists their A · anchor product out of the CG loop and merges it
        # with the gradient's A x. Formed before the loop, that product
        # compiles on the TPU as a sweep of its own.
        w = obj.local_curvature(curv, data)
        return dispatch.glm_hvp(a, w, v, mu=obj.l2, backend=backend)

    return matvec


def _local_solve(curv, rhs, cfg: FedNewConfig, obj=None, data=None,
                 with_info=False):
    """(H_i + (alpha+rho) I)^{-1} rhs, batched over clients (eq. 9).

    ``with_info=True`` (diagnostics) returns ``(y_i, CGResult-or-None)``
    instead of ``y_i`` — the CG result carries per-client
    iterations-to-tolerance and final residuals on the matfree path, None
    on the direct solves (their residual is solver-exact)."""
    if cfg.matfree:
        # `curv` holds per-client anchor points; each CG matvec applies every
        # client's Hessian at once — H_i never exists as a matrix.
        res = hvp.cg_solve_clients(
            _matfree_matvec(curv, cfg, obj, data),
            rhs,
            damping=cfg.damping,
            iters=cfg.cg_iters,
            tol=cfg.cg_tol,
            track_iters=with_info,
        )
        return (res.x, res) if with_info else res.x
    if cfg.solve_uses_kernel:
        # `curv` holds the raw Hessians on this path (see _factorize)
        y = dispatch.client_solve(
            curv, rhs, damping=cfg.damping, backend=cfg.resolved_solve_backend
        )
    else:
        y = jax.vmap(lambda L, r: jsl.cho_solve((L, True), r))(curv, rhs)
    return (y, None) if with_info else y


def _mask_rows(mask, new, old):
    """Per-client select: sampled clients take the new row, the rest keep
    their stale state (lam, codec state, cached factors)."""
    m = mask.reshape(mask.shape + (1,) * (new.ndim - 1))
    return jnp.where(m > 0, new, old)


# Per-client codec PRNG keys (device-count invariant); now shared across
# solvers as ``repro.comm.client_keys`` — this alias keeps the historical
# import site.
_client_keys = comm.client_keys


def _step_tree(
    state: FedNewState,
    obj: Objective,
    data,
    cfg: FedNewConfig,
    mask: Optional[jax.Array] = None,
):
    """One outer round over a param *pytree* — the same Algorithm 1 flow as
    the flat path below, with every (n, d) stack generalized to per-leaf
    (n, ...) trees: matfree CG on autodiff HVPs for eq. 9, per-leaf codec
    application on the uplink (``comm.encode_decode_tree``), tree-generic
    ADMM aggregation/dual update, per-leaf exact bit accounting. The flat
    path is never routed here, so its lowering (and every bit-exactness pin)
    is untouched."""
    n_local = jax.tree.leaves(state.lam)[0].shape[0]
    # -- local Hessian refresh: re-anchor sampled clients' curvature at x^k --
    if cfg.hessian_period > 0:
        with jax.named_scope("fednew.hessian"):
            refresh = (state.step % cfg.hessian_period) == 0
            curv = jax.lax.cond(
                refresh,
                lambda: admm.bcast_clients(state.x, n_local),
                lambda: state.curv,
            )
            if mask is not None:
                curv = admm.mask_client_rows(mask, curv, state.curv)
    else:
        curv = state.curv

    with jax.named_scope("fednew.grad"):
        g_i = obj.local_grad(state.x, data)  # per-leaf (n, ...), never sent
        rhs = admm.admm_rhs(
            g_i, state.lam, admm.bcast_clients(state.y, n_local), cfg.rho
        )

    # -- eq. 9: batched damped CG on the autodiff HVP oracle ----------------
    with jax.named_scope("fednew.eq9"):
        cg_res = hvp.cg_solve_clients(
            lambda v: obj.local_hvp(curv, data, v),
            rhs,
            damping=cfg.damping,
            iters=cfg.cg_iters,
            tol=cfg.cg_tol,
            track_iters=cfg.diagnostics,
        )
        y_i = cg_res.x

    # -- uplink compression: the codec applied leaf-wise --------------------
    codec = cfg.build_codec()
    with jax.named_scope("fednew.codec"):
        if codec.needs_rng:
            key, sub = jax.random.split(state.key)
        else:
            key, sub = state.key, state.key  # deterministic codecs: unused
        y_i_tx, comm_state = comm.encode_decode_tree(
            codec, sub, y_i, state.comm, step=state.step
        )
        if mask is not None:
            comm_state = admm.mask_client_rows(mask, comm_state, state.comm)

    with jax.named_scope("fednew.aggregate"):
        # -- eqs. 13 + 12: the ONLY communication + dual update -------------
        y = admm.tree_mean_clients(y_i_tx, None, weights=mask)
        lam = admm.dual_update(
            state.lam, y_i_tx, admm.bcast_clients(y, n_local), cfg.rho,
            weights=mask,
        )

        # -- exact per-leaf uplink accounting -------------------------------
        bits = comm.tree_payload_bits_metric(codec, y, state.step)
        if mask is not None:
            from repro.core import participation

            bits = participation.masked_bits_metric(bits, mask, None)

        x = jax.tree.map(lambda p, yl: p - yl, state.x, y)  # eq. 14

    new_state = FedNewState(
        x=x, y=y, lam=lam, curv=curv, comm=comm_state, key=key,
        step=state.step + 1,
    )
    with jax.named_scope("fednew.eval"):
        metrics = StepMetrics(
            loss=obj.global_loss(x, data),
            grad_norm=hvp.tree_norm(obj.global_grad(x, data)),
            uplink_bits_per_client=bits,
            dual_sum_residual=admm.dual_sum_residual(lam),
            direction_norm=hvp.tree_norm(y),
        )
        if cfg.diagnostics:
            metrics = _diag_metrics(
                state, cfg, metrics, y_i=y_i, y_i_tx=y_i_tx, y=y, curv=curv,
                cg_info=cg_res, mask=mask, axis_name=None,
            )
    return new_state, metrics


def step(
    state: FedNewState,
    obj: Objective,
    data: ClientDataset,
    cfg: FedNewConfig,
    *,
    axis_name: Optional[str] = None,
    n_global_clients: Optional[int] = None,
    mask: Optional[jax.Array] = None,
):
    """One outer round of Algorithm 1 (optionally quantized).

    With ``axis_name`` the round runs inside a ``shard_map`` manual region:
    ``data`` and the per-client state rows (lam/curv/comm) hold only this
    shard's clients, eq. 13 and the metric aggregates become collectives over
    the client mesh axis, and ``n_global_clients`` (static, required on the
    Q-FedNew path) lets every shard derive the same per-client PRNG keys as
    the single-device run — sharding changes the schedule, not the math.

    ``mask`` (a (n_local,) {0,1} participation mask from
    ``repro.core.participation``) restricts the round to the sampled clients:
    eq. 13 aggregates only their y_i, only they update lam/codec-state/cached
    factors, and only they are charged uplink bits. ``mask=None`` is full
    participation — the original code path, bit for bit. Loss/grad-norm
    metrics always evaluate the *global* objective (evaluation is not
    communication).

    Compression routes through the config's ``repro.comm`` codec: the step
    encodes each client's direction (per-client keys only when the codec is
    stochastic — plain FedNew never touches the PRNG), aggregates the PS-side
    ``decode`` of the wire payload, and updates ``state.comm``. The identity
    codec reproduces pre-codec FedNew and ``bits=b`` (the stoch_quant codec)
    reproduces Q-FedNew bit for bit (pinned in tests/test_comm.py).
    """
    # Engine contract: a sharded caller passes an obj already bound to this
    # axis (with_axis is idempotent then); the rebind here covers direct
    # callers, whose metrics would otherwise silently aggregate shard-local.
    if is_param_tree(state.x):
        _check_tree_mode(cfg, axis_name)
        _check_matfree(obj, cfg)
        return _step_tree(state, obj, data, cfg, mask)
    if axis_name is not None:
        obj = obj.with_axis(axis_name)
    _check_matfree(obj, cfg)
    n_local = state.lam.shape[0]
    # -- local Hessian refresh (pure client-side compute; no communication) --
    if cfg.hessian_period > 0:
        with jax.named_scope("fednew.hessian"):
            refresh = (state.step % cfg.hessian_period) == 0
            curv = jax.lax.cond(
                refresh,
                lambda: _fresh_curv(obj, state.x, data, cfg, n_local),
                lambda: state.curv,
            )
            if mask is not None:
                # Only sampled clients saw x^k; the rest keep the stale factor.
                curv = _mask_rows(mask, curv, state.curv)
    else:
        curv = state.curv

    with jax.named_scope("fednew.grad"):
        g_i = obj.local_grad(state.x, data)  # (n, d) — never transmitted
        rhs = admm.admm_rhs(
            g_i, state.lam, jnp.broadcast_to(state.y, g_i.shape), cfg.rho
        )

    # -- eq. 9: client sub-problem solve ------------------------------------
    with jax.named_scope("fednew.eq9"):
        if cfg.diagnostics:
            y_i, cg_info = _local_solve(curv, rhs, cfg, obj, data,
                                        with_info=True)
        else:
            y_i = _local_solve(curv, rhs, cfg, obj, data)

    # -- uplink compression (repro.comm codec) ------------------------------
    # Encode client-side, aggregate the PS-side decode: eq. 13 and the dual
    # update run on the *reconstructed* y_i so the sum-lambda invariant holds
    # (every client knows its own reconstruction). Deterministic codecs never
    # touch the PRNG — plain FedNew's key stays bit-frozen, as it always was.
    codec = cfg.build_codec()
    with jax.named_scope("fednew.codec"):
        if codec.needs_rng:
            key, sub = jax.random.split(state.key)
            keys = _client_keys(sub, y_i.shape[0], axis_name, n_global_clients)
        else:
            key, keys = state.key, None
        wire = codec.encode(keys, y_i, state.comm, state.step)
        y_i_tx = codec.decode(wire, state.comm, state.step)
        comm_state = codec.update_state(y_i_tx, y_i, state.comm, state.step)
        if mask is not None:
            # Sampled clients advance their codec state (ŷ / EF residual); the
            # rest encoded nothing this round and keep it stale. Their y_i_tx
            # rows are irrelevant: the weighted aggregates zero them out.
            comm_state = _mask_rows(mask, comm_state, state.comm)

    with jax.named_scope("fednew.aggregate"):
        # -- eqs. 13 + 12: the ONLY communication + dual update -------------
        y = admm.tree_mean_clients(y_i_tx, axis_name, weights=mask)
        lam = admm.dual_update(
            state.lam, y_i_tx, jnp.broadcast_to(y, y_i_tx.shape), cfg.rho,
            weights=mask,
        )

        # -- exact uplink accounting ----------------------------------------
        bits = codec.payload_bits_metric(
            data.dim, word_bits(y_i_tx), state.step
        )
        if mask is not None:
            from repro.core import participation

            bits = participation.masked_bits_metric(bits, mask, axis_name)

        x = state.x - y  # outer Newton step (eq. 14)

    new_state = FedNewState(
        x=x, y=y, lam=lam, curv=curv, comm=comm_state, key=key,
        step=state.step + 1,
    )
    with jax.named_scope("fednew.eval"):
        metrics = StepMetrics(
            loss=obj.global_loss(x, data),
            grad_norm=jnp.linalg.norm(obj.global_grad(x, data)),
            uplink_bits_per_client=bits,
            dual_sum_residual=admm.dual_sum_residual(lam, axis_name),
            direction_norm=jnp.linalg.norm(y),
        )
        if cfg.diagnostics:
            metrics = _diag_metrics(
                state, cfg, metrics, y_i=y_i, y_i_tx=y_i_tx, y=y, curv=curv,
                cg_info=cg_info, mask=mask, axis_name=axis_name,
            )
    return new_state, metrics


def solver(cfg: FedNewConfig):
    """This algorithm as a ``repro.core.engine.FederatedSolver``."""
    from repro.core import engine

    codec_name = cfg.codec_spec["name"]
    if cfg.bits:
        name = f"q-fednew({cfg.bits}b)"
    elif codec_name != "identity":
        name = f"fednew+{codec_name}"
    else:
        name = "fednew"
    return engine.FederatedSolver(
        name=name,
        init=lambda obj, data, key, x0=None: init(obj, data, cfg, key, x0),
        step=lambda state, obj, data, **axis_kw: step(state, obj, data, cfg, **axis_kw),
        client_fields=("lam", "curv", "comm"),
    )


def ledger(cfg: FedNewConfig):
    """Exact bit accounting: the codec's uplink payload (``word*d`` for the
    identity codec — plain FedNew; ``bits*d + 32`` for Q-FedNew; the exact
    ``payload_bits`` in general), and the ``word*d`` broadcast iterate down.
    FedNew never transmits curvature, so Hessian-refresh rounds cost no
    extra bits in either direction."""
    from repro.core import engine
    from repro.core.quantization import exact_payload_bits

    codec = cfg.build_codec()
    return engine.SolverLedger(
        uplink=lambda d, word, round_index: codec.payload_bits(
            d, word, round_index
        ),
        downlink=lambda d, word, round_index: exact_payload_bits(d, word),
    )


def run(
    obj: Objective,
    data: ClientDataset,
    cfg: FedNewConfig,
    rounds: int,
    key: Optional[jax.Array] = None,
    x0=None,
):
    """Legacy driver, kept as the bit-exact reference: a thin wrapper over
    ``repro.core.engine.run(mode="host")``, which jits one step and iterates
    on the host exactly as this function always did. New code should call the
    engine directly (``mode="scan"`` compiles whole round-blocks)."""
    from repro.core import engine

    return engine.run(solver(cfg), obj, data, rounds, key=key, x0=x0, mode="host")
