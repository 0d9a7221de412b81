"""Federated execution engine: one driver for every Newton-type solver.

The paper-faithful modules (``core.fednew``, ``core.baselines``) define the
*math* of a round; this module owns the *schedule*. Everything that used to
be an ad-hoc host loop — one jitted step per round, re-implemented by every
benchmark and example — routes through two orthogonal mechanisms:

  * **scan compilation** — rounds are grouped into fixed-size blocks and each
    block is one ``lax.scan`` inside one ``jit`` with the carried state
    donated. A thousand-round run compiles at most twice (full block + tail
    block) and streams metrics back as stacked ``(rounds,)`` arrays instead
    of a thousand host round-trips.

  * **client sharding** — with a ``mesh``, the client axis of the dataset and
    of the per-client state rows (``FederatedSolver.client_fields``) is
    sharded across the mesh's client axis and the whole scan block runs
    inside one ``shard_map`` manual region. Cross-client aggregation (eq. 13,
    the metric means, the dual-sum invariant) lowers to collectives over that
    axis; everything else is embarrassingly client-parallel, including the
    Pallas ``client_solve`` path, which sees per-device batched Hessian
    blocks of shape ``(n_clients/n_devices, d, d)``.

Solvers implement the :class:`FederatedSolver` protocol — ``init`` and a
per-round ``step`` — and are registered in :func:`get_solver` by name, so
benchmarks and examples select methods by string instead of re-wiring loops.

The legacy drivers (``fednew.run``, ``baselines.run_simple``) remain as thin
wrappers over ``mode="host"``, which reproduces the historical
one-jitted-step-per-round loop bit for bit.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from typing import Any, Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import participation as participation_lib
from repro.core.objectives import ClientDataset, Objective
from repro.launch import mesh as mesh_lib
from repro.sharding import api as sh_api
from repro.sharding import specs as sh

# Rounds per compiled scan block. Large enough that host dispatch is noise,
# small enough that the first block's results stream back quickly.
DEFAULT_BLOCK = 64


@dataclasses.dataclass(frozen=True)
class FederatedSolver:
    """Protocol adapter: the math of one federated method.

    init(obj, data, key, x0=None) -> state
        Build the round-0 state on the full (unsharded) dataset. States are
        NamedTuples of arrays.
    step(state, obj, data, *, axis_name=None, n_global_clients=None)
        -> (state, metrics)
        One outer round. ``axis_name``/``n_global_clients`` are forwarded
        only to solvers that shard per-client state (others may swallow
        them); metrics must be scalars, replicated across the client axis
        when sharded.
    client_fields
        Names of state fields carrying a leading global-client axis; the
        sharded driver splits exactly these (plus the dataset) across the
        client mesh axis and replicates the rest.
    """

    name: str
    init: Callable[..., Any]
    step: Callable[..., Tuple[Any, Any]]
    client_fields: Tuple[str, ...] = ()


@dataclasses.dataclass(frozen=True)
class SolverLedger:
    """Exact per-message communication accounting for one configured solver.

    ``uplink(d, word, round_index)`` / ``downlink(d, word, round_index)``
    return the bits ONE sampled client sends/receives in round
    ``round_index`` for a d-parameter model transmitted at ``word`` bits per
    element — as exact Python ints (arbitrary precision, no float
    round-trip; the PR-2 contract). Round-indexed so one-shot charges
    (Newton-Zero's round-0 Hessian, FedNL's ``init_hessian="exact"`` seed)
    and schedules (``bit_schedule``) stay exact per round. ``repro.api``'s
    cumulative ledgers are sums of these over the replayed participation
    masks."""

    uplink: Callable[[int, int, int], int]
    downlink: Callable[[int, int, int], int]


@dataclasses.dataclass(frozen=True)
class SolverEntry:
    """One registry row: how to build a solver, validate its hparams, and
    account its communication.

    factory(**hparams)  -> FederatedSolver
    config_cls          config dataclass whose fields are the valid hparams
                        (None for config-less solvers like ``newton``)
    ledger(**hparams)   -> SolverLedger for that configuration
    """

    factory: Callable[..., "FederatedSolver"]
    config_cls: Optional[type]
    ledger: Callable[..., SolverLedger]


def _registry() -> dict:
    """name -> :class:`SolverEntry`. Hparams are validated against the
    config dataclass's fields before construction, so typos surface as named
    errors instead of opaque dataclass ``TypeError``s."""
    from repro.core import baselines, fagh, fednew, fednl, fedns
    from repro.events import fedbuff

    def entry(factory, cfg_cls, ledger):
        if cfg_cls is None:
            return SolverEntry(
                factory=lambda **hp: factory(),
                config_cls=None,
                ledger=lambda **hp: ledger(),
            )
        return SolverEntry(
            factory=lambda **hp: factory(cfg_cls(**hp)),
            config_cls=cfg_cls,
            ledger=lambda **hp: ledger(cfg_cls(**hp)),
        )

    fednew_entry = entry(fednew.solver, fednew.FedNewConfig, fednew.ledger)
    return {
        "fednew": fednew_entry,
        "q-fednew": fednew_entry,
        "fednew-async": entry(
            fedbuff.solver, fedbuff.FedNewAsyncConfig, fedbuff.ledger
        ),
        "fednl": entry(fednl.solver, fednl.FedNLConfig, fednl.ledger),
        "fedns": entry(fedns.solver, fedns.FedNSConfig, fedns.ledger),
        "fagh": entry(fagh.solver, fagh.FAGHConfig, fagh.ledger),
        "fedgd": entry(
            baselines.fedgd_solver, baselines.FedGDConfig, baselines.fedgd_ledger
        ),
        "newton-zero": entry(
            baselines.newton_zero_solver,
            baselines.NewtonZeroConfig,
            baselines.newton_zero_ledger,
        ),
        "newton": entry(baselines.newton_solver, None, baselines.newton_ledger),
    }


def canonical_solver_name(name: str) -> str:
    return name.lower().replace("_", "-")


def solver_names() -> Tuple[str, ...]:
    """Registered solver names (canonical form), for error messages and the
    declarative ``repro.api`` spec validation."""
    return tuple(sorted(_registry()))


def solver_hparam_names(name: str) -> Tuple[str, ...]:
    """Valid hparam keys for a registered solver (the fields of its config
    dataclass; empty for config-less solvers like ``newton``)."""
    key = canonical_solver_name(name)
    reg = _registry()
    if key not in reg:
        raise KeyError(
            f"unknown solver {name!r}; registered solvers: "
            f"{', '.join(sorted(reg))}"
        )
    cfg_cls = reg[key].config_cls
    if cfg_cls is None:
        return ()
    return tuple(f.name for f in dataclasses.fields(cfg_cls))


def validate_solver_hparams(name: str, **hparams) -> None:
    """Value-level hparam validation: construct (and discard) the solver's
    config dataclass so its ``__post_init__`` checks (enum strings like
    ``hessian_repr``, positivity of ``cg_iters``, backend names) fire at
    spec-build time instead of three layers down. Unknown names/solvers
    raise the same errors as :func:`get_solver`."""
    key = canonical_solver_name(name)
    valid = solver_hparam_names(key)  # raises KeyError on unknown solver
    unknown = sorted(set(hparams) - set(valid))
    if unknown:
        raise TypeError(
            f"solver {key!r} got unknown hparam(s) {unknown}; valid hparams: "
            f"{list(valid) if valid else '<none>'}"
        )
    cfg_cls = _registry()[key].config_cls
    if cfg_cls is not None:
        cfg_cls(**hparams)


def get_solver(name: str, **hparams) -> FederatedSolver:
    """Solver registry: ``fednew`` / ``q-fednew`` (needs ``bits``) /
    ``fednl`` / ``fedns`` / ``fagh`` / ``fedgd`` / ``newton-zero`` /
    ``newton``. ``hparams`` feed the method's config dataclass (e.g.
    ``rho=0.1, alpha=0.03, hessian_period=10``).

    The second-order zoo (see docs/solvers.md for the update rules and bit
    formulas): ``fednl`` maintains per-client Hessian estimates via
    compressed corrections (``codec=`` takes any ``repro.comm`` spec, same
    as fednew), ``fedns`` uplinks ``sketch_size``-column Nystrom sketches of
    the local Hessians, and ``fagh`` spends exactly one ``local_hvp`` per
    client per round to maintain an approximate global-Hessian direction
    (needs an Objective with the HVP oracle, like ``hessian_repr=
    "matfree"``).

    FedNew/Q-FedNew accept ``backend="auto"|"pallas"|"reference"`` (plus
    per-loop ``solve_backend``/``quant_backend`` overrides): the eq. 9
    client solve and the eqs. 25-30 quantizer then route through the Pallas
    kernels via ``repro.kernels.dispatch`` — compiled on TPU, interpret mode
    when ``pallas`` is forced off-TPU, jnp reference otherwise. The sharded
    driver composes with this: inside the ``shard_map`` region each device's
    kernel call sees its own ``(n_clients/n_devices, ...)`` tile.

    What FedNew transmits is a ``repro.comm`` codec: ``bits=b`` is sugar for
    the ``stoch_quant`` codec (Q-FedNew, bit for bit), and
    ``codec={"name": "topk", "fraction": 0.1}`` (or any registered codec
    spec) swaps the compressor. Per-client codec state (previous quantized
    vector, error-feedback residual) is a ``client_fields`` entry
    (``FedNewState.comm``), so it shards and scans like every other
    per-client row.

    ``hessian_repr="matfree"`` (+ ``cg_iters``/``cg_tol``) switches the
    eq. 9 solve to CG on the objective's closed-form HVPs: no ``(n, d, d)``
    Hessian is ever built, per-client state is O(d), and the scan/shard_map
    schedules are unchanged (CG is pure tree ops; eq. 13 aggregation and the
    metric collectives are untouched)."""
    key = canonical_solver_name(name)
    # One validation path for spec-build time and solver-build time: unknown
    # solvers/hparams and bad values raise identical, named errors.
    validate_solver_hparams(key, **hparams)
    if key == "q-fednew" and not hparams.get("bits"):
        raise ValueError("q-fednew requires bits=<int>")
    return _registry()[key].factory(**hparams)


def solver_ledger(name: str, **hparams) -> SolverLedger:
    """Exact bit accounting for a configured solver, by registry name.

    Validates ``hparams`` exactly like :func:`get_solver` (same named
    errors), then builds the solver's :class:`SolverLedger`. This is the one
    authority ``repro.api``'s cumulative uplink/downlink ledgers consume —
    adding a solver to the registry with a ``ledger`` factory is all it
    takes for ``api.run`` to account it."""
    key = canonical_solver_name(name)
    validate_solver_hparams(key, **hparams)
    if key == "q-fednew" and not hparams.get("bits"):
        raise ValueError("q-fednew requires bits=<int>")
    return _registry()[key].ledger(**hparams)


# ---------------------------------------------------------------------------
# drivers
# ---------------------------------------------------------------------------


def run(
    solver: FederatedSolver,
    obj: Objective,
    data: ClientDataset,
    rounds: int,
    *,
    key: Optional[jax.Array] = None,
    x0=None,
    mode: str = "scan",
    block_size: Optional[int] = None,
    mesh=None,
    axis_name: Optional[str] = None,
    donate: bool = True,
    participation: Optional[participation_lib.Participation] = None,
    timings: Optional[List[Tuple[int, float]]] = None,
    tracer=None,
):
    """Run ``rounds`` federated rounds; returns ``(final_state, metrics)``
    with every metric stacked to shape ``(rounds,)``.

    mode="scan"  (default) scan-compiled round blocks (``block_size``).
    mode="host"  legacy one-jitted-step-per-round loop (bit-exact reference).
    mesh=...     shard the client axis across ``axis_name`` (default: the
                 mesh's first axis) and run scan blocks inside shard_map.
    participation=Participation(fraction, kind, seed)
                 per-round client sampling: the participation key rides in
                 the scan carry, each round draws a global client mask, and
                 the solver step aggregates/charges only the sampled clients.
                 ``fraction=1.0`` (or None) is full participation — the
                 original code path, bit for bit.
    timings=[]   pass a list to receive one ``(rounds_in_call, seconds)``
                 entry per dispatched jit call (per block under scan, per
                 round under host), each blocked to completion before the
                 clock stops. The first entry of a fresh run includes trace
                 + compile time; callers split compile from steady-state
                 cost with it (``repro.api`` reports ``compile_s`` vs
                 ``steady_wall_clock_s``). ``None`` (default) adds no
                 synchronization at all.
    tracer=...   a duck-typed telemetry hook (``repro.telemetry.
                 EngineTracer``): ``span(name, **args)`` context managers
                 wrap the host phases (init, each dispatch), and — when its
                 ``wants_profile`` flag is set — ``profile_dispatch(label,
                 jitted, *args)`` is offered each distinct compiled callable
                 BEFORE it first executes (AOT lowering only; the
                 computation never runs, so profiling cannot perturb the
                 trajectory). ``None`` (default) is the historical
                 zero-overhead path.
    """
    if rounds <= 0:
        raise ValueError("rounds must be positive")
    if mode not in ("scan", "host"):
        raise ValueError(f"unknown mode {mode!r}")
    key = jax.random.PRNGKey(0) if key is None else key
    part = participation if (participation and participation.active) else None
    if mesh is not None:
        if mode != "scan":
            raise ValueError("mesh runs are always scan-compiled; drop mode="
                             f"{mode!r} or the mesh")
        return _run_sharded(
            solver, obj, data, rounds, mesh,
            key=key, x0=x0, block_size=block_size,
            axis_name=axis_name, donate=donate, participation=part,
            timings=timings, tracer=tracer,
        )

    with _span(tracer, "init", solver=solver.name):
        state = solver.init(obj, data, key, x0)
    # The dataset is an argument of every jitted call, never a closure: a
    # closed-over array is embedded in the program as a constant, which at
    # a few GB of client data exhausts host memory during compilation.
    if part is None:
        step1 = lambda s, d: solver.step(s, obj, d)
        carry = state
    else:
        n = data.n_clients

        def step1(c, d):
            s, pkey = c
            pkey, sub = participation_lib.split_round(pkey)
            mask = participation_lib.round_mask(sub, n, part)
            s, m = solver.step(s, obj, d, mask=mask)
            return (s, pkey), m

        carry = (state, part.init_key())
    if mode == "host":
        carry, metrics = _host_loop(step1, carry, data, rounds, timings,
                                    tracer)
    else:
        if donate:
            # init() may alias caller arrays (the PRNG key, x0); donating
            # those buffers into the first block would delete them under the
            # caller.
            carry = jax.tree.map(jnp.copy, carry)
        carry, metrics = _scan_blocks(
            step1, carry, data, rounds, block_size, donate, timings, tracer
        )
    return (carry[0] if part is not None else carry), metrics


def _span(tracer, name: str, **args):
    """The tracer's host span, or a no-op when telemetry is off."""
    if tracer is None:
        return contextlib.nullcontext()
    return tracer.span(name, **args)


def _profile(tracer, label: str, jitted, *args) -> None:
    """Offer one compiled callable to the tracer's HLO cost capture (a
    pre-execution AOT lowering; dedup'd by label inside the tracer)."""
    if tracer is not None and getattr(tracer, "wants_profile", False):
        tracer.profile_dispatch(label, jitted, *args)


def _timed(call, n_rounds: int, timings, tracer=None, label="step"):
    """Run one dispatched jit call, optionally timing it to completion."""
    if timings is None and tracer is None:
        return call()
    t0 = time.perf_counter()
    with _span(tracer, "dispatch", label=label, rounds=n_rounds):
        out = jax.block_until_ready(call())
    if timings is not None:
        timings.append((n_rounds, time.perf_counter() - t0))
    return out


def _host_loop(step1, state, data, rounds: int, timings=None, tracer=None):
    """The historical driver: jit one step, iterate on the host."""
    jstep = jax.jit(step1)
    _profile(tracer, "host_step", jstep, state, data)
    history = []
    for _ in range(rounds):
        state, m = _timed(lambda: jstep(state, data), 1, timings, tracer,
                          "host_step")
        history.append(m)
    return state, jax.tree.map(lambda *xs: jnp.stack(xs), *history)


def _block_plan(rounds: int, block_size: Optional[int]):
    block = max(1, min(rounds, block_size or DEFAULT_BLOCK))
    sizes = [block] * (rounds // block)
    if rounds % block:
        sizes.append(rounds % block)
    return sizes


def _concat_metrics(chunks):
    if len(chunks) == 1:
        return chunks[0]
    return jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *chunks)


def _scan_blocks(step1, state, data, rounds: int, block_size, donate: bool,
                 timings=None, tracer=None):
    def block(s, d, length):
        return jax.lax.scan(lambda c, _: step1(c, d), s, None, length=length)

    jblock = jax.jit(
        block, static_argnums=2, donate_argnums=(0,) if donate else ()
    )
    chunks = []
    for n in _block_plan(rounds, block_size):
        label = f"scan_block[{n}r]"
        _profile(tracer, label, jblock, state, data, n)
        state, m = _timed(lambda: jblock(state, data, n), n, timings, tracer,
                          label)
        chunks.append(m)
    return state, _concat_metrics(chunks)


# ---------------------------------------------------------------------------
# sharded driver
# ---------------------------------------------------------------------------


def _run_sharded(
    solver: FederatedSolver,
    obj: Objective,
    data: ClientDataset,
    rounds: int,
    mesh,
    *,
    key,
    x0,
    block_size,
    axis_name: Optional[str],
    donate: bool,
    participation: Optional[participation_lib.Participation] = None,
    timings=None,
    tracer=None,
):
    axis = axis_name or mesh.axis_names[0]
    n_shards = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    n = data.n_clients
    if n % n_shards:
        raise ValueError(
            f"n_clients={n} must divide evenly over the {n_shards}-way "
            f"client axis {axis!r} (equal shards keep eq. 13 a plain pmean)"
        )
    n_local = n // n_shards
    part = participation

    # Round-0 state is built on the full dataset on the default device, then
    # laid out: per-client rows split over the client axis, rest replicated.
    with _span(tracer, "init", solver=solver.name):
        state = solver.init(obj, data, key, x0)
    if donate:
        state = jax.tree.map(jnp.copy, state)  # don't donate caller aliases
    state_specs = sh.fed_state_specs(state, solver.client_fields, axis)
    data_specs = sh.fed_data_specs(data, axis)
    if part is None:
        carry, carry_specs = state, state_specs
    else:
        # The participation key rides in the carry, replicated: every shard
        # draws the same global mask and slices out its own clients.
        carry = (state, part.init_key())
        carry_specs = (state_specs, sh.P())
    carry = jax.device_put(carry, sh.shardings(carry_specs, mesh))
    data = jax.device_put(data, sh.shardings(data_specs, mesh))

    obj_ax = obj.with_axis(axis)

    def block(c, d, length):
        def one(carry, _):
            if part is None:
                return solver.step(
                    carry, obj_ax, d, axis_name=axis, n_global_clients=n
                )
            s, pkey = carry
            pkey, sub = participation_lib.split_round(pkey)
            gmask = participation_lib.round_mask(sub, n, part)
            lmask = participation_lib.shard_mask(gmask, axis, n_local)
            s, m = solver.step(
                s, obj_ax, d, axis_name=axis, n_global_clients=n, mask=lmask
            )
            return (s, pkey), m

        return jax.lax.scan(one, c, None, length=length)

    @functools.lru_cache(maxsize=None)
    def jitted(length: int):
        body = sh_api.shard_map_compat(
            functools.partial(block, length=length),
            mesh,
            in_specs=(carry_specs, data_specs),
            out_specs=(carry_specs, sh.P()),
            manual_axes=(axis,),
        )
        return jax.jit(body, donate_argnums=(0,) if donate else ())

    chunks = []
    for length in _block_plan(rounds, block_size):
        jfn = jitted(length)
        label = f"shard_block[{length}r]"
        _profile(tracer, label, jfn, carry, data)
        carry, m = _timed(lambda: jfn(carry, data), length, timings, tracer, label)
        chunks.append(m)
    final = carry[0] if part is not None else carry
    return final, _concat_metrics(chunks)


def run_sharded_on_host(
    solver: FederatedSolver,
    obj: Objective,
    data: ClientDataset,
    rounds: int,
    **kw,
):
    """Convenience: run on a 1-D client mesh over whatever this host offers
    (one device on a laptop — the shard_map path with a size-1 axis, so the
    same code that runs on a pod is exercised everywhere)."""
    mesh = mesh_lib.make_client_mesh(auto_client_devices(data.n_clients))
    return run(solver, obj, data, rounds, mesh=mesh, **kw)


def auto_client_devices(n_clients: int) -> int:
    """Largest local device count that divides ``n_clients`` evenly (the
    mesh size ``run_sharded_on_host`` and ``ScheduleSpec(mesh_devices=
    "auto")`` use)."""
    for k in range(len(jax.devices()), 0, -1):
        if n_clients % k == 0:
            return k
    return 1
