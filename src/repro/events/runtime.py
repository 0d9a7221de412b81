"""The event-driven executor: streamed cohorts + event heap + buffered FedNew.

Two schedules share one state law:

  * **barrier** (``buffer_size == 0``) — synchronous rounds over a streamed
    cohort: dispatch ``cohort`` clients, run ONE fednew round over exactly
    their rows, pay the slowest sampled client's service time (the
    ``netsim.round_time_s`` straggler barrier, bit for bit at zero compute).
    With ``cohort == n_clients`` this is synchronous FedNew verbatim — the
    jitted step is the same trace as ``engine.run(mode="host")``, so the
    trajectory is bit-exact (pinned in tests/test_events.py).

  * **async** (``buffer_size == K >= 1``) — a discrete-event simulation:
    dispatched clients occupy the timeline independently; each completed
    upload lands in the server buffer; every K-th landing triggers a
    staleness-weighted ``fedbuff.flush``. Clients solve eq. 9 against the
    iterate of the server VERSION they were dispatched at (the stateless
    re-derivation contract: curvature anchor == dispatch iterate, which is
    why events mode requires ``hessian_period == 1``).

The memory contract (the "millions of users" north star): nothing fleet-sized
is ever resident. Client data comes from a *source* (``materialize(ids)`` —
``events.population`` at true scale, an in-memory adapter for API-built
datasets), per-client solver rows (duals + codec state) live in a bounded
:class:`CohortCache` whose evictions spill through ``repro.checkpoint``, and
untouched clients are represented by nothing at all — their rows are the
init-time law (zeros), re-derivable from ``(seed, client_id,
last_sync_round)``. :attr:`EventsResult.peak_state_bytes` is the audited
resident-state high-water mark; its independence from ``n_clients`` is an
acceptance test.
"""

from __future__ import annotations

import contextlib
import dataclasses
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import comm
from repro.checkpoint import io as ckpt_io
from repro.core import fednew
from repro.core.objectives import ClientDataset, Objective
from repro.core.quantization import word_bits
from repro.events import arrivals as arrivals_lib
from repro.events import fedbuff, sim
from repro.events.fedbuff import FedNewAsyncConfig


# ---------------------------------------------------------------------------
# data sources: anything that can materialize a cohort by client id
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ArraySource:
    """In-memory fleet (an already-built ClientDataset) behind the streaming
    ``materialize(ids)`` interface — the adapter ``repro.api`` uses, and the
    reference the population law is cross-checked against."""

    data: ClientDataset

    @property
    def n_clients(self) -> int:
        return self.data.n_clients

    @property
    def dim(self) -> int:
        return self.data.dim

    def materialize(self, ids) -> ClientDataset:
        ids = np.asarray(ids)
        return jax.tree.map(lambda a: a[ids], self.data)


def as_source(data_or_source):
    """Duck-typed source coercion: ClientDatasets get wrapped, anything with
    ``materialize``/``n_clients``/``dim`` (e.g. ``population.Population``)
    passes through."""
    if isinstance(data_or_source, ClientDataset):
        return ArraySource(data_or_source)
    for attr in ("materialize", "n_clients", "dim"):
        if not hasattr(data_or_source, attr):
            raise TypeError(
                f"not a cohort source: {type(data_or_source).__name__} has "
                f"no {attr!r} (need materialize(ids)/n_clients/dim)"
            )
    return data_or_source


# ---------------------------------------------------------------------------
# bounded per-client state: the O(sampled) half of the memory contract
# ---------------------------------------------------------------------------


class CohortCache:
    """LRU cache of per-client solver rows ``(lam, comm, last_sync)``.

    A client that was never touched has the init-time law's row (zeros) and
    costs NOTHING — the cache stores only diverged rows. Past ``capacity``
    resident rows, least-recently-used rows spill to ``spill_dir`` through
    ``repro.checkpoint.io`` (npz + manifest, one file per spilled client)
    and are restored transparently on the next touch. ``resident_bytes`` /
    ``peak_bytes`` audit exactly what this process holds."""

    def __init__(
        self,
        dim: int,
        comm_width: int,
        dtype=np.float32,
        capacity: int = 4096,
        spill_dir: Optional[str] = None,
    ):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.dim = dim
        self.comm_width = comm_width
        self.dtype = np.dtype(dtype)
        self.capacity = capacity
        self.spill_dir = spill_dir
        self._rows: "OrderedDict[int, Dict[str, Any]]" = OrderedDict()
        self._spilled: set = set()
        self.n_spills = 0
        self.n_restores = 0
        self.peak_bytes = 0

    @property
    def row_bytes(self) -> int:
        return (self.dim + self.comm_width) * self.dtype.itemsize

    @property
    def resident_bytes(self) -> int:
        return len(self._rows) * self.row_bytes

    def _default_row(self) -> Dict[str, Any]:
        return {
            "lam": np.zeros((self.dim,), self.dtype),
            "comm": np.zeros((self.comm_width,), self.dtype),
            "last_sync": -1,
        }

    def _touch(self, cid: int) -> Dict[str, Any]:
        if cid in self._rows:
            self._rows.move_to_end(cid)
            return self._rows[cid]
        if cid in self._spilled:
            row = self._restore(cid)
            self.n_restores += 1
        else:
            row = self._default_row()
        self._rows[cid] = row
        self._evict()
        self.peak_bytes = max(self.peak_bytes, self.resident_bytes)
        return row

    def _evict(self) -> None:
        while len(self._rows) > self.capacity:
            cid, row = self._rows.popitem(last=False)
            if row["last_sync"] < 0:
                continue  # never diverged from the law; nothing to keep
            if self.spill_dir is None:
                raise RuntimeError(
                    f"CohortCache overflow: {len(self._rows) + 1} diverged "
                    f"client rows exceed capacity={self.capacity} and no "
                    "spill_dir was configured (pass checkpoint_dir=)"
                )
            ckpt_io.save(
                self.spill_dir,
                f"client_{cid:09d}",
                {"lam": row["lam"], "comm": row["comm"]},
                step=row["last_sync"],
            )
            self._spilled.add(cid)
            self.n_spills += 1

    def _restore(self, cid: int) -> Dict[str, Any]:
        like = {
            "lam": np.zeros((self.dim,), self.dtype),
            "comm": np.zeros((self.comm_width,), self.dtype),
        }
        tree = ckpt_io.restore(self.spill_dir, f"client_{cid:09d}", like)
        import json
        import os

        with open(
            os.path.join(self.spill_dir, f"client_{cid:09d}.json")
        ) as f:
            step = json.load(f)["step"]
        self._spilled.discard(cid)
        return {
            "lam": np.asarray(tree["lam"]),
            "comm": np.asarray(tree["comm"]),
            "last_sync": int(step),
        }

    def gather(self, ids: Sequence[int]):
        """Stacked ``(k, d)`` duals and ``(k, w)`` codec rows for a cohort."""
        rows = [self._touch(int(c)) for c in ids]
        lam = np.stack([r["lam"] for r in rows])
        cstate = np.stack([r["comm"] for r in rows])
        return lam, cstate

    def scatter(self, ids: Sequence[int], lam, comm_state, last_sync: int):
        lam = np.asarray(lam)
        comm_state = np.asarray(comm_state)
        for j, c in enumerate(ids):
            row = self._touch(int(c))
            row["lam"] = lam[j]
            row["comm"] = comm_state[j]
            row["last_sync"] = last_sync


# ---------------------------------------------------------------------------
# results
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class EventsResult:
    """One event-driven run. Per-SERVER-STEP series (variable simulated
    seconds per step — never assume uniform rounds; see
    ``benchmarks/common.seconds_to_rel_gap``)."""

    x: Any  # final iterate
    metrics: Dict[str, List[float]]
    round_time_s: List[float]  # simulated seconds between server steps
    uplink_bits_total: List[int]  # exact ints, summed over landed uploads
    downlink_bits_total: List[int]  # exact ints, summed over dispatches
    contributors: List[int]  # uploads aggregated by each server step
    n_server_steps: int
    simulated_time_s: float
    peak_state_bytes: int
    n_dropped: int = 0
    n_spills: int = 0
    n_restores: int = 0


# Per-client simulated-clock trace bars are emitted only for the first
# this-many client ids: a fleet-sized trace would defeat the O(sampled)
# memory contract the events executor exists for.
_MAX_TRACED_CLIENTS = 256


def _span(tracer, name: str, **args):
    """Duck-typed host span (see ``engine._span``): telemetry stays an
    optional import-free hook here too."""
    if tracer is None:
        return contextlib.nullcontext()
    return tracer.span(name, **args)


def _recorder(tracer):
    return getattr(tracer, "recorder", None) if tracer is not None else None


def _client_bar(rec, fleet: sim.ClientFleet, cid: int, t0_s: float,
                up_msg: int, down_msg: int, **args) -> None:
    """download/compute/upload bars for one service interval, placed on the
    simulated clock. Latency rides inside the transfer segments, so the bar
    ends exactly at ``t0_s + sim.service_time_s(...)``."""
    if rec is None or cid >= _MAX_TRACED_CLIENTS:
        return
    links = fleet.links
    rec.client_segments(
        int(cid),
        t0_s,
        down_s=down_msg / float(links.downlink_bps[cid])
        + float(links.latency_s[cid]),
        compute_s=float(fleet.compute_s[cid]),
        up_s=up_msg / float(links.uplink_bps[cid])
        + float(links.latency_s[cid]),
        **args,
    )


def _eval_ids(n: int, eval_cohort: int) -> np.ndarray:
    """Fixed loss-telemetry cohort: evaluating the true global objective
    would materialize the fleet, so events mode reports loss on a pinned
    ``min(n, eval_cohort)``-client panel (== the global loss when the fleet
    fits)."""
    return np.arange(min(n, eval_cohort), dtype=np.int64)


def _comm_width(codec, dim: int, dtype) -> int:
    return int(codec.init_state(1, dim, dtype).shape[-1])


# ---------------------------------------------------------------------------
# barrier schedule (buffer_size == 0): streamed synchronous rounds
# ---------------------------------------------------------------------------


def _barrier_run(
    cfg: FedNewAsyncConfig,
    obj: Objective,
    source,
    fleet: sim.ClientFleet,
    rounds: int,
    cohort: int,
    key,
    x0,
    cache: CohortCache,
    ledger,
    eval_cohort: int,
    tracer=None,
) -> EventsResult:
    fcfg = cfg.fednew_config()
    n = source.n_clients
    solver = fednew.solver(fcfg)
    rec = _recorder(tracer)

    # Round 0 state comes from fednew.init on the first cohort — the same
    # builder the engine uses, so x0/dtype/codec-width defaults can't drift.
    ids0 = np.arange(cohort, dtype=np.int64) % n
    data0 = source.materialize(ids0)
    with _span(tracer, "init", schedule="barrier"):
        state = solver.init(obj, data0, key, x0)
    word = word_bits(state.x)
    curv_shape = np.asarray(state.curv).shape
    curv_dtype = np.asarray(state.curv).dtype

    # The cohort's data is an argument of the step, as in the engine's
    # compiled rounds: when the cohort IS the fleet this is the identical jit
    # trace to engine.run(mode="host"), which is what makes the sync
    # degeneracy bit-exact.
    jstep = jax.jit(lambda s, d: solver.step(s, obj, d))

    history: List[Any] = []
    round_time_s: List[float] = []
    up_totals: List[int] = []
    down_totals: List[int] = []
    contributors: List[int] = []
    x, y, k = state.x, state.y, state.key
    peak = 0
    t_total = 0.0
    for r in range(rounds):
        ids = (np.arange(cohort, dtype=np.int64) + r * cohort) % n
        data = data0 if r == 0 else source.materialize(ids)
        lam_rows, comm_rows = cache.gather(ids)
        st = fednew.FedNewState(
            x=x,
            y=y,
            lam=jnp.asarray(lam_rows),
            # Placeholder past round 0: hessian_period == 1 (enforced by
            # run_events) refreshes curvature from x inside the step, so
            # only the shape/dtype of this field matter.
            curv=state.curv if r == 0 else jnp.zeros(curv_shape, curv_dtype),
            comm=jnp.asarray(comm_rows),
            key=k,
            step=jnp.asarray(r, jnp.int32),
        )
        with _span(tracer, "dispatch", label="barrier_step", rounds=1):
            st2, m = jstep(st, data)
        x, y, k = st2.x, st2.y, st2.key
        cache.scatter(ids, np.asarray(st2.lam), np.asarray(st2.comm), r)
        history.append(jax.tree.map(np.asarray, m))

        up_msg = ledger.uplink(source.dim, word, r)
        down_msg = ledger.downlink(source.dim, word, r)
        mask = np.zeros(n, dtype=np.float64)
        mask[ids] = 1.0
        dt = _barrier_time(fleet, mask, up_msg, down_msg)
        if rec is not None:
            for cid in ids:
                _client_bar(rec, fleet, int(cid), t_total, up_msg, down_msg,
                            round=r)
            rec.sim_instant("server_step", t_total + dt, round=r)
        t_total += dt
        round_time_s.append(dt)
        up_totals.append(up_msg * len(ids))
        down_totals.append(down_msg * len(ids))
        contributors.append(len(ids))
        # Resident accounting: cache rows + this round's working set (data
        # and the cohort-shaped state rows). Nothing here scales with n.
        working = sum(
            np.asarray(l).nbytes for l in jax.tree.leaves((data, st2))
        )
        peak = max(peak, cache.resident_bytes + working)

    metrics = jax.tree.map(lambda *xs: np.stack(xs), *history)
    metric_lists = {
        name: [float(v) for v in vals]
        for name, vals in zip(metrics._fields, metrics)
    }
    return EventsResult(
        x=np.asarray(x),
        metrics=metric_lists,
        round_time_s=round_time_s,
        uplink_bits_total=up_totals,
        downlink_bits_total=down_totals,
        contributors=contributors,
        n_server_steps=rounds,
        # summed as netsim.simulate_rounds sums (Python's compensated sum),
        # not as the running clock above, so the two totals agree exactly
        simulated_time_s=float(sum(round_time_s)),
        peak_state_bytes=peak,
        n_spills=cache.n_spills,
        n_restores=cache.n_restores,
    )


def _barrier_time(
    fleet: sim.ClientFleet, mask: np.ndarray, up: int, down: int
) -> float:
    """Slowest sampled client's service time. With all-zero compute this IS
    ``netsim.round_time_s(fleet.links, up, down, mask)`` bit for bit: the
    per-client terms are the same expression in the same order and
    ``t + 0.0 == t`` exactly for finite IEEE floats."""
    active = mask > 0
    if not active.any():
        return 0.0
    links = fleet.links
    t = (
        down / links.downlink_bps[active]
        + up / links.uplink_bps[active]
        + 2.0 * links.latency_s[active]
        + fleet.compute_s[active]
    )
    return float(t.max())


# ---------------------------------------------------------------------------
# async schedule (buffer_size == K >= 1): the discrete-event simulation
# ---------------------------------------------------------------------------


def _async_run(
    cfg: FedNewAsyncConfig,
    obj: Objective,
    source,
    fleet: sim.ClientFleet,
    server_steps: int,
    cohort: int,
    key,
    x0,
    cache: CohortCache,
    ledger,
    eval_cohort: int,
    trace: Optional[arrivals_lib.ArrivalTrace],
    dropout_prob: float,
    seed: int,
    tracer=None,
) -> EventsResult:
    fcfg = cfg.fednew_config()
    K = cfg.buffer_size
    n = source.n_clients
    codec = fcfg.build_codec()
    rec = _recorder(tracer)

    # Iterate bookkeeping. Versions are server steps; per-version (x, y)
    # pairs are kept only while some in-flight or buffered client references
    # them — the history is bounded by inflight + K, never by steps.
    ids_probe = np.arange(1, dtype=np.int64)
    data_probe = source.materialize(ids_probe)
    with _span(tracer, "init", schedule="async"):
        probe_state = fednew.init(obj, data_probe, fcfg, key, x0)
    x = np.asarray(probe_state.x)
    dtype = x.dtype
    word = word_bits(probe_state.x)
    y = np.zeros_like(x)
    rng_key = probe_state.key
    version = 0
    hist: Dict[int, Any] = {0: (x, y)}
    refcount: Dict[int, int] = {0: 0}

    eval_data = source.materialize(_eval_ids(n, eval_cohort))
    eval_loss = jax.jit(lambda xx: obj.global_loss(xx, eval_data))

    needs_rng = codec.needs_rng

    @jax.jit
    def _flush_fn(xx, lam_rows, comm_rows, x_rows, y_rows, stale, keys, data,
                  step):
        y_i_tx, new_comm = fedbuff.client_update_rows(
            cfg, obj, data, x_rows, y_rows, lam_rows, comm_rows,
            keys if needs_rng else None, step,
        )
        new_x, y_bar, new_lam = fedbuff.flush(cfg, xx, lam_rows, y_i_tx, stale)
        return new_x, y_bar, new_lam, new_comm

    esim = sim.EventSim(dropout_prob=dropout_prob, seed=seed)
    busy: set = set()
    next_cid = 0  # closed-loop round-robin cursor
    closed_loop = trace is None

    down_spent = 0  # exact ints accumulated between flushes
    up_spent = 0
    buffer: List[Any] = []  # (cid, version)

    def _retain(v):
        refcount[v] = refcount.get(v, 0) + 1

    def _release(v):
        refcount[v] -= 1
        if refcount[v] == 0 and v != version:
            del refcount[v]
            del hist[v]

    def _dispatch(cid: int) -> None:
        nonlocal down_spent
        if cid in busy:
            return  # still working on an earlier dispatch (re-connect noise)
        busy.add(cid)
        up_msg = ledger.uplink(source.dim, word, version)
        down_msg = ledger.downlink(source.dim, word, version)
        down_spent += down_msg  # broadcast happens whether or not it returns
        _retain(version)
        ok = esim.dispatch(
            fleet, cid, up_msg, down_msg, (cid, version, up_msg)
        )
        if not ok:
            busy.discard(cid)
            _release(version)
        elif rec is not None:
            _client_bar(rec, fleet, cid, esim.now_s, up_msg, down_msg,
                        version=version)

    if closed_loop:
        for _ in range(min(cohort, n)):
            _dispatch(next_cid)
            next_cid = (next_cid + 1) % n
    else:
        for t, cid in zip(trace.times_s, trace.client_ids):
            esim.push(float(t), sim.ARRIVE, int(cid))

    history_rows: List[Dict[str, float]] = []
    round_time_s: List[float] = []
    up_totals: List[int] = []
    down_totals: List[int] = []
    contributors: List[int] = []
    peak = 0
    last_flush_t = 0.0

    while version < server_steps:
        ev = esim.pop()
        if ev is None:
            break  # trace exhausted before reaching server_steps
        t, kind, payload = ev
        if kind == sim.ARRIVE:
            _dispatch(int(payload))
            continue
        cid, v_disp, up_msg = payload
        busy.discard(cid)
        up_spent += up_msg
        buffer.append((cid, v_disp))
        if closed_loop:
            _dispatch(next_cid)
            next_cid = (next_cid + 1) % n
        if len(buffer) < K:
            continue

        # ---- the K-th landing: one staleness-weighted server step --------
        ids = np.asarray([c for c, _ in buffer], dtype=np.int64)
        versions = np.asarray([v for _, v in buffer], dtype=np.int64)
        data = source.materialize(ids)
        lam_rows, comm_rows = cache.gather(ids)
        x_rows = np.stack([hist[int(v)][0] for v in versions])
        y_rows = np.stack([hist[int(v)][1] for v in versions])
        stale = (version - versions).astype(np.float32)
        if needs_rng:
            rng_key, sub = jax.random.split(rng_key)
            keys = comm.client_keys(sub, K, None, None)
        else:
            keys = jnp.zeros((K, 2), jnp.uint32)  # unused placeholder
        with _span(tracer, "dispatch", label="async_flush", rounds=1):
            new_x, y_bar, new_lam, new_comm = _flush_fn(
                jnp.asarray(x), jnp.asarray(lam_rows), jnp.asarray(comm_rows),
                jnp.asarray(x_rows), jnp.asarray(y_rows), jnp.asarray(stale),
                keys, data, jnp.asarray(version, jnp.int32),
            )
        cache.scatter(ids, np.asarray(new_lam), np.asarray(new_comm), version)
        for _, v in buffer:
            _release(int(v))
        buffer.clear()
        x, y = np.asarray(new_x), np.asarray(y_bar)
        version += 1
        hist[version] = (x, y)
        refcount.setdefault(version, 0)
        # Prune the just-vacated old head if nothing references it anymore.
        for v in [v for v, c in list(refcount.items())
                  if c == 0 and v != version]:
            del refcount[v]
            del hist[v]

        if rec is not None:
            rec.sim_instant(
                "server_step", t, version=version,
                staleness_mean=float(stale.mean()),
                staleness_max=float(stale.max()),
            )
        with _span(tracer, "eval", version=version):
            loss_now = float(eval_loss(jnp.asarray(x)))
        history_rows.append({
            "loss": loss_now,
            "direction_norm": float(np.linalg.norm(y)),
            "staleness_mean": float(stale.mean()),
            "staleness_max": float(stale.max()),
        })
        round_time_s.append(t - last_flush_t)
        last_flush_t = t
        up_totals.append(up_spent)
        down_totals.append(down_spent)
        contributors.append(K)
        up_spent = down_spent = 0

        working = sum(
            np.asarray(l).nbytes for l in jax.tree.leaves(data)
        ) + lam_rows.nbytes + comm_rows.nbytes + x_rows.nbytes + y_rows.nbytes
        hist_bytes = sum(hx.nbytes + hy.nbytes for hx, hy in hist.values())
        peak = max(peak, cache.resident_bytes + working + hist_bytes)

    metric_lists: Dict[str, List[float]] = {
        k: [row[k] for row in history_rows]
        for k in (history_rows[0] if history_rows else {})
    }
    return EventsResult(
        x=x,
        metrics=metric_lists,
        round_time_s=round_time_s,
        uplink_bits_total=up_totals,
        downlink_bits_total=down_totals,
        contributors=contributors,
        n_server_steps=len(round_time_s),
        simulated_time_s=last_flush_t,
        peak_state_bytes=peak,
        n_dropped=esim.n_dropped,
        n_spills=cache.n_spills,
        n_restores=cache.n_restores,
    )


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def run_events(
    cfg: FedNewAsyncConfig,
    obj: Objective,
    data_or_source,
    fleet: sim.ClientFleet,
    *,
    server_steps: int,
    cohort: int,
    key=None,
    x0=None,
    arrival_trace: Optional[arrivals_lib.ArrivalTrace] = None,
    dropout_prob: float = 0.0,
    seed: int = 0,
    cache_capacity: int = 4096,
    checkpoint_dir: Optional[str] = None,
    eval_cohort: int = 64,
    tracer=None,
) -> EventsResult:
    """Run ``server_steps`` server steps of event-driven FedNew.

    ``cfg.buffer_size == 0`` runs the synchronous barrier schedule over
    round-robin cohorts of ``cohort`` clients; ``buffer_size == K >= 1``
    runs the buffered-asynchronous event loop with ``cohort`` concurrent
    in-flight clients (closed loop) or the given ``arrival_trace``
    (open loop). Requires ``hessian_period == 1``: event-mode curvature is
    stateless — every client re-anchors at the iterate it was dispatched
    (the re-derivation contract that makes O(sampled) memory possible)."""
    if cfg.hessian_period != 1:
        raise ValueError(
            "events mode requires hessian_period=1: clients re-derive "
            "curvature from the dispatch iterate (stateless streaming); a "
            f"period of {cfg.hessian_period} would need fleet-resident "
            "curvature state"
        )
    if server_steps < 1:
        raise ValueError(f"server_steps must be >= 1, got {server_steps}")
    source = as_source(data_or_source)
    n = source.n_clients
    if not 1 <= cohort <= n:
        raise ValueError(f"cohort must be in [1, {n}], got {cohort}")
    if cfg.buffer_size > cohort and arrival_trace is None:
        raise ValueError(
            f"buffer_size={cfg.buffer_size} can never fill with only "
            f"cohort={cohort} closed-loop in-flight clients"
        )
    if fleet.n_clients != n:
        raise ValueError(
            f"fleet describes {fleet.n_clients} clients, source has {n}"
        )
    key = jax.random.PRNGKey(0) if key is None else key
    fcfg = cfg.fednew_config()
    codec = fcfg.build_codec()
    width = _comm_width(codec, source.dim, jnp.float32)
    cache = CohortCache(
        source.dim, width, capacity=cache_capacity, spill_dir=checkpoint_dir
    )
    ledger = fedbuff.ledger(cfg)
    if cfg.buffer_size == 0:
        if arrival_trace is not None:
            raise ValueError(
                "the barrier schedule (buffer_size=0) is closed-loop "
                "round-robin; arrival traces need buffer_size >= 1"
            )
        if dropout_prob:
            raise ValueError(
                "the barrier schedule has no dropout model (a synchronous "
                "round waits for every sampled client); use buffer_size >= 1"
            )
        return _barrier_run(
            cfg, obj, source, fleet, server_steps, cohort, key, x0, cache,
            ledger, eval_cohort, tracer=tracer,
        )
    return _async_run(
        cfg, obj, source, fleet, server_steps, cohort, key, x0, cache,
        ledger, eval_cohort, arrival_trace, dropout_prob, seed,
        tracer=tracer,
    )
