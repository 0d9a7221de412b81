"""One run of one cell: set-up, the timed window, the traced window and the
comparison with the plain reference.

The window drives ``repro.core.engine.run`` on the solver and objective that
``repro.api.build`` makes from the cell's ``ExperimentSpec``, on the
benchmark's own data: one training job from a fresh round-0 state, in scan
blocks of the configuration's size, each blocked to completion. The engine's
telemetry hook (``tracer``) marks every block's end on the host clock and
hands over the carry after the first block, which is what the reference is
compared with.
"""

from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import json
import math
import os
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

import datagen
import peaks as peaks_lib
import reference
import registry
import devtrace

CACHE_DIR = os.path.join(registry.BENCH_DIR, ".cache")
LN2 = math.log(2.0)  # f(x_0) at x_0 = 0 for logistic regression
MIN_BLOCKS = 4  # a window holds at least this many scan blocks
TRACE_SECONDS = 0.5  # length of the traced job's steady part


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, for every program however fast it compiles, so that only a
    checkout's first run compiles."""
    path = os.path.join(CACHE_DIR, "jax")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def devices_for(chips: int) -> list:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"needs {chips} chips; JAX found {len(devs)}")
    return devs[:chips]


class BlockClock:
    """The engine's ``tracer`` hook: a profiler annotation around each host
    phase (so idle gaps in a device trace can be named), the host time at
    which each dispatched block completed, and a copy of the model after
    the first block."""

    wants_profile = True

    def __init__(self):
        self.block_end = []
        self.after_first = None
        self._offered = 0

    @contextlib.contextmanager
    def span(self, name, **_):
        with jax.profiler.TraceAnnotation(f"bench.{name}"):
            yield
        if name == "dispatch":
            self.block_end.append(time.perf_counter())

    def profile_dispatch(self, label, jitted, carry, *rest):
        if self._offered == 1:
            state = carry[0] if isinstance(carry, tuple) and not hasattr(carry, "x") else carry
            self.after_first = jnp.copy(state.x)
        self._offered += 1


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict

    @property
    def block(self) -> int:
        return self.config["block_size"]

    @property
    def hparams(self) -> dict:
        """Solver hyperparameters with the objective's mu, for the reference
        and the work counters."""
        hp = dict(self.config["solver"]["hparams"])
        hp.setdefault("hessian_repr", "dense")
        hp.setdefault("hessian_period", 1)
        hp["mu"] = self.config["objective"]["mu"]
        hp.update(self.config.get("work", {}))
        return hp

    @property
    def codec(self) -> dict:
        return self.traffic["codec"]


def load_cell(name: str) -> Cell:
    w = registry.cell(name)
    cfg = registry.config(w["config"])
    traffic = registry.traffic(w["traffic"])
    if traffic["partition"] != "iid":
        raise ValueError(f"{name}: datagen lays out only the iid partition, "
                         f"not {traffic['partition']!r}")
    return Cell(name=name, chips=w["chips"], config=cfg, traffic=traffic,
                limits=registry.limits(name))


def build_program(cell: Cell):
    """(objective, solver) as ``repro.api.build`` makes them from the
    cell's spec: the configuration's objective and solver, the traffic's
    codec and participation."""
    from repro import api
    from repro.api import build

    cfg, tr = cell.config, cell.traffic
    codec = dict(tr["codec"])
    spec = api.ExperimentSpec(
        name=cell.name,
        objective=api.ObjectiveSpec(**cfg["objective"]),
        solver=api.SolverSpec(cfg["solver"]["name"], dict(cfg["solver"]["hparams"])),
        schedule=api.ScheduleSpec(block_size=cfg["block_size"], mode="scan"),
        participation=api.ParticipationSpec(**tr["participation"]),
        compression=None if codec["name"] == "identity" else api.CompressionSpec(
            codec=codec.pop("name"), params=codec),
    )
    obj = build.build_objective(spec.objective)
    build.check_solver_objective(spec, obj)
    return obj, build.build_solver(spec.solver, spec.compression), build.build_participation(spec)


@dataclasses.dataclass
class Job:
    rounds: int
    block_end: list  # host seconds from the job's start, one per block
    loss: np.ndarray
    direction_norm: np.ndarray
    bits: np.ndarray
    x_first_block: np.ndarray

    def outputs(self) -> dict:
        """What :func:`compare` reads of the job."""
        return {"loss": self.loss, "direction_norm": self.direction_norm,
                "x": self.x_first_block}


def run_job(cell, obj, solver, part, data, key, rounds, mesh) -> Job:
    """One training job from a fresh round-0 state through the engine."""
    from repro.core import engine

    clock = BlockClock()
    t0 = time.perf_counter()
    state, m = engine.run(solver, obj, data, rounds, key=key, mode="scan",
                          block_size=cell.block, mesh=mesh, participation=part,
                          timings=[], tracer=clock)
    x1 = clock.after_first if rounds > cell.block else state.x
    return Job(
        rounds=rounds, block_end=[t - t0 for t in clock.block_end],
        loss=np.asarray(m.loss), direction_norm=np.asarray(m.direction_norm),
        bits=np.asarray(m.uplink_bits_per_client),
        x_first_block=None if x1 is None else np.asarray(x1),
    )


def f_star(cell: Cell, A, b) -> dict:
    """f(x*) from the plain reference at float32, highest matmul precision,
    with the norm of the global gradient where it stopped; cached per
    configuration inside the checkout (every seed lays out the same
    dataset, ``datagen``)."""
    cfg = cell.config
    tag = hashlib.sha256(json.dumps(
        [cfg["geometry"], cfg["generator"], cfg["objective"], cfg["fstar"]],
        sort_keys=True).encode()).hexdigest()[:16]
    path = os.path.join(CACHE_DIR, "fstar", f"{cfg['name']}-{tag}.json")
    if os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    fs = cfg["fstar"]
    mu = cfg["objective"]["mu"]
    with jax.default_matmul_precision("highest"):
        if fs["method"] == "newton":
            _, f, g = reference.newton_dense(A, b, mu=mu, steps=fs["steps"])
        else:
            _, f, g = reference.newton_cg(A, b, mu=mu, steps=fs["steps"],
                                          cg_iters=fs["cg_iters"])
    out = {"f_star": float(f), "grad_norm": float(g)}
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as fh:
        json.dump(out, fh)
    return out


def reference_rounds(cell: Cell, A, b, key, dtype) -> dict:
    """The first block's rounds of the plain reference (float32 at highest
    precision; bfloat16 for the control)."""
    hp = cell.hparams
    keys = ("mu", "rho", "alpha", "hessian_period", "hessian_repr", "cg_iters")
    hpt = tuple((k, hp.get(k)) for k in keys)
    codec = tuple(sorted(cell.codec.items()))
    with jax.default_matmul_precision("highest"):
        loss, dn, x = reference.fednew_rounds(
            A, b, key, hp=hpt, codec=codec, rounds=cell.block, dtype=dtype)
    return {"loss": np.asarray(loss), "direction_norm": np.asarray(dn),
            "x": np.asarray(x)}


def rel_gap(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def compare(cell: Cell, prog: dict, ref: dict) -> dict:
    """The numbers compared, each a relative gap to the reference over the
    first block: the worst per-round loss, the first direction's norm, and
    the norm of the model's change after the block (from 0)."""
    K = cell.block
    loss = max(rel_gap(float(p), float(r))
               for p, r in zip(prog["loss"][:K], ref["loss"][:K]))
    dir1 = rel_gap(float(prog["direction_norm"][0]), float(ref["direction_norm"][0]))
    xnorm = rel_gap(float(np.linalg.norm(prog["x"])), float(np.linalg.norm(ref["x"])))
    out = {"loss": loss, "dir1": dir1, "xnorm": xnorm}
    return {k: (v if math.isfinite(v) else float("inf")) for k, v in out.items()}


@dataclasses.dataclass
class Record:
    """What the per-layer readers see of a run."""

    cell: Cell
    peaks: peaks_lib.Peaks
    chips: int
    round_ms: float
    rounds_to_gap: Optional[int]
    block_s: list
    trace: Optional[devtrace.Summary]

    def work(self, name: str):
        return registry.work(name).work(self.cell.config["geometry"], self.cell.hparams)

    def kernel_roofline(self, name: str):
        """Least time of one kernel call over its measured device time per
        call (per chip), as a percentage; None when the trace holds none."""
        if self.trace is None or not self.trace.calls(name):
            return None
        flops, nbytes = registry.work(name).work(
            self.cell.config["geometry"], self.cell.hparams)
        least = max(flops / self.peaks.flops_bf16, nbytes / self.peaks.hbm_bw)
        per_call = self.trace.time(name) / self.trace.calls(name)
        return 100.0 * least / self.chips / per_call


@dataclasses.dataclass
class Prepared:
    obj: object
    solver: object
    part: object
    data: object
    mesh: object
    run_key: jax.Array


def prepare(cell: Cell, seed: int, devs: list) -> Prepared:
    """The program's objective and solver, the client mesh for a multi-chip
    cell, and the cell's data from ``seed``, generated on the device."""
    from repro.core.objectives import ClientDataset

    mesh = sharding = None
    if cell.chips > 1:
        from repro.launch.mesh import make_client_mesh

        mesh = make_client_mesh(cell.chips)
        sharding = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(mesh.axis_names[0]))
    order_key, run_key = jax.random.split(datagen.seed_key(seed))
    obj, solver, part = build_program(cell)
    A, b = jax.block_until_ready(datagen.make(cell.config, order_key, sharding))
    return Prepared(obj, solver, part, ClientDataset(features=A, labels=b), mesh, run_key)


def run_cell(name: str, seed: int, seconds: float, traced: bool, *, t_process: float):
    """Everything one command-line run does; returns (result dict, checks).
    Raises :class:`NoChip` before any work where the chips are missing."""
    cell = load_cell(name)
    devs = devices_for(cell.chips)
    enable_compile_cache()
    pk = peaks_lib.peaks(devs[0].device_kind)

    p = prepare(cell, seed, devs)
    obj, solver, part, data, mesh, run_key = p.obj, p.solver, p.part, p.data, p.mesh, p.run_key
    A, b = data.features, data.labels

    # Warm-up: the window's own block shape, compiled (or loaded) and run.
    warm = run_job(cell, obj, solver, part, data, run_key, 3 * cell.block, mesh)
    block_s = min(np.diff(warm.block_end))
    n_blocks = max(MIN_BLOCKS, math.ceil(seconds / block_s))
    setup_s = time.perf_counter() - t_process

    job = run_job(cell, obj, solver, part, data, run_key, n_blocks * cell.block, mesh)
    B, R = cell.block, job.rounds
    round_ms = 1e3 * (job.block_end[-1] - job.block_end[0]) / (R - B)
    mem_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs)

    summary = None
    if traced:
        tr_blocks = max(MIN_BLOCKS, min(n_blocks, math.ceil(TRACE_SECONDS / block_s) + 1))
        summary = devtrace.record(
            lambda: run_job(cell, obj, solver, part, data, run_key, tr_blocks * B, mesh),
            os.path.join(CACHE_DIR, "trace"), rounds_per_block=B,
            chips=len(devs))

    # The reference runs once the window has closed and its memory is read.
    fstar = f_star(cell, A, b)["f_star"]
    gap = (job.loss - fstar) / (LN2 - fstar)
    hit = np.nonzero(gap <= cell.config["gap_target"])[0]
    rounds_to_gap = int(hit[0]) + 1 if hit.size else None
    time_to_gap = job.block_end[(rounds_to_gap - 1) // B] if hit.size else None
    ref = reference_rounds(cell, A, b, run_key, jnp.float32)
    numbers = compare(cell, job.outputs(), ref)
    exact = reference.uplink_bits(cell.codec, cell.config["geometry"]["dim"])
    numbers["bits"] = float(np.max(np.abs(job.bits.astype(np.float64) - exact)))
    numbers["final_gap"] = float(gap[-1]) if math.isfinite(gap[-1]) else float("inf")
    limits = dict(cell.limits["limits"])
    limits["final_gap"] = cell.config["gap_target"]
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    failed = int(np.sum(~np.isfinite(job.loss)))

    rec = Record(cell=cell, peaks=pk, chips=len(devs), round_ms=round_ms,
                 rounds_to_gap=rounds_to_gap, block_s=list(np.diff(job.block_end)),
                 trace=summary)
    if traced:
        metrics = {}
        for m in registry.per_layer_for(name):
            mod = registry.metric(m["name"])
            if (mod.UNIT, mod.LAYER, mod.MOVES) != (m["unit"], m["layer"], m["moves"]):
                raise ValueError(f"metrics/{m['name']}.py disagrees with BENCHMARK.json")
            v = mod.read(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = {"round_ms": round_ms, "time_to_gap_s": time_to_gap, "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in registry.end_to_end_for(name) if values[m["name"]] is not None}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(mem_peak)}
    result = {"correct": bool(correct), "attempted": R, "failed": failed,
              "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
    result["checks"] = checks
    return result, checks
