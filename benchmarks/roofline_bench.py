"""Roofline suite: achieved vs attainable FLOPs/bytes for everything the
engine dispatches.

Two sections, one record schema (``repro.telemetry.profile.roofline_record``):

  * **engine** — small FedNew runs (dense + matfree) profiled through the
    engine's ``tracer=`` hook: each distinct compiled block (scan blocks,
    host steps) is AOT-lowered, its optimized HLO walked by
    ``repro.roofline.hlo_cost``, and the fastest observed call supplies the
    achieved-rate denominator.
  * **kernels** — the FedNew hot ops (stochastic quantization, batched
    client solve) analyzed standalone, both the pure-XLA reference and the
    ``repro.kernels.dispatch`` path the engine actually calls.

The attainable ceiling is the running device's, from the ``device_kind``
table in ``repro.roofline.model``; a device that is not in the table (a CPU
runner) gets no attainable ceiling and no roofline share, only the HLO
counts and its own timings. Records go to ``benchmarks/out/`` only; schema
checked by scripts/check_roofline_artifact.py.

    TELEMETRY_SMOKE=1 PYTHONPATH=src python -m benchmarks.run --only roofline_bench
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from benchmarks.common import emit, save_json, timed
from repro.api import build, specs
from repro.core import engine, quantization
from repro.kernels import dispatch
from repro.kernels.client_solve.ref import client_solve_ref
from repro.roofline.model import device_peaks
from repro.telemetry import EngineTracer, analyze_jitted, roofline_record

SMOKE = os.environ.get("TELEMETRY_SMOKE", "0") == "1"
ROUNDS = int(os.environ.get("BENCH_ROUNDS", "4" if SMOKE else "12"))

_ENGINE_CASES = [
    ("fednew-dense", {"rho": 0.1, "alpha": 0.03, "hessian_period": 1}),
    ("fednew-matfree", {"rho": 0.1, "alpha": 0.03, "hessian_period": 1,
                        "hessian_repr": "matfree", "cg_iters": 16}),
]


def _problem():
    spec = specs.ExperimentSpec()
    return build.build_problem(spec)


def _engine_records():
    obj, data = _problem()
    records = []
    for label, hparams in _ENGINE_CASES:
        tracer = EngineTracer(profile=True)
        solver = engine.get_solver("fednew", **hparams)
        engine.run(
            solver, obj, data, ROUNDS,
            key=jax.random.PRNGKey(0), mode="scan",
            block_size=max(1, ROUNDS // 2), tracer=tracer,
        )
        for rec in tracer.roofline_records():
            rec = {"case": label, **rec}
            records.append(rec)
            emit(
                f"roofline/engine/{label}/{rec['label']}",
                (rec["seconds_per_call"] or 0.0) * 1e6, _derived(rec),
            )
    return records


def _derived(rec) -> str:
    return (f"bound={rec['bound']};ai={rec['arithmetic_intensity']:.2f};"
            f"frac={rec['achieved_fraction']}")


def _analyze_callable(label: str, fn, *args):
    """roofline_record for one jitted callable: AOT HLO analysis + fastest
    timed call. A lowering or compile error propagates."""
    jitted = jax.jit(fn)
    cost = analyze_jitted(jitted, *args)
    _, us = timed(lambda: jitted(*args), iters=3)
    rec = roofline_record(label, cost, us * 1e-6)
    emit(f"roofline/kernel/{label}", us, _derived(rec))
    return rec


def _kernel_records():
    records = []
    n, d, bits = 8, 1024, 3
    ky, kp, kk = jax.random.split(jax.random.PRNGKey(0), 3)
    y = jax.random.normal(ky, (n, d), jnp.float32)
    prev = jax.random.normal(kp, (n, d), jnp.float32) * 0.1
    keys = jax.random.split(kk, n)
    records.append(_analyze_callable(
        "quantize_ref",
        lambda k_, y_, p_: quantization.quantize_with_keys(k_, y_, p_, bits),
        keys, y, prev,
    ))
    records.append(_analyze_callable(
        "quantize_dispatch",
        lambda k_, y_, p_: dispatch.quantize_with_keys(
            k_, y_, p_, bits, backend="pallas"
        ),
        keys, y, prev,
    ))

    ds = 256
    kA, kb = jax.random.split(jax.random.PRNGKey(ds))
    Q = jnp.linalg.qr(jax.random.normal(kA, (n, ds, ds)))[0]
    eigs = jnp.broadcast_to(jnp.logspace(0, 1.5, ds)[None], (n, ds))
    A = jnp.einsum("nij,nj,nkj->nik", Q, eigs, Q)
    b = jax.random.normal(kb, (n, ds), jnp.float32)
    records.append(_analyze_callable(
        "client_solve_ref",
        lambda A_, b_: client_solve_ref(A_, b_, damping=1.0),
        A, b,
    ))
    records.append(_analyze_callable(
        "client_solve_dispatch",
        lambda A_, b_: dispatch.client_solve(
            A_, b_, damping=1.0, iters=64, backend="pallas"
        ),
        A, b,
    ))
    return records


def main():
    kind = jax.devices()[0].device_kind
    peaks = device_peaks(kind)
    results = {
        "config": {
            "smoke": SMOKE,
            "rounds": ROUNDS,
            "backend": jax.default_backend(),
            "device_kind": kind,
            "resolved_pallas_backend": dispatch.resolve_backend("pallas"),
            "peak_flops_bf16": None if peaks is None else peaks.flops_bf16,
            "hbm_bw": None if peaks is None else peaks.hbm_bw,
        },
        "engine": _engine_records(),
        "kernels": _kernel_records(),
    }
    save_json("roofline_bench.json", results)
    return results


if __name__ == "__main__":
    main()
