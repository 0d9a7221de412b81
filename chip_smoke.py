"""Run FedNew's main path once on a TPU and check what comes out.

    python3 chip_smoke.py              # phases A-D on one chip
    python3 chip_smoke.py --chips 4    # the client-mesh path on four chips

Everything runs in this one process (a chip belongs to one process at a
time) and goes through ``repro.api``, the entry point users call:

  A  dense FedNew, w8a geometry (60 clients x 829 samples x d=267, float32),
     scan-compiled: ``backend="auto"`` must resolve to the compiled Pallas
     eq. 9 kernel, and its trajectory must track the Cholesky reference.
  B  Q-FedNew at 3 bits on the same data: the Pallas quantizer against the
     jnp reference, with identical integer levels, an identical trajectory
     and an exact 3·d+32-bit uplink ledger.
  C  matrix-free FedNew at d=1e5 on ~3.3 GB of client data.
  D  the LM objective at ``examples/specs/lm_tiny.json`` size: shows only
     that the autodiff-HVP step lowers and runs on the chip.

``--chips 4`` runs FedNew and Q-FedNew on a 4-chip ``('clients',)`` mesh
against the scan schedule on one chip, and nothing else.

Each phase prints one line of results. The seconds in it are informational,
not a measurement. Any failed check raises, so the exit code is nonzero;
the last line of a run that passes is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The script refuses to run where JAX finds no TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SEED = 0
W8A_HPARAMS = {"rho": 0.1, "alpha": 0.03, "hessian_period": 1}  # paper_logreg
ROUNDS, BLOCK = 20, 5
# CG (32 fixed iterations, in-kernel) vs a direct Cholesky solve: per-round
# losses agree to this relative tolerance, not bitwise.
DENSE_RTOL = 1e-3
# 4-chip mesh vs one-chip scan: the eq. 13 sums associate differently.
MESH_RTOL = 1e-4
# Phase C: 16 clients x 512 samples x d=1e5 float32 = 3.3 GB of features.
MATFREE_SHAPE = (16, 512, 100_000)
MATFREE_HPARAMS = {"rho": 1.0, "alpha": 1.0, "hessian_period": 1,
                   "hessian_repr": "matfree", "cg_iters": 8}


def _w8a_spec(name: str, solver: str, hparams: dict, rounds: int = ROUNDS):
    from repro import api

    return api.ExperimentSpec(
        name=name,
        seed=SEED,
        objective=api.ObjectiveSpec(kind="logreg", mu=1e-3),
        partition=api.PartitionSpec(dataset="w8a", seed=SEED, dtype="float32"),
        solver=api.SolverSpec(solver, hparams),
        schedule=api.ScheduleSpec(rounds=rounds, block_size=BLOCK, mode="scan"),
    )


def _resolved(spec) -> dict:
    """The execution mode each FedNew hot loop resolves to for this spec."""
    from repro.api import build
    from repro.core import fednew
    from repro.kernels import dispatch

    cfg = fednew.FedNewConfig(
        **build._merged_solver_hparams(spec.solver, spec.compression)
    )
    solve = dispatch.resolve_backend(cfg.resolved_solve_backend)
    if cfg.matfree:  # the logreg objective gives the glm_hvp kernel its split
        solve = "cg-on-glm_hvp" if dispatch.use_pallas(solve) else "cg-on-hvp"
    return {
        "solve": solve,
        "quant": dispatch.resolve_backend(cfg.resolved_quant_backend)
        if cfg.codec_spec["name"] == "stoch_quant" else "none",
    }


def _step_program(spec):
    """One round of the spec's solver, compiled from shapes alone (nothing
    is allocated): the program ``engine.run`` scans over."""
    import jax

    from repro.api import build

    obj = build.build_objective(spec.objective)
    data = jax.eval_shape(
        lambda: build.build_dataset(spec.objective, spec.partition)
    )
    solver = build.build_solver(spec.solver, spec.compression)
    state = jax.eval_shape(
        lambda d: solver.init(obj, d, jax.random.PRNGKey(spec.seed)), data
    )
    step = jax.jit(lambda s, d: solver.step(s, obj, d))
    return step.lower(state, data).compile()


def _check_trajectory(res, label: str) -> list:
    import numpy as np

    loss = np.asarray(res.metrics["loss"])
    assert np.all(np.isfinite(loss)), f"{label}: non-finite loss {loss}"
    assert loss[-1] < loss[0], f"{label}: loss did not decrease {loss}"
    return loss


def _seconds(res) -> str:
    return (f"informational: compile {res.compile_s:.2f}s "
            f"({res.compile_rounds} rounds), steady {res.steady_wall_clock_s:.3f}s "
            f"({res.steady_rounds} rounds)")


def _emit(phase: str, **fields) -> None:
    print(f"phase {phase} " + json.dumps(fields), flush=True)


def phase_a(device_kind: str) -> None:
    """Dense FedNew: compiled Pallas eq. 9 solve vs the Cholesky reference."""
    import numpy as np

    from repro import api

    spec = _w8a_spec("chip-A", "fednew", {**W8A_HPARAMS, "backend": "auto"})
    ref_spec = _w8a_spec("chip-A-ref", "fednew",
                         {**W8A_HPARAMS, "backend": "reference"})
    backends = _resolved(spec)
    assert backends["solve"] == "pallas", backends
    assert "tpu_custom_call" in _step_program(spec).as_text(), (
        "the auto step program holds no Pallas kernel"
    )
    res, ref = api.run(spec), api.run(ref_spec)
    loss = _check_trajectory(res, "A auto")
    ref_loss = _check_trajectory(ref, "A reference")
    rel = float(np.max(np.abs(loss - ref_loss) / np.abs(ref_loss)))
    assert rel <= DENSE_RTOL, f"A: auto vs reference rel diff {rel}"
    d = res.dim
    assert all(b == 32 * d for b in res.metrics["uplink_bits_per_client"])
    _emit("A", what="dense FedNew, w8a 60x829x267 f32, scan",
          device=device_kind, backends=backends,
          reference_backends=_resolved(ref_spec),
          loss_first=float(loss[0]), loss_last=float(loss[-1]),
          ref_loss_last=float(ref_loss[-1]), max_rel_diff=rel,
          rtol=DENSE_RTOL, uplink_bits_per_client_round=32 * d,
          auto=_seconds(res), reference=_seconds(ref))


def phase_b(device_kind: str) -> None:
    """Q-FedNew 3 bits: Pallas quantizer vs jnp reference, bit for bit."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import api
    from repro.kernels import dispatch

    hp = {**W8A_HPARAMS, "bits": 3, "backend": "auto"}
    spec = _w8a_spec("chip-B", "q-fednew", hp)
    ref_spec = _w8a_spec("chip-B-ref", "q-fednew",
                         {**hp, "quant_backend": "reference"})
    backends = _resolved(spec)
    assert backends == {"solve": "pallas", "quant": "pallas"}, backends
    assert _resolved(ref_spec)["quant"] == "reference"

    # integer levels on the wire: same keys, same inputs, both paths
    n, d = 60, 267
    ky, kp, kk = jax.random.split(jax.random.PRNGKey(SEED), 3)
    y = jax.random.normal(ky, (n, d), jnp.float32)
    prev = 0.1 * jax.random.normal(kp, (n, d), jnp.float32)
    keys = jax.random.split(kk, n)
    lv = {b: np.asarray(dispatch.quantize_with_keys(
        keys, y, prev, 3, backend=b).levels) for b in ("auto", "reference")}
    np.testing.assert_array_equal(lv["auto"], lv["reference"])

    res, ref = api.run(spec), api.run(ref_spec)
    loss = _check_trajectory(res, "B auto")
    _check_trajectory(ref, "B reference")
    for name in res.metrics:
        np.testing.assert_array_equal(
            np.asarray(res.metrics[name]), np.asarray(ref.metrics[name]),
            err_msg=f"B: metric {name} differs between quantizer backends",
        )
    per_client = 3 * res.dim + 32
    assert res.uplink_bits_total == [per_client * n] * ROUNDS
    assert all(b == per_client for b in res.metrics["uplink_bits_per_client"])
    _emit("B", what="Q-FedNew 3 bits, w8a 60x829x267 f32, scan",
          device=device_kind, backends=backends,
          reference_backends=_resolved(ref_spec),
          levels_identical=True, trajectory_identical=True,
          loss_first=float(loss[0]), loss_last=float(loss[-1]),
          uplink_bits_per_client_round=per_client,
          auto=_seconds(res), reference=_seconds(ref))


def phase_c(device_kind: str) -> None:
    """Matrix-free FedNew at d=1e5 on a few GB of client data."""
    import jax

    from repro import api

    n, m, d = MATFREE_SHAPE
    spec = api.ExperimentSpec(
        name="chip-C",
        seed=SEED,
        objective=api.ObjectiveSpec(kind="logreg", mu=1e-3),
        partition=api.PartitionSpec(
            dataset="custom", seed=SEED, dtype="float32",
            n_clients=n, samples_per_client=m, dim=d,
        ),
        solver=api.SolverSpec("fednew", MATFREE_HPARAMS),
        schedule=api.ScheduleSpec(rounds=4, block_size=2, mode="scan"),
    )
    mem = _step_program(spec).memory_analysis()
    step_bytes = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    limit = jax.devices()[0].memory_stats()["bytes_limit"]
    assert step_bytes < limit, f"C: step needs {step_bytes} of {limit} bytes"
    res = api.run(spec)
    loss = _check_trajectory(res, "C")
    _emit("C", what=f"matrix-free FedNew, {n}x{m}x{d} f32, scan",
          device=device_kind, backends=_resolved(spec),
          step_argument_bytes=mem.argument_size_in_bytes,
          step_temp_bytes=mem.temp_size_in_bytes, device_bytes_limit=limit,
          loss_first=float(loss[0]), loss_last=float(loss[-1]),
          uplink_bits_per_client_round=res.uplink_bits_total[0] // n,
          auto=_seconds(res))


def phase_d(device_kind: str) -> None:
    """The LM objective at CI size: the autodiff-HVP step lowers and runs."""
    import numpy as np

    from repro import api

    with open(os.path.join(ROOT, "examples", "specs", "lm_tiny.json")) as f:
        spec = api.ExperimentSpec.from_dict(json.load(f))
    res = api.run(spec)
    loss = np.asarray(res.metrics["loss"])
    assert np.all(np.isfinite(loss)), f"D: non-finite loss {loss}"
    _emit("D", what="LM objective at lm_tiny.json size (lowers and runs; "
          "CI size, not a measurement)",
          device=device_kind, arch=spec.objective.arch, params=res.dim,
          loss_first=float(loss[0]), loss_last=float(loss[-1]),
          uplink_bits_per_client_round=res.uplink_bits_total[0] // res.n_clients,
          auto=_seconds(res))


def phase_mesh(device_kind: str, n_dev: int) -> None:
    """FedNew and Q-FedNew on an n_dev-chip client mesh vs one-chip scan."""
    import jax
    import numpy as np

    from repro import api
    from repro.api import build
    from repro.launch.mesh import make_client_mesh

    assert len(jax.devices()) >= n_dev, jax.devices()
    mesh = make_client_mesh(n_dev)
    for solver, hp in (("fednew", W8A_HPARAMS),
                       ("q-fednew", {**W8A_HPARAMS, "bits": 3})):
        spec = _w8a_spec(f"chip-mesh-{solver}", solver, hp)
        obj, data = build.build_problem(spec)
        run = lambda mesh_: api.run_components(
            solver, obj, data, ROUNDS, key=jax.random.PRNGKey(SEED),
            mesh=mesh_, block_size=BLOCK, **hp,
        )
        s_mesh, m_mesh = run(mesh)
        _, m_scan = run(None)
        devices = {sh.device for sh in s_mesh.lam.addressable_shards}
        rows = {sh.data.shape[0] for sh in s_mesh.lam.addressable_shards}
        assert len(devices) == n_dev, devices
        assert rows == {data.n_clients // n_dev}, rows
        assert len(s_mesh.lam.sharding.device_set) == n_dev
        loss, ref = np.asarray(m_mesh.loss), np.asarray(m_scan.loss)
        assert np.all(np.isfinite(loss)) and loss[-1] < loss[0], loss
        np.testing.assert_allclose(loss, ref, rtol=MESH_RTOL)
        np.testing.assert_array_equal(
            np.asarray(m_mesh.uplink_bits_per_client),
            np.asarray(m_scan.uplink_bits_per_client),
        )
        _emit(f"mesh[{solver}]",
              what=f"w8a 60x829x267 f32 on a {n_dev}-chip clients mesh "
                   "vs one-chip scan",
              device=device_kind, backends=_resolved(spec),
              carry_devices=len(devices), clients_per_device=rows.pop(),
              loss_first=float(loss[0]), loss_last=float(loss[-1]),
              max_rel_diff=float(np.max(np.abs(loss - ref) / np.abs(ref))),
              rtol=MESH_RTOL)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the client-mesh path, on four chips")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    kind = dev.device_kind
    if args.chips == 4:
        phase_mesh(kind, 4)
    else:
        for phase in (phase_a, phase_b, phase_c, phase_d):
            phase(kind)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": kind, "count": len(jax.devices()),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
