"""The plain reference against the program at a small size on the CPU,
where float32 arithmetic is exact enough for the two to agree closely, and
its f(x*) solvers against each other."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import reference
import registry
import tiny

CELLS = [w["name"] for w in registry.benchmark()["workloads"] if w["chips"] == 1]


def _program_and_reference(cell_name, seed=3):
    with tiny.harness_on_cpu():
        cell = harness.load_cell(cell_name)
    p = harness.prepare(cell, seed, jax.devices()[:1])
    job = harness.run_job(cell, p.obj, p.solver, p.part, p.data, p.run_key,
                          2 * cell.block, p.mesh)
    ref = harness.reference_rounds(cell, p.data.features, p.data.labels, p.run_key, jnp.float32)
    ctl = harness.reference_rounds(cell, p.data.features, p.data.labels, p.run_key, jnp.bfloat16)
    return cell, job, job.outputs(), ref, ctl


@pytest.mark.parametrize("cell_name", CELLS)
def test_reference_follows_the_program(cell_name):
    # Tolerance 1e-5: both sides compute in float32, the program through
    # its own kernels' CPU references (Cholesky, or CG on HVPs) and a
    # different summation order; 1e-5 is some 80 float32 ulps of a loss
    # near 0.3, and the control below reads hundreds of times more.
    cell, job, prog, ref, _ = _program_and_reference(cell_name)
    numbers = harness.compare(cell, prog, ref)
    assert max(numbers.values()) < 1e-5, numbers
    assert np.all(job.bits == reference.uplink_bits(cell.codec, cell.config["geometry"]["dim"]))


@pytest.mark.parametrize("cell_name", CELLS)
def test_bfloat16_control_fails_the_cell_limits(cell_name):
    cell, _, _, ref, ctl = _program_and_reference(cell_name)
    numbers = harness.compare(cell, ctl, ref)
    limits = cell.limits["limits"]
    assert any(numbers[k] > limits[k] for k in numbers), (numbers, limits)


def test_newton_cg_reaches_the_dense_newton_optimum():
    # 8 Newton steps of 60 CG iterations on a 24-dimensional problem: the
    # CG solves are exact to rounding, so both reach f(x*) to float32
    # rounding of a loss near 0.2 (1e-6 relative).
    cfg = tiny.config(CELLS[0])
    import datagen

    A, b = datagen.make(cfg, datagen.seed_key(9))
    with jax.default_matmul_precision("highest"):
        _, f_dense, _ = reference.newton_dense(A, b, mu=1e-3, steps=30)
        _, f_cg, g = reference.newton_cg(A, b, mu=1e-3, steps=8, cg_iters=60)
    assert float(f_cg) == pytest.approx(float(f_dense), rel=1e-6)
    assert float(g) < 1e-4


def test_seed_key_keeps_the_high_bits():
    import datagen

    a, b = datagen.seed_key(5), datagen.seed_key(2**32 + 5)
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError):
        datagen.seed_key(-1)


def test_seed_draws_the_order_of_one_fixed_dataset():
    # Two seeds lay out the same dataset in different orders: the arrays
    # differ, the objective (a mean over clients and rows) does not.
    import datagen

    cfg = tiny.config(CELLS[0])
    (A1, b1), (A2, b2) = (datagen.make(cfg, datagen.seed_key(s)) for s in (1, 2))
    assert not np.array_equal(np.asarray(A1), np.asarray(A2))
    np.testing.assert_array_equal(np.sort(np.asarray(A1).ravel()), np.sort(np.asarray(A2).ravel()))
    x = jax.random.normal(jax.random.PRNGKey(0), (cfg["geometry"]["dim"],))
    with jax.default_matmul_precision("highest"):
        f1, f2 = (float(reference.global_loss(x, A, b, 1e-3)) for A, b in ((A1, b1), (A2, b2)))
    assert f1 == pytest.approx(f2, rel=1e-6)
    A3, _ = datagen.make(cfg, datagen.seed_key(1))
    np.testing.assert_array_equal(np.asarray(A1), np.asarray(A3))
