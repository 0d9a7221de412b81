"""Least work of one FedNew round, from shapes and hyperparameters.

Each equation of the paper counts once, with no recomputation and with
everything that can be fused assumed fused, so that a leaner program can
approach but never pass these counts:

- gradient at x: ``4·n·m·d`` FLOPs (A x and A^T s, two multiply-adds each);
- dense Hessian formation on a refresh round: ``2·n·m·d²`` (A^T D A),
  amortised over ``hessian_period`` rounds (never after round 0 when 0);
- eq. 9 dense: ``iters·n·2·d²`` for the fixed-iteration CG kernel;
- eq. 9 matrix-free: ``cg_iters·n·4·m·d`` (one HVP, A v and A^T (w·Av),
  per iteration; the curvature weights come with the gradient's A x).

Least bytes: one read of the client features per sequentially dependent
sweep over them. The matrix-free round needs ``cg_iters`` of them (each CG
iteration's HVP waits on the one before, and one HVP, a sum over rows of
``a_i w_i (a_i . v)``, is one sweep); the gradient, the loss and the vector
updates are taken as fused into those sweeps. Each feature is read at
``operand_bytes``, the width of the operands the configuration's matmul
precision hands the chip's matrix unit: 2 at JAX's default precision on a
TPU, which rounds float32 operands to bfloat16, so a program may keep and
stream a bfloat16 copy of the features without changing a result. The
dense round has no such count here (None): its features stay in HBM for
the Hessian only.
"""


def work(geom: dict, hp: dict):
    n, m, d = geom["n_clients"], geom["samples_per_client"], geom["dim"]
    flops = 4.0 * n * m * d
    if hp.get("hessian_repr", "dense") == "matfree":
        flops += hp["cg_iters"] * n * 4.0 * m * d
        return flops, hp["cg_iters"] * float(n * m * d * hp["operand_bytes"])
    period = hp.get("hessian_period", 1)
    if period > 0:
        flops += 2.0 * n * m * d * d / period
    flops += hp["eq9_cg_iters"] * n * 2.0 * d * d
    return flops, None
