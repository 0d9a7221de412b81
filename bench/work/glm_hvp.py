"""Least work of one call of the matfree HVP kernel (``kernels/glm_hvp``):
every client's A_i^T (w_i * (A_i v_i)) / m + mu v_i.

FLOPs: A v and A^T u, a multiply-add per feature each, ``4·n·m·d`` (the
weights, the scale and mu v are O(n·(m + d))). Bytes: one read of the
features at ``operand_bytes``, the width the kernel streams them at (2: the
bf16 copy that JAX's default precision would hand the matrix unit anyway);
the vectors in and out are O(n·d), a 2·m-th of that, and left out.
"""


def work(geom: dict, hp: dict):
    n, m, d = geom["n_clients"], geom["samples_per_client"], geom["dim"]
    return 4.0 * n * m * d, float(n * m * d * hp["operand_bytes"])
