"""Least work of one call of the eq. 9 kernel (``kernels/client_solve``):
``iters`` CG iterations on each of n damped (d, d) systems.

FLOPs: one (d, d) matrix-vector product per iteration and client, 2·d²,
at the unpadded d (the kernel's padding to the 128-lane tile is its own
cost). Bytes: its inputs and outputs in HBM, read and written once — the
n Hessians (n·d²), the right-hand sides and the solutions (2·n·d).
"""


def work(geom: dict, hp: dict):
    n, d = geom["n_clients"], geom["dim"]
    itemsize = 4 if geom["dtype"] == "float32" else 2
    flops = hp["eq9_cg_iters"] * n * 2.0 * d * d
    return flops, float(itemsize * (n * d * d + 2 * n * d))
