"""Least work of one call of the uplink quantizer kernel
(``kernels/stoch_quant``, paper eqs. 25-30) on n client directions of
length d.

Bytes: its inputs and outputs in HBM, once each — the directions, the
previous reconstructions and the uniforms in (3·n·d words), one range per
client in, the int32 levels and the new reconstructions out (2·n·d words).
FLOPs: about ten elementwise operations per coordinate, which never bind.
"""


def work(geom: dict, hp: dict):
    n, d = geom["n_clients"], geom["dim"]
    return 10.0 * n * d, 4.0 * (5 * n * d + n)
