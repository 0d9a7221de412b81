"""The FedNew hot-path kernels compile for a TPU v5e.

Interpret mode (every other kernel test) accepts block shapes and VMEM
footprints the TPU compiler refuses, so these tests compile each dispatched
kernel ahead of time for a *described* v5e chip at the main path's widths
(w8a: 60 clients x d=267; matrix-free scale: 16 clients x d=1e5) and check
that the Pallas kernel is in the program (``tpu_custom_call``). Nothing
runs, so no chip is needed; where no TPU topology can be described the
tests skip.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and the test
workers each import every test file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.client_solve import ops as cs_ops
from repro.kernels.stoch_quant import ops as sq_ops
from repro.kernels.stoch_quant.stoch_quant import stoch_quant


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    """ShapeDtypeStruct factory placed on one described v5e chip, with the
    persistent compilation cache off: a compile for a described chip is
    written to it but cannot be read back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache

    one_chip = SingleDeviceSharding(topo.devices[0])
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield lambda dims, dtype=jnp.float32: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one_chip
    )
    jax.config.update("jax_enable_compilation_cache", prev)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("n,N", [(60, 267), (16, 100_000)])
def test_stoch_quant_kernel_compiles_for_v5e(shape, n, N):
    text = _compiled_text(
        lambda y, p, u, R: stoch_quant(y, p, u, R, bits=3),
        shape((n, N)), shape((n, N)), shape((n, N)), shape((n,)),
    )
    assert "tpu_custom_call" in text


def test_quantize_with_keys_compiles_for_v5e(shape):
    """The wrapper the stoch_quant codec dispatches to, at w8a width."""
    n, N = 60, 267
    text = _compiled_text(
        lambda k, y, p: sq_ops.quantize_with_keys(k, y, p, 3, interpret=False),
        shape((n, 2), jnp.uint32), shape((n, N)), shape((n, N)),
    )
    assert "tpu_custom_call" in text


def test_client_solve_compiles_for_v5e(shape):
    """Eq. 9 at w8a width: d=267 pads to a (384, 384) VMEM tile."""
    n, d = 60, 267
    text = _compiled_text(
        lambda A, b: cs_ops.client_solve(A, b, damping=0.13, interpret=False),
        shape((n, d, d)), shape((n, d)),
    )
    assert "tpu_custom_call" in text


def test_client_solve_refuses_tile_over_vmem_limit():
    """Past MAX_DIM the compiled kernel cannot hold its A tile in VMEM; the
    wrapper says so instead of leaving it to the TPU compiler."""
    d = cs_ops.MAX_DIM + 1
    A = jax.ShapeDtypeStruct((2, d, d), jnp.float32)
    b = jax.ShapeDtypeStruct((2, d), jnp.float32)
    with pytest.raises(ValueError, match="VMEM"):
        jax.eval_shape(
            lambda A, b: cs_ops.client_solve(A, b, damping=1.0, interpret=False),
            A, b,
        )
