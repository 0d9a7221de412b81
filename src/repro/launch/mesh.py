"""Mesh construction. Functions only — importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax import).

**Client-axis mesh convention** (shared with ``repro.sharding.specs`` and
``repro.core.engine``): federated clients live on dedicated mesh axes.

  * ``make_client_mesh`` — the engine's 1-D ``('clients',)`` mesh for the
    paper-scale solvers: the dataset's and per-client state's leading client
    dim is split over it, everything else is replicated, and eq. 13 is one
    all-reduce over ``'clients'``.
  * ``make_production_mesh`` / ``make_host_mesh`` — LM-scale meshes where the
    client axes come from ``fed.client_axes`` (usually ``('data',)``) and
    the remaining axes form each client's private tensor-parallel mesh.

Every mesh axis is typed ``AxisType.Auto``.
"""

from __future__ import annotations

import jax

from repro.sharding.specs import CLIENT_AXIS


def _make_mesh(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    """Production target: TPU v5e, 256 chips/pod.

    single-pod: (16, 16)  ('data', 'model')
    multi-pod:  (2, 16, 16) ('pod', 'data', 'model') — the 'pod' axis models
    the slow inter-pod (DCN/WAN) links; FedNew's client aggregation is the
    only collective that must cross it for pod-federated configs.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh():
    """Whatever this host offers (tests/examples): 1 device -> (1,1) mesh so
    the same sharded code paths run unchanged."""
    n = len(jax.devices())
    return _make_mesh((n, 1), ("data", "model"))


def make_client_mesh(n_devices: int | None = None):
    """1-D ``('clients',)`` mesh over ``n_devices`` (default: all local
    devices) for the federated engine. ``n_devices`` must divide the run's
    client count; a single device gives a size-1 client axis, so laptops
    exercise the same shard_map code path as a pod."""
    n = len(jax.devices()) if n_devices is None else n_devices
    return _make_mesh((n,), (CLIENT_AXIS,))
