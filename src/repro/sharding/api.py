"""Logical-axis sharding constraints (MaxText-style, minimal) + the one
shard_map entry point.

Model code calls ``constrain(x, ("batch", None, "embed"))`` with *logical*
names. The launcher installs a rules table (logical name -> mesh axes) and a
mesh via ``use_rules``; outside that context the call is a no-op, so the same
model code runs on a laptop CPU and on a 512-chip mesh unchanged.

``shard_map_compat`` is the one place the repo enters a manual region: the
federated engine (``repro.core.engine``) and the LM-scale federated step
(``repro.core.fednew_hf``) both go through it. Callers name the *manual*
(client) axes; remaining mesh axes stay auto, per the client-axis
convention in ``repro.sharding.specs``.
"""

from __future__ import annotations

import contextlib
import threading

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

_state = threading.local()


def current_rules():
    return getattr(_state, "rules", None), getattr(_state, "mesh", None)


@contextlib.contextmanager
def use_rules(rules: dict, mesh):
    """rules: {logical_name: mesh axis | tuple | None}."""
    old = current_rules()
    _state.rules, _state.mesh = rules, mesh
    try:
        yield
    finally:
        _state.rules, _state.mesh = old


def logical_to_spec(names, rules) -> P:
    axes = []
    used = set()
    for n in names:
        if n is None:
            axes.append(None)
            continue
        ax = rules.get(n)
        if ax is None:
            axes.append(None)
            continue
        flat = tuple(a for a in ((ax,) if isinstance(ax, str) else ax) if a not in used)
        used.update(flat)
        axes.append(flat if len(flat) != 1 else flat[0])
    return P(*axes)


def constrain(x, names):
    rules, mesh = current_rules()
    if rules is None or mesh is None:
        return x
    spec = logical_to_spec(names, rules)
    # Inside a shard_map region the tracing context carries an *abstract*
    # mesh with some axes Manual; constraints must be expressed against it
    # (our rules only ever name auto axes there — client axes are excluded).
    am = jax.sharding.get_abstract_mesh()
    if not am.empty:
        return jax.lax.with_sharding_constraint(x, NamedSharding(am, spec))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def shard_map_compat(f, mesh, *, in_specs, out_specs, manual_axes):
    """``jax.shard_map`` manual over ``manual_axes`` (the client axes);
    every other mesh axis remains auto/GSPMD inside the region. Replication
    checking is disabled — the federated bodies establish replication
    through explicit pmeans."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        axis_names=set(manual_axes), check_vma=False,
    )
