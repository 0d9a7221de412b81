"""Client data on the device in one jitted call: a fixed dataset per
configuration, laid out in an order drawn from ``--seed``.

The law is the program's ``data/synthetic.make_dataset`` (LibSVM-like
features around per-client anchors, ill-conditioned column scales, labels
from a planted linear model with logistic noise), kept here so that no
change to the program can change the benchmark's inputs. Two departures,
both so that a seed cannot change the work a run does:

- the dataset is drawn from the configuration's ``data_key``, the same for
  every seed; every row has a key of its own (its client's and its index,
  folded in), so it can be drawn wherever it is placed;
- ``--seed`` draws the order: which of the dataset's clients sits in each
  client slot, and the order of each client's rows. FedNew averages over
  clients and each client over its rows, so every seed gives the same
  problem, the same optimum and the same number of rounds to a gap, up to
  the order of float summation, while the arrays the program sees differ.

With a client mesh the output is laid out over it from the start: every
chip draws its own slots' rows and none holds the whole dataset.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed: ``PRNGKey`` keeps only the
    low 32 bits, so the high bits are folded in."""
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def _generate(data_key, order_key, *, n, m, d, sparse, heterogeneity,
              separation, noise, col_spread, dtype):
    k_anchor, k_feat, k_mask, k_w, k_noise = jax.random.split(data_key, 5)
    k_clients, k_rows = jax.random.split(order_key)
    clients = jax.random.permutation(k_clients, n)  # dataset client per slot
    rows = jax.vmap(lambda k: jax.random.permutation(k, m))(
        jax.random.split(k_rows, n))  # dataset row per (slot, position)

    anchors = heterogeneity * jax.random.normal(k_anchor, (n, d), dtype) / jnp.sqrt(d)
    scales = jnp.logspace(0.0, col_spread, d, dtype=dtype)
    w_true = separation * jax.random.normal(k_w, (d,), dtype) / scales

    def row(c, r):
        key = lambda k: jax.random.fold_in(jax.random.fold_in(k, c), r)
        a = jax.random.normal(key(k_feat), (d,), dtype) / jnp.sqrt(d) + anchors[c]
        if sparse:
            keep = jax.random.bernoulli(key(k_mask), 0.15, (d,))
            a = jnp.where(keep, jnp.sign(a) * (jnp.abs(a) + 0.5), 0.0)
        a = a * scales
        logit = jnp.dot(a, w_true) + jax.random.logistic(key(k_noise), (), dtype) * noise
        return a, jnp.where(logit > 0, 1.0, -1.0).astype(dtype)

    per_client = jax.vmap(row, in_axes=(None, 0))
    return jax.vmap(per_client)(clients, rows)


def make(config: dict, order_key: jax.Array, sharding=None):
    """``(features (n, m, d), labels (n, m))`` for a configuration's
    geometry and generator section, in the order ``order_key`` draws,
    placed by ``sharding`` (a client-axis ``NamedSharding``, or None for
    the default device)."""
    g = config["geometry"]
    gen = config["generator"]
    kw = dict(
        n=g["n_clients"], m=g["samples_per_client"], d=g["dim"],
        sparse=gen["sparse"], heterogeneity=gen["heterogeneity"],
        separation=gen["separation"], noise=gen["noise"],
        col_spread=gen["col_spread"], dtype=jnp.dtype(g["dtype"]),
    )
    out = None
    if sharding is not None:
        lab = jax.sharding.NamedSharding(
            sharding.mesh, jax.sharding.PartitionSpec(*sharding.spec[:1])
        )
        out = (sharding, lab)
    fn = jax.jit(lambda k: _generate(jax.random.PRNGKey(gen["data_key"]), k, **kw),
                 out_shardings=out)
    return fn(order_key)
