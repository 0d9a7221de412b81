"""Step-builder + sharding-spec integration tests (host-scale, 1 device)."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import InputShape
from repro.configs.registry import get_config
from repro.launch.mesh import make_host_mesh
from repro.sharding import specs as sh
from repro.train import steps as steps_mod

TINY_TRAIN = InputShape("tiny_train", seq_len=32, global_batch=4, kind="train")
TINY_PREFILL = InputShape("tiny_prefill", seq_len=32, global_batch=2, kind="prefill")
TINY_DECODE = InputShape("tiny_decode", seq_len=32, global_batch=4, kind="decode")


def _reduced(arch):
    cfg = get_config(arch).reduced()
    return dataclasses.replace(cfg, remat=False)


@pytest.mark.parametrize("arch", ["gemma3-4b", "mixtral-8x7b", "recurrentgemma-2b"])
def test_fednew_train_step_runs_and_decreases_grad(arch):
    cfg = _reduced(arch)
    mesh = make_host_mesh()
    bundle = steps_mod.make_fednew_train_step(cfg, mesh, TINY_TRAIN)
    # concrete state + batch matching the abstract trees
    from repro.data.tokens import client_batches

    state = steps_mod.init_train_state(cfg, mesh, TINY_TRAIN, jax.random.PRNGKey(0))
    batch = client_batches(cfg, TINY_TRAIN, bundle.n_clients, seed=0)
    with mesh:
        step = bundle.jitted()
        s1, m1 = step(state, batch)
        s2, m2 = step(s1, batch)
    assert jnp.isfinite(m1.loss) and jnp.isfinite(m2.loss)
    # same batch, Newton-type steps: loss must drop across two rounds
    assert float(m2.loss) < float(m1.loss)
    # sum_i lam_i = 0 invariant (eq. 13's justification) holds at LM scale
    assert float(m2.dual_sum_residual) < 1e-3 * max(1.0, float(m2.direction_norm))


def test_train_step_lowers_with_shardings():
    cfg = _reduced("yi-6b")
    mesh = make_host_mesh()
    bundle = steps_mod.make_fednew_train_step(cfg, mesh, TINY_TRAIN)
    with mesh:
        compiled = bundle.lower().compile()
    assert compiled.cost_analysis().get("flops", 0) > 0


@pytest.mark.parametrize("arch", ["gemma2-27b", "xlstm-350m", "whisper-medium", "internvl2-2b"])
def test_serve_steps_lower(arch):
    cfg = _reduced(arch)
    mesh = make_host_mesh()
    with mesh:
        steps_mod.make_prefill_step(cfg, mesh, TINY_PREFILL).lower().compile()
        steps_mod.make_serve_step(cfg, mesh, TINY_DECODE).lower().compile()


def test_leaf_spec_greedy_rules():
    sizes = {"data": 16, "model": 16}
    # (vocab, d): model on the big divisible dim, data on the next
    assert sh.leaf_spec((262144, 2560), sizes, ("model", "data")) == jax.sharding.PartitionSpec("model", "data")
    # indivisible dims stay replicated
    assert sh.leaf_spec((99,), sizes, ("model", "data")) == jax.sharding.PartitionSpec(None)
    # scan leaves never shard the leading repeat axis
    spec = sh.leaf_spec((6, 2560, 2048), sizes, ("model", "data"), skip_leading=1)
    assert spec == jax.sharding.PartitionSpec(None, "model", "data")


def test_param_count_matches_init():
    from repro.core.fednew_hf import param_count
    from repro.models import lm
    from repro.roofline import param_counts

    cfg = _reduced("yi-6b")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    analytic = param_counts(cfg)["total"]
    real = param_count(params)
    # analytic count ignores norm scales (O(L*D) — tiny); must agree within 1%
    assert abs(real - analytic) / real < 0.01, (real, analytic)
