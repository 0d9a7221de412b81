"""Step builders: FedNew-HF training, prefill, and decode, mesh-ready.

Everything the launcher and dry-run need for one (arch × input-shape):

  make_fednew_train_step(cfg, mesh) -> StepBundle   (train_4k)
  make_prefill_step(cfg, mesh, shape) -> StepBundle (prefill_32k)
  make_serve_step(cfg, mesh, shape) -> StepBundle   (decode_32k / long_500k)

A ``StepBundle`` carries the step fn, abstract input trees (ShapeDtypeStructs
only — nothing allocated, safe at 512 dry-run devices), and matching
NamedSharding trees for jit in/out_shardings.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import InputShape, ModelConfig
from repro.core import fednew_hf
from repro.core.hvp import gauss_newton_hvp, hvp
from repro.models import lm
from repro.sharding import api as sh_api
from repro.sharding import specs as sh


def _with_rules(fn, rules, mesh):
    """Bake an activation-rules context into a step fn: the rules are active
    while jit traces the body, so every ``constrain()`` in the model resolves
    against this mesh (and is a no-op on meshes where nothing divides)."""

    def wrapped(*args):
        with sh_api.use_rules(rules, mesh):
            return fn(*args)

    return wrapped


@dataclasses.dataclass(frozen=True)
class StepBundle:
    step: Callable
    abstract_args: tuple  # positional args as ShapeDtypeStruct pytrees
    in_shardings: tuple
    out_shardings: Any
    n_clients: int = 1

    def jitted(self):
        return jax.jit(
            self.step,
            in_shardings=self.in_shardings,
            out_shardings=self.out_shardings,
        )

    def lower(self):
        return self.jitted().lower(*self.abstract_args)


# ---------------------------------------------------------------------------
# loss / HVP plumbing
# ---------------------------------------------------------------------------


def make_grad_fn(cfg: ModelConfig):
    def grad_fn(params, batch):
        return jax.value_and_grad(lambda p: lm.train_loss(p, cfg, batch))(params)

    return grad_fn


def make_hvp_fn(cfg: ModelConfig):
    """(params, batch, v) -> H v. Gauss-Newton by default (PSD — the paper's
    convexity assumption restored for the inner quadratic); exact Pearlmutter
    HVP when fed.use_gauss_newton=False."""
    if cfg.fed.use_gauss_newton:

        def hvp_fn(params, batch, v):
            return gauss_newton_hvp(
                lambda p: lm.backbone_features(p, cfg, batch)[0],
                lambda f: lm.head_loss(params, cfg, f, batch),
                params,
                v,
            )

    else:

        def hvp_fn(params, batch, v):
            return hvp(lambda p, b: lm.train_loss(p, cfg, b), params, v, batch)

    return hvp_fn


# ---------------------------------------------------------------------------
# abstract inputs
# ---------------------------------------------------------------------------


def abstract_params(cfg: ModelConfig, key=None):
    key = jax.random.PRNGKey(0) if key is None else key
    return jax.eval_shape(lambda: lm.init_params(cfg, key))


def abstract_state(cfg: ModelConfig, n_clients: int):
    p = abstract_params(cfg)
    return jax.eval_shape(lambda: fednew_hf.init(_zeros(p), cfg.fed, n_clients))


def _zeros(abs_tree):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), abs_tree)


def client_input_specs(cfg: ModelConfig, shape: InputShape, n_clients: int):
    """Training batch stand-ins with the leading client axis."""
    flat = lm.input_specs(cfg, shape)
    B = shape.global_batch
    assert B % n_clients == 0, (B, n_clients)

    def split(s):
        return jax.ShapeDtypeStruct((n_clients, B // n_clients, *s.shape[1:]), s.dtype)

    return jax.tree.map(split, flat)


# ---------------------------------------------------------------------------
# training (FedNew-HF)
# ---------------------------------------------------------------------------


def _pspecs(cfg: ModelConfig, tree, mesh, order=("model", "data")):
    """Param specs with expert-parallel preference for MoE weight stacks."""
    prefer = (cfg.n_experts,) if cfg.is_moe else ()
    return sh.param_specs(tree, mesh, order=order, prefer_model_sizes=prefer)


def state_shardings(cfg: ModelConfig, mesh, state_abs):
    """NamedShardings for a FedNewHFState: greedy param rule on the non-client
    axes (every client holds the full model, FSDP-sharded over its own slice);
    the per-client trees (lam, y_hat) get the client axes prepended."""
    client_axes = sh.resolve_client_axes(cfg, mesh)
    # params/y/anchor may only use the axes the clients don't occupy
    inner_order = ("model",) + tuple(
        a for a in mesh.axis_names if a != "model" and a not in client_axes
    )
    p_spec = _pspecs(cfg, state_abs.params, mesh, order=inner_order)

    def per_client(tree_abs):
        if not client_axes:
            return sh.shardings(_pspecs(cfg, tree_abs, mesh), mesh)
        payload_abs = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype), tree_abs
        )
        payload_spec = _pspecs(cfg, payload_abs, mesh, order=inner_order)
        return sh.shardings(sh.prepend_axes(payload_spec, client_axes), mesh)
    return fednew_hf.FedNewHFState(
        params=sh.shardings(p_spec, mesh),
        y=sh.shardings(_pspecs(cfg, state_abs.y, mesh, order=inner_order), mesh),
        lam=per_client(state_abs.lam),
        anchor=None if state_abs.anchor is None else sh.shardings(p_spec, mesh),
        y_hat=None if state_abs.y_hat is None else per_client(state_abs.y_hat),
        step=jax.NamedSharding(mesh, jax.sharding.PartitionSpec()),
    )


def make_fednew_train_step(cfg: ModelConfig, mesh, shape: InputShape) -> StepBundle:
    client_axes = sh.resolve_client_axes(cfg, mesh)
    n_axes = sh.n_clients(cfg, mesh)
    n = min(n_axes, shape.global_batch)  # every client needs >=1 sequence
    # shard_map is only safe when its auto remainder is exactly {'model'}
    # (XLA partial-manual grouping bug, see resolve_client_axes docstring);
    # other layouts (pod-federated big-client archs) take the vmap path with
    # the same explicit shardings — verified equivalent in
    # tests/test_federated_equivalence.py.
    auto_rest = set(mesh.axis_names) - set(client_axes)
    federated = (
        bool(client_axes) and n == n_axes and n > 1
        and auto_rest == {"model"}
    )
    if n <= 1:
        client_axes = ()

    grad_fn, hvp_fn = make_grad_fn(cfg), make_hvp_fn(cfg)
    if federated:
        step = fednew_hf.make_step_federated(
            grad_fn, hvp_fn, cfg.fed, mesh, client_axes
        )
    else:
        # host-scale / single-client / pod-client fallback: vmap client axis
        step = fednew_hf.make_step(grad_fn, hvp_fn, cfg.fed)
    rules = sh.activation_rules(
        cfg, mesh, client_axes=client_axes,
        batch=shape.global_batch // n,
    )
    step = _with_rules(step, rules, mesh)

    state_abs = abstract_state(cfg, n)
    batch_abs = client_input_specs(cfg, shape, n)
    state_sh = state_shardings(cfg, mesh, state_abs)
    batch_sh = sh.batch_shardings(batch_abs, mesh, client_axes=client_axes)

    args = (state_abs, batch_abs)
    in_sh = (state_sh, batch_sh)
    if cfg.fed.bits:
        args = args + (jax.ShapeDtypeStruct((2,), jnp.uint32),)
        in_sh = in_sh + (jax.NamedSharding(mesh, jax.sharding.PartitionSpec()),)
    return StepBundle(
        step=step,
        abstract_args=args,
        in_shardings=in_sh,
        out_shardings=(state_sh, None),
        n_clients=n,
    )


def init_train_state(cfg: ModelConfig, mesh, shape: InputShape, key):
    """Concrete, host-scale state init (examples/tests; not for dry-runs)."""
    n = min(sh.n_clients(cfg, mesh), shape.global_batch)
    params = lm.init_params(cfg, key)
    return fednew_hf.init(params, cfg.fed, n)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def make_prefill_step(cfg: ModelConfig, mesh, shape: InputShape) -> StepBundle:
    def prefill_step(params, batch):
        return lm.prefill(params, cfg, batch, max_len=shape.seq_len)

    rules = sh.activation_rules(cfg, mesh, batch=shape.global_batch)
    prefill_step = _with_rules(prefill_step, rules, mesh)
    params_abs = abstract_params(cfg)
    batch_abs = lm.input_specs(cfg, shape)
    params_sh = sh.shardings(_pspecs(cfg, params_abs, mesh), mesh)
    batch_sh = sh.batch_shardings(batch_abs, mesh)
    return StepBundle(
        step=prefill_step,
        abstract_args=(params_abs, batch_abs),
        in_shardings=(params_sh, batch_sh),
        out_shardings=None,
    )


def make_serve_step(cfg: ModelConfig, mesh, shape: InputShape) -> StepBundle:
    B, L = shape.global_batch, shape.seq_len

    def serve_step(params, tokens, pos, caches):
        return lm.decode_step(params, cfg, tokens, pos, caches)

    rules = sh.activation_rules(cfg, mesh, batch=B)
    serve_step = _with_rules(serve_step, rules, mesh)
    params_abs = abstract_params(cfg)
    cache_abs = lm.decode_cache_specs(cfg, B, L)
    tok_abs = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    pos_abs = jax.ShapeDtypeStruct((B,), jnp.int32)

    params_sh = sh.shardings(_pspecs(cfg, params_abs, mesh), mesh)
    cache_sh = sh.cache_specs(cache_abs, mesh, batch=B, kv_len=L)
    bspec = sh.batch_spec(mesh, global_batch=B)
    tok_sh = jax.NamedSharding(mesh, jax.sharding.PartitionSpec(*bspec, None))
    pos_sh = jax.NamedSharding(mesh, jax.sharding.PartitionSpec(*bspec))
    return StepBundle(
        step=serve_step,
        abstract_args=(params_abs, tok_abs, pos_abs, cache_abs),
        in_shardings=(params_sh, tok_sh, pos_sh, cache_sh),
        out_shardings=(None, cache_sh),
    )


def make_bundle(cfg: ModelConfig, mesh, shape: InputShape) -> StepBundle:
    if shape.kind == "train":
        return make_fednew_train_step(cfg, mesh, shape)
    if shape.kind == "prefill":
        return make_prefill_step(cfg, mesh, shape)
    return make_serve_step(cfg, mesh, shape)
