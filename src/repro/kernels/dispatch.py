"""Backend-aware dispatch for the FedNew hot-path kernels.

The engine has exactly two byte-moving inner loops — the eq. 9 client solve
and the eqs. 25-30 stochastic quantizer — and each exists in two
implementations: a Pallas TPU kernel (``repro.kernels.<name>``) and a jnp
reference (``repro.core.quantization`` / ``client_solve/ref.py``). This
module owns the routing between them so no call site ever hardcodes
``interpret=True`` (the "silent interpreter" bug) or imports a kernel
module directly.

Backend names accepted from configs / ``engine.get_solver``:

  ``auto``       pick per platform: compiled Pallas on TPU, the jnp
                 reference elsewhere (the interpreter is a correctness tool,
                 not a fast path — never selected silently).
  ``pallas``     force the kernel: compiled on TPU, ``interpret`` mode on
                 CPU/GPU (explicitly requested, so interpretation is fine).
  ``reference``  force the jnp path.

``resolve_backend`` maps those onto the *resolved* execution modes
``pallas`` / ``pallas-interpret`` / ``reference``; the resolved name is what
tests assert against. The ``REPRO_KERNEL_BACKEND`` environment variable
overrides how ``auto`` resolves (CI uses it to run the interpret leg without
touching configs).

The kernel registry itself lives here (populated by
``repro.kernels.__init__``); entries are module-path strings resolved
lazily, so importing this module never drags in a kernel that the selected
backend will not use.
"""

from __future__ import annotations

import importlib
import os
from typing import Callable, Dict, Optional

import jax

BACKENDS = ("auto", "pallas", "reference")
RESOLVED_BACKENDS = ("pallas", "pallas-interpret", "reference")

ENV_BACKEND = "REPRO_KERNEL_BACKEND"


def platform() -> str:
    """The XLA platform kernels would compile for ('tpu', 'cpu', 'gpu')."""
    return jax.default_backend()


def validate_backend(backend: str) -> str:
    if backend not in BACKENDS and backend not in RESOLVED_BACKENDS:
        raise ValueError(
            f"unknown kernel backend {backend!r}; expected one of "
            f"{BACKENDS} (or resolved {RESOLVED_BACKENDS})"
        )
    return backend


def resolve_backend(backend: str = "auto", plat: Optional[str] = None) -> str:
    """Map a config-level backend name to a resolved execution mode.

    'auto'   -> 'pallas' on TPU, else 'reference' (env override honored)
    'pallas' -> 'pallas' on TPU, else 'pallas-interpret'
    already-resolved names pass through unchanged.
    """
    validate_backend(backend)
    plat = platform() if plat is None else plat
    if backend == "auto":
        env = os.environ.get(ENV_BACKEND)
        if env:
            return resolve_backend(validate_backend(env), plat)
        return "pallas" if plat == "tpu" else "reference"
    if backend == "pallas":
        return "pallas" if plat == "tpu" else "pallas-interpret"
    return backend  # 'reference' / 'pallas-interpret'


def use_pallas(resolved: str) -> bool:
    """True when the resolved mode executes the Pallas kernel."""
    return resolved in ("pallas", "pallas-interpret")


def interpret_flag(resolved: str) -> bool:
    """The ``interpret=`` argument the kernel wrapper should receive."""
    return resolved == "pallas-interpret"


def default_interpret() -> bool:
    """Backend-aware default for kernel wrappers called without an explicit
    ``interpret`` flag: compile on TPU, interpret elsewhere. This replaces
    the old hardcoded ``interpret=True`` defaults that sent TPU users
    through the interpreter silently."""
    return platform() != "tpu"


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

# name -> {resolved-flavor: "module.path:attr"}; flavors are "pallas"
# (kernel wrapper taking an ``interpret`` kwarg) and "reference" (pure jnp).
_REGISTRY: Dict[str, Dict[str, str]] = {}


def register_kernel(name: str, *, pallas: str, reference: str) -> None:
    """Register a dispatchable kernel (idempotent; later wins)."""
    _REGISTRY[name] = {"pallas": pallas, "reference": reference}


def registered_kernels() -> tuple:
    return tuple(sorted(_REGISTRY))


def _load(path: str) -> Callable:
    mod, _, attr = path.partition(":")
    return getattr(importlib.import_module(mod), attr)


def resolve_impl(name: str, backend: str = "auto") -> tuple:
    """Resolve ``backend`` and return ``(callable, resolved_flavor)`` from
    the registry; the flavor tells call sites whether to pass kernel-only
    kwargs like ``interpret``. A kernel that fails to import raises: the
    reference never stands in for a kernel the backend asked for."""
    if name not in _REGISTRY:
        raise KeyError(f"unknown kernel {name!r}; have {registered_kernels()}")
    resolved = resolve_backend(backend)
    flavor = "pallas" if use_pallas(resolved) else "reference"
    return _load(_REGISTRY[name][flavor]), resolved


def get_impl(name: str, backend: str = "auto") -> Callable:
    """Resolve ``backend`` and return the implementing callable."""
    return resolve_impl(name, backend)[0]


# ---------------------------------------------------------------------------
# dispatched ops — what fednew/fednew_hf/engine actually call
# ---------------------------------------------------------------------------


def quantize(key, y, y_hat_prev, bits: int, *, backend: str = "auto"):
    """Eq. 25-30 for one client vector; returns a QuantResult. Bit-exact
    across backends for float32 inputs (same key -> same levels)."""
    fn, resolved = resolve_impl("stoch_quant.quantize", backend)
    if use_pallas(resolved):
        return fn(key, y, y_hat_prev, bits, interpret=interpret_flag(resolved))
    return fn(key, y, y_hat_prev, bits)


def quantize_with_keys(keys, y, y_hat_prev, bits: int, *, backend: str = "auto"):
    """Batched eq. 25-30 over a leading client axis with caller-supplied
    per-client keys — the engine's Q-FedNew hot loop, reached through the
    ``repro.comm`` stoch_quant codec (which keeps the integer levels as the
    wire payload and reconstructs ŷ itself so client and server agree bit
    for bit). The Pallas route runs one 2-D ``(clients, blocks)`` grid over
    the whole shard-local batch."""
    fn, resolved = resolve_impl("stoch_quant", backend)
    if use_pallas(resolved):
        return fn(keys, y, y_hat_prev, bits, interpret=interpret_flag(resolved))
    return fn(keys, y, y_hat_prev, bits)


def quantize_batch(key, y, y_hat_prev, bits: int, *, backend: str = "auto"):
    """Batched eq. 25-30, one PRNG split per client (leaf-wise fednew_hf
    route). Key-splitting matches ``quantization.quantize_batch`` exactly."""
    keys = jax.random.split(key, y.shape[0])
    return quantize_with_keys(keys, y, y_hat_prev, bits, backend=backend)


def client_solve(A, b, *, damping: float, iters: int = 32, backend: str = "auto"):
    """Eq. 9: batched (A_i + damping I)^{-1} b_i. The Pallas route is the
    in-VMEM CG kernel; the reference is the direct dense solve."""
    fn, resolved = resolve_impl("client_solve", backend)
    if use_pallas(resolved):
        return fn(A, b, damping=damping, iters=iters,
                  interpret=interpret_flag(resolved))
    return fn(A, b, damping=damping)
