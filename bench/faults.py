"""Faults planted in the program under test, to show that the comparison
with the reference catches them (``calibrate.py`` on the chip,
``tests/test_faults.py`` on the CPU). Each is a context manager that
patches one function of the program for the jobs run inside it; the engine
traces every job anew, so the patch reaches the compiled blocks.

- ``frozen``: the round returns its state unchanged;
- ``half``: eq. 13 averages over half of the clients, leaving the rest out;
- ``alter``: one client's eq. 9 answer is doubled where it is produced;
- ``no_exchange``: the mean across chips is left out (each chip averages
  its own clients only), for cells on a client mesh.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

FAULTS = ("frozen", "half", "alter", "no_exchange")


@contextlib.contextmanager
def planted(name: str):
    from repro.core import admm, fednew

    if name == "frozen":
        target, attr, orig = fednew, "step", fednew.step

        def patched(state, *a, **k):
            return state, orig(state, *a, **k)[1]
    elif name == "half":
        target, attr, orig = admm, "tree_mean_clients", admm.tree_mean_clients

        def patched(tree, axis_name=None, weights=None):
            n = jax.tree.leaves(tree)[0].shape[0]
            half = (jnp.arange(n) < max(1, n // 2)).astype(jnp.float32)
            return orig(tree, axis_name, weights=half if weights is None else weights * half)
    elif name == "alter":
        target, attr, orig = fednew, "_local_solve", fednew._local_solve

        def patched(*a, **k):
            out = orig(*a, **k)
            if isinstance(out, tuple):
                return (out[0].at[0].multiply(2.0),) + tuple(out[1:])
            return out.at[0].multiply(2.0)
    elif name == "no_exchange":
        target, attr, orig = jax.lax, "pmean", jax.lax.pmean

        def patched(x, axis_name, **k):
            return x
    else:
        raise ValueError(f"unknown fault {name!r}; have {FAULTS}")
    setattr(target, attr, patched)
    try:
        yield
    finally:
        setattr(target, attr, orig)
