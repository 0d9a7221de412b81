"""Device time by the program's named scopes.

The FedNew step wraps each layer of a round in a ``jax.named_scope``
(``fednew.hessian``, ``fednew.grad``, ``fednew.eq9``, ``fednew.codec``,
``fednew.aggregate``, ``fednew.eval``), which reaches the ``op_name`` of
every instruction in the optimized HLO. A device trace names its ops by
instruction only, so the map from instruction to scope comes from the scan
block's HLO text. The run keeps no handle on the program it dispatched, so
after the window the block is built again from the cell's shapes
(``repro.core.engine.compile_block``): the same program, so the same
instruction names. A map that names under ``MIN_MATCH`` of the window's
op time is not that program, and nothing is read from it.

An op counts toward the innermost ``fednew.*`` scope of its ``op_name``:
XLA prefixes an op it moves into a ``cond`` branch with the branch's path
(the eq. 9 padding sits in the Hessian refresh's branch as
``fednew.hessian/cond/.../fednew.eq9/...``). A fusion whose own
``op_name`` names no scope (its root is an op the compiler made, such as a
``convert``) counts toward the one scope of the ops fused into it. Ops left
with no scope are copies the compiler inserts (layouts, prefetches) and
ops of other programs.
"""

from __future__ import annotations

import re
from typing import Dict, Optional

PREFIX = "fednew."
MIN_MATCH = 0.999

_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_TEXTS: Dict[str, Optional[str]] = {}


def scope_map(hlo_text: str) -> Dict[str, Optional[str]]:
    """Every instruction of an HLO module's text -> its ``fednew.*`` scope
    (None where it has none)."""
    own, calls, inside = {}, {}, {}
    computation = None
    for line in hlo_text.splitlines():
        if line[:1] not in (" ", "\t"):
            if line.endswith("{"):  # "[ENTRY ]%name (params) -> shape {"
                computation = line.removeprefix("ENTRY ").split()[0].lstrip("%")
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, rhs = m.groups()
        op = _OP_NAME.search(rhs)
        scoped = [p for p in op.group(1).split("/") if p.startswith(PREFIX)] if op else []
        own[name] = scoped[-1] if scoped else None
        if own[name]:
            inside.setdefault(computation, set()).add(own[name])
        called = _CALLS.search(rhs)
        if called:
            calls[name] = called.group(1)
    out = {}
    for name, scope in own.items():
        fused = inside.get(calls.get(name), set())
        out[name] = scope or (next(iter(fused)) if len(fused) == 1 else None)
    return out


def scope_seconds(top_ops, hlo_text: str) -> Optional[Dict[Optional[str], float]]:
    """Self time of the window's ops (``devtrace.Summary.top_ops``) summed
    by scope (key None: no scope); None when the program's instruction
    names cover under ``MIN_MATCH`` of the ops' time."""
    smap = scope_map(hlo_text)
    total = sum(s for _, s in top_ops)
    found = sum(s for name, s in top_ops if name in smap)
    if total <= 0 or found < MIN_MATCH * total:
        return None
    out: Dict[Optional[str], float] = {}
    for name, s in top_ops:
        key = smap.get(name)
        out[key] = out.get(key, 0.0) + s
    return out


def block_text(cell) -> Optional[str]:
    """Optimized HLO of the cell's scan block, built from its shapes alone;
    None for a multi-chip cell or a program with no ``compile_block``.
    Built once per cell in a process."""
    if cell.name not in _TEXTS:
        _TEXTS[cell.name] = _build_text(cell)
    return _TEXTS[cell.name]


def _build_text(cell) -> Optional[str]:
    import jax

    import datagen
    import harness
    from repro.core import engine
    from repro.core.objectives import ClientDataset

    compile_block = getattr(engine, "compile_block", None)
    if compile_block is None or cell.chips != 1:
        return None
    key = jax.eval_shape(lambda: jax.random.split(datagen.seed_key(0))[1])
    A, b = jax.eval_shape(lambda k: datagen.make(cell.config, k), key)
    obj, solver, part = harness.build_program(cell)
    exe = compile_block(solver, obj, ClientDataset(features=A, labels=b),
                        cell.block, key=key, participation=part)
    return exe.as_text()


def per_round_ms(rec, scope: str) -> Optional[float]:
    """Device self time per round under ``scope`` over the traced window,
    per chip; None without a trace, a program map, or ops in the scope."""
    if rec.trace is None:
        return None
    text = block_text(rec.cell)
    if text is None:
        return None
    by_scope = scope_seconds(rec.trace.top_ops, text)
    if not by_scope or not by_scope.get(scope):
        return None
    return 1e3 * by_scope[scope] / rec.trace.rounds
