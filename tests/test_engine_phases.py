"""The engine's phase spans and the FedNew step's named scopes.

Every driver (scan, host, sharded) builds each distinct program with JAX's
staged API and reports ``init``, ``trace``, ``lower``, ``compile`` and
``dispatch{launch, wait}`` through the tracer hook, all under one ``job``
id per run; the hook changes no trajectory. The step's ``fednew.*`` scopes
reach the optimized HLO's ``op_name``s and change nothing else in it.
"""

import contextlib
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.core import engine, fednew, objectives
from repro.core.objectives import ClientDataset, logistic_regression

KEY = jax.random.PRNGKey(3)
N, M, D = 8, 16, 24
ALL_SCOPES = {"fednew.hessian", "fednew.grad", "fednew.eq9", "fednew.codec",
              "fednew.aggregate", "fednew.eval"}
# A codec with nothing to encode (the identity) emits no op to name.
NO_CODEC = ALL_SCOPES - {"fednew.codec"}
OP_NAME = re.compile(r'op_name="([^"]*)"')


class SpanLog:
    """A tracer hook that logs each span as it opens and closes, and each
    program handed to ``compiled``."""

    def __init__(self):
        self.events = []  # ("enter"|"exit", name, args)
        self.programs = []  # (label, Compiled)

    @contextlib.contextmanager
    def span(self, name, **args):
        self.events.append(("enter", name, args))
        yield
        self.events.append(("exit", name, args))

    def compiled(self, label, compiled):
        self.programs.append((label, compiled))

    def entered(self):
        return [name for kind, name, _ in self.events if kind == "enter"]


def expected_phases(n_programs_then_blocks):
    """``init`` then, per block, the build (first use of its program) and
    the dispatch."""
    out = ["init"]
    for built in n_programs_then_blocks:
        if built:
            out += ["trace", "lower", "compile"]
        out += ["dispatch", "launch", "wait"]
    return out


def nesting(events):
    """(name, parent) for every span, from the open/close order."""
    stack, pairs = [], []
    for kind, name, _ in events:
        if kind == "enter":
            pairs.append((name, stack[-1] if stack else None))
            stack.append(name)
        else:
            assert stack.pop() == name
    assert not stack
    return pairs


@pytest.fixture(scope="module")
def problem():
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    A = jax.random.normal(k1, (N, M, D)) / jnp.sqrt(D)
    b = jnp.where(jax.random.normal(k2, (N, M)) > 0, 1.0, -1.0)
    return logistic_regression(mu=1e-3), ClientDataset(features=A, labels=b)


def _solver(**hp):
    return fednew.solver(fednew.FedNewConfig(rho=0.1, alpha=0.03, **hp))


@pytest.mark.parametrize("mode,blocks", [
    ("scan", [True, False, True]),  # blocks of 2, 2 and a 1-round tail
    ("host", [True] + [False] * 4),  # one step program, five rounds
])
def test_drivers_emit_phase_spans_in_order(problem, mode, blocks):
    obj, data = problem
    log = SpanLog()
    engine.run(_solver(bits=3), obj, data, 5, key=KEY, mode=mode,
               block_size=2, tracer=log)
    assert log.entered() == expected_phases(blocks)
    assert {args["job"] for _, _, args in log.events} == {log.events[0][2]["job"]}
    parents = dict(nesting(log.events))
    assert parents["launch"] == parents["wait"] == "dispatch"
    assert parents["trace"] is None and parents["dispatch"] is None
    labels = [label for label, _ in log.programs]
    assert labels == (["scan_block[2r]", "scan_block[1r]"] if mode == "scan"
                      else ["host_step"])


def test_each_run_is_a_new_job(problem):
    obj, data = problem
    jobs = []
    for _ in range(2):
        log = SpanLog()
        engine.run(_solver(), obj, data, 2, key=KEY, tracer=log)
        jobs.append(log.events[0][2]["job"])
    assert jobs[1] > jobs[0]


@pytest.mark.parametrize("mode", ["scan", "host"])
@pytest.mark.parametrize("hp", [{}, {"bits": 3}, {"hessian_repr": "matfree", "cg_iters": 4}],
                         ids=["fednew", "q-fednew", "matfree"])
def test_tracer_and_timings_leave_the_trajectory_bit_identical(problem, mode, hp):
    obj, data = problem
    s0, m0 = engine.run(_solver(**hp), obj, data, 5, key=KEY, mode=mode,
                        block_size=2)
    timings = []
    s1, m1 = engine.run(_solver(**hp), obj, data, 5, key=KEY, mode=mode,
                        block_size=2, tracer=SpanLog(), timings=timings)
    for a, b in zip(jax.tree.leaves((s0, m0)), jax.tree.leaves((s1, m1))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert sum(n for n, _ in timings) == 5


SHARDED = """
import json, sys
sys.path.insert(0, {tests!r})
import jax, jax.numpy as jnp, numpy as np
from test_engine_phases import SpanLog, _solver, KEY, N, M, D
from repro.core import engine
from repro.core.objectives import ClientDataset, logistic_regression
from repro.launch.mesh import make_client_mesh

k1, k2 = jax.random.split(jax.random.PRNGKey(0))
data = ClientDataset(features=jax.random.normal(k1, (N, M, D)) / jnp.sqrt(D),
                     labels=jnp.where(jax.random.normal(k2, (N, M)) > 0, 1.0, -1.0))
obj, mesh = logistic_regression(mu=1e-3), make_client_mesh(4)
out = {{"devices": len(mesh.devices.flat)}}
# The identity codec's state is (n, 0) wide; the 3-bit codec's is (n, d).
for name, hp, rounds in (("identity", {{}}, 5), ("q3", {{"bits": 3}}, 5),
                         ("identity-6", {{}}, 6)):
    s0, m0 = engine.run(_solver(**hp), obj, data, rounds, key=KEY, mesh=mesh, block_size=2)
    log = SpanLog()
    s1, m1 = engine.run(_solver(**hp), obj, data, rounds, key=KEY, mesh=mesh,
                        block_size=2, tracer=log, timings=[])
    out[name] = {{
        "same": all(np.array_equal(np.asarray(a), np.asarray(b))
                    for a, b in zip(jax.tree.leaves((s0, m0)), jax.tree.leaves((s1, m1)))),
        "events": [[k, n, a] for k, n, a in log.events],
        "labels": [label for label, _ in log.programs]}}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def sharded_runs():
    """Traced and untraced sharded runs on four virtual devices, in a
    process of its own (the device count is fixed when JAX starts)."""
    tests = os.path.dirname(os.path.abspath(__file__))
    src = os.path.join(os.path.dirname(tests), "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=src,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", SHARDED.format(tests=tests)],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out.pop("devices") == 4
    return out


def test_sharded_driver_emits_phase_spans_on_four_devices(sharded_runs):
    for case in ("identity", "q3"):
        out = sharded_runs[case]
        assert out["same"], case
        events = [tuple(e) for e in out["events"]]
        assert [n for k, n, _ in events if k == "enter"] == \
            expected_phases([True, False, True]), case
        assert len({a["job"] for _, _, a in events}) == 1, case
        assert dict(nesting(events))["launch"] == "dispatch", case
        assert out["labels"] == ["shard_block[2r]", "shard_block[1r]"], case


def test_sharded_job_builds_each_block_length_once(sharded_runs):
    """The identity codec's zero-width state keeps its layout across
    blocks, so three blocks of two rounds share one program."""
    out = sharded_runs["identity-6"]
    assert out["same"]
    events = [tuple(e) for e in out["events"]]
    assert [n for k, n, _ in events if k == "enter"] == expected_phases([True, False, False])
    assert out["labels"] == ["shard_block[2r]"]


def test_first_timing_includes_the_build(problem):
    obj, data = problem
    log, timings = SpanLog(), []
    engine.run(_solver(), obj, data, 4, key=KEY, block_size=2, tracer=log,
               timings=timings)
    assert [n for n, _ in timings] == [2, 2]
    assert timings[0][1] > timings[1][1]


# ---------------------------------------------------------------------------
# named scopes
# ---------------------------------------------------------------------------


def scopes_in(text):
    found = set()
    for name in OP_NAME.findall(text):
        found |= {p for p in name.split("/") if p.startswith("fednew.")}
    return found


def instructions(text):
    """The module's instruction lines without their metadata (the stack
    frame tables after the computations are metadata too)."""
    body = text.split("\nFileNames")[0]
    return re.sub(r", metadata=\{[^}]*\}", "", body).splitlines()[1:]


CASES = {
    "dense": ({}, NO_CODEC),
    "dense-pallas": ({"backend": "pallas"}, NO_CODEC),
    "matfree": ({"hessian_repr": "matfree", "cg_iters": 4}, NO_CODEC),
    "q-fednew": ({"bits": 3}, ALL_SCOPES),
}


@pytest.mark.parametrize("case", list(CASES))
def test_compiled_block_names_every_scope_and_only_metadata_moves(
        problem, monkeypatch, case):
    hp, want = CASES[case]
    obj, data = problem
    shapes = jax.eval_shape(lambda d: d, data)
    text = engine.compile_block(_solver(**hp), obj, shapes, 2, key=KEY).as_text()
    assert scopes_in(text) == want
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    bare = engine.compile_block(_solver(**hp), obj, shapes, 2, key=KEY).as_text()
    assert scopes_in(bare) == set()
    assert instructions(bare) == instructions(text)


def test_pytree_step_names_every_scope():
    n, m, k = 4, 6, 5

    def loss_fn(p, b):
        r = b["A"] @ p["w"] + p["b"] - b["y"]
        return 0.5 * jnp.mean(r * r)

    obj = objectives.from_loss_fn(loss_fn)
    k1, k2 = jax.random.split(KEY)
    data = objectives.TokenDataset(batch={"A": jax.random.normal(k1, (n, m, k)),
                                          "y": jax.random.normal(k2, (n, m))})
    x0 = {"w": jnp.zeros((k,)), "b": jnp.zeros(())}
    solver = _solver(hessian_repr="matfree", cg_iters=3, bits=3)
    text = engine.compile_block(solver, obj, data, 2, key=KEY, x0=x0).as_text()
    assert scopes_in(text) == ALL_SCOPES


def test_compile_block_is_the_program_run_dispatches(problem):
    obj, data = problem
    log = SpanLog()
    engine.run(_solver(bits=3), obj, data, 4, key=KEY, block_size=4, tracer=log)
    (label, ran), = log.programs
    rebuilt = engine.compile_block(_solver(bits=3), obj,
                                   jax.eval_shape(lambda d: d, data), 4, key=KEY)
    assert label == "scan_block[4r]"
    assert rebuilt.as_text().split("\nFileNames")[0] == ran.as_text().split("\nFileNames")[0]


# ---------------------------------------------------------------------------
# EngineTracer
# ---------------------------------------------------------------------------


def test_engine_tracer_annotates_and_profiles_the_built_program(problem, monkeypatch):
    obj, data = problem
    annotated, lowered = [], []

    class Annotation(contextlib.ContextDecorator):
        def __init__(self, name, **_):
            annotated.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    real_lower = jax.stages.Traced.lower
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    monkeypatch.setattr(jax.stages.Traced, "lower",
                        lambda self, *a, **k: lowered.append(1) or real_lower(self, *a, **k))
    tracer = telemetry.EngineTracer(recorder=telemetry.TraceRecorder(), profile=True)
    engine.run(_solver(), obj, data, 3, key=KEY, block_size=2, tracer=tracer)
    assert sorted(tracer.costs) == ["scan_block[1r]", "scan_block[2r]"]
    assert len(lowered) == 2  # one lowering per program: no second one to profile
    assert annotated[:4] == ["engine.init", "engine.trace", "engine.lower", "engine.compile"]
    assert {"engine.dispatch", "engine.launch", "engine.wait"} <= set(annotated)
    host = [e for e in tracer.recorder.events if e["ph"] == "X"]
    assert {e["name"] for e in host} >= {"init", "trace", "lower", "compile",
                                          "dispatch", "launch", "wait", "hlo-analyze"}
    assert len({e["args"]["job"] for e in host if "job" in e.get("args", {})}) == 1
