"""``repro.comm`` subsystem tests: codec protocol + registry, the codec
path checked against a plain float32 Algorithm 1 loop and against the host
driver (scan AND shard_map), topk/bit_schedule behavior, the netsim time
model, and the declarative CompressionSpec/NetworkSpec surface end to
end."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api, comm
from repro.core import engine, fednew
from repro.core.objectives import logistic_regression
from repro.core.quantization import quantize_with_keys
from repro.data.synthetic import PAPER_DATASETS, make_dataset
from repro.launch.mesh import make_client_mesh


@pytest.fixture(scope="module")
def problem():
    data = make_dataset(PAPER_DATASETS["a1a"], jax.random.PRNGKey(0))
    return logistic_regression(mu=1e-3), data


HP = {"rho": 0.1, "alpha": 0.03, "hessian_period": 1}


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def test_registry_names_and_errors():
    assert set(comm.codec_names()) >= {
        "identity", "stoch_quant", "topk", "bit_schedule"
    }
    with pytest.raises(ValueError, match="registered codecs"):
        comm.build_codec("gzip")
    with pytest.raises(ValueError, match="valid params"):
        comm.build_codec({"name": "stoch_quant", "bitz": 3})
    with pytest.raises(ValueError, match="bits"):
        comm.build_codec({"name": "stoch_quant", "bits": 0})
    with pytest.raises(ValueError, match="exactly one"):
        comm.build_codec({"name": "topk"})
    with pytest.raises(ValueError, match="exactly one"):
        comm.build_codec({"name": "topk", "k": 3, "fraction": 0.5})
    with pytest.raises(ValueError, match="fraction"):
        comm.build_codec({"name": "topk", "fraction": 1.5})
    with pytest.raises(ValueError, match="feedback"):
        comm.build_codec({"name": "topk", "k": 3, "feedback": "ef99"})
    with pytest.raises(ValueError, match="round 0"):
        comm.build_codec({"name": "bit_schedule", "schedule": [[5, 2]]})
    with pytest.raises(ValueError, match="increasing"):
        comm.build_codec({"name": "bit_schedule",
                          "schedule": [[0, 2], [0, 4]]})
    # specs rebuild the codec they came from
    for spec in ({"name": "identity"}, {"name": "stoch_quant", "bits": 3},
                 {"name": "topk", "fraction": 0.1, "value_bits": 32},
                 {"name": "bit_schedule", "schedule": [[0, 2], [9, 4]]}):
        assert comm.build_codec(comm.build_codec(spec).spec()).spec() == \
            comm.build_codec(spec).spec()


def test_exact_payload_bits_are_python_ints():
    d, word = 10**9, 32
    cases = {
        "identity": comm.build_codec("identity").payload_bits(d, word),
        "sq8": comm.build_codec(
            {"name": "stoch_quant", "bits": 8}).payload_bits(d, word),
        "topk": comm.build_codec(
            {"name": "topk", "fraction": 0.01}).payload_bits(d, word),
    }
    assert cases["identity"] == 32 * d
    assert cases["sq8"] == 8 * d + 32
    # ceil(0.01 * 1e9) values at 32 bits + 30-bit indices
    assert cases["topk"] == 10**7 * (32 + 30)
    for v in cases.values():
        assert type(v) is int  # exact, never numpy/float


# ---------------------------------------------------------------------------
# codec transforms
# ---------------------------------------------------------------------------


def test_identity_codec_roundtrip():
    c = comm.build_codec("identity")
    y = jax.random.normal(jax.random.PRNGKey(0), (4, 9))
    st = c.init_state(4, 9, y.dtype)
    assert st.shape == (4, 0)
    wire = c.encode(None, y, st, 0)
    y_tx = c.decode(wire, st, 0)
    np.testing.assert_array_equal(np.asarray(y_tx), np.asarray(y))
    assert c.update_state(y_tx, y, st, 0).shape == (4, 0)
    assert not c.needs_rng


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_stoch_quant_decode_matches_reference_reconstruction(backend):
    """The wire (levels, R) decodes to EXACTLY the reference eq. 30 ŷ, and
    encode's carried state equals the decode — client and server never
    drift, on either backend."""
    c = comm.build_codec({"name": "stoch_quant", "bits": 3}, backend=backend)
    key = jax.random.PRNGKey(5)
    y = jax.random.normal(jax.random.PRNGKey(1), (6, 33))
    prev = 0.1 * jax.random.normal(jax.random.PRNGKey(2), (6, 33))
    keys = jax.random.split(key, 6)
    wire = c.encode(keys, y, prev, 0)
    decoded = c.decode(wire, prev, 0)
    state = c.update_state(decoded, y, prev, 0)
    np.testing.assert_array_equal(np.asarray(decoded), np.asarray(state))
    ref = quantize_with_keys(keys, y, prev, 3)
    np.testing.assert_array_equal(np.asarray(wire["levels"]),
                                  np.asarray(ref.levels))
    np.testing.assert_allclose(np.asarray(decoded), np.asarray(ref.y_hat),
                               rtol=0, atol=1e-6)
    assert c.needs_rng


def test_topk_diff_feedback_tracks_input():
    """diff feedback: the carried reconstruction converges to a constant
    input after ~d/k rounds, and decode == carried state (dense estimate)."""
    c = comm.build_codec({"name": "topk", "k": 4})
    y = jax.random.normal(jax.random.PRNGKey(3), (3, 12))
    st = c.init_state(3, 12, y.dtype)
    for _ in range(3):  # 3 rounds x k=4 = 12 coords: full delivery
        wire = c.encode(None, y, st, 0)
        y_tx = c.decode(wire, st, 0)
        st_new = c.update_state(y_tx, y, st, 0)
        np.testing.assert_array_equal(np.asarray(y_tx), np.asarray(st_new))
        assert wire["values"].shape == (3, 4)
        assert wire["indices"].dtype == jnp.int32
        st = st_new
    np.testing.assert_allclose(np.asarray(st), np.asarray(y), atol=1e-6)


def test_topk_residual_feedback_conserves_mass():
    """residual feedback: transmitted + carried == input + carried_prev
    (nothing is lost), and the decode is k-sparse."""
    c = comm.build_codec({"name": "topk", "k": 3, "feedback": "residual"})
    y = jax.random.normal(jax.random.PRNGKey(4), (5, 20))
    e = 0.5 * jax.random.normal(jax.random.PRNGKey(5), (5, 20))
    wire = c.encode(None, y, e, 0)
    y_tx = c.decode(wire, e, 0)
    e_new = c.update_state(y_tx, y, e, 0)
    assert int((np.asarray(y_tx) != 0).sum(axis=1).max()) <= 3
    np.testing.assert_allclose(np.asarray(y_tx + e_new), np.asarray(y + e),
                               rtol=1e-6)


def test_topk_value_bits_casts_wire_values():
    c = comm.build_codec({"name": "topk", "k": 2, "value_bits": 32})
    with jax.enable_x64(True):
        y = jax.random.normal(jax.random.PRNGKey(0), (2, 8), jnp.float64)
        st = c.init_state(2, 8, jnp.float64)
        wire = c.encode(None, y, st, 0)
        # values went through float32 on the wire
        vals = np.asarray(wire["values"])
        np.testing.assert_array_equal(vals, vals.astype(np.float32))


def test_bit_schedule_stages_and_ledger():
    c = comm.build_codec({"name": "bit_schedule",
                          "schedule": [[0, 2], [5, 4]]})
    d, word = 99, 32
    assert c.payload_bits(d, word, 0) == 2 * d + 32
    assert c.payload_bits(d, word, 4) == 2 * d + 32
    assert c.payload_bits(d, word, 5) == 4 * d + 32
    # traced metric agrees with the host ledger at every round
    for r in (0, 4, 5, 11):
        assert float(c.payload_bits_metric(d, word, jnp.asarray(r))) == float(
            c.payload_bits(d, word, r)
        )
    # stage 0 emits the same WIRE (integer levels) as a plain 2-bit
    # stoch_quant encode. (The float reconstruction may differ from the
    # un-switched codec by an ulp — lax.switch branches compile as a unit
    # and contract mul+add chains; the wire and the single-decode
    # client/server agreement are the contract.)
    sq = comm.build_codec({"name": "stoch_quant", "bits": 2})
    y = jax.random.normal(jax.random.PRNGKey(1), (4, 13))
    prev = jnp.zeros_like(y)
    keys = jax.random.split(jax.random.PRNGKey(2), 4)
    w_bs = c.encode(keys, y, prev, jnp.asarray(0))
    w_sq = sq.encode(keys, y, prev, 0)
    np.testing.assert_array_equal(np.asarray(w_bs["levels"]),
                                  np.asarray(w_sq["levels"]))
    np.testing.assert_allclose(
        np.asarray(c.decode(w_bs, prev, jnp.asarray(0))),
        np.asarray(sq.decode(w_sq, prev, 0)), rtol=0, atol=1e-6,
    )


@pytest.mark.parametrize("spec", [
    {"name": "identity"},
    {"name": "stoch_quant", "bits": 3},
    {"name": "topk", "k": 4},
])
def test_encode_decode_serves_both_layouts(spec):
    """``comm.encode_decode`` on a bare (n, d) array is the flat round
    (encode, decode, update_state on ``client_keys``); on a one-leaf dict it
    is ``encode_decode_tree`` with its per-leaf keys. Bit for bit."""
    codec = comm.build_codec(spec)
    n, d = 5, 13
    y = jax.random.normal(jax.random.PRNGKey(1), (n, d))
    shape = codec.init_state(n, d, jnp.float32).shape
    state = jax.random.normal(jax.random.PRNGKey(3), shape)
    key, step = jax.random.PRNGKey(2), jnp.asarray(1)

    keys = comm.client_keys(key, n, None, None) if codec.needs_rng else None
    y_tx = codec.decode(codec.encode(keys, y, state, step), state, step)
    want = (y_tx, codec.update_state(y_tx, y, state, step))
    got = comm.encode_decode(codec, key, y, state, step=step)
    tree = ({"w": y}, {"w": state})
    want_tree = comm.encode_decode_tree(codec, key, *tree, step=step)
    got_tree = comm.encode_decode(codec, key, *tree, step=step)
    for g, w in zip(jax.tree.leaves((got, got_tree)),
                    jax.tree.leaves((want, want_tree))):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ---------------------------------------------------------------------------
# acceptance pins: the codec path IS Algorithm 1, checked in one process
# ---------------------------------------------------------------------------


def _plain_algorithm1(data, rounds, rho, alpha, mu=1e-3):
    """FedNew written out in float32 ``jax.numpy`` at ``highest`` matmul
    precision: per-client closed-form logistic gradient and Hessian, a
    Cholesky solve of eq. 9, then eqs. 13, 12 and 14. Returns the loss
    after each round and the last iterate."""
    A = jnp.asarray(data.features, jnp.float32)
    b = jnp.asarray(data.labels, jnp.float32)
    n, m, d = A.shape
    x, y, lam, losses = jnp.zeros(d), jnp.zeros(d), jnp.zeros((n, d)), []
    with jax.default_matmul_precision("highest"):
        for _ in range(rounds):
            z = b * jnp.einsum("nmd,d->nm", A, x)
            g = jnp.einsum("nmd,nm->nd", A, -jax.nn.sigmoid(-z) * b) / m + mu * x
            s = jax.nn.sigmoid(z)
            H = (jnp.einsum("nmd,nm,nme->nde", A, s * (1 - s), A) / m
                 + (mu + alpha + rho) * jnp.eye(d))
            L = jnp.linalg.cholesky(H)
            y_i = jax.vmap(
                lambda Li, r: jax.scipy.linalg.cho_solve((Li, True), r)
            )(L, g - lam + rho * y)
            y = y_i.mean(0)  # eq. 13
            lam = lam + rho * (y_i - y)  # eq. 12
            x = x - y  # eq. 14
            z = b * jnp.einsum("nmd,d->nm", A, x)
            losses.append(jnp.mean(jnp.logaddexp(0.0, -z)) + 0.5 * mu * x @ x)
    return np.asarray(losses), np.asarray(x)


def _engine_run(sol, obj, data, sched, rounds=6):
    mesh = make_client_mesh(1) if sched == "shard_map" else None
    return engine.run(sol, obj, data, rounds, key=jax.random.PRNGKey(0),
                      block_size=4, mesh=mesh)


@pytest.mark.parametrize("sched", ["scan", "shard_map"])
def test_fednew_identity_codec_matches_plain_algorithm1(problem, sched):
    """Plain FedNew (identity codec) through the engine follows the float32
    Algorithm 1 loop above. Both sides are f32 Cholesky solves of the same
    system, summed in other orders: rtol 1e-5 on the loss (about 80 f32
    ulps) and 1e-5 on the iterate, whose entries are O(1)."""
    obj, data = problem
    st, m = _engine_run(engine.get_solver("fednew", **HP), obj, data, sched)
    loss, x = _plain_algorithm1(data, 6, HP["rho"], HP["alpha"])
    np.testing.assert_allclose(np.asarray(m.loss), loss, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(st.x), x, rtol=0, atol=1e-5)


@pytest.mark.parametrize("sched", ["scan", "shard_map"])
@pytest.mark.parametrize("form", ["bits", "codec"])
def test_q_fednew_spellings_match_host_run(problem, sched, form):
    """Q-FedNew's two spellings (bits=3 sugar and the explicit stoch_quant
    codec) give the same bits under one schedule, and both match the
    one-round-per-call host driver (``fednew.run``): bit for bit under scan,
    to float tolerance under shard_map, whose collectives sum in another
    order."""
    obj, data = problem
    spelled = {
        "bits": ("q-fednew", {**HP, "bits": 3}),
        "codec": ("fednew", {**HP, "codec": {"name": "stoch_quant", "bits": 3}}),
    }
    (name, hp), (other_name, other_hp) = spelled[form], spelled[
        "codec" if form == "bits" else "bits"]
    st, m = _engine_run(engine.get_solver(name, **hp), obj, data, sched)
    st_o, m_o = _engine_run(engine.get_solver(other_name, **other_hp), obj,
                            data, sched)
    np.testing.assert_array_equal(np.asarray(m.loss), np.asarray(m_o.loss))
    np.testing.assert_array_equal(np.asarray(st.x), np.asarray(st_o.x))
    st_h, m_h = fednew.run(obj, data, fednew.FedNewConfig(**hp), 6,
                           key=jax.random.PRNGKey(0))
    if sched == "scan":
        np.testing.assert_array_equal(np.asarray(m.loss), np.asarray(m_h.loss))
        np.testing.assert_array_equal(np.asarray(st.x), np.asarray(st_h.x))
    else:
        np.testing.assert_allclose(np.asarray(m.loss), np.asarray(m_h.loss),
                                   rtol=1e-6)
        np.testing.assert_allclose(np.asarray(st.x), np.asarray(st_h.x),
                                   rtol=0, atol=1e-6)
    assert float(m.uplink_bits_per_client[0]) == 3 * data.dim + 32


def test_fednew_key_untouched_by_deterministic_codecs(problem):
    """Deterministic codecs never split the run key (the historical FedNew
    behavior); stochastic ones consume it every round."""
    obj, data = problem
    key = jax.random.PRNGKey(7)
    for codec, moves in [(None, False), ({"name": "topk", "k": 5}, False),
                         ({"name": "stoch_quant", "bits": 2}, True)]:
        hp = dict(HP, codec=codec) if codec else HP
        st, _ = engine.run(engine.get_solver("fednew", **hp), obj, data, 3,
                           key=key)
        changed = not np.array_equal(np.asarray(st.key), np.asarray(key))
        assert changed == moves, codec


def test_topk_codec_converges_through_engine(problem):
    """fednew+topk (diff feedback) through the scan engine: descent to
    within 1% of the optimum f*, which eight Newton steps on the global
    objective find here, at a tenth of the coordinates per message."""
    obj, data = problem
    sol = engine.get_solver(
        "fednew", rho=0.02, alpha=0.03, hessian_period=1,
        codec={"name": "topk", "fraction": 0.1, "value_bits": 32},
    )
    assert sol.name == "fednew+topk"
    _, m = engine.run(sol, obj, data, 40, key=jax.random.PRNGKey(0))
    loss = np.asarray(m.loss)
    assert np.all(np.isfinite(loss))
    x = jnp.zeros(data.dim)
    for _ in range(8):
        x = x - jnp.linalg.solve(obj.global_hessian(x, data),
                                 obj.global_grad(x, data))
    assert float(jnp.linalg.norm(obj.global_grad(x, data))) < 1e-6
    f_star = float(obj.global_loss(x, data))
    assert f_star <= loss[-1] < 1.01 * f_star
    # exact metric: k=10 coords at 32-bit values + 7-bit indices
    assert float(m.uplink_bits_per_client[0]) == 10 * (32 + 7)


def test_codec_state_rides_shard_map_carry():
    """topk's error-feedback state is per-client state in the sharded
    engine too: scan and shard_map trajectories agree to float tolerance.
    Delegates to the registry-wide conformance battery (the same leg runs
    for every solver in tests/test_solver_conformance.py)."""
    import conformance as conf

    case = next(c for c in conf.CASES if c.label == "fednew-topk")
    state_s, metrics_s = conf.run_case(case, rounds=8)
    state_m, metrics_m = conf.run_case_sharded(case, rounds=8)
    conf.assert_tree_close(state_s, state_m, rtol=case.rtol)
    conf.assert_tree_close(metrics_s, metrics_m, rtol=case.rtol)


def test_bit_schedule_through_engine_matches_ledger(problem):
    """Round-indexed bits inside one compiled scan block: the traced metric
    follows the schedule and matches the RunResult integer ledger."""
    obj, data = problem
    sol = engine.get_solver(
        "fednew", **HP, codec={"name": "bit_schedule",
                               "schedule": [[0, 2], [3, 4]]},
    )
    _, m = engine.run(sol, obj, data, 6, key=jax.random.PRNGKey(0),
                      block_size=6)
    d = data.dim
    want = [2 * d + 32] * 3 + [4 * d + 32] * 3
    np.testing.assert_array_equal(
        np.asarray(m.uplink_bits_per_client, np.float64), want
    )


def test_config_rejects_bits_plus_codec():
    with pytest.raises(ValueError, match="not both"):
        fednew.FedNewConfig(bits=3, codec={"name": "topk", "k": 2})
    with pytest.raises(ValueError, match="registered codecs"):
        fednew.FedNewConfig(codec={"name": "nope"})
    # spec-build validation fires through the engine registry too
    with pytest.raises(ValueError, match="valid params"):
        api.SolverSpec("fednew", {"codec": {"name": "topk", "j": 2}})


# ---------------------------------------------------------------------------
# netsim
# ---------------------------------------------------------------------------


def test_netsim_homogeneous_round_time():
    links = comm.build_links(4, uplink_mbps=10.0, downlink_mbps=100.0,
                             latency_s=0.05)
    # 1e6 bits up at 10 Mbps = 0.1 s; 1e6 down at 100 Mbps = 0.01 s; + 2*lat
    t = comm.round_time_s(links, 10**6, 10**6)
    assert t == pytest.approx(0.1 + 0.01 + 0.1)
    # empty round moves nothing
    assert comm.round_time_s(links, 10**6, 10**6,
                             np.zeros(4)) == 0.0
    # masked round: only sampled clients gate the barrier
    assert comm.round_time_s(links, 10**6, 10**6,
                             np.array([1, 0, 0, 0])) == pytest.approx(t)


def test_netsim_heterogeneous_deterministic_and_straggler_bound():
    kw = dict(uplink_mbps=10.0, downlink_mbps=100.0, latency_s=0.05,
              heterogeneity="lognormal", sigma=0.8, seed=3)
    a, b = comm.build_links(64, **kw), comm.build_links(64, **kw)
    np.testing.assert_array_equal(a.uplink_bps, b.uplink_bps)
    assert comm.build_links(64, **{**kw, "seed": 4}).uplink_bps[0] != \
        a.uplink_bps[0]
    # the barrier is the max over sampled clients: the full-fleet round is
    # at least as slow as any sub-cohort's
    full = comm.round_time_s(a, 10**6, 10**6)
    half = comm.round_time_s(a, 10**6, 10**6,
                             np.arange(64) < 32)
    assert full >= half > 0


def test_netsim_simulate_rounds_consumes_ledgers():
    links = comm.build_links(2, uplink_mbps=1.0, downlink_mbps=1.0,
                             latency_s=0.0)
    per_round, total = comm.simulate_rounds(
        links, [10**6, 2 * 10**6], [0, 0], None
    )
    assert per_round == [pytest.approx(1.0), pytest.approx(2.0)]
    assert total == pytest.approx(3.0)
    with pytest.raises(ValueError, match="same rounds"):
        comm.simulate_rounds(links, [1], [1, 2], None)


# ---------------------------------------------------------------------------
# declarative surface (CompressionSpec / NetworkSpec -> RunResult)
# ---------------------------------------------------------------------------


def _comm_spec(**over):
    kw = dict(
        partition=api.PartitionSpec(dataset="custom", n_clients=6,
                                    samples_per_client=16, dim=12, seed=0),
        solver=api.SolverSpec("fednew", {"rho": 0.1, "alpha": 0.03}),
        schedule=api.ScheduleSpec(rounds=4, block_size=2),
    )
    kw.update(over)
    return api.ExperimentSpec(**kw)


def test_compression_network_specs_round_trip_and_validate():
    spec = _comm_spec(
        compression=api.CompressionSpec(codec="topk",
                                        params={"fraction": 0.25}),
        network=api.NetworkSpec(uplink_mbps=5.0, heterogeneity="lognormal",
                                sigma=0.4, seed=2),
    )
    assert api.ExperimentSpec.from_json(spec.to_json()) == spec
    # absent sections serialize as null and round-trip
    bare = _comm_spec()
    assert bare.to_dict()["compression"] is None
    assert api.ExperimentSpec.from_json(bare.to_json()) == bare
    with pytest.raises(ValueError, match="registered codecs"):
        api.CompressionSpec(codec="gzip")
    with pytest.raises(ValueError, match="valid params"):
        api.CompressionSpec(codec="topk", params={"frac": 0.1})
    with pytest.raises(ValueError, match="positive"):
        api.NetworkSpec(uplink_mbps=0.0)
    with pytest.raises(ValueError, match="heterogeneity"):
        api.NetworkSpec(heterogeneity="pareto")
    with pytest.raises(ValueError, match="no effect"):
        api.NetworkSpec(sigma=0.5)  # sigma without the lognormal law
    with pytest.raises(ValueError, match="fednew"):
        _comm_spec(solver=api.SolverSpec("fedgd", {"lr": 1.0}),
                   compression=api.CompressionSpec(codec="identity"))
    with pytest.raises(ValueError, match="conflicts"):
        _comm_spec(solver=api.SolverSpec("fednew", {"bits": 3}),
                   compression=api.CompressionSpec(codec="identity"))


def test_run_result_downlink_and_simulated_time():
    spec = _comm_spec(
        compression=api.CompressionSpec(codec="stoch_quant",
                                        params={"bits": 2}),
        network=api.NetworkSpec(uplink_mbps=10.0, downlink_mbps=100.0,
                                latency_s=0.01),
    )
    res = api.run(spec)
    d, n, rounds = res.dim, res.n_clients, res.rounds
    # uplink ledger follows the codec; downlink is the word*d broadcast
    assert res.uplink_bits_total == [(2 * d + 32) * n] * rounds
    assert res.downlink_bits_total == [32 * d * n] * rounds
    assert res.cumulative_downlink_bits_total[-1] == 32 * d * n * rounds
    for v in res.downlink_bits_total:
        assert type(v) is int
    # simulated time: per-message bits over the homogeneous links + latency
    expect = (32 * d) / 100e6 + (2 * d + 32) / 10e6 + 0.02
    assert res.simulated_round_s == [pytest.approx(expect)] * rounds
    assert res.simulated_time_s == pytest.approx(expect * rounds)
    # solver routed through the codec registry
    assert res.solver == "fednew+stoch_quant"
    # JSON survives with the new fields
    payload = json.loads(json.dumps(res.to_dict()))
    assert payload["simulated_time_s"] == pytest.approx(res.simulated_time_s)


def test_downlink_charged_to_sampled_clients_only():
    spec = _comm_spec(
        schedule=api.ScheduleSpec(rounds=6),
        participation=api.ParticipationSpec(fraction=0.5, kind="fixed",
                                            seed=1),
    )
    res = api.run(spec)
    assert res.sampled_clients == [3] * 6
    assert res.downlink_bits_total == [32 * res.dim * 3] * 6
    assert res.simulated_round_s is None  # no network section -> no sim


def test_network_masks_gate_simulated_time():
    """Under partial participation the straggler barrier runs over the
    sampled cohort only: simulated time is deterministic per seeds and no
    slower than the full-fleet run of the same spec."""
    net = api.NetworkSpec(uplink_mbps=1.0, downlink_mbps=10.0,
                          latency_s=0.05, heterogeneity="lognormal",
                          sigma=1.0, seed=0)
    part = api.ParticipationSpec(fraction=0.5, kind="fixed", seed=3)
    spec_half = _comm_spec(schedule=api.ScheduleSpec(rounds=5),
                           participation=part, network=net)
    spec_full = _comm_spec(schedule=api.ScheduleSpec(rounds=5), network=net)
    t_half = api.run(spec_half).simulated_time_s
    t_full = api.run(spec_full).simulated_time_s
    assert 0 < t_half <= t_full
    assert api.run(spec_half).simulated_time_s == t_half  # deterministic


def test_comm_tradeoff_smoke_artifact_schema(monkeypatch, tmp_path):
    """The benchmark's smoke mode emits the artifact schema CI asserts."""
    monkeypatch.setenv("COMM_SMOKE", "1")
    monkeypatch.setenv("BENCH_ROUNDS", "4")
    import importlib

    import benchmarks.comm_tradeoff as ct
    ct = importlib.reload(ct)
    monkeypatch.setattr(
        "benchmarks.common.OUT_DIR", str(tmp_path), raising=False
    )
    results = ct.main()
    from scripts.check_comm_artifact import check_payload

    check_payload(results)
    assert results["config"]["smoke"] is True
    assert len(results["runs"]) == 3
    # reload once more to restore non-smoke module constants for any
    # later importer in this process
    monkeypatch.delenv("COMM_SMOKE")
    monkeypatch.delenv("BENCH_ROUNDS")
    importlib.reload(ct)
