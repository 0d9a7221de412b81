"""Composable uplink compression codecs (the ``repro.comm`` registry).

FedNew's headline claim is communication efficiency, but compression is a
*family* of operators, not one quantizer: FedNL studies Newton-type FL under
generic compressors, and top-k sparsification with error feedback composes
with Newton updates just as well as the paper's eqs. 25-30 stochastic
quantizer. This module makes the compressor a first-class, swappable part of
the solver:

    Codec protocol
      init_state(n, d, dtype)   per-client codec memory, a ``(n, width)``
                                array that rides the engine's scan/shard_map
                                carry (``FedNewState.comm``): the previous
                                quantized vector for stoch_quant, the
                                error-feedback residual for topk, nothing
                                (width 0) for identity.
      encode(keys, y, state, step) -> wire
                                Client-side: compress a ``(n, d)`` batch of
                                directions. ``wire`` is a dict of arrays —
                                exactly what crosses the uplink. Batched over
                                the leading client axis, traceable, safe
                                inside ``lax.scan``/``shard_map``.
      decode(wire, state, step) -> y_tx
                                Server-side reconstruction from the wire
                                payload and the server's mirror of the codec
                                state. Computed ONCE per round; its result
                                also feeds ``update_state``, so client and
                                server hold bit-identical views by
                                construction.
      update_state(y_tx, y, state, step) -> new_state
                                Client-side codec-state advance given the
                                shared reconstruction (ŷ := y_tx for
                                stoch_quant, ĝ := y_tx or e := y+e-y_tx for
                                topk).
      payload_bits(d, word, round_index) -> int
                                EXACT uplink bits per message as a Python int
                                (arbitrary precision — the same contract as
                                ``quantization.payload_bits``); feeds the
                                integer ledger in ``repro.api``.
      payload_bits_metric(d, word, step) -> traced scalar
                                The per-round metric the compiled step emits;
                                equals ``payload_bits`` lowered via
                                ``payload_bits_array`` (round-indexed for
                                ``bit_schedule``).

Registered codecs: ``identity``, ``stoch_quant`` (wraps the dispatched
eqs. 25-30 kernel — ``q-fednew`` is literally ``fednew`` + this codec, bit
for bit), ``topk`` (magnitude sparsification with per-client error
feedback), ``bit_schedule`` (round-indexed quantizer widths, e.g. low-bits
warmup). Specs are JSON-able dicts ``{"name": ..., **params}`` so
``repro.api.CompressionSpec`` round-trips them losslessly.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Mapping, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.quantization import (
    R_BITS,
    exact_payload_bits,
    payload_bits,
    payload_bits_array,
    word_bits,
)
from repro.kernels import dispatch

Wire = Dict[str, jax.Array]
CodecSpec = Union[str, Mapping[str, Any], "Codec"]


class Codec:
    """Base codec: full-precision pass-through behavior, no state, no RNG.

    Subclasses override the pieces that differ; every method is batched over
    a leading client axis and traceable (the engine calls encode/decode from
    inside compiled scan blocks, possibly in a ``shard_map`` manual region
    where each device sees its local client rows only).
    """

    name = "identity"
    needs_rng = False  # True => the solver splits its PRNG key per round

    def __init__(self, backend: str = "auto"):
        del backend  # registry uniformity; the base codec is pure jnp

    # -- spec / registry ----------------------------------------------------

    def spec(self) -> Dict[str, Any]:
        """JSON-able ``{"name": ..., **params}`` that rebuilds this codec."""
        return {"name": self.name}

    # -- state --------------------------------------------------------------

    def state_width(self, d: int) -> int:
        return 0

    def init_state(self, n_clients: int, d: int, dtype) -> jax.Array:
        return jnp.zeros((n_clients, self.state_width(d)), dtype)

    # -- exact accounting ---------------------------------------------------

    def payload_bits(self, d: int, word: int, round_index: int = 0) -> int:
        """Exact Python-int uplink bits for ONE client's message."""
        return exact_payload_bits(d, word)

    def payload_bits_metric(self, d: int, word: int, step) -> jax.Array:
        """Traced per-round metric; round-invariant codecs lower the exact
        count once (``step`` unused)."""
        del step
        return payload_bits_array(self.payload_bits(d, word))

    # -- transform ----------------------------------------------------------
    #
    # One round is encode -> decode -> update_state. ``decode`` is computed
    # ONCE per round and its result is handed to ``update_state``, so the
    # client's carried state and the server's reconstruction agree bit for
    # bit by construction — no duplicated float chains that separate
    # compilations could contract differently.

    def encode(
        self, keys: Optional[jax.Array], y: jax.Array, state: jax.Array, step
    ) -> Wire:
        del keys, state, step
        return {"values": y}

    def decode(self, wire: Wire, state: jax.Array, step) -> jax.Array:
        del state, step
        return wire["values"]

    def update_state(
        self, y_tx: jax.Array, y: jax.Array, state: jax.Array, step
    ) -> jax.Array:
        """Client-side state advance, given the shared reconstruction
        ``y_tx = decode(encode(...))``. Stateless codecs keep state as-is."""
        del y_tx, y, step
        return state


class IdentityCodec(Codec):
    """Full precision on the wire: ``word·d`` bits per message (exactly the
    pre-codec FedNew accounting)."""


class StochQuantCodec(Codec):
    """Paper eqs. 25-30 stochastic quantization of ``y - state`` (``state``
    is the previously quantized vector ŷ, the built-in error feedback).

    The transform itself is reached through ``repro.kernels.dispatch`` —
    compiled Pallas on TPU, jnp reference elsewhere — with the PR-2 contract
    that the same keys give the same integer levels on every backend. The
    wire is ``(levels, R)``: int levels plus the per-client float32-accounted
    range scalar (the paper's ``bits·d + 32``). ``decode`` rebuilds
    ``state + Δ·levels - R`` with the reference's eq. 30 expression, which is
    bit-identical to the ``QuantResult.y_hat`` the kernel path emits.
    """

    name = "stoch_quant"
    needs_rng = True

    def __init__(self, bits: int, backend: str = "auto"):
        if not isinstance(bits, int) or isinstance(bits, bool) or bits < 1:
            raise ValueError(
                f"stoch_quant bits must be a positive int, got {bits!r}"
            )
        self.bits = bits
        self.backend = dispatch.validate_backend(backend)

    def spec(self) -> Dict[str, Any]:
        return {"name": self.name, "bits": self.bits}

    def state_width(self, d: int) -> int:
        return d

    def payload_bits(self, d: int, word: int, round_index: int = 0) -> int:
        del word, round_index  # quantized words; R accounted at R_BITS
        return payload_bits(self.bits, d)

    def encode(self, keys, y, state, step):
        del step
        qr = dispatch.quantize_with_keys(
            keys, y, state, self.bits, backend=self.backend
        )
        # The wire is the integer levels plus the range scalar the client
        # actually transmits (accounted at R_BITS); delta is derived from R
        # on both ends with the same expression. The kernel wrapper's own
        # fused reconstruction is NOT used — the round's single ``decode``
        # serves server and client state alike (see the base-class note).
        R = jnp.max(jnp.abs(y - state), axis=-1)
        return {"levels": qr.levels, "range": R}

    def decode(self, wire, state, step):
        del step
        return _dequantize(wire["levels"], wire["range"], state, self.bits)

    def update_state(self, y_tx, y, state, step):
        del y, state, step
        return y_tx  # the reconstruction IS the next round's ŷ


def _dequantize(levels, R, state, bits: int) -> jax.Array:
    """Eq. 30 with the reference's exact expression (see
    ``repro.core.quantization.quantize``): ŷ = ŷ_prev + Δ·q - R."""
    n_levels = (1 << bits) - 1
    delta = 2.0 * R / n_levels
    return state + delta[:, None] * levels.astype(state.dtype) - R[:, None]


class TopKCodec(Codec):
    """Magnitude top-k sparsification with per-client error feedback.

    Two feedback laws, selected by ``feedback`` (both carry one ``(n, d)``
    error-feedback array in the scan/shard_map state):

      ``"diff"`` (default) — difference coding against a carried per-client
        *reconstruction* g_i (the EF21 structure, and exactly how the
        eqs. 25-30 quantizer uses its previous quantized vector): transmit
        the top-k coordinates of ``y_i - g_i`` scaled by ``eta``, both ends
        update ``g_i <- g_i + scatter(wire)``, and the PS aggregates the
        DENSE estimate g_i. The aggregate tracks mean y_i with geometrically
        decaying error, which is what keeps Newton-type outer steps stable
        under aggressive sparsification — the classic residual law feeds
        rank-k directions straight into eq. 14 and diverges for small k
        (measured in benchmarks/comm_tradeoff.py).
      ``"residual"`` — the classic EF-SGD law: compress ``u = y_i + e_i``,
        transmit top-k(u)*eta, keep ``e_i <- u - decode(wire)``; the PS
        aggregates the sparse message itself.

    The wire costs ``k * (value_bits + ceil(log2 d))`` bits exactly —
    values at the transmitted word size (``value_bits=32`` casts float64
    runs' values to float32 on the wire, halving value cost; ``None`` sends
    full words) plus minimal index addressing. ``k`` may be given directly
    or as ``fraction`` of d (ceil, at least 1). ``eta`` scales the
    transmitted update (an estimate step size; <1 trades rounds for
    stability).
    """

    name = "topk"
    FEEDBACK = ("diff", "residual")

    def __init__(
        self,
        k: Optional[int] = None,
        fraction: Optional[float] = None,
        feedback: str = "diff",
        eta: float = 1.0,
        value_bits: Optional[int] = None,
        backend: str = "auto",
    ):
        del backend  # accepted for registry uniformity; topk is pure jnp
        if (k is None) == (fraction is None):
            raise ValueError("topk takes exactly one of k= or fraction=")
        if k is not None and (not isinstance(k, int) or isinstance(k, bool)
                              or k < 1):
            raise ValueError(f"topk k must be a positive int, got {k!r}")
        if fraction is not None and not (0.0 < fraction <= 1.0):
            raise ValueError(
                f"topk fraction must be in (0, 1], got {fraction!r}"
            )
        if feedback not in self.FEEDBACK:
            raise ValueError(
                f"topk feedback must be one of {self.FEEDBACK}, got "
                f"{feedback!r}"
            )
        if not (0.0 < eta <= 1.0):
            raise ValueError(f"topk eta must be in (0, 1], got {eta!r}")
        if value_bits is not None and value_bits not in (32, 64):
            raise ValueError(
                f"topk value_bits must be None, 32 or 64, got {value_bits!r}"
            )
        self.k = k
        self.fraction = fraction
        self.feedback = feedback
        self.eta = eta
        self.value_bits = value_bits

    def spec(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {"name": self.name}
        if self.k is not None:
            out["k"] = self.k
        else:
            out["fraction"] = self.fraction
        if self.feedback != "diff":
            out["feedback"] = self.feedback
        if self.eta != 1.0:
            out["eta"] = self.eta
        if self.value_bits is not None:
            out["value_bits"] = self.value_bits
        return out

    def resolved_k(self, d: int) -> int:
        if self.k is not None:
            return min(self.k, d)
        return max(1, min(d, math.ceil(self.fraction * d)))

    @staticmethod
    def index_bits(d: int) -> int:
        """Minimal bits to address a coordinate of a length-d vector."""
        return max(1, (d - 1).bit_length())

    def state_width(self, d: int) -> int:
        return d  # reconstruction g_i ("diff") or residual e_i ("residual")

    def payload_bits(self, d: int, word: int, round_index: int = 0) -> int:
        del round_index
        vbits = self.value_bits if self.value_bits is not None else word
        return self.resolved_k(d) * (vbits + self.index_bits(d))

    def _sparsify(self, u: jax.Array) -> Wire:
        k = self.resolved_k(u.shape[-1])
        _, idx = jax.lax.top_k(jnp.abs(u), k)
        vals = jnp.take_along_axis(u, idx, axis=-1) * self.eta
        if self.value_bits == 32 and vals.dtype != jnp.float32:
            vals = vals.astype(jnp.float32).astype(vals.dtype)
        return {"values": vals, "indices": idx.astype(jnp.int32)}

    def encode(self, keys, y, state, step):
        del keys, step
        if self.feedback == "diff":
            return self._sparsify(y - state)
        return self._sparsify(y + state)  # residual: direction + carried error

    def decode(self, wire, state, step):
        del step
        sparse = self._scatter(wire, state.shape[-1], state.dtype)
        return state + sparse if self.feedback == "diff" else sparse

    def update_state(self, y_tx, y, state, step):
        del step
        if self.feedback == "diff":
            return y_tx  # the dense estimate g_i both ends now hold
        return (y + state) - y_tx  # e_i: everything the wire dropped

    @staticmethod
    def _scatter(wire, d: int, dtype) -> jax.Array:
        scatter_one = lambda v, i: jnp.zeros((d,), dtype).at[i].set(v)
        return jax.vmap(scatter_one)(
            wire["values"].astype(dtype), wire["indices"]
        )



class BitScheduleCodec(Codec):
    """Round-indexed stochastic-quantizer widths (e.g. low-bits warmup).

    ``schedule`` is ``((round, bits), ...)``: from ``round`` onward messages
    use ``bits`` (first entry must start at round 0). Encode/decode pick the
    stage with ``lax.switch`` on the traced step counter, so the whole
    schedule lives inside one compiled scan block; ``payload_bits`` resolves
    the stage from the host-side round index, keeping the integer ledger
    exact per round.
    """

    name = "bit_schedule"
    needs_rng = True

    def __init__(self, schedule, backend: str = "auto"):
        try:
            stages = tuple((int(r), int(b)) for r, b in schedule)
        except (TypeError, ValueError):
            raise ValueError(
                "bit_schedule schedule must be a sequence of (round, bits) "
                f"pairs, got {schedule!r}"
            ) from None
        if not stages:
            raise ValueError("bit_schedule schedule must be non-empty")
        if stages[0][0] != 0:
            raise ValueError(
                f"bit_schedule must start at round 0, got {stages!r}"
            )
        if any(b < 1 for _, b in stages):
            raise ValueError(f"bit_schedule bits must be >= 1, got {stages!r}")
        if any(r1 <= r0 for (r0, _), (r1, _) in zip(stages, stages[1:])):
            raise ValueError(
                f"bit_schedule rounds must be strictly increasing, got {stages!r}"
            )
        self.schedule = stages
        self.backend = dispatch.validate_backend(backend)
        self._stages = tuple(
            StochQuantCodec(bits, backend) for _, bits in stages
        )

    def spec(self) -> Dict[str, Any]:
        return {"name": self.name, "schedule": [list(s) for s in self.schedule]}

    def state_width(self, d: int) -> int:
        return d  # shared ŷ error-feedback state across stages

    def stage_index(self, round_index: int) -> int:
        """Host-side stage lookup (exact-ledger path)."""
        idx = 0
        for i, (start, _) in enumerate(self.schedule):
            if round_index >= start:
                idx = i
        return idx

    def _traced_stage(self, step) -> jax.Array:
        starts = jnp.asarray([s for s, _ in self.schedule], jnp.int32)
        return jnp.sum(step >= starts).astype(jnp.int32) - 1

    def payload_bits(self, d: int, word: int, round_index: int = 0) -> int:
        bits = self.schedule[self.stage_index(round_index)][1]
        return payload_bits(bits, d)

    def payload_bits_metric(self, d, word, step):
        per_stage = jnp.stack([
            payload_bits_array(self.payload_bits(d, word, start))
            for start, _ in self.schedule
        ])
        return per_stage[self._traced_stage(step)]

    def encode(self, keys, y, state, step):
        branches = [
            (lambda c: lambda k_, y_, s_: c.encode(k_, y_, s_, 0))(c)
            for c in self._stages
        ]
        return jax.lax.switch(self._traced_stage(step), branches, keys, y, state)

    def decode(self, wire, state, step):
        branches = [
            (lambda c: lambda w_, s_: c.decode(w_, s_, 0))(c)
            for c in self._stages
        ]
        return jax.lax.switch(self._traced_stage(step), branches, wire, state)

    def update_state(self, y_tx, y, state, step):
        del y, state, step
        return y_tx  # every stage is a stoch_quant: ŷ := the reconstruction


def client_keys(sub, n_local: int, axis_name, n_global_clients):
    """Per-client PRNG keys for a stochastic codec, identical across
    schedules: split for ALL clients and slice this shard's rows, so the
    client-axis layout never changes the randomness. Every solver that
    encodes through an RNG codec (fednew, fednl) needs this device-count
    invariance."""
    if axis_name is None:
        return jax.random.split(sub, n_local)
    if n_global_clients is None:
        raise ValueError("sharded codec encoding needs static n_global_clients")
    keys = jax.random.split(sub, n_global_clients)
    start = jax.lax.axis_index(axis_name) * n_local
    return jax.lax.dynamic_slice_in_dim(keys, start, n_local)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, type] = {}


def register_codec(name: str, cls: type) -> None:
    """Register a codec class (idempotent; later wins)."""
    _REGISTRY[name] = cls


def codec_names() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


register_codec("identity", IdentityCodec)
register_codec("stoch_quant", StochQuantCodec)
register_codec("topk", TopKCodec)
register_codec("bit_schedule", BitScheduleCodec)


def normalize_spec(spec: CodecSpec) -> Dict[str, Any]:
    """Canonical dict form of a codec spec (validates the name)."""
    if isinstance(spec, Codec):
        return spec.spec()
    if isinstance(spec, str):
        out: Dict[str, Any] = {"name": spec}
    elif isinstance(spec, Mapping):
        out = dict(spec)
    else:
        raise ValueError(
            f"codec spec must be a name, a {{'name': ...}} mapping, or a "
            f"Codec, got {type(spec).__name__}"
        )
    name = out.get("name")
    if name not in _REGISTRY:
        raise ValueError(
            f"unknown codec {name!r}; registered codecs: "
            f"{', '.join(codec_names())}"
        )
    return out


def build_codec(spec: CodecSpec, *, backend: str = "auto") -> Codec:
    """Build a codec from its JSON-able spec. Unknown names/params raise
    ``ValueError`` naming the valid choices (the contract ``repro.api``'s
    spec validation relies on)."""
    if isinstance(spec, Codec):
        return spec
    norm = normalize_spec(spec)
    name = norm.pop("name")
    cls = _REGISTRY[name]
    try:
        return cls(**norm, backend=backend)
    except TypeError as e:
        import inspect

        params = [
            p for p in inspect.signature(cls.__init__).parameters
            if p not in ("self", "backend")
        ]
        raise ValueError(
            f"bad params for codec {name!r}: {e}; valid params: {params}"
        ) from None


# ---------------------------------------------------------------------------
# one round of the codec, and its pytree helpers
# ---------------------------------------------------------------------------


def init_state_tree(codec: Codec, n_clients: int, tree):
    """Per-client codec state for a param pytree: one
    ``(n, state_width(leaf_size))`` array per leaf — exactly the layout
    ``encode_decode_tree`` consumes (each leaf is an independent codec
    message, flattened to its own vector)."""
    return jax.tree.map(
        lambda l: codec.init_state(n_clients, int(l.size), l.dtype), tree
    )


def tree_payload_bits(codec: Codec, template, round_index: int = 0) -> int:
    """EXACT Python-int uplink bits for ONE client's pytree message: the
    codec applied leaf-wise means one payload per (client, leaf), so the
    total is the per-leaf ``payload_bits`` summed over leaves — e.g.
    ``bits·size + R_BITS`` per leaf for stoch_quant, matching
    ``fednew_hf._uplink_bits``'s ``r_bits = R_BITS · n_leaves`` accounting.
    ``template`` is any pytree with the transmitted shapes/dtypes (the
    direction tree, or ``jax.eval_shape`` structs)."""
    return sum(
        codec.payload_bits(int(l.size), word_bits(l.dtype), round_index)
        for l in jax.tree.leaves(template)
    )


def tree_payload_bits_metric(codec: Codec, template, step):
    """Traced per-round counterpart of :func:`tree_payload_bits` (sum of the
    per-leaf ``payload_bits_metric``; round-indexed codecs resolve the stage
    from the traced ``step`` exactly as on the flat path)."""
    total = None
    for l in jax.tree.leaves(template):
        b = codec.payload_bits_metric(int(l.size), word_bits(l.dtype), step)
        total = b if total is None else total + b
    return total


def encode_decode_tree(codec: Codec, key, tree, state_tree, *, step=0):
    """Leaf-wise codec application over a per-client pytree: every
    ``(n_clients, ...)`` leaf is flattened to ``(n, leaf_size)``, encoded,
    and decoded back; per-leaf keys are ``fold_in(key, leaf_index)`` split
    per client — exactly the key schedule fednew_hf's original hand-rolled
    quantizer used, so Q-FedNew-HF trajectories are unchanged bit for bit
    (its step builders now call this directly). Returns
    ``(y_tx_tree, new_state_tree)``."""
    leaves, treedef = jax.tree.flatten(tree)
    prev = jax.tree.leaves(state_tree)
    tx, states = [], []
    for j, (leaf, p) in enumerate(zip(leaves, prev)):
        n = leaf.shape[0]
        keys = None
        if codec.needs_rng:
            keys = jax.random.split(jax.random.fold_in(key, j), n)
        flat, pflat = leaf.reshape(n, -1), p.reshape(n, -1)
        wire = codec.encode(keys, flat, pflat, step)
        y_tx = codec.decode(wire, pflat, step)
        new_state = codec.update_state(y_tx, flat, pflat, step)
        tx.append(y_tx.reshape(leaf.shape).astype(leaf.dtype))
        states.append(new_state.reshape(p.shape).astype(p.dtype))
    return jax.tree.unflatten(treedef, tx), jax.tree.unflatten(treedef, states)


def encode_decode(codec: Codec, key, y, state, *, step=0, axis_name=None,
                  n_global_clients=None):
    """One round of the uplink codec, ``(y_tx, new_state)``, for either
    layout of the per-client directions ``y``. A bare ``(n, d)`` array is
    one message per client, keyed by :func:`client_keys` (the same keys
    under every schedule); a pytree goes through :func:`encode_decode_tree`
    and its per-leaf key schedule, on one device only. ``key`` is the
    round's subkey, unused by a deterministic codec."""
    if not jax.tree_util.treedef_is_leaf(jax.tree.structure(y)):
        return encode_decode_tree(codec, key, y, state, step=step)
    keys = None
    if codec.needs_rng:
        keys = client_keys(key, y.shape[0], axis_name, n_global_clients)
    wire = codec.encode(keys, y, state, step)
    y_tx = codec.decode(wire, state, step)
    return y_tx, codec.update_state(y_tx, y, state, step)


def encode_decode_tree_one(codec: Codec, key, tree, state_tree, *, step=0):
    """Single-client variant (the shard_map one-client-per-shard route):
    leaves have no leading client axis; the per-leaf key is used as the one
    client's key directly — the schedule fednew_hf's shard_map step relies
    on (``dispatch.quantize`` draws from the un-split per-leaf key, which
    equals a batch of one with that key)."""
    leaves, treedef = jax.tree.flatten(tree)
    prev = jax.tree.leaves(state_tree)
    tx, states = [], []
    for j, (leaf, p) in enumerate(zip(leaves, prev)):
        keys = None
        if codec.needs_rng:
            keys = jax.random.fold_in(key, j)[None]
        flat, pflat = leaf.reshape(1, -1), p.reshape(1, -1)
        wire = codec.encode(keys, flat, pflat, step)
        y_tx = codec.decode(wire, pflat, step)
        new_state = codec.update_state(y_tx, flat, pflat, step)
        tx.append(y_tx.reshape(leaf.shape).astype(leaf.dtype))
        states.append(new_state.reshape(p.shape).astype(p.dtype))
    return jax.tree.unflatten(treedef, tx), jax.tree.unflatten(treedef, states)
