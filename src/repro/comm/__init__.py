"""``repro.comm`` — composable compression codecs + network-cost simulation.

Two halves, both riding the repo's *exact* communication ledgers:

  * :mod:`repro.comm.codecs` — the ``Codec`` protocol and registry
    (``identity`` / ``stoch_quant`` / ``topk`` / ``bit_schedule``). The
    solver's compressor is a swappable component: ``q-fednew`` is literally
    ``fednew`` + the ``stoch_quant`` codec (pinned bit-exact), and per-client
    codec state (previous quantized vector, error-feedback residual) rides
    the engine's scan/shard_map carry as ``FedNewState.comm``.
  * :mod:`repro.comm.netsim` — per-client bandwidth/latency models that
    consume the exact uplink + downlink ledgers and the replayed
    participation masks to produce simulated synchronous-round wall-clock
    (max over the sampled clients).

``repro.api`` exposes both declaratively (``CompressionSpec`` /
``NetworkSpec``); see docs/comm.md.
"""

from repro.comm.codecs import (
    BitScheduleCodec,
    Codec,
    IdentityCodec,
    StochQuantCodec,
    TopKCodec,
    build_codec,
    client_keys,
    codec_names,
    encode_decode,
    encode_decode_tree,
    encode_decode_tree_one,
    init_state_tree,
    normalize_spec,
    register_codec,
    tree_payload_bits,
    tree_payload_bits_metric,
)
from repro.comm.netsim import (
    ClientLinks,
    build_links,
    round_time_s,
    simulate_rounds,
)

__all__ = [
    "Codec",
    "IdentityCodec",
    "StochQuantCodec",
    "TopKCodec",
    "BitScheduleCodec",
    "build_codec",
    "client_keys",
    "codec_names",
    "normalize_spec",
    "register_codec",
    "encode_decode",
    "encode_decode_tree",
    "encode_decode_tree_one",
    "init_state_tree",
    "tree_payload_bits",
    "tree_payload_bits_metric",
    "ClientLinks",
    "build_links",
    "round_time_s",
    "simulate_rounds",
]
