"""Per-chip peaks by device kind, and the three-term roofline model of the
compiled dry-run artifacts (TPU v5e target).

    compute term    = HLO_FLOPs_per_chip / peak_FLOP/s
    memory term     = HLO_bytes_per_chip / HBM_bw
    collective term = collective_bytes_per_chip / link_bw

Sources: ``compiled.cost_analysis()`` (flops, bytes accessed) for the first
two; the third parses the post-SPMD HLO text (per-device program) and sums
operand bytes of every collective op (``repro.roofline.hlo``). All three are
per-chip quantities, so no further division by chip count is applied.

MODEL_FLOPS = 6·N·D (dense) / 6·N_active·D (MoE) is the "useful work"
yardstick; HLO_FLOPs/MODEL_FLOPS exposes remat/CG/attention overheads.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from repro.configs.base import InputShape, ModelConfig


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    flops_bf16: float  # FLOP/s
    hbm_bw: float  # bytes/s
    ici_link_bw: float  # bytes/s per chip-to-chip link


# Published per-chip peaks, keyed by ``jax.Device.device_kind``. A device
# that is not here has no roofline: nothing is scaled by another chip's
# peaks. Source: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16,
# 16 GB HBM at 819 GB/s, 1,600 Gbit/s interchip interconnect (4 links of
# 50 GB/s).
V5E = "TPU v5 lite"
DEVICE_PEAKS = {
    V5E: DevicePeaks(flops_bf16=197e12, hbm_bw=819e9, ici_link_bw=50e9),
}


def device_peaks(device_kind: str) -> Optional[DevicePeaks]:
    """The peaks of ``device_kind``, or None for a device not in the table
    (a CPU, say)."""
    return DEVICE_PEAKS.get(device_kind)


@dataclasses.dataclass(frozen=True)
class Roofline:
    """Roofline terms of one dry-run program on the production TPU v5e
    mesh (a described target, so its peaks are the v5e's by definition)."""

    flops_per_chip: float
    bytes_per_chip: float
    collective_bytes_per_chip: float
    model_flops_per_chip: float  # 6·N(active)·tokens / chips (0 for serving)
    peak_bytes_per_chip: float  # memory_analysis: argument+output+temp+gen

    @property
    def compute_s(self) -> float:
        return self.flops_per_chip / DEVICE_PEAKS[V5E].flops_bf16

    @property
    def memory_s(self) -> float:
        return self.bytes_per_chip / DEVICE_PEAKS[V5E].hbm_bw

    @property
    def collective_s(self) -> float:
        return self.collective_bytes_per_chip / DEVICE_PEAKS[V5E].ici_link_bw

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flop_ratio(self) -> float:
        return self.model_flops_per_chip / self.flops_per_chip if self.flops_per_chip else 0.0

    def as_dict(self) -> dict:
        return {
            "flops_per_chip": self.flops_per_chip,
            "bytes_per_chip": self.bytes_per_chip,
            "collective_bytes_per_chip": self.collective_bytes_per_chip,
            "model_flops_per_chip": self.model_flops_per_chip,
            "peak_bytes_per_chip": self.peak_bytes_per_chip,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "useful_flop_ratio": self.useful_flop_ratio,
        }


# ---------------------------------------------------------------------------
# parameter counting (analytic; matches lm.init_params)
# ---------------------------------------------------------------------------


def param_counts(cfg: ModelConfig) -> dict:
    """{'total': N, 'active': N_active} — active discounts MoE experts to the
    top-k actually touched per token (the 6·N_active·D convention)."""
    D, F, dh = cfg.d_model, cfg.d_ff, cfg.resolved_head_dim
    H, Hkv = cfg.n_heads, cfg.n_kv_heads
    per_kind_total = {}
    per_kind_active = {}
    attn_self = D * H * dh + 2 * D * Hkv * dh + H * dh * D
    # decoder layers of enc-dec models carry a same-shaped cross-attention
    attn = attn_self * 2 if cfg.is_encoder_decoder else attn_self
    dense_ffn = 3 * D * F if F else 0
    moe_total = cfg.n_experts * 3 * D * F + D * cfg.n_experts if cfg.is_moe else 0
    moe_active = cfg.experts_per_token * 3 * D * F + D * cfg.n_experts if cfg.is_moe else 0
    for kind in set(cfg.layer_pattern):
        if kind in ("global", "local", "bidir"):
            t = attn + (moe_total if cfg.is_moe else dense_ffn)
            a = attn + (moe_active if cfg.is_moe else dense_ffn)
        elif kind == "rglru":
            W = cfg.lru_width or D
            t = a = 2 * D * W + W * D + cfg.conv1d_width * W + 2 * W * W + W + dense_ffn
        elif kind == "mlstm":
            # up_l/up_r + conv + full qkv (P x P) + i/f gates + down
            Dp = int(cfg.mlstm_proj_factor * D)
            t = a = (2 * D * Dp + cfg.conv1d_width * Dp + 3 * Dp * Dp
                     + 2 * Dp * cfg.n_heads + Dp * D)
        elif kind == "slstm":
            # wx (D,4D) + block-diag recurrence (4D^2/H) + down + gated FFN
            t = a = (4 * D * D + 4 * D * D // cfg.n_heads + D * D + 4 * D
                     + 3 * D * int(cfg.slstm_ffn_factor * D))
        else:
            t = a = 0
        per_kind_total[kind] = t
        per_kind_active[kind] = a

    def stack_sum(table):
        reps = cfg.pattern_repeats
        s = reps * sum(table[k] for k in cfg.layer_pattern)
        s += sum(table[cfg.layer_pattern[t]] for t in range(cfg.tail_len))
        return s

    total = stack_sum(per_kind_total)
    active = stack_sum(per_kind_active)
    emb = cfg.vocab_size * D
    total += emb + (0 if cfg.tie_embeddings else emb)
    active += emb + (0 if cfg.tie_embeddings else emb)
    if cfg.is_encoder_decoder:
        enc = cfg.encoder_layers * (attn_self + dense_ffn) + D * D
        total += enc
        active += enc
    if cfg.vit_embed_dim:
        total += cfg.vit_embed_dim * D + D * D
        active += cfg.vit_embed_dim * D + D * D
    return {"total": total, "active": active}


def model_flops(cfg: ModelConfig, shape: InputShape, n_chips: int) -> float:
    """6·N_active·tokens per chip for one training round (fwd+bwd of the
    global batch — the useful-work floor; FedNew's CG passes are overhead by
    this yardstick, which is exactly what useful_flop_ratio exposes).
    Serving steps use 2·N_active·tokens (forward only)."""
    counts = param_counts(cfg)
    n_active = counts["active"]
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens / n_chips
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens / n_chips
    tokens = shape.global_batch  # decode: one token per sequence
    return 2.0 * n_active * tokens / n_chips
