"""Pallas TPU kernels for the compute hot-spots this system optimizes
(validated under interpret=True on CPU against each ref.py oracle):

  swa_attention — flash sliding-window attention (gemma/mixtral local layers)
  client_solve  — in-VMEM CG for FedNew's eq. 9 damped SPD solve
  glm_hvp       — matrix-free eq. 9: A^T (w * (A v)) / m + mu v per client
                  in one read of the features (one call per CG iteration)
  stoch_quant   — Q-FedNew stochastic quantizer (eqs. 25-30), 2-D
                  (clients, blocks) grid with in-kernel tail masking
  slstm_scan    — fused sLSTM recurrence (VMEM-resident state; §Perf pair C)

The FedNew hot loops (client_solve, glm_hvp, stoch_quant) are registered with
the backend-aware dispatch layer (``repro.kernels.dispatch``) and reached
by the engine through it — call sites select ``auto``/``pallas``/
``reference`` instead of importing kernel modules or passing ``interpret=``
by hand. Entries are lazy module-path strings so importing this package
stays cheap.
"""

from repro.kernels import dispatch
from repro.kernels.dispatch import (  # noqa: F401  (public re-exports)
    get_impl,
    register_kernel,
    registered_kernels,
    resolve_backend,
)

dispatch.register_kernel(
    "client_solve",
    pallas="repro.kernels.client_solve.ops:client_solve",
    reference="repro.kernels.client_solve.ref:client_solve_ref",
)
dispatch.register_kernel(
    "glm_hvp",
    pallas="repro.kernels.glm_hvp.ops:glm_hvp",
    reference="repro.kernels.glm_hvp.ref:glm_hvp_ref",
)
# the engine's batched Q-FedNew hot loop ...
dispatch.register_kernel(
    "stoch_quant",
    pallas="repro.kernels.stoch_quant.ops:quantize_with_keys",
    reference="repro.core.quantization:quantize_with_keys",
)
# ... and the single-vector form (fednew_hf's shard_map one-client route)
dispatch.register_kernel(
    "stoch_quant.quantize",
    pallas="repro.kernels.stoch_quant.ops:quantize",
    reference="repro.core.quantization:quantize",
)
# LM fine-tuning hot spots: registered so the dispatch layer (and its
# interpret-mode CI sweep) covers every kernel package, not just the two
# FedNew loops — repro.analysis' kernel-pairing rule enforces this.
dispatch.register_kernel(
    "swa_attention",
    pallas="repro.kernels.swa_attention.ops:swa_attention",
    reference="repro.kernels.swa_attention.ref:swa_attention_ref",
)
dispatch.register_kernel(
    "slstm_scan",
    pallas="repro.kernels.slstm_scan.ops:slstm_scan",
    reference="repro.kernels.slstm_scan.ref:slstm_scan_ref",
)
