"""Schema assertion for the benchmarks/roofline_bench.py artifact.

CI smoke leg: ``python scripts/check_roofline_artifact.py \
benchmarks/out/roofline_bench.json`` after running the suite with
``TELEMETRY_SMOKE=1``.

Checks the record schema shared by the engine-profiled blocks and the
standalone kernel analyses — every record carrying consistent HLO counts
and timings, and a roofline (peaks, attainable ceiling, bound, share)
exactly when the run's ``device_kind`` has published peaks. A CPU run has
none, so its records carry no roofline share. Not the numbers themselves.
Shared shape primitives live in scripts/_artifact_check.py.
"""

from __future__ import annotations

import sys

try:
    from scripts._artifact_check import (
        fail, require_keys, require_positive, run_cli,
    )
except ImportError:  # invoked as `python scripts/check_roofline_artifact.py`
    from _artifact_check import (
        fail, require_keys, require_positive, run_cli,
    )

_RECORD_KEYS = {
    "label", "device_kind", "flops", "bytes", "collective_bytes",
    "arithmetic_intensity", "attainable_flops_per_s", "bound",
    "unknown_loops", "seconds_per_call", "achieved_flops_per_s",
    "achieved_bytes_per_s", "achieved_fraction",
}


def _check_record(rec: dict, section: str, has_peaks: bool) -> None:
    label = rec.get("label", "<unlabelled>")
    where = f"{section}/{label}"
    require_keys(rec, _RECORD_KEYS, label=where, exact=False)
    if rec["flops"] < 0 or rec["bytes"] < 0:
        fail(f"{where}: negative flops/bytes", rec["flops"], rec["bytes"])
    if has_peaks:
        require_positive(rec["attainable_flops_per_s"],
                         f"{where} attainable_flops_per_s")
        if rec["bound"] not in ("compute", "memory"):
            fail(f"{where}: bound must be compute|memory", rec["bound"])
    elif (rec["attainable_flops_per_s"], rec["bound"],
          rec["achieved_fraction"]) != (None, None, None):
        fail(f"{where}: a roofline for device {rec['device_kind']!r}, "
             "which has no published peaks")
    if rec["seconds_per_call"] is not None:
        require_positive(rec["seconds_per_call"],
                         f"{where} seconds_per_call")
        require_positive(rec["achieved_flops_per_s"],
                         f"{where} achieved_flops_per_s")
        if has_peaks:
            require_positive(rec["achieved_fraction"],
                             f"{where} achieved_fraction")
        # achieved = flops / seconds must be self-consistent with the pair
        derived = rec["flops"] / rec["seconds_per_call"]
        if rec["flops"] > 0 and abs(
            derived - rec["achieved_flops_per_s"]
        ) > 1e-6 * max(derived, 1.0):
            fail(f"{where}: achieved_flops_per_s inconsistent",
                 rec["achieved_flops_per_s"], derived)


def check_payload(payload: dict) -> None:
    """Raise AssertionError if the artifact doesn't match the schema."""
    require_keys(payload, {"config", "engine", "kernels"})
    cfg = payload["config"]
    require_keys(
        cfg,
        ("smoke", "rounds", "backend", "device_kind",
         "resolved_pallas_backend", "peak_flops_bf16", "hbm_bw"),
        label="config", exact=False,
    )
    has_peaks = cfg["peak_flops_bf16"] is not None
    if has_peaks:
        require_positive(cfg["peak_flops_bf16"], "config peak_flops_bf16")
        require_positive(cfg["hbm_bw"], "config hbm_bw")
    elif cfg["hbm_bw"] is not None:
        fail("config: hbm_bw without peak_flops_bf16", cfg["hbm_bw"])
    for section in ("engine", "kernels"):
        records = payload[section]
        if not records:
            fail(f"no {section} records")
        for rec in records:
            _check_record(rec, section, has_peaks)


def main(path: str) -> None:
    run_cli(
        check_payload, path,
        lambda p: (
            f"OK {path}: {len(p['engine'])} engine + "
            f"{len(p['kernels'])} kernel roofline records "
            f"(device_kind={p['config']['device_kind']})"
        ),
    )


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else
         "benchmarks/out/roofline_bench.json")
