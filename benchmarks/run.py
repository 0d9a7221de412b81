"""Benchmark harness entry point: ``PYTHONPATH=src python -m benchmarks.run``.

One module per paper table/figure (+ kernels + privacy). Each emits
``name,us_per_call,derived`` CSV lines and writes a JSON artifact under
benchmarks/out/. ``--only <name>`` runs a single suite.

Training-curve suites (fig1/fig2/bits_ablation) are declarative: each
method is a ``repro.api.ExperimentSpec`` run through ``repro.api.run``
(scan-compiled engine underneath), so the per-round us numbers reflect the
compiled driver rather than host dispatch overhead and a new scenario is a
spec edit, not a new loop.
"""

from __future__ import annotations

import argparse
import sys
import time

import jax

from repro.launch.compile_cache import enable_compile_cache

SUITES = ("bits_table", "paper_fig1", "paper_fig2", "bits_ablation", "privacy_demo", "kernel_bench", "matfree_scaling", "comm_tradeoff", "solver_frontier", "lm_workload", "async_frontier", "roofline_bench")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", choices=SUITES, default=None)
    args = ap.parse_args()

    enable_compile_cache()
    # the paper-repro suites run in f64 like the paper's CPU experiments
    jax.config.update("jax_enable_x64", True)

    suites = [args.only] if args.only else list(SUITES)
    failures = []
    for name in suites:
        print(f"# === {name} ===", flush=True)
        t0 = time.time()
        mod = __import__(f"benchmarks.{name}", fromlist=["main"])
        try:
            mod.main()
        except Exception as e:  # keep the harness going; report at the end
            failures.append((name, repr(e)))
            print(f"{name}/ERROR,0.0,{type(e).__name__}")
        print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)

    if failures:
        for name, err in failures:
            print(f"FAILED suite {name}: {err}", file=sys.stderr)
        raise SystemExit(1)
    print("# all suites passed")


if __name__ == "__main__":
    main()
