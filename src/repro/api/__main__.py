"""CLI for the declarative experiment API.

    python -m repro.api SPEC.json [--out RESULT.json]
    python -m repro.api --template          # print a default spec to edit

Loads the spec, turns on the persistent compilation cache
(``repro.launch.compile_cache``), auto-enables ``jax_enable_x64`` when the
partition asks for float64, runs it through ``repro.api.run``, prints a
short summary, and writes the RunResult JSON to ``--out`` (or the spec's
``telemetry.save_path``). Exercised by ``scripts/ci.sh`` on
``examples/specs/quickstart.json`` so the CLI and the JSON schema cannot
silently rot.
"""

from __future__ import annotations

import argparse
import json
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.api",
        description="Run a declarative FedNew experiment spec.",
    )
    ap.add_argument("spec", nargs="?", help="path to an ExperimentSpec JSON")
    ap.add_argument("--out", help="write the RunResult JSON here "
                                  "(overrides telemetry.save_path)")
    ap.add_argument("--template", action="store_true",
                    help="print a default spec JSON and exit")
    args = ap.parse_args(argv)

    if args.template:
        from repro.api.specs import ExperimentSpec

        print(ExperimentSpec(name="template").to_json())
        return 0
    if not args.spec:
        ap.error("a spec path is required (or --template)")

    with open(args.spec) as f:
        raw = json.load(f)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    # float64 partitions need x64 — flip it before any jax arrays exist.
    if (raw.get("partition") or {}).get("dtype") == "float64":
        import jax

        jax.config.update("jax_enable_x64", True)

    from repro.api import ExperimentSpec, run

    spec = ExperimentSpec.from_dict(raw)
    if args.out and spec.telemetry.save_path:
        # --out overrides telemetry.save_path: suppress the runner's own
        # save so exactly one result file is written.
        import dataclasses

        spec = spec.replace(
            telemetry=dataclasses.replace(spec.telemetry, save_path=None)
        )
    result = run(spec)

    label = spec.name or args.spec
    print(f"spec        {label}")
    print(f"solver      {result.solver}")
    print(f"dataset     n={result.n_clients} clients, d={result.dim}, "
          f"{result.rounds} rounds")
    print(f"sampled     {min(result.sampled_clients)}..."
          f"{max(result.sampled_clients)} clients/round")
    print(f"final loss  {result.final_loss:.6e}"
          + (f"  (gap {result.metrics['gap'][-1]:.3e})"
             if "gap" in result.metrics else ""))
    print(f"uplink      {result.cumulative_uplink_bits_per_client[-1] / 8e6:.3f} "
          "MB/client cumulative (exact ledger)")
    print(f"wall clock  {result.wall_clock_s:.2f}s "
          f"(compile {result.compile_s:.2f}s, "
          f"steady {result.steady_wall_clock_s:.2f}s)")

    out = args.out or spec.telemetry.save_path
    if out:
        path = result.save_json(out)
        print(f"result      {path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
