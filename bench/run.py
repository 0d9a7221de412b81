"""FedNew on-chip benchmark: one run of one cell.

    python3 bench/run.py --workload w8a-dense.full --seed 7 --seconds 10 --trace 0

A cell is ``<config>.<traffic>`` as listed in ``BENCHMARK.json``. The run
builds the cell's data from ``--seed`` on the device, compiles and warms up
its scan blocks (``setup_s``), times one training job from a fresh round-0
state for about ``--seconds`` seconds, and compares what that job produced
with the plain float32 reference. ``--trace 1`` reports the per-layer
metrics instead of the end-to-end ones, reading the device from a profiler
trace of a short second job.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each compared number beside its
limit); the same numbers close standard error. Without a TPU, or with
fewer chips than the cell needs, it prints no result and exits 2.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(os.path.dirname(HERE), "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # libtpu's logs, like every other file a run writes, stay in the checkout.
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(HERE, ".cache", "tpu_logs"))
    os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)
    import harness

    try:
        result, checks = harness.run_cell(
            args.workload, args.seed, args.seconds, bool(args.trace),
            t_process=T_PROCESS)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
