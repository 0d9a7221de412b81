"""Readings that the correctness limits of a cell are set from, on the chip.

    python3 bench/calibrate.py --workload w8a-dense.full --seeds 11,12,13 \
        --rounds 96 --controls 3 --out calib.jsonl [--data-key 5]

For every seed it runs the program's job through the timed path (the
engine, in the cell's blocks) for ``--rounds`` rounds and compares its first
block with the float32 reference, as a run does. On the first ``--controls``
seeds it also reads the control, the reference in bfloat16 put in the
program's place, and each fault of ``faults.py`` that the cell can have,
planted in the program. One JSON line per reading; nothing here decides
``correct``. It also reports, per seed, the rounds each candidate gap target
takes and the seconds f(x*) and the reference took. ``--data-key`` draws
another dataset than the configuration's (every seed of a run lays out the
one dataset in its own order), so that the limits rest on more than one.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(os.path.dirname(HERE), "src"))

TARGETS = (0.5, 0.4, 0.35, 0.3, 0.25, 0.2, 0.15, 1e-1, 3e-2, 1e-2, 3e-3, 1e-3, 3e-4, 1e-4, 3e-5, 1e-5, 3e-6, 1e-6)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rounds", type=int, required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--out", required=True)
    ap.add_argument("--data-key", type=int, default=None)
    args = ap.parse_args(argv)

    import jax.numpy as jnp
    import numpy as np

    import faults
    import harness

    cell = harness.load_cell(args.workload)
    if args.data_key is not None:
        cell.config["generator"]["data_key"] = args.data_key
    data_key = cell.config["generator"]["data_key"]
    devs = harness.devices_for(cell.chips)
    harness.enable_compile_cache()
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    out = open(args.out, "a")

    def emit(**kw):
        line = json.dumps({"cell": cell.name, "data_key": data_key, **kw})
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    fault_names = [f for f in faults.FAULTS if f != "no_exchange" or cell.chips > 1]
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        p = harness.prepare(cell, seed, devs)
        A, b = p.data.features, p.data.labels
        job = lambda: harness.run_job(cell, p.obj, p.solver, p.part, p.data,
                                      p.run_key, args.rounds, p.mesh)
        prog = job()
        t0 = time.perf_counter()
        fs = harness.f_star(cell, A, b)
        fstar = fs["f_star"]
        t_fstar = time.perf_counter() - t0
        t0 = time.perf_counter()
        ref = harness.reference_rounds(cell, A, b, p.run_key, jnp.float32)
        t_ref = time.perf_counter() - t0
        gap = (prog.loss - fstar) / (harness.LN2 - fstar)
        hits = {f"{t:g}": (int(np.nonzero(gap <= t)[0][0]) + 1 if np.any(gap <= t) else None)
                for t in TARGETS}
        emit(seed=seed, kind="program",
             numbers=harness.compare(cell, prog.outputs(), ref),
             f_star=fstar, fstar_grad_norm=fs["grad_norm"], fstar_s=t_fstar, reference_s=t_ref,
             rounds_to_target=hits, final_gap=float(gap[-1]),
             gap=[float(g) for g in gap],
             block_end=prog.block_end[:8])
        if i >= args.controls:
            continue
        ctl = harness.reference_rounds(cell, A, b, p.run_key, jnp.bfloat16)
        emit(seed=seed, kind="control_bf16", numbers=harness.compare(cell, ctl, ref))
        for f in fault_names:
            with faults.planted(f):
                bad = job()
            emit(seed=seed, kind=f"fault_{f}",
                 numbers=harness.compare(cell, bad.outputs(), ref))
        del p, A, b
    out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
