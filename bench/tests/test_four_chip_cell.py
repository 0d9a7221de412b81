"""The four-chip cell on four virtual CPU devices, at tiny size, in a
process of its own (the device count is fixed when JAX starts): the
harness lays it out on the client mesh, the sound program is correct, and
every fault, leaving out the exchange between chips among them, is
caught."""

import json
import os
import subprocess
import sys

import registry

CELLS = [w["name"] for w in registry.benchmark()["workloads"] if w["chips"] == 4]

SCRIPT = """
import json, sys
sys.path[:0] = [{bench!r}, {tests!r}, {src!r}]
import faults, tiny
out = {{}}
for cell in {cells!r}:
    res, checks = tiny.run(cell)
    out[cell] = {{"sound": res["correct"], "checks": checks,
                 "devices": res["device"]["count"]}}
    for f in faults.FAULTS:
        with faults.planted(f):
            out[cell][f] = tiny.run(cell)[0]["correct"]
print(json.dumps(out))
"""


def test_four_chip_cells_sound_and_faults():
    assert CELLS
    tests = os.path.dirname(os.path.abspath(__file__))
    code = SCRIPT.format(bench=registry.BENCH_DIR, tests=tests,
                         src=os.path.join(registry.ROOT, "src"), cells=CELLS)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    for cell, res in out.items():
        assert res.pop("devices") == 4, cell
        checks = res.pop("checks")
        assert res.pop("sound") is True, (cell, checks)
        assert not any(res.values()), (cell, res)
