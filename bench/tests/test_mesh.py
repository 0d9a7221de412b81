"""The harness's client-mesh path on four virtual CPU devices, in a process
of its own (the device count is fixed when JAX starts): a one-chip cell
laid out as if it asked for four chips runs sharded and is correct, and
every fault, leaving out the exchange between chips among them, is caught."""

import json
import os
import subprocess
import sys

import registry

CELL = "rcv1-matfree.full"

SCRIPT = """
import json, sys
sys.path[:0] = [{bench!r}, {tests!r}, {src!r}]
import faults, registry, tiny
cell = registry.cell
registry.cell = lambda name, root=registry.ROOT: dict(cell(name, root), chips=4)
out = {{}}
res, _ = tiny.run({cell!r})
out["sound"] = res["correct"]
for f in faults.FAULTS:
    with faults.planted(f):
        out[f] = tiny.run({cell!r})[0]["correct"]
print(json.dumps(out))
"""


def test_mesh_path_sound_and_faults():
    tests = os.path.dirname(os.path.abspath(__file__))
    code = SCRIPT.format(bench=registry.BENCH_DIR, tests=tests,
                         src=os.path.join(registry.ROOT, "src"), cell=CELL)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out.pop("sound") is True, out
    assert not any(out.values()), out
