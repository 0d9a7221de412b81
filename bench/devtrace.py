"""From a profiler trace to the device's busy time, kernel times and gaps.

``record`` runs one job under ``jax.profiler.trace`` and ``reduce`` reads
the ``.xplane.pb`` it wrote with ``jax.profiler.ProfileData``:

- the steady window runs from the end of the job's first ``bench.dispatch``
  host span (the harness's annotation around each scan block) to the end of
  its last: the same window ``round_ms`` is timed over;
- busy time is the union of the op intervals on each TPU plane's
  ``XLA Ops`` line, clipped to the window, averaged over the chips (a scan
  block's ``while`` op covers its body, so idle is time outside programs);
- ops are told apart by the HLO instruction name and opcode in each event:
  the Pallas kernels are custom calls named after their jitted wrappers,
  the collectives are ``all-reduce`` ops (either line);
- the top ops count self time (containers such as ``while`` left out), and
  each idle gap is named by the innermost ``bench.*`` host span it falls in.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import shutil
from typing import Callable, Dict, List, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
SPAN_PREFIX = "bench."

# Device-time categories. The Pallas kernels are the custom calls named
# after their jitted wrappers (the kernels themselves have no name of their
# own yet); the rest of each wrapper (padding, layout copies, the uniforms)
# cannot be told apart in the trace until the program names its scopes.
KERNELS = {"client_solve": "client_solve", "stoch_quant": "quantize_with_keys"}
CONTAINERS = ("while", "conditional", "call")
COLLECTIVES = ("all-reduce", "all-reduce-start", "all-reduce-done")


@dataclasses.dataclass
class Op:
    start: int
    end: int
    name: str  # HLO instruction name, e.g. "client_solve.9"
    opcode: str  # e.g. "custom-call", "fusion", "while"
    sync: bool  # on the "XLA Ops" line (not the async-copy line)


def parse_hlo(text: str) -> Tuple[str, str]:
    """(instruction name, opcode) from a trace event's HLO text
    ``%name = <shape> opcode(operands), ...``."""
    lhs, _, rhs = text.partition(" = ")
    name = lhs.strip().lstrip("%")
    rhs = rhs.lstrip()
    if rhs.startswith("("):  # tuple shape: skip to its closing paren
        depth = 0
        for i, ch in enumerate(rhs):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rhs = rhs[i + 1:]
                break
    else:
        rhs = rhs.partition(" ")[2]
    return name, rhs.lstrip().partition("(")[0].strip()


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    rounds: int
    chips: int
    cat_s: Dict[str, float]  # per chip
    cat_n: Dict[str, float]  # calls per chip
    top_ops: List[Tuple[str, float]]
    gaps: List[Tuple[str, float]]

    def time(self, category: str) -> float:
        return self.cat_s.get(category, 0.0)

    def calls(self, category: str) -> float:
        return self.cat_n.get(category, 0.0)

    def breakdown(self) -> dict:
        return {"device_ops": [[n, s] for n, s in self.top_ops[:10]],
                "idle_gaps": [[n, s] for n, s in self.gaps[:10]]}


def categories(op: Op) -> List[str]:
    """The categories an op's device time counts toward."""
    base = op.name.split(".")[0]
    out = [cat for cat, wrapper in KERNELS.items()
           if op.opcode == "custom-call" and base == wrapper]
    if op.opcode in COLLECTIVES:
        out.append("allreduce")
    return out


def union_length(intervals: List[Tuple[int, int]]) -> int:
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(intervals, lo: int, hi: int) -> List[Tuple[int, int]]:
    gaps, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            gaps.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        gaps.append((t, hi))
    return [(s, e) for s, e in gaps if e > s]


def load(path: str):
    """(host spans [(name, start, end)], device ops {plane: [Op]}) from an
    ``.xplane.pb``; device and host events share the trace's clock."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    spans, devices = [], {}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = []
            for line in plane.lines:
                if line.name not in (OPS_LINE, ASYNC_LINE):
                    continue
                for ev in line.events:
                    name, opcode = parse_hlo(ev.name)
                    ops.append(Op(ev.start_ns, ev.start_ns + ev.duration_ns,
                                  name, opcode, line.name == OPS_LINE))
            if ops:
                devices[plane.name] = ops
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    return spans, devices


def reduce(spans, devices, rounds_per_block: int) -> Summary:
    """Busy time, category times, top ops and named gaps over the steady
    window, per chip."""
    blocks = sorted((s, e) for n, s, e in spans if n == "bench.dispatch")
    if len(blocks) < 2 or not devices:
        raise ValueError("trace holds fewer than two block spans or no TPU ops")
    lo, hi = blocks[0][1], blocks[-1][1]
    chips = len(devices)
    busy, cat_s, cat_n, op_s, gaps = 0, {}, {}, {}, []
    for ops in devices.values():
        inside = [(max(o.start, lo), min(o.end, hi), o) for o in ops
                  if o.end > lo and o.start < hi]
        sync = [(s, e) for s, e, o in inside if o.sync]
        busy += union_length(sync)
        gaps += idle_gaps(sync, lo, hi)
        for s, e, o in inside:
            if o.sync and o.opcode not in CONTAINERS:
                op_s[o.name] = op_s.get(o.name, 0) + (e - s)
            for c in categories(o):
                cat_s[c] = cat_s.get(c, 0) + (e - s)
                cat_n[c] = cat_n.get(c, 0) + 1
    named = {}
    for s, e in gaps:
        mid = (s + e) // 2
        owner = [n for n, ss, se in spans if ss <= mid < se]
        key = owner[-1] if owner else "between bench spans"
        named[key] = named.get(key, 0) + (e - s)
    ns = 1e-9
    by_time = lambda d: sorted(((k, v * ns / chips) for k, v in d.items()),
                               key=lambda kv: -kv[1])
    return Summary(
        window_s=(hi - lo) * ns,
        busy_s=busy * ns / chips,
        rounds=(len(blocks) - 1) * rounds_per_block,
        chips=chips,
        cat_s={k: v * ns / chips for k, v in cat_s.items()},
        cat_n={k: v / chips for k, v in cat_n.items()},
        top_ops=by_time(op_s),
        gaps=by_time(named),
    )


def record(job: Callable[[], object], trace_dir: str, *, rounds_per_block: int,
           chips: int) -> Summary:
    """Run ``job`` under the profiler and reduce the trace it leaves."""
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    with jax.profiler.trace(trace_dir):
        job()
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    spans, devices = load(files[0])
    summary = reduce(spans, devices, rounds_per_block)
    if summary.chips != chips:
        raise RuntimeError(f"trace holds {summary.chips} TPU planes, expected {chips}")
    return summary
