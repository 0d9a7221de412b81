"""``python -m repro.telemetry`` — summarize / validate telemetry artifacts.

    python -m repro.telemetry summarize <file>
    python -m repro.telemetry validate <file> [--stream f.jsonl] \
        [--expect-domain host] [--expect-domain sim]

``<file>`` is sniffed by content, not extension:

  * a **trace** (``{"traceEvents": [...]}`` — :mod:`repro.telemetry.trace`)
  * a **diagnostics stream** (JSONL, one object per round)
  * a **RunResult** JSON (``repro.api``)
  * a **dry-run cache** (``repro.launch.dryrun`` records — the tables the
    retired ``launch/report.py`` used to render live here now, so there is
    exactly one reporting path)

``validate`` exits nonzero with a named reason on any structural violation;
CI runs traced smoke runs through it so the trace schema cannot rot.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict, List, Optional

from repro.telemetry.trace import HOST_PID, SIM_PID

_DOMAIN_PIDS = {"host": HOST_PID, "sim": SIM_PID}


def _fmt_bytes_gib(b: float) -> str:
    return f"{b / 2**30:.2f}"


def _fmt_s(x: float) -> str:
    if x == 0:
        return "0"
    if x < 1e-3:
        return f"{x*1e6:.1f}µs"
    if x < 1:
        return f"{x*1e3:.1f}ms"
    return f"{x:.2f}s"


def _load(path: str):
    """(kind, payload): sniff one artifact. JSONL streams are detected by
    parsing line-wise; everything else must be one JSON document."""
    with open(path) as f:
        text = f.read()
    try:
        payload = json.loads(text)
    except json.JSONDecodeError:
        rows = []
        for i, line in enumerate(text.splitlines()):
            line = line.strip()
            if not line:
                continue
            try:
                rows.append(json.loads(line))
            except json.JSONDecodeError as e:
                raise SystemExit(
                    f"{path}: neither JSON nor JSONL (line {i + 1}: {e})"
                )
        return "stream", rows
    if isinstance(payload, dict) and "traceEvents" in payload:
        return "trace", payload
    if isinstance(payload, dict) and "metrics" in payload and "spec" in payload:
        return "runresult", payload
    if isinstance(payload, dict) and payload and all(
        isinstance(v, dict) and "arch" in v and "status" in v
        for v in payload.values()
    ):
        return "dryrun", payload
    return "json", payload


# ---------------------------------------------------------------------------
# validate
# ---------------------------------------------------------------------------


def _fail(msg: str) -> None:
    raise SystemExit(f"INVALID: {msg}")


def validate_trace(payload: Dict[str, Any], expect_domains) -> Dict[str, int]:
    events = payload.get("traceEvents")
    if not isinstance(events, list) or not events:
        _fail("traceEvents must be a non-empty list")
    per_pid: Dict[int, int] = {}
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            _fail(f"event {i} is not an object")
        for k in ("name", "ph", "pid"):
            if k not in ev:
                _fail(f"event {i} missing {k!r}")
        if ev["ph"] == "M":
            continue
        if "ts" not in ev:
            _fail(f"event {i} ({ev['name']!r}) missing 'ts'")
        if ev["ts"] < 0:
            _fail(f"event {i} ({ev['name']!r}) has negative ts")
        if ev["ph"] == "X":
            if "dur" not in ev or ev["dur"] < 0:
                _fail(
                    f"event {i} ({ev['name']!r}) is a complete event "
                    f"without a non-negative 'dur'"
                )
        per_pid[ev["pid"]] = per_pid.get(ev["pid"], 0) + 1
    for dom in expect_domains or ():
        pid = _DOMAIN_PIDS[dom]
        if not per_pid.get(pid):
            _fail(
                f"expected {dom!r} clock-domain events (pid {pid}); trace "
                f"has pids {sorted(per_pid)}"
            )
    return per_pid


def validate_stream(rows: List[Dict[str, Any]]) -> None:
    if not rows:
        _fail("stream has no rows")
    last = None
    for i, row in enumerate(rows):
        if not isinstance(row, dict):
            _fail(f"stream row {i} is not an object")
        if "round" not in row:
            _fail(f"stream row {i} missing 'round'")
        r = row["round"]
        if not isinstance(r, int) or isinstance(r, bool):
            _fail(f"stream row {i}: 'round' must be an int, got {r!r}")
        if last is not None and r <= last:
            _fail(
                f"stream row {i}: rounds must be strictly increasing "
                f"({r} after {last})"
            )
        last = r


def cmd_validate(args) -> int:
    kind, payload = _load(args.path)
    if kind == "trace":
        per_pid = validate_trace(payload, args.expect_domain)
        doms = ", ".join(
            f"{name}={per_pid.get(pid, 0)}"
            for name, pid in sorted(_DOMAIN_PIDS.items())
        )
        print(f"OK {args.path}: valid trace ({doms} events)")
    elif kind == "stream":
        validate_stream(payload)
        print(f"OK {args.path}: valid stream ({len(payload)} rows)")
    else:
        _fail(
            f"{args.path} is a {kind} artifact; validate takes a trace or "
            f"a JSONL stream"
        )
    if args.stream:
        skind, srows = _load(args.stream)
        if skind != "stream":
            _fail(f"{args.stream} is not a JSONL stream (sniffed {skind})")
        validate_stream(srows)
        print(f"OK {args.stream}: valid stream ({len(srows)} rows)")
    return 0


# ---------------------------------------------------------------------------
# summarize
# ---------------------------------------------------------------------------


def _summarize_trace(payload: Dict[str, Any]) -> None:
    events = payload.get("traceEvents", [])
    host = [e for e in events if e.get("pid") == HOST_PID and e.get("ph") == "X"]
    simx = [e for e in events if e.get("pid") == SIM_PID and e.get("ph") == "X"]
    print(f"trace: {len(events)} events "
          f"({len(host)} host spans, {len(simx)} simulated spans)")
    by_name: Dict[str, List[float]] = {}
    for e in host:
        by_name.setdefault(e["name"], []).append(e["dur"] / 1e6)
    for name in sorted(by_name, key=lambda n: -sum(by_name[n])):
        durs = by_name[name]
        print(f"  host {name:<16} x{len(durs):<4} total {_fmt_s(sum(durs))} "
              f"max {_fmt_s(max(durs))}")
    if simx:
        t0 = min(e["ts"] for e in simx) / 1e6
        t1 = max(e["ts"] + e["dur"] for e in simx) / 1e6
        tids = {e.get("tid", 0) for e in simx}
        print(f"  simulated timeline [{t0:.3f}s, {t1:.3f}s] over "
              f"{len(tids)} rows")
    roofline = payload.get("otherData", {}).get("roofline")
    if roofline:
        print(f"  roofline: {len(roofline)} profiled dispatch(es)")
        for rec in roofline:
            frac = rec.get("achieved_fraction")
            ach = (f"{rec['achieved_flops_per_s']:.3e} FLOP/s "
                   f"({frac:.2e} of attainable)") if frac is not None \
                else "no roofline share"
            bound = (f"{rec['bound']}-bound" if rec.get("bound")
                     else f"no peaks for {rec.get('device_kind')!r}")
            print(f"    {rec['label']}: {rec['flops']:.3e} flops, "
                  f"{rec['bytes']:.3e} bytes, {bound}, {ach}")


def _summarize_stream(rows: List[Dict[str, Any]]) -> None:
    keys = sorted({k for row in rows for k in row} - {"round"})
    print(f"stream: {len(rows)} rows, fields: {', '.join(keys)}")
    if rows:
        last = rows[-1]
        for k in keys:
            if k in last and isinstance(last[k], (int, float)):
                print(f"  final {k} = {last[k]:.6g}")


def _summarize_runresult(payload: Dict[str, Any]) -> None:
    metrics = payload.get("metrics", {})
    loss = metrics.get("loss", [])
    print(f"runresult: solver={payload.get('solver')} "
          f"rounds={payload.get('rounds')} "
          f"n_clients={payload.get('n_clients')} dim={payload.get('dim')}")
    if loss:
        print(f"  loss {loss[0]:.6g} -> {loss[-1]:.6g}")
    cum = payload.get("cumulative_uplink_bits_total") or []
    if cum:
        print(f"  uplink bits total {cum[-1]}")
    if payload.get("simulated_time_s") is not None:
        print(f"  simulated time {_fmt_s(payload['simulated_time_s'])}")
    diags = payload.get("diagnostics") or {}
    series = {k: v for k, v in diags.items() if isinstance(v, list) and v}
    if series:
        print(f"  diagnostics ({len(series)} series):")
        for k in sorted(series):
            v = series[k]
            if all(isinstance(x, (int, float)) for x in v):
                print(f"    {k}: {v[0]:.6g} -> {v[-1]:.6g}")
    for k, v in sorted(diags.items()):
        if not isinstance(v, list):
            print(f"    {k} = {v}")


def _summarize_dryrun(cache: Dict[str, Any]) -> None:
    """The retired ``launch/report.py`` tables, one reporting path now."""
    print("| arch | shape | status | resident GiB/chip | flops/chip | "
          "dominant | useful ratio |")
    print("|---|---|---|---|---|---|---|")
    for key in sorted(cache):
        rec = cache[key]
        arch, shape = rec.get("arch", "?"), rec.get("shape", "?")
        if rec.get("status") != "ok":
            reason = str(rec.get("reason", rec.get("error", "")))[:70]
            print(f"| {arch} | {shape} | **{str(rec.get('status')).upper()}**"
                  f" — {reason} | | | | |")
            continue
        r = rec.get("roofline", {})
        print(
            f"| {arch} | {shape} | ok | "
            f"{_fmt_bytes_gib(rec.get('resident_bytes_per_chip', 0.0))} | "
            f"{r.get('flops_per_chip', 0.0):.2e} | "
            f"**{r.get('dominant', '?')}** | "
            f"{r.get('useful_flop_ratio', 0.0):.3f} |"
        )


def cmd_summarize(args) -> int:
    kind, payload = _load(args.path)
    if kind == "trace":
        _summarize_trace(payload)
    elif kind == "stream":
        _summarize_stream(payload)
    elif kind == "runresult":
        _summarize_runresult(payload)
    elif kind == "dryrun":
        _summarize_dryrun(payload)
    else:
        _fail(f"{args.path}: unrecognized artifact (plain {kind})")
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.telemetry",
        description="summarize / validate telemetry artifacts "
        "(traces, diagnostics streams, RunResults, dry-run caches)",
    )
    sub = ap.add_subparsers(dest="cmd", required=True)
    ps = sub.add_parser("summarize", help="human-readable artifact summary")
    ps.add_argument("path")
    ps.set_defaults(fn=cmd_summarize)
    pv = sub.add_parser("validate", help="schema-check a trace or stream")
    pv.add_argument("path")
    pv.add_argument("--stream", default=None,
                    help="also validate this JSONL diagnostics stream")
    pv.add_argument("--expect-domain", action="append",
                    choices=sorted(_DOMAIN_PIDS),
                    help="require events in this clock domain (repeatable)")
    pv.set_defaults(fn=cmd_validate)
    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
