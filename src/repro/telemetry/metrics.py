"""The JSONL diagnostics stream: one JSON object per round.

:func:`stream_rows` is the per-round escape hatch: one JSON object per
line, so multi-million-round runs can be tailed without parsing one giant
RunResult. Exact ints (the bit ledgers) stay Python ints on the way out.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, Iterable, List, Mapping


def stream_rows(path: str, rows: Iterable[Mapping[str, Any]]) -> str:
    """Write one JSON object per line (the diagnostics stream). Ints stay
    ints — the encoder refuses anything json can't represent exactly."""
    parent = os.path.dirname(os.path.abspath(path))
    os.makedirs(parent, exist_ok=True)
    with open(path, "w") as f:
        for row in rows:
            f.write(json.dumps(dict(row)) + "\n")
    return path


def read_stream(path: str) -> List[Dict[str, Any]]:
    rows = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                rows.append(json.loads(line))
    return rows
