"""What several per-layer readers of a traced serve run need of the decode
program's device time: which traced program it is, and its seconds under
one layer scope a step."""

from __future__ import annotations

from typing import Dict, Optional

from . import program_trace


def step_seconds(rec: Dict, scope: str) -> Optional[float]:
    """Device seconds one decode step spends under `scope`: the decode
    program's seconds under it in the trace (of the programs with `decode`
    in their name, the one with most device time) over the program's count
    of runs; None where the trace, the scopes or such seconds are missing."""
    scopes = program_trace.device_scopes(rec)
    trace = rec.get("trace")
    if rec.get("kind") != "serve" or not scopes or not trace:
        return None
    names = [n for n in scopes["programs"]
             if "decode" in n and n in trace.get("modules", {})]
    if not names:
        return None
    name = max(names, key=lambda n: scopes["programs"][n]["total_s"])
    seconds = scopes["programs"][name]["by_scope"].get(scope, 0.0) \
        / trace["modules"][name]["count"]
    return seconds if seconds > 0.0 else None
