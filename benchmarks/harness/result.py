"""The last line of a run: exactly the contract's object."""

from __future__ import annotations

import json
from typing import Dict, Optional


def device_facts(trace: Optional[dict] = None) -> dict:
    """The device as jax reports it; ``memory_peak_bytes`` is the peak on
    the fullest chip.  A traced run adds the profiler's busy seconds
    (averaged over the chips used) and the traced window's length."""
    import jax

    devices = jax.devices()
    out = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in devices),
    }
    if trace is not None:
        out["busy_s"] = trace["busy_s"]
        out["window_s"] = trace["window_s"]
    return out


def result_line(*, correct: bool, attempted: int, failed: int,
                values: Dict[str, float], units: Dict[str, str],
                device: dict, breakdown: Optional[dict] = None,
                compared: Optional[dict] = None) -> str:
    """``values`` maps metric name -> number as measured (all digits);
    a metric whose reader found nothing to read is simply absent.
    ``compared`` — each number ``correct`` rests on beside its limit —
    comes last, so the end of the line shows it."""
    obj = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {name: {"value": float(v), "unit": units[name]}
                    for name, v in values.items()},
        "device": device,
    }
    if breakdown is not None:
        obj["breakdown"] = breakdown
    if compared is not None:
        obj["compared"] = compared
    return json.dumps(obj)


def compared_lines(compared: dict) -> str:
    """The compared numbers as the last lines of standard error."""
    return "\n".join(f"[compared] {name} = {c['value']!r} (limit "
                     f"{c['limit']!r})" for name, c in compared.items())
