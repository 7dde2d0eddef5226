"""From a profiler trace to numbers.  Kept with the benchmark so that
every PR computes the same number in the same way.

A trace is read once (``load``) into plain lists, and every figure is
arithmetic on those lists, so the arithmetic is checked on a hand-built
trace (tests/benchmarks) without a chip:

* device ops: the events of each device plane's "XLA Ops" line — one
  event per executed HLO instruction.  The profiler names an event by the
  instruction's whole text (``%fusion.7 = f32[...] fusion(...), kind=...``);
  ``load`` keeps the instruction's name (``fusion.7``) and remembers
  which names are Mosaic (Pallas) kernels.  A kernel whose
  ``pallas_call`` was given a name carries it (``ragged_paged_attention.24``);
  one that was not is named after its jax transform (``jvp__.1``);
* device modules: the "XLA Modules" line — one event per executed jitted
  program, named ``jit_<function>(<fingerprint>)``;
* host spans: events the benchmark's own ``jax.profiler.TraceAnnotation``
  wrote (names starting ``bench.``), on the same clock.

Busy time is the UNION of op intervals on a device (ops can overlap);
idle share is 1 - busy / traced span, averaged over the devices used.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

Event = Tuple[str, float, float]  # name, start_s, duration_s

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPAN_PREFIX = "bench."


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def instruction_name(text: str) -> str:
    """``%fusion.7 = f32[8]{0} fusion(...)`` -> ``fusion.7``."""
    return text.split(" = ", 1)[0].lstrip("%")


def op_family(text: str) -> str:
    """What a reader of a breakdown wants to see an op filed under: its
    name without XLA's ``.N`` suffix and its (first) output's type and
    dims — ``%copy.351 = f32[512,32,16,64]{3,2,1,0:T(8,128)} copy(...)``
    -> ``copy f32[512,32,16,64]`` — so that the 96 pool copies of a frame
    are one line, not ninety-six."""
    name, _, rest = text.partition(" = ")
    stem = re.sub(r"\.\d+$", "", name.lstrip("%"))
    shape = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])", rest)
    return f"{stem} {shape.group(1)}" if shape else stem


def is_mosaic_call(text: str) -> bool:
    """A Pallas TPU kernel: a custom call to ``tpu_custom_call``."""
    return " custom-call(" in text and "tpu_custom_call" in text


def load(profile) -> dict:
    """``jax.profiler.ProfileData`` -> {"devices": {plane: {"ops": [...],
    "modules": [...]}}, "mosaic_ops": {names}, "families": {name: family},
    "host_spans": [...]}; times in seconds on the trace's own clock."""
    devices: Dict[str, dict] = {}
    host_spans: List[Event] = []
    mosaic_ops = set()
    families: Dict[str, str] = {}
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            lanes = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key is None:
                    continue
                for ev in line.events:
                    name = instruction_name(ev.name)
                    if key == "ops" and name not in families:
                        families[name] = op_family(ev.name)
                        if is_mosaic_call(ev.name):
                            mosaic_ops.add(name)
                    lanes[key].append(
                        (name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9))
            if lanes["ops"]:
                devices[plane.name] = lanes
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_spans.extend(
                    (ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                    for ev in line.events
                    if ev.name.startswith(HOST_SPAN_PREFIX))
    return {"devices": devices, "mosaic_ops": mosaic_ops,
            "families": families,
            "host_spans": sorted(host_spans, key=lambda e: e[1])}


def load_file(path: str) -> dict:
    from jax.profiler import ProfileData

    return load(ProfileData.from_file(path))


# ---- arithmetic on event lists ------------------------------------------

def merged_intervals(events: Iterable[Event]) -> List[Tuple[float, float]]:
    """Union of [start, start + duration) as disjoint sorted intervals."""
    out: List[List[float]] = []
    for start, end in sorted((s, s + d) for _, s, d in events):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(a, b) for a, b in out]


def busy_seconds(events: Iterable[Event]) -> float:
    return sum(b - a for a, b in merged_intervals(events))


def span(events: Sequence[Event]) -> Tuple[float, float]:
    """First start and last end over ``events``."""
    return (min(s for _, s, _ in events),
            max(s + d for _, s, d in events))


def window(trace: dict) -> Tuple[float, float]:
    """The traced span all devices share: from the first op anywhere to
    the last op anywhere."""
    spans = [span(d["ops"]) for d in trace["devices"].values()]
    return min(a for a, _ in spans), max(b for _, b in spans)


def device_busy(trace: dict) -> dict:
    """busy_s (mean over the devices that ran anything), window_s and
    idle share of the traced span."""
    t0, t1 = window(trace)
    busy = [busy_seconds(d["ops"]) for d in trace["devices"].values()]
    busy_s = sum(busy) / len(busy)
    return {"busy_s": busy_s, "window_s": t1 - t0,
            "idle_share": 1.0 - busy_s / (t1 - t0)}


def op_seconds(trace: dict, match) -> float:
    """Device seconds (mean over devices) of the ops ``match(name)``
    accepts — a kernel's time is the sum of its events' durations."""
    per_dev = [sum(d for n, _, d in dev["ops"] if match(n))
               for dev in trace["devices"].values()]
    return sum(per_dev) / len(per_dev)


def seconds_by_name(trace: dict) -> Dict[str, float]:
    """Device seconds of every instruction name, summed over its events,
    mean over devices: what a join with the compiled program's text
    (``device.scopes_of``) starts from."""
    total: Dict[str, float] = {}
    for dev in trace["devices"].values():
        for name, _, dur in dev["ops"]:
            total[name] = total.get(name, 0.0) + dur
    k = len(trace["devices"])
    return {name: sec / k for name, sec in total.items()}


def ops_inside_modules(trace: dict, match) -> float:
    """Device BUSY seconds (mean over devices) of ops that start inside a
    module event ``match(name)`` accepts: the device time of one jitted
    program."""
    per_dev = []
    for dev in trace["devices"].values():
        spans = merged_intervals(m for m in dev["modules"] if match(m[0]))
        starts = [a for a, _ in spans]
        inside = []
        for op in dev["ops"]:
            k = bisect.bisect_right(starts, op[1]) - 1
            if k >= 0 and op[1] < spans[k][1]:
                inside.append(op)
        per_dev.append(busy_seconds(inside))
    return sum(per_dev) / len(per_dev)


def top_ops(trace: dict, n: int = 10) -> List[list]:
    """The ``n`` op families (``op_family``) with most device time,
    summed over events, mean over devices."""
    total: Dict[str, float] = {}
    for dev in trace["devices"].values():
        for name, _, dur in dev["ops"]:
            family = trace["families"][name]
            total[family] = total.get(family, 0.0) + dur
    k = len(trace["devices"])
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, sec / k] for name, sec in ranked]


def idle_gaps(trace: dict, n: int = 10) -> List[list]:
    """The idle time of the first device by what the host was doing:
    every gap between busy intervals is charged to the benchmark's host
    span that covers most of it (``"(none)"`` where no span does), and
    the ``n`` labels with most idle time are returned."""
    dev = next(iter(trace["devices"].values()))
    busy = merged_intervals(dev["ops"])
    spans = trace["host_spans"]  # sorted by start
    starts = [s for _, s, _ in spans]
    longest = max((d for _, _, d in spans), default=0.0)
    by_label: Dict[str, float] = {}
    for (_, a), (b, _) in zip(busy, busy[1:]):
        best, best_cover = "(none)", 0.0
        k = bisect.bisect_left(starts, b) - 1
        while k >= 0 and starts[k] > a - longest:  # spans that can reach a
            name, s, d = spans[k]
            cover = min(b, s + d) - max(a, s)
            if cover > best_cover:
                best, best_cover = name, cover
            k -= 1
        by_label[best] = by_label.get(best, 0.0) + (b - a)
    ranked = sorted(by_label.items(), key=lambda kv: -kv[1])[:n]
    return [[name, sec] for name, sec in ranked]


def breakdown(trace: dict) -> dict:
    return {"device_ops": top_ops(trace), "idle_gaps": idle_gaps(trace)}

