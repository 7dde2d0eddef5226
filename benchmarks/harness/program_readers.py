"""Readers of the per-layer metrics the PROGRAM measures on itself: the
spans and counters of ``flexflow_tpu.obs.metrics.METRICS``, read in the
run's own process.  The registry holds the whole process — build, probe,
warm-up, window and traced tail — not the window alone; each metric's
file says so under ``reads``.

A histogram is named as the program names it (``serve.wait_s``, filled
by the span ``ff.phase/serve.wait``), a counter likewise
(``decode.frames``).  Each reader returns None where the program has no
such span or counter, or no sample of it, as a program older than the
metric has not: the harness then leaves the metric out of the line.
"""

from __future__ import annotations


def _snapshot(ctx) -> dict:
    """``METRICS.snapshot()``, taken once a run however many readers
    ask (a snapshot sorts every reservoir)."""
    if "registry" not in ctx:
        try:
            from flexflow_tpu.obs.metrics import METRICS
        except ImportError:
            ctx["registry"] = {}
        else:
            ctx["registry"] = METRICS.snapshot()
    return ctx["registry"]


def _amount(snapshot: dict, name: str):
    """A counter's value, or the SUM of a histogram's samples; None
    where neither exists or the histogram is empty."""
    if name in snapshot.get("counters", {}):
        return snapshot["counters"][name]
    summary = snapshot.get("histograms", {}).get(name)
    if not summary or not summary.get("count"):
        return None
    return summary["sum"]


def hist(ctx, name: str, stat: str, scale: float = 1.0):
    """``stat`` (``p50``, ``p95``, ``p99``, ``mean``, ``max``, ``sum``,
    ``count``) of one histogram, times ``scale``."""
    summary = _snapshot(ctx).get("histograms", {}).get(name)
    if not summary or not summary.get("count"):
        return None
    return summary[stat] * scale


def total(ctx, names, scale: float = 1.0):
    """Sum of counters' values and histograms' sums, times ``scale``;
    None unless every name has something to read."""
    amounts = [_amount(_snapshot(ctx), n) for n in names]
    if any(a is None for a in amounts):
        return None
    return sum(amounts) * scale


def ratio(ctx, num: str, den: str, scale: float = 1.0):
    """``num`` ÷ ``den`` (counters, or histogram sums), times ``scale``;
    None where either is missing or ``den`` is 0."""
    snapshot = _snapshot(ctx)
    n, d = _amount(snapshot, num), _amount(snapshot, den)
    if n is None or not d:
        return None
    return n / d * scale


def one_minus_ratio(ctx, num: str, den: str, scale: float = 1.0):
    """(1 − ``num`` ÷ ``den``) × ``scale``: the share of ``den`` that
    ``num`` does not cover."""
    r = ratio(ctx, num, den)
    return None if r is None else (1.0 - r) * scale


def mean_outside(ctx, whole: str, part: str, scale: float = 1.0):
    """(sum of histogram ``whole`` − sum of histogram ``part``) ÷ the
    samples of ``whole``, times ``scale``: the mean of a span less the
    child span inside it — a serving step's host work where ``part`` is
    the wait for the device.  None where either has no sample."""
    histograms = _snapshot(ctx).get("histograms", {})
    outer, inner = histograms.get(whole), histograms.get(part)
    if not outer or not inner or not outer.get("count") \
            or not inner.get("count"):
        return None
    return (outer["sum"] - inner["sum"]) / outer["count"] * scale
