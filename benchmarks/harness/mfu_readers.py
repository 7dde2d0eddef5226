"""The whole window's share of the chip's peak: FLOPs the ALGORITHM
needed for the tokens the window trained or served — priced by the
configuration's ``work`` module, never by the program — over the
window's seconds on the host clock, the chips and the bf16 peak.  A
kernel's roofline share goes silent when a later PR takes the kernel off
the path; this share still bounds what that PR can claim.

The drivers put ``window_flops`` and ``window_s`` among the run's facts;
one reader serves ``train.mfu`` and ``serve.mfu``.
"""

from __future__ import annotations

from benchmarks.harness.peaks import peaks_for


def share_of_peak(flops: float, seconds: float, chips: int,
                  peak_flops_per_s: float) -> float:
    return flops / seconds / (chips * peak_flops_per_s)


def window_mfu(ctx):
    """Per cent of ``chips`` x the bf16 peak; None where the driver
    counted no work or no time."""
    facts = ctx["facts"]
    flops, seconds = facts.get("window_flops"), facts.get("window_s")
    if not flops or not seconds:
        return None
    peak = peaks_for(ctx["device_kind"])["flops_bf16_per_s"]
    return share_of_peak(flops, seconds, ctx["cell"].chips, peak) * 100.0
