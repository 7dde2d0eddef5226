"""``benchmarks/reference/trinity.py`` at the numbers of the CPU tests'
preset (``tiny-trinity-serve.json``): window 32, top 2 — what the
parameter shapes cannot tell and the harness, which calls
``forward(params, ids)``, cannot hand over."""

from __future__ import annotations

from benchmarks.reference import trinity

SPEC = trinity.Spec(window=32, top_k=2)


def forward(params, ids):
    return trinity.forward(params, ids, SPEC)
