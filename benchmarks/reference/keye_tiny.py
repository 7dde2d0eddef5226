"""``benchmarks/reference/keye.py`` at the numbers of the CPU tests'
preset (``tiny-keye-serve.json``): top 4 of the experts, top 16 of the
cached positions, rotary on the leading 8 of an index head of 16 — what
the parameter shapes cannot tell and the harness, which calls
``forward(params, ids)``, cannot hand over."""

from __future__ import annotations

from benchmarks.reference import keye

SPEC = keye.Spec(top_k=4, index_topk=16, index_rope_dim=8)


def forward(params, ids):
    return keye.forward(params, ids, SPEC)
