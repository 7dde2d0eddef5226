"""N-replica serving fleet behind a searched SLO-aware router.

``search/fleet.py`` decides the fleet shape — how many replica blocks,
which strategy each, and which per-SLO-class routing fractions — in
the per-class p99 currency.  This module EXECUTES that decision: N
``ContinuousBatchingExecutor`` replicas behind a router whose dispatch
follows the searched fractions deterministically.

Routing is deficit-style proportional assignment: per (class, replica)
the router tracks how many requests it has sent, and each arrival goes
to the replica minimizing ``(count + 1) / fraction`` over the replicas
its class routes to — the discrete sequence whose running shares
converge to the searched fractions from the very first requests (a
weighted round-robin, not a sampler).  Exact ties break through a
seeded ``random.Random`` so a trace replayed under the same seed maps
every request to the same replica, bit-reproducibly (the routing
determinism test).

Admission stays the replicas' own: each ``ContinuousBatchingExecutor``
keeps its priority lanes, deadline expiry and preemption
(runtime/decode.py) — the router decides WHERE a request queues, the
replica decides WHEN it runs.

Wall-clock semantics: replicas are independent once routed, so
``run()`` drains each replica to completion separately — every
replica's measured spans are self-consistent on its own clock, and
cross-replica concurrency (real fleets run replicas on disjoint
devices) is represented by NOT serializing one replica's frames into
another's latencies.  ``step()`` advances every live replica one frame
for interleaved/elastic operation under the controller.
"""

from __future__ import annotations

import random
from typing import Dict, List, Optional, Sequence, Tuple

from flexflow_tpu.obs.events import BUS
from flexflow_tpu.obs.tracing import TRACER
from flexflow_tpu.runtime.decode import (
    ContinuousBatchingExecutor,
    DecodeRequest,
    SLOClass,
)


class FleetExecutor:
    """Route requests over N decode replicas per searched per-class
    fractions; roll per-replica request records up into fleet-level
    per-class percentiles."""

    def __init__(self, replicas: Sequence[ContinuousBatchingExecutor],
                 routing: Dict[str, Sequence[float]], *,
                 slo_classes: Optional[Sequence[SLOClass]] = None,
                 seed: int = 0):
        if not replicas:
            raise ValueError("a fleet needs at least one replica")
        self.replicas: List[ContinuousBatchingExecutor] = list(replicas)
        for i, ex in enumerate(self.replicas):
            if ex.replica_label is None:
                ex.replica_label = str(i)
        k = len(self.replicas)
        self.routing: Dict[str, Tuple[float, ...]] = {}
        for name, fr in routing.items():
            fr = tuple(float(v) for v in fr)
            if len(fr) != k:
                raise ValueError(
                    f"routing row {name!r} has {len(fr)} fractions for "
                    f"{k} replicas")
            tot = sum(fr)
            if tot <= 0:
                raise ValueError(
                    f"routing row {name!r} routes nowhere: {fr}")
            self.routing[name] = tuple(v / tot for v in fr)
        self.slo_classes: Dict[str, SLOClass] = {
            c.name: c for c in (slo_classes or ())}
        self.seed = int(seed)
        self._rng = random.Random(self.seed)
        # deficit counters: class -> per-replica dispatched counts
        self._sent: Dict[str, List[int]] = {}
        self.assignments: Dict[str, int] = {}  # rid -> replica index

    # ------------------------------------------------------------------
    def _fractions(self, slo: str) -> Tuple[float, ...]:
        fr = self.routing.get(slo)
        if fr is None:
            fr = self.routing.get("standard")
        if fr is None:
            k = len(self.replicas)
            fr = tuple(1.0 / k for _ in range(k))
        return fr

    def route(self, req: DecodeRequest) -> int:
        """The replica this request dispatches to (deficit-minimizing
        over its class's searched fractions, seeded tie-break)."""
        slo = req.slo or "standard"
        fr = self._fractions(slo)
        sent = self._sent.setdefault(slo, [0] * len(self.replicas))
        best = None
        ties: List[int] = []
        for r, f in enumerate(fr):
            if f <= 0.0:
                continue
            score = (sent[r] + 1) / f
            if best is None or score < best:
                best, ties = score, [r]
            elif score == best:
                ties.append(r)
        pick = ties[0] if len(ties) == 1 \
            else ties[self._rng.randrange(len(ties))]
        sent[pick] += 1
        return pick

    def submit(self, requests: Sequence[DecodeRequest]) -> None:
        obs = BUS.enabled  # one check per submit batch
        tr = TRACER.enabled  # ditto for the request span tree
        for req in requests:
            i = self.route(req)
            self.assignments[req.rid] = i
            if tr:
                self._trace_route(req, i)
            self.replicas[i].submit([req])
            if obs:
                BUS.emit("fleet.route", rid=req.rid, replica=i,
                         slo=req.slo or "standard")

    def _trace_route(self, req: DecodeRequest, replica: int) -> None:
        """Mint the request's trace at the FRONT (route time — the
        first component that sees the request) and stamp the router's
        decision as a zero-duration ``route`` child with the replica
        tag; the replica's submit then finds the mapping and only adds
        the queue/prefill/decode children."""
        tid = TRACER.request_root(req.rid, slo=req.slo or "standard")
        TRACER.annotate(tid, "route", parent="request", replica=replica,
                        label=self.replicas[replica].replica_label)

    # ------------------------------------------------------------------
    def step(self) -> int:
        """One frame on every replica that has work — queued, live or
        in flight — the interleaved mode the controller's elastic loop
        drives.  Returns how many replicas stepped."""
        stepped = 0
        for ex in self.replicas:
            if ex.has_work():
                ex.step()
                stepped += 1
        return stepped

    def run(self, requests: Sequence[DecodeRequest] = (),
            max_frames: int = 10_000) -> Dict[str, List[int]]:
        """Route then drain every replica to completion.  Replicas
        drain INDEPENDENTLY (disjoint devices run concurrently in a
        real fleet): the whole trace is routed first (deficit routing
        sees the global arrival order), then each replica's batch is
        submitted immediately before ITS drain — enqueue stamps land on
        the replica's own clock, so one replica's frames never inflate
        another's queue/TTFT spans.  Returns rid -> generated token ids
        across the fleet."""
        out: Dict[str, List[int]] = {}
        if requests:
            obs = BUS.enabled  # one check per run
            tr = TRACER.enabled
            per_replica: List[List[DecodeRequest]] = \
                [[] for _ in self.replicas]
            for req in requests:
                i = self.route(req)
                self.assignments[req.rid] = i
                per_replica[i].append(req)
                if tr:
                    self._trace_route(req, i)
                if obs:
                    BUS.emit("fleet.route", rid=req.rid, replica=i,
                             slo=req.slo or "standard")
            for ex, batch in zip(self.replicas, per_replica):
                if batch:
                    ex.submit(batch)
                out.update(ex.run(max_frames=max_frames))
        else:
            for ex in self.replicas:
                out.update(ex.run(max_frames=max_frames))
        return out

    # ------------------------------------------------------------------
    @property
    def request_records(self) -> List[dict]:
        """Per-replica records merged in replica order (stable — the
        roll-up quantiles are order-independent, determinism tests
        compare the merged list directly)."""
        merged: List[dict] = []
        for i, ex in enumerate(self.replicas):
            for rec in ex.request_records:
                merged.append(dict(rec, replica=i))
        return merged

    def measured_request_p99(self, metric: str = "ttft_s",
                             slo: Optional[str] = None,
                             window: int = 0) -> Optional[float]:
        """Fleet-level per-class request-latency quantile: the merged
        per-replica completions, each class watched at its own
        quantile — the measured side ``TrainingController.
        observe_fleet`` compares against the proposal's predictions."""
        recs = [r for r in self.request_records
                if r.get("phase") == "finish"
                and (slo is None or r.get("slo") == slo)
                and r.get(metric) is not None]
        if window:
            recs = recs[-window:]
        cls = self.slo_classes.get(slo) if slo else None
        return ContinuousBatchingExecutor._quantile(
            [r[metric] for r in recs], cls.quantile if cls else 0.99)

    def summary(self) -> dict:
        """Fleet roll-up: per-replica executor summaries plus merged
        per-class p50/p99 across the whole fleet."""
        q = ContinuousBatchingExecutor._quantile
        recs = [r for r in self.request_records
                if r.get("phase") == "finish"]
        by_class: Dict[str, List[dict]] = {}
        for r in recs:
            by_class.setdefault(r.get("slo", "standard"), []).append(r)
        out = {
            "replicas": len(self.replicas),
            "routing": {c: list(fr)
                        for c, fr in sorted(self.routing.items())},
            "completed": len(recs),
            "per_replica": [ex.summary() for ex in self.replicas],
            "slo_classes": {
                name: {
                    "completed": len(rs),
                    "ttft_p50_s": q([r["ttft_s"] for r in rs
                                     if r.get("ttft_s") is not None],
                                    0.5),
                    "ttft_p99_s": q([r["ttft_s"] for r in rs
                                     if r.get("ttft_s") is not None],
                                    0.99),
                    "e2e_p50_s": q([r["e2e_s"] for r in rs
                                    if r.get("e2e_s") is not None],
                                   0.5),
                    "e2e_p99_s": q([r["e2e_s"] for r in rs
                                    if r.get("e2e_s") is not None],
                                   0.99),
                }
                for name, rs in sorted(by_class.items())
            },
        }
        return out
