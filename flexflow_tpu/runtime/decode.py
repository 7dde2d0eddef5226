"""Continuous-batching decode executor.

The runtime half of the serving workload (ROADMAP item 4): compose
RAGGED requests into FIXED decode frames — the [max_seqs]-slot shape
the compiled decode graph (models/decode.py) was specialized for — so
one jitted program serves an arbitrary request stream:

* a ``PageAllocator`` owns the KV page pool; a request is **admitted**
  only when its full page allotment is reservable.  With radix
  **prefix sharing** armed, part of that allotment may CLAIM
  already-cached pages by refcount — a prefix-trie lookup keyed on
  token ids at page granularity finds the longest cached prefix of
  the prompt — and a mid-page divergence duplicates exactly that one
  page at admission (copy-on-write, ``copy_page_fn``).  The residency
  contract is **reserve-on-divergence**: the moment a sequence is
  admitted, every page at or after the first position it will write
  is PRIVATE (refcount 1, asserted by
  ``PageAllocator.assert_divergence_reserved``) — so an admitted
  sequence can always grow to ``max_seq_len`` unpreempted by pool
  pressure, writes never land in a shared page, and eviction returns
  a page to the free list only at refcount zero.  Admission runs in
  **priority order** when SLO classes are armed: higher-priority
  requests admit first, a request whose ``deadline_frames`` passed
  while queued is EXPIRED instead of served late, and a
  strictly-higher-priority arrival may preempt the lowest-priority
  live sequence (pages refcount-released, sequence re-queued with
  its tokens so far — regeneration is deterministic, and re-admission
  may re-claim the prefix a sibling still holds);
* prompts enter through the **chunked prefill lane** when one is armed
  (``prefill_fn`` — runtime/prefill.py builds it from the decode
  model, ``compiled_decode_step(model, prefill_chunk=C)``): the
  prompt's causal forward runs once per C-token chunk and scatters
  K/V straight into the sequence's pages, then the sequence joins the
  decode loop at its LAST prompt token — token-identical to the
  prefill-via-decode fallback (one decode frame per prompt token),
  which remains the no-prefill-fn path; under prefix sharing both
  paths START at the first token past the claimed cached prefix
  (prefill skips pages the trie already holds);
* each ``step`` fills every live slot's next token through ONE decode
  graph call, until ``max_new_tokens`` or EOS — and where the step
  function's output CARRIES the frame's tokens (``FrameOutput``: the
  greedy choice made on the device), ONE FRAME STAYS IN FLIGHT: frame
  n+1 is composed from frame n's device tokens and dispatched before
  frame n's [B] tokens are pulled and harvested, so the device works
  under the host's harvest, eviction, admission and composition.  A
  plain array of logits is harvested as it always was — synchronously,
  ``argmax`` on the host.  The output decides, frame by frame; the
  token streams are the same (tests/test_decode_ahead.py);
* every frame emits a ``decode.frame`` obs event (admissions,
  evictions, live slots, pages in use, measured latency, predicted
  latency when the caller supplies the search's number) and the run
  ends with a ``decode.summary`` roll-up — the decode phase of the
  predicted-vs-measured story; ``decode_drift_report`` folds the
  measured frame latencies against the search's predicted p99 into
  the same DriftReport shape model.fit produces for training steps
  (``ffobs report`` renders both).

The executor is deliberately decoupled from FFModel: it drives any
``step_fn(token_ids [B,1] i32, page_table [B,P] i32, seq_lens [B] i32)
-> output``, three NumPy arrays in.  **The ``step_fn`` contract.**  The
output is the frame's logits [B, 1, V] (anything ``np.asarray`` takes;
the token is their ``argmax``), or the frame's tokens ([B] or [B, 1]
ids), or an object that carries ``tokens`` [B] beside its logits and
pulls either only when asked (``FrameOutput``) — ``_host_tokens`` is
the one rule.  Only a function whose outputs carry ``tokens`` is run
ahead, and only it is ever handed an id of -1: "this row's id is the
token YOUR LAST CALL chose for it".  The host supplies an id wherever
it knows one — a prompt token, the last token of a newly admitted
sequence.  Running ahead, an end by ``max_new_tokens`` is known a frame
early (the host counts), so that sequence is not sent again and its
slot is refilled for the very next frame; an end the host learns from
the token (EOS) or decides after the dispatch (SLO preemption) finds
one row of that sequence already in flight: its token is DROPPED
(``decode.rows_dropped``), its K/V write lands in pages nothing
dispatched earlier can read, and the EOS costs that sequence's slot one
frame.  An output whose logits someone has already pulled (a tap that
reads every frame) is harvested at once: that frame has run.
``compiled_decode_step`` builds such a function from a compiled decode
model (threading the KV-cache state dict and the last frame's tokens
across calls, and handing the frame and the prefill chunk the SERVED
weight tree — ``model.params`` cast to the compute dtype and laid out
as the matmuls read it, once, not inside every call).
"""

from __future__ import annotations

import collections
import functools
import itertools
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from flexflow_tpu.obs import annotate, device_counters
from flexflow_tpu.obs.annotate import phase_span
from flexflow_tpu.obs.events import BUS
from flexflow_tpu.obs.metrics import METRICS
from flexflow_tpu.obs.tracing import TRACER

# the phases of one ``ContinuousBatchingExecutor.step``, children of
# ``ff.phase/decode_frame`` (histograms ``serve.<phase>_s``)
_ADMIT = annotate.PHASE_PREFIX + "serve.admit"
_COMPOSE = annotate.PHASE_PREFIX + "serve.compose"
_DISPATCH = annotate.PHASE_PREFIX + "serve.dispatch"
_WAIT = annotate.PHASE_PREFIX + "serve.wait"
_HARVEST = annotate.PHASE_PREFIX + "serve.harvest"
_EVICT = annotate.PHASE_PREFIX + "serve.evict"

# always-on counters (registry objects survive ``METRICS.reset()``)
_FRAMES = METRICS.counter("decode.frames")
_ACTIVE_SLOT_FRAMES = METRICS.counter("decode.active_slot_frames")
_SLOT_FRAMES = METRICS.counter("decode.slot_frames")
# pages the ragged kernel walks a frame (every row attends its fresh
# token too; an idle row walks one page) against the page slots of the
# frame's table: the share of the table that is live
_LIVE_PAGES = METRICS.counter("decode.live_pages")
_PAGE_SLOTS = METRICS.counter("decode.page_slots")
_TOKENS_GENERATED = METRICS.counter("decode.tokens_generated")
_PREFILL_CHUNKS = METRICS.counter("decode.prefill_chunks")
# calls of the prefill program: one an admission that prefills, however
# many chunks its prompt takes (÷ into ``decode.prefill_chunks``: the
# chunks a call runs)
_PREFILL_CALLS = METRICS.counter("decode.prefill_calls")
_PREFILL_TOKENS = METRICS.counter("decode.prefill_tokens")
_PROMPT_TOKENS = METRICS.counter("decode.prompt_tokens")
_PREFIX_HIT_TOKENS = METRICS.counter("decode.prefix_hit_tokens")
_FRAME_S = METRICS.histogram("decode.frame_s")
# one frame in flight: frames dispatched while the frame before them was
# still unharvested (over ``decode.frames``: how often the executor runs
# ahead — 0 for a step function whose output carries no tokens), and
# rows of such frames whose token was thrown away because the sequence
# had ended (EOS) or lost its slot (preemption) in the meantime
_FRAMES_AHEAD = METRICS.counter("decode.frames_ahead")
_ROWS_DROPPED = METRICS.counter("decode.rows_dropped")
# the weight tree the two serving programs take (compiled_decode_step):
# how often it was derived, its bytes and the bytes of ``model.params``
_WEIGHT_PREPARES = METRICS.counter("decode.weight_prepares")
_WEIGHT_BYTES = METRICS.gauge("decode.weight_bytes")
_WEIGHT_BYTES_MASTER = METRICS.gauge("decode.weight_bytes_master")
# two kinds of KV page (compiled_decode_step): the bytes of the pools the
# page table addresses and of the window layers' rings, and — a frame, a
# layer, a row — the pages the kernel walks (a window layer's capped at
# its window) against the live pages a walk with no window would take
_KV_BYTES_GLOBAL = METRICS.gauge("decode.kv_bytes_global")
_KV_BYTES_WINDOW = METRICS.gauge("decode.kv_bytes_window")
_KV_PAGES_WALKED = METRICS.counter("decode.kv_pages_walked")
_KV_PAGES_LIVE = METRICS.counter("decode.kv_pages_live")
# the prefill chunk's attention (compiled_decode_step): the keys its
# blocks cover — a chunk, a layer, from the positions sent and by the
# op's own ``chunk_walk`` — against the keys of the page table
_PREFILL_KEYS_WALKED = METRICS.counter("decode.prefill_keys_walked")
_PREFILL_KEYS_TABLE = METRICS.counter("decode.prefill_keys_table")
# a frame's device counters are published every this many frames, from a
# copy that left the device with an earlier frame's tokens
_OBS_EVERY = 16


@dataclass
class DecodeRequest:
    """One sequence to serve: the prompt's token ids and how many new
    tokens to generate.  ``eos_id`` stops generation early when the
    model emits it (None = run to max_new_tokens).  ``slo`` names the
    request's SLO class (resolved against the executor's class table);
    ``priority``/``deadline_frames`` override the class defaults —
    higher priority admits first, a deadline (frames from enqueue to
    admission) expires the request instead of serving it late."""

    rid: str
    prompt: Sequence[int]
    max_new_tokens: int = 8
    eos_id: Optional[int] = None
    slo: str = "standard"
    priority: Optional[int] = None
    deadline_frames: Optional[int] = None


@dataclass(frozen=True)
class SLOClass:
    """One request class of the serving deployment: admission priority,
    queue deadline, and the arrival quantile its latency is watched at
    (``measured_request_p99``/``TrainingController.observe_p99`` per
    class).  Persisted into ``__meta__.disaggregation.slo_classes``
    (fflint STR211 checks the shape stdlib-only)."""

    name: str
    priority: int = 0
    deadline_frames: int = 0  # 0 = no deadline
    quantile: float = 0.99

    def to_jsonable(self) -> dict:
        return {"name": self.name, "priority": self.priority,
                "deadline_frames": self.deadline_frames,
                "quantile": self.quantile}


@dataclass
class _Pending:
    """A queued sequence: a fresh submission, or a preempted live
    sequence carrying the tokens it already produced (regeneration is
    deterministic, so re-decoding continues the same stream)."""

    req: DecodeRequest
    seq: int               # submission order (FIFO tie-break)
    priority: int
    deadline_frames: int   # 0 = none
    enqueue_frame: int
    tokens: List[int] = field(default_factory=list)
    generated: int = 0
    preempted: int = 0     # times this sequence lost its slot
    # telemetry stamps carried across preemption (first values win)
    enqueue_t: Optional[float] = None
    admit_t: Optional[float] = None
    prefill_done_t: Optional[float] = None
    first_token_t: Optional[float] = None
    started_frame: Optional[int] = None


@dataclass
class _Live:
    req: DecodeRequest
    pages: List[int]
    tokens: List[int] = field(default_factory=list)  # prompt + generated
    cached: int = 0        # tokens already written into the KV cache
    generated: int = 0
    started_frame: int = 0
    priority: int = 0
    preempted: int = 0
    seq: int = 0
    deadline_frames: int = 0
    enqueue_frame: int = 0
    # request lifecycle span stamps (perf_counter seconds), always taken.
    # prefill_done_t closes the PREFILL span: the cache holds every
    # prompt token but the last, so TTFT decomposes exactly into
    # queue (enqueue→admit) + prefill (admit→prefill_done) +
    # first decode frame (prefill_done→first_token).
    enqueue_t: Optional[float] = None
    admit_t: Optional[float] = None
    prefill_done_t: Optional[float] = None
    first_token_t: Optional[float] = None
    # one frame in flight: frames dispatched for this sequence and not
    # yet harvested (its next position is ``cached + ahead``); ``retired``
    # once its slot and pages went back while its LAST frame was still in
    # flight; ``closed`` once it finished or was preempted — a frame still
    # in flight for it then carries a token nobody takes
    ahead: int = 0
    retired: bool = False
    closed: bool = False


@dataclass
class _Frame:
    """One composed frame: the arrays the step function is given, the
    (slot, sequence) of every row that carries one and, once
    dispatched, the step function's output and the instant it went."""

    ids: np.ndarray
    table: np.ndarray
    lens: np.ndarray
    rows: List[tuple]
    out: object = None
    t0: float = 0.0


class FrameOutput:
    """A frame's output with the token already chosen on the device:
    ``tokens`` [B] int32 (greedy: argmax over the vocabulary, the first
    index on a tie, as ``np.argmax`` has it) beside the ``logits``
    [B, 1, V] they were taken from.  Both stay on the device until
    someone asks: the executor pulls only ``tokens``; ``np.asarray(out)``
    and ``out[...]`` pull the logits (a probe, a test), once —
    ``pulled`` says that this has happened, so that frame has run."""

    __slots__ = ("logits", "tokens", "_host")

    def __init__(self, logits, tokens):
        self.logits = logits
        self.tokens = tokens
        self._host = None

    @property
    def shape(self):
        return self.logits.shape

    @property
    def pulled(self) -> bool:
        return self._host is not None

    def __array__(self, dtype=None, copy=None):
        if self._host is None:
            self._host = np.asarray(self.logits)
        return (self._host if dtype is None
                else self._host.astype(dtype, copy=False))

    def __getitem__(self, index):
        return self.__array__()[index]


class PageAllocator:
    """Free-list page allocator over the decode graph's pool, with
    copy-on-write refcounts and a radix prefix trie.

    Every in-use page carries a refcount (``alloc`` starts it at 1;
    ``share`` lets a second sequence claim it; ``free`` decrements and
    returns the page to the free list only at zero).  The trie maps
    token-id prefixes — at page granularity — to the page caching that
    prefix's K/V, published by ``register_prefix`` as sequences fill
    pages and consulted by ``lookup_prefix`` at admission.  Sharing a
    cached page is sound because a causal decoder's K/V at position i
    is a deterministic function of tokens[:i+1] alone.

    The residency contract is **reserve-on-divergence**: callers must
    arrange (CoW at admission) that every page at or after a
    sequence's first write position is private — checked by
    ``assert_divergence_reserved``.  That preserves the historical
    guarantee in the new regime: an admitted sequence can always grow
    to ``max_seq_len`` unpreempted by pool pressure, because its
    writable tail is reserved up front and shared pages are read-only
    by construction."""

    def __init__(self, num_pages: int):
        self.num_pages = num_pages
        self._free = list(range(num_pages - 1, -1, -1))
        self._ref: Dict[int, int] = {}  # in-use page -> refcount
        # prefix trie, flattened: full-prefix tuple -> page caching its
        # last page_size tokens; parent prefix -> {page: token chunk}
        # for mid-page (CoW) matches; page -> (parent, chunk) for
        # removal at refcount zero
        self._prefix: Dict[tuple, int] = {}
        self._children: Dict[tuple, Dict[int, tuple]] = {}
        self._page_key: Dict[int, tuple] = {}

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def pages_in_use(self) -> int:
        return self.num_pages - len(self._free)

    def refcount(self, page: int) -> int:
        return self._ref.get(page, 0)

    def alloc(self, n: int) -> Optional[List[int]]:
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._ref[p] = 1
        return pages

    def alloc_ids(self, ids: Sequence[int]) -> Optional[List[int]]:
        """Reserve SPECIFIC page ids (the slot-aligned fast path), or
        None when any is already in use."""
        if any(p not in self._free for p in ids):
            return None
        for p in ids:
            self._free.remove(p)
            self._ref[p] = 1
        return list(ids)

    def share(self, pages: Sequence[int]) -> None:
        """Claim already-cached pages for one more sequence: each must
        be live (a sibling holds it), its refcount goes up by one, and
        ``free`` from either owner now only drops the count."""
        for p in pages:
            assert self._ref.get(p, 0) >= 1, (
                f"page {p} is not live — the trie served a stale hit")
            self._ref[p] += 1

    def free(self, pages: Sequence[int]) -> None:
        for p in pages:
            assert 0 <= p < self.num_pages and p not in self._free, p
            r = self._ref.get(p, 0)
            assert r >= 1, f"page {p} freed more times than referenced"
            if r > 1:
                self._ref[p] = r - 1
            else:
                del self._ref[p]
                self._drop_prefix(p)
                self._free.append(p)

    # ---- prefix trie -----------------------------------------------------
    def register_prefix(self, tokens: Sequence[int], page_size: int,
                        pages: Sequence[int], cached: int) -> None:
        """Publish every FULLY-cached page of a sequence (the first
        ``cached`` tokens of ``tokens`` live in ``pages``) into the
        trie.  First registration of a prefix wins; already-published
        pages are skipped, so calling this at every page boundary is
        idempotent and O(full pages)."""
        for j in range(cached // page_size):
            p = pages[j]
            if p in self._page_key:
                continue  # this page already backs a trie entry
            parent = tuple(tokens[:j * page_size])
            chunk = tuple(tokens[j * page_size:(j + 1) * page_size])
            if parent + chunk in self._prefix:
                continue  # a sibling's page already owns this prefix
            self._prefix[parent + chunk] = p
            self._children.setdefault(parent, {})[p] = chunk
            self._page_key[p] = (parent, chunk)

    def lookup_prefix(self, tokens: Sequence[int], page_size: int):
        """Longest cached prefix of ``tokens``: returns
        ``(pages, matched, partial)`` — the fully-matching cached pages
        (claim them via ``share``), the token count they cover, and,
        when a further cached page agrees on ``extra`` more tokens
        mid-page, ``partial = (src_page, extra)`` for the caller to
        copy-on-write.  Pure lookup: claims nothing."""
        tokens = tuple(tokens)
        pages: List[int] = []
        k = 0
        while (k + 1) * page_size <= len(tokens):
            p = self._prefix.get(tokens[:(k + 1) * page_size])
            if p is None:
                break
            pages.append(p)
            k += 1
        matched = k * page_size
        partial = None
        rest = tokens[matched:]
        if rest:
            best_m, best_p = 0, None
            for p, chunk in self._children.get(tokens[:matched],
                                               {}).items():
                m = 0
                for a, b in zip(chunk, rest):
                    if a != b:
                        break
                    m += 1
                if m > best_m:
                    best_m, best_p = m, p
            if best_m:
                partial = (best_p, best_m)
        return pages, matched, partial

    def assert_divergence_reserved(self, pages: Sequence[int],
                                   first_write_page: int) -> None:
        """The reserve-on-divergence invariant, checked at admission:
        every page at or after the first page this sequence will write
        must be PRIVATE (refcount exactly 1) — shared pages are
        read-only, so post-admission writes can never need an
        in-flight CoW and the sequence's growth to ``max_seq_len`` is
        reserved up front."""
        for j in range(first_write_page, len(pages)):
            assert self._ref.get(pages[j], 0) == 1, (
                f"page {pages[j]} (allotment index {j}) is shared at "
                f"refcount {self._ref.get(pages[j], 0)} but lies at or "
                f"after the sequence's first write page "
                f"{first_write_page} — reserve-on-divergence violated")

    def _drop_prefix(self, page: int) -> None:
        """Remove a page's trie entry when its refcount hits zero —
        the bytes are about to be reused, so the prefix is no longer
        cached anywhere."""
        key = self._page_key.pop(page, None)
        if key is None:
            return
        parent, chunk = key
        if self._prefix.get(parent + chunk) == page:
            del self._prefix[parent + chunk]
        kids = self._children.get(parent)
        if kids is not None:
            kids.pop(page, None)
            if not kids:
                del self._children[parent]


def _host_tokens(out) -> np.ndarray:
    """The [B] tokens of a frame's output, on the host (this blocks
    until the frame has run).  ONE rule: the output is the frame's
    tokens ([B] or [B, 1] ids) — those it carries as ``tokens`` where it
    has them beside its logits, and then only they are pulled — or it IS
    the logits [B, 1, V], whose argmax over the vocabulary is the
    token."""
    tokens = np.asarray(getattr(out, "tokens", out))
    if tokens.ndim == 3:
        tokens = tokens.argmax(axis=-1)
    return tokens.reshape(-1).astype(np.int32)


def _in_flight(out) -> bool:
    """The output's tokens are still on the device: it carries them,
    and nobody has pulled the frame to the host yet (a tap that reads
    every frame's logits has: that frame is over, and the executor
    harvests it at once)."""
    return (getattr(out, "tokens", None) is not None
            and not getattr(out, "pulled", False))


class ContinuousBatchingExecutor:
    """Admit ragged requests into fixed decode frames and drive the
    step function until every request completes."""

    def __init__(self, step_fn: Callable, *, max_seqs: int,
                 page_size: int, pages_per_seq: int, num_pages: int = 0,
                 predicted_step_s: Optional[float] = None,
                 prefill_fn: Optional[Callable] = None,
                 prefill_chunk: int = 0,
                 slo_classes: Optional[Sequence[SLOClass]] = None,
                 replica_label: Optional[str] = None,
                 prefix_sharing: bool = False,
                 copy_page_fn: Optional[Callable] = None):
        self.step_fn = step_fn
        # fleet membership (runtime/fleet.py): when set, the request
        # histograms are ALSO observed under `name|replica=...,slo=...`
        # labeled series so /metrics can tell fleet members apart
        self.replica_label = replica_label
        self.max_seqs = max_seqs
        self.page_size = page_size
        self.pages_per_seq = pages_per_seq
        # chunked prefill lane (runtime/prefill.py): when armed, a
        # prompt's first len-1 tokens are written into the cache in
        # ceil((len-1)/chunk) batched passes at admission instead of
        # one decode frame each; None keeps the historical
        # prefill-via-decode path byte-identical
        self.prefill_fn = prefill_fn
        self.prefill_chunk = int(prefill_chunk or 0)
        if prefill_fn is not None and self.prefill_chunk < 1:
            raise ValueError(
                "prefill_fn needs prefill_chunk >= 1 (the chunk size "
                "the jitted writer was built for)")
        # SLO classes: priority admission / deadline expiry / preemption
        # (empty table = single-class FIFO, the historical behavior)
        self.slo_classes: Dict[str, SLOClass] = {
            c.name: c for c in (slo_classes or ())}
        self._seq = 0  # submission counter (FIFO tie-break)
        # radix prefix sharing: admission claims trie-cached prefix
        # pages by refcount instead of allocating them, mid-page
        # divergence copies that one page via copy_page_fn (CoW at
        # admission — reserve-on-divergence, see PageAllocator), and
        # prefill starts at the first token past the claimed prefix.
        # Off keeps every historical path byte-identical.
        self.prefix_sharing = bool(prefix_sharing)
        self.copy_page_fn = copy_page_fn
        self.allocator = PageAllocator(num_pages or max_seqs * pages_per_seq)
        # slot-aligned allocation: when the pool covers every slot,
        # slot i always takes pages [i*pps, (i+1)*pps) — contiguous
        # slot shards own contiguous page ranges, which is EXACTLY the
        # page-dim split the decode op's state_shardings places under a
        # batch-split view, so the device-local cache streaming the
        # cost model credits to batch splits is realized by the
        # executor, not merely priced.  Undersized (oversubscribed)
        # pools fall back to the free list, where a sequence's pages
        # may land on another group's shard — the locality price of
        # oversubscription.  Prefix sharing ALSO forces the free list:
        # a claimed page lives wherever the sibling's allotment put it,
        # so slot-aligned page identities cannot hold.
        self.slot_aligned = (
            not self.prefix_sharing
            and self.allocator.num_pages >= max_seqs * pages_per_seq)
        # a model whose window layers keep a RING of pages a sequence
        # slot reads the slot off the table row: free-list pages (an
        # oversubscribed pool, prefix sharing) would name another
        # sequence's ring
        if not self.slot_aligned and any(
                getattr(f, "needs_slot_aligned", False)
                for f in (step_fn, prefill_fn)):
            raise ValueError(
                "the step function's window layers hold a ring of KV pages "
                "a sequence SLOT (the op's ring_pages; slot i owns pages "
                "[i*pps, (i+1)*pps) of the table); this executor hands out "
                "pages from the free list (prefix sharing, or a pool "
                "smaller than max_seqs x pages_per_seq), which would name "
                "another sequence's ring")
        # idle frame rows still scatter one garbage k/v (static-shape
        # scatter — the op cannot skip rows), so they must point at a
        # page no LIVE sequence can own.  Slot-aligned pools use the
        # idle slot's OWN range (free by construction while the slot is
        # idle; a later admission rewrites every position before
        # reading it).  Oversubscribed pools RESERVE one scratch page
        # up front — one page of capacity is the price of a pool that
        # can otherwise be fully exhausted while slots sit idle (the
        # free-list fallback of picking "some free page" corrupts live
        # cache exactly then).
        self._scratch_page = None
        if not self.slot_aligned:
            got = self.allocator.alloc(1)
            assert got, "page pool too small to reserve the scratch page"
            self._scratch_page = got[0]
        # the search's predicted (p99) decode-step seconds, when the
        # caller has one — recorded per frame so drift is computable
        self.predicted_step_s = predicted_step_s
        self.slots: List[Optional[_Live]] = [None] * max_seqs
        self.queue: List[_Pending] = []
        self.finished: Dict[str, List[int]] = {}
        self.expired: Dict[str, List[int]] = {}  # deadline-missed rids
        self.frame = 0
        self.frame_seconds: List[float] = []
        # one frame in flight (see ``step``): the frame dispatched and
        # not yet harvested, the sequences whose slot went back while
        # their last frame was in it, and when the last frame's tokens
        # reached the host
        self._flight: Optional[_Frame] = None
        self._retired: List[_Live] = []
        self._harvested_t = 0.0
        self.total_admitted = 0
        self.total_evicted = 0
        self.total_expired = 0
        self.total_preempted = 0
        self.prefill_chunks = 0  # chunked-prefill passes run
        self.prefill_calls = 0  # prefill_fn calls: one a prefilled prompt
        self.prefill_tokens = 0  # prompt tokens written by the lane
        # prefix-sharing roll-up (all zero while sharing is off)
        self.prefix_hits = 0     # admissions that claimed a cached prefix
        self.shared_pages = 0    # pages claimed by refcount, cumulative
        self.cow_copies = 0      # mid-page divergences copied at admission
        self.prefix_tokens = 0   # prompt tokens served from shared cache
        # per-request lifecycle records (enqueue→admit→prefill→first
        # token→EOS/evict; TTFT/TPOT/e2e + the TTFT split).  The stamps
        # and the ``decode.*_s`` histograms are always taken; the record
        # dicts and their ``decode.request`` events only while the obs
        # bus is armed — BUS.enabled is read ONCE per frame (and once
        # per submit batch)
        self.request_records: List[dict] = []

    # ------------------------------------------------------------------
    def submit(self, requests: Sequence[DecodeRequest]) -> None:
        obs = BUS.enabled  # one check per submit batch
        tr = TRACER.enabled  # ditto — the request-trace gate
        for r in requests:
            assert r.prompt, f"request {r.rid!r} has an empty prompt"
            need = len(r.prompt) + r.max_new_tokens
            cap = self.page_size * self.pages_per_seq
            assert need <= cap, (
                f"request {r.rid!r} wants {need} tokens but a sequence "
                f"caps at {cap} (page_size x pages_per_seq)")
            cls = self.slo_classes.get(r.slo)
            entry = _Pending(
                req=r, seq=self._seq,
                priority=(r.priority if r.priority is not None
                          else (cls.priority if cls else 0)),
                deadline_frames=(
                    r.deadline_frames if r.deadline_frames is not None
                    else (cls.deadline_frames if cls else 0)),
                enqueue_frame=self.frame,
                tokens=list(r.prompt),
            )
            self._seq += 1
            entry.enqueue_t = time.perf_counter()
            if tr:
                # trace minted at enqueue (idempotent: the fleet router
                # minted it at route time, then this opens children);
                # the queue span runs enqueue -> admission
                tid = TRACER.request_root(r.rid, slo=r.slo)
                TRACER.begin(tid, "queue", parent="request")
            self.queue.append(entry)

    def _expire(self, obs: bool = False, tr: bool = False) -> int:
        """Drop queued requests whose admission deadline passed —
        deadline-based admission control: a request the deployment can
        no longer serve inside its SLO is refused loudly (recorded in
        ``expired``, one ``decode.request`` phase="expired" event),
        never served late."""
        expired = 0
        kept = []
        for e in self.queue:
            if (e.deadline_frames
                    and self.frame - e.enqueue_frame > e.deadline_frames):
                self.expired[e.req.rid] = e.tokens[len(e.req.prompt):]
                expired += 1
                if obs:
                    rec = {"rid": e.req.rid, "phase": "expired",
                           "slo": e.req.slo,
                           "queued_frames": self.frame - e.enqueue_frame,
                           "deadline_frames": e.deadline_frames}
                    self.request_records.append(rec)
                    BUS.emit("decode.request", **rec)
                if tr:
                    tid = TRACER.trace_of(e.req.rid)
                    if tid is not None:
                        TRACER.end(tid, "queue", expired=True)
                        TRACER.finish_request(e.req.rid,
                                              outcome="expired")
            else:
                kept.append(e)
        self.queue = kept
        self.total_expired += expired
        return expired

    def _preempt_for(self, entry: _Pending, obs: bool,
                     tr: bool = False) -> bool:
        """Free a slot + pages for a strictly-higher-priority pending
        request by evicting the LOWEST-priority live sequence
        (latest-admitted tie-break).  The victim re-queues with its
        tokens so far — regeneration is deterministic, so its stream
        continues unchanged after re-admission."""
        victims = [
            (live.priority, -live.started_frame, -i, i)
            for i, live in enumerate(self.slots)
            if live is not None and live.priority < entry.priority
        ]
        if not victims:
            return False
        _, _, _, i = min(victims)
        live = self.slots[i]
        self.allocator.free(live.pages)
        self.slots[i] = None
        live.closed = True  # a frame in flight for it is dropped
        self.total_preempted += 1
        back = _Pending(
            req=live.req, seq=live.seq, priority=live.priority,
            deadline_frames=live.deadline_frames,
            enqueue_frame=live.enqueue_frame,
            tokens=list(live.tokens), generated=live.generated,
            preempted=live.preempted + 1,
            enqueue_t=live.enqueue_t, admit_t=live.admit_t,
            prefill_done_t=live.prefill_done_t,
            first_token_t=live.first_token_t,
            started_frame=live.started_frame,
        )
        self.queue.append(back)
        if obs:
            BUS.emit("decode.request", rid=live.req.rid,
                     phase="preempted", slo=live.req.slo,
                     by=entry.req.rid, tokens=live.generated)
        if tr:
            tid = TRACER.trace_of(live.req.rid)
            if tid is not None:
                # the victim was mid-decode or (via-decode path)
                # mid-prefill; either way its residency window closes
                # and a fresh queue span opens — the re-queue edge
                TRACER.end_any(tid, ("decode", "prefill"),
                               preempted_by=entry.req.rid)
                TRACER.begin(tid, "queue", parent="request",
                             requeue=True)
        return True

    def _run_prefill(self, live: _Live, obs: bool) -> None:
        """The chunked prefill lane: write the sequence's first
        ``len(tokens) - 1`` cached-to-be tokens through the batched
        chunk writer (``run_chunked_prefill``, runtime/prefill.py: ONE
        ``prefill_fn`` call for all of the prompt's chunks), so
        the decode loop starts at the LAST token and produces the first
        generated token in its first frame.  Under prefix sharing the
        first ``live.cached`` tokens are already in claimed/copied
        pages — the writer starts at the first divergent token."""
        n_pre = len(live.tokens) - 1
        start = live.cached  # shared-prefix skip-ahead (0 off-sharing)
        if n_pre - start <= 0 or self.prefill_fn is None:
            return
        from flexflow_tpu.runtime.prefill import run_chunked_prefill

        chunks = run_chunked_prefill(
            self.prefill_fn, live.tokens, live.pages,
            chunk=self.prefill_chunk,
            cap=self.page_size * self.pages_per_seq,
            start=start)
        live.cached = n_pre
        self.prefill_chunks += chunks
        self.prefill_calls += 1
        self.prefill_tokens += n_pre - start
        _PREFILL_CHUNKS.inc(chunks)
        _PREFILL_CALLS.inc()
        _PREFILL_TOKENS.inc(n_pre - start)
        if obs:
            BUS.emit("decode.prefill", rid=live.req.rid,
                     tokens=n_pre - start,
                     chunks=chunks, chunk=self.prefill_chunk)

    def _admit(self, obs: bool = False, tr: bool = False) -> int:
        """Fill open slots from the queue in (priority, submission)
        order while the allocator can reserve a FULL per-sequence
        allotment; expired requests are refused first, and a
        strictly-higher-priority arrival may preempt the
        lowest-priority live sequence when no allotment is free.  With
        a frame in flight, a sequence whose LAST frame is in it gives
        its slot and pages back first (``_retire_sent``)."""
        if self._flight is not None:
            self._retire_sent()
        self._expire(obs, tr)
        admitted = 0
        while self.queue:
            order = sorted(range(len(self.queue)),
                           key=lambda j: (-self.queue[j].priority,
                                          self.queue[j].seq))
            entry = self.queue[order[0]]
            open_slots = [i for i in range(self.max_seqs)
                          if self.slots[i] is None]
            if not open_slots and not self._preempt_for(entry, obs, tr):
                break
            open_slots = [i for i in range(self.max_seqs)
                          if self.slots[i] is None]
            i = open_slots[0]
            # prefix-sharing claim: the trie lookup runs INSIDE the
            # preempt-retry loop because a preemption below may free a
            # matched page to refcount zero (stale hit otherwise).
            # Only the to-be-cached prefix (all but the last token) is
            # eligible — the last token is fed through decode, and its
            # scatter must land in a page this sequence owns.
            shared: List[int] = []
            matched = 0
            partial = None
            if self.prefix_sharing:
                shared, matched, partial = self.allocator.lookup_prefix(
                    entry.tokens[:-1], self.page_size)
                if partial is not None and self.copy_page_fn is None:
                    partial = None  # cannot CoW without a page copier
            if self.slot_aligned:
                pages = self.allocator.alloc_ids(range(
                    i * self.pages_per_seq, (i + 1) * self.pages_per_seq))
            else:
                pages = self.allocator.alloc(
                    self.pages_per_seq - len(shared))
            if pages is None:
                if not self._preempt_for(entry, obs, tr):
                    break
                continue  # retry with the freed allotment
            if shared:
                self.allocator.share(shared)
                pages = shared + pages
            if partial is not None:
                # mid-page divergence: duplicate the one agreeing page
                # into the first fresh page NOW (CoW at admission), so
                # every post-admission write lands in owned pages
                src, extra = partial
                dst = pages[len(shared)]
                self.copy_page_fn(src, dst)
                matched += extra
                self.cow_copies += 1
            if matched:
                self.prefix_hits += 1
                self.shared_pages += len(shared)
                self.prefix_tokens += matched
                _PREFIX_HIT_TOKENS.inc(matched)
            if self.prefix_sharing:
                self.allocator.assert_divergence_reserved(
                    pages, matched // self.page_size)
            self.queue.pop(order[0])
            live = _Live(req=entry.req, pages=pages,
                         tokens=list(entry.tokens), cached=matched,
                         generated=entry.generated,
                         started_frame=(entry.started_frame
                                        if entry.started_frame is not None
                                        else self.frame),
                         priority=entry.priority,
                         preempted=entry.preempted, seq=entry.seq,
                         deadline_frames=entry.deadline_frames,
                         enqueue_frame=entry.enqueue_frame)
            # what the sequence arrives with: its prompt and, after a
            # preemption, the tokens it had generated (prefilled again)
            _PROMPT_TOKENS.inc(len(entry.tokens))
            live.enqueue_t = entry.enqueue_t
            live.admit_t = entry.admit_t or time.perf_counter()
            live.prefill_done_t = entry.prefill_done_t
            live.first_token_t = entry.first_token_t
            tid = TRACER.trace_of(entry.req.rid) if tr else None
            if tid is not None:
                # admission edge: the queue window closes, the prefill
                # window opens (chunk children land under it);
                # cached_prefix records how many prompt tokens the
                # shared cache already held — the span's duration is
                # the cost of the REMAINING tokens only
                TRACER.end(tid, "queue")
                TRACER.begin(tid, "prefill", parent="request",
                             slot=i, pages=len(pages),
                             cached_prefix=matched)
            self._run_prefill(live, obs)
            if self.prefix_sharing and live.cached:
                # publish this sequence's fully-cached pages (claimed
                # ones are already in the trie and skip out)
                self.allocator.register_prefix(
                    live.tokens, self.page_size, live.pages, live.cached)
            if live.prefill_done_t is None:
                # the prefill span closes here for the chunked lane,
                # for single-token prompts, and for prompts fully
                # served from a shared prefix (nothing left to
                # prefill); the via-decode path closes it in step()
                # when the cache holds every prompt token but the last
                if (self.prefill_fn is not None or len(live.tokens) <= 1
                        or live.cached >= len(live.tokens) - 1):
                    live.prefill_done_t = time.perf_counter()
            if tid is not None and (self.prefill_fn is not None
                                    or len(live.tokens) <= 1
                                    or live.cached >= len(live.tokens) - 1):
                # same edge for the span tree: prefill closes, the
                # decode residency window opens (the via-decode path
                # closes prefill in step() instead)
                if TRACER.end(tid, "prefill") is not None:
                    TRACER.begin(tid, "decode", parent="request")
            self.slots[i] = live
            admitted += 1
        self.total_admitted += admitted
        return admitted

    @staticmethod
    def _sent(live: _Live) -> bool:
        """The sequence's LAST frame is in flight: the host counts, so
        an end by ``max_new_tokens`` is known a frame before its token
        is."""
        return bool(live.ahead) and (
            live.cached + live.ahead
            >= len(live.req.prompt) + live.req.max_new_tokens - 1)

    def _retire_sent(self) -> None:
        """Give back the slot and the pages of every sequence whose last
        frame is the one in flight, so the coming admission can fill the
        slot in the very next frame.  Sound because whatever is
        dispatched from now on — a prefill chunk into those pages, an
        idle row's scatter — runs AFTER the frame in flight (every
        program takes the donated state the one before it returned).
        The sequence waits in ``_retired`` for its last token."""
        for i, live in enumerate(self.slots):
            if live is not None and self._sent(live):
                self.allocator.free(live.pages)
                self.slots[i] = None
                live.retired = True
                self._retired.append(live)

    def _evict(self, obs: bool, tr: bool, now: float) -> int:
        """Close every sequence that ended in the frame just harvested
        (``max_new_tokens`` or EOS): its tokens go to ``finished``, its
        pages back and its slot reopens — unless ``_retire_sent`` did
        that a frame ago; ``now`` is when the frame's tokens reached the
        host."""
        evicted = 0
        for i, live in enumerate(self.slots):
            if live is not None and self._ended(live):
                self._finish(live, obs, tr, now, slot=i)
                evicted += 1
        for live in self._retired:  # their last token has just come
            assert self._ended(live), live.req.rid
            self._finish(live, obs, tr, now)
            evicted += 1
        self._retired = []
        self.total_evicted += evicted
        return evicted

    @staticmethod
    def _ended(live: _Live) -> bool:
        return (live.generated >= live.req.max_new_tokens
                or (live.req.eos_id is not None and live.generated > 0
                    and live.tokens[-1] == live.req.eos_id))

    def _finish(self, live: _Live, obs: bool, tr: bool, now: float,
                slot: Optional[int] = None) -> None:
        self.finished[live.req.rid] = live.tokens[len(live.req.prompt):]
        live.closed = True  # a frame in flight for it is dropped
        if slot is not None:
            self.allocator.free(live.pages)
            self.slots[slot] = None
        self._record_request(live, now, obs)
        if tr:
            tid = TRACER.trace_of(live.req.rid)
            if tid is not None:
                eos = (live.req.eos_id is not None and live.generated > 0
                       and live.tokens[-1] == live.req.eos_id)
                TRACER.end(tid, "decode", eos=eos, tokens=live.generated)
                TRACER.finish_request(
                    live.req.rid, outcome="finish",
                    tokens=live.generated, preempted=live.preempted)

    def _record_request(self, live: _Live, now: float, obs: bool) -> None:
        """Close a finished request's lifecycle: queue wait
        (enqueue→admit), TTFT (enqueue→first generated token), TPOT
        (steady per-token after the first), e2e — always observed into
        the metrics registry's histograms; kept as a record and emitted
        as one ``decode.request`` event only when the bus was armed at
        eviction time (``obs``, the caller's one-check-per-frame
        gate)."""
        enq, adm, first = live.enqueue_t, live.admit_t, live.first_token_t
        pre = live.prefill_done_t
        queue_s = (adm - enq) if (enq is not None and adm is not None) \
            else None
        ttft_s = (first - enq) if (enq is not None and first is not None) \
            else None
        # the TTFT split: queue + prefill + first decode frame sum to
        # TTFT exactly (prefill_done closes when the cache holds every
        # prompt token but the last — chunked lane or via-decode alike)
        prefill_s = (pre - adm) if (adm is not None and pre is not None) \
            else None
        first_frame_s = (first - pre) \
            if (pre is not None and first is not None) else None
        e2e_s = (now - enq) if enq is not None else None
        tpot_s = None
        if first is not None and live.generated > 1:
            tpot_s = (now - first) / (live.generated - 1)
        # labeled series: the global aggregates stay (back-compat), and
        # the request-latency histograms are ALSO observed per
        # (replica, SLO class) so /metrics can tell fleet members and
        # priority lanes apart (obs/exposition.py parses the |k=v
        # suffix into Prometheus labels).
        slo = live.req.slo or "standard"
        lab = (f"slo={slo}" if self.replica_label is None
               else f"replica={self.replica_label},slo={slo}")
        labeled = ("decode.queue_s", "decode.ttft_s", "decode.tpot_s",
                   "decode.e2e_s")
        for key, v in (("decode.queue_s", queue_s),
                       ("decode.prefill_s", prefill_s),
                       ("decode.first_frame_s", first_frame_s),
                       ("decode.ttft_s", ttft_s),
                       ("decode.tpot_s", tpot_s),
                       ("decode.e2e_s", e2e_s)):
            if v is not None:
                METRICS.histogram(key).observe(v)
                if key in labeled:
                    METRICS.histogram(f"{key}|{lab}").observe(v)
        if not obs:
            return
        rec = {
            "rid": live.req.rid,
            "phase": "finish",
            "slo": live.req.slo,
            "queue_s": queue_s,
            "prefill_s": prefill_s,
            "first_frame_s": first_frame_s,
            "ttft_s": ttft_s,
            "tpot_s": tpot_s,
            "e2e_s": e2e_s,
            "tokens": live.generated,
            "frames": self.frame - live.started_frame + 1,
            "preempted": live.preempted,
        }
        self.request_records.append(rec)
        BUS.emit("decode.request", **rec)

    # ------------------------------------------------------------------
    def _compose_frame(self) -> _Frame:
        """The fixed-shape arrays of the NEXT frame to dispatch: every
        live slot with a frame still to send contributes the token at
        its next position, ``cached + ahead`` — a prompt token still
        being prefilled or the last generated token where the host has
        it, else -1: the token the frame in flight is choosing, which
        the step function takes on the device (only a step function
        whose output carries tokens is ever handed one).  Idle slots
        carry token 0 at length 0 — page_table rows of idle slots point
        at page 0 of live-anywhere pages, masked off by seq_lens=0."""
        b = self.max_seqs
        ids = np.zeros((b, 1), np.int32)
        table = np.zeros((b, self.pages_per_seq), np.int32)
        lens = np.zeros((b,), np.int32)
        rows = []
        for i, live in enumerate(self.slots):
            if live is None or self._sent(live):
                # idle row: its scatter must land where no live
                # sequence reads (see __init__ — own slot range when
                # slot-aligned, the reserved scratch page otherwise)
                if self.slot_aligned:
                    table[i, :] = np.arange(
                        i * self.pages_per_seq,
                        (i + 1) * self.pages_per_seq)
                else:
                    table[i, :] = self._scratch_page
                continue
            rows.append((i, live))
            pos = live.cached + live.ahead
            ids[i, 0] = live.tokens[pos] if pos < len(live.tokens) else -1
            table[i, :len(live.pages)] = live.pages
            lens[i] = pos
        return _Frame(ids, table, lens, rows)

    def _dispatch(self, frame: _Frame) -> _Frame:
        frame.t0 = time.perf_counter()
        frame.out = self.step_fn(frame.ids, frame.table, frame.lens)
        for _, live in frame.rows:
            live.ahead += 1
        return frame

    def has_work(self) -> bool:
        """A request queued, a sequence live, or a frame in flight whose
        tokens are not on the host yet."""
        return bool(self.queue or self._flight is not None
                    or any(s is not None for s in self.slots))

    def step(self) -> dict:
        """One decode frame HARVESTED: admit, compose, dispatch, wait,
        harvest, evict — each a ``ff.phase/serve.*`` child of the
        frame's ``ff.phase/decode_frame`` span.  Returns the harvested
        frame's record (also emitted as ``decode.frame``).

        What is dispatched depends on what the step function's output
        carries.  A plain array of logits: the frame composed here is
        dispatched, waited for and harvested by this call, the token
        chosen on the host — the synchronous loop.  An output that
        carries ``tokens`` (``FrameOutput``): ONE FRAME STAYS IN FLIGHT
        between calls.  This call composes the frame AFTER it — a row
        that continues takes id -1, its token on the device — and
        dispatches that BEFORE it blocks on the [B] tokens of the frame
        in flight, so the device runs the next frame under harvest,
        evict, the caller's bookkeeping and the next admit and compose.
        (A call that finds nothing in flight dispatches two frames, the
        second composed inside the dispatch span.)  A token still exists
        only once it is on the host: ``generated``, ``tokens`` and
        ``finished`` advance in the harvest of the frame that made it.
        An end by ``max_new_tokens`` is known a frame ahead, so that
        sequence is not sent again and its slot is refilled at once
        (``_retire_sent``); an end by EOS or a preemption finds one row
        already in flight, whose token is dropped (``decode.rows_dropped``)
        and whose K/V lands where no live sequence reads (its own freed
        pages, before anything dispatched later).  No frame is
        dispatched ahead with no row to carry.

        ``frame_seconds`` is the time the harvested frame had the
        pipeline to itself: from its dispatch — or from the harvest of
        the frame before it, where that came later — to its tokens on
        the host; dispatch + wait in the synchronous loop, the step's
        period with a frame in flight.  Events and the request span tree
        cost exactly one ``BUS.enabled`` and one ``TRACER.enabled`` read
        per frame when they are off (test-enforced)."""
        obs = BUS.enabled  # ONE check per frame gates every event
        tr = TRACER.enabled  # ditto for the request span tree
        with phase_span(annotate.DECODE_PHASE, key=self.frame):
            with phase_span(_ADMIT):
                admitted = self._admit(obs, tr)
            with phase_span(_COMPOSE):
                frame = self._compose_frame()
            with phase_span(_DISPATCH):
                flight, self._flight = self._flight, None
                if flight is None:
                    flight = self._dispatch(frame)
                    frame = None
                    if _in_flight(flight.out):
                        frame = self._compose_frame()
                if frame is not None and frame.rows:
                    self._flight = self._dispatch(frame)
                    _FRAMES_AHEAD.inc()
            with phase_span(_WAIT):  # blocks until the frame has run
                out = flight.out
                if getattr(out, "tokens", None) is None:
                    out = np.asarray(out)  # the logits come to the host
                else:
                    np.asarray(out.tokens)  # only the [B] tokens do
            now = time.perf_counter()  # the frame's tokens are on the host
            dt = now - max(flight.t0, self._harvested_t)
            self._harvested_t = now
            self.frame_seconds.append(dt)
            _FRAME_S.observe(dt)
            with phase_span(_HARVEST):
                generated = self._harvest(out, flight.rows, now, tr)
            with phase_span(_EVICT):
                evicted = self._evict(obs, tr, now)
        _FRAMES.inc()
        _ACTIVE_SLOT_FRAMES.inc(len(flight.rows))
        _SLOT_FRAMES.inc(self.max_seqs)
        _LIVE_PAGES.inc(int((flight.lens // self.page_size + 1).sum()))
        _PAGE_SLOTS.inc(self.max_seqs * self.pages_per_seq)
        _TOKENS_GENERATED.inc(generated)
        rec = {
            "frame": self.frame,
            "active": len(flight.rows),
            "admitted": admitted,
            "evicted": evicted,
            "pages_in_use": self.allocator.pages_in_use,
            "queued": len(self.queue),
            "measured_s": dt,
            "predicted_s": self.predicted_step_s,
        }
        if obs:
            BUS.emit("decode.frame", **rec)
        self.frame += 1
        return rec

    def _harvest(self, out, rows, now: float, tr: bool) -> int:
        """Advance every row — (slot, sequence) — of the harvested
        frame by the token its output holds for it (``_host_tokens``);
        returns the tokens generated.  A row whose sequence was closed
        after the frame went (EOS, preemption) is dropped."""
        next_tokens = _host_tokens(out)
        generated = 0
        for i, live in rows:
            live.ahead -= 1
            if live.closed:
                _ROWS_DROPPED.inc()
                continue
            live.cached += 1
            if (self.prefix_sharing and not live.retired
                    and live.cached % self.page_size == 0):
                # a page just filled — publish it so later admissions
                # can claim it (generated tokens included: the stream
                # is deterministic, so equal prefixes mean equal K/V)
                self.allocator.register_prefix(
                    live.tokens, self.page_size, live.pages, live.cached)
            if live.cached < len(live.tokens):
                # still prefilling via decode: the next prompt token is
                # queued.  The prefill span closes when only the LAST
                # prompt token remains (the frame that feeds it is the
                # first decode frame — it produces the first token).
                if live.cached >= len(live.tokens) - 1:
                    if live.prefill_done_t is None:
                        live.prefill_done_t = now
                    if tr:
                        tid = TRACER.trace_of(live.req.rid)
                        if tid is not None and TRACER.end(
                                tid, "prefill") is not None:
                            TRACER.begin(tid, "decode",
                                         parent="request")
                continue
            # the model's prediction extends the sequence
            live.tokens.append(int(next_tokens[i]))
            live.generated += 1
            generated += 1
            if live.first_token_t is None:
                live.first_token_t = now  # TTFT closes here
        return generated

    def run(self, requests: Sequence[DecodeRequest] = (),
            max_frames: int = 10_000) -> Dict[str, List[int]]:
        """Drive frames until every submitted request finished and no
        frame is in flight — every token on the host — (or the frame
        cap trips — a stuck executor must fail loud, not spin).
        Returns rid -> generated token ids."""
        if requests:
            self.submit(requests)
        while self.has_work():
            if self.frame >= max_frames:
                raise RuntimeError(
                    f"decode executor exceeded {max_frames} frames with "
                    f"{len(self.queue)} queued and "
                    f"{sum(s is not None for s in self.slots)} live")
            self.step()
        if BUS.enabled:
            BUS.emit("decode.summary", **self.summary())
        return dict(self.finished)

    # ------------------------------------------------------------------
    @staticmethod
    def _quantile(values, f: float):
        if not values:
            return None
        s = sorted(values)
        return s[min(len(s) - 1, int(f * (len(s) - 1)))]

    def measured_p99(self, window: int = 0) -> Optional[float]:
        """p99 of the measured frame latencies — over the trailing
        ``window`` frames when given (the CONTINUOUS drift signal a
        long-running server feeds the controller), else the whole
        run."""
        times = self.frame_seconds[-window:] if window \
            else self.frame_seconds
        return self._quantile(times, 0.99)

    def measured_request_p99(self, metric: str = "ttft_s",
                             slo: Optional[str] = None,
                             window: int = 0) -> Optional[float]:
        """p99 of a per-request latency metric (``ttft_s``/``tpot_s``/
        ``e2e_s``/``queue_s``), optionally restricted to one SLO class
        and to the trailing ``window`` completions — the per-class
        serve-currency signal a long-running server feeds
        ``TrainingController.observe_p99`` (each class watched at its
        own quantile is the SLO story; p99 here matches the spec's
        default)."""
        recs = [r for r in self.request_records
                if r.get("phase") == "finish"
                and (slo is None or r.get("slo") == slo)
                and r.get(metric) is not None]
        if window:
            recs = recs[-window:]
        cls = self.slo_classes.get(slo) if slo else None
        return self._quantile([r[metric] for r in recs],
                              cls.quantile if cls else 0.99)

    def summary(self) -> dict:
        q = lambda f: self._quantile(self.frame_seconds, f)  # noqa: E731
        out = {
            "frames": self.frame,
            "completed": len(self.finished),
            "admitted": self.total_admitted,
            "evicted": self.total_evicted,
            "expired": self.total_expired,
            "preempted": self.total_preempted,
            "prefill_chunks": self.prefill_chunks,
            "prefill_tokens": self.prefill_tokens,
            "measured_p50_s": q(0.5),
            "measured_p99_s": q(0.99),
            "predicted_step_s": self.predicted_step_s,
            # which decode-attention implementation served the frames
            # (compiled_decode_step names it; None for a bare step_fn)
            "attention_path": getattr(self.step_fn, "attention_path",
                                      None),
        }
        if self.prefix_sharing:
            # prefix-sharing roll-up (keys appear only when the mode is
            # armed, keeping historical summaries byte-identical):
            # cumulative hits/claims/copies plus the private-page
            # complement so ffobs can render shared vs private
            out["prefix_hits"] = self.prefix_hits
            out["shared_pages"] = self.shared_pages
            out["private_pages"] = (
                self.total_admitted * self.pages_per_seq
                - self.shared_pages)
            out["cow_copies"] = self.cow_copies
            out["prefix_tokens"] = self.prefix_tokens
        recs = [r for r in self.request_records
                if r.get("phase") == "finish"]
        if recs:
            # request-level currency (recorded while the bus was
            # armed): TTFT / TPOT / e2e percentiles across completions,
            # with TTFT split into its queue + prefill + first-frame
            # components so the prompt path's cost is attributable per
            # phase
            for key in ("ttft_s", "tpot_s", "e2e_s", "queue_s",
                        "prefill_s", "first_frame_s"):
                vals = [r[key] for r in recs if r.get(key) is not None]
                out[f"{key[:-2]}_p50_s"] = self._quantile(vals, 0.5)
                out[f"{key[:-2]}_p99_s"] = self._quantile(vals, 0.99)
            out["requests_recorded"] = len(recs)
            by_class: Dict[str, list] = {}
            for r in recs:
                by_class.setdefault(r.get("slo", "standard"),
                                    []).append(r)
            if self.slo_classes or len(by_class) > 1:
                out["slo_classes"] = {
                    name: {
                        "completed": len(rs),
                        "ttft_p99_s": self._quantile(
                            [r["ttft_s"] for r in rs
                             if r.get("ttft_s") is not None], 0.99),
                        "e2e_p99_s": self._quantile(
                            [r["e2e_s"] for r in rs
                             if r.get("e2e_s") is not None], 0.99),
                    }
                    for name, rs in sorted(by_class.items())
                }
        return out

    def decode_drift_report(self, threshold: float = 0.5,
                            window: int = 0):
        """Predicted-vs-measured DECODE drift: the search's p99 step
        prediction against the measured frame-latency p99 — the decode
        phase of the DriftReport family (obs/drift.py).  ``window``
        restricts the measured side to the trailing frames, turning a
        one-shot report into the continuous serve-currency signal
        (feed ``report.ratio`` — or the executor itself — to
        ``TrainingController.observe_p99`` to make it a re-search
        trigger).  None when either side is missing.  Emitted as a
        ``drift.report`` event when the bus is armed, like model.fit's
        training-side report."""
        from flexflow_tpu.obs.drift import build_drift_report

        measured = self.measured_p99(window)
        if not self.predicted_step_s or not measured:
            return None
        report = build_drift_report(
            {"total_s": self.predicted_step_s},
            measured, threshold=threshold)
        if report is not None:
            report.phases["decode"] = {
                "predicted_s": self.predicted_step_s,
                "measured_s": measured,
                "ratio": report.ratio,
            }
            if BUS.enabled:
                BUS.emit("drift.report", predicted_s=report.predicted_s,
                         measured_s=report.measured_s, ratio=report.ratio,
                         stale=report.stale, phase="decode")
        return report


class _LiveState:
    """``step.state``: a read-only window on the live state the frame
    takes — the model's state dict and the step's last tokens — under
    the historical key (``step.state["state"]``)."""

    def __init__(self, live_state):
        self._live_state = live_state

    def __getitem__(self, key):
        if key != "state":
            raise KeyError(key)
        return self._live_state()


_KV_LEAVES = ("k_cache", "v_cache", "k_scale", "v_scale")
# the state leaf that carries a frame's chosen tokens [B] into the next
# frame (``compiled_decode_step``): not an op's state, so no "/" in it
_LAST_TOKENS = "last_tokens"
# the device counters' totals after a frame, [n] int32: handed out beside
# the state (never taken back in), so the host's copy outlives the next
# call's donation
_OBS_TOTALS = "obs_totals"


def _tree_bytes(tree) -> int:
    import jax

    return sum(leaf.nbytes for leaf in jax.tree.leaves(tree))


def compiled_decode_step(model, prefill_chunk: int = 0) -> Callable:
    """A ``step_fn`` over a COMPILED decode model: one jitted forward
    per frame over the model's state dict (the caches are model state —
    compiler/lowering.py init_params placed them under the strategy's
    view).

    THE TOKEN IS CHOSEN ON THE DEVICE.  ``step(ids, page_table,
    seq_lens)`` returns a ``FrameOutput``: the frame's float32 logits
    and their ``argmax`` over the vocabulary as [B] int32 (the first
    index on a tie, as ``np.argmax``), both still on the device, the 64
    bytes of tokens already on their way to the host.  The tokens also
    stay with the step, as one more leaf of the state the next frame
    takes (``last_tokens``; the model's own state dict never holds it):
    a row whose id is -1 is fed the token the step's LAST call chose
    for it, inside the frame program — no program is added, and a
    continuing row's id never visits the host.  That is what lets
    ``ContinuousBatchingExecutor`` keep one frame in flight (its
    docstring has when a row's token is dropped, and that an end by EOS
    costs a slot one frame).

    The state is DONATED into every program here, as the train step
    donates its own: the scatter writes the KV pool where it sits and
    the program holds no second pool.  So the live pool has ONE owner,
    ``model.state`` — each call reads it there and puts the program's
    output back, the arrays it consumed are dead, and any number of
    steps built over one model (and ``model.state`` read after serving)
    see the same live pool.  ``step.state["state"]`` is that dict.

    ``prefill_chunk > 0`` additionally builds the chunked prefill
    writer over the SAME graph, params and state (runtime/prefill.py —
    one parameter set by construction, the cache scatter lands in the
    placed state arrays), attached as
    ``step.prefill(ids [1,n·C], positions [1,n·C], page_table [1,P])``
    for the executor's ``prefill_fn``: a run of n chunks
    (``run_chunked_prefill``'s) in ONE call of one program, which loops
    over them on the device.

    The weights are NOT ``model.params``: ``step.weights`` is the
    served tree — every op's ``Operator.serving_weights`` of its own
    leaves, keyed as ``model.params`` is: the matmuls' kernels in the
    compute dtype, the attention projections fused [E, H·D] / [H·D, E];
    embedding tables, norms and biases the master's own arrays —
    derived by ONE jitted program when the step is built (each leaf
    laid out over the mesh as its master is), and again on the first
    call after ``model.params`` became another tree (a restored
    checkpoint, a weight swap; compared by identity).  ``model.params``
    stays what checkpoints, ``fit`` and a float32 reference read.
    Counter ``decode.weight_prepares`` counts the derivations, gauges
    ``decode.weight_bytes`` / ``decode.weight_bytes_master`` the two
    trees' bytes.

    ``step.frame_fn`` is the jitted frame itself (``(weights, state,
    [ids, page_table, seq_lens]) -> ((logits, tokens), state)``;
    ``step.state["state"]`` is the state the step hands it: the
    model's, and ``last_tokens``; over a state without that leaf every
    id is taken as given), ``step.chunk_fn`` the jitted
    prefill program (``(weights, state, ids, positions, page_table,
    n_chunks)``: the first ``n_chunks`` C-slices of ids and positions
    [B, L], in order — ``step.prefill`` pads every run to L = the
    context in whole chunks);
    either takes ``step.weights`` — the program ``step()`` and
    ``step.prefill()`` run — or ``model.params``, whose fp32 [E, H, D]
    leaves it then converts and fuses inside the call (the same
    arithmetic, bit for bit).  ``step.attention_path`` names
    what its decode attention lowered to — ``"pallas"`` or ``"xla"``,
    by ``DecodeAttentionOp.attention_path``'s rule."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.core.optype import OperatorType

    compiled = model.compiled
    # the chosen tokens, whole on every device of the mesh: a frame
    # takes them back laid out as it handed them out (one program)
    whole = jax.sharding.NamedSharding(compiled.mesh,
                                       jax.sharding.PartitionSpec())

    # the ops' device counters (``*/obs/*`` integers of the state,
    # obs/device_counters.py): the frame hands their totals out as ONE
    # fresh vector beside the state it is donated, so the host reads a
    # copy that the next call does not consume
    obs_keys = sorted(
        k for k, v in model.state.items()
        if device_counters.MARK in k and jnp.issubdtype(v.dtype, jnp.integer))

    def frame(p, s, ins):
        """``(logits, tokens), state``: the frame's logits and the
        greedy token of each row.  Where the state carries the tokens
        of the frame before (``_LAST_TOKENS``), a row whose id is -1 is
        fed that token — it never left the device — and the state
        handed back carries this frame's, and (``_OBS_TOTALS``) the
        device counters' totals after this frame."""
        ids, page_table, seq_lens = ins
        s = dict(s)
        last = s.pop(_LAST_TOKENS, None)
        if last is not None:
            ids = jnp.where(ids < 0, last[:, None], ids)
        logits, s = compiled.apply(p, s, [ids, page_table, seq_lens],
                                   None, False)
        tokens = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
        if compiled._multi_device:
            tokens = jax.lax.with_sharding_constraint(tokens, whole)
        if last is not None:
            s[_LAST_TOKENS] = tokens
            if obs_keys:
                s[_OBS_TOTALS] = jnp.stack(
                    [s[k].astype(jnp.int32) for k in obs_keys])
        return (logits, tokens), s

    fn = jax.jit(frame, donate_argnums=(1,))
    owners = {n.op.name: n.op for n in model.graph.topo_order()}

    def serve_all(params):
        return {name: owners[name].serving_weights(
                    ws, compiled.compute_dtype)
                for name, ws in params.items()}

    # the leaves serving changes (a matmul's operands); every other —
    # embedding tables, norms, biases — is served as the master's own
    # array.  One program derives them all, each laid out over the mesh
    # as the compiler carries its master's sharding through
    avals = jax.eval_shape(serve_all, model.params)
    moved = [(name, w) for name, ws in model.params.items() for w in ws
             if (avals[name][w].shape, avals[name][w].dtype)
             != (ws[w].shape, ws[w].dtype)]

    @jax.jit
    def derive(params):
        served = serve_all(params)
        return {(name, w): served[name][w] for name, w in moved}

    held = {"master": None}  # the tree ``step.weights`` was derived from

    def weights():
        """The served tree of ``model.params`` as it is NOW: derived
        again when the model was given another tree (a restored
        checkpoint, a weight swap)."""
        master = model.params
        if held["master"] is not master:
            made = derive(master)
            step.weights = {
                name: {w: made.get((name, w), leaf)
                       for w, leaf in ws.items()}
                for name, ws in master.items()}
            held["master"] = master
            _WEIGHT_PREPARES.inc()
            _WEIGHT_BYTES.set(_tree_bytes(step.weights))
            _WEIGHT_BYTES_MASTER.set(_tree_bytes(master))
        return step.weights

    decode_ops = [n.op for n in model.graph.topo_order()
                  if n.op.op_type == OperatorType.DECODE_ATTENTION]
    paths = {op.attention_path(compiled._multi_device) for op in decode_ops}
    # window layers: (window, ring pages a slot) of each; a ring is
    # addressed by the SLOT a table row belongs to, so the tables must be
    # slot-aligned (``ContinuousBatchingExecutor.slot_aligned``, which
    # refuses to be built over this step otherwise)
    windows = [(op.attrs["window"], op.attrs["ring_pages"])
               for op in decode_ops if op.attrs.get("window")]
    page_size = decode_ops[0].attrs["page_size"] if decode_ops else 1
    for w, r in windows:
        if r * page_size < w + prefill_chunk + page_size:
            raise ValueError(
                f"a window layer's ring of {r} pages of {page_size} holds "
                f"a window of {w} and a chunk of "
                f"{r * page_size - w - page_size} tokens; prefill_chunk "
                f"{prefill_chunk} would overwrite pages its own queries "
                f"still see — build the model for this chunk")
    def pool_bytes(ops):
        return sum(v.nbytes for op in ops for k, v in model.state.items()
                   if k.startswith(op.name + "/")
                   and k.rsplit("/", 1)[-1] in _KV_LEAVES)

    _KV_BYTES_WINDOW.set(pool_bytes(
        op for op in decode_ops if op.attrs.get("ring_pages")))
    _KV_BYTES_GLOBAL.set(pool_bytes(
        op for op in decode_ops if not op.attrs.get("ring_pages")))

    def count_walk(seq_lens):
        """``decode.kv_pages_walked`` / ``_live`` of one frame, from its
        lengths on the host: every row attends its fresh token too, an
        idle row walks one page (as ``decode.live_pages`` counts)."""
        lens = np.asarray(seq_lens, np.int64) + 1
        live = -(-lens // page_size)
        walked = (len(decode_ops) - len(windows)) * int(live.sum())
        for w, _ in windows:
            walked += int((live - np.maximum(lens - w, 0) // page_size).sum())
        _KV_PAGES_WALKED.inc(walked)
        _KV_PAGES_LIVE.inc(len(decode_ops) * int(live.sum()))

    cold = {"decode_frame", "prefill_chunk"}  # programs never called yet

    def call(program, jitted, *args):
        """``jitted(*args)`` and nothing else under a span of its own:
        ``ff.phase/call.<program>`` — flattening the arguments, the
        enqueue, any wait for room in the device's queue; what the span
        around it holds besides is the Python of ``step`` / ``prefill``.
        The FIRST call — trace, lower and compile or load from the
        cache — is ``ff.phase/setup.first_call.<program>`` instead."""
        if program in cold:
            cold.discard(program)
            tag = annotate.FIRST_CALL_PHASE + program
        else:
            tag = annotate.CALL_PHASE + program
        with phase_span(tag):
            return jitted(*args)

    # the tokens the last frame chose, as the next frame's state carries
    # them (donated with it: ``FrameOutput.tokens`` is another buffer)
    slots = compiled._input_nodes[0].op.output_shapes[0].sizes[0]
    last = {"tokens": jax.device_put(np.zeros((slots,), np.int32), whole)}

    def live_state():
        return {**model.state, _LAST_TOKENS: last["tokens"]}

    # the device counters' totals of the last three frames, each on its
    # way to the host with its frame's tokens; the oldest has arrived (a
    # frame's tokens were waited for before the call after next is made),
    # so reading it costs no frame a sync
    sent = collections.deque(maxlen=3)
    calls = itertools.count(1)

    def publish_obs(block: bool = False) -> None:
        """Put the device counters into ``METRICS``: from the oldest of
        the last three totals vectors, or — ``block`` — from the live
        state itself, after everything dispatched has run (the end of a
        run, a test)."""
        if block:
            values = {k: model.state[k] for k in obs_keys}
        elif len(sent) == sent.maxlen:
            values = dict(zip(obs_keys, np.asarray(sent[0])))
        else:
            return
        device_counters.publish(values, model._obs_seen)

    def step(ids, page_table, seq_lens):
        (logits, tokens), state = call(
            "decode_frame", fn,
            weights(), live_state(), [ids, page_table, seq_lens])
        last["tokens"] = state.pop(_LAST_TOKENS)
        obs = state.pop(_OBS_TOTALS, None)
        model.state = state
        tokens.copy_to_host_async()  # 64 bytes, on their way at once
        if windows:
            count_walk(seq_lens)
        if obs is not None:
            obs.copy_to_host_async()
            sent.append(obs)
            if next(calls) % _OBS_EVERY == 0:
                publish_obs()
        return FrameOutput(logits, tokens)

    weights()
    step.state = _LiveState(live_state)  # tests inspect the live cache
    step.frame_fn = fn
    step.attention_path = "+".join(sorted(paths)) or None
    step.publish_obs = publish_obs
    step.needs_slot_aligned = bool(windows)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def copy_kv_page(state, src, dst):
        return {key: (val.at[dst].set(val[src])
                      if key.rsplit("/", 1)[-1] in _KV_LEAVES else val)
                for key, val in state.items()}

    def copy_page(src: int, dst: int) -> None:
        """CoW page copy for the prefix-sharing executor
        (``copy_page_fn``): duplicate page ``src`` of every layer's
        paged KV state — k/v pools and, under an int8 pool, their
        per-slot scales — into page ``dst``, which the divergent
        sequence then owns.  One program whatever the pages (they are
        traced scalars), the state donated: one page a leaf moves."""
        model.state = copy_kv_page(model.state, np.int32(src),
                                   np.int32(dst))

    step.copy_page = copy_page
    if prefill_chunk:
        from flexflow_tpu.runtime.prefill import build_run_forward

        pf = jax.jit(build_run_forward(model.graph, compiled.compute_dtype,
                                       prefill_chunk),
                     donate_argnums=(1,))
        # every run is padded to ONE width, the context in whole chunks:
        # one program for every prompt length
        cap = decode_ops[0].max_seq_len
        width = -(-cap // prefill_chunk) * prefill_chunk

        # the layers whose chunks walk alike, as {kind: (one of them, how
        # many)}: the host prices a chunk's walk once a kind, not a layer
        walks = {}
        for op in decode_ops:
            kind = (type(op), op.chunk_block_pages, op.attrs.get("window", 0))
            walks[kind] = (op, walks.get(kind, (op, 0))[1] + 1)
        table_keys = sum(op.max_seq_len for op in decode_ops)

        def prefill(ids, positions, page_table):
            ids = np.asarray(ids, np.int32)
            positions = np.asarray(positions, np.int32)
            rows, sent = ids.shape
            n = sent // prefill_chunk
            assert n * prefill_chunk == sent and 0 < sent <= width, (
                f"a run of {sent} positions is not 1 to "
                f"{width // prefill_chunk} chunks of {prefill_chunk}")
            run_ids = np.zeros((rows, width), np.int32)
            run_pos = np.full((rows, width), cap - 1, np.int32)
            run_ids[:, :sent], run_pos[:, :sent] = ids, positions
            model.state = call("prefill_chunk", pf, weights(), model.state,
                               run_ids, run_pos, page_table, np.int32(n))
            for c0 in range(0, sent, prefill_chunk):
                _PREFILL_KEYS_WALKED.inc(sum(
                    k * op.chunk_keys_walked(
                        positions[:, c0:c0 + prefill_chunk])
                    for op, k in walks.values()))
            _PREFILL_KEYS_TABLE.inc(table_keys * rows * n)

        prefill.needs_slot_aligned = bool(windows)
        step.prefill = prefill
        step.chunk_fn = pf
    return step
