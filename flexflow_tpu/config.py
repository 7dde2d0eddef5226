"""Runtime configuration.

The TPU-native analogue of FFConfig (reference: include/flexflow/config.h:92-157,
src/runtime/model.cc:3371 parse_args): every knob of the training run,
the search, and the cost model, parseable from argv with the reference's
flag spellings so existing launch scripts translate directly.
"""

from __future__ import annotations

import argparse
from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple

from flexflow_tpu.core.machine import MachineSpec


def parse_slo_classes(value) -> Tuple[Dict, ...]:
    """Normalize the SLO-class table: the CLI spelling
    ``"name:priority:deadline_frames[:quantile[:weight]][,...]"`` or an
    iterable of dicts -> a tuple of ``{"name", "priority",
    "deadline_frames", "quantile", "weight"}`` dicts
    (runtime/decode.py ``SLOClass`` consumes them; the winning
    disaggregation/fleet persists them in ``__meta__``).  ``weight`` is
    the class's RELATIVE arrival rate (default 1 = classes arrive
    equally often) — the fleet search prices routing against it, so an
    interactive trickle and a batch flood are different placement
    questions."""
    if isinstance(value, str):
        classes = []
        for part in value.split(","):
            fields = part.split(":")
            if len(fields) not in (3, 4, 5):
                raise ValueError(
                    f"SLO class {part!r} must be "
                    f"name:priority:deadline_frames[:quantile[:weight]]")
            classes.append({
                "name": fields[0],
                "priority": int(fields[1]),
                "deadline_frames": int(fields[2]),
                "quantile": float(fields[3]) if len(fields) >= 4 else 0.99,
                "weight": float(fields[4]) if len(fields) == 5 else 1.0,
            })
        value = classes
    out = []
    seen = set()
    for c in value:
        c = {"name": str(c["name"]), "priority": int(c["priority"]),
             "deadline_frames": int(c.get("deadline_frames", 0)),
             "quantile": float(c.get("quantile", 0.99)),
             "weight": float(c.get("weight", 1.0))}
        if not c["name"] or c["name"] in seen:
            raise ValueError(
                f"SLO class names must be unique and non-empty "
                f"(got {c['name']!r})")
        if c["deadline_frames"] < 0 or not (0.0 < c["quantile"] < 1.0):
            raise ValueError(
                f"SLO class {c['name']!r}: deadline_frames must be >= 0 "
                f"and quantile in (0, 1)")
        if not c["weight"] > 0.0:
            raise ValueError(
                f"SLO class {c['name']!r}: weight must be > 0, got "
                f"{c['weight']}")
        seen.add(c["name"])
        out.append(c)
    return tuple(out)


def parse_slice_levels(value) -> Tuple[Tuple[int, float, float], ...]:
    """Normalize a slice-level hierarchy: the CLI spelling
    ``"span:bw:lat[,span:bw:lat...]"`` or an iterable of (span,
    bandwidth, latency) triples -> MachineSpec.slice_levels tuples.
    Structural validation (ascending aligned spans) stays in
    MachineSpec.topology_levels(), the one reader."""
    if isinstance(value, str):
        levels = []
        for part in value.split(","):
            fields = part.split(":")
            if len(fields) != 3:
                raise ValueError(
                    f"slice level {part!r} must be span:bandwidth:latency")
            levels.append(
                (int(fields[0]), float(fields[1]), float(fields[2])))
        return tuple(levels)
    return tuple(
        (int(span), float(bw), float(lat)) for span, bw, lat in value)


@dataclass
class IterationConfig:
    """Per-iteration knobs threaded into forward/backward
    (reference: config.h:159-164 FFIterationConfig.seq_length)."""

    seq_length: int = -1


@dataclass
class FFConfig:
    # training
    epochs: int = 1
    batch_size: int = 64
    learning_rate: float = 0.01
    weight_decay: float = 0.0001
    # machine
    num_devices: int = 0  # 0 = all visible jax devices
    machine_spec: Optional[MachineSpec] = None
    machine_model_file: Optional[str] = None
    slice_levels: Optional[object] = None  # multi-slice link hierarchy
    # above ICI (MachineSpec.slice_levels, PR 6) without writing a
    # machine file: a tuple of (span, bandwidth, latency) tuples, or
    # the CLI spelling "span:bw:lat[,span:bw:lat...]"
    # (--slice-levels).  Applied on top of whichever machine_spec /
    # machine_model_file resolves, the way --machine-model-file itself
    # layers over the default spec.
    # parallelization search (reference: config.h:116-157; the osdi22ae
    # scripts run with budgets 10-30)
    search_budget: int = 16
    search_alpha: float = 1.05
    only_data_parallel: bool = False
    enable_parameter_parallel: bool = True
    enable_attribute_parallel: bool = True
    enable_inplace_optimizations: bool = True
    search_num_devices: int = 0  # override devices for search (search a big
    # strategy on a small machine, reference: graph.cc:1535-1540)
    base_optimize_threshold: int = 10
    search_timeout_s: float = 45.0  # wall-clock bound on the joint
    # search; <=0 disables.  The reference bounds work via --budget
    # alone (substitution.cc:2007); a hard deadline guarantees compile
    # latency at any model scale
    enable_pipeline_search: bool = True  # compile's joint search also
    # costs pp in {2,4,8} pipelined candidates for stacked-block graphs
    # (search/pipeline_search.py) and lowers a winner automatically —
    # the capability the reference stubs as OP_PIPELINE (ffconst.h:148)
    enable_placement_search: bool = True  # compile also costs 2-block
    # inter-op placed candidates (search/placement_search.py) and lowers
    # a margin-beating winner via the placed executor — the reference's
    # VERTICAL resource splits + mapper placement (graph.cc:161-295,
    # mapper.cc:371-475)
    placement_search_max_nodes: int = 80  # placement cut enumeration is
    # quadratic-ish in graph size; larger graphs skip the pass
    search_improvement_margin: float = 0.03  # a searched strategy is
    # accepted only when its simulated win over plain data parallelism
    # exceeds this fraction — the simulator has finite fidelity, and a
    # sub-margin "win" is noise that execution routinely loses to GSPMD
    # resharding (measured: a 1.4% predicted BERT win executed 7-12%
    # SLOWER than DP on the 8-device host mesh).  Within the margin the
    # search returns uniform DP, whose lowering has zero resharding
    # boundaries.
    substitution_json: Optional[str] = None
    calibration_file: Optional[str] = None  # persisted measured
    # per-(op, view) costs (search/calibration.py); the search loads it
    # when present (reference: ProfilingRecord, simulator.cc:515-554)
    calibrate: bool = False  # probe this graph's (op, view) costs on
    # the live backend at compile time and rank with them — the
    # reference's default behavior (it measures lazily mid-search,
    # simulator.cc:515; model.cu:38-74).  Off by default here because
    # probing costs real wall time per compile; combined with
    # calibration_file the probes persist and later compiles are free
    calibration_budget_s: float = 60.0  # wall bound on compile-time probes
    export_strategy_file: Optional[str] = None
    import_strategy_file: Optional[str] = None
    import_strategy_partial: bool = False  # best-effort strategy import
    # (--import-strategy-partial): downgrade the provenance checks
    # (digest/coverage, STR2xx) to warnings and apply the views whose op
    # names match — the historical behavior, now an explicit opt-in
    export_strategy_computation_graph_file: Optional[str] = None
    export_strategy_task_graph_file: Optional[str] = None  # simulated
    # schedule dot export (reference: config.h:142, simulator.cc:1008)
    objective: str = "train"  # "train" | "serve" — what the strategy
    # search optimizes.  "train" (default) ranks by mean step time
    # (throughput), bit-identical to history.  "serve" ranks a DECODE
    # graph (models/decode.py, ops/decode_attention.py) by simulated
    # p99 decode-step latency over a ragged-batch arrival model
    # (search/serving.py): batch splits pay the max-shard imbalance of
    # ragged KV loads, head splits (decode TP) don't — a different
    # Pareto point than throughput.  Per-device KV residency at full
    # page-pool occupancy enters the memory check either way, so
    # HBM-infeasible strategies are rejected during search, not at OOM.
    serve_p99_budget_ms: float = 0.0  # declared p99 SLO for the serve
    # objective (--serve-p99-budget-ms): recorded in __meta__.serving
    # and linted (SHD163 warns when the predicted p99 exceeds it);
    # 0 = no declared budget (rank-only)
    serve_disaggregation: str = "off"  # "off" | "search" — under
    # objective="serve", compile() additionally searches a
    # PREFILL/DECODE DISAGGREGATION (search/disaggregation.py): the
    # prompt graph and the decode graph placed on disjoint submeshes as
    # a two-block placement, the KV-page handoff priced as a
    # cross-block transfer and the serve load split per phase
    # (prefill = compute-bound arrivals, decode = p99 token load); a
    # margin-beating winner is lint-gated (SHD164/165) and persists as
    # __meta__.disaggregation (fflint STR211).  "off" (default) is
    # byte-identical to history.
    prefill_chunk: int = 32  # chunk size of the batched prefill lane
    # (runtime/prefill.py, --prefill-chunk): the prompt's causal
    # forward runs once per this many tokens and scatters K/V straight
    # into the page pool, instead of one decode frame per prompt token;
    # recorded in __meta__.disaggregation.  Must be >= 1.
    serve_prompt_tokens_mean: int = 0  # phase-split arrival model
    # (ServingSpec.prefill_tokens_per_frame): mean prompt length of the
    # arrival stream; 0 derives max_seq_len // 2
    serve_decode_tokens_mean: int = 0  # mean generated tokens per
    # request (slot turnover rate); 0 derives max_seq_len // 4
    serve_fleet: str = "off"  # "off" | "search" — under
    # objective="serve", compile() additionally searches a SERVING
    # FLEET (search/fleet.py): N replica blocks on disjoint submeshes,
    # each with its own full rewriting search at its width (and its own
    # intra-replica prefill/decode split), priced together with
    # per-SLO-class routing fractions in the per-class p99 currency; a
    # margin-beating fleet is lint-gated (SHD166/167) and persists as
    # __meta__.fleet (fflint STR212).  "off" (default) is byte-identical
    # to history.
    serve_fleet_max_replicas: int = 4  # fleet search bound
    # (--serve-fleet-max-replicas): the partition enumeration caps at
    # this many replica blocks.  Must be >= 1.
    serve_fleet_offered_load: float = 0.85  # steady-state offered load
    # of the whole deployment, in frames (1.0 = the arrival stream
    # exactly fills one full decode frame per frame time): sets the
    # queueing utilization the per-class p99 pricing charges each
    # replica.  The controller's elastic re-search scales it by the
    # measured/predicted drift ratio (observe_fleet).
    serve_slo_classes: Optional[object] = None  # request SLO classes
    # (--serve-slo-classes "name:priority:deadline_frames[:quantile],
    # ..."): priority admission / deadline expiry / preemption on the
    # executor's page allocator (runtime/decode.py SLOClass), per-class
    # p99 windows, persisted with the disaggregation meta
    kv_precision: str = "off"  # KV page-pool dtype lane
    # (ops/decode_attention.py kv_dtype, --kv-precision): "off"
    # (default) never touches the lane — cost-cache keys, signatures
    # and the lowered program stay byte-identical to history.  "fp32"/
    # "bf16"/"int8" pin the pool dtype (int8 adds per-(page, slot)
    # fp32 scales, dequant inside the ragged paged-attention kernel's
    # page loop); "search" makes the dtype a searched lane under
    # objective="serve" — each candidate dtype is priced through the
    # decode op's cache-stream + quantize-overhead terms (the same
    # EQuARX discipline as sync_precision) and the winner persists as
    # __meta__.kv behind the digest gate (SHD168/169 lint-gated,
    # fflint STR213).
    serve_shared_prefix_pages: int = 0  # radix prefix sharing
    # (runtime/decode.py PageAllocator, --serve-shared-prefix-pages):
    # declared number of page-pool pages per sequence expected to be
    # CLAIMED from the shared prefix trie rather than privately
    # allocated (e.g. a fleet-wide system prompt of N*page_size
    # tokens).  Enters ServingSpec.shared_residency_factor so SHD161
    # HBM residency and kv_residency_bytes price SHARED residency —
    # the search sees the multiplied effective batch.  0 (default) =
    # no sharing assumed, bit-identical to history.  Must be
    # < pages_per_seq of the decode graph (linted, SHD168).
    comp_mode: str = "training"  # "training" | "inference" — set by
    # compile(comp_mode=...); inference searches rank strategies by
    # forward latency with no weight sync (reference:
    # COMP_MODE_INFERENCE, config.h:47-50) and fit() refuses to run
    # numerics
    compute_dtype: str = "bfloat16"  # matmul dtype on TPU
    param_dtype: str = "float32"
    # execution
    profiling: bool = False
    perform_fusion: bool = True
    grad_accum_steps: int = 1  # >1: each optimizer step processes the
    # batch as this many microbatches inside a lax.scan, averaging
    # grads — full effective batch at batch/N activation memory
    # (reference has no analogue; with remat, the second memory lever)
    trace_steps: int = 1  # >1: fit() runs this many optimizer steps per
    # compiled call (lax.scan over stacked batches) — the XLA-native
    # analogue of the reference's Legion iteration tracing
    # (flexflow_cffi.py:1867-1874), amortizing per-step dispatch
    remat: bool = False  # rematerialize activations in backward
    # (jax.checkpoint) — trades FLOPs for HBM; the reference has no
    # equivalent (Legion keeps all activations resident)
    sync_precision: str = "fp32"  # gradient-sync wire precision
    # (comm/quantized.py, EQuARX arXiv:2506.17615): "fp32" keeps the
    # historical bit-exact psum; "bf16"/"int8" request compressed
    # collectives for every weight group the gradient-safety heuristic
    # admits (search/sync_precision.py); "search" makes the precision a
    # PER-WEIGHT-GROUP dimension of the strategy search — the cost
    # model prices each group's sync at its cheapest admissible
    # precision (wire bytes shrink, quantize overhead added) and the
    # chosen map is executed by the lowering's _sync_grads
    sync_schedule: str = "off"  # gradient-sync SCHEDULE
    # (search/sync_schedule.py): "search" partitions the synced weight
    # groups into issue-ordered buckets (reverse-topological, coalesced
    # to amortize collective latency, per-bucket precision composing
    # with sync_precision), priced with the simulator's exposed-comm
    # semantics and executed by comm/bucketed.py — adopted only when it
    # beats the monolithic post-backward sync.  "off" (default) keeps
    # the historical single post-backward sync (fp32 bit-exact).
    sync_bucket_bytes: int = 0  # pin the schedule search's coalescing
    # floor (fused fp32 payload bytes per bucket); 0 sweeps the
    # DEFAULT_BUCKET_BYTES thresholds plus adaptive fractions of the
    # model's total sync bytes
    sync_ef: str = "off"  # error-feedback residuals on int8 gradient
    # sync (comm.quantized_allreduce_ef, EF-SGD): "auto" upgrades every
    # int8 group the precision search picks to "int8_ef" — each device
    # re-injects its local quantization error next step, carried as
    # persistent training-loop state (the lowering threads the residual
    # through the model-state dict), so compression error stops
    # accumulating across steps.  The residual add's (real, small) HBM
    # overhead is priced into the choice; the fidelity win is the
    # point — the cost currency cannot see it, so this is a policy
    # gate, not a cost comparison.  "off" (default) keeps the plain
    # int8 wire bit-identical to history.  Deliberately independent of
    # co_search: EF shifts the pricing currency (its overhead is
    # priced), so folding it into the joint-vs-sequential comparison
    # would conflate two effects.
    co_search: bool = False  # joint strategy x comm-plan co-search
    # (search/comm_plan.py): candidate strategies inside
    # optimize_strategy — substitution proposals, DP re-validations,
    # chain-segment solves — are priced with their BEST comm plan
    # (sync schedule + per-group wire precision + staged reduction
    # plans + per-group optimizer-state sharding) through the
    # simulator's exposed-comm semantics, instead of choosing the
    # strategy first under the legacy per-node overlap credit and
    # fitting the comm plan afterwards.  A comm-plan memo keyed by the
    # strategy's synced-group signature keeps the inner loop cheap
    # (most substitutions do not change the synced-group set, so the
    # plan is served, not re-searched).  Enabling this auto-enables
    # sync_schedule="search".  False (the default) keeps the
    # sequential strategy→plan pipeline bit-identical to history.
    # observability (flexflow_tpu/obs): unified telemetry
    obs_log_file: Optional[str] = None  # JSONL structured-event sink
    # (search-decision tracing, strategy tables, drift reports); also
    # enabled process-wide via FLEXFLOW_TPU_OBS=<path>.  None (the
    # default) keeps every emit to a single boolean check — near-zero
    # overhead off.
    obs_trace_file: Optional[str] = None  # compile() writes the
    # PREDICTED task timeline here as Chrome-trace JSON (Perfetto-
    # loadable), the artifact to view next to the real device_trace
    device_trace_dir: Optional[str] = None  # fit() captures a REAL
    # jax.profiler device trace of the post-compile steps into this
    # logdir, with the lowered step's sync buckets bracketed by
    # stable-lane-id markers (obs/annotate.py) and host phases
    # annotated; after the run the capture is ingested and tag-matched
    # against the predicted lanes (obs/trace_ingest.py) into
    # model.lane_drift_report, filling the per-bucket DriftReport
    # measured fields.  None (default): no capture, no markers — the
    # lowered program is byte-identical to history.
    drift_threshold: float = 0.5  # |measured/predicted - 1| above which
    # the DriftReport flags the prediction stale (and, when a measured
    # calibration table was consulted, the TABLE as stale)
    cost_cache_file: Optional[str] = None  # persistent cost cache
    # (search/cost_cache.py): per-(op, view) cost rows + search results
    # keyed by node digest x machine view x calibration signature,
    # invalidated wholesale when the signature moves.  None falls back
    # to $FLEXFLOW_TPU_COST_CACHE (path; "0"/empty disables); empty
    # string "" disables outright (--no-cost-cache)
    verify: bool = False  # static-analysis verification
    # (flexflow_tpu/analysis, --verify, env FLEXFLOW_TPU_VERIFY=1):
    # run the graph-invariant checker after EVERY GraphXfer.apply and
    # check the compile-time graph before lowering.  The strategy/
    # sharding legality lint in optimize_strategy is always on; this
    # flag adds the per-rewrite structural proof.
    zero_dp_shard: bool = False  # ZeRO-1 / weight-update sharding
    # (arXiv:2004.13336): shard optimizer state (and the update
    # compute) of replicated weights over the mesh axes they are
    # replicated on.  Grad psum becomes reduce-scatter + all-gather of
    # the update (same ring bytes), optimizer memory and update FLOPs
    # drop by the replication factor.  Beyond the reference (its PS
    # mode reduces on ONE owner device, optimizer.cc:90-155 — this
    # spreads the update over all of them)
    seed: int = 0
    iteration: IterationConfig = field(default_factory=IterationConfig)

    def __post_init__(self):
        if self.sync_precision not in ("fp32", "bf16", "int8", "search"):
            raise ValueError(
                f"sync_precision must be fp32|bf16|int8|search, got "
                f"{self.sync_precision!r}"
            )
        if self.sync_schedule not in ("off", "search"):
            raise ValueError(
                f"sync_schedule must be off|search, got "
                f"{self.sync_schedule!r}"
            )
        if self.sync_ef not in ("off", "auto"):
            raise ValueError(
                f"sync_ef must be off|auto, got {self.sync_ef!r}"
            )
        if self.objective not in ("train", "serve"):
            raise ValueError(
                f"objective must be train|serve, got {self.objective!r}"
            )
        if self.serve_disaggregation not in ("off", "search"):
            raise ValueError(
                f"serve_disaggregation must be off|search, got "
                f"{self.serve_disaggregation!r}"
            )
        if self.prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {self.prefill_chunk}"
            )
        if self.serve_fleet not in ("off", "search"):
            raise ValueError(
                f"serve_fleet must be off|search, got "
                f"{self.serve_fleet!r}"
            )
        if self.serve_fleet_max_replicas < 1:
            raise ValueError(
                f"serve_fleet_max_replicas must be >= 1, got "
                f"{self.serve_fleet_max_replicas}"
            )
        if not (0.0 < self.serve_fleet_offered_load <= 4.0):
            raise ValueError(
                f"serve_fleet_offered_load must be in (0, 4], got "
                f"{self.serve_fleet_offered_load}"
            )
        if self.serve_slo_classes is not None:
            self.serve_slo_classes = parse_slo_classes(
                self.serve_slo_classes)
        if self.kv_precision not in ("off", "fp32", "bf16", "int8",
                                     "search"):
            raise ValueError(
                f"kv_precision must be off|fp32|bf16|int8|search, got "
                f"{self.kv_precision!r}"
            )
        if self.serve_shared_prefix_pages < 0:
            raise ValueError(
                f"serve_shared_prefix_pages must be >= 0, got "
                f"{self.serve_shared_prefix_pages}"
            )
        if self.objective == "serve" and self.co_search:
            # the joint pricer's exposed-comm currency is a TRAINING
            # currency (weight-grad sync plans); mixing it with the
            # serve p99 currency would price plans a decode step never
            # executes — refuse instead of silently conflating
            raise ValueError(
                "objective='serve' does not compose with co_search "
                "(the joint comm-plan currency prices gradient sync, "
                "which a decode step does not run)"
            )
        if self.co_search and self.sync_schedule == "off":
            # the joint pricing currency IS the exposed-comm scheduled
            # sync — co-search without the schedule dimension would
            # price candidates with plans the lowering never executes
            self.sync_schedule = "search"
        if self.num_devices == 0:
            # a backend that fails to initialize raises here: a failed
            # TPU must not turn into a one-device run
            import jax

            self.num_devices = len(jax.devices())
        if self.machine_spec is None:
            if self.machine_model_file:
                self.machine_spec = MachineSpec.from_file(self.machine_model_file)
            else:
                self.machine_spec = MachineSpec.tpu_v5e(self.num_devices)
        if self.slice_levels:
            import dataclasses as _dc

            levels = parse_slice_levels(self.slice_levels)
            self.machine_spec = _dc.replace(
                self.machine_spec, slice_levels=levels)
            # fail at construction, not mid-search: topology_levels()
            # validates the aligned-nesting rules
            self.machine_spec.topology_levels()
            self.slice_levels = levels

    @property
    def search_devices(self) -> int:
        return self.search_num_devices or self.num_devices

    # ---- argv parsing ----------------------------------------------------
    @staticmethod
    def parse_args(argv: Optional[Sequence[str]] = None) -> "FFConfig":
        """Accepts the reference's flag spellings
        (reference: model.cc:3371-3654, README.md:79-102)."""
        p = argparse.ArgumentParser(add_help=False, allow_abbrev=False)
        p.add_argument("-e", "--epochs", type=int, default=1)
        p.add_argument("-b", "--batch-size", type=int, default=64)
        p.add_argument("--lr", "--learning-rate", dest="lr", type=float, default=0.01)
        p.add_argument("--wd", "--weight-decay", dest="wd", type=float, default=1e-4)
        p.add_argument("-ll:tpu", "--num-devices", dest="num_devices", type=int, default=0)
        p.add_argument("--budget", "--search-budget", dest="budget", type=int, default=128)
        p.add_argument("--alpha", "--search-alpha", dest="alpha", type=float, default=1.05)
        p.add_argument("--only-data-parallel", action="store_true")
        p.add_argument("--enable-parameter-parallel", action="store_true", default=True)
        p.add_argument("--enable-attribute-parallel", action="store_true", default=True)
        p.add_argument("--search-num-nodes", type=int, default=0)
        p.add_argument("--search-num-workers", type=int, default=0)
        p.add_argument("--base-optimize-threshold", type=int, default=10)
        p.add_argument("--search-timeout", dest="search_timeout", type=float, default=45.0)
        p.add_argument("--search-improvement-margin",
                       dest="search_improvement_margin", type=float,
                       default=0.03,
                       help="minimum simulated win over plain DP before a "
                            "searched strategy is accepted (champion-vs-DP "
                            "floor)")
        p.add_argument("--disable-pipeline-search",
                       dest="disable_pipeline_search", action="store_true",
                       help="compile() stops proposing pipelined lowerings "
                            "for stacked-block graphs")
        p.add_argument("--substitution-json", type=str, default=None)
        p.add_argument("--calibration-file", type=str, default=None)
        p.add_argument("--calibrate", action="store_true")
        p.add_argument("--calibration-budget", dest="calibration_budget",
                       type=float, default=60.0)
        p.add_argument("--export-strategy", dest="export_strategy", type=str, default=None)
        p.add_argument("--import-strategy", dest="import_strategy", type=str, default=None)
        p.add_argument("--import-strategy-partial",
                       dest="import_strategy_partial", action="store_true",
                       help="apply a strategy file best-effort even when "
                            "its graph digest/coverage does not match "
                            "(provenance checks downgrade to warnings)")
        p.add_argument("--machine-model-file", type=str, default=None)
        p.add_argument("--slice-levels", dest="slice_levels", type=str,
                       default=None,
                       help="multi-slice link hierarchy above ICI "
                            "without a machine file: comma list of "
                            "span:bandwidth:latency triples, e.g. "
                            "'16:3.1e9:1e-5' for one DCN class "
                            "spanning 16 devices (MachineSpec."
                            "slice_levels)")
        p.add_argument("--taskgraph", dest="export_taskgraph", type=str, default=None)
        p.add_argument("--profiling", action="store_true")
        p.add_argument("--trace-steps", dest="trace_steps", type=int, default=1)
        p.add_argument("--grad-accum-steps", dest="grad_accum_steps",
                       type=int, default=1)
        p.add_argument("--remat", action="store_true")
        p.add_argument("--zero-dp-shard", dest="zero_dp_shard",
                       action="store_true")
        p.add_argument("--sync-precision", dest="sync_precision",
                       choices=("fp32", "bf16", "int8", "search"),
                       default="fp32",
                       help="gradient-sync wire precision; 'search' "
                            "lets the strategy search pick it per "
                            "weight group")
        p.add_argument("--sync-schedule", dest="sync_schedule",
                       choices=("off", "search"), default="off",
                       help="gradient-sync schedule: 'search' buckets "
                            "the weight-grad collectives and issues "
                            "them inside the backward "
                            "(search/sync_schedule.py)")
        p.add_argument("--sync-bucket-bytes", dest="sync_bucket_bytes",
                       type=int, default=0,
                       help="pin the schedule search's per-bucket "
                            "coalescing floor in bytes (0 = sweep)")
        p.add_argument("--co-search", dest="co_search",
                       action="store_true",
                       help="joint strategy x comm-plan co-search: "
                            "price every candidate strategy with its "
                            "best sync schedule/precision/reduction "
                            "plan inside the substitution search "
                            "(search/comm_plan.py)")
        p.add_argument("--sync-ef", dest="sync_ef",
                       choices=("off", "auto"), default="off",
                       help="error-feedback residuals on int8 gradient "
                            "sync (per-group int8_ef wire choice, "
                            "residual threaded as training-loop state)")
        p.add_argument("--objective", dest="objective",
                       choices=("train", "serve"), default="train",
                       help="search objective: 'serve' ranks decode "
                            "graphs by simulated p99 latency over a "
                            "ragged arrival model under the HBM "
                            "KV-residency budget (search/serving.py)")
        p.add_argument("--serve-p99-budget-ms",
                       dest="serve_p99_budget_ms", type=float,
                       default=0.0,
                       help="declared p99 SLO for objective=serve "
                            "(recorded in __meta__.serving, linted "
                            "SHD163); 0 = rank-only")
        p.add_argument("--serve-disaggregation",
                       dest="serve_disaggregation",
                       choices=("off", "search"), default="off",
                       help="under objective=serve, also search a "
                            "prefill/decode disaggregation: prompt and "
                            "decode graphs on disjoint submeshes, the "
                            "KV handoff priced as a cross-block "
                            "transfer (search/disaggregation.py)")
        p.add_argument("--prefill-chunk", dest="prefill_chunk",
                       type=int, default=32,
                       help="chunk size of the batched prefill lane "
                            "(runtime/prefill.py): prompt tokens "
                            "written into the KV page pool per causal "
                            "forward pass")
        p.add_argument("--serve-fleet", dest="serve_fleet",
                       choices=("off", "search"), default="off",
                       help="under objective=serve, also search a "
                            "serving FLEET: N replica blocks on "
                            "disjoint submeshes, per-replica strategy "
                            "and per-SLO-class routing priced together "
                            "in per-class p99 (search/fleet.py)")
        p.add_argument("--serve-fleet-max-replicas",
                       dest="serve_fleet_max_replicas", type=int,
                       default=4,
                       help="upper bound on fleet replica count the "
                            "partition enumeration explores")
        p.add_argument("--serve-slo-classes", dest="serve_slo_classes",
                       type=str, default=None,
                       help="request SLO classes for the serving "
                            "executor: comma list of name:priority:"
                            "deadline_frames[:quantile] — priority "
                            "admission, deadline expiry, preemption "
                            "(runtime/decode.py)")
        p.add_argument("--kv-precision", dest="kv_precision",
                       choices=("off", "fp32", "bf16", "int8", "search"),
                       default="off",
                       help="KV page-pool dtype lane (ops/"
                            "decode_attention.py): pin fp32/bf16/int8 "
                            "(int8 adds per-page scales + in-kernel "
                            "dequant) or 'search' to price the lane "
                            "under objective=serve; 'off' is "
                            "byte-identical to history")
        p.add_argument("--serve-shared-prefix-pages",
                       dest="serve_shared_prefix_pages", type=int,
                       default=0,
                       help="pages per sequence expected to be CLAIMED "
                            "from the radix prefix trie instead of "
                            "privately allocated (runtime/decode.py) — "
                            "prices SHARED KV residency in SHD161 and "
                            "kv_residency_bytes")
        p.add_argument("--obs-log", dest="obs_log", type=str, default=None,
                       help="JSONL structured-event telemetry sink "
                            "(flexflow_tpu/obs; tools/ffobs.py renders it)")
        p.add_argument("--obs-trace", dest="obs_trace", type=str,
                       default=None,
                       help="write the PREDICTED task timeline as "
                            "Chrome-trace JSON at compile (Perfetto)")
        p.add_argument("--device-trace-dir", dest="device_trace_dir",
                       type=str, default=None,
                       help="capture a REAL jax.profiler device trace "
                            "of fit's post-compile steps into this "
                            "logdir, lane-stamped and tag-matched "
                            "against the predicted comm lanes "
                            "(obs/trace_ingest.py LaneDriftReport)")
        p.add_argument("--drift-threshold", dest="drift_threshold",
                       type=float, default=0.5,
                       help="predicted-vs-measured step-time drift "
                            "beyond which the DriftReport flags "
                            "calibration staleness")
        p.add_argument("--cost-cache-file", dest="cost_cache_file",
                       type=str, default=None,
                       help="persistent per-(op, view) cost-row + "
                            "search-result cache (search/cost_cache.py); "
                            "repeated searches start warm")
        p.add_argument("--no-cost-cache", dest="no_cost_cache",
                       action="store_true",
                       help="bypass the persistent cost cache even when "
                            "a file/env default is configured")
        p.add_argument("--verify", action="store_true",
                       help="static-analysis verification "
                            "(flexflow_tpu/analysis): check graph "
                            "invariants after every rewrite and the "
                            "compile-time graph before lowering")
        p.add_argument("--seed", type=int, default=0)
        args, _ = p.parse_known_args(argv)
        search_devs = args.search_num_workers * max(1, args.search_num_nodes or 1)
        return FFConfig(
            epochs=args.epochs,
            batch_size=args.batch_size,
            learning_rate=args.lr,
            weight_decay=args.wd,
            num_devices=args.num_devices,
            search_budget=args.budget,
            search_alpha=args.alpha,
            only_data_parallel=args.only_data_parallel,
            search_num_devices=search_devs,
            base_optimize_threshold=args.base_optimize_threshold,
            search_timeout_s=args.search_timeout,
            search_improvement_margin=args.search_improvement_margin,
            enable_pipeline_search=not args.disable_pipeline_search,
            substitution_json=args.substitution_json,
            calibration_file=args.calibration_file,
            calibrate=args.calibrate,
            calibration_budget_s=args.calibration_budget,
            export_strategy_file=args.export_strategy,
            import_strategy_file=args.import_strategy,
            import_strategy_partial=args.import_strategy_partial,
            export_strategy_task_graph_file=args.export_taskgraph,
            machine_model_file=args.machine_model_file,
            slice_levels=args.slice_levels,
            profiling=args.profiling,
            trace_steps=args.trace_steps,
            grad_accum_steps=args.grad_accum_steps,
            remat=args.remat,
            zero_dp_shard=args.zero_dp_shard,
            sync_precision=args.sync_precision,
            sync_schedule=args.sync_schedule,
            sync_bucket_bytes=args.sync_bucket_bytes,
            co_search=args.co_search,
            sync_ef=args.sync_ef,
            objective=args.objective,
            serve_p99_budget_ms=args.serve_p99_budget_ms,
            serve_disaggregation=args.serve_disaggregation,
            serve_fleet=args.serve_fleet,
            serve_fleet_max_replicas=args.serve_fleet_max_replicas,
            prefill_chunk=args.prefill_chunk,
            serve_slo_classes=args.serve_slo_classes,
            kv_precision=args.kv_precision,
            serve_shared_prefix_pages=args.serve_shared_prefix_pages,
            obs_log_file=args.obs_log,
            obs_trace_file=args.obs_trace,
            device_trace_dir=args.device_trace_dir,
            drift_threshold=args.drift_threshold,
            cost_cache_file="" if args.no_cost_cache else args.cost_cache_file,
            verify=args.verify,
            seed=args.seed,
        )
