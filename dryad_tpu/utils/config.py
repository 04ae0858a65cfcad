"""Typed job configuration — the DryadLinqContext knob surface.

The reference exposes ~40 typed properties on DryadLinqContext
(DryadLinqContext.cs:728-1053: JobMinNodes/MaxNodes, PartitionUncPath,
CompressionScheme, EnableSpeculativeDuplication, MatchClientNetFrameworkVersion,
…).  This is the TPU-native equivalent: one frozen dataclass, validated at
construction, threaded to every subsystem.  Each field cites the subsystem
it controls; fields whose reference counterpart is Windows/cluster plumbing
that has no TPU meaning are deliberately absent rather than stubbed.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from dryad_tpu.adapt.thresholds import (
    SKEW_SIBLING_MEDIAN_FACTOR as _SKEW_FACTOR)
from dryad_tpu.utils.compile_cache import (
    DEFAULT_CACHE_DIR as _DEFAULT_COMPILE_CACHE_DIR)

__all__ = ["JobConfig"]


@dataclasses.dataclass(frozen=True)
class JobConfig:
    """All knobs, grouped by subsystem.  Defaults reproduce the framework's
    historical behavior; construct with overrides and pass to
    ``Context(config=...)``."""

    # -- executor: capacity management (exec/executor.py) ------------------
    # retries after the first overflow; each retry is right-sized from the
    # measured need (DrDynamicDistributionManager role)
    max_capacity_retries: int = 3
    # initial send-slot slack factor for exchanges (C = ceil(slack*cap/D))
    initial_send_slack: int = 2
    # exact-first-wave exchanges: pure repartition legs (no ops) whose
    # input exceeds this many MB run a counts-only probe (one tiny
    # program + one scalar fetch) so even the FIRST wave ships measured
    # slots instead of the structural slack (the reference's pull
    # shuffle ships exact file sizes, kernel/DrCluster.cpp:553-569).
    # -1 disables; 0 probes always (wire_check/tests)
    exchange_probe_min_mb: float = 8.0
    # on-device sample lanes per partition for range bounds
    # (DryadLinqSampler.cs:38 samples 0.1%; we take a fixed per-part cap)
    range_samples_per_partition: int = 4096
    # compiled-stage LRU entries (per executor)
    compile_cache_size: int = 256
    # persistent (on-disk) XLA compilation cache shared by all processes:
    # the reference pays vertex codegen once per job (csc BuildAssembly,
    # DryadLinqCodeGen.cs:2283); this is our once-per-(program, shapes)
    # equivalent across driver restarts AND worker processes.  None
    # disables; JAX_COMPILATION_CACHE_DIR, where set, overrides this
    # (utils/compile_cache.py — the single source of the default path)
    compilation_cache_dir: Optional[str] = _DEFAULT_COMPILE_CACHE_DIR
    # device-time profiling: when set, every executor run is wrapped in a
    # jax.profiler trace written under this directory (open with
    # TensorBoard / xprof — the device-timeline view the reference
    # surfaces through Artemis; SURVEY.md §5 tracing).  Workers profile
    # into per-process subdirectories.
    profile_dir: Optional[str] = None
    # hot-key salting (exec/executor.py + parallel/shuffle.py
    # skew_join_exchange, DrDynamicDistributor.h:79 role): a saltable join
    # stage switches to the salted exchange when a retry would need
    # >= trigger x the current per-destination capacity
    salt_trigger_factor: int = 4
    # a key is hot when its global row count exceeds factor x (rows / P)
    salt_hot_factor: float = 4.0
    # per-partition heavy-hitter candidates nominated for the hot set
    salt_topk: int = 8

    # -- fault tolerance (exec/recovery.py) --------------------------------
    # replays allowed before FailureBudgetExceeded (DrFailureDictionary,
    # DrGraph.cpp:39)
    failure_budget: int = 16
    # durable stage-output spill: None disables; "gzip" compresses spill
    # partitions (GzipCompressionChannelTransform.cpp)
    spill_compression: Optional[str] = None

    # -- collect shrink policy (exec/data.py) ------------------------------
    # capacities at or under this are never shrunk before host transfer
    collect_shrink_min_capacity: int = 1024
    # shrink only when capacity exceeds this multiple of the max count
    collect_shrink_waste_factor: int = 4

    # -- text ingest (api read_text / ops/text.py) -------------------------
    text_max_line_len: int = 256
    # default delimiters for split_words (reference LineRecord tokenizers)
    token_delims: bytes = b" \t\r\n.,;:!?\"'()[]{}<>"
    token_max_len: int = 24
    string_max_len: int = 64          # from_columns string payload bytes

    # -- store (io/store.py) -----------------------------------------------
    # default compression for to_store (None | "gzip")
    store_compression: Optional[str] = None
    # verify fnv64 partition checksums on read (fingerprint.cpp role)
    store_verify_checksums: bool = True

    # -- out-of-core streaming (exec/ooc.py, exec/stream_exec.py) ----------
    # default chunk size for ChunkSource constructors
    ooc_chunk_rows: int = 1 << 16
    # default scatter fan-out for streaming_group_aggregate
    ooc_hash_buckets: int = 64
    # in-flight device batches for the double-buffered stream (depth)
    ooc_inflight: int = 2
    # memory-hierarchy-aware sort tier: a streamed sort whose TOTAL data
    # (counted by the sampling pass it already runs) fits this many bytes
    # skips the bucket round-trip — one H2D, one device sort, one D2H
    # (the reference's channels pick RAM FIFOs over disk files the same
    # way, channelbufferqueue vs channelbuffernativewriter).  0 forces
    # the out-of-core machinery regardless of size.
    ooc_incore_bytes: int = 1 << 30
    # from_store switches to streamed execution when the store holds at
    # least this many rows (0 = off); read_store_stream always streams
    ooc_auto_stream_rows: int = 0
    # max rows the materialized build side of a streamed join may hold
    ooc_join_build_rows: int = 1 << 18
    # host-IO prefetch depth for the chunk pipeline (exec/ooc.py
    # prefetch_iter): a background thread pulls up to this many chunks
    # ahead of the device, overlapping the next chunk's store read /
    # ranged fetch / unpack with the current chunk's compute (the
    # reference's completion-port double buffering,
    # channelbuffernativereader.cpp).  0 disables (the A/B lever the
    # regression guard keeps).
    ooc_prefetch_depth: int = 2
    # store-backed re-streaming cache tier for Dataset.cache() on
    # streamed / edge-scale data (exec/ooc.py cache_source): the cold
    # pass writes a LOCAL chunked cache (io/store layout, per-chunk
    # fingerprints) keyed by the producing query's stable fingerprint;
    # warm passes — iteration 2..N of do_while bodies, or a restarted
    # job with an intact cache dir — re-stream from local sequential
    # reads instead of ranged hdfs://, s3://, or http:// fetches.
    # False restores the legacy behavior (device-/cluster-resident
    # cache(); streamed cache() spools to an unvalidated temp store) —
    # the cache-off A/B lever.
    ooc_restream_cache: bool = True
    # root directory for re-streaming cache entries.  None = a
    # per-Context temp dir (removed at Context GC — warm iterations
    # still hit, restarts do not); set a persistent path to let a
    # restarted job with an intact cache dir skip the cold pass.
    ooc_cache_dir: Optional[str] = None

    # -- cluster runtime (runtime/cluster.py) ------------------------------
    cluster_processes: int = 2
    cluster_devices_per_process: int = 2
    cluster_startup_timeout_s: float = 180.0
    cluster_job_timeout_s: float = 600.0
    cluster_fn_modules: Tuple[str, ...] = ()
    # gang straggler/wedge watchdog (runtime/cluster.py; the reference
    # duplicates ANY slow vertex, DrVertex.h:195 + DrStageStatistics.cpp:
    # 24-25 — an SPMD gang can't duplicate one member, so a wedged worker
    # triggers teardown + one replay on a fresh gang instead of hanging
    # every collective until the hard job timeout):
    # workers send progress frames every hb_every seconds while a job
    # runs (0 disables the watchdog)...
    gang_heartbeat_s: float = 2.0
    # ...and a worker silent for longer than this is declared WEDGED
    gang_heartbeat_timeout_s: float = 60.0
    # once the FIRST worker reply lands, the rest must land within
    # max(rel x first-reply latency, abs seconds) — post-collective skew
    # between gang members is otherwise milliseconds
    gang_straggler_rel_margin: float = 1.0
    gang_straggler_abs_margin_s: float = 15.0

    # optimistic stage execution (exec/recovery.Run._settle): stages run
    # with ZERO per-stage host syncs; every needs vector is batch-fetched
    # once at job end, and overflows replay synchronously from the first
    # affected stage: O(1) instead of O(stages) host round trips per
    # job, which matters in proportion to the dispatch latency.
    # Reference: one DVertexCommandBlock start per vertex — the GM does
    # not chat mid-vertex (dvertexcommand.h:199).
    deferred_needs: bool = True

    # whole-group streamed operators (group_apply / group_median over
    # chunk streams, exec/ooc.streaming_group_whole): max raw rows one
    # key bucket may materialize on device — whole groups do not
    # compose, so this bound is the honest memory contract
    ooc_group_bucket_rows: int = 1 << 21

    # pick ooc chunk sizes from MEASURED link + dispatch rates instead of
    # the static ooc_chunk_rows (exec/autotune.pick_chunk_rows): on a
    # high-latency link the tuner grows chunks until the per-dispatch
    # floor is amortized; on a local link the lower clamp applies.
    # Opt-in: explicit chunk_rows arguments always win.
    ooc_chunk_autotune: bool = False

    # cluster streamed generator sources (Dataset.from_stream /
    # read_text_stream on a cluster Context): the driver SPOOLS the
    # stream into a store at this directory — which must be reachable by
    # the workers (shared filesystem or s3://) — then the gang streams
    # the store (FromEnumerable parity: the client writes the enumerable
    # into cluster storage, DryadLinqContext.cs:1210).  None = a driver
    # temp dir (valid for single-machine clusters).
    cluster_stream_spool_dir: str | None = None

    # -- task farm / speculation (runtime/farm.py) -------------------------
    # EnableSpeculativeDuplication + DrStageStatistics caps
    speculation_enabled: bool = True
    speculation_duplication_budget: float = 0.2
    speculation_outlier_sigma: float = 3.0
    speculation_min_samples: int = 5
    speculation_rel_margin: float = 0.5
    speculation_abs_margin_s: float = 0.5
    farm_task_timeout_s: float = 600.0

    # -- planner (plan/planner.py) -----------------------------------------
    # default fan-out allowance for join output capacity (out = expansion *
    # max(input caps)); per-join override via Dataset.join(expansion=...)
    join_expansion: float = 1.0
    # broadcast the build side instead of hash-exchanging both sides when
    # its capacity is at most this fraction of the probe side's
    broadcast_join_threshold: float = 0.0   # 0 disables auto-broadcast

    # -- iteration (api do_while) ------------------------------------------
    max_loop_iterations: int = 1000

    # -- observability: forensics / profiling / history (dryad_tpu/obs) ----
    # background resource sampler period (obs/profile.py): driver and
    # workers emit periodic resource_sample events (RSS, CPU%, device
    # buffer bytes, gc counts; level 2) that export as Chrome-trace
    # counter tracks.  0 disables.  The sampler only runs when an event
    # consumer exists (same no-consumer-zero-work contract as spans).
    resource_sample_s: float = 0.5
    # where task-failure forensics bundles persist (obs/flight.py);
    # None = a bundles/ dir next to the job's EventLog JSONL, or a temp
    # dir when the log is memory-only
    forensics_dir: Optional[str] = None
    # job history archive (obs/history.py): when set, every job's
    # EventLog snapshots {events, plan, metrics, bundles} here on close
    # (the JobBrowser job-history role); browse with
    # `python -m dryad_tpu.obs history <dir>`
    history_dir: Optional[str] = None

    # -- adaptive execution (dryad_tpu/adapt) ------------------------------
    # stage-boundary graph rewriting from observed per-partition stats
    # (the reference's DrDynamicAggregate/Distribution/BroadcastManager
    # roles).  "off" (default): the adapt subsystem is never constructed
    # — byte-identical plans and results to the non-adaptive runtime.
    # "on": the not-yet-executed suffix of the StageGraph may be
    # rewritten at each stage materialization; requires the per-stage
    # stats sync, so deferred-needs batching is disabled for the run.
    adaptive: str = "off"
    # a partition is skewed at >= this multiple of its sibling median —
    # SAME constant diagnose_events flags on (adapt/thresholds.py), so
    # detection and action cannot drift
    adapt_skew_factor: float = _SKEW_FACTOR
    # collapse a hierarchical aggregation tree to one global exchange
    # when the measured upstream rows are at most this many
    adapt_agg_collapse_rows: int = 4096
    # expand a flat merge into per-axis hops (multi-level meshes) when
    # measured upstream rows reach this many
    adapt_agg_expand_rows: int = 1 << 20
    # shrink a downstream exchange's capacity when the static plan
    # capacity exceeds this multiple of the measured row bound
    adapt_shrink_factor: float = 2.0
    # broadcast joins: measured build side must stay within this
    # fraction of the probe side's rows — above it a planned broadcast
    # demotes to hash exchange, below it a saltable hash join promotes
    adapt_broadcast_max_ratio: float = 0.25

    # -- pre-submit static analysis (dryad_tpu/analysis) -------------------
    # gate every executor/cluster/stream submission through the plan
    # verifier + UDF lint (the reference's phase-1 static validation,
    # DryadLinqQueryGen.cs): "off" = no checking, "warn" = run the job
    # but log findings to the EventLog (viewer Diagnostics section),
    # "error" = refuse to submit when error-severity findings exist
    # (analysis.LintError).  Dataset.check() is the interactive form.
    lint: str = "off"
    # per-device HBM budget for the static cost analyzer
    # (analysis/cost.py, DTA2xx): with lint enabled, a plan whose
    # predicted per-device working set PROVABLY exceeds this many bytes
    # fails pre-submit (DTA201); predicted-spill warnings (DTA202) and
    # the cache()-of-edge-scale-data warning (DTA204) key off it too.
    # 0 = unknown/disabled — the cost pass still runs (per-stage cost
    # table, unbounded-fan-out warnings, runtime cost_model_miss
    # cross-check) but never gates on a memory budget.
    device_hbm_bytes: int = 0

    def __post_init__(self):
        checks = [
            (self.ooc_group_bucket_rows > 0,
             "ooc_group_bucket_rows > 0"),
            (self.max_capacity_retries >= 0, "max_capacity_retries >= 0"),
            (self.initial_send_slack >= 1, "initial_send_slack >= 1"),
            (self.exchange_probe_min_mb >= -1,
             "exchange_probe_min_mb >= -1"),
            (self.range_samples_per_partition >= 2,
             "range_samples_per_partition >= 2"),
            (self.compile_cache_size >= 1, "compile_cache_size >= 1"),
            (self.salt_trigger_factor >= 2, "salt_trigger_factor >= 2"),
            (self.salt_hot_factor >= 1.0, "salt_hot_factor >= 1.0"),
            (self.salt_topk >= 1, "salt_topk >= 1"),
            (self.failure_budget >= 0, "failure_budget >= 0"),
            (self.spill_compression in (None, "gzip"),
             "spill_compression in (None, 'gzip')"),
            (self.store_compression in (None, "gzip"),
             "store_compression in (None, 'gzip')"),
            (self.collect_shrink_min_capacity >= 1,
             "collect_shrink_min_capacity >= 1"),
            (self.collect_shrink_waste_factor >= 1,
             "collect_shrink_waste_factor >= 1"),
            (self.text_max_line_len >= 1, "text_max_line_len >= 1"),
            (self.token_max_len >= 1, "token_max_len >= 1"),
            (self.string_max_len >= 1, "string_max_len >= 1"),
            (len(self.token_delims) >= 1, "token_delims non-empty"),
            (self.ooc_chunk_rows >= 1, "ooc_chunk_rows >= 1"),
            (self.ooc_hash_buckets >= 1, "ooc_hash_buckets >= 1"),
            (self.ooc_inflight >= 1, "ooc_inflight >= 1"),
            (self.ooc_incore_bytes >= 0, "ooc_incore_bytes >= 0"),
            (self.ooc_auto_stream_rows >= 0, "ooc_auto_stream_rows >= 0"),
            (self.ooc_join_build_rows >= 1, "ooc_join_build_rows >= 1"),
            (self.ooc_prefetch_depth >= 0, "ooc_prefetch_depth >= 0"),
            (self.cluster_processes >= 1, "cluster_processes >= 1"),
            (self.cluster_devices_per_process >= 1,
             "cluster_devices_per_process >= 1"),
            (self.gang_heartbeat_s >= 0, "gang_heartbeat_s >= 0"),
            (self.gang_heartbeat_timeout_s > 0,
             "gang_heartbeat_timeout_s > 0"),
            (self.gang_straggler_rel_margin >= 0,
             "gang_straggler_rel_margin >= 0"),
            (self.gang_straggler_abs_margin_s > 0,
             "gang_straggler_abs_margin_s > 0"),
            (0.0 <= self.speculation_duplication_budget <= 1.0,
             "speculation_duplication_budget in [0, 1]"),
            (self.speculation_min_samples >= 1,
             "speculation_min_samples >= 1"),
            (self.join_expansion > 0, "join_expansion > 0"),
            (self.broadcast_join_threshold >= 0,
             "broadcast_join_threshold >= 0"),
            (self.max_loop_iterations >= 1, "max_loop_iterations >= 1"),
            (self.lint in ("off", "warn", "error"),
             "lint in ('off', 'warn', 'error')"),
            (self.device_hbm_bytes >= 0, "device_hbm_bytes >= 0"),
            (self.adaptive in ("off", "on"),
             "adaptive in ('off', 'on')"),
            (self.adapt_skew_factor >= 1.0, "adapt_skew_factor >= 1.0"),
            (self.adapt_agg_collapse_rows >= 1,
             "adapt_agg_collapse_rows >= 1"),
            (self.adapt_agg_expand_rows >= 1,
             "adapt_agg_expand_rows >= 1"),
            (self.adapt_shrink_factor >= 1.0,
             "adapt_shrink_factor >= 1.0"),
            (self.adapt_broadcast_max_ratio > 0,
             "adapt_broadcast_max_ratio > 0"),
            (self.resource_sample_s >= 0, "resource_sample_s >= 0"),
        ]
        for ok, msg in checks:
            if not ok:
                raise ValueError(f"JobConfig: {msg}")

    def replace(self, **kw) -> "JobConfig":
        return dataclasses.replace(self, **kw)
