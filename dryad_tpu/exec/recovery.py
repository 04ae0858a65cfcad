"""Replay-based fault tolerance: lineage-tracked runs with on-demand
recomputation and optional durable materialization.

The reference's model (SURVEY.md §3.5): deterministic vertices re-execute
from their (materialized, re-readable) inputs on failure —
`ReactToFailedVertex` rebuilds a new execution version (DrVertex.h:184),
bounded by a failure budget (DrFailureDictionary, DrGraph.cpp:39); durability
comes from materialized intermediate files.

Here: a ``Run`` memoizes stage outputs and records lineage (stage -> input
stages).  Losing an output (device OOM, preemption, or test fault injection)
just invalidates the memo entry; re-requesting it recomputes transitively
from surviving ancestors — stages are deterministic (fixed hash constants,
seeded sampling), so replay is exact.  With ``spill_dir`` set, every stage
output is also persisted as a columnar store; recovery then reloads from
disk instead of recomputing, and a NEW process can resume the run
(checkpoint/resume, which the reference lacks — SURVEY.md §5).

A ``Run`` is also the per-JOB driver state boundary (the reference's
one-Graph-Manager-per-job model made this per-process; the job-service
daemon runs many concurrent jobs in one process, dryad_tpu/service):
the event sink, failure budget, adaptive manager, cost report, and
observed-stats slot all live on the Run, never on the shared Executor.
``event=`` overrides the executor's process-default sink and ``job=``
tags every emitted event with the job id, so two concurrent Runs over
ONE executor can never interleave their streams.
"""

from __future__ import annotations

import os
import threading
from typing import Dict, Optional

from dryad_tpu.exec.data import PData
from dryad_tpu.plan.stages import StageGraph

__all__ = ["Run", "FailureBudgetExceeded", "HandoffPause"]

# spill save/restore runs EAGER device ops (store segmentation gathers)
# outside any compiled stage; concurrent eager dispatch from multiple
# fleet threads can wedge the CPU client, and the writes are disk-bound
# anyway — one process-wide ticket serializes them
_SPILL_IO_LOCK = threading.Lock()


class FailureBudgetExceeded(RuntimeError):
    pass


class HandoffPause(RuntimeError):
    """Raised at a stage boundary when the run's ``pause`` event is
    set: the daemon is draining for a rolling upgrade.  Every settled
    stage is already spilled + checkpointed, so the successor daemon
    resumes from exactly this boundary (service/durable)."""

    def __init__(self, sid: int):
        self.stage = sid
        super().__init__(f"run paused at stage {sid} boundary for "
                         f"daemon handoff")


class Run:
    """One execution of a StageGraph with lineage-based recovery."""

    def __init__(self, executor, graph: StageGraph,
                 bindings: Optional[Dict[str, PData]] = None,
                 spill_dir: Optional[str] = None,
                 failure_budget: Optional[int] = None,
                 spill_compression: Optional[str] = None,
                 cost_report=None, event=None, job=None,
                 checkpoint=None, pause=None):
        cfg = getattr(executor, "config", None)
        self.ex = executor
        self.graph = graph
        self.bindings = bindings or {}
        self.spill_dir = spill_dir
        self.cost_report = cost_report
        self.job = job
        # durable-service hooks (service/durable): ``checkpoint(run,
        # sid)`` snapshots driver state after each stage boundary;
        # ``pause`` (a threading.Event) stops the run AT a boundary —
        # settled work spilled, the rest resumable by a successor
        self.checkpoint = checkpoint
        self.pause = pause
        # per-job event sink: explicit ``event`` wins over the executor's
        # process default; with a job id every event is tagged so streams
        # from concurrent jobs sharing one executor never interleave
        # anonymously (the sink keeps the underlying EventLog's level so
        # span gating still sees the consumer's verdict)
        sink = event if event is not None else executor._event
        if job is not None:
            base = sink

            def _tagged(e, _base=base, _job=job):
                e.setdefault("job", _job)
                _base(e)

            from dryad_tpu.obs import trace as _trace
            sink = _trace.leveled(_tagged, getattr(base, "level", None))
        self._event = sink
        # observed-stats slot for the adaptive boundary hook: a one-slot
        # box owned by THIS run (a shared executor attribute would let a
        # concurrent job's stage leak its stats into our rewrite rules)
        self._stats_box = [None]
        self.spill_compression = (spill_compression if spill_compression
                                  is not None else
                                  (cfg.spill_compression if cfg else None))
        self.failure_budget = (failure_budget if failure_budget is not None
                               else (cfg.failure_budget if cfg else 16))
        self.failures = 0
        self._results: Dict[int, PData] = {}
        # optimistic (deferred-needs) execution: stages run without any
        # host sync; every needs vector is batch-fetched ONCE at job end
        # (see _settle).  Off when spilling (the durable write already
        # syncs each stage, and a truncated output must not be persisted
        # as good) and on multi-process gangs (workers advance in
        # lockstep; the sync path keeps their retry decisions identical).
        # adaptive execution (dryad_tpu/adapt): stage-boundary graph
        # rewriting needs the per-stage stats sync, so it forces the
        # synchronous path — the observability-for-round-trips trade the
        # reference GM makes at every vertex completion
        adaptive_on = bool(cfg) and getattr(cfg, "adaptive", "off") == "on"
        self.adapt = None
        if adaptive_on:
            from dryad_tpu.adapt.manager import (AdaptiveManager,
                                                 levels_of_mesh)
            self.adapt = AdaptiveManager(
                graph, cfg, executor.nparts,
                levels=levels_of_mesh(getattr(executor, "mesh", None)),
                event=self._event, cost_report=cost_report)
        defer_ok = (getattr(cfg, "deferred_needs", True) if cfg else True)
        self._defer = ([] if defer_ok and not spill_dir
                       and not adaptive_on
                       and not getattr(executor, "_multiproc", False)
                       else None)
        if spill_dir:
            os.makedirs(spill_dir, exist_ok=True)
        # record the EXECUTED plan in the event stream (Calypso topology
        # events role) so viewers draw the DAG that actually ran — a
        # re-planned graph gets fresh stage ids, so a separately serialized
        # plan would not match the stage events
        try:
            from dryad_tpu.plan.serialize import graph_to_json
            self._event({"event": "plan",
                         "plan": graph_to_json(graph)})
        except Exception:
            pass  # plan serialization must never block execution

    # -- public ------------------------------------------------------------

    def output(self) -> PData:
        import time as _time

        from dryad_tpu.obs import profile as _profile
        from dryad_tpu.obs import trace
        from dryad_tpu.obs.metrics import REGISTRY
        t0 = _time.time()
        # background resource sampler for this run's duration
        # (obs/profile.py): gated by the sink's level like spans, so a
        # no-consumer run starts no thread.  Worker processes (tagged
        # with DRYAD_WORKER_ID) run their OWN per-command samplers
        # (runtime/worker.py) — sampling here too would double-report
        # them under a driver label.
        sampler = _profile.start(
            self._event if os.environ.get("DRYAD_WORKER_ID") is None
            else None,
            getattr(getattr(self.ex, "config", None),
                    "resource_sample_s", 0.0) or 0.0,
            role="driver")
        try:
            # the job span: every stage/io span of this run parents into
            # it (on a worker the envelope's trace_ctx makes it a child
            # of the driver's job span — obs/trace.py propagation)
            # the span stays bound past the with-block so job_done can
            # carry the trace id (the null span below level 2 has
            # none): the service's latency waterfall links its p99
            # exemplar to this run's recorded trace through it
            with trace.span("run", "job", sink=self._event,
                            stages=len(self.graph.stages)) as jsp:
                # re-read out_stage after the walk: an adaptive rewrite
                # (agg-tree expansion) may have redirected it to an
                # appended finalizing stage mid-run
                while True:
                    out_sid = self.graph.out_stage
                    out = self.result(out_sid)
                    if self.graph.out_stage == out_sid:
                        break
                if self._defer:
                    out = self._settle()
        finally:
            _profile.stop(sampler)
        # surfaced per run so the cluster/farm reply path can report how
        # adaptive this job was without re-scanning the event stream
        self.ex._last_run_rewrites = (self.adapt.rewrite_count
                                      if self.adapt else 0)
        # a broadcast flip changes the job output's PLACEMENT (a
        # promoted join keeps the left producer's distribution, not the
        # planned hash claim) — persisted partitioning claims must drop,
        # same contract as runtime salting (test_skew.py)
        self.ex._last_run_placement_changed = bool(self.adapt) and any(
            ev.get("kind") in ("broadcast_promote", "broadcast_demote")
            for ev in self.adapt.applied)
        # the final progress record counts the stages the finished DAG
        # actually NEEDED (reachable from out_stage): adaptive rewrites
        # may orphan ladder levels or append stages, so len(stages)
        # would contradict pct=100 (done < total) on a completed job
        reach = set()
        frontier = [self.graph.out_stage]
        while frontier:
            sid = frontier.pop()
            if sid in reach:
                continue
            reach.add(sid)
            frontier.extend(self.graph.stage(sid).input_stage_ids())
        self._event({"event": "progress",
                        "done": len(reach & set(self._results)),
                        "total": len(reach), "pct": 100.0})
        # job-end metrics snapshot.  "metrics" carries CUMULATIVE
        # process counters (the Prometheus model: monotone since process
        # start), not per-job deltas.  Farm workers suppress this event
        # (runtime/worker.py sets _emit_job_done=False) — a 16-task farm
        # is one job, not 16.
        if getattr(self.ex, "_emit_job_done", True):
            done_e = {"event": "job_done",
                      "wall_s": round(_time.time() - t0, 4),
                      "stages": len(self.graph.stages),
                      "replays": self.failures,
                      "metrics": REGISTRY.snapshot()}
            trace_id = getattr(jsp, "trace_id", None)
            if trace_id:
                done_e["trace"] = trace_id
            self._event(done_e)
        return out

    def _settle(self) -> PData:
        """Resolve every deferred needs vector in ONE host round trip.

        Fetches jnp.stack of all infos (1 dispatch + 1 fetch regardless
        of stage count), emits the stage_done events the sync path would
        have, and — when a stage overflowed — applies the shared retry
        policy to its sticky knobs, invalidates it plus every dependent
        result, and replays synchronously.  Overflow is the rare case;
        the common case pays zero per-stage round trips."""
        import time

        import numpy as np

        import jax.numpy as jnp

        from dryad_tpu.obs import trace
        from dryad_tpu.obs.metrics import REGISTRY, family_counter

        deferred, self._defer = self._defer, None   # replay runs sync
        # the host waits here for the device: every info is an output of
        # its stage program, so the fetch ends when the last program has
        t0 = time.perf_counter()
        with trace.span("settle", "wait", sink=self._event,
                        deferred=len(deferred)):
            infos = np.asarray(jnp.stack([r["info"] for r in deferred]))
        family_counter(REGISTRY, "run_seconds").inc(
            time.perf_counter() - t0)
        bad: Dict[int, tuple] = {}
        for rec, info in zip(deferred, infos):
            stage = rec["stage"]
            # exchange slot feedback rides the batched fetch: the next
            # run of each stage (iterative supersteps, re-collects, and
            # the overflow replay below) ships measured exact slots
            self.ex._note_slot_feedback(stage, info)
            need_scale = int(info[:, 0].max())
            need_slack = int(info[:, 1].max())
            need_exch = int(info[:, 2].max())
            of = need_scale > 0 or need_slack > 0
            range_attrs = dict(rec.get("range", {}))
            if range_attrs:
                from dryad_tpu.exec.executor import _INFO_TIE
                range_attrs["tie_rows"] = int(info[:, _INFO_TIE].sum())
            from dryad_tpu.exec.executor import stage_info_attrs
            self._event({
                "event": "stage_done", "stage": stage.id,
                "label": stage.label, "attempt": 0,
                "scale": rec["scale"], "slack": rec["slack"],
                "overflow": of, "need_scale": need_scale,
                "need_slack": need_slack, "need_exchange": need_exch,
                "salted": rec["salted"], "rows": info[:, 3].tolist(),
                "compile_s": rec["compile_s"],
                "cache_hit": rec.get("cache_hit", False),
                "out_bytes": rec.get("out_bytes", 0),
                "deferred": True,
                "dispatches": 1,   # program launch only; fetch amortized
                "wall_s": rec["enqueue_s"], **rec.get("join", {}),
                **range_attrs, **rec.get("filters", {}),
                **stage_info_attrs(info)})
            if not of:
                # settled clean at the planned shapes: cross-check the
                # measured rows/bytes against the static cost prediction
                # (cost_model_miss events) — overflowing records replay
                # below and cross-check on their synchronous re-run
                self.ex._check_cost(stage, rec["scale"],
                                    int(info[:, 3].sum()),
                                    rec.get("out_bytes", 0),
                                    report=self.cost_report,
                                    event=self._event)
            if of:
                # the deferred path counts runs/bytes at enqueue
                # (executor defer branch); the overflow verdict only
                # exists here, so the retry counter settles here too
                family_counter(REGISTRY, "cap_retries").inc()
                decision = self.ex._decide_needs(
                    stage, rec["scale"], rec["slack"], rec["salted"],
                    need_scale, need_slack, need_exch)
                if decision[0] == "retry":
                    bad[stage.id] = decision
        if bad:
            # the settle replay IS a capacity retry — a zero budget means
            # the user wants the first overflow surfaced, not healed
            from dryad_tpu.exec.executor import CapacityError
            max_retries = getattr(self.ex.config, "max_capacity_retries",
                                  3)
            if max_retries == 0:
                sid = min(bad)
                st = self.graph.stage(sid)
                raise CapacityError(
                    f"stage {st.id} ({st.label}) still overflowing after "
                    f"0 capacity retries (deferred settle)")
            # drop every overflowed stage AND anything computed from it
            # (their inputs were truncated), then replay synchronously
            # with the right-sized sticky knobs
            dirty = set(bad)
            changed = True
            while changed:
                changed = False
                for sid in list(self._results):
                    if sid in dirty:
                        continue
                    st = self.graph.stage(sid)
                    if any(d in dirty for d in st.input_stage_ids()):
                        dirty.add(sid)
                        changed = True
            for sid, (_, scale, slack, salted) in bad.items():
                st = self.graph.stage(sid)
                st._capacity_scale = scale
                st._send_slack = slack
                st._salted = salted
            for sid in dirty:
                self._results.pop(sid, None)
            self._event({"event": "settle_replay",
                            "stages": sorted(dirty)})
        return self.result(self.graph.out_stage)

    def result(self, sid: int) -> PData:
        """Materialize stage ``sid`` demand-driven.

        Each outer iteration walks from ``sid`` to its DEEPEST
        unmaterialized ancestor and computes exactly that one stage,
        re-reading the graph's edges on every step: an adaptive rewrite
        fired by a completed ancestor (``self.adapt``) may have
        redirected legs mid-walk, and a stage orphaned by a rewrite
        must not be computed just because a pre-rewrite edge pointed at
        it.  The walk is O(depth) per materialization — noise next to a
        stage launch — and replays lost ancestors exactly like the old
        recursive form."""
        while sid not in self._results:
            cur = sid
            while True:
                spilled = self._load_spill(cur)
                if spilled is not None:
                    self._results[cur] = spilled
                    break
                missing = [d for d in
                           self.graph.stage(cur).input_stage_ids()
                           if d not in self._results]
                if not missing:
                    self._compute(cur)
                    break
                cur = missing[0]
        return self._results[sid]

    def _compute(self, sid: int) -> None:
        """Run one ready stage (all inputs materialized) and fire the
        adaptive boundary hook."""
        if self.pause is not None and self.pause.is_set():
            raise HandoffPause(sid)
        stage = self.graph.stage(sid)
        from dryad_tpu.obs import trace
        # one span per stage execution (compile + run attempts; on the
        # deferred path this covers the enqueue only — the wait for the
        # device is the run's ``settle`` span)
        with trace.span(f"stage {stage.id}:{stage.label}", "stage",
                        sink=self._event, stage=stage.id,
                        label=stage.label,
                        deferred=self._defer is not None) as sp:
            out = self.ex._run_stage(stage, self._results, self.bindings,
                                     defer=self._defer, event=self._event,
                                     cost_report=self.cost_report,
                                     stats_box=self._stats_box,
                                     job=self.job, span=sp)
        self._results[sid] = out
        self._save_spill(sid, out)
        if self.checkpoint is not None:
            self.checkpoint(self, sid)
        # progress percentage pushed to the event stream (the reference
        # pushes it to the launcher, DrGraph.cpp:109-110); the settled
        # stage rides along so live consumers (the service dashboard's
        # per-job progress bars, SSE followers) can label the tick
        total = len(self.graph.stages)
        self._event({"event": "progress", "done": len(self._results),
                        "total": total, "stage": sid,
                        "pct": round(100.0 * len(self._results) / total, 1)})
        # adaptive boundary: the unexecuted suffix may be rewritten from
        # this stage's observed stats BEFORE any dependent runs (the
        # connection-manager hook, DrConnectionManager
        # NotifyUpstreamVertexCompleted parity)
        if self.adapt is not None:
            st = self._stats_box[0]
            if st is not None and st.stage == sid:
                n_before = len(self.adapt.applied)
                self.adapt.on_stage_materialized(st, set(self._results))
                # a rewrite reshapes stages the static model never saw:
                # drop their predictions so the runtime cross-check
                # cannot fire spurious misses against pre-rewrite bounds
                rep = self.cost_report
                if rep is not None:
                    for ev in self.adapt.applied[n_before:]:
                        for rid in ([ev.get("stage")]
                                    + list(ev.get("new_stages", ()))
                                    + list(ev.get("orphaned", ()))):
                            if rid is not None:
                                rep._by_stage.pop(rid, None)

    def invalidate(self, sid: int, count_failure: bool = True,
                   drop_spill: bool = False) -> None:
        """Report a lost stage output (fault injection / preemption)."""
        if count_failure:
            self.failures += 1
            self._event({"event": "stage_replay", "stage": sid,
                            "label": self.graph.stage(sid).label,
                            "failures": self.failures})
            if self.failures > self.failure_budget:
                raise FailureBudgetExceeded(
                    f"{self.failures} failures > budget "
                    f"{self.failure_budget}")
        self._results.pop(sid, None)
        if drop_spill and self.spill_dir:
            import shutil
            p = self._spill_path(sid)
            if os.path.exists(p):
                shutil.rmtree(p)

    # -- spill -------------------------------------------------------------

    def _spill_path(self, sid: int) -> str:
        return os.path.join(self.spill_dir, f"stage-{sid:04d}")

    def _stage_fp(self, sid: int) -> str:
        import hashlib
        return hashlib.sha256(
            self.graph.stage(sid).fingerprint().encode()).hexdigest()

    def _save_spill(self, sid: int, pd: PData) -> None:
        if not self.spill_dir:
            return
        from dryad_tpu.io.store import write_store
        with _SPILL_IO_LOCK:
            write_store(self._spill_path(sid), pd,
                        compression=self.spill_compression)
        if self.adapt is not None:
            # adaptive runs may reshape a stage before it executes; a
            # later resume replans WITHOUT the rewrite (no stats yet),
            # so a bare stage-id spill could restore rewrite-shaped
            # data into a differently-shaped plan (e.g. an expanded
            # merge's PARTIAL output as the finalized result).  Record
            # the executed shape so loads can refuse mismatches.
            with open(self._spill_path(sid) + ".fp", "w") as f:
                f.write(self._stage_fp(sid))
        self._event({"event": "stage_spilled", "stage": sid})

    def _load_spill(self, sid: int) -> Optional[PData]:
        if not self.spill_dir:
            return None
        p = self._spill_path(sid)
        if not os.path.exists(p):
            return None
        # refuse shape-mismatched spills (see _save_spill); a miss just
        # recomputes — conservative, never wrong.  A recorded .fp is
        # checked by EVERY run (a non-adaptive resume must not swallow
        # an adaptive run's rewrite-shaped output either); an adaptive
        # run refuses bare spills outright (this run may already have
        # rewritten the stage).  Fingerprints of UDF-bearing stages
        # embed callable ids, so a NEW-process adaptive resume
        # recomputes those too (by design).
        fp_file = p + ".fp"
        if os.path.exists(fp_file):
            try:
                with open(fp_file) as f:
                    ok = f.read().strip() == self._stage_fp(sid)
            except OSError:
                ok = False
        else:
            ok = self.adapt is None
        if not ok:
            return None
        from dryad_tpu.io.store import read_store
        with _SPILL_IO_LOCK:
            pd = read_store(p, self.ex.mesh)
        self._event({"event": "stage_restored", "stage": sid})
        return pd
