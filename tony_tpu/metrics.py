"""Executor-side metrics sampler.

Mirrors the reference TaskMonitor (tony-core/.../TaskMonitor.java:34-170):
a scheduled sampler keeping max + running-average of per-task resource
metrics, pushed to the driver over the metrics RPC. The reference samples
process-tree RSS (YARN ResourceCalculatorProcessTree) and GPU
util/FB-mem/BAR1-mem via nvidia-smi (util/gpu/GpuDiscoverer.java); here we
sample the user-process-tree RSS from /proc and TPU duty cycle / HBM from
libtpu metrics when available (cluster/tpu_metrics.py).
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from pathlib import Path
from typing import Any

log = logging.getLogger(__name__)

MEMORY_RSS = "memory_rss_mb"
TPU_DUTY_CYCLE = "tpu_duty_cycle_pct"
TPU_HBM_USED = "tpu_hbm_used_mb"
# device-memory watermark: peak bytes in use since client start
# (memory_stats()["peak_bytes_in_use"]) — the number capacity planning
# actually needs; reported only where the runtime serves stats (CPU
# devices return None and the series is omitted, never rendered as zero)
TPU_HBM_PEAK = "tpu_hbm_peak_mb"
# framework-tracked live device buffers (jax.live_arrays) — reported when no
# runtime channel serves occupancy; excludes XLA temps/executables, so it is
# a floor on true HBM use and labeled distinctly to say so
TPU_HBM_LIVE = "tpu_hbm_live_buffer_mb"

# serving-load gauges (fed by cli/serve.ServeApp once per scheduling turn;
# named here so the /stats payload, the portal/history renderer, and tests
# share one contract). The *_total names are cumulative counters sampled as
# gauges — their max_ snapshot is the running total.
SERVING_ACTIVE_SLOTS = "serving_active_slots"
SERVING_QUEUE_DEPTH = "serving_queue_depth"
SERVING_PREFILL_REUSED_FRAC = "serving_prefill_reused_frac"
SERVING_SHED_TOTAL = "serving_shed_total"
SERVING_CANCELLED_TOTAL = "serving_cancelled_total"
SERVING_EXPIRED_TOTAL = "serving_expired_total"
SERVING_LOOP_RESTARTS = "serving_loop_restarts"
# latency gauges sampled from the observability histograms (tony_tpu/
# observability.py): quantiles at observation time, host-monotonic spans.
# The histograms themselves are exposed in full on GET /metrics; these
# gauge snapshots exist so the /stats + portal path needs no new shape.
SERVING_TTFT_P50_S = "serving_ttft_p50_s"
SERVING_TTFT_P99_S = "serving_ttft_p99_s"
SERVING_TPOT_P50_S = "serving_tpot_p50_s"
SERVING_TPOT_P99_S = "serving_tpot_p99_s"
SERVING_RETRY_AFTER_S = "serving_retry_after_s"
# request durability (events/journal.py + SlotServer replay — docs/
# serving.md "Request durability & replay"): admissions that resumed
# from a journaled/teacher-forced prefix instead of failing, and the
# emitted tokens carried across the death boundary
SERVING_REPLAYS_TOTAL = "serving_replays_total"
SERVING_REPLAYED_TOKENS_TOTAL = "serving_replayed_tokens_total"
# multi-model serving (models/registry.py): the info gauge (one series
# per registered model, value 1) that makes the model inventory
# scrapeable; the per-model partitions of the serving families carry a
# {model="..."} label next to the process-level unlabeled aggregates
# (docs/observability.md "Per-model labels")
SERVING_MODELS = "serving_models"
# speculative decoding in continuous batching (models/serving.py
# _spec_block): verify rounds dispatched, draft proposals verified vs
# accepted (host-observed, lag the device by the pipeline), the live
# autotuned gamma, and the acceptance-rate / verify-rounds-per-request
# histograms the autotuner and capacity planning read
SERVING_SPEC_ROUNDS_TOTAL = "serving_spec_rounds_total"
SERVING_SPEC_PROPOSED_TOKENS_TOTAL = "serving_spec_proposed_tokens_total"
SERVING_SPEC_ACCEPTED_TOKENS_TOTAL = "serving_spec_accepted_tokens_total"
SERVING_SPEC_GAMMA = "serving_spec_gamma"
SERVING_SPEC_ACCEPTANCE_RATE = "serving_spec_acceptance_rate"
SERVING_SPEC_VERIFY_ROUNDS = "serving_spec_verify_rounds"
# streaming delivery (tony_tpu/api/stream.py + SlotServer token
# streams — docs/serving.md "Streaming & OpenAI compatibility"): live
# SSE streams, streams ever opened, feeds that found the per-request
# chunk queue full (the consumer can't drain — coalesced, accounted,
# never dropped), and clients that vanished mid-stream (mapped onto
# cancel(); the freed slot's next occupant stays byte-identical)
SERVING_STREAMS_ACTIVE = "serving_streams_active"
SERVING_STREAMS_OPENED_TOTAL = "serving_streams_opened_total"
SERVING_STREAM_STALLS_TOTAL = "serving_stream_backpressure_stalls_total"
SERVING_STREAM_DISCONNECTS_TOTAL = "serving_stream_disconnects_total"
# paged-pool occupancy + KV block transfer (models/serving.py paged
# allocator and the disaggregated prefill/decode handoff — docs/
# serving.md "Disaggregated serving"): pool blocks by OWNER
# {state=free|slot|trie|shared}, finished prefills serialized for
# handoff, transfer payloads installed into the local pool, and
# payloads rejected as damaged (version/geometry/checksum — the router
# falls back to journal replay, i.e. re-prefill from the prompt)
SERVING_KV_POOL_BLOCKS = "serving_kv_pool_blocks"
SERVING_KV_EXPORTS_TOTAL = "serving_kv_exports_total"
SERVING_KV_IMPORTS_TOTAL = "serving_kv_imports_total"
SERVING_KV_IMPORT_REJECTS_TOTAL = "serving_kv_import_rejects_total"

# driver-side cluster telemetry (rendered by Driver.render_metrics on the
# driver's GET /metrics — docs/observability.md "Driver metrics"). Named
# here under the same one-contract rule as the SERVING_* gauges; the
# metrics-name lint test (tests/test_observability.py) asserts every
# constant in this module is rendered and documented.
DRIVER_GANG_LAUNCH_SECONDS = "driver_gang_launch_seconds"
DRIVER_HEARTBEAT_INTERVAL_SECONDS = "driver_heartbeat_interval_seconds"
DRIVER_TASK_RESTARTS_TOTAL = "driver_task_restarts_total"
DRIVER_TASK_ROLLS_TOTAL = "driver_task_rolls_total"
DRIVER_HEARTBEAT_EXPIRED_TOTAL = "driver_heartbeat_expired_total"
DRIVER_STRAGGLER_REGISTRATION_S = "driver_straggler_registration_s"
DRIVER_STRAGGLER_HEARTBEAT_S = "driver_straggler_heartbeat_s"
DRIVER_TASKS = "driver_tasks"
DRIVER_TASK_METRIC = "driver_task_metric"
DRIVER_TASK_SERVICE_PORT = "driver_task_service_port"
# elastic / preemption-tolerant training (docs/training-robustness.md):
# preemption drains relayed (budget-free relaunches, like rolls but
# fault-initiated), gang resizes (down on a worker lost past its budget,
# up when capacity returns), and per-task checkpoint recency — how many
# seconds of training each worker would lose if it died right now
DRIVER_PREEMPTIONS_TOTAL = "driver_preemptions_total"
DRIVER_GANG_RESIZES_TOTAL = "driver_gang_resizes_total"
DRIVER_CHECKPOINT_AGE_S = "driver_checkpoint_age_s"
# warm executor pool (tony_tpu/warmpool.py, docs/performance.md "Launch
# path"): ready standbys on the driver host's pool, task launches that
# ADOPTED a pre-warmed child (child_adopted spans), and launches that
# had the pool configured but fell back to a cold spawn
DRIVER_WARM_POOL_SIZE = "driver_warm_pool_size"
DRIVER_WARM_POOL_ADOPTIONS_TOTAL = "driver_warm_pool_adoptions_total"
DRIVER_WARM_POOL_MISSES_TOTAL = "driver_warm_pool_misses_total"
# control-plane recovery (docs/training-robustness.md "Control-plane
# recovery"): how many times this job's driver was restarted from its
# journal (driver.journal.jsonl replay), and how many live tasks those
# recoveries RE-ADOPTED (heartbeats re-attached by task id + attempt)
# instead of relaunching — the AM-restart "worker restarts = 0" bound
DRIVER_RECOVERIES_TOTAL = "driver_recoveries_total"
DRIVER_TASKS_READOPTED_TOTAL = "driver_tasks_readopted_total"
# closed-loop autoscaler + multi-tenant arbiter (tony_tpu/autoscale.py,
# docs/autoscaling.md): controller decisions (scale-ups launch a parked
# replica slot via warm-pool adoption, scale-downs SIGTERM-drain the
# least-loaded replica), the replica-count view {stat=current|min|
# max}, the newest observed control signals, and the shared-pool
# quota accounting — slots held per role {role,stat=held|quota}, pool
# free capacity, and the batch->interactive capacity flow (donations =
# batch workers preempt-drained to free slots for serving, reclaims =
# donated slots returned when traffic ebbed)
DRIVER_AUTOSCALE_SCALE_UPS_TOTAL = "driver_autoscale_scale_ups_total"
DRIVER_AUTOSCALE_SCALE_DOWNS_TOTAL = "driver_autoscale_scale_downs_total"
DRIVER_AUTOSCALE_REPLICAS = "driver_autoscale_replicas"
DRIVER_AUTOSCALE_TTFT_P99_S = "driver_autoscale_ttft_p99_s"
DRIVER_AUTOSCALE_QUEUE_DEPTH = "driver_autoscale_queue_depth"
DRIVER_QUOTA_POOL_SLOTS = "driver_quota_pool_slots"
DRIVER_QUOTA_POOL_FREE = "driver_quota_pool_free"
DRIVER_QUOTA_SLOTS = "driver_quota_slots"
DRIVER_QUOTA_DONATIONS_TOTAL = "driver_quota_donations_total"
DRIVER_QUOTA_RECLAIMS_TOTAL = "driver_quota_reclaims_total"
# fleet metrics pipeline + SLO engine (tony_tpu/metricshub.py +
# tony_tpu/slo.py, docs/observability.md "Metrics pipeline & SLO
# alerting"): failed scrapes per target {target} — from the watcher's
# fetch path and the hub's alike, so a half-blind control loop is
# visible — the hub's scrape/retention health, and the SLO families:
# burn rate per {slo,window_s}, budget remaining per {slo}, and the
# firing state per {slo,severity} burn-rate pair
DRIVER_AUTOSCALE_SCRAPE_FAILURES_TOTAL = (
    "driver_autoscale_scrape_failures_total")
DRIVER_METRICSHUB_SCRAPES_TOTAL = "driver_metricshub_scrapes_total"
DRIVER_METRICSHUB_SERIES = "driver_metricshub_series"
DRIVER_METRICSHUB_TARGETS = "driver_metricshub_targets"
DRIVER_SLO_BURN_RATE = "driver_slo_burn_rate"
DRIVER_SLO_ERROR_BUDGET_REMAINING = "driver_slo_error_budget_remaining"
DRIVER_SLO_ALERTS_FIRING = "driver_slo_alerts_firing"

# fleet-router exposition families (rendered by tony_tpu/router.py's GET
# /metrics; same one-contract rule — the metrics-name lint pins these to
# the router renderer and docs/observability.md, both directions)
ROUTER_REPLICA_UP = "router_replica_up"
ROUTER_REPLICAS_LIVE = "router_replicas_live"
# fleet-level ejection/readmission visibility (ISSUE 18): total known
# replicas, the live/ejected split as a labeled family, and the
# requests this router currently relays — the router-TIER saturation
# signal the autoscaler scrapes per front door
ROUTER_FLEET_SIZE = "router_fleet_size"
ROUTER_REPLICAS = "router_replicas"
ROUTER_RELAY_INFLIGHT = "router_relay_inflight"
ROUTER_REQUESTS_TOTAL = "router_requests_total"
ROUTER_RETRIES_TOTAL = "router_retries_total"
ROUTER_SHED_TOTAL = "router_shed_total"
ROUTER_FAILED_TOTAL = "router_requests_failed_total"
ROUTER_EJECTIONS_TOTAL = "router_ejections_total"
ROUTER_ROUTING_SECONDS = "router_routing_decision_seconds"
ROUTER_E2E_SECONDS = "router_request_seconds"
ROUTER_AFFINITY_HITS_TOTAL = "router_affinity_hits_total"
ROUTER_AFFINITY_REQUESTS_TOTAL = "router_affinity_requests_total"
ROUTER_AFFINITY_HIT_RATIO = "router_affinity_hit_ratio"
# replay-aware failover: mid-request resubmissions to another replica
# after a transport failure/ejection, carrying the emitted prefix the
# router last learned from /progress (resume_tokens)
ROUTER_FAILOVERS_TOTAL = "router_failovers_total"
# streaming pass-through (docs/serving.md "Streaming & OpenAI
# compatibility"): live relayed SSE streams, tokens forwarded through
# them, mid-stream failovers where the resume prefix was HARVESTED
# from the relayed stream itself (no /progress poll needed), and
# front-door clients that vanished mid-relay
ROUTER_STREAMS_ACTIVE = "router_streams_active"
ROUTER_STREAMED_TOKENS_TOTAL = "router_streamed_tokens_total"
ROUTER_STREAM_FAILOVERS_TOTAL = "router_stream_failovers_total"
ROUTER_STREAM_DISCONNECTS_TOTAL = "router_stream_disconnects_total"
# 1 while driver discovery is flying blind (driver.json missing/stale,
# the RPC endpoint refusing, or an implausible empty fleet inside the
# drop grace) and the router is serving its LAST-KNOWN fleet — the
# control-plane-outage visibility gauge (0 with a live driver view)
ROUTER_DISCOVERY_STALE = "router_discovery_stale"
# disaggregated prefill/decode serving (docs/serving.md "Disaggregated
# serving"): requests the router attempted to split across a prefill
# specialist and a decode replica, handoffs that completed (prefill leg
# -> /kv/import on the decode leg), and attempts that fell back to the
# classic single-replica path (no specialist live, prefill leg failed,
# handoff aged out, or the decode import was refused — fallback
# re-prefills from the prompt, so correctness only costs recompute)
ROUTER_DISAGG_REQUESTS_TOTAL = "router_disagg_requests_total"
ROUTER_DISAGG_HANDOFFS_TOTAL = "router_disagg_handoffs_total"
ROUTER_DISAGG_FALLBACKS_TOTAL = "router_disagg_fallbacks_total"

# distributed tracing (docs/observability.md "Distributed tracing"):
# where router-attributed fleet time goes, one histogram per leg —
# leg="relay" is the classic single-replica relay POST, "prefill" the
# disagg leg-1 wall, "transfer" submit→first-relayed-frame of a
# streamed /kv/import leg-2 (payload ship + install), "decode" the
# rest (a buffered leg-2 books entirely as decode: no frame instants)
ROUTER_LEG_SECONDS = "router_leg_seconds"

# executor-accumulator metric names (ride update_metrics pushes the same
# way memory_rss_mb does; surface on the driver /metrics as
# driver_task_metric{name="max_..."} gauges and in TASK_FINISHED events)
HEARTBEAT_RTT_MS = "heartbeat_rtt_ms"
HEARTBEATS_MISSED = "heartbeats_missed"
CHILD_ALIVE = "child_alive"
STEP_TIME_MEAN_S = "step_time_mean_s"
STEP_TIME_P50_S = "step_time_p50_s"
STEP_TIME_P99_S = "step_time_p99_s"
STEPS_PER_SEC = "steps_per_sec"
# compile telemetry sampled from the training child's StepTimer JSONL
# (observability.CompileTelemetry snapshot embedded per record): how much
# wall time XLA compilation ate in that worker, and whether it kept
# compiling after warmup — a nonzero xla_recompiles_post_warm on a
# steady-state training job is the shape-leak bug surfacing centrally
XLA_COMPILES = "xla_compiles"
XLA_COMPILE_TIME_S = "xla_compile_time_s"
XLA_RECOMPILES_POST_WARM = "xla_recompiles_post_warm"
# training progress + checkpoint recency sampled from the same JSONL
# records (StepTimer ``tick(train_step=...)`` / ``note_checkpoint``):
# the driver's chaos/straggler/elastic machinery keys off train_step,
# and ckpt_unix_ts renders centrally as driver_checkpoint_age_s
TRAIN_STEP = "train_step"
CKPT_STEP = "ckpt_step"
CKPT_UNIX_TS = "ckpt_unix_ts"
# note()-d / sampled names that are cumulative totals, not per-event
# samples: they take set semantics (latest total) in the accumulator —
# averaging a monotone counter's successive values is meaningless
_COUNTER_NOTES = frozenset({HEARTBEATS_MISSED, XLA_COMPILES,
                            XLA_COMPILE_TIME_S, XLA_RECOMPILES_POST_WARM,
                            TRAIN_STEP, CKPT_STEP, CKPT_UNIX_TS})


def _proc_tree_rss_mb(root_pid: int) -> float:
    """Sum RSS over root_pid and its descendants via /proc (the reference uses
    YARN's ResourceCalculatorProcessTree for the same walk). Uses the C++
    sampler (native/src/procstats.cc) when built; Python walk otherwise."""
    try:
        from .native import proc_tree_rss_mb as native_rss

        value = native_rss(root_pid)
        if value is not None:
            return value
    except Exception:
        pass
    children: dict[int, list[int]] = {}
    pids = []
    try:
        for entry in os.listdir("/proc"):
            if not entry.isdigit():
                continue
            pid = int(entry)
            try:
                with open(f"/proc/{pid}/stat") as f:
                    fields = f.read().split()
                ppid = int(fields[3])
            except (OSError, IndexError, ValueError):
                continue
            pids.append(pid)
            children.setdefault(ppid, []).append(pid)
    except OSError:
        return 0.0
    tree, stack = set(), [root_pid]
    while stack:
        pid = stack.pop()
        if pid in tree:
            continue
        tree.add(pid)
        stack.extend(children.get(pid, []))
    total_kb = 0
    for pid in tree:
        try:
            with open(f"/proc/{pid}/status") as f:
                for line in f:
                    if line.startswith("VmRSS:"):
                        total_kb += int(line.split()[1])
                        break
        except (OSError, ValueError):
            continue
    return total_kb / 1024.0


class MetricsAccumulator:
    """max + running average per metric — reference
    TaskMonitor.setAvgMetrics/setMaxMetrics (TaskMonitor.java:101-170)."""

    def __init__(self) -> None:
        self._count: dict[str, int] = {}
        self._avg: dict[str, float] = {}
        self._max: dict[str, float] = {}

    def observe(self, name: str, value: float) -> None:
        n = self._count.get(name, 0)
        self._avg[name] = (self._avg.get(name, 0.0) * n + value) / (n + 1)
        self._count[name] = n + 1
        self._max[name] = max(self._max.get(name, float("-inf")), value)

    def set(self, name: str, value: float) -> None:
        """Overwrite semantics for cumulative counters: averaging a
        monotone total's successive values yields a meaningless number,
        so both snapshots report the latest total."""
        self._count[name] = 1
        self._avg[name] = value
        self._max[name] = value

    def snapshot(self) -> list[dict[str, Any]]:
        out = []
        for name in sorted(self._count):
            out.append({"name": f"max_{name}", "value": self._max[name]})
            out.append({"name": f"avg_{name}", "value": round(self._avg[name], 3)})
        return out


class TaskMonitor:
    """Executor-side sampler + the executor->driver telemetry channel.

    Beyond the reference's resource sampling, each ``update_metrics``
    push also carries (a) externally ``note()``-d metrics — the
    Heartbeater feeds RPC round-trip time and a missed-beat counter —
    (b) the child process's liveness (``child_alive``), (c) step-time
    quantiles read from the training child's StepTimer JSONL
    (``set_step_log``; TONY_STEP_LOG env contract), and (d) executor-
    side lifecycle spans (``add_span``: work_dir_ready, child_spawned,
    child_exited) that the driver merges into the task's TaskTrace."""

    def __init__(self, rpc_client, task_id: str, interval_s: float = 5.0):
        self._rpc = rpc_client
        self._task_id = task_id
        self._interval = interval_s
        self._acc = MetricsAccumulator()
        self._ctx = None
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        # refresh runs on the monitor thread while note()/add_span() come
        # from the heartbeater and the executor main thread
        self._mlock = threading.Lock()
        self._spans: list[list] = []        # [name, unix_ts] (+ attrs)
        self._step_log: str | None = None

    def set_context(self, ctx) -> None:
        self._ctx = ctx

    def set_step_log(self, path: str | None) -> None:
        """Where the training child's StepTimer writes its JSONL; the
        sampler folds the newest record's quantiles into the push."""
        self._step_log = path

    def note(self, name: str, value: float) -> None:
        """Observe an externally-measured metric (heartbeat RTT, missed
        beats) into the accumulator; rides the next push. Cumulative
        counters take set semantics — see MetricsAccumulator.set."""
        with self._mlock:
            if name in _COUNTER_NOTES:
                self._acc.set(name, value)
            else:
                self._acc.observe(name, value)

    def add_span(self, name: str, t: float | None = None) -> None:
        """Record an executor-side lifecycle span (wall-clock unix
        seconds — the driver re-anchors onto its monotonic timeline)."""
        with self._mlock:
            self._spans.append([name, time.time() if t is None else t])

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self._loop, name="task-monitor", daemon=True
        )
        self._thread.start()

    def _loop(self) -> None:
        while not self._stop.wait(self._interval):
            try:
                self.refresh()
            except Exception:
                log.exception("metrics refresh failed")

    def _sample_step_log(self) -> dict[str, float]:
        """Newest StepTimer record -> step-time metrics (per-worker step
        skew becomes centrally visible on the driver's /metrics)."""
        if not self._step_log:
            return {}
        try:
            # only the newest record matters: read the file's tail, not
            # the whole thing (it grows for the life of the training run)
            with open(self._step_log, "rb") as f:
                f.seek(0, os.SEEK_END)
                f.seek(max(0, f.tell() - 8192))
                lines = f.read().splitlines()
        except OSError:
            return {}
        for raw in reversed(lines):     # torn-tail tolerant, like traces
            try:
                rec = json.loads(raw)
            except ValueError:
                continue
            out = {}
            for src, dst in (("mean_step_s", STEP_TIME_MEAN_S),
                             ("p50_s", STEP_TIME_P50_S),
                             ("p99_s", STEP_TIME_P99_S),
                             ("steps_per_sec", STEPS_PER_SEC),
                             ("xla_compiles", XLA_COMPILES),
                             ("xla_compile_time_s", XLA_COMPILE_TIME_S),
                             ("xla_recompiles_post_warm",
                              XLA_RECOMPILES_POST_WARM),
                             ("train_step", TRAIN_STEP),
                             ("last_ckpt_step", CKPT_STEP),
                             ("last_ckpt_ts", CKPT_UNIX_TS),
                             # the chip owner's own sample (StepTimer)
                             (TPU_DUTY_CYCLE, TPU_DUTY_CYCLE),
                             (TPU_HBM_USED, TPU_HBM_USED),
                             (TPU_HBM_PEAK, TPU_HBM_PEAK),
                             (TPU_HBM_LIVE, TPU_HBM_LIVE)):
                if isinstance(rec.get(src), (int, float)):
                    out[dst] = float(rec[src])
            return out
        return {}

    def refresh(self) -> None:
        proc = getattr(self._ctx, "child_process", None) if self._ctx else None
        child_alive = proc is not None and proc.poll() is None
        root = proc.pid if child_alive else os.getpid()
        rss = _proc_tree_rss_mb(root)
        tpu = sample_tpu_metrics()
        steps = self._sample_step_log()
        with self._mlock:
            self._acc.observe(MEMORY_RSS, rss)
            if proc is not None:
                self._acc.observe(CHILD_ALIVE, 1.0 if child_alive else 0.0)
            for name, value in {**tpu, **steps}.items():
                if name in _COUNTER_NOTES:
                    self._acc.set(name, value)
                else:
                    self._acc.observe(name, value)
            metrics = self._acc.snapshot()
            spans = [list(s) for s in self._spans]
        # adapter-marked spans (child_spawned) live on the TaskContext
        spans += [list(s) for s in getattr(self._ctx, "spans", []) or []]
        spans.sort(key=lambda s: s[1])
        try:
            self._rpc.call(
                "update_metrics", task_id=self._task_id, metrics=metrics,
                spans=spans,
            )
        except Exception as e:
            log.warning("metrics push failed: %s", e)

    def stop(self) -> None:
        self._stop.set()
        # final flush so short tasks still report
        try:
            self.refresh()
        except Exception:
            pass


def parse_tpu_metric_values(name: str, values: list[str]) -> dict[str, float]:
    """Reduce one libtpu metric's per-chip string list to named floats.

    The SDK contract (libtpu.sdk.tpumonitoring.get_metric(name).data()):
    `duty_cycle_pct` is one percentage string per chip; `hbm_capacity_usage`
    is one integer-bytes string per chip. An empty list means the host's TPU
    runtime isn't serving metrics (e.g. no local chips) — sample nothing
    rather than zeros."""
    if not values:
        return {}
    nums = [float(v) for v in values]
    if name == "duty_cycle_pct":
        return {TPU_DUTY_CYCLE: sum(nums) / len(nums)}
    if name == "hbm_capacity_usage":
        return {TPU_HBM_USED: sum(nums) / 1e6}
    raise ValueError(f"unmapped TPU metric {name!r}")


# libtpu metric names sampled per refresh (of tpumonitoring.list_supported_
# metrics(), verified on a v5e VM: tensorcore_util, duty_cycle_pct,
# hbm_capacity_total/usage, hlo_execution_timing, ...)
_SAMPLED_TPU_METRICS = ("duty_cycle_pct", "hbm_capacity_usage")


def _live_tpu_devices() -> list:
    """TPU devices of a jax client ALREADY initialised in this process;
    [] when there is none. Never imports jax and never initialises a
    backend: a chip belongs to one process, and ``local_devices()`` in a
    process without a live client (the executor, whose child owns the
    chip) would open a second TPU client and contend with the owner."""
    import sys

    jax = sys.modules.get("jax")
    if jax is None:
        return []
    # only proceed when the private bridge registry POSITIVELY confirms an
    # initialized backend (present in jax 0.9.0). If a version bump moves
    # the module or the attribute, fail SAFE and report nothing.
    bridge = getattr(getattr(jax, "_src", None), "xla_bridge", None)
    if not getattr(bridge, "_backends", None):
        return []
    try:
        return [d for d in jax.local_devices()
                if getattr(d, "platform", "") == "tpu"]
    except Exception:
        return []


def _jax_memory_stats() -> dict[str, float]:
    """HBM occupancy from this process's own live jax client: per-device
    ``memory_stats()``, summed over TPU devices — same semantics as the
    SDK's hbm_capacity_usage channel."""
    import sys

    devices = _live_tpu_devices()
    if not devices:
        return {}        # never report host/GPU memory under TPU names
    jax = sys.modules["jax"]
    used, peak = [], []
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        # memory_stats() returns None where the runtime serves no
        # allocator stats: OMIT the series rather than render zeros a
        # dashboard would read as "device empty"
        if stats and "bytes_in_use" in stats:
            used.append(float(stats["bytes_in_use"]))
        if stats and "peak_bytes_in_use" in stats:
            peak.append(float(stats["peak_bytes_in_use"]))
    if used:
        out = {TPU_HBM_USED: sum(used) / 1e6}
        if peak:
            # high-watermark occupancy since client start — the capacity-
            # planning number a point-in-time gauge can't give
            out[TPU_HBM_PEAK] = sum(peak) / 1e6
        return out
    # last resort (a runtime whose memory_stats() is None):
    # framework-tracked live buffers — a floor on occupancy, honestly named
    try:
        total = sum(
            a.nbytes for a in jax.live_arrays()
            if getattr(a, "nbytes", None) is not None
        )
    except Exception:
        return {}
    if total <= 0:
        return {}
    return {TPU_HBM_LIVE: total / 1e6}


def sample_tpu_metrics(explain: bool = False):
    """TPU counters of the chip THIS process owns: libtpu's SDK monitoring
    API (duty cycle, HBM in use) plus the live jax client's
    ``memory_stats()``. Plays the role of the reference's nvidia-smi XML
    sampling (util/gpu/GpuDiscoverer.java:41-59 + the fixture-tested
    GpuDeviceInformation parser) — but reads an in-process API instead of
    forking and parsing XML.

    A process without an initialised TPU client gets {} and never touches
    libtpu: loading it takes the chip's lock (or blocks on it), and the
    executor's monitor runs before and while its child holds the chip.
    The child's numbers reach the executor through the step log instead
    (train.profiling.StepTimer writes them, TaskMonitor reads them).

    ``explain=True`` returns ``(metrics, reason)`` where ``reason`` (str |
    None) says WHY the sample is empty — an artifact recording plain ``{}``
    cannot distinguish "the channel is broken" from "this host's runtime
    serves no local metrics"."""
    if not _live_tpu_devices():
        why = ("this process holds no initialised TPU client: only the "
               "process that owns the chip loads libtpu")
        return ({}, why) if explain else {}
    reasons: list[str] = []
    out: dict[str, float] = {}
    try:
        from libtpu.sdk import tpumonitoring  # present on TPU VMs
    except Exception as e:  # ImportError, or OSError from the .so loader
        reasons.append(f"libtpu.sdk.tpumonitoring not importable: {e!r}")
        tpumonitoring = None
    if tpumonitoring is not None:
        for name in _SAMPLED_TPU_METRICS:
            try:
                values = tpumonitoring.get_metric(name).data()
                parsed = parse_tpu_metric_values(name, values)
                if not parsed:
                    reasons.append(
                        f"{name}: runtime returned no per-chip data")
                out.update(parsed)
            except Exception as e:
                # per-metric, logged: format drift or a runtime that isn't
                # serving stays visible without ever failing the sampler
                # (TaskMonitor.refresh and bench rely on best-effort here)
                log.debug("tpu metric %s unavailable: %s", name, e)
                reasons.append(f"{name}: {e!r}")
    if TPU_HBM_USED not in out:
        out.update(_jax_memory_stats())
    if explain:
        return out, ("; ".join(reasons) if not out and reasons else None)
    return out
