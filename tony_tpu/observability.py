"""Request-level serving telemetry: traces, histograms, exposition.

The reference TonY ships metrics/history/portal plumbing but no tracing
subsystem (SURVEY.md §5); after continuous batching, the prefix cache,
and the failure model, the serving stack's behavior was visible only
through cumulative counters — no way to answer "what is p99 TTFT right
now" or "where did request 1234's 3 seconds go". This module is the
shared observability layer every serving component feeds:

- **``RequestTrace``** — per-request lifecycle spans on the HOST
  monotonic clock (``time.monotonic()``; never device time — decode is
  dispatched asynchronously, so span timestamps mark when the *host*
  observed each transition, which for ``first_token``/``finished`` is
  the event-log replay position in ``SlotServer._process``, lagging the
  device by up to ``pipeline_depth`` blocks). Span order for a served
  request: ``submitted -> admitted -> prefill_done -> first_token ->
  finished``; requests that never serve end at ``cancelled``,
  ``expired``, ``shed``, or ``failed`` instead. A request that survived
  a loop crash via journal replay carries a mid-life ``replayed`` mark
  (attrs ``replays``/``replayed_tokens``) followed by a fresh
  admitted/prefill chain — the request-level analogue of TaskTrace's
  ``restarted`` repeat-chain. Dumped as JSONL next to
  the job's history events (events/trace.py) so the portal can render a
  per-request waterfall.
- **``TaskTrace``** — the same span machinery at TASK granularity for
  the job-orchestration path (driver.py): ``requested -> allocated ->
  launched -> registered -> first_heartbeat -> running``, executor-side
  enrichment spans shipped over the metrics RPC, ``restarted`` marks,
  and a terminal from ``TASK_TERMINAL_SPANS``. Dumped as
  ``tasks.trace.jsonl`` next to the job history; the portal renders the
  gang-launch waterfall at ``/tasks/<app_id>``.
- **``Histogram``** — fixed log-spaced buckets, mergeable, with
  quantile estimation. Fixed buckets (vs t-digest et al) because they
  merge across servers by integer addition and render directly as
  Prometheus cumulative buckets.
- **``ServingTelemetry``** — the named latency histograms (TTFT, TPOT,
  queue wait, e2e, prefill dispatch, decode-block dispatch, loop turn)
  fed from trace spans; ``SlotServer.stats()`` and ``/metrics`` both
  read it.
- **``ServiceRateEstimator``** — EWMA of observed per-request service
  time; turns "queue is full" into a data-driven ``Retry-After``
  (seconds until a queue seat frees) instead of a constant 1s.
- **``PromRenderer``** — Prometheus text exposition (``# HELP`` /
  ``# TYPE`` format, version 0.0.4) so any scraper works with no
  client library; ``ServeApp`` and the portal share it.
- **``DispatchTracker``** — device-time attribution: every dispatched
  program registers an output buffer, and a background reaper thread
  ``block_until_ready``s them IN DISPATCH ORDER off the hot path,
  yielding dispatch→ready latency histograms per program kind, an
  in-flight-dispatch depth gauge, and per-dispatch ready instants the
  serving loop turns into a measured ``device_lag`` on request traces
  (the host-observation lag that used to be documented only as "up to
  ``pipeline_depth`` blocks").
- **``CompileTelemetry``** — XLA compile-time visibility via
  ``jax.monitoring.register_event_duration_secs_listener``: a compile
  histogram + counter, and a post-warmup recompile-storm warning (a
  serving loop that recompiles after warmup is silently re-paying
  seconds per dispatch — the classic shape-leak bug).
- **``phase``** — the serving turn's phases (``PHASES``) as spans in
  the PROFILER's own trace, on the device trace's clock: everything
  above runs on ``time.monotonic()`` beside the device trace, these
  lie in it, so a capture shows what the host was doing under each
  device gap.

See docs/observability.md for metric names, the trace schema, and a
scrape example.
"""

from __future__ import annotations

import bisect
import collections
import contextlib
import functools
import hashlib
import logging
import math
import os
import re
import threading
import time
from dataclasses import dataclass, field

log = logging.getLogger(__name__)

# terminal span names: exactly one ends every trace
TERMINAL_SPANS = ("finished", "cancelled", "expired", "shed", "failed")

# terminal spans of an ORCHESTRATION task's lifecycle trace (TaskTrace):
# the driver-side analogue of the request terminals above. "finished" =
# container exited 0, "failed" = nonzero exit (restart budget spent),
# "killed" = torn down with the job, "heartbeat_expired" = deemed dead
# after missing the liveness budget with no restarts left.
TASK_TERMINAL_SPANS = ("finished", "failed", "killed", "heartbeat_expired")


class Histogram:
    """Fixed log-spaced-bucket histogram of non-negative values.

    ``per_decade`` buckets between successive powers of ten from ``lo``
    to ``hi`` (values above ``hi`` land in the +Inf overflow bucket,
    values at or below ``lo`` in the first). Bucket ``i`` counts values
    ``v <= bounds[i]`` exclusive of earlier buckets — the same
    upper-bound (``le``) semantics Prometheus cumulative buckets use,
    so exposition is a running sum, no re-binning.

    ``merge`` adds another histogram's counts (bounds must match) —
    per-slot or per-server histograms aggregate by addition.
    ``quantile`` linearly interpolates inside the containing bucket
    (the first bucket's lower edge is 0; the overflow bucket reports
    its lower edge, i.e. ``hi`` — the honest answer when the tail is
    unbounded)."""

    __slots__ = ("bounds", "counts", "count", "sum")

    def __init__(self, lo: float = 1e-3, hi: float = 120.0,
                 per_decade: int = 5):
        if not (0 < lo < hi):
            raise ValueError(f"need 0 < lo < hi, got {lo}, {hi}")
        n = int(math.ceil(math.log10(hi / lo) * per_decade)) + 1
        bounds = [lo * 10 ** (i / per_decade) for i in range(n)]
        # the log series rarely lands on hi exactly; clamp so the last
        # finite bucket ends AT hi and anything above is +Inf, as the
        # contract above says
        self.bounds = [b for b in bounds if b < hi] + [float(hi)]
        self.counts = [0] * (n + 1)         # +1: the +Inf overflow bucket
        self.count = 0
        self.sum = 0.0

    def observe(self, value: float) -> None:
        self.counts[bisect.bisect_left(self.bounds, value)] += 1
        self.count += 1
        self.sum += value

    def merge(self, other: "Histogram") -> None:
        if other.bounds != self.bounds:
            raise ValueError("cannot merge histograms with different buckets")
        for i, c in enumerate(other.counts):
            self.counts[i] += c
        self.count += other.count
        self.sum += other.sum

    def quantile(self, q: float) -> float:
        """q in [0, 1] -> estimated value; 0.0 on an empty histogram."""
        if not 0 <= q <= 1:
            raise ValueError(f"quantile wants q in [0, 1], got {q}")
        if self.count == 0:
            return 0.0
        rank = q * self.count
        seen = 0
        for i, c in enumerate(self.counts):
            if seen + c >= rank and c > 0:
                lo = self.bounds[i - 1] if i > 0 else 0.0
                hi = (self.bounds[i] if i < len(self.bounds)
                      else self.bounds[-1])
                if hi <= lo:                # overflow bucket: lower edge
                    return lo
                return lo + (hi - lo) * max(0.0, rank - seen) / c
            seen += c
        return self.bounds[-1]

    @property
    def mean(self) -> float:
        return self.sum / self.count if self.count else 0.0

    def snapshot(self) -> dict:
        """The /stats payload for one histogram: count + headline
        quantiles (bucket-resolution estimates, see ``quantile``)."""
        return {
            "count": self.count,
            "mean_s": round(self.mean, 6),
            "p50_s": round(self.quantile(0.50), 6),
            "p90_s": round(self.quantile(0.90), 6),
            "p99_s": round(self.quantile(0.99), 6),
        }

    def state(self) -> dict:
        """Full serializable state (bounds + raw bucket counts) — the
        persistence counterpart of ``snapshot()``'s lossy quantile view.
        ``restore()`` on a fresh histogram resumes the cumulative buckets
        exactly, so a server restart doesn't zero /metrics."""
        return {"bounds": list(self.bounds), "counts": list(self.counts),
                "count": self.count, "sum": self.sum}

    def restore(self, state: dict) -> None:
        """Adopt a ``state()`` dump. Bounds must match this histogram's
        construction — resuming into different buckets would silently
        re-bin history."""
        if list(state["bounds"]) != self.bounds:
            raise ValueError("cannot restore state with different buckets")
        if len(state["counts"]) != len(self.counts):
            raise ValueError("cannot restore state with different buckets")
        self.counts = [int(c) for c in state["counts"]]
        self.count = int(state["count"])
        self.sum = float(state["sum"])


# distributed-tracing header contract: a sender stamps
# ``X-Tony-Trace: <trace_id>:<span_id>`` on every outbound hop; the
# receiver adopts the trace_id, records the sender's span_id as its
# parent_span_id, and mints a fresh span_id for its own work. Front
# doors echo ``X-Tony-Trace-Id: <trace_id>`` back to the client so a
# request can be looked up later. docs/observability.md "Distributed
# tracing" documents the contract; the api-contract lint pins it.
TRACE_HEADER = "X-Tony-Trace"
TRACE_ID_RESPONSE_HEADER = "X-Tony-Trace-Id"

_TRACE_TOKEN = re.compile(r"^[0-9a-f]{8,32}$")


class TraceContext:
    """One hop's identity inside a distributed trace.

    ``trace_id`` names the whole request across tiers; ``span_id`` names
    THIS process's work on it; ``parent_span_id`` names the span that
    caused it (None at the root). The context travels between processes
    as the ``X-Tony-Trace`` header (``trace_id:span_id``) and inside
    durable payloads (journal entries, KV handoff ``entry`` dicts) as
    ``as_dict()``. Identity is carried in ``RequestTrace.attrs`` — span
    records stay self-describing JSONL lines that ``TraceCollector``
    can merge by trace_id with no side tables.
    """

    __slots__ = ("trace_id", "span_id", "parent_span_id")

    def __init__(self, trace_id: str, span_id: str,
                 parent_span_id: str | None = None):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_span_id = parent_span_id

    @staticmethod
    def _new_id() -> str:
        return os.urandom(8).hex()

    @classmethod
    def mint(cls) -> "TraceContext":
        """A fresh root context — minted at a front door when the client
        sent no trace header."""
        return cls(cls._new_id(), cls._new_id(), None)

    @classmethod
    def for_request_id(cls, request_id: str) -> "TraceContext":
        """A root context whose trace_id is DERIVED from the client's
        idempotency key. Two doors that never exchanged a byte (the
        cross-door failover resubmit: door0 died before responding, the
        client re-POSTs the same ``request_id`` at door1) still land in
        the same trace — the distributed-tracing analogue of the
        portable ``req:<id>`` progress-key discipline."""
        digest = hashlib.sha256(
            b"tony-trace:" + request_id.encode("utf-8", "replace"))
        return cls(digest.hexdigest()[:16], cls._new_id(), None)

    @classmethod
    def from_header(cls, value: str | None) -> "TraceContext | None":
        """Parse an inbound ``X-Tony-Trace`` header into the RECEIVER's
        context: same trace, sender's span as parent, fresh span_id.
        Malformed or absent headers yield None (caller mints a root) —
        a garbled proxy header must never crash the request path."""
        if not value:
            return None
        trace_id, sep, span_id = value.strip().partition(":")
        if not sep or not _TRACE_TOKEN.match(trace_id) \
                or not _TRACE_TOKEN.match(span_id):
            return None
        return cls(trace_id, cls._new_id(), span_id)

    @classmethod
    def from_dict(cls, d: dict | None) -> "TraceContext | None":
        """Rehydrate a context persisted via ``as_dict()`` (journal
        entry, KV handoff). Returns the SAME span identity — journal
        recovery of a dead attempt deliberately reuses the dead span's
        ids so its children are never orphaned; the merge-time fence
        dedupes any double-written records."""
        if not isinstance(d, dict):
            return None
        trace_id, span_id = d.get("trace_id"), d.get("span_id")
        if not isinstance(trace_id, str) or not isinstance(span_id, str):
            return None
        parent = d.get("parent_span_id")
        return cls(trace_id, span_id,
                   parent if isinstance(parent, str) else None)

    def child(self) -> "TraceContext":
        """The context a downstream hop should run under: same trace,
        this span as parent, fresh span_id."""
        return type(self)(self.trace_id, self._new_id(), self.span_id)

    def to_header(self) -> str:
        return f"{self.trace_id}:{self.span_id}"

    def as_dict(self) -> dict:
        return {"trace_id": self.trace_id, "span_id": self.span_id,
                "parent_span_id": self.parent_span_id}


class RequestTrace:
    """One request's lifecycle spans: (name, t_monotonic) pairs in the
    order the HOST observed them, plus free-form ``attrs``
    (prefix_hit_blocks, n_tokens, finish_reason, ...). ``submitted_unix``
    anchors the monotonic timeline to wall-clock for display only —
    durations always come from the monotonic spans."""

    __slots__ = ("id", "spans", "attrs")

    # the span names that may end a trace of this kind; subclasses with a
    # different lifecycle vocabulary (TaskTrace) override
    TERMINALS = TERMINAL_SPANS

    def __init__(self, request_id):
        self.id = request_id
        self.spans: list[tuple[str, float]] = []
        self.attrs: dict = {"submitted_unix": time.time()}

    def mark(self, name: str, t: float | None = None) -> None:
        self.spans.append((name, time.monotonic() if t is None else t))

    def bind(self, ctx: "TraceContext | None") -> "RequestTrace":
        """Attach a distributed-trace identity. Carried in ``attrs`` (no
        schema change to the span list) so every sealed JSONL record is
        self-describing for cross-tier merge. No-op when ctx is None —
        single-tier deployments keep their old trace shape."""
        if ctx is not None:
            self.attrs.update(ctx.as_dict())
        return self

    @property
    def ctx(self) -> "TraceContext | None":
        """The bound TraceContext, if any (inverse of ``bind``)."""
        return TraceContext.from_dict(self.attrs)

    def t(self, name: str) -> float | None:
        for n, t in self.spans:
            if n == name:
                return t
        return None

    def dur(self, a: str, b: str) -> float | None:
        """Seconds from span ``a`` to span ``b``; None unless both
        were recorded."""
        ta, tb = self.t(a), self.t(b)
        return None if ta is None or tb is None else tb - ta

    @property
    def terminal(self) -> str | None:
        if self.spans and self.spans[-1][0] in type(self).TERMINALS:
            return self.spans[-1][0]
        return None

    def last_t(self, name: str) -> float | None:
        """Newest occurrence of span ``name`` — a restarted lifecycle
        records the same span once per attempt, and attempt-relative
        durations must measure from the latest one."""
        for n, t in reversed(self.spans):
            if n == name:
                return t
        return None

    def to_dict(self) -> dict:
        return {"id": self.id,
                "spans": [[n, round(t, 6)] for n, t in self.spans],
                "attrs": dict(self.attrs)}


class TaskTrace(RequestTrace):
    """One orchestration task's lifecycle spans, id = ``role:index``.

    Same host-monotonic clock contract as RequestTrace, recorded on the
    DRIVER's clock: ``requested -> allocated -> launched -> registered ->
    first_heartbeat -> running`` (running = the gang barrier opened for
    this task), executor-shipped enrichment spans (``work_dir_ready``,
    ``child_spawned``, ``child_exited`` — wall-clock instants re-anchored
    onto the driver's monotonic timeline at receipt, so cross-host NTP
    skew shifts them but never reorders driver-observed spans), zero or
    more ``restarted`` spans (one per spent restart-budget unit; the
    whole requested->registered chain repeats after each), zero or more
    budget-FREE relaunch marks — ``rolled`` (deliberate roll),
    ``preempting``/``preempted`` (preemption drain), ``resized``
    (elastic gang re-formation, attrs carry the generation) — each also
    followed by a fresh attempt chain, and exactly one terminal from
    TASK_TERMINAL_SPANS."""

    __slots__ = ()

    TERMINALS = TASK_TERMINAL_SPANS


# histogram name -> HELP text; the keys are the ``ServingTelemetry``
# vocabulary and (with _s -> _seconds) the /metrics series names
TELEMETRY_HISTOGRAMS = {
    "ttft_s": "time from submit to the host observing the first emitted "
              "token (host monotonic clock; lags the device by the "
              "processing pipeline)",
    "tpot_s": "mean time per output token after the first, per request",
    "queue_wait_s": "time from submit to admission into a slot",
    "e2e_s": "time from submit to the terminal span (any finish reason)",
    "prefill_s": "admission-burst prefill dispatch time (host-side)",
    "decode_block_s": "host dispatch time of one decode block (async "
                      "dispatch, not device execution time)",
    "loop_turn_s": "one ServeApp scheduling turn",
    "device_lag_s": "measured lag between a decode block becoming ready "
                    "on device and the host observing its tokens (the "
                    "pipeline-depth lag, now measured per block instead "
                    "of bounded on paper)",
    "replay_catchup_s": "time from a reset-replay requeue (the "
                        "'replayed' span) to the request's terminal — "
                        "what a loop crash actually cost the request in "
                        "latency instead of failing it",
    "stream_itl_s": "inter-token latency OBSERVED AT THE EMISSION "
                    "POINT: the gap between consecutive token-chunk "
                    "feeds into a request's TokenStream (tokens inside "
                    "one processed block arrive together, so this is "
                    "the between-chunk gap a streaming client actually "
                    "waits — the worst-case per-token spacing)",
}


class ServingTelemetry:
    """The serving path's latency histograms, fed from trace spans (and
    directly for dispatch timings). One instance per SlotServer;
    everything here is host bookkeeping — no locks (callers serialize
    on the serving lock) and no device interaction."""

    def __init__(self):
        self.hist = {name: Histogram() for name in TELEMETRY_HISTOGRAMS}

    def observe(self, name: str, seconds: float) -> None:
        self.hist[name].observe(seconds)

    def observe_trace(self, trace: RequestTrace) -> None:
        """Fold one finished trace into the histograms. Only spans that
        were actually recorded contribute — a shed request feeds e2e
        (its rejection latency) but no ttft."""
        for name, a, b in (("queue_wait_s", "submitted", "admitted"),
                           ("prefill_s", "admitted", "prefill_done"),
                           ("ttft_s", "submitted", "first_token")):
            d = trace.dur(a, b)
            if d is not None:
                self.hist[name].observe(max(0.0, d))
        if trace.spans:
            e2e = trace.spans[-1][1] - trace.spans[0][1]
            self.hist["e2e_s"].observe(max(0.0, e2e))
            # replay catch-up: the NEWEST 'replayed' mark (a request can
            # be replayed more than once) to the terminal — the latency
            # a loop crash cost instead of a failed request
            rt = trace.last_t("replayed")
            if rt is not None:
                self.hist["replay_catchup_s"].observe(
                    max(0.0, trace.spans[-1][1] - rt))
        n_tokens = trace.attrs.get("n_tokens", 0)
        d = trace.dur("first_token", "finished")
        if d is not None and n_tokens >= 2:
            self.hist["tpot_s"].observe(max(0.0, d) / (n_tokens - 1))

    def snapshot(self) -> dict:
        """{histogram name: {count, mean, p50, p90, p99}} — the
        ``SlotServer.stats()["latency"]`` payload."""
        return {name: h.snapshot() for name, h in self.hist.items()
                if h.count}

    def state(self) -> dict:
        """Full serializable bucket state of every histogram — persist
        this across server restarts so the /metrics cumulative buckets
        survive a re-arm (``restore()`` on the fresh instance resumes
        them). ``SlotServer.reset()`` keeps its telemetry object, so this
        pair is for PROCESS-level restarts (the serve CLI dumps it next
        to the trace JSONL)."""
        return {name: h.state() for name, h in self.hist.items()}

    def restore(self, state: dict) -> None:
        """Adopt a ``state()`` dump. Unknown histogram names are ignored
        (an old dump must not block a newer server from starting);
        mismatched buckets raise (see ``Histogram.restore``)."""
        for name, h_state in state.items():
            if name in self.hist:
                self.hist[name].restore(h_state)


class ServiceRateEstimator:
    """EWMA of observed per-request service time (admission ->
    slot-freeing terminal), turned into a Retry-After estimate.

    With S slots serving concurrently at ~``ewma`` seconds per request,
    slots free at S/ewma per second; a queue of Q waiting requests plus
    the shed one drains in ewma * (Q + 1) / S seconds — monotonic in
    queue depth, so a deeper backlog always advertises a longer (never
    shorter) retry. Clamped to [1, 60] integer seconds: sub-second
    estimates round up to the header's 1s floor, and past a minute the
    estimate says "overloaded", not "come back in exactly 7 minutes"."""

    __slots__ = ("_ewma", "alpha", "default_s")

    def __init__(self, alpha: float = 0.2, default_s: float = 1.0):
        self.alpha = alpha
        self.default_s = default_s
        self._ewma: float | None = None

    def observe(self, service_s: float) -> None:
        if service_s < 0:
            return
        self._ewma = (service_s if self._ewma is None
                      else self.alpha * service_s
                      + (1 - self.alpha) * self._ewma)

    @property
    def service_time_s(self) -> float:
        return self._ewma if self._ewma is not None else self.default_s

    def retry_after_s(self, queued: int, slots: int) -> int:
        eta = self.service_time_s * (max(0, queued) + 1) / max(1, slots)
        return int(min(60, max(1, math.ceil(eta))))


# ---------------------------------------------------- device-time tracking


class DispatchTracker:
    """Dispatch→ready attribution for asynchronously dispatched device
    programs.

    Every dispatch registers one of its OUTPUT buffers (``track``); a
    background reaper thread ``block_until_ready``s the buffers in
    dispatch order — dispatch order is device order, so when buffer N is
    ready every earlier one is too, and the serial walk never waits on
    anything the device hasn't already passed — and records the ready
    instant. That yields, off the hot path:

    - a dispatch→ready latency Histogram per program ``kind`` (prefill,
      decode_block, prefix_copy, ...): how long the device actually
      spent behind each dispatch, which host-side dispatch timing
      (``decode_block_s``) cannot see;
    - an ``in_flight`` depth gauge (dispatched, not yet ready) — the
      real pipeline depth, vs the host's bookkeeping lag bound;
    - ``ready_time(seq)``: the recorded ready instant of one dispatch,
      which the serving loop subtracts from its observation instant to
      measure ``device_lag`` on request traces.

    All host-side, no jax import: a tracked object only needs a
    ``block_until_ready()`` method (every jax array has one; tests use
    stubs). The reaper is deliberately one thread: readiness is ordered,
    so concurrency would buy nothing and unorder the histogram feed.

    ``reset()`` discards pending entries and recorded ready instants
    WITHOUT blocking on them (after a failed dispatch the buffers may be
    dead — ``block_until_ready`` on a deleted array raises, which the
    reaper tolerates) and re-arms the same thread: no stale
    ready-instants cross a reset, no thread is leaked per reset.
    ``shutdown()`` stops the thread for good."""

    # keep at most this many reaped ready-instants for ready_time();
    # callers look up recent dispatches only (the processing pipeline is
    # a few blocks deep), so a small ring bounds memory forever
    READY_KEEP = 512

    def __init__(self, max_pending: int = 1024):
        self.max_pending = max_pending
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._queue: collections.deque = collections.deque()
        self._ready: collections.OrderedDict[int, float] = \
            collections.OrderedDict()
        self.hist: dict[str, Histogram] = {}
        self._seq = 0
        self._gen = 0               # bumped by reset(): stale entries drop
        self._busy = False          # reaper mid-block_until_ready
        self._busy_seq = -1         # which dispatch it is blocking on
        self.tracked_total = 0
        self.dropped = 0            # queue overflow (reaper fell behind)
        self.reap_errors = 0        # block_until_ready raised (dead buffer)
        self._stop = False
        self._thread = threading.Thread(
            target=self._reap, name="dispatch-reaper", daemon=True)
        self._thread.start()

    def track(self, kind: str, buf) -> int:
        """Register one dispatched program's output buffer; returns the
        dispatch sequence number (monotonic). The hot-path cost is one
        lock + deque append; the blocking wait happens on the reaper."""
        with self._cv:
            self._seq += 1
            seq = self._seq
            if self._stop:
                return seq
            if len(self._queue) >= self.max_pending:
                # never let a wedged reaper grow host memory unboundedly;
                # an untracked dispatch loses telemetry, nothing else
                self.dropped += 1
                return seq
            self.tracked_total += 1
            self._queue.append((seq, kind, time.monotonic(), buf,
                                self._gen))
            self._cv.notify_all()
        return seq

    def _reap(self) -> None:
        while True:
            with self._cv:
                while not self._queue and not self._stop:
                    self._cv.wait()
                if self._stop:
                    return
                seq, kind, t0, buf, gen = self._queue.popleft()
                self._busy, self._busy_seq = True, seq
            try:
                buf.block_until_ready()
                t_ready = time.monotonic()
            except Exception:
                # a donated buffer killed by a failed dispatch, or a
                # stub without the method: count it, never die — the
                # tracker outlives every individual dispatch failure
                t_ready = None
                with self._lock:
                    self.reap_errors += 1
            with self._cv:
                if t_ready is not None and gen == self._gen:
                    h = self.hist.get(kind)
                    if h is None:
                        h = self.hist[kind] = Histogram()
                    h.observe(max(0.0, t_ready - t0))
                    self._ready[seq] = t_ready
                    while len(self._ready) > self.READY_KEEP:
                        self._ready.popitem(last=False)
                self._busy = False
                self._cv.notify_all()

    def ready_time(self, seq: int, timeout: float = 0.0) -> float | None:
        """Recorded ready instant of dispatch ``seq``, or None if it was
        never tracked / already evicted / not yet reaped. A small
        ``timeout`` gives the reaper a beat to catch up — callers ask
        right after forcing the buffer themselves, so every queued
        ``block_until_ready`` up to ``seq`` returns immediately and the
        wait is microseconds unless the reaper is wedged."""
        deadline = time.monotonic() + timeout
        with self._cv:
            while True:
                t = self._ready.get(seq)
                if t is not None or seq > self._seq:
                    return t
                pending = (self._busy and self._busy_seq == seq) or any(
                    s == seq for s, *_ in self._queue)
                if not pending:
                    return None
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return None
                self._cv.wait(remaining)

    @property
    def in_flight(self) -> int:
        """Dispatches registered but not yet observed ready — the
        measured device pipeline depth."""
        with self._lock:
            return len(self._queue) + (1 if self._busy else 0)

    def drain(self, timeout: float = 5.0) -> bool:
        """Block until every tracked dispatch has been reaped (or the
        timeout passes); True when fully drained."""
        deadline = time.monotonic() + timeout
        with self._cv:
            while self._queue or self._busy:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._cv.wait(remaining)
        return True

    def reset(self) -> None:
        """Discard pending entries and recorded ready-instants without
        blocking on possibly-dead buffers; the reaper thread survives
        and keeps serving the next generation. Histograms are cumulative
        telemetry and deliberately survive (same contract as
        ``ServingTelemetry`` across ``SlotServer.reset()``)."""
        with self._cv:
            self._gen += 1
            self._queue.clear()
            self._ready.clear()
            self._cv.notify_all()

    def shutdown(self, timeout: float = 5.0) -> None:
        """Stop the reaper thread (idempotent). Pending entries are
        discarded — shutdown must never block on a dead device."""
        with self._cv:
            self._stop = True
            self._queue.clear()
            self._cv.notify_all()
        if self._thread.is_alive():
            self._thread.join(timeout)

    @property
    def alive(self) -> bool:
        return self._thread.is_alive() and not self._stop

    def snapshot(self) -> dict:
        """The stats()/bench payload: per-kind dispatch→ready quantiles
        + the tracker's own counters."""
        with self._lock:
            return {
                "in_flight": len(self._queue) + (1 if self._busy else 0),
                "tracked": self.tracked_total,
                "dropped": self.dropped,
                "reap_errors": self.reap_errors,
                "dispatch_ready": {k: h.snapshot()
                                   for k, h in self.hist.items()},
            }

    def histograms(self) -> dict[str, Histogram]:
        """Consistent copies of the per-kind dispatch→ready histograms,
        taken under the tracker lock — safe to render (bucket iteration)
        while the reaper keeps observing into the originals."""
        with self._lock:
            states = {k: h.state() for k, h in self.hist.items()}
        out = {}
        for k, s in states.items():
            h = Histogram()
            h.restore(s)
            out[k] = h
        return out


# ------------------------------------------------- profiler-clock phases

# The phases of one serving turn, as they are named in a profiler trace.
# Every one is a LEAF: no phase is entered inside another on the same
# thread, so a phase's duration is its self time and a device gap has one
# owner. serve.loop.* are ServeApp's (cli/serve.py), serve.step.* are
# SlotServer's (models/serving.py), serve.submit.* lie on the caller's
# thread. docs/observability.md lists the counts each one carries.
PHASE_LOOP_LOCK_WAIT = "serve.loop.lock_wait"
PHASE_ADMIT = "serve.step.admit"
PHASE_DISPATCH = "serve.step.dispatch"
PHASE_SYNC = "serve.step.sync"
PHASE_BOOKKEEP = "serve.step.bookkeep"
PHASE_DRAIN = "serve.loop.drain"
PHASE_OBSERVE = "serve.loop.observe"
PHASE_DELIVER = "serve.loop.deliver"
PHASE_IDLE = "serve.loop.idle"
PHASE_SUBMIT_LOCK_WAIT = "serve.submit.lock_wait"
PHASES = (PHASE_LOOP_LOCK_WAIT, PHASE_ADMIT, PHASE_DISPATCH, PHASE_SYNC,
          PHASE_BOOKKEEP, PHASE_DRAIN, PHASE_OBSERVE, PHASE_DELIVER,
          PHASE_IDLE, PHASE_SUBMIT_LOCK_WAIT)

_NO_SPAN = contextlib.nullcontext()


@functools.cache
def _trace_annotation():
    """``jax.profiler.TraceAnnotation``, imported on first use; None in a
    process without jax (router, portal), which has no profiler either."""
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:
        return None
    return TraceAnnotation


def phase(name: str, **counts: int):
    """A context manager that writes the span ``name`` (one of
    ``PHASES``), with ``counts`` on it, into the profiler's trace.

    There is no switch: outside a profiler session a ``TraceAnnotation``
    is a flag test, so "tracing off" is "no session open". The two things
    that open one are the benchmark's ``--trace 1`` window and serve's
    on-demand capture (``/debug/profile``). Counts known only once the
    work is done go on through the entered span's ``set_metadata`` (the
    sites that do so are in modules that import jax themselves: without
    jax the shared null context is returned and enters as None)."""
    annotation = _trace_annotation()
    if annotation is None:
        return _NO_SPAN
    return annotation(name, **counts)


# the jax.monitoring event that fires once per actual XLA compilation
# (cache hits fire nothing); the other /jax/core/compile/* events time
# tracing/lowering stages of the same compile and would triple-count
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileTelemetry:
    """XLA compile-time visibility: a listener on
    ``jax.monitoring.register_event_duration_secs_listener`` feeds a
    compile-duration Histogram + counters. ``mark_warm()`` draws the
    line after warmup (first served request / first training step):
    compiles past it are RECOMPILES — a serving loop that recompiles in
    steady state is silently paying seconds of latency per new shape,
    and crossing ``storm_threshold`` post-warm compiles logs one loud
    warning instead of letting the storm hide in p99.

    ``install()`` registers the process-global listener once (jax only
    offers clear-all, never unregister-one, so the hook is permanent);
    the instance stays usable without jax via ``note()`` — tests feed it
    directly."""

    def __init__(self, storm_threshold: int = 8):
        # compiles run 10ms..minutes: wider buckets than the latency
        # histograms' 120s default ceiling
        self.hist = Histogram(lo=1e-3, hi=600.0)
        self.compiles = 0
        self.compile_time_s = 0.0
        self.storm_threshold = storm_threshold
        self._warm_at: int | None = None
        self._storm_warned = False
        self._lock = threading.Lock()

    def note(self, event: str, duration_s: float) -> None:
        if event != _COMPILE_EVENT:
            return
        with self._lock:
            self.compiles += 1
            self.compile_time_s += duration_s
            self.hist.observe(duration_s)
            storm = (self._warm_at is not None
                     and not self._storm_warned
                     and self.compiles - self._warm_at
                     >= self.storm_threshold)
            if storm:
                self._storm_warned = True
        if storm:
            log.warning(
                "recompile storm: %d XLA compiles after warmup "
                "(%.1fs total compile time) — a steady-state workload "
                "should not see new program shapes; check for leaking "
                "dynamic shapes in dispatched programs",
                self.compiles - self._warm_at, self.compile_time_s)

    def mark_warm(self) -> None:
        """Draw the warmup line (idempotent — only the first call
        counts): compiles after this are recompiles."""
        with self._lock:
            if self._warm_at is None:
                self._warm_at = self.compiles

    @property
    def recompiles_post_warm(self) -> int:
        with self._lock:
            if self._warm_at is None:
                return 0
            return self.compiles - self._warm_at

    def hist_copy(self) -> Histogram:
        """Consistent copy of the compile-duration histogram, taken
        under the listener lock — safe to render while jax's compile
        threads keep feeding the original."""
        with self._lock:
            state = self.hist.state()
        h = Histogram(lo=1e-3, hi=600.0)
        h.restore(state)
        return h

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "compiles": self.compiles,
                "compile_time_s": round(self.compile_time_s, 3),
                "recompiles_post_warm": (
                    self.compiles - self._warm_at
                    if self._warm_at is not None else 0),
                "warm": self._warm_at is not None,
            }


# the process-global instance install() feeds; one per process because
# jax.monitoring listeners cannot be unregistered individually
COMPILE_TELEMETRY = CompileTelemetry()
_compile_listener_installed = False


def install_compile_telemetry(only_if_loaded: bool = False) -> CompileTelemetry:
    """Register the jax.monitoring listener feeding COMPILE_TELEMETRY
    (idempotent; returns the instance either way). Import of jax happens
    here, not at module import — observability.py stays usable without
    an accelerator stack.

    ``only_if_loaded=True`` skips installation while jax is absent from
    ``sys.modules`` instead of forcing the (seconds-heavy) import — for
    processes like the driver that run no device code on the common path
    but want the listener once user code brings jax in (no jax import
    means no compile events were possible anyway). Call again later to
    pick jax up once something imported it."""
    global _compile_listener_installed
    if not _compile_listener_installed:
        import sys

        if only_if_loaded and "jax" not in sys.modules:
            return COMPILE_TELEMETRY
        try:
            import jax.monitoring

            jax.monitoring.register_event_duration_secs_listener(
                lambda event, duration, **kw:
                COMPILE_TELEMETRY.note(event, duration))
            _compile_listener_installed = True
        except Exception:   # no jax / API drift: telemetry is optional
            log.exception("could not install compile-telemetry listener")
    return COMPILE_TELEMETRY


# ------------------------------------------------------------- exposition

_NAME_OK = re.compile(r"[a-zA-Z_:][a-zA-Z0-9_:]*$")


def _sanitize(name: str) -> str:
    name = re.sub(r"[^a-zA-Z0-9_:]", "_", name)
    return name if _NAME_OK.match(name) else "_" + name


def _fmt(v: float) -> str:
    if v == math.inf:
        return "+Inf"
    f = float(v)
    return str(int(f)) if f == int(f) and abs(f) < 1e15 else repr(f)


def _labels(labels: dict | None) -> str:
    if not labels:
        return ""
    esc = {ord("\\"): "\\\\", ord('"'): '\\"', ord("\n"): "\\n"}
    return "{" + ",".join(
        f'{_sanitize(k)}="{str(v).translate(esc)}"'
        for k, v in labels.items()) + "}"


class PromRenderer:
    """Prometheus text-format (0.0.4) builder. ``# HELP``/``# TYPE``
    are emitted once per family, on first use; multiple label sets of
    one family group under it. No client library — the format is three
    line shapes and a content type."""

    def __init__(self):
        self._families: dict[str, list[str]] = {}
        self._order: list[str] = []

    def _family(self, name: str, kind: str, help_text: str) -> list[str]:
        name = _sanitize(name)
        fam = self._families.get(name)
        if fam is None:
            fam = []
            if help_text:
                fam.append(f"# HELP {name} {help_text}")
            fam.append(f"# TYPE {name} {kind}")
            self._families[name] = fam
            self._order.append(name)
        return fam

    def gauge(self, name: str, value: float, help_text: str = "",
              labels: dict | None = None) -> None:
        self._sample(name, "gauge", value, help_text, labels)

    def counter(self, name: str, value: float, help_text: str = "",
                labels: dict | None = None) -> None:
        self._sample(name, "counter", value, help_text, labels)

    def _sample(self, name, kind, value, help_text, labels) -> None:
        fam = self._family(name, kind, help_text)
        fam.append(f"{_sanitize(name)}{_labels(labels)} {_fmt(value)}")

    def histogram(self, name: str, hist: Histogram,
                  help_text: str = "", labels: dict | None = None) -> None:
        """``labels`` (e.g. {"role": "worker"}) lets one family carry a
        histogram per label set — the per-role gang-launch histograms on
        the driver's /metrics; ``le`` is appended after them."""
        name = _sanitize(name)
        fam = self._family(name, "histogram", help_text)
        base = _labels(labels)[1:-1] if labels else ""
        prefix = base + "," if base else ""
        cum = 0
        for bound, c in zip(hist.bounds + [math.inf], hist.counts):
            cum += c
            fam.append(
                f'{name}_bucket{{{prefix}le="{_fmt(bound)}"}} {cum}')
        suffix = "{" + base + "}" if base else ""
        fam.append(f"{name}_sum{suffix} {_fmt(hist.sum)}")
        fam.append(f"{name}_count{suffix} {hist.count}")

    def render(self) -> str:
        return "\n".join(
            line for fam in self._order for line in self._families[fam]
        ) + "\n"


PROM_CONTENT_TYPE = "text/plain; version=0.0.4; charset=utf-8"


# ---------------------------------------------------------------- parsing
#
# The other half of the exposition contract: ONE strict parser for the
# text format every tier renders (serve, router, driver, portal). Every
# consumer that used to hand-roll a regex over /metrics — the
# autoscaler's FleetWatcher, the metrics hub, bench — reads through
# this, so a renderer bug (malformed label, broken histogram) fails the
# conformance lint instead of silently skewing a control law. Grammar
# per Prometheus text format 0.0.4: ``# HELP``/``# TYPE`` metadata
# lines, then ``name{labels} value [timestamp]`` samples.

_HELP_LINE_RE = re.compile(r"^# HELP ([a-zA-Z_:][a-zA-Z0-9_:]*)(?: (.*))?$")
_TYPE_LINE_RE = re.compile(
    r"^# TYPE ([a-zA-Z_:][a-zA-Z0-9_:]*) "
    r"(counter|gauge|histogram|summary|untyped)$")
_SAMPLE_LINE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)"     # metric name
    r"(\{.*\})?"                       # optional label block
    r"\s+(\S+)"                        # value
    r"(?:\s+(-?[0-9]+))?\s*$")         # optional ms timestamp (ignored)
_LABEL_PAIR_RE = re.compile(
    r'\s*([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')
_LABEL_UNESCAPE_RE = re.compile(r"\\(.)")
_HIST_SUFFIXES = ("_bucket", "_sum", "_count")


def _unescape_label_value(raw: str) -> str:
    return _LABEL_UNESCAPE_RE.sub(
        lambda m: {"n": "\n", "\\": "\\", '"': '"'}.get(m.group(1),
                                                        "\\" + m.group(1)),
        raw)


def _parse_label_block(body: str, strict: bool, line: str) -> dict[str, str]:
    """``body`` is the text between the braces. Strict mode demands the
    pairs tile the block exactly (a stray token between labels is a
    renderer bug, not noise to skip)."""
    labels: dict[str, str] = {}
    i, n = 0, len(body)
    while i < n:
        m = _LABEL_PAIR_RE.match(body, i)
        if not m:
            if strict:
                raise ValueError(f"malformed label block: {line!r}")
            # lenient: salvage whatever well-formed pairs exist
            return {k: _unescape_label_value(v)
                    for k, v in _LABEL_PAIR_RE.findall(body)}
        if strict and m.group(1) in labels:
            raise ValueError(f"duplicate label {m.group(1)!r}: {line!r}")
        labels[m.group(1)] = _unescape_label_value(m.group(2))
        i = m.end()
        if i < n:
            if body[i] != ",":
                if strict:
                    raise ValueError(f"malformed label block: {line!r}")
                break
            i += 1
    return labels


@dataclass
class PromFamily:
    """One metric family: its declared type/help plus every sample.
    Histogram component samples (``_bucket``/``_sum``/``_count``) group
    under the base family name; each sample keeps its full label set."""

    name: str
    kind: str = "untyped"
    help: str = ""
    samples: list[tuple[str, dict[str, str], float]] = field(
        default_factory=list)

    def values(self, **labels) -> list[float]:
        """Samples whose label set contains every given pair."""
        want = {k: str(v) for k, v in labels.items()}
        return [v for _, ls, v in self.samples
                if all(ls.get(k) == val for k, val in want.items())]

    def buckets(self, exclude: tuple[str, ...] = ()) -> dict[str, float]:
        """``{le: cumulative_count}`` summed across the family's
        ``_bucket`` samples, skipping partitions that carry any label
        named in ``exclude`` (``le`` itself never excludes)."""
        out: dict[str, float] = {}
        for name, labels, value in self.samples:
            if not name.endswith("_bucket") or "le" not in labels:
                continue
            if any(k in labels for k in exclude):
                continue
            le = labels["le"]
            out[le] = out.get(le, 0.0) + value
        return out


def _check_histogram_invariants(fam: PromFamily) -> None:
    """Strict-mode conformance: per label partition the cumulative
    buckets must be non-decreasing in ``le``, end at ``+Inf``, and agree
    with ``_count`` when one is rendered."""
    parts: dict[frozenset, dict[str, float]] = {}
    counts: dict[frozenset, float] = {}
    for name, labels, value in fam.samples:
        if name.endswith("_bucket") and "le" in labels:
            key = frozenset((k, v) for k, v in labels.items() if k != "le")
            parts.setdefault(key, {})[labels["le"]] = value
        elif name.endswith("_count"):
            counts[frozenset(labels.items())] = value
    for key, buckets in parts.items():
        def _edge(le: str) -> float:
            return math.inf if le in ("+Inf", "inf") else float(le)
        ordered = sorted(buckets.items(), key=lambda kv: _edge(kv[0]))
        if not ordered or _edge(ordered[-1][0]) != math.inf:
            raise ValueError(
                f"histogram {fam.name} partition {dict(key)} lacks +Inf")
        prev = -math.inf
        for _, v in ordered:
            if v < prev:
                raise ValueError(
                    f"histogram {fam.name} buckets not cumulative")
            prev = v
        if key in counts and counts[key] != ordered[-1][1]:
            raise ValueError(
                f"histogram {fam.name} _count != +Inf bucket")


def parse_prom_text(text: str,
                    strict: bool = False) -> dict[str, PromFamily]:
    """Parse Prometheus text exposition into ``{family: PromFamily}``.

    Lenient by default (a scrape must survive a half-written body:
    unparseable lines are skipped), strict for the conformance lint
    (any malformed line, label block, duplicate series, or histogram
    invariant violation raises ValueError naming the offense).

    Samples WITHOUT metadata still parse — ``# TYPE``-less bucket lines
    group into a histogram family when they carry an ``le`` label, so a
    minimal test server serving bare samples reads the same as a full
    renderer surface.
    """
    types: dict[str, str] = {}
    helps: dict[str, str] = {}
    raw: list[tuple[str, dict[str, str], float]] = []
    seen_series: set[tuple[str, frozenset]] = set()
    for line in text.splitlines():
        stripped = line.strip()
        if not stripped:
            continue
        if stripped.startswith("#"):
            m = _HELP_LINE_RE.match(stripped)
            if m:
                helps[m.group(1)] = m.group(2) or ""
                continue
            m = _TYPE_LINE_RE.match(stripped)
            if m:
                if strict and m.group(1) in types:
                    raise ValueError(f"duplicate TYPE for {m.group(1)}")
                types[m.group(1)] = m.group(2)
                continue
            if strict and stripped.startswith(("# TYPE", "# HELP")):
                raise ValueError(f"malformed metadata line: {line!r}")
            continue                      # other comments are legal
        m = _SAMPLE_LINE_RE.match(stripped)
        if not m:
            if strict:
                raise ValueError(f"malformed sample line: {line!r}")
            continue
        name, block, value_s = m.group(1), m.group(2), m.group(3)
        labels = (_parse_label_block(block[1:-1], strict, line)
                  if block else {})
        try:
            value = float(value_s)
        except ValueError:
            if strict:
                raise ValueError(f"bad sample value: {line!r}")
            continue
        if strict:
            series = (name, frozenset(labels.items()))
            if series in seen_series:
                raise ValueError(f"duplicate series: {line!r}")
            seen_series.add(series)
        raw.append((name, labels, value))
    # base names that are histograms even without metadata: any _bucket
    # sample carrying an le label implies its base family
    hist_bases = {n for n, k in types.items() if k in ("histogram",
                                                       "summary")}
    hist_bases.update(
        n[:-len("_bucket")] for n, labels, _ in raw
        if n.endswith("_bucket") and "le" in labels)
    families: dict[str, PromFamily] = {}
    for name in types:                    # declared-but-empty families
        families[name] = PromFamily(name, types[name],
                                    helps.get(name, ""))
    for name, labels, value in raw:
        base = name
        for suf in _HIST_SUFFIXES:
            if name.endswith(suf) and name[:-len(suf)] in hist_bases:
                base = name[:-len(suf)]
                break
        fam = families.get(base)
        if fam is None:
            kind = types.get(base, "histogram" if base in hist_bases
                             else "untyped")
            fam = families[base] = PromFamily(base, kind,
                                              helps.get(base, ""))
        fam.samples.append((name, labels, value))
    if strict:
        for fam in families.values():
            if fam.kind in ("histogram", "summary") or (
                    fam.kind == "untyped" and fam.name in hist_bases):
                _check_histogram_invariants(fam)
    return families


__all__ = ["Histogram", "RequestTrace", "TaskTrace", "TraceContext",
           "TRACE_HEADER", "TRACE_ID_RESPONSE_HEADER", "ServingTelemetry",
           "ServiceRateEstimator", "PromRenderer", "PROM_CONTENT_TYPE",
           "PromFamily", "parse_prom_text",
           "TELEMETRY_HISTOGRAMS", "TERMINAL_SPANS", "TASK_TERMINAL_SPANS",
           "DispatchTracker", "CompileTelemetry", "COMPILE_TELEMETRY",
           "install_compile_telemetry", "PHASES", "phase"]
