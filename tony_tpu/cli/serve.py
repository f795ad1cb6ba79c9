"""``tony-tpu serve`` — a long-lived generation service over the
continuous-batching slot pool (models/serving.py).

    python -m tony_tpu.cli.main serve --port 8200 \
        --checkpoint-dir /ckpt --vocab 4096 --d-model 256 ...   # or
        --hf-checkpoint /path/to/llama

    curl -s localhost:8200/generate -d '{"prompt": [1,2,3],
                                         "max_new_tokens": 64}'
    -> {"id": 0, "tokens": [...], "finish_reason": "length"}

One serving thread owns the device: it admits queued requests into freed
KV-cache slots and runs compiled decode blocks; HTTP handler threads only
enqueue and wait. POST /generate blocks until the request completes
(simple and proxy-friendly — the reference fronts exactly this kind of
long-lived service with its proxy, tony-proxy/.../ProxyServer.java:27-39)
— or STREAMS it: ``/generate?stream=true`` (or ``"stream": true``)
delivers per-token SSE frames fed at every processed decode block, and
``POST /v1/completions`` / ``/v1/chat/completions`` give the same engine
an OpenAI-compatible front door (tony_tpu/api/, ``--text-codec``;
docs/serving.md "Streaming & OpenAI compatibility"). A client that
vanishes mid-stream is cancelled through the PR 3 path.
GET /stats reports slot occupancy, queue depth, the prefix-cache counters
(hits/misses/evictions, prefill tokens computed vs reused — see
``--prefix-cache-blocks`` and docs/serving.md), the latency-histogram
quantiles (TTFT/TPOT/queue wait/e2e), and a MetricsAccumulator snapshot
of the serving-load gauges, the same shape the portal/history layer
renders for executor metrics. GET /metrics renders the same numbers in
Prometheus text format (histograms included) so any scraper works with
no client library; ``--trace-dir`` additionally dumps every terminated
request's lifecycle trace as JSONL (events/trace.py) for the portal's
per-request timeline. GET /debug/profile?seconds=N captures a
jax.profiler trace (xplane) of live traffic into
``<trace-dir>/profiles/`` — the portal lists captures on
``/profiles/<app_id>``. See docs/observability.md.

Model loading matches lm_generate: an lm_train orbax checkpoint (with the
matching hyperparam flags), a local HF Llama/Mistral checkpoint dir, or
random init for smoke tests. ``--mesh "tensor=4"`` (axis=size pairs) serves
TENSOR-PARALLEL: weights are prepared once onto the mesh and the slot
pool's KV cache shards over ("batch", "kv") — a model bigger than one
chip's HBM serves live traffic with this same single-controller loop
(models/serving.py).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import select
import socket
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from .. import metrics as _metrics
from .. import observability as _obs


def build_argparser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="tony-tpu serve")
    p.add_argument("--port", type=int, default=8200)
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--checkpoint-dir", default="",
                   help="orbax dir from lm_train; empty = random init")
    p.add_argument("--hf-checkpoint", default="",
                   help="local HuggingFace Llama/Mistral checkpoint dir")
    p.add_argument("--d-model", type=int, default=256)
    p.add_argument("--n-layers", type=int, default=4)
    p.add_argument("--n-heads", type=int, default=8)
    p.add_argument("--d-ff", type=int, default=1024)
    p.add_argument("--vocab", type=int, default=4096)
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--slots", type=int, default=8,
                   help="concurrent KV-cache slots (the max in-flight batch)")
    p.add_argument("--max-len", type=int, default=2048,
                   help="per-slot cache capacity: prompt + generation")
    p.add_argument("--block-size", type=int, default=16,
                   help="decode steps per compiled dispatch; trades "
                        "scheduling latency against host-sync amortization")
    p.add_argument("--prefill-chunk", type=int, default=128)
    p.add_argument("--kv-dtype", default="native", choices=("native", "int8"))
    p.add_argument("--weight-dtype", default="native",
                   choices=("native", "int8"))
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--top-k", type=int, default=0)
    p.add_argument("--stop-tokens", default="",
                   help="whitespace-separated EOS token ids")
    p.add_argument("--pad-id", type=int, default=0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--mesh", default="",
                   help="serve tensor-parallel: comma-separated axis=size "
                        "pairs (e.g. 'tensor=4' or 'data=2,tensor=2'); "
                        "axes from parallel.mesh.AXIS_ORDER. Empty = "
                        "single device")
    p.add_argument("--prefix-cache-blocks", type=int, default=0,
                   help="enable the chunk-aligned prefix KV cache with "
                        "this many shared prefill-chunk-sized blocks "
                        "(the HBM budget; 0 = disabled). Shared prompt "
                        "prefixes — system prompts, few-shot templates — "
                        "then prefill once and later requests copy the "
                        "cached KV instead of recomputing it")
    p.add_argument("--no-cache-prompts", action="store_true",
                   help="with --prefix-cache-blocks: serve FROM the cache "
                        "but never insert admitted prompts into it unless "
                        "a request sets cache_prompt=true explicitly")
    p.add_argument("--max-queue", type=int, default=0,
                   help="bound the wait queue: requests beyond this many "
                        "waiting are shed with HTTP 429 + Retry-After "
                        "instead of queueing past their deadlines "
                        "(0 = unbounded)")
    p.add_argument("--paged-kv", action="store_true",
                   help="swap the slots x max-len ring KV cache for one "
                        "paged block pool with per-slot block tables "
                        "(docs/serving.md 'Paged KV & admission tiers'): "
                        "admission is gated on free pool blocks, so "
                        "concurrency is bounded by actual KV demand "
                        "instead of the worst-case slot reservation")
    p.add_argument("--kv-block", type=int, default=0,
                   help="with --paged-kv: tokens per KV block (must "
                        "divide --max-len and --prefill-chunk; default: "
                        "--block-size)")
    p.add_argument("--kv-pool-blocks", type=int, default=0,
                   help="with --paged-kv: allocatable blocks in the "
                        "shared pool — the real KV memory budget "
                        "(default: slots * max-len / kv-block, the ring "
                        "equivalent; set it LOWER to oversubscribe)")
    p.add_argument("--prefill-interleave", type=int, default=0,
                   help="with --paged-kv: pump at most this many pending "
                        "prefill TOKENS per decode block so a long "
                        "admission storm cannot stall running decodes "
                        "(0 = prefills run to completion at admission)")
    p.add_argument("--class-budget-interactive", type=int, default=0,
                   help="with --paged-kv: cap the KV blocks the "
                        "'interactive' tier may hold exclusively "
                        "(0 = uncapped)")
    p.add_argument("--class-budget-batch", type=int, default=0,
                   help="with --paged-kv: cap the KV blocks the 'batch' "
                        "tier may hold exclusively (0 = uncapped)")
    p.add_argument("--role", default="both",
                   choices=("prefill", "decode", "both"),
                   help="disaggregated serving role (docs/serving.md "
                        "'Disaggregated serving'): 'prefill' runs "
                        "admission + chunked prefill only and answers "
                        "/generate with finish_reason='prefilled' plus "
                        "a KV handoff payload (requires --paged-kv); "
                        "'decode' additionally accepts POST /kv/import; "
                        "'both' (default) is today's behavior")
    p.add_argument("--batch-queue-frac", type=float, default=0.5,
                   help="with --max-queue: batch-priority requests are "
                        "shed once the queue is this fraction full "
                        "(interactive requests use the full queue and "
                        "displace queued batch work under pressure)")
    p.add_argument("--loop-max-restarts", type=int, default=3,
                   help="serving-loop recovery budget: consecutive step "
                        "failures tolerated (each one resets the slot "
                        "state and restarts under exponential backoff) "
                        "before /healthz flips to 503")
    p.add_argument("--loop-backoff-s", type=float, default=0.5,
                   help="base of the exponential restart backoff")
    p.add_argument("--drain-timeout-s", type=float, default=30.0,
                   help="SIGTERM/SIGINT graceful drain: how long in-"
                        "flight requests get to finish before shutdown")
    p.add_argument("--trace-dir", default="",
                   help="dump every terminated request's lifecycle trace "
                        "as JSONL (requests.trace.jsonl) into this "
                        "directory — point it at the job's history dir "
                        "(<intermediate>/<app_id>/) and the portal "
                        "renders a per-request timeline. Also makes the "
                        "request journal FILE-backed "
                        "(requests.journal.jsonl): a killed process's "
                        "unfinished requests are recovered and finished "
                        "by the restarted one. Empty = off")
    p.add_argument("--no-replay", action="store_true",
                   help="disable the request journal + replay: a loop "
                        "crash fails in-flight requests (the pre-journal "
                        "fail-fast contract) and process restarts "
                        "recover nothing")
    p.add_argument("--model", action="append", default=[],
                   metavar="NAME=SPEC",
                   help="register a named model (repeatable; multi-model "
                        "serving: each gets its own engine/slot pool and "
                        "requests route by their 'model' field). SPEC is "
                        "'random[:seed]' (random init at the CLI dims), "
                        "'hf:<dir>' (HF checkpoint), or an orbax "
                        "checkpoint dir (optionally 'ckpt:<dir>'). "
                        "Omitted: the classic single-model flags load "
                        "one model named 'default'")
    p.add_argument("--draft-model", default="",
                   metavar="NAME-or-SPEC",
                   help="enable speculative decoding on the default "
                        "model: a registered model NAME (from --model) "
                        "or a SPEC loaded at the --draft-* dims. The "
                        "draft proposes --spec-gamma tokens per verify "
                        "round; completions stay byte-identical to "
                        "spec-off greedy serving")
    p.add_argument("--spec-gamma", type=int, default=0,
                   help="pin the speculative draft window (tokens "
                        "proposed per verify round); 0 = autotune from "
                        "the measured acceptance-rate EWMA, clamped to "
                        "--spec-gamma-max")
    p.add_argument("--spec-gamma-max", type=int, default=4,
                   help="autotune ceiling for the draft window")
    p.add_argument("--draft-d-model", type=int, default=64)
    p.add_argument("--draft-n-layers", type=int, default=2)
    p.add_argument("--draft-n-heads", type=int, default=4)
    p.add_argument("--draft-d-ff", type=int, default=256)
    p.add_argument("--text-codec", default="ids", choices=("ids", "bytes"),
                   help="text<->token mapping for the OpenAI-compatible "
                        "/v1 endpoints (no tokenizer ships with the "
                        "repo): 'ids' = text is space-separated decimal "
                        "token ids (exact round-trip, the default); "
                        "'bytes' = UTF-8 byte-level (needs vocab >= "
                        "256; ids >= 256 decode as U+FFFD)")
    p.add_argument("--journal-checkpoint-s", type=float, default=1.0,
                   help="durability-checkpoint cadence: process the "
                        "open-loop pipeline down to pipeline_depth this "
                        "often so the journal's emitted prefixes (what "
                        "replay and router failover resume from) stay "
                        "fresh for sparse traffic. Costs one packed "
                        "device->host transfer per checkpoint. 0 = only "
                        "at natural processing points")
    return p


def build_serving_mesh(spec_str: str):
    """'data=2,tensor=2' -> a Mesh over the first prod(sizes) devices.
    Unnamed axes are pinned to 1 (no wildcard -1: a server's parallelism
    should be exactly what the operator asked for)."""
    from ..parallel.mesh import AXIS_ORDER, MeshSpec, build_mesh
    import jax
    import math

    sizes = {}
    for part in spec_str.split(","):
        axis, sep, val = part.strip().partition("=")
        if not sep or axis not in AXIS_ORDER:
            raise SystemExit(
                f"--mesh: expected axis=size pairs over {AXIS_ORDER}, "
                f"got {part!r}")
        try:
            size = int(val)
        except ValueError:
            size = 0
        if size < 1:
            raise SystemExit(
                f"--mesh: axis size must be a positive integer, "
                f"got {part!r}")
        if axis in sizes:
            raise SystemExit(
                f"--mesh: axis {axis!r} given twice — a duplicate would "
                "silently serve with only the last value")
        sizes[axis] = size
    n = math.prod(sizes.values())
    if n > len(jax.devices()):
        raise SystemExit(
            f"--mesh needs {n} devices, only {len(jax.devices())} visible")
    spec = MeshSpec(**{**{a: 1 for a in AXIS_ORDER}, **sizes})
    return build_mesh(spec, devices=jax.devices()[:n])


def load_model(args):
    """(params, cfg) from the classic single-model flags — same sources
    as lm_generate (examples/lm_generate.py). Thin front for
    ``load_named_model`` (the ``--model NAME=SPEC`` loader), so the
    hf/orbax/random paths exist exactly once."""
    if args.hf_checkpoint and args.checkpoint_dir:
        raise SystemExit("--hf-checkpoint and --checkpoint-dir are exclusive")
    if args.hf_checkpoint:
        return load_named_model("hf:" + args.hf_checkpoint, args)
    if args.checkpoint_dir:
        return load_named_model("ckpt:" + args.checkpoint_dir, args)
    return load_named_model("random", args)


def load_named_model(spec: str, args, dims: dict | None = None):
    """(params, cfg) for one ``--model NAME=SPEC`` / ``--draft-model``
    entry. SPEC: ``random[:seed]`` (random init at the CLI dims —
    smoke/bench), ``hf:<dir>`` (HF Llama/Mistral), or an orbax
    checkpoint dir (optionally ``ckpt:<dir>``). ``dims`` overrides the
    CLI dims (the draft model's smaller shape)."""
    import jax
    import jax.numpy as jnp

    from ..models import transformer

    if spec.startswith("hf:"):
        from ..models.hf_import import load_hf

        return load_hf(spec[3:], dtype=getattr(jnp, args.dtype))
    d = dict(d_model=args.d_model, n_layers=args.n_layers,
             n_heads=args.n_heads, d_ff=args.d_ff)
    if dims:
        d.update(dims)
    cfg = transformer.TransformerConfig(
        vocab_size=args.vocab, d_model=d["d_model"],
        n_layers=d["n_layers"], n_heads=d["n_heads"],
        n_kv_heads=d["n_heads"], d_ff=d["d_ff"],
        dtype=getattr(jnp, args.dtype))
    if spec == "random" or spec.startswith("random:"):
        _, _, seedtxt = spec.partition(":")
        seed = int(seedtxt) if seedtxt else args.seed
        return transformer.init(jax.random.PRNGKey(seed), cfg), cfg
    path = spec[5:] if spec.startswith("ckpt:") else spec
    from ..train.checkpoint import CheckpointManager
    from ..train.step import make_optimizer

    mgr = CheckpointManager(path)
    if mgr.latest_step() is None:
        raise SystemExit(f"no checkpoint found in {path}")
    p0 = transformer.init(jax.random.PRNGKey(args.seed), cfg)
    restored = mgr.restore(
        template={"params": p0, "opt_state": make_optimizer().init(p0)})
    mgr.close()
    return restored["params"], cfg


# sibling of requests.trace.jsonl under --trace-dir: the ServingTelemetry
# histogram-bucket dump written at shutdown and restored at startup
TELEMETRY_STATE_FILE = "telemetry.state.json"


class ServingLoopError(RuntimeError):
    """The serving loop died; the message carries the cause."""


class UnknownModelError(ValueError):
    """The request names a model this process does not serve (HTTP
    400 — the model-aware router only posts to replicas advertising
    the model, so reaching this means a stale advertisement or a
    client talking to the wrong fleet)."""


class ServeApp:
    """The serving loop + request rendezvous. One lock guards the
    engines (a SlotServer is not thread-safe); HTTP threads enqueue
    under it and block on a per-request event the loop thread sets at
    completion.

    Multi-model serving: construct with a ``{name: SlotServer}`` dict
    (one engine per registry entry — cache shapes are per-config, so
    each model owns its own slot pool) and requests route by their
    ``model=`` field; the single loop thread steps every busy engine
    round-robin, so two models genuinely serve concurrently from one
    process. A bare SlotServer keeps the classic single-model shape
    (it becomes the one engine, under its registry name).

    Failure model (docs/serving.md "Failure model"): a step failure is
    NOT terminal. The loop fails only the requests whose in-flight work
    died, re-arms the slot state via ``SlotServer.reset()`` (weights
    untouched), and restarts under an exponential-backoff budget of
    ``max_loop_restarts`` CONSECUTIVE failures (a successful scheduling
    turn re-arms the streak). ``/healthz`` reports ``degraded`` while a
    restart is pending and flips to 503 ``down`` only when the budget is
    exhausted (or the engine has no ``reset()``) — at which point every
    waiter is failed immediately and new submissions are rejected.
    ``shutdown(drain=True)`` stops admission, fails queued-but-unstarted
    requests with a clear error, and lets in-flight slots finish up to a
    drain deadline. A waiter that gives up (``generate`` timeout, HTTP
    client gone) actively CANCELS its request so dead work stops burning
    decode steps."""

    def __init__(self, server, *, max_loop_restarts: int = 3,
                 loop_backoff_s: float = 0.5, trace_dir: str = "",
                 journal_checkpoint_s: float = 1.0):
        from ..metrics import MetricsAccumulator
        from ..observability import install_compile_telemetry
        from ..train.profiling import StepTimer

        # engines: {model name -> SlotServer}; the first entry is the
        # default model a nameless request gets. A bare engine is
        # wrapped as the single entry under its own registry name.
        if isinstance(server, dict):
            if not server:
                raise ValueError("ServeApp needs at least one engine")
            self.engines = dict(server)
        else:
            self.engines = {
                str(getattr(server, "model", None) or "default"): server}
        self.default_model = next(iter(self.engines))
        self.server = self.engines[self.default_model]  # default engine
        # which engine serves each live request id (routing for cancel/
        # progress/journal-seal; pruned at delivery and failure)
        self._rid_engine: dict[int, object] = {}
        self._stepping = None           # engine inside step() (recovery)
        self.trace_dir = trace_dir      # also hosts /debug/profile dumps
        self.lock = threading.Lock()
        self.wake = threading.Event()
        self.stop = threading.Event()
        # XLA compile visibility (observability.CompileTelemetry): the
        # process-global jax.monitoring listener feeds compile-duration
        # histograms + a recompile counter into /metrics; the first
        # DELIVERED completion marks warmup done, so later compiles count
        # as recompiles (a steady-state serving loop that keeps compiling
        # is leaking dynamic shapes — it logs a storm warning)
        self.compile_telemetry = install_compile_telemetry()
        # one capture at a time: jax.profiler has a single global trace
        self._profile_lock = threading.Lock()
        self.status = "ok"              # "ok" | "degraded" | "down"
        self.draining = False
        self.error: str | None = None
        self.max_loop_restarts = max_loop_restarts
        self.loop_backoff_s = loop_backoff_s
        # durability-checkpoint cadence: every this-many seconds of busy
        # serving, process the open-loop pipeline down to pipeline_depth
        # (SlotServer.checkpoint_progress) so the journal's emitted
        # prefixes — what replay and router failover resume from — stay
        # fresh even for sparse traffic that would otherwise only
        # process at completion. 0 disables (journal advances at natural
        # processing points only).
        self.journal_checkpoint_s = journal_checkpoint_s
        self._last_checkpoint = 0.0
        self.loop_failures = 0          # step exceptions, cumulative
        self.loop_restarts = 0          # successful reset+restart cycles
        # streaming delivery: clients that vanished mid-SSE-stream (the
        # handler maps the disconnect onto cancel(), so the slot goes
        # back to live traffic; counted here because only the HTTP
        # layer can see the socket die)
        self.stream_disconnects = 0
        self._restart_streak = 0        # consecutive failures (the budget)
        self._events: dict[int, threading.Event] = {}
        self._results: dict[int, object] = {}
        # client progress keys -> engine request ids (GET /progress): a
        # router polls these to journal emitted prefixes for failover
        # resume. Bounded FIFO — terminal requests' keys age out instead
        # of needing a reverse index on every completion.
        import collections as _collections

        self._progress_keys: "_collections.OrderedDict[str, int]" = \
            _collections.OrderedDict()
        self._progress_keys_cap = 4096
        # SSE reconnect state (docs/serving.md "SSE reconnect"): when a
        # streaming client vanishes mid-stream, the handler parks the
        # request's full emitted prefix here under its request id —
        # exactly the journaled prefix, since stream feeds advance in
        # lockstep with the journal. A reconnect presenting
        # ``Last-Event-ID: <rid>:<n>`` pops it, teacher-forces the
        # prefix into a fresh request, and re-delivers only the tokens
        # past the client's acked position. Single-use, bounded FIFO.
        self._resume_cache: "_collections.OrderedDict[int, list[int]]" = \
            _collections.OrderedDict()
        self._resume_cache_cap = 256
        # fleet-autoscaler backpressure hint: (remaining scale-up
        # cooldown seconds, the monotonic instant it was set). Folded
        # into 429 Retry-After so a shed client is told to come back
        # when new capacity can actually exist — not merely when one
        # queue seat frees. Pushed by the driver's autoscale tick
        # (POST /autoscale/hint) or set in-process; decays on its own.
        self._autoscale_hint: tuple[float, float] = (0.0, 0.0)
        # serving-load gauges (active slots, queue depth, reused-token
        # fraction, shed/cancelled/expired/restart counters) accumulated
        # the same way TaskMonitor accumulates executor metrics —
        # snapshot rides /stats so the portal/history layer sees serving
        # load next to the resource metrics
        self.metrics = MetricsAccumulator()
        # scheduling-turn cadence rides the SAME StepTimer the training
        # loop uses (train/profiling.py, monotonic) and feeds the
        # loop_turn_s histogram — one timing convention everywhere
        # compile_warm_on_step=False: loop turns tick before the first
        # request compiles anything — the serving warm line is the first
        # DELIVERED completion (_deliver), not the first loop turn
        self._turn_timer = StepTimer(compile_warm_on_step=False)
        self.thread = threading.Thread(
            target=self._loop, name="serve-loop", daemon=True)

    @property
    def healthy(self) -> bool:
        """Mirrors the /healthz bool (see ``health()``): degraded still
        serves (requests queue through a restart), but ``down`` and
        ``draining`` are both out of rotation."""
        return self.status != "down" and not self.draining

    def start(self):
        self.thread.start()

    def shutdown(self, drain: bool = False, drain_timeout_s: float = 30.0):
        """Stop the loop. ``drain=True`` first parks admission, fails
        queued-but-unstarted requests with a clear error, and waits (up
        to ``drain_timeout_s``) for every in-flight waiter to be answered
        — a supervisor's SIGTERM then never kills a request mid-decode."""
        if drain and self.thread.is_alive() and self.status != "down":
            with self.lock:
                self.draining = True
                for eng in self.engines.values():
                    if hasattr(eng, "pause_admission"):
                        eng.pause_admission = True
                    fail_queued = getattr(eng, "fail_queued", None)
                    for req in (fail_queued() if callable(fail_queued)
                                else []):
                        ev = self._events.pop(req.id, None)
                        self._rid_engine.pop(req.id, None)
                        if ev is not None:
                            self._results[req.id] = ServingLoopError(
                                f"request {req.id} failed: server "
                                "shutting down before it was admitted")
                            ev.set()
            deadline = time.monotonic() + drain_timeout_s
            while time.monotonic() < deadline:
                with self.lock:
                    if (not self._events and all(
                            getattr(e, "n_active", 0) == 0
                            for e in self.engines.values())):
                        break
                time.sleep(0.05)
            with self.lock:
                if self._events:    # drain deadline exceeded: fail loudly
                    self._fail_pending(RuntimeError(
                        f"shutdown drain deadline ({drain_timeout_s}s) "
                        "exceeded"))
        self.stop.set()
        self.wake.set()
        self.thread.join(timeout=10)
        # stop the engines' background threads (the DispatchTracker
        # reaper) — idempotent, and stubs without shutdown() are fine
        for eng in self.engines.values():
            engine_shutdown = getattr(eng, "shutdown", None)
            if callable(engine_shutdown):
                engine_shutdown()

    def _fail_pending(self, exc: Exception) -> None:
        """Fail every waiting request with the loop's error — waiters get
        a ServingLoopError instead of hanging to their timeouts. Their
        journal entries are SEALED: the client was told 'failed', so a
        later restart's journal recovery must not resurrect the request
        and decode it for nobody (the terminal is the terminal)."""
        for rid, ev in list(self._events.items()):
            self._results[rid] = ServingLoopError(
                f"serving loop failed: {exc!r}")
            self._events.pop(rid, None)
            eng = self._rid_engine.pop(rid, self.server)
            seal = getattr(eng, "seal_journal", None)
            if callable(seal):
                seal(rid)
            # a streamed request's consumer must see the same terminal
            # error its waiter got — never hang to its own deadline
            fail_stream = getattr(eng, "fail_stream", None)
            if callable(fail_stream):
                fail_stream(rid, f"serving loop failed: {exc!r}")
            ev.set()

    @contextlib.contextmanager
    def _locked(self, wait_phase: str, **counts):
        """``with self.lock``, the wait for it written into the
        profiler's trace as ``wait_phase``: the span closes where the lock
        is held, so what runs under the lock is not inside it."""
        with _obs.phase(wait_phase, **counts):
            self.lock.acquire()
        try:
            yield
        finally:
            self.lock.release()

    def _loop(self):
        while not self.stop.is_set():
            try:
                self._serve()
                return                  # clean stop
            except Exception as e:
                if not self._recover(e):
                    return              # terminally down

    def _serve(self):
        """The inner serving loop; any exception out of here is a step
        failure handed to _recover."""
        # recovery attestation: a turn only proves the engine recovered
        # when it actually TOUCHED the device — the dispatch counters
        # moved. Idle passes, drain-only turns, and expired-sweep-only
        # turns prove nothing; re-arming on them would let a permanently
        # broken engine fail sparse requests one at a time forever
        # without ever exhausting the budget (or flipping /healthz).
        # Engines without the counters (test stubs) fall back to "had
        # work to do" (active slots or a queue) observed pre-step.
        has_ctrs = any(hasattr(e, "blocks_dispatched")
                       for e in self.engines.values())

        def dispatch_ctrs():
            return tuple(
                (getattr(e, "admission_dispatches", 0),
                 getattr(e, "blocks_dispatched", 0))
                for e in self.engines.values())

        def drain(eng, done):
            # the engine call first: a predictive engine syncs inside it,
            # under serve.step.* phases of its own, and those must not
            # nest under this one
            got = eng.drain_completed()
            with _obs.phase(_obs.PHASE_DRAIN, completions=len(got)):
                done.update(got)

        while not self.stop.is_set():
            with self._locked(_obs.PHASE_LOOP_LOCK_WAIT):
                busy = False
                attests = False
                pre = dispatch_ctrs()
                done = {}
                now = time.monotonic()
                ckpt_due = bool(
                    self.journal_checkpoint_s
                    and now - self._last_checkpoint
                    >= self.journal_checkpoint_s)
                # one loop thread steps every busy engine round-robin:
                # two models serve concurrently from one process, each
                # from its own slot pool. One engine's step() failure
                # must NOT discard completions another engine already
                # DRAINED this turn (draining popped them from the
                # engine and sealed their journal entries — dropping
                # `done` would strand their waiters unrecoverably) and
                # must not STARVE the engines after it in iteration
                # order: the remaining engines still step this turn, the
                # drained set is delivered, and only then does the FIRST
                # failure propagate to _recover (which resets exactly
                # self._stepping, the engine whose step died; a second
                # failing engine is caught on the next turn).
                step_exc: Exception | None = None
                failed_eng = None
                for eng in self.engines.values():
                    if eng.idle:
                        continue
                    busy = True
                    attests = attests or (
                        getattr(eng, "n_active", 1) > 0
                        or getattr(eng, "pending", 1) > 0)
                    self._stepping = eng
                    try:
                        eng.step()
                        # only drain when something is (or is known to
                        # be) finished: in predictive mode
                        # drain_completed forces a device sync, which
                        # called every tick would serialize compute
                        # with the host round trip
                        if eng.completions_ready:
                            drain(eng, done)
                        elif ckpt_due:
                            # durability checkpoint (bounded cadence):
                            # keep the journal's emitted prefixes fresh
                            # for replay/failover without draining the
                            # dispatch runway (see
                            # SlotServer.checkpoint_progress)
                            ckpt = getattr(eng, "checkpoint_progress",
                                           None)
                            if callable(ckpt):
                                ckpt()
                                if eng.completions_ready:
                                    drain(eng, done)
                    except Exception as e:
                        if step_exc is None:
                            step_exc, failed_eng = e, eng
                if step_exc is None:
                    self._stepping = None
                    if busy and ckpt_due:
                        self._last_checkpoint = now
                    if busy:
                        with _obs.phase(_obs.PHASE_OBSERVE):
                            self._observe_load()
                    if has_ctrs:
                        attests = dispatch_ctrs() != pre
                    if busy and attests and self.status == "degraded":
                        # a real device dispatch survived: recovery
                        # complete — the failure streak, its backoff,
                        # and the sticky error message re-arm
                        self.status = "ok"
                        self._restart_streak = 0
                        self.error = None
            if done:
                self._deliver(done)
            if step_exc is not None:
                self._stepping = failed_eng     # _recover resets THIS one
                raise step_exc
            if not busy:
                # idle: the next busy turn must not record this gap as a
                # giant scheduling turn in loop_turn_s
                self._turn_timer.reset_interval()
                with _obs.phase(_obs.PHASE_IDLE):
                    self.wake.wait(0.02)
                self.wake.clear()

    def _deliver(self, done: dict) -> None:
        # the first completed request proves every warmup program shape
        # compiled: XLA compiles from here on are RECOMPILES (idempotent
        # — only the first call draws the line)
        self.compile_telemetry.mark_warm()
        # deliver under the lock so this can't interleave with a
        # waiter's timeout cleanup (event popped here, then the
        # waiter clears _results, then the store below lands and
        # leaks) — atomically: either the waiter cleaned up first
        # (ev is None, completion dropped) or the store+set land
        # before the waiter's cleanup pops both
        with self._locked(_obs.PHASE_LOOP_LOCK_WAIT), \
                _obs.phase(_obs.PHASE_DELIVER, completions=len(done)):
            for rid, comp in done.items():
                ev = self._events.pop(rid, None)
                self._rid_engine.pop(rid, None)
                if ev is None:
                    # no waiter (timed out / cancelled / failed submit):
                    # drop the completion instead of growing _results
                    continue
                if getattr(comp, "finish_reason", None) == "expired":
                    # the deadline passed while queued; the waiter gets
                    # the timeout it already paid for, as an error — not
                    # a 200 with zero tokens
                    self._results[rid] = TimeoutError(
                        f"request {rid} expired in queue before admission")
                else:
                    self._results[rid] = comp
                ev.set()

    def _recover(self, exc: Exception) -> bool:
        """Handle a serving-loop failure: reset the engine and report
        True to restart, or flip terminally down and report False."""
        import traceback

        print("serving loop failed:\n" + traceback.format_exc(),
              flush=True)
        # the failed step + the coming backoff must not book into
        # loop_turn_s as one giant scheduling turn (same contract as the
        # idle-branch reset)
        self._turn_timer.reset_interval()
        with self.lock:
            self.loop_failures += 1
            self._restart_streak += 1
            self.error = f"{type(exc).__name__}: {exc}"
            # reset the engine whose step died (the others' state is
            # intact — resetting them would re-prefill for nothing)
            failed_eng = self._stepping or self.server
            reset = getattr(failed_eng, "reset", None)
            if not callable(reset):
                self.status = "down"
                self._fail_pending(exc)
                return False
            if self._restart_streak > self.max_loop_restarts:
                self.status = "down"
                self.error += (f" (restart budget of "
                               f"{self.max_loop_restarts} exhausted)")
                self._fail_pending(exc)
                return False
            self.status = "degraded"
            try:
                lost = reset()
            except Exception as e2:
                print("serving reset failed:\n" + traceback.format_exc(),
                      flush=True)
                self.status = "down"
                self.error = f"reset failed: {type(e2).__name__}: {e2}"
                self._fail_pending(e2)
                return False
            # fail ONLY the requests whose in-flight work died with the
            # ring; queued waiters ride through the restart untouched
            for rid in lost:
                ev = self._events.pop(rid, None)
                self._rid_engine.pop(rid, None)
                if ev is not None:
                    self._results[rid] = ServingLoopError(
                        f"request {rid} lost to a serving-loop failure: "
                        f"{self.error}")
                    ev.set()
            self.loop_restarts += 1
            backoff = min(
                self.loop_backoff_s * (2 ** (self._restart_streak - 1)),
                10.0)
        # exponential backoff OUTSIDE the lock (waiters must be able to
        # time out / submit while we sit out a flapping device)
        return not self.stop.wait(backoff)

    # ------------------------------------------------------------ requests

    def _engine_for(self, model: str | None):
        """Route a request's ``model=`` to its engine (None = the
        default model). Unknown names are an UnknownModelError — the
        HTTP layer's 400, never a silent fallback to the wrong
        weights."""
        if model is None:
            return self.server
        eng = self.engines.get(str(model))
        if eng is None:
            raise UnknownModelError(
                f"unknown model {model!r}; this process serves "
                f"{sorted(self.engines)}")
        return eng

    def submit_async(self, prompt, max_new_tokens: int,
                     timeout: float = 600.0,
                     temperature: float | None = None,
                     top_k: int | None = None,
                     cache_prompt: bool | None = None,
                     resume_tokens: list | None = None,
                     progress_key: str | None = None,
                     model: str | None = None,
                     stream=None,
                     stop: list | None = None,
                     logprobs: int = 0,
                     priority: str = "interactive",
                     trace=None,
                     routes: bool = False):
        """Admission half of generate(): returns (request_id, event). The
        request carries ``timeout`` as its queue deadline — if it is
        still queued when the waiter would have given up, admission skips
        it instead of decoding for nobody. ``resume_tokens`` teacher-
        forces an already-emitted prefix (router failover resume — the
        completion's tokens include it); ``progress_key`` registers a
        caller-chosen key for GET /progress so a router can journal
        this request's emitted prefix while it runs; ``model`` routes
        to the named engine (multi-model serving); ``stream`` attaches
        a caller-owned ``api.stream.TokenStream`` for per-token
        delivery — attachment is atomic with the submit, so no emitted
        token can slip between them; ``priority`` is the admission
        tier ("interactive" | "batch" — docs/serving.md "Paged KV &
        admission tiers"); ``routes`` asks a model with routed expert
        layers for the experts it chose (``Completion.routes``)."""
        from ..models.serving import Request

        engine = self._engine_for(model)
        req = Request(prompt=prompt, max_new_tokens=max_new_tokens,
                      temperature=temperature, top_k=top_k,
                      cache_prompt=cache_prompt,
                      resume_tokens=resume_tokens,
                      deadline=time.monotonic() + timeout,
                      stop=stop, logprobs=int(logprobs or 0),
                      priority=str(priority or "interactive"),
                      trace=trace, routes=bool(routes),
                      model=getattr(engine, "model", None)
                      if model is not None else None)
        ev = threading.Event()
        try:
            # health check + event registration + submit are ONE atomic
            # step vs the loop's failure handler (which flips the status
            # and fails registered events under this same lock)
            with self._locked(_obs.PHASE_SUBMIT_LOCK_WAIT, rid=req.id):
                if self.status == "down":
                    raise ServingLoopError(
                        f"serving loop is down: {self.error}")
                if self.draining:
                    raise ServingLoopError(
                        "server is draining; not accepting requests")
                self._events[req.id] = ev
                engine.submit(req)          # may shed: QueueFullError
                self._rid_engine[req.id] = engine
                if stream is not None:
                    attach = getattr(engine, "attach_stream", None)
                    if callable(attach):
                        attach(req.id, stream)
                    else:       # engine without streaming (test stubs)
                        stream.fail("engine does not support streaming")
                if progress_key:
                    self._progress_keys[str(progress_key)] = req.id
                    if len(self._progress_keys) > self._progress_keys_cap:
                        self._evict_progress_keys_locked()
        except Exception:
            self._events.pop(req.id, None)   # rejected: no waiter to leak
            self._rid_engine.pop(req.id, None)
            raise
        self.wake.set()
        return req.id, ev

    def _evict_progress_keys_locked(self) -> None:
        """Shrink the progress-key map to its cap, evicting TERMINAL
        requests' keys first (oldest first; the engine journal says
        which rids are still live). Evicting purely by age would drop a
        long-running decode's key — exactly the request with the most
        work invested — while dead keys sat resident. Live requests are
        bounded by slots+queue, far under the cap, so the blind
        oldest-first fallback only fires for engines without a
        journal."""
        for key in list(self._progress_keys):
            if len(self._progress_keys) <= self._progress_keys_cap:
                return
            rid = self._progress_keys[key]
            prog = getattr(self._rid_engine.get(rid, self.server),
                           "progress", None)
            if not callable(prog) or prog(rid) is None:     # terminal
                del self._progress_keys[key]
        while len(self._progress_keys) > self._progress_keys_cap:
            self._progress_keys.popitem(last=False)

    def progress(self, keys) -> dict:
        """The GET /progress payload: per requested key, the live
        request's replay state ({tokens, prompt_tokens}) from the
        engine journal — keys that are unknown or whose request is
        already terminal are simply absent (the caller treats absence
        as 'no information', keeping whatever prefix it last saw)."""
        out = {}
        with self.lock:
            for key in keys:
                rid = self._progress_keys.get(key)
                if rid is None:
                    continue
                prog = getattr(self._rid_engine.get(rid, self.server),
                               "progress", None)
                if not callable(prog):
                    continue
                p = prog(rid)
                if p is not None:
                    out[key] = p
        return out

    def take_result(self, request_id: int):
        res = self._results.pop(request_id)
        if isinstance(res, Exception):   # the loop failed this request
            raise res
        return res

    def discard_result(self, request_id: int) -> None:
        """Streamed-request cleanup: the SSE handler delivered the
        terminal through the TokenStream, so the waiter-side event and
        any stored result are dropped unread (atomic vs ``_deliver``:
        popping the event means a not-yet-delivered completion is
        dropped instead of leaking into ``_results``)."""
        with self.lock:
            self._events.pop(request_id, None)
            self._results.pop(request_id, None)
            self._rid_engine.pop(request_id, None)

    def note_stream_disconnect(self) -> None:
        with self.lock:
            self.stream_disconnects += 1

    def cancel(self, request_id: int) -> bool:
        """The abandonment path: drop the waiter and stop the request
        wherever it is (queued, prefilling, or mid-decode) so a dead
        client's work stops burning decode steps in its slot."""
        with self.lock:
            self._events.pop(request_id, None)
            self._results.pop(request_id, None)
            eng = self._rid_engine.pop(request_id, self.server)
            srv_cancel = getattr(eng, "cancel", None)
            return bool(callable(srv_cancel) and srv_cancel(request_id))

    def import_async(self, payload: dict, timeout: float = 600.0,
                     stream=None, trace=None):
        """Admission half of the KV-transfer decode leg (POST
        /kv/import): install a prefill replica's exported blocks into
        the matching engine and register a waiter exactly like
        ``submit_async`` — returns (request_id, event). The engine
        raises ValueError on payload damage (the torn-transfer
        contract: the caller falls back to journal replay, i.e.
        re-prefilling from the prompt on a replica that decodes) and
        QueueFullError when no slot/pool blocks are free."""
        with self.lock:
            if self.status == "down":
                raise ServingLoopError(
                    f"serving loop is down: {self.error}")
            if self.draining:
                raise ServingLoopError(
                    "server is draining; not accepting requests")
            engine = self._engine_for(
                payload.get("model") if isinstance(payload, dict)
                else None)
            imp = getattr(engine, "import_blocks", None)
            if not callable(imp):
                raise ValueError(
                    "this engine does not support KV import")
            # keyword only when set: engines/test stubs predating the
            # trace kwarg keep working header-less
            if trace is not None:
                rid = imp(payload, trace=trace)
            else:
                rid = imp(payload)  # ValueError/QueueFullError propagate
            ev = threading.Event()
            self._events[rid] = ev
            self._rid_engine[rid] = engine
            if stream is not None:
                attach = getattr(engine, "attach_stream", None)
                if callable(attach):
                    attach(rid, stream)
                else:
                    stream.fail("engine does not support streaming")
        self.wake.set()
        return rid, ev

    def export_payload(self, request_id: int) -> dict:
        """Pop a prefilled request's KV handoff payload (rides the
        /generate response on a prefill-role replica). KeyError when no
        engine holds one — the stash is bounded, so an aged-out export
        simply sends the router down the replay fallback."""
        with self.lock:
            for eng in self.engines.values():
                exp = getattr(eng, "export_blocks", None)
                if not callable(exp):
                    continue
                try:
                    return exp(request_id)
                except KeyError:
                    continue
        raise KeyError(f"no KV export payload for request {request_id}")

    def generate(self, prompt, max_new_tokens: int, timeout: float = 600.0,
                 temperature: float | None = None,
                 top_k: int | None = None,
                 cache_prompt: bool | None = None,
                 model: str | None = None):
        rid, ev = self.submit_async(
            prompt, max_new_tokens, timeout=timeout,
            temperature=temperature, top_k=top_k, cache_prompt=cache_prompt,
            model=model)
        if not ev.wait(timeout):
            self.cancel(rid)     # free the slot, don't decode for nobody
            raise TimeoutError(
                f"request {rid} timed out after {timeout}s; cancelled")
        return self.take_result(rid)

    # -------------------------------------------------------- observability

    def _observe_load(self) -> None:
        """Feed the serving-load gauges (called under the lock, once per
        scheduling turn — block-paced, so sampling is cheap). The turn
        cadence itself lands in the loop_turn_s histogram, and the
        histogram quantiles ride back into the accumulator as gauges so
        the portal/history layer sees TTFT next to the resource
        metrics without learning a new payload shape."""
        m = self.metrics
        engines = list(self.engines.values())

        def total(attr):
            return float(sum(getattr(e, attr, 0) for e in engines))

        m.observe(_metrics.SERVING_ACTIVE_SLOTS, total("n_active"))
        m.observe(_metrics.SERVING_QUEUE_DEPTH, total("pending"))
        computed = total("prefill_tokens_computed")
        reused = total("prefill_tokens_reused")
        if computed + reused > 0:
            m.observe(_metrics.SERVING_PREFILL_REUSED_FRAC,
                      reused / (computed + reused))
        m.observe(_metrics.SERVING_SHED_TOTAL, total("shed_requests"))
        m.observe(_metrics.SERVING_CANCELLED_TOTAL,
                  total("cancelled_requests"))
        m.observe(_metrics.SERVING_EXPIRED_TOTAL, total("expired_requests"))
        m.observe(_metrics.SERVING_LOOP_RESTARTS,
                  float(self.loop_restarts))
        tel = getattr(self.server, "telemetry", None)
        if tel is not None:
            # the scheduling turn is app-level (one loop thread steps
            # every engine); it ticks into the default engine's
            # telemetry, whose loop_turn_s is therefore the process's
            dt = self._turn_timer.tick()
            if dt is not None:
                tel.observe("loop_turn_s", dt)

            def merged(name):
                hists = [t.hist[name] for t in
                         (getattr(e, "telemetry", None)
                          for e in engines) if t is not None]
                if len(hists) == 1:
                    return hists[0]
                from ..observability import Histogram

                out = Histogram()
                for h in hists:
                    out.merge(h)
                return out

            ttft, tpot = merged("ttft_s"), merged("tpot_s")
            if ttft.count:
                m.observe(_metrics.SERVING_TTFT_P50_S, ttft.quantile(0.5))
                m.observe(_metrics.SERVING_TTFT_P99_S, ttft.quantile(0.99))
            if tpot.count:
                m.observe(_metrics.SERVING_TPOT_P50_S, tpot.quantile(0.5))
                m.observe(_metrics.SERVING_TPOT_P99_S, tpot.quantile(0.99))
        est = getattr(self.server, "estimate_retry_after", None)
        if callable(est):
            m.observe(_metrics.SERVING_RETRY_AFTER_S, float(est()))

    def set_autoscale_hint(self, cooldown_s: float) -> None:
        """Record the fleet autoscaler's remaining scale-up cooldown
        (seconds). Every 429 Retry-After from now on advertises at
        least this window (decaying as wall time passes): a shed client
        told to retry in 2s against a fleet that cannot add a replica
        for 20s just gets shed again 10 times. The driver's autoscale
        tick pushes it over POST /autoscale/hint after each scale
        decision; 0 clears it."""
        with self.lock:
            self._autoscale_hint = (max(0.0, float(cooldown_s)),
                                    time.monotonic())

    def _autoscale_hint_remaining_locked(self) -> float:
        hint, t0 = self._autoscale_hint
        if hint <= 0.0:
            return 0.0
        return max(0.0, hint - (time.monotonic() - t0))

    def retry_after_s(self, engine_estimate: float | None = None) -> int:
        """The 429 Retry-After value: the LARGER of the engine's
        service-rate estimate (seconds until a queue seat frees —
        passed in when the shed already carried one, re-asked
        otherwise) and the autoscaler's remaining scale-up cooldown
        (``set_autoscale_hint``), clamped to [1, 60]; 1 when the
        engine has no estimator (test stubs) or the estimate fails."""
        import math

        est = 0.0
        if engine_estimate is not None:
            try:
                est = float(engine_estimate)
            except (TypeError, ValueError):
                est = 0.0
        else:
            fn = getattr(self.server, "estimate_retry_after", None)
            if callable(fn):
                try:
                    with self.lock:
                        est = float(fn())
                except Exception:
                    est = 0.0
        with self.lock:
            cooldown = self._autoscale_hint_remaining_locked()
        return max(1, min(60, int(math.ceil(max(est, cooldown, 1.0)))))

    # ----------------------------------------------------- SSE reconnect

    def save_resume_prefix(self, request_id: int, tokens) -> None:
        """Park a vanished streaming client's full emitted prefix so a
        ``Last-Event-ID`` reconnect can resume it (docs/serving.md "SSE
        reconnect"). The handler accumulates exactly what the stream
        fed it — the journaled prefix — and saves it at disconnect."""
        toks = [int(t) for t in tokens]
        if not toks:
            return
        with self.lock:
            self._resume_cache[int(request_id)] = toks
            self._resume_cache.move_to_end(int(request_id))
            while len(self._resume_cache) > self._resume_cache_cap:
                self._resume_cache.popitem(last=False)

    def resume_prefix(self, request_id: int) -> list | None:
        """The emitted prefix a ``Last-Event-ID: <rid>:<n>`` reconnect
        resumes from, or None when ``rid`` is unknown (the reconnect
        degrades to a fresh request). Checks the disconnect cache
        first (single use — popped); a rid still LIVE means the client
        reconnected before the server noticed the old connection die:
        the zombie request is cancelled (its slot returns to live
        traffic) and its journaled prefix resumed."""
        rid = int(request_id)
        with self.lock:
            toks = self._resume_cache.pop(rid, None)
            if toks is not None:
                return toks
            eng = self._rid_engine.get(rid)
            prog = (getattr(eng, "progress", None)
                    if eng is not None else None)
            p = prog(rid) if callable(prog) else None
        if p is None:
            return None
        self.cancel(rid)
        return [int(t) for t in p.get("tokens", [])] or None

    def prometheus_metrics(self) -> str:
        """The GET /metrics payload: every /stats number in Prometheus
        text format — SERVING_* gauges/counters, loop lifecycle, the
        latency histograms (cumulative buckets), and the
        MetricsAccumulator snapshot as labeled gauges."""
        from ..observability import PromRenderer, TELEMETRY_HISTOGRAMS

        st = self.stats()
        r = PromRenderer()
        r.gauge("serving_slots", st.get("slots", 0),
                "configured KV-cache slots")
        r.gauge(_metrics.SERVING_ACTIVE_SLOTS, st.get("active", 0),
                "slots holding an unfinished request")
        r.gauge(_metrics.SERVING_QUEUE_DEPTH, st.get("queued", 0),
                "requests waiting for a slot")
        computed = st.get("prefill_tokens_computed", 0)
        reused = st.get("prefill_tokens_reused", 0)
        if computed + reused > 0:
            r.gauge(_metrics.SERVING_PREFILL_REUSED_FRAC,
                    reused / (computed + reused),
                    "fraction of prefill tokens served from the prefix "
                    "cache")
        r.gauge(_metrics.SERVING_RETRY_AFTER_S,
                st.get("retry_after_s", 1),
                "current 429 Retry-After estimate (seconds until a "
                "queue seat frees)")
        for name, key, help_text in (
                (_metrics.SERVING_SHED_TOTAL, "shed",
                 "requests refused with queue full (HTTP 429)"),
                (_metrics.SERVING_CANCELLED_TOTAL, "cancelled",
                 "requests cancelled by their waiter"),
                (_metrics.SERVING_EXPIRED_TOTAL, "expired",
                 "requests whose deadline passed while queued"),
                ("serving_engine_resets_total", "resets",
                 "SlotServer.reset() recoveries"),
                (_metrics.SERVING_REPLAYS_TOTAL, "replays",
                 "requests resumed from a journaled/teacher-forced "
                 "prefix instead of failing (reset replay, journal "
                 "recovery, router-failover resume)"),
                (_metrics.SERVING_REPLAYED_TOKENS_TOTAL,
                 "replayed_tokens",
                 "emitted tokens carried across a death boundary by "
                 "replay (teacher-forced, re-prefilled not re-decoded)"),
                ("serving_blocks_dispatched_total", "blocks_dispatched",
                 "decode blocks dispatched to the device"),
                ("serving_admission_dispatches_total",
                 "admission_dispatches", "prefill programs dispatched"),
                ("serving_prefill_tokens_computed_total",
                 "prefill_tokens_computed",
                 "prompt tokens prefilled through the model"),
                ("serving_prefill_tokens_reused_total",
                 "prefill_tokens_reused",
                 "prompt tokens copied from the prefix cache"),
        ):
            if key in st:
                r.counter(name, st[key], help_text)
        # streaming delivery families (docs/observability.md "Streaming
        # metrics"): rendered unconditionally — a zero is a statement
        r.gauge(_metrics.SERVING_STREAMS_ACTIVE,
                st.get("streams_active", 0),
                "live per-request SSE token streams")
        r.counter(_metrics.SERVING_STREAMS_OPENED_TOTAL,
                  st.get("streams_opened", 0),
                  "token streams ever attached")
        r.counter(_metrics.SERVING_STREAM_STALLS_TOTAL,
                  st.get("stream_stalls", 0),
                  "stream feeds that found the consumer's chunk queue "
                  "full (backpressure: coalesced, accounted, never "
                  "dropped)")
        r.counter(_metrics.SERVING_STREAM_DISCONNECTS_TOTAL,
                  st.get("stream_disconnects", 0),
                  "clients that vanished mid-stream (mapped onto "
                  "cancel(): the slot returns to live traffic)")
        # paged-KV allocator families (docs/serving.md "Paged KV &
        # admission tiers"): pool occupancy, per-class block usage,
        # admission deferrals and interleaved prefill chunks
        pk = st.get("paged_kv")
        if pk:
            r.gauge("serving_kv_pool_blocks_total",
                    pk.get("pool_blocks_total", 0),
                    "allocatable KV blocks in the paged pool")
            r.gauge("serving_kv_pool_blocks_free",
                    pk.get("pool_blocks_free", 0),
                    "KV blocks on the free list")
            r.gauge("serving_kv_pool_blocks_used",
                    pk.get("pool_blocks_used", 0),
                    "KV blocks held by slots, the prefix trie, or the "
                    "draft mirror (refcounted)")
            r.gauge("serving_kv_pool_blocks_peak",
                    pk.get("pool_blocks_peak", 0),
                    "high-water mark of used KV blocks")
            r.counter("serving_kv_admission_defers_total",
                      pk.get("admission_defers", 0),
                      "admissions deferred for pool blocks or a class "
                      "budget (the request stays queued, never fails)")
            r.counter("serving_prefill_chunks_interleaved_total",
                      pk.get("prefill_chunks_interleaved", 0),
                      "prefill chunks dispatched between decode blocks "
                      "(chunked-prefill interleaving)")
            # pool occupancy by OWNER (disaggregated serving lands and
            # leaves blocks through both slots and the trie — one gauge
            # family makes pressure readable): slot+trie+shared+free ==
            # total
            for state, n in sorted(
                    (pk.get("pool_state") or {}).items()):
                r.gauge("serving_kv_pool_blocks", n,
                        "KV pool blocks by owner: free list, slot "
                        "tables only, prefix trie only, or shared "
                        "(slot+trie at once)", labels={"state": state})
            # KV block transfer (docs/serving.md "Disaggregated
            # serving"): prefill-side exports, decode-side imports, and
            # payloads rejected as damaged (torn transfer -> journal
            # replay fallback)
            r.counter("serving_kv_exports_total",
                      pk.get("kv_exports", 0),
                      "finished prefills serialized for handoff")
            r.counter("serving_kv_imports_total",
                      pk.get("kv_imports", 0),
                      "transfer payloads installed into the local pool")
            r.counter("serving_kv_import_rejects_total",
                      pk.get("kv_import_rejects", 0),
                      "transfer payloads rejected (version/geometry/"
                      "checksum damage; the router re-prefills via "
                      "journal replay)")
            for cls, used in sorted(
                    (pk.get("class_used") or {}).items()):
                r.gauge("serving_kv_class_blocks_used", used,
                        "KV blocks exclusively held per admission tier "
                        "(COW/shared blocks are unattributed)",
                        labels={"class": cls})
        for cls, n in sorted((st.get("shed_by_class") or {}).items()):
            r.counter("serving_shed_by_class_total", n,
                      "requests shed per admission tier (queue-full "
                      "429s plus batch displacements by interactive "
                      "arrivals)", labels={"class": cls})
        loop = st.get("loop", {})
        r.counter(_metrics.SERVING_LOOP_RESTARTS,
                  loop.get("restarts", self.loop_restarts),
                  "successful serving-loop recoveries")
        r.counter("serving_loop_failures_total",
                  loop.get("failures", self.loop_failures),
                  "serving-loop step failures")
        r.gauge("serving_loop_up",
                0 if loop.get("status", self.status) == "down" else 1,
                "1 unless the serving loop is terminally down")
        tel = getattr(self.server, "telemetry", None)
        if tel is not None:
            # render under the serving lock: the loop thread mutates the
            # histograms under it, and a mid-observe scrape would emit
            # buckets disagreeing with _count/_sum. Multi-model: the
            # unlabeled series is the PROCESS aggregate — engines'
            # histograms share bounds, so they merge into a scratch
            # copy (the {model=...} partition below carries each
            # engine's own)
            from ..observability import Histogram as _Hist

            tels = [t for t in (getattr(e, "telemetry", None)
                                for e in self.engines.values())
                    if t is not None]
            with self.lock:
                for name, help_text in TELEMETRY_HISTOGRAMS.items():
                    prom = "serving_" + name[:-2] + "_seconds"
                    if len(tels) > 1:
                        merged = _Hist()
                        for t in tels:
                            merged.merge(t.hist[name])
                        r.histogram(prom, merged, help_text)
                    else:
                        r.histogram(prom, tel.hist[name], help_text)
        # device-time attribution (observability.DispatchTracker): how
        # long the device actually spent behind each dispatched program,
        # per program kind, plus the measured in-flight pipeline depth —
        # the histograms are copied under the tracker's own lock (the
        # reaper thread feeds them outside the serving lock)
        tracker = getattr(self.server, "dispatch_tracker", None)
        if tracker is not None:
            for kind, h in sorted(tracker.histograms().items()):
                r.histogram("serving_dispatch_ready_seconds", h,
                            "dispatch -> device-ready latency per "
                            "program kind (reaper-measured, off the "
                            "hot path)", labels={"kind": kind})
            r.gauge("serving_inflight_dispatches", tracker.in_flight,
                    "device programs dispatched but not yet observed "
                    "ready (the measured pipeline depth)")
            r.counter("serving_dispatches_tracked_total",
                      tracker.tracked_total,
                      "dispatches registered with the tracker")
            r.counter("serving_dispatch_track_dropped_total",
                      tracker.dropped,
                      "dispatches untracked because the reaper fell "
                      "behind (telemetry loss, not request loss)")
            r.counter("serving_dispatch_reap_errors_total",
                      tracker.reap_errors,
                      "tracked buffers whose block_until_ready raised "
                      "(died with a failed dispatch)")
        # XLA compile telemetry (observability.CompileTelemetry): every
        # actual backend compile in this process, and how many happened
        # after warmup — nonzero post-warm recompiles in steady state
        # mean a dispatched program leaks dynamic shapes
        ct = self.compile_telemetry
        comp = ct.snapshot()
        r.histogram("serving_xla_compile_seconds", ct.hist_copy(),
                    "XLA backend compile duration per compilation "
                    "(cache hits don't count)")
        r.counter("serving_xla_compiles_total", comp["compiles"],
                  "XLA backend compilations in this process")
        r.counter("serving_xla_recompiles_post_warm_total",
                  comp["recompiles_post_warm"],
                  "compilations after the first served request "
                  "(steady-state recompiles: the shape-leak signal)")
        for entry in st.get("metrics", []):
            r.gauge("serving_task_metric", entry["value"],
                    "MetricsAccumulator snapshot (max_/avg_ per gauge)",
                    labels={"name": entry["name"]})
        # ---- per-model partition (multi-model serving) ----
        # every registered model gets an info-gauge series, and the
        # serving load/latency families repeat with a {model="..."}
        # label partitioning the unlabeled process-level aggregates
        # above — so two models behind one process are separable in any
        # scraper, resolving the "one anonymous model" limitation
        # (docs/observability.md "Per-model labels")
        per_model = st.get("models", {})
        for name, eng in self.engines.items():
            lab = {"model": name}
            r.gauge(_metrics.SERVING_MODELS, 1,
                    "registered serving models (info gauge: one series "
                    "per model, value 1)", labels=lab)
            est = per_model.get(name) or {}
            r.gauge(_metrics.SERVING_ACTIVE_SLOTS, est.get("active", 0),
                    "slots holding an unfinished request",
                    labels=lab)
            r.gauge(_metrics.SERVING_QUEUE_DEPTH, est.get("queued", 0),
                    "requests waiting for a slot", labels=lab)
            for fam, key in (
                    (_metrics.SERVING_SHED_TOTAL, "shed"),
                    (_metrics.SERVING_CANCELLED_TOTAL, "cancelled"),
                    (_metrics.SERVING_EXPIRED_TOTAL, "expired"),
                    (_metrics.SERVING_REPLAYS_TOTAL, "replays"),
                    (_metrics.SERVING_REPLAYED_TOKENS_TOTAL,
                     "replayed_tokens"),
                    ("serving_blocks_dispatched_total",
                     "blocks_dispatched")):
                if key in est:
                    r.counter(fam, est[key], labels=lab)
            etel = getattr(eng, "telemetry", None)
            if etel is not None:
                with self.lock:
                    for hname in ("ttft_s", "tpot_s", "queue_wait_s",
                                  "e2e_s"):
                        r.histogram(
                            "serving_" + hname[:-2] + "_seconds",
                            etel.hist[hname], labels=lab)
            # speculative decoding families (spec-enabled engines only):
            # proposals vs acceptances, the live autotuned gamma, and
            # the acceptance-rate / verify-round histograms
            spec = est.get("speculative")
            if spec:
                r.counter(_metrics.SERVING_SPEC_ROUNDS_TOTAL,
                          spec.get("rounds", 0),
                          "speculative verify rounds dispatched",
                          labels=lab)
                r.counter(_metrics.SERVING_SPEC_PROPOSED_TOKENS_TOTAL,
                          spec.get("proposed_tokens", 0),
                          "draft tokens proposed for verification",
                          labels=lab)
                r.counter(_metrics.SERVING_SPEC_ACCEPTED_TOKENS_TOTAL,
                          spec.get("accepted_tokens", 0),
                          "draft tokens the target accepted", labels=lab)
                r.gauge(_metrics.SERVING_SPEC_GAMMA,
                        spec.get("gamma", 0),
                        "the next verify round's draft window (autotuned "
                        "from the acceptance EWMA, or pinned)",
                        labels=lab)
                # render under the serving lock: the loop thread
                # mutates these histograms in _process, same contract
                # as the telemetry histograms above
                with self.lock:
                    ah = getattr(eng, "spec_accept_hist", None)
                    if ah is not None:
                        r.histogram(
                            _metrics.SERVING_SPEC_ACCEPTANCE_RATE, ah,
                            "per-round draft acceptance rate "
                            "(accepted/gamma, pre-clamp)", labels=lab)
                    vh = getattr(eng, "spec_rounds_hist", None)
                    if vh is not None:
                        r.histogram(
                            _metrics.SERVING_SPEC_VERIFY_ROUNDS, vh,
                            "verify rounds per completed request",
                            labels=lab)
        return r.render()

    def health(self) -> dict:
        """The /healthz payload: ``status`` is the lifecycle word
        (ok/degraded/draining/down), ``healthy`` the load-balancer bool.
        Draining reports UNhealthy: the whole point of a graceful drain
        is that the balancer stops routing here while in-flight requests
        finish — a 200 would feed it traffic that only ever sees 503s.
        Degraded stays healthy: the server still accepts and queues."""
        with self.lock:
            status = ("draining" if self.draining and self.status != "down"
                      else self.status)
            return {"healthy": self.healthy, "status": status,
                    "error": self.error,
                    "loop_restarts": self.loop_restarts}

    # top-level /stats keys a multi-model process SUMS across engines so
    # the unlabeled process view (and the /metrics counters rendered
    # from it) stays a true aggregate, not the default engine's slice
    _AGGREGATE_STAT_KEYS = (
        "slots", "active", "queued", "shed", "cancelled", "expired",
        "resets", "replays", "replayed_tokens", "blocks_dispatched",
        "admission_dispatches", "prefill_tokens_computed",
        "prefill_tokens_reused", "chaos_faults_injected",
        "streams_active", "streams_opened", "stream_stalls")

    def stats(self) -> dict:
        with self.lock:
            # per-model partition: one stats payload per engine, keyed
            # by registry name (the router's model-aware routing reads
            # the KEYS as this replica's advertised model set). The
            # top-level payload is the DEFAULT engine's (computed once
            # — its dict doubles as the models entry), with the load/
            # counter keys summed across engines so single-number
            # consumers see the whole process.
            per = {
                name: (eng.stats() if hasattr(eng, "stats") else {
                    "slots": getattr(eng, "slots", 0),
                    "active": getattr(eng, "n_active", 0),
                    "queued": getattr(eng, "pending", 0),
                    "max_len": getattr(eng, "max_len", 0),
                    "block_size": getattr(eng, "block_size", 0)})
                for name, eng in self.engines.items()}
            out = dict(per[self.default_model])
            out["models"] = per
            if len(self.engines) > 1:
                for k in self._AGGREGATE_STAT_KEYS:
                    if k in out:
                        out[k] = sum(int(p.get(k, 0) or 0)
                                     for p in per.values())
            out["loop"] = {
                "status": self.status,
                "restarts": self.loop_restarts,
                "failures": self.loop_failures,
                "max_restarts": self.max_loop_restarts,
            }
            # streaming: only the HTTP layer sees sockets die, so the
            # disconnect counter lives here, next to the engines'
            # streams_active/streams_opened/stream_stalls aggregates
            out["stream_disconnects"] = self.stream_disconnects
            # which process answers here — fleet tooling (and the kill-a-
            # replica e2e) needs to map an endpoint back to its process
            import os as _os

            from ..utils.jaxenv import device_report

            out["pid"] = _os.getpid()
            # where this replica runs — platform/kind/count as jax
            # reports them — beside the engine's device-time attribution
            out["device"] = {**out.get("device", {}), **device_report()}
            # disaggregated-serving role advertisement (docs/serving.md
            # "Disaggregated serving"): the fleet router reads this to
            # split prefill traffic from decode traffic; engines without
            # a role (test stubs) advertise the default "both"
            out["role"] = out.get("role") or getattr(
                self.server, "role", "both")
            out["metrics"] = self.metrics.snapshot()
            # XLA compile telemetry: compiles/compile_time_s/
            # recompiles_post_warm — /stats mirror of the
            # serving_xla_compile_* exposition families
            out["compile"] = self.compile_telemetry.snapshot()
            return out

    def capture_profile(self, seconds: float) -> dict:
        """The GET /debug/profile?seconds=N implementation: capture a
        jax.profiler trace (xplane proto) of whatever the device is
        doing for ``seconds`` into ``<trace_dir>/profiles/<stamp>/``.
        Runs on the HTTP handler thread — the serving loop keeps
        dispatching, which is the point: the capture sees live traffic.
        One capture at a time (jax's trace machinery is process-global);
        a concurrent request gets a busy error."""
        from pathlib import Path

        from .. import constants as c
        from ..train.profiling import trace

        if not self.trace_dir:
            raise RuntimeError(
                "profiling needs --trace-dir (nowhere to write the "
                "xplane dump)")
        if not 0 < seconds <= 120:
            raise ValueError("seconds must be in (0, 120]")
        if not self._profile_lock.acquire(blocking=False):
            raise BlockingIOError("a profile capture is already running")
        try:
            out_dir = (Path(self.trace_dir) / c.PROFILE_DIR_NAME
                       / f"serve_{int(time.time())}_{seconds:g}s")
            with trace(out_dir):
                time.sleep(seconds)
            files = sorted(str(p.relative_to(out_dir))
                           for p in out_dir.rglob("*") if p.is_file())
            return {"dir": str(out_dir), "seconds": seconds,
                    "files": files}
        finally:
            self._profile_lock.release()


def make_handler(app: ServeApp, codec=None):
    """The serve HTTP surface. ``codec`` is the ``api.openai.TokenCodec``
    the /v1 endpoints use for text<->token mapping (default: "ids" —
    text is space-separated decimal token ids; serve --text-codec)."""
    from ..api.openai import TokenCodec

    if codec is None:
        codec = TokenCodec("ids")

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, *a):      # quiet; the loop is the log story
            pass

        def _send(self, code: int, obj: dict, headers: dict | None = None):
            body = json.dumps(obj).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            for k, v in (headers or {}).items():
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(body)

        def _trace_ctx(self):
            """This hop's distributed-trace context: adopt the inbound
            X-Tony-Trace header (a router stamped it), else mint a root
            — serve is a front door too (docs/observability.md
            'Distributed tracing')."""
            from ..observability import TRACE_HEADER, TraceContext

            ctx = TraceContext.from_header(self.headers.get(TRACE_HEADER))
            return ctx if ctx is not None else TraceContext.mint()

        def _client_gone(self) -> bool:
            """True when the client hung up while we wait on its
            completion — a peeked EOF on the connection. A client with
            pipelined bytes still pending reads as alive. Known
            limitation (shared with asgi-style disconnect detection): a
            client that half-closes its send side after the request
            (shutdown(SHUT_WR)) delivers the same EOF and is treated as
            gone — don't half-close if you want the response."""
            try:
                r, _, _ = select.select([self.connection], [], [], 0)
                if not r:
                    return False
                return self.connection.recv(1, socket.MSG_PEEK) == b""
            except OSError:
                return True

        def do_GET(self):
            if self.path == "/healthz":
                payload = app.health()
                self._send(200 if payload["healthy"] else 503, payload)
            elif self.path == "/stats":
                self._send(200, app.stats())
            elif self.path == "/metrics":
                from ..observability import PROM_CONTENT_TYPE

                body = app.prometheus_metrics().encode()
                self.send_response(200)
                self.send_header("Content-Type", PROM_CONTENT_TYPE)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            elif self.path.partition("?")[0] == "/progress":
                # failover-resume support: a router polls its routed
                # requests' emitted prefixes (?keys=a,b or ?key=a) so a
                # replica death mid-request resumes elsewhere from the
                # last known prefix instead of from scratch
                from urllib.parse import parse_qs, urlparse

                qs = parse_qs(urlparse(self.path).query)
                keys = []
                for k in qs.get("key", []):
                    keys.append(k)
                for ks in qs.get("keys", []):
                    keys.extend(x for x in ks.split(",") if x)
                self._send(200, app.progress(keys))
            elif self.path.partition("?")[0] == "/debug/profile":
                # on-demand device profiling: blocks THIS handler thread
                # for the capture window while the serving loop keeps
                # dispatching; the dump lands under --trace-dir and the
                # portal lists it on /profiles/<app_id>
                from urllib.parse import parse_qs, urlparse

                qs = parse_qs(urlparse(self.path).query)
                try:
                    seconds = float(qs.get("seconds", ["2"])[0])
                    result = app.capture_profile(seconds)
                except ValueError as e:
                    self._send(400, {"error": str(e)})
                    return
                except BlockingIOError as e:
                    self._send(409, {"error": str(e)})
                    return
                except RuntimeError as e:       # no --trace-dir
                    self._send(409, {"error": str(e)})
                    return
                except Exception as e:          # profiler/backend failure
                    self._send(500, {"error": f"capture failed: {e}"})
                    return
                self._send(200, result)
            else:
                self._send(404, {"error": "unknown path"})

        # ------------------------------------------------------- streaming

        def _read_json(self) -> dict:
            from ..api.stream import read_json_body

            return read_json_body(self)

        def _begin_sse(self) -> None:
            from ..api.stream import begin_sse

            begin_sse(self)

        def _relay_sse(self, rid, stream, deadline, frame_fn, final_fn,
                       error_fn, on_disconnect=None) -> None:
            """Drain one request's TokenStream into SSE frames (headers
            already sent). ``frame_fn(tokens) -> bytes`` per delta,
            ``final_fn(reason) -> bytes`` at the terminal,
            ``error_fn(message) -> bytes`` for in-band errors. A write
            failure or a peeked EOF = the client vanished: the request
            is CANCELLED (PR 3 path — the freed slot's next occupant is
            byte-identical to a fresh server), the disconnect counted,
            and ``on_disconnect`` (if given) runs — the SSE-reconnect
            path parks the emitted prefix there for a later
            ``Last-Event-ID`` resume."""
            try:
                for kind, payload in stream.events(poll_s=0.25):
                    if kind == "tokens":
                        self.wfile.write(frame_fn(payload))
                        self.wfile.flush()
                    elif kind == "done":
                        self.wfile.write(final_fn(payload))
                        self.wfile.flush()
                        break
                    elif kind == "error":
                        self.wfile.write(error_fn(payload))
                        self.wfile.flush()
                        break
                    else:                   # wait beat: our own checks
                        if time.monotonic() >= deadline:
                            app.cancel(rid)
                            self.wfile.write(error_fn(
                                f"request {rid} timed out; cancelled"))
                            self.wfile.flush()
                            break
                        if self._client_gone():
                            raise BrokenPipeError("client went away")
            except (BrokenPipeError, ConnectionResetError, OSError):
                # mid-stream disconnect: stop decoding for nobody
                app.cancel(rid)
                app.note_stream_disconnect()
                if on_disconnect is not None:
                    on_disconnect()
            finally:
                app.discard_result(rid)
            self.close_connection = True

        # -------------------------------------------------------- endpoints

        def do_POST(self):
            path = self.path.partition("?")[0]
            if path == "/generate":
                self._post_generate()
            elif path == "/v1/completions":
                self._post_openai(chat=False)
            elif path == "/v1/chat/completions":
                self._post_openai(chat=True)
            elif path == "/autoscale/hint":
                self._post_autoscale_hint()
            elif path == "/kv/import":
                self._post_kv_import()
            else:
                self._send(404, {"error": "unknown path"})

        def _post_autoscale_hint(self):
            """Driver-pushed backpressure: the fleet autoscaler's
            remaining scale-up cooldown, folded into every 429's
            Retry-After from here on (ServeApp.set_autoscale_hint).
            The hint decays on its own — a driver that dies after one
            push cannot pin the advertised retry window forever."""
            try:
                payload = self._read_json()
                cd = float(payload.get("cooldown_s", 0.0))
                if not 0 <= cd < float("inf"):
                    raise ValueError(
                        "cooldown_s must be a finite number >= 0")
            except (KeyError, ValueError, TypeError) as e:
                self._send(400, {"error": str(e)})
                return
            app.set_autoscale_hint(cd)
            self._send(200, {"ok": True, "cooldown_s": cd})

        def _post_kv_import(self):
            """The KV-transfer decode leg (docs/serving.md
            'Disaggregated serving'): the body is a prefill replica's
            exported handoff payload VERBATIM — its keys are the pinned
            transfer contract (models/serving.py KV_IMPORT_KEYS), so
            stream/timeout ride the QUERY string, never the body. The
            request then behaves exactly like /generate: buffered waits
            for the completion, ``?stream=true`` delivers per-token SSE
            frames from the resumed decode. A damaged payload is a LOUD
            400 (the router falls back to journal replay: re-prefill
            from the prompt); pool/slot pressure is the usual 429 +
            Retry-After."""
            from urllib.parse import parse_qs, urlparse

            from ..models.serving import QueueFullError

            qs = parse_qs(urlparse(self.path).query)
            try:
                timeout = float((qs.get("timeout_s") or ["600"])[0])
                if not 0 < timeout < float("inf"):
                    raise ValueError(
                        "timeout_s must be a positive finite number")
                stream_on = (qs.get("stream") or ["false"])[0].lower() \
                    in ("1", "true", "yes")
                payload = self._read_json()
                ts = None
                if stream_on:
                    from ..api.stream import TokenStream

                    ts = TokenStream()
                ctx = self._trace_ctx()
                rid, ev = app.import_async(payload, timeout=timeout,
                                           stream=ts, trace=ctx)
            except QueueFullError as e:
                ra = getattr(e, "retry_after_s", 0)
                self._send(429, {"error": str(e)}, headers={
                    "Retry-After": str(app.retry_after_s(
                        engine_estimate=ra or None))})
                return
            except ServingLoopError as e:
                self._send(503, {"error": str(e)})
                return
            except UnknownModelError as e:
                self._send(400, {"error": str(e)})
                return
            except (KeyError, ValueError, TypeError) as e:
                self._send(400, {"error": str(e)})
                return
            if ts is not None:
                from ..api.stream import sse_frame

                seen = {"n": 0}

                def frame(toks):
                    toks = [int(t) for t in toks]
                    seen["n"] += len(toks)
                    return sse_frame({"tokens": toks},
                                     event_id=f"{rid}:{seen['n']}")

                def final(reason):
                    return sse_frame(
                        {"id": rid, "finish_reason": reason,
                         "n_tokens": seen["n"],
                         "trace_id": ctx.trace_id},
                        event_id=f"{rid}:{seen['n']}")

                def err(msg):
                    return sse_frame({"error": str(msg)})

                self._begin_sse()
                self._relay_sse(rid, ts, time.monotonic() + timeout,
                                frame, final, err)
                return
            deadline = time.monotonic() + timeout
            while not ev.wait(0.25):
                if time.monotonic() >= deadline:
                    app.cancel(rid)
                    self._send(504, {"error": f"request {rid} timed "
                                     f"out after {timeout}s; cancelled"})
                    return
                if self._client_gone():
                    app.cancel(rid)
                    self.close_connection = True
                    return
            try:
                comp = app.take_result(rid)
            except ServingLoopError as e:
                self._send(503, {"error": str(e)})
                return
            except TimeoutError as e:
                self._send(504, {"error": str(e)})
                return
            from ..observability import TRACE_ID_RESPONSE_HEADER

            body = {"id": comp.id, "tokens": comp.tokens,
                    "finish_reason": comp.finish_reason}
            self._send(200, body, headers={
                TRACE_ID_RESPONSE_HEADER: ctx.trace_id})

        def _post_generate(self):
            from ..models.serving import QueueFullError

            try:
                payload = self._read_json()
                prompt = payload["prompt"]
                max_new = int(payload.get("max_new_tokens", 64))
                temp = payload.get("temperature")
                top_k = payload.get("top_k")
                cache_prompt = payload.get("cache_prompt")
                if cache_prompt is not None and not isinstance(
                        cache_prompt, bool):
                    # bool("false") is True — coercion would invert a
                    # string opt-out into caching the prompt
                    raise ValueError(
                        "cache_prompt must be a JSON boolean")
                timeout = float(payload.get("timeout_s", 600.0))
                # NaN/Infinity pass float() and json.loads: a NaN
                # deadline compares False forever, silently disabling
                # both the 504 path and the queue-expiry sweep (NaN
                # fails the chained comparison too)
                if not 0 < timeout < float("inf"):
                    raise ValueError(
                        "timeout_s must be a positive finite number")
                resume = payload.get("resume_tokens")
                if resume is not None:
                    if not isinstance(resume, list):
                        raise ValueError(
                            "resume_tokens must be a JSON list of ints")
                    resume = [int(t) for t in resume]
                progress_key = payload.get("progress_key")
                if progress_key is not None and not isinstance(
                        progress_key, str):
                    raise ValueError("progress_key must be a string")
                model = payload.get("model")
                if model is not None and not isinstance(model, str):
                    raise ValueError("model must be a string")
                # per-request stop sequences (docs/serving.md "Stop
                # sequences & logprobs"): a flat int list is ONE
                # sequence, a list of lists several; deep validation
                # (non-empty, ints) is the engine's _normalize_stop
                stop = payload.get("stop")
                if stop is not None and not isinstance(stop, list):
                    raise ValueError(
                        "stop must be a list of token ids or a list "
                        "of token-id lists")
                logprobs = payload.get("logprobs", 0)
                if logprobs is None:
                    logprobs = 0
                if isinstance(logprobs, bool) or not isinstance(
                        logprobs, int):
                    raise ValueError("logprobs must be an integer")
                # admission tier (docs/serving.md "Paged KV & admission
                # tiers"): batch requests queue under a lower threshold
                # and are displaced first under pressure
                priority = payload.get("priority") or "interactive"
                if priority not in ("interactive", "batch"):
                    raise ValueError(
                        "priority must be 'interactive' or 'batch'")
                # per-token streaming: ?stream=true or "stream": true
                from ..api.stream import stream_requested

                stream_on = stream_requested(payload, self.path)
                if stream_on and logprobs:
                    raise ValueError(
                        "logprobs are unavailable on streamed "
                        "requests (buffered responses only)")
                ts = None
                skip = 0
                if stream_on:
                    from ..api.stream import (TokenStream,
                                              parse_last_event_id)

                    # SSE reconnect (docs/serving.md "SSE reconnect"):
                    # a client re-POSTing with the last frame's id
                    # resumes from the parked prefix — the emitted
                    # tokens are teacher-forced, and only those past
                    # the acked position are re-delivered
                    lei = parse_last_event_id(
                        self.headers.get("Last-Event-ID"))
                    if lei is not None:
                        prev = app.resume_prefix(lei[0])
                        if prev is not None:
                            resume = prev
                            skip = min(lei[1], len(prev))
                    ts = TokenStream()
                ctx = self._trace_ctx()
                rid, ev = app.submit_async(
                    prompt, max_new, timeout=timeout,
                    temperature=None if temp is None else float(temp),
                    top_k=None if top_k is None else int(top_k),
                    cache_prompt=cache_prompt,
                    resume_tokens=resume, progress_key=progress_key,
                    model=model, stream=ts, stop=stop,
                    logprobs=logprobs, priority=priority, trace=ctx)
            except QueueFullError as e:
                # shed: the queue is full. 429 + Retry-After is the
                # load-balancer contract — retry elsewhere/later instead
                # of queueing into a deadline miss. The header is the
                # engine's service-rate estimate of seconds until a queue
                # seat frees (EWMA over served requests, clamped [1, 60]),
                # not a constant — a saturated queue advertises a longer
                # retry than a momentarily full one. The engine attaches
                # the estimate to the error (computed under the lock the
                # submit already held); the app folds the autoscaler's
                # cooldown hint in either way.
                ra = getattr(e, "retry_after_s", 0)
                self._send(429, {"error": str(e)}, headers={
                    "Retry-After": str(app.retry_after_s(
                        engine_estimate=ra or None))})
                return
            except ServingLoopError as e:
                self._send(503, {"error": str(e)})
                return
            except (KeyError, ValueError, TypeError) as e:
                self._send(400, {"error": str(e)})
                return
            if ts is not None:
                # SSE per-token delivery. Native frame contract
                # (docs/serving.md "Streaming & OpenAI compatibility"):
                # {"tokens": [...]} deltas, then one closing
                # {"id", "finish_reason", "n_tokens"} frame. Every
                # frame carries an ``id: <rid>:<abs>`` line — the
                # reconnect cursor — and on a resumed stream the first
                # ``skip`` already-acked tokens are withheld.
                from ..api.stream import sse_frame

                seen = {"n": 0}
                got: list = []

                def frame(toks):
                    toks = [int(t) for t in toks]
                    got.extend(toks)
                    start = max(0, skip - seen["n"])
                    seen["n"] += len(toks)
                    new = toks[start:]
                    if not new:
                        return b""
                    return sse_frame({"tokens": new},
                                     event_id=f"{rid}:{seen['n']}")

                def final(reason):
                    return sse_frame(
                        {"id": rid, "finish_reason": reason,
                         "n_tokens": max(0, seen["n"] - skip),
                         "trace_id": ctx.trace_id},
                        event_id=f"{rid}:{seen['n']}")

                def err(msg):
                    return sse_frame({"error": str(msg)})

                self._begin_sse()
                self._relay_sse(
                    rid, ts, time.monotonic() + timeout, frame, final,
                    err,
                    on_disconnect=lambda: app.save_resume_prefix(
                        rid, got))
                return
            # wait in short beats so a vanished client is noticed and its
            # request CANCELLED — the slot goes back to live traffic
            # instead of decoding to completion for nobody
            deadline = time.monotonic() + timeout
            while not ev.wait(0.25):
                if time.monotonic() >= deadline:
                    app.cancel(rid)
                    self._send(504, {"error": f"request {rid} timed out "
                                     f"after {timeout}s; cancelled"})
                    return
                if self._client_gone():
                    app.cancel(rid)     # abandonment: nobody to answer
                    self.close_connection = True
                    return
            try:
                comp = app.take_result(rid)
            except ServingLoopError as e:
                self._send(503, {"error": str(e)})
                return
            except TimeoutError as e:
                self._send(504, {"error": str(e)})
                return
            if comp.finish_reason == "shed":
                # displaced from the batch queue by an interactive
                # arrival (admission tiers): same contract as an
                # admission-time shed — 429 + honest Retry-After
                self._send(429, {"error": f"request {comp.id} shed by "
                                 "admission tiers; retry later"},
                           headers={"Retry-After":
                                    str(app.retry_after_s())})
                return
            from ..observability import TRACE_ID_RESPONSE_HEADER

            body = {"id": comp.id, "tokens": comp.tokens,
                    "finish_reason": comp.finish_reason}
            if comp.logprobs is not None:
                body["logprobs"] = comp.logprobs
            if comp.finish_reason == "prefilled":
                # prefill-role handoff: the KV transfer payload rides
                # the SAME response the router already waits on — no
                # extra round trip. An aged-out stash just omits it;
                # the router re-prefills via the replay fallback.
                try:
                    body["handoff"] = app.export_payload(comp.id)
                except KeyError:
                    pass
            self._send(200, body, headers={
                TRACE_ID_RESPONSE_HEADER: ctx.trace_id})

        def _oai_error(self, code: int, message: str, etype: str) -> None:
            self._send(code, {"error": {"message": message,
                                        "type": etype}})

        def _post_openai(self, chat: bool):
            """OpenAI-compatible front door: ``/v1/completions`` and
            ``/v1/chat/completions``, streaming and non-streaming. The
            payload mapping (accepted params, response keys,
            finish_reason mapping) is pinned in ``api.openai`` and
            docs/serving.md, both directions, by the api-contract lint."""
            from ..api import openai as oai
            from ..models.serving import QueueFullError

            try:
                payload = self._read_json()
                req = (oai.parse_chat_request(payload, codec) if chat
                       else oai.parse_completion_request(payload, codec))
            except (KeyError, ValueError, TypeError) as e:
                self._oai_error(400, str(e), "invalid_request_error")
                return
            model_name = req["model"] or app.default_model
            ts = None
            skip = 0
            resume = None
            if req["stream"]:
                from ..api.stream import TokenStream, parse_last_event_id

                # SSE reconnect: same contract as /generate — the /v1
                # frames' ``id:`` lines carry the engine rid + absolute
                # delivered-token cursor the client echoes back here
                lei = parse_last_event_id(
                    self.headers.get("Last-Event-ID"))
                if lei is not None:
                    prev = app.resume_prefix(lei[0])
                    if prev is not None:
                        resume = prev
                        skip = min(lei[1], len(prev))
                ts = TokenStream()
            ctx = self._trace_ctx()
            try:
                rid, ev = app.submit_async(
                    req["prompt_tokens"], req["max_new_tokens"],
                    timeout=req["timeout_s"],
                    temperature=req.get("temperature"),
                    top_k=req.get("top_k"),
                    resume_tokens=resume,
                    model=req["model"], stream=ts,
                    stop=req.get("stop_sequences"),
                    logprobs=req.get("logprobs", 0),
                    priority=req.get("priority") or "interactive",
                    trace=ctx)
            except QueueFullError as e:
                ra = getattr(e, "retry_after_s", 0)
                self._send(429, {"error": {"message": str(e),
                                           "type": "rate_limit_error"}},
                           headers={"Retry-After": str(
                               app.retry_after_s(
                                   engine_estimate=ra or None))})
                return
            except ServingLoopError as e:
                self._oai_error(503, str(e), "service_unavailable")
                return
            except UnknownModelError as e:
                self._oai_error(400, str(e), "invalid_request_error")
                return
            except (KeyError, ValueError, TypeError) as e:
                self._oai_error(400, str(e), "invalid_request_error")
                return
            n_prompt = len(req["prompt_tokens"])
            if ts is not None:
                got: list = []
                frame, final, err = oai.stream_frame_fns(
                    rid, model_name, codec, chat, skip=skip,
                    collect=got, trace_id=ctx.trace_id)
                self._begin_sse()
                self._relay_sse(
                    rid, ts, time.monotonic() + req["timeout_s"],
                    frame, final, err,
                    on_disconnect=lambda: app.save_resume_prefix(
                        rid, got))
                return
            deadline = time.monotonic() + req["timeout_s"]
            while not ev.wait(0.25):
                if time.monotonic() >= deadline:
                    app.cancel(rid)
                    self._oai_error(
                        504, f"request {rid} timed out after "
                             f"{req['timeout_s']}s; cancelled", "timeout")
                    return
                if self._client_gone():
                    app.cancel(rid)
                    self.close_connection = True
                    return
            try:
                comp = app.take_result(rid)
            except ServingLoopError as e:
                self._oai_error(503, str(e), "service_unavailable")
                return
            except TimeoutError as e:
                self._oai_error(504, str(e), "timeout")
                return
            if comp.finish_reason == "shed":
                self._send(429, {"error": {
                    "message": f"request {comp.id} shed by admission "
                               "tiers; retry later",
                    "type": "rate_limit_error"}},
                    headers={"Retry-After": str(app.retry_after_s())})
                return
            from ..observability import TRACE_ID_RESPONSE_HEADER

            build = oai.chat_response if chat else oai.completion_response
            self._send(200, build(comp.id, model_name, comp.tokens,
                                  comp.finish_reason, n_prompt, codec,
                                  logprobs=comp.logprobs),
                       headers={TRACE_ID_RESPONSE_HEADER: ctx.trace_id})

    return Handler


def main(argv=None) -> int:
    # conf-templated flags (runtimes/serving.py exports them from the
    # tony.serving.* keys): PREPENDED so explicit flags override them
    import os as _os
    import sys as _sys

    from .. import constants as _c

    extra = _os.environ.get(_c.ENV_SERVE_EXTRA_FLAGS, "").split()
    if argv is None:
        argv = _sys.argv[1:]
    args = build_argparser().parse_args(extra + list(argv))

    from ..utils.jaxenv import place_compile_cache

    place_compile_cache()
    from ..models.registry import ModelRegistry
    from ..models.serving import SlotServer

    # ---- model registry: every served model is a named entry ----
    registry = ModelRegistry()
    if args.model:
        if args.hf_checkpoint or args.checkpoint_dir:
            raise SystemExit(
                "--model and the classic --hf-checkpoint/"
                "--checkpoint-dir flags are exclusive: with --model, "
                "the classic flags would be silently ignored — name "
                "the checkpoint as a --model entry instead")
        for item in args.model:
            name, sep, spec = item.partition("=")
            if not sep or not name:
                raise SystemExit(
                    f"--model expects NAME=SPEC, got {item!r}")
            registry.register(name, *load_named_model(spec, args),
                              source=spec)
    else:
        # the registry holds the ONLY reference to the weights: a local
        # name here would keep the unsharded masters alive on the first
        # device after --mesh replaces the entry (6 GB of a Llama-3.2-1B
        # sat there beside its tensor=4 share on the v5e)
        registry.register(
            "default", *load_model(args),
            source=args.hf_checkpoint or args.checkpoint_dir or "random")
    default_name = registry.default.name
    draft_name = None
    if args.draft_model:
        if args.draft_model in registry:
            draft_name = args.draft_model
        else:
            if "draft" in registry:
                raise SystemExit(
                    "--draft-model SPEC registers under the reserved "
                    "name 'draft', which --model already claimed — "
                    "either reference that entry by name "
                    "(--draft-model draft) or rename it")
            dp, dc = load_named_model(
                args.draft_model, args,
                dims=dict(d_model=args.draft_d_model,
                          n_layers=args.draft_n_layers,
                          n_heads=args.draft_n_heads,
                          d_ff=args.draft_d_ff))
            registry.register("draft", dp, dc, source=args.draft_model)
            draft_name = "draft"
        if draft_name == default_name:
            raise SystemExit(
                f"--draft-model {args.draft_model!r} names the default "
                "serving model itself — a model cannot be its own "
                "draft (register the draft as a separate --model entry "
                "or give a SPEC)")
        # the default model speculates with this draft; the SlotServer
        # resolves the pairing straight off the registry entry
        registry.get(default_name).draft = draft_name
    serving_names = [n for n in registry.names() if n != draft_name]

    if args.mesh:
        if len(serving_names) > 1 or draft_name:
            raise SystemExit(
                "--mesh serves a single model without a draft "
                "(tensor-parallel speculative/multi-model serving is "
                "not wired)")
        from ..models.generate import prepare_decode

        mesh = build_serving_mesh(args.mesh)
        # prepare ONCE onto the mesh and drop the unsharded masters: the
        # server then holds a single sharded copy of the model
        entry = registry.get(default_name)
        registry.register(
            default_name,
            prepare_decode(entry.weights, entry.cfg,
                           weight_dtype=args.weight_dtype, mesh=mesh),
            entry.cfg, source=entry.source)
        del entry
    # request durability: file-backed journal under --trace-dir (a
    # SIGKILLed process's unfinished requests are recovered below and
    # FINISHED by this one); in-memory otherwise (loop-crash replay
    # only). --no-replay restores the fail-fast contract end to end.
    # ONE journal serves every engine (ids are process-global); entries
    # carry the model name so recovery resubmits to the right engine.
    journal = None
    recovered_entries = []
    if not args.no_replay and args.trace_dir:
        from pathlib import Path as _Path

        from ..events.journal import JOURNAL_FILE, RequestJournal

        journal, recovered_entries = RequestJournal.recover(
            _Path(args.trace_dir) / JOURNAL_FILE)
        print(f"request journal -> {journal.path}", flush=True)
    class_budgets = {}
    if args.class_budget_interactive:
        class_budgets["interactive"] = args.class_budget_interactive
    if args.class_budget_batch:
        class_budgets["batch"] = args.class_budget_batch
    engines = {}
    for n in serving_names:
        engines[n] = SlotServer(
            registry=registry, model=n,
            slots=args.slots, max_len=args.max_len,
            block_size=args.block_size, prefill_chunk=args.prefill_chunk,
            kv_dtype=args.kv_dtype, weight_dtype=args.weight_dtype,
            temperature=args.temperature, top_k=args.top_k,
            stop_tokens=tuple(int(t) for t in args.stop_tokens.split()),
            pad_id=args.pad_id, seed=args.seed,
            prefix_cache_blocks=args.prefix_cache_blocks,
            cache_prompts=not args.no_cache_prompts,
            max_queue=args.max_queue,
            journal=journal, replay=not args.no_replay,
            spec_gamma=args.spec_gamma,
            spec_gamma_max=args.spec_gamma_max,
            paged=args.paged_kv, kv_block=args.kv_block,
            kv_pool_blocks=args.kv_pool_blocks,
            prefill_interleave=args.prefill_interleave,
            class_budgets=class_budgets or None,
            batch_queue_frac=args.batch_queue_frac,
            role=args.role)
    slot_server = engines[default_name]
    if recovered_entries:
        # pre-multi-model records carry no model name and belong to the
        # default engine; entries naming a model this relaunch no longer
        # registers are dropped LOUDLY (no engine could serve them).
        # compact=False: the engines share ONE journal file, and
        # compacting after the first engine's resubmission would erase
        # the only durable copy of the later engines' entries — a crash
        # in that window would silently lose them. One compaction after
        # EVERY engine has journaled its resubmissions keeps the
        # double-replay-never-lose contract (it also finally drops the
        # orphaned-model records, which no future launch could serve).
        for n, eng in engines.items():
            mine = [e for e in recovered_entries
                    if (e.model or default_name) == n]
            if mine:
                cnt = eng.recover_journal(mine, compact=False)
                print(f"journal recovery: resumed {cnt} unfinished "
                      f"request(s) for model {n!r} from the previous "
                      "process", flush=True)
        orphans = [e for e in recovered_entries
                   if (e.model or default_name) not in engines]
        if orphans:
            print(f"journal recovery: dropped {len(orphans)} entr(y/ies) "
                  f"naming models this process no longer serves "
                  f"({sorted({e.model for e in orphans})})", flush=True)
        if journal is not None:
            journal.compact()
    trace_writer = None
    telemetry_state_path = None
    if args.trace_dir:
        from pathlib import Path

        from ..events.trace import TraceWriter

        trace_writer = TraceWriter(args.trace_dir)
        for eng in engines.values():
            eng.trace_sink = trace_writer.write
        print(f"request traces -> {trace_writer.path}", flush=True)
        # histogram persistence across serve restarts: a re-armed server
        # resumes the cumulative /metrics buckets instead of zeroing
        # them (docs/observability.md "Histogram persistence").
        # SlotServer.reset() already keeps its telemetry; this covers
        # PROCESS-level restarts pointing at the same trace dir.
        telemetry_state_path = Path(args.trace_dir) / TELEMETRY_STATE_FILE
        if telemetry_state_path.exists():
            try:
                slot_server.telemetry.restore(
                    json.loads(telemetry_state_path.read_text()))
                print(f"telemetry restored from {telemetry_state_path}",
                      flush=True)
            except (ValueError, KeyError, TypeError, AttributeError,
                    OSError) as e:
                # a stale/incompatible dump must not block startup —
                # including valid JSON of the wrong shape
                print(f"telemetry state not restored: {e}", flush=True)
    app = ServeApp(engines, max_loop_restarts=args.loop_max_restarts,
                   loop_backoff_s=args.loop_backoff_s,
                   trace_dir=args.trace_dir,
                   journal_checkpoint_s=(0.0 if args.no_replay
                                         else args.journal_checkpoint_s))
    app.start()
    from ..api.openai import TokenCodec

    codec = TokenCodec(args.text_codec, vocab_size=args.vocab)
    httpd = ThreadingHTTPServer((args.host, args.port),
                                make_handler(app, codec))

    # graceful drain on SIGTERM/SIGINT: a supervisor's TERM must finish
    # in-flight requests instead of killing them mid-decode. A foreground
    # ^C reaches the same path; a SECOND signal force-exits. The drain
    # runs on a helper thread — httpd.shutdown() deadlocks if called from
    # the serve_forever thread, and signal handlers must return fast.
    # Handlers install BEFORE the readiness print: a supervisor that
    # TERMs the instant it sees the serving line must hit the drain
    # path, not the default-action kill (the old order lost that race).
    import os as _os
    import signal as _signal

    draining = threading.Event()

    def _drain_and_stop():
        app.shutdown(drain=True, drain_timeout_s=args.drain_timeout_s)
        httpd.shutdown()

    def _on_signal(signum, frame):
        if draining.is_set():
            print("second signal: exiting immediately", flush=True)
            _os._exit(128 + signum)
        draining.set()
        print(f"signal {signum}: draining (finishing in-flight requests, "
              f"up to {args.drain_timeout_s}s)", flush=True)
        threading.Thread(target=_drain_and_stop, daemon=True).start()

    _signal.signal(_signal.SIGTERM, _on_signal)
    _signal.signal(_signal.SIGINT, _on_signal)
    model_descs = ", ".join(
        f"{n}={registry.get(n).cfg.n_layers}L"
        f"d{registry.get(n).cfg.d_model}" for n in serving_names)
    spec_desc = (f" +draft {draft_name}" if draft_name else "")
    print(f"serving {model_descs}{spec_desc} on "
          f"http://{args.host}:{httpd.server_address[1]} "
          f"({args.slots} slots x {args.max_len} tokens)", flush=True)
    try:
        httpd.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        app.shutdown()      # no-op after a completed drain
        httpd.server_close()
        if telemetry_state_path is not None:
            try:
                # tmp+rename: a crash mid-write must leave the previous
                # dump intact, not a truncated one
                tmp = telemetry_state_path.with_suffix(".json.tmp")
                tmp.write_text(json.dumps(slot_server.telemetry.state()))
                tmp.rename(telemetry_state_path)
            except OSError as e:
                print(f"telemetry state not persisted: {e}", flush=True)
        if trace_writer is not None:
            trace_writer.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
