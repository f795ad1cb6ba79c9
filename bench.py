"""Benchmark: orchestrated mnist training throughput vs plain jax-on-TPU.

BASELINE.md metric: "mnist steps/sec/chip submitted via the ClusterSubmitter
-equivalent, target >= 90% of plain jax-on-TPU step throughput"
(BASELINE.json north star). This script measures

  1. plain JAX: the mnist train loop of tony_tpu/examples/mnist_jax.py run
     directly as a subprocess on the local accelerator(s)
  2. orchestrated: the SAME script submitted as a 1-worker job through
     TonyClient -> driver -> executor (the ClusterSubmitter path)

and reports orchestrated steps/sec with vs_baseline = orchestrated / plain.
Orchestration happens off the training path (heartbeats + metrics RPC only),
so the ratio should be ~1.0.

Noise control (the round-4 regression forensics, docs/performance.md):
  - The workload reports a TWO-POINT device rate: scan blocks of N and N/2
    steps, interleaved; the step delta over the median-time delta cancels the
    fixed per-call cost. In the round-4 records that fixed cost was ~90%
    of a 1000-step call's wall time, so the old wall-rate ratio compared
    dispatch jitter, not training speed — the whole r04 "5pp regression"
    lived in that jitter. The wall-rate ratio is still recorded.
  - A/B pairs run adjacent in time and the MEDIAN of paired ratios is
    scored; one stalled (or lucky) pair cannot move the gate.
  - Pair ORDER alternates (pair 0 orchestrated-first for the cold-launch
    breakdown, then flipping): any systematic within-pair drift — link
    warming, page cache — hits each arm first equally often instead of
    always favoring the second runner.
  - Host telemetry per arm: loadavg + /proc/stat busy fraction, persisted so
    a deficit can be attributed to host contention instead of guessed at.

BASELINE.md metric 2 (launch-to-first-step) is reported as a breakdown:
orchestration (submit -> user-process exec) vs in-process phases (import,
backend init + data staging, first-block compile), once cold and
once warm — a persistent XLA compilation cache shared by both arms makes
relaunches skip most of the compile phase, which is the path users iterate
on. r02's undiagnosed 28->47s drift was entirely the in-process share
(backend init ~25s + 1000-step-scan compile ~20-29s, both variable);
orchestration's share is ~1s.

Prints exactly ONE JSON line on stdout:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...breakdown}

`python bench.py --serving` instead benchmarks the continuous-batching
SlotServer (models/serving.py): tokens/sec with batched multi-slot
admission vs the serial per-slot path (same completions, fewer host
dispatches per admission burst — both counts reported), and, when >= 2
devices are visible, the mesh-sharded (tensor-parallel) server with a
parity check against the single-device completions. On CPU run it under
`XLA_FLAGS=--xla_force_host_platform_device_count=4`. Results land in
PERF.json under `continuous_batching_tp`, and the timed pass's
p50/p90/p99 TTFT/TPOT/queue-wait/e2e (from the observability
histograms, docs/observability.md) under `serving_latency` — the
latency baseline future perf PRs regress against — plus a `device_time`
section (dispatch→ready quantiles per program kind from the
DispatchTracker, measured device lag behind host observation, and the
XLA compile count/time for the whole bench process). An `open_loop`
arm rides along: the same workload offered as seeded Poisson arrivals
at the measured burst capacity (byte-identity vs the burst asserted;
the latency block there is the open-loop shape, not the burst's
deep-backlog artifact).

`python bench.py --serving --shared-prefix` benchmarks the chunk-aligned
prefix KV cache on the workload it exists for: N requests sharing one
long template + short unique suffixes (the system-prompt/few-shot shape).
A cold server (prefix cache off) and a warm one (`prefix_cache_blocks`)
serve the identical submission order; the bench asserts byte-identical
completions and reports the reused-token fraction, prefill/copy/insert
dispatch counts, and tokens/sec for both paths. Results land in PERF.json
under `prefix_cache`.

`python bench.py --serving --fleet` benchmarks driver-orchestrated
fleet serving (docs/serving.md "Fleet serving"): 2-3 real serve
processes (one pinned per core, prefix caches on) behind the
prefix-aware FleetRouter — fleet-vs-single CAPACITY (closed-loop,
concurrency-matched, best-of-trials; asserted > 1.5x), open-loop
CAPACITY arms (Poisson arrivals at each arm's own measured capacity,
best-of-trials; asserted > 1.3x), Poisson open-loop collapse passes
at 1.2x measured fleet capacity, and prefix-affinity
vs random routing on the fleet-wide trie reuse fraction (asserted
affinity > random) and merged p99 TTFT. Results land in PERF.json
under `serving_fleet`.

`python bench.py --serving --paged-kv` gates the paged KV allocator
(docs/serving.md "Paged KV & admission tiers") on TINY shapes: (1)
byte-identical greedy completions vs the ring engine with peak
concurrency strictly above the ring's `slots` bound at EQUAL device
memory (same pool bytes, more slots, admission gated on free blocks);
(2) an admission storm of long prompts against in-flight decodes,
chaos-paced (20ms/turn) so the comparison is deterministic — TPOT p99
with chunked-prefill interleaving ON must stay ≤ 1.2x the quiescent
baseline while the interleave-OFF arm's single-turn stall is reported
(and must exceed the interleaved arm's); (3) admission tiers under
queue pressure — queued batch requests shed (finish_reason "shed")
before any interactive arrival is refused, zero failed requests, and
the 429s carry engine-derived Retry-After. Results land in PERF.json
under `paged_kv`.

`python bench.py --serving --disagg` gates disaggregated prefill/
decode serving (docs/serving.md "Disaggregated serving"): (1) a mixed
workload (long-prompt prefill storm dropped on in-flight interactive
decodes) on 1 prefill specialist + 1 decode replica vs 2 role="both"
replicas at EQUAL hardware — the decode tier's TPOT p99 must be ≥
1.2x better because prefill chunks never ride its scheduling turns —
with byte-identity vs solo greedy and zero failed requests enforced;
(2) a fleet leg with a mid-transfer SIGKILL of the prefill specialist:
completed handoffs before the kill, journal-replay fallback after it
(the router re-prefills from the prompt on the decode replica), zero
failed requests, byte-identical. Results land in PERF.json under
`disaggregated_serving`.

`python bench.py --serving --streaming` gates the streaming subsystem
(docs/serving.md "Streaming & OpenAI compatibility"): an open-loop
Poisson arrival process streamed per-token through the FleetRouter
against 2 TINY serve processes with a mid-stream replica SIGKILL —
ENFORCES zero failed requests and per-request byte-identity of the
concatenated client-side stream vs non-streamed greedy (stream
failovers included, resume prefix harvested from the stream), and
reports client-observed inter-token-latency quantiles from per-token
arrival timestamps. Results land in PERF.json under
`streaming_serving`.

`python bench.py --launch-path` measures the warm-executor-pool launch
story (docs/performance.md "Launch path"): the same 1-worker mnist job
submitted three ways in one run — cold (first-ever: cold XLA disk
cache, cold child), warm (resubmit, pool off), adopted (resubmit,
`tony.warmpool.size=1`: the task adopts a pre-warmed standby that
prepaid jax import + backend init + the warmup hook's staging and
train-block compile). Asserts the adopted arm adopted, the others did
not, and training results are identical across arms; results land in
PERF.json under `launch_path` with value = cold/adopted speedup (the
>=3x acceptance gate).

`python bench.py --elastic` exercises the TRAINING failure model
(docs/training-robustness.md): a real 2-worker local job running the
elastic_train drill under the driver's seeded chaos harness
(TONY_TEST_DRIVER_{KILL_RATE,PREEMPT_AT_STEP,CHAOS_SEED}) — random
container SIGKILLs plus one relayed preemption drain, with elasticity
on. The bench asserts ZERO failed jobs, ≤ save_interval steps recomputed
per recovery with no silent step skips (from the per-step StepTimer
JSONLs), and reports each loss→running recovery wall time from
tasks.trace.jsonl. Results land in PERF.json under
`training_robustness`.

`python bench.py --serving --overload --chaos` exercises the failure
model (docs/serving.md): a burst far exceeding slots + max_queue hits a
ServeApp whose SlotServer runs with seeded fault injection
(TONY_TEST_SERVING_DISPATCH_FAIL_RATE, constants.py). The bench asserts
the invariants the robustness tests pin — every submitted request
terminates with a completion, a shed (429-equivalent QueueFullError), or
an explicit error; zero hung waiters; the loop recovers within its
restart budget — and reports goodput, shed/cancelled/expired counts,
recovery counters, and the p50 latency of admitted requests. Results
land in PERF.json under `serving_robustness` (`--overload` alone runs
the same burst with injection off).

`python bench.py --serving --replay` gates the request-durability layer
(docs/serving.md "Request durability & replay"): a deterministic
mid-decode loop crash (TONY_TEST_SERVING_CRASH_AT_BLOCKS) and a replica
SIGKILL mid-burst behind the FleetRouter must both finish with ZERO
failed requests and byte-identical completions vs an uninterrupted run
(replay recompute bounded by one prompt+emitted-prefix re-prefill per
replay; the journal-off path must preserve today's fail-fast
behavior), and the SIGKILLed replica restarted against the same
--trace-dir must recover its file journal and finish the orphaned
requests. Results land in PERF.json under `serving_replay`.

`python bench.py --serving --router-ha` gates the shared-nothing router
tier (docs/serving.md "Router tier HA"): a real driver launches 2 serve
replicas behind 2 `router`-framework front doors, SIGKILLs door 0 on
its Nth request mid-burst, and ENFORCES zero failed requests (clients
re-POST the same request_id on the survivor), byte-identical buffered
AND streamed responses for every rerouted request, live cross-door
affinity agreement after the driver relaunches the dead door (restart
budget: router:0 restarts == 1, no collateral), reporting the p50
latency cost of losing a front door. Results land in PERF.json under
`router_ha`.

`python bench.py --serving --tracing` gates END-TO-END DISTRIBUTED
TRACING (docs/observability.md "Distributed tracing"): a disaggregated
fleet (1 prefill + 1 decode replica, --paged-kv) behind 2 router front
doors, every tier writing --trace-dir JSONL; door 0 is SIGKILLed upon
receiving its Nth front-door request mid-burst and the clients re-POST
the same request_id at door 1. The bench merges every tier's trace
file with TraceCollector and ENFORCES: every completed request yields
exactly ONE merged trace (the deterministic for_request_id trace_id
each response header echoed), ZERO orphan spans, >= 1 failover trace
carrying spans from BOTH router nonces under one trace_id (the dead
door contributes its unsealed write-ahead record), >= 1 trace whose
serve spans come from both the prefill and the decode replica (the
disagg handoff is one trace), and the span-union coverage accounts for
each client-observed e2e within a bounded gap. Results land in
PERF.json under `distributed_tracing`.

`python bench.py --serving --spec` gates speculative decoding inside
continuous batching (docs/serving.md "Speculative decoding &
multi-model serving"): a target and a 12x-smaller draft trained on the
same Markov corpus (real acceptance, the bench_transformer speculative
methodology) serve the identical burst spec-off and spec-on — the
bench asserts byte-identical completions and >= 1.3x tokens/s, reports
the measured acceptance + autotuned gamma + the acceptance-0 floor
(random draft), and a multi-model arm rolls a two-model serve process
mid-burst (SIGTERM drain -> relaunch with one checkpoint swapped under
the same name + journal dir) asserting zero failed requests. Results
land in PERF.json under `speculative_serving`.

`python bench.py --driver-failover` gates the CONTROL-PLANE recovery
layer (docs/training-robustness.md "Control-plane recovery") with two
arms. Training: a real 2-worker elastic_train job whose driver SIGKILLs
itself mid-job (TONY_TEST_DRIVER_SIGKILL_AT_STEP); the bench relaunches
`tony-tpu driver --recover`, which replays driver.journal.jsonl and
re-adopts both live workers — the job must SUCCEED with ZERO
outage-attributable worker restarts and ZERO recomputed steps (the
children never stopped stepping), and each worker's recovery→first
re-attached heartbeat is read off its `readopted` trace and bounded.
Fleet: a driver-orchestrated 2-replica serving fleet behind the
FleetRouter answers a paced burst while the driver is SIGKILLed and
recovered mid-burst — the router must serve the whole burst from its
last-known fleet (router_discovery_stale observed high, then clear)
with ZERO failed requests and zero replica restarts. Results land in
PERF.json under `control_plane_robustness`.

`python bench.py --autoscale` gates the CLOSED LOOP (docs/
autoscaling.md): one driver schedules a serving role (2 replica slots,
1 parked) and a batch elastic_train role over a 3-slot shared pool. A
seeded Poisson traffic ramp through the FleetRouter floods the single
replica past the queue SLO; the driver-resident autoscaler preempt-
drains the batch worker (donation, checkpoint at the step boundary),
scales the fleet up on the freed slot, and the measured client TTFT p99
recovers — no manual resize. The driver is SIGKILLed once the second
replica is live and relaunched with `--recover`: the journaled scale
ledger resumes mid-cooldown, so the final journal carries EXACTLY one
"up" and one "down" decision (no duplicates, no flapping). On
ramp-down the fleet scales back, the batch tier RECLAIMS the donated
slot (relaunched with the checkpoint prestaged), and the training job
runs to SUCCEEDED with ≤ save_interval recomputed steps per recovery
and ZERO failed serving requests. Results land in PERF.json under
`autoscaling`.

`python bench.py --serving --slo` gates the FLEET METRICS PIPELINE +
SLO ALERTING (docs/observability.md "Metrics pipeline & SLO
alerting"): one driver runs 2 replicas behind a `router`-framework
front door with a declared availability SLO; the driver-resident
metrics hub scrapes every tier. A healthy open-loop warm-up must fire
ZERO alerts; a replica SIGKILL under a Poisson overload burst sheds on
the survivor and the fast burn-rate pair must fire inside its window
(journaled); the driver is then SIGKILLed MID-INCIDENT and relaunched
with `--recover` — the replayed metrics.tsdb.jsonl + journal-seeded
alert state must RESUME the alert with exactly one firing transition
in the final journal (no duplicate); the alert clears after the
replica relaunch, and the engine's budget accounting must equal
(failed+shed)/total computed from the router's own /metrics counters
EXACTLY. Results land in PERF.json under `slo_alerting`.
"""

from __future__ import annotations

import json
import os
import shutil
import statistics
import subprocess
import sys
import tempfile
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
STEPS = 120000          # total long-block steps timed (short blocks add half)
STEPS_PER_CALL = 12000  # long block; short is half -> diff ~0.125s of device
                        # time per round vs per-call RTT jitter of a few ms;
                        # 10 rounds tighten each median to ~1-2ms (the first
                        # r05 trial at 5 rounds x 6k steps still showed +-7%
                        # pair noise, all of it from the PLAIN arm's medians)
BATCH = 512
# 5 pairs: with 3, one noisy pair put the median at the mercy of a single
# run (r03 spread was 29%); two more pairs cost ~4 min and make the median
# robust to two bad pairs
PAIRS = 5


def _workload_args(out: Path) -> list[str]:
    return [
        "--steps", str(STEPS), "--steps-per-call", str(STEPS_PER_CALL),
        "--batch-size", str(BATCH), "--metrics-out", str(out),
    ]


def _compile_cache_env(gate: str, *, cold: bool) -> dict[str, str]:
    """Environment that places a gate's children's persistent XLA cache
    (tony_tpu/utils/jaxenv.py) at a FIXED sub-directory of the checkout's
    cache — the path is part of the cache key, so one built from a temp
    name never hits. ``cold`` empties it first: the gate's first child
    then compiles, the later ones measure the warm relaunch users
    actually iterate on. The threshold is zeroed because the mnist
    programs compile in under JAX's one-second default."""
    sys.path.insert(0, str(REPO))
    from tony_tpu.utils.jaxenv import CACHE_ENV, DEFAULT_CACHE_DIR

    cache = DEFAULT_CACHE_DIR / f"bench-{gate}"
    if cold:
        shutil.rmtree(cache, ignore_errors=True)
    return {CACHE_ENV: str(cache),
            "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"}


def _cpu_busy() -> tuple[float, float]:
    """(busy_jiffies, total_jiffies) from /proc/stat line 1."""
    with open("/proc/stat") as f:
        parts = f.readline().split()[1:]
    nums = [float(p) for p in parts]
    idle = nums[3] + (nums[4] if len(nums) > 4 else 0.0)  # idle + iowait
    return sum(nums) - idle, sum(nums)


class _HostLoad:
    """Samples host contention around one arm's run."""

    def __enter__(self):
        self._busy0, self._total0 = _cpu_busy()
        self.load_start = os.getloadavg()[0]
        return self

    def __exit__(self, *exc):
        busy1, total1 = _cpu_busy()
        self.load_end = os.getloadavg()[0]
        dt = total1 - self._total0
        self.cpu_busy_frac = (busy1 - self._busy0) / dt if dt > 0 else 0.0

    def as_dict(self) -> dict:
        return {
            "loadavg_start": round(self.load_start, 2),
            "loadavg_end": round(self.load_end, 2),
            "cpu_busy_frac": round(self.cpu_busy_frac, 4),
        }


def run_plain(tmp: Path, rep: int) -> tuple[dict, dict]:
    out = tmp / f"plain{rep}.json"
    with _HostLoad() as hl:
        proc = subprocess.run(
            [sys.executable, "-m", "tony_tpu.examples.mnist_jax",
             *_workload_args(out)],
            cwd=REPO, capture_output=True, text=True, timeout=900,
            env={**os.environ, **_compile_cache_env("mnist", cold=False)},
        )
    if proc.returncode != 0:
        print(proc.stdout, proc.stderr, file=sys.stderr)
        raise RuntimeError("plain jax run failed")
    return json.loads(out.read_text()), hl.as_dict()


def run_orchestrated(tmp: Path, rep: int) -> tuple[dict, float, float, dict]:
    sys.path.insert(0, str(REPO))
    from tony_tpu.client import TonyClient
    from tony_tpu.conf import TonyConf

    out = tmp / f"orch{rep}.json"
    conf = TonyConf({
        "tony.staging.dir": str(tmp / f"staging{rep}"),
        "tony.history.intermediate": str(tmp / "hist/intermediate"),
        "tony.worker.instances": 1,
        "tony.worker.command": (
            f"{sys.executable} -m tony_tpu.examples.mnist_jax "
            + " ".join(_workload_args(out))
        ),
        "tony.execution.env": ",".join(
            f"{k}={v}" for k, v in
            _compile_cache_env("mnist", cold=False).items()),
        "tony.am.monitor-interval-ms": 100,
    })
    client = TonyClient(conf, poll_interval_s=0.1)
    with _HostLoad() as hl:
        t_submit = time.time()
        client.submit()
        status = client.monitor()
    if status.value != "SUCCEEDED":
        log_dir = Path(client.job_dir)
        for p in sorted(log_dir.rglob("*.std*")) + sorted(log_dir.rglob("*.log")):
            print(f"==== {p} ====\n{p.read_text()[-2000:]}", file=sys.stderr)
        raise RuntimeError(f"orchestrated job finished {status}")
    return json.loads(out.read_text()), time.time() - t_submit, t_submit, hl.as_dict()


def _launch_breakdown(m: dict, t_submit: float) -> dict:
    """Split launch-to-first-step into the orchestration share (submit ->
    user process exec, the part BASELINE.md metric 2 is really about) and
    the in-process phases the workload reports."""
    return {
        "orchestration_submit_to_exec_s": round(m["t_start_epoch"] - t_submit, 2),
        "import_s": round(m["import_s"], 2),
        "backend_and_data_s": round(m["backend_and_data_s"], 2),
        "compile_first_block_s": round(m["compile_first_block_s"], 2),
        "total_submit_to_first_step_s": round(
            m["t_start_epoch"] - t_submit + m["time_to_first_step_s"], 2
        ),
    }


def run_serving_bench() -> int:
    """Continuous-batching serving benchmark (in-process, one JSON line).

    One warm-up pass compiles every program variant; the timed pass then
    measures pure serving throughput: all requests submitted up front, so
    the first _admit() sees a full burst of free slots."""
    import time as _time

    sys.path.insert(0, str(REPO))
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tony_tpu.models import transformer
    from tony_tpu.models.serving import Request, SlotServer
    from tony_tpu.observability import install_compile_telemetry

    # compile-time attribution rides the same run: installed BEFORE any
    # program compiles so the warm-up pass's compiles are counted
    compile_telemetry = install_compile_telemetry()

    cfg = transformer.TransformerConfig(
        vocab_size=2048, d_model=256, n_layers=4, n_heads=8, n_kv_heads=4,
        d_ff=1024, max_seq_len=512,
        dtype=jnp.bfloat16 if jax.default_backend() == "tpu"
        else jnp.float32,
    )
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    slots, max_len = 8, 512
    prompt_lens = [16, 48, 96, 160]
    budgets = [32, 96, 48, 64, 16, 80, 56, 40]
    n_requests = 24
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(0, cfg.vocab_size, size=prompt_lens[i % len(prompt_lens)],
                     dtype=np.int32)
        for i in range(n_requests)
    ]

    def serve(server_params, *, mesh=None):
        srv = SlotServer(
            server_params, cfg, slots=slots, max_len=max_len,
            block_size=16, prefill_chunk=64, mesh=mesh)
        reqs = [Request(prompt=p, max_new_tokens=budgets[i % len(budgets)])
                for i, p in enumerate(prompts)]
        for r in reqs:
            srv.submit(r)
        t0 = _time.time()
        done = srv.run_until_drained()
        wall = _time.time() - t0
        # key by submission index: Request.id is a process-global counter,
        # so ids differ between server instances serving the same workload
        toks = {i: done[r.id].tokens for i, r in enumerate(reqs)}
        n_tokens = sum(len(t) for t in toks.values())
        srv.dispatch_tracker.drain(timeout=10.0)    # reaper catches up
        out = {
            "wall_s": round(wall, 3),
            "tokens_per_sec": round(n_tokens / wall, 1),
            "useful_tokens": n_tokens,
            "admission_dispatches": srv.admission_dispatches,
            "latency": srv.telemetry.snapshot(),
            "device": srv.dispatch_tracker.snapshot(),
        }
        srv.shutdown()      # bench builds many servers: no thread pile-up
        return out, toks

    serve(params)                                     # compile warm-up
    # warmup line: compiles past here are RECOMPILES — the timed pass
    # replays warm shapes, so a healthy run reads ~0 post-warm
    compile_telemetry.mark_warm()
    batched, toks_b = serve(params)
    # snapshot BEFORE the TP pass, which legitimately compiles new
    # program shapes (sharded programs) and would drown the timed pass's
    # recompile signal
    compile_snap = compile_telemetry.snapshot()

    # open-loop Poisson arrivals (ROADMAP leftover, ISSUE 16): the same
    # workload offered the way real traffic arrives — seeded
    # interarrivals at the measured burst capacity — instead of all up
    # front. Capacity is whatever the engine sustains under that
    # arrival process; byte-identity is asserted (arrival timing is
    # scheduling, never numerics), and the latency shape is the
    # open-loop one rather than the burst's deep-backlog artifact.
    def serve_open_loop(offered_tok_s):
        srv = SlotServer(params, cfg, slots=slots, max_len=max_len,
                         block_size=16, prefill_chunk=64)
        mean_new = sum(budgets) / len(budgets)
        interarrival = mean_new / offered_tok_s
        sched = np.cumsum(np.random.default_rng(16).exponential(
            scale=interarrival, size=n_requests))
        reqs = [Request(prompt=p, max_new_tokens=budgets[i % len(budgets)])
                for i, p in enumerate(prompts)]
        done: dict = {}
        nxt = 0
        t0 = _time.time()
        while nxt < len(reqs) or not srv.idle:
            now = _time.time() - t0
            while nxt < len(reqs) and sched[nxt] <= now:
                srv.submit(reqs[nxt])
                nxt += 1
            if srv.idle and nxt < len(reqs):
                _time.sleep(min(0.002, max(0.0, sched[nxt] - now)))
                continue
            srv.step()
            # host-observe per turn (the ServeApp journal cadence):
            # predictive processing is lazy, and without this the
            # first_token/finished marks collapse into end-of-run
            # bursts and the latency block below is fiction
            srv.checkpoint_progress()
            if srv._done:
                done.update(srv.drain_completed())
        done.update(srv.drain_completed())
        wall = _time.time() - t0
        toks = {i: done[r.id].tokens for i, r in enumerate(reqs)}
        n_tokens = sum(len(t) for t in toks.values())
        lat = srv.telemetry.snapshot()
        srv.dispatch_tracker.drain(timeout=10.0)
        out = {
            "offered_tokens_per_sec": round(offered_tok_s, 1),
            "poisson_interarrival_s": round(interarrival, 4),
            "wall_s": round(wall, 3),
            "tokens_per_sec": round(n_tokens / wall, 1),
            "useful_tokens": n_tokens,
            "latency": {k: v for k, v in lat.items()
                        if k in ("ttft_s", "tpot_s", "queue_wait_s",
                                 "e2e_s")},
        }
        srv.shutdown()
        return out, toks
    serve_open_loop(batched["tokens_per_sec"])        # warm the pacer
    open_loop, toks_ol = serve_open_loop(batched["tokens_per_sec"])
    assert toks_ol == toks_b, "arrival process changed completions"

    # latency baseline (ISSUE 4): p50/p90/p99 TTFT / TPOT / queue wait /
    # e2e of the timed batched pass, from the observability histograms —
    # the PERF.json `serving_latency` section future perf PRs regress
    # against. Host-monotonic spans; the whole burst is submitted up
    # front, so queue waits here measure the saturated-backlog shape.
    latency_full = batched.pop("latency")
    serving_latency = {
        k: v for k, v in latency_full.items()
        if k in ("ttft_s", "tpot_s", "queue_wait_s", "e2e_s")
    }
    # device-time attribution (ISSUE 6): dispatch→ready quantiles per
    # program kind, the measured device lag behind host observation, and
    # the XLA compile bill of warm-up + timed pass (compile_snap was
    # taken before the per-slot/TP passes) — the PERF.json `device_time`
    # section future PRs track the trajectory against. The device lag is
    # the saturated-backlog shape, same caveat as serving_latency: the
    # burst is submitted up front and blocks go device-ready well before
    # the host replays them.
    device = batched.pop("device")
    device_lag = latency_full.get("device_lag_s", {})
    device_time = {
        "dispatch_ready": device["dispatch_ready"],
        "dispatches_tracked": device["tracked"],
        "dispatch_track_dropped": device["dropped"],
        "mean_device_lag_s": device_lag.get("mean_s", 0.0),
        "p99_device_lag_s": device_lag.get("p99_s", 0.0),
        "compile": compile_snap,
    }
    out = {
        "metric": "continuous_batching_serving_tokens_per_sec",
        "value": batched["tokens_per_sec"],
        "unit": "tokens/s",
        "slots": slots,
        "n_requests": n_requests,
        "prompt_lens_cycle": prompt_lens,
        "budgets_cycle": budgets,
        "serving_latency": serving_latency,
        "device_time": device_time,
        "batched_admission": batched,
        "open_loop": {**open_loop,
                      "byte_identical_vs_burst": toks_ol == toks_b},
        "num_devices": jax.device_count(),
    }
    if jax.device_count() >= 2:
        from tony_tpu.models.generate import prepare_decode
        from tony_tpu.parallel import MeshSpec, build_mesh

        tensor = 2 if cfg.n_kv_heads % 2 == 0 else 1
        data = 2 if jax.device_count() >= 4 else 1
        mesh = build_mesh(MeshSpec(data=data, fsdp=1, tensor=tensor),
                          devices=jax.devices()[:data * tensor])
        prep = prepare_decode(params, cfg, mesh=mesh)
        serve(prep, mesh=mesh)                        # warm-up
        tp, toks_tp = serve(prep, mesh=mesh)
        tp.pop("latency", None)
        tp.pop("device", None)
        out["tp"] = {**tp, "mesh": dict(mesh.shape),
                     "parity_vs_single_device": toks_tp == toks_b}
    print(json.dumps(out))
    return 0


def run_paged_kv_bench() -> int:
    """Paged-KV allocator benchmark (one JSON line -> PERF.json
    `paged_kv`; see the module docstring). TINY shapes throughout —
    every gate here is an INVARIANT (byte-identity, concurrency bound,
    shed order, bounded TPOT ratio), not a host-speed number, and the
    storm arm is chaos-paced so the per-turn sleep dominates compute
    and the ratio is deterministic on any host."""
    import time as _time

    sys.path.insert(0, str(REPO))
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tony_tpu.models import transformer
    from tony_tpu.models.serving import QueueFullError, Request, SlotServer

    cfg = transformer.TransformerConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=128, dtype=jnp.float32)
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    B, max_len, chunk = 8, 64, 8
    ring_slots = 4
    pool_blocks = ring_slots * max_len // B     # EQUAL device memory
    rng = np.random.default_rng(16)

    # ---- arm 1: byte-identity + concurrency above the ring bound ----
    # Requests sized so the pool holds ~10 concurrent block tables
    # (mean ~3 blocks each) where the ring engine pins concurrency at
    # ring_slots=4 regardless of actual KV bytes.
    plens, budgets_c = [6, 10, 14, 18], [6, 12, 8, 10]
    n_requests = 16
    prompts = [rng.integers(0, cfg.vocab_size, size=plens[i % 4],
                            dtype=np.int32) for i in range(n_requests)]

    def drive(srv):
        """run_until_drained, sampling peak concurrent active slots."""
        reqs = [Request(prompt=p, max_new_tokens=budgets_c[i % 4])
                for i, p in enumerate(prompts)]
        for r in reqs:
            srv.submit(r)
        done: dict = {}
        peak = 0
        t0 = _time.time()
        while not srv.idle:
            srv.step()
            peak = max(peak, srv.n_active)
            if srv._done:
                done.update(srv.drain_completed())
        done.update(srv.drain_completed())
        wall = _time.time() - t0
        toks = {i: done[r.id].tokens for i, r in enumerate(reqs)}
        reasons = [done[r.id].finish_reason for r in reqs]
        return toks, peak, wall, reasons

    def mk_ring():
        return SlotServer(params, cfg, slots=ring_slots, max_len=max_len,
                          block_size=4, prefill_chunk=chunk)

    def mk_paged(**kw):
        kw.setdefault("slots", 12)
        kw.setdefault("kv_pool_blocks", pool_blocks)
        return SlotServer(params, cfg, max_len=max_len, block_size=4,
                          prefill_chunk=chunk, paged=True, kv_block=B,
                          **kw)

    drive(mk_ring())                            # compile warm-up
    toks_ring, peak_ring, wall_ring, reasons_r = drive(mk_ring())
    drive(mk_paged())
    paged_srv = mk_paged()
    toks_paged, peak_paged, wall_paged, reasons_p = drive(paged_srv)
    pkv = paged_srv.stats()["paged_kv"]
    assert toks_paged == toks_ring, (
        "paged engine diverged from the ring engine on greedy outputs")
    assert all(r in ("stop", "length") for r in reasons_r + reasons_p), (
        f"failed/early requests: {reasons_r} {reasons_p}")
    assert peak_paged > ring_slots, (
        f"paged peak concurrency {peak_paged} did not exceed the ring "
        f"slots x max_len bound ({ring_slots}) at equal device memory")
    assert pkv["pool_blocks_peak"] <= pool_blocks
    paged_srv._allocator.check()

    # ---- arm 2: admission-storm TPOT, interleave on vs off ----------
    # 20ms per scheduling turn dwarfs TINY compute, so TPOT measures
    # TURN CADENCE: interleaved prefill rides the decode turn (cadence
    # unchanged, ratio ~1.0x) while the uncapped pump drains the whole
    # storm's chunks inside ONE turn (a concentrated stall every
    # in-flight stream feels).
    os.environ["TONY_TEST_SERVING_STEP_DELAY_MS"] = "20"
    try:
        def run_storm(interleave, storm=True):
            srv = SlotServer(
                params, cfg, slots=16, max_len=max_len, block_size=4,
                prefill_chunk=chunk, paged=True, kv_block=B,
                kv_pool_blocks=128, prefill_interleave=interleave)
            r2 = np.random.default_rng(17)
            cohort = [Request(prompt=r2.integers(0, cfg.vocab_size,
                                                 size=8, dtype=np.int32),
                              max_new_tokens=32) for _ in range(4)]
            for r in cohort:
                srv.submit(r)
            for _ in range(6):          # cohort admitted + mid-decode
                srv.step()
                srv.checkpoint_progress()
            if storm:
                for _ in range(12):     # 6 prefill chunks each
                    srv.submit(Request(
                        prompt=r2.integers(0, cfg.vocab_size, size=48,
                                           dtype=np.int32),
                        max_new_tokens=1))
            done: dict = {}
            turn_walls = []
            while not srv.idle:
                t1 = _time.time()
                srv.step()
                # predictive processing is lazy; pace it per turn the
                # way ServeApp's journal checkpoint does, so the host
                # first_token/finished marks (the TPOT spans) track
                # turn cadence instead of collapsing into one
                # end-of-run processing burst
                srv.checkpoint_progress()
                turn_walls.append(_time.time() - t1)
                if srv._done:
                    done.update(srv.drain_completed())
            done.update(srv.drain_completed())
            assert all(c.finish_reason in ("stop", "length")
                       for c in done.values())
            # cohort-only TPOT, exact from the request traces (the
            # stats histogram is bucket-resolution; at 4 samples the
            # quantization would dominate the gated ratio) — the
            # storm's max_new=1 requests emit no TPOT samples
            tpots = []
            for c in done.values():
                spans = dict(c.trace["spans"])
                n = len(c.tokens)
                if "first_token" in spans and "finished" in spans \
                        and n >= 2:
                    tpots.append(
                        (spans["finished"] - spans["first_token"])
                        / (n - 1))
            assert len(tpots) == 4, f"cohort TPOT samples: {len(tpots)}"
            return {
                "tpot_p99_s": max(tpots),
                "max_turn_s": round(max(turn_walls), 4),
                "chunks_interleaved":
                    srv.stats()["paged_kv"]["prefill_chunks_interleaved"],
            }

        run_storm(chunk, storm=True)    # compile warm-up: every program
        run_storm(0, storm=True)        # shape both timed arms will hit
        quiescent = run_storm(chunk, storm=False)
        storm_on = run_storm(chunk, storm=True)
        storm_off = run_storm(0, storm=True)
    finally:
        del os.environ["TONY_TEST_SERVING_STEP_DELAY_MS"]
    tpot_ratio_on = storm_on["tpot_p99_s"] / quiescent["tpot_p99_s"]
    assert tpot_ratio_on <= 1.2, (
        f"storm TPOT p99 with interleaving is {tpot_ratio_on:.2f}x "
        "quiescent (gate: <= 1.2x)")
    assert storm_on["chunks_interleaved"] > 0, (
        "the storm never exercised the interleave cap")
    assert storm_off["max_turn_s"] > 1.5 * storm_on["max_turn_s"], (
        "uncapped admission should stall one turn for the whole "
        f"storm's prefill: off {storm_off['max_turn_s']}s vs "
        f"on {storm_on['max_turn_s']}s")

    # ---- arm 3: admission tiers — batch sheds before interactive ----
    srv = SlotServer(params, cfg, slots=2, max_len=max_len, block_size=4,
                     prefill_chunk=chunk, paged=True, kv_block=B,
                     max_queue=4, batch_queue_frac=0.5)
    r3 = np.random.default_rng(18)

    def _req(priority):
        return Request(prompt=r3.integers(0, cfg.vocab_size, size=6,
                                          dtype=np.int32),
                       max_new_tokens=12, priority=priority)

    occupants = [_req("interactive") for _ in range(2)]
    for r in occupants:
        srv.submit(r)
    for _ in range(4):                  # both slots occupied, mid-decode
        srv.step()
    refused = {"batch": 0, "interactive": 0}
    retry_afters = []
    submitted = []
    # batch fills its (frac-limited) share of the queue, then 429s
    for _ in range(3):
        try:
            submitted.append(srv.submit(_req("batch")))
        except QueueFullError as e:
            refused[e.priority] += 1
            retry_afters.append(e.retry_after_s)
    # interactive fills the rest, then displaces the queued batch work
    for _ in range(5):
        try:
            submitted.append(srv.submit(_req("interactive")))
        except QueueFullError as e:
            refused[e.priority] += 1
            retry_afters.append(e.retry_after_s)
    done = srv.run_until_drained()
    shed = srv.stats()["shed_by_class"]
    shed_completions = [c for c in done.values()
                        if c.finish_reason == "shed"]
    assert refused["batch"] >= 1, "batch tier never hit its 429 line"
    assert shed["batch"] >= len(shed_completions) >= 2, (
        f"queued batch work was not displaced: {shed}")
    assert all(1 <= ra <= 60 for ra in retry_afters), retry_afters
    # every interactive request either finished or was refused AT THE
    # DOOR with Retry-After — none failed, none displaced mid-queue
    n_interactive_ok = sum(
        1 for c in done.values() if c.finish_reason in ("stop", "length"))
    assert n_interactive_ok == 2 + 5 - refused["interactive"] + \
        3 - refused["batch"] - len(shed_completions), done
    srv._allocator.check()

    out = {
        "metric": "paged_kv_storm_tpot_p99_ratio_vs_quiescent",
        "value": round(tpot_ratio_on, 3),
        "unit": "x (chunked-prefill interleaving ON; gate <= 1.2x)",
        "kv_block": B,
        "pool_blocks": pool_blocks,
        "equal_device_memory_kv_rows": pool_blocks * B,
        "ring_concurrency_bound": ring_slots,
        "peak_concurrent_paged": peak_paged,
        "byte_identical_vs_ring": True,
        "zero_failed_requests": True,
        "ring_wall_s": round(wall_ring, 3),
        "paged_wall_s": round(wall_paged, 3),
        "admission_defers": pkv["admission_defers"],
        "storm": {
            "chaos_step_delay_ms": 20,
            "quiescent_tpot_p99_s": round(quiescent["tpot_p99_s"], 4),
            "interleave_on_tpot_p99_s":
                round(storm_on["tpot_p99_s"], 4),
            "interleave_off_tpot_p99_s":
                round(storm_off["tpot_p99_s"], 4),
            "interleave_on_max_turn_s": storm_on["max_turn_s"],
            "interleave_off_max_turn_s": storm_off["max_turn_s"],
            "chunks_interleaved": storm_on["chunks_interleaved"],
        },
        "tiers": {
            "shed_by_class": shed,
            "queued_batch_displaced": len(shed_completions),
            "refused_429": refused,
            "retry_after_s_range": [min(retry_afters),
                                    max(retry_afters)],
            "batch_shed_before_interactive":
                shed["interactive"] <= refused["interactive"],
        },
    }
    print(json.dumps(out))
    return 0


def run_disagg_bench() -> int:
    """Disaggregated prefill/decode serving gate (one JSON line ->
    PERF.json `disaggregated_serving`; see the module docstring).
    TINY shapes; the TPOT comparison is real-compute (NOT chaos-paced:
    the win IS the compute a decode turn no longer carries) and every
    correctness property — byte-identity, zero failed requests, the
    SIGKILL replay fallback — is an enforced invariant."""
    import re as _re
    import signal as _signal
    import subprocess
    import threading
    import time as _time
    import urllib.request

    sys.path.insert(0, str(REPO))
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tony_tpu.models import transformer
    from tony_tpu.models.serving import (
        QueueFullError, Request, SlotServer,
    )

    cfg = transformer.TransformerConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=128, dtype=jnp.float32)
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    B, max_len, chunk, slots, pool = 8, 64, 8, 8, 96
    rng = np.random.default_rng(17)

    # mixed workload: an interactive decode cohort already in flight
    # when a long-prompt prefill storm arrives. Cohort TPOT is what the
    # decode tier's SLO protects; the storm is pure prefill pressure.
    n_cohort, cohort_new = 6, 48
    n_storm, storm_new = 16, 2
    cohort_p = [rng.integers(0, cfg.vocab_size, size=8, dtype=np.int32)
                for _ in range(n_cohort)]
    storm_p = [rng.integers(0, cfg.vocab_size, size=48, dtype=np.int32)
               for _ in range(n_storm)]

    def mk(role="both"):
        return SlotServer(params, cfg, slots=slots, max_len=max_len,
                          block_size=4, prefill_chunk=chunk, paged=True,
                          kv_block=B, kv_pool_blocks=pool, role=role)

    def creq(i):
        return Request(prompt=cohort_p[i], max_new_tokens=cohort_new)

    def sreq(i):
        return Request(prompt=storm_p[i], max_new_tokens=storm_new)

    def _p99(walls):
        assert len(walls) >= 10, f"too few turn samples: {len(walls)}"
        s = sorted(walls)
        return s[min(len(s) - 1, int(0.99 * len(s)))]

    # ---- byte reference: every request solo on ONE paged engine ----
    solo = mk()
    solo_reqs = ([creq(i) for i in range(n_cohort)]
                 + [sreq(i) for i in range(n_storm)])
    for r in solo_reqs:
        solo.submit(r)
    solo_done = solo.run_until_drained()
    refs = [solo_done[r.id].tokens for r in solo_reqs]

    # Both legs drive every engine serially in ONE process, so a
    # stream's trace spans would absorb the OTHER replica's compute —
    # the opposite of the separate-hardware reality. The faithful
    # per-replica TPOT is the engine's OWN per-turn step wall while
    # cohort work is in flight: an in-flight stream emits one token
    # per scheduling turn, so its TPOT is exactly its replica's turn
    # time, and whatever rides that turn (storm prefill chunks on a
    # role=both replica; nothing on a decode specialist) is what the
    # measurement must charge.

    def run_both_leg():
        """2 x role='both' at equal hardware: each replica carries half
        the cohort AND half the storm — storm prefill chunks ride the
        cohort's decode turns (bounded by the interleave cap, but
        riding them all the same)."""
        engines = [mk(), mk()]
        reqs = [creq(i) for i in range(n_cohort)]
        cohort_ids: list = [set(), set()]
        for i, r in enumerate(reqs):
            engines[i % 2].submit(r)
            cohort_ids[i % 2].add(r.id)
        for _ in range(3):              # cohort admitted, mid-decode
            for e in engines:
                e.step()
                e.checkpoint_progress()
        for i in range(n_storm):
            engines[i % 2].submit(sreq(i))
        done: list[dict] = [{}, {}]
        walls: list = []
        while not all(e.idle for e in engines):
            for ei, e in enumerate(engines):
                if not e.idle:
                    t1 = _time.time()
                    e.step()
                    w = _time.time() - t1
                    e.checkpoint_progress()
                    if cohort_ids[ei] - set(done[ei]):
                        walls.append(w)
                if e._done:
                    done[ei].update(e.drain_completed())
        for ei, e in enumerate(engines):
            done[ei].update(e.drain_completed())
            e._allocator.check()
        reasons = [c.finish_reason for d in done for c in d.values()]
        assert all(r in ("stop", "length") for r in reasons), reasons
        return _p99(walls)

    def run_disagg_leg():
        """1 prefill specialist + 1 decode replica (equal hardware):
        every request prefills on the specialist and decodes — via the
        exported-block handoff — on the decode replica, whose turns
        carry ONLY decode work."""
        pre, dec = mk("prefill"), mk("decode")
        done_pre: dict = {}
        done_dec: dict = {}
        handoffs: list = []             # payloads awaiting a dec slot
        rid_map: dict = {}              # original id -> dec-side id
        kv_imports = 0

        def pump_pre():
            nonlocal kv_imports
            if not pre.idle:
                pre.step()
                pre.checkpoint_progress()
            if pre._done:
                done_pre.update(pre.drain_completed())
            for rid in list(done_pre):
                comp = done_pre.pop(rid)
                assert comp.finish_reason == "prefilled", comp
                handoffs.append(pre.export_blocks(rid))
            while handoffs:
                try:
                    new_rid = dec.import_blocks(handoffs[0])
                except QueueFullError:
                    break               # dec full; retry next turn
                # the decode replica assigns its own request id; the
                # entry carries the original for the caller's join
                rid_map[handoffs[0]["entry"]["id"]] = new_rid
                handoffs.pop(0)
                kv_imports += 1

        # leg ordering mirrors the both leg: cohort first, mid-decode,
        # then the storm drops
        cohort = [creq(i) for i in range(n_cohort)]
        for r in cohort:
            pre.submit(r)
        while kv_imports < n_cohort:    # cohort handed off to dec
            pump_pre()
        for _ in range(3):              # cohort admitted, mid-decode
            dec.step()
            dec.checkpoint_progress()
        storm = [sreq(i) for i in range(n_storm)]
        for r in storm:
            pre.submit(r)
        all_reqs = cohort + storm
        walls: list = []
        cohort_orig = {r.id for r in cohort}
        while len(done_dec) < len(all_reqs):
            pump_pre()
            if not dec.idle:
                t1 = _time.time()
                dec.step()
                w = _time.time() - t1
                dec.checkpoint_progress()
                if {rid_map[i] for i in cohort_orig
                        if i in rid_map} - set(done_dec):
                    walls.append(w)
            if dec._done:
                done_dec.update(dec.drain_completed())
        pre._allocator.check()
        dec._allocator.check()
        assert dec.stats()["paged_kv"]["kv_imports"] == len(all_reqs)
        assert pre.stats()["paged_kv"]["kv_exports"] == len(all_reqs)
        reasons = [c.finish_reason for c in done_dec.values()]
        assert all(r in ("stop", "length") for r in reasons), reasons
        toks = [done_dec[rid_map[r.id]].tokens for r in all_reqs]
        return _p99(walls), toks

    run_both_leg()                      # compile warm-up, both shapes
    run_disagg_leg()
    tpot_both = run_both_leg()
    tpot_disagg, disagg_toks = run_disagg_leg()
    speedup = tpot_both / tpot_disagg
    assert disagg_toks == refs, (
        "disaggregated completions diverged from solo greedy")
    assert speedup >= 1.2, (
        f"decode TPOT p99: 2x both {tpot_both:.4f}s vs disagg "
        f"{tpot_disagg:.4f}s = {speedup:.2f}x (gate: >= 1.2x)")

    # ---- fleet leg: mid-transfer SIGKILL -> journal-replay fallback --
    import tempfile as _tempfile

    from tony_tpu.router import FleetRouter

    f_requests = 10
    f_budgets = [8, 12, 16]
    f_prompts = [rng.integers(0, cfg.vocab_size, size=24,
                              dtype=np.int32).tolist()
                 for _ in range(f_requests)]
    # the serve CLI always sets n_kv_heads=n_heads (and the default
    # max_seq_len), so the fleet byte-reference uses the CLI's shape —
    # NOT the in-process cfg above
    f_cfg = transformer.TransformerConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=4,
        d_ff=128, dtype=jnp.float32)
    f_params = transformer.init(jax.random.PRNGKey(0), f_cfg)
    f_solo = SlotServer(f_params, f_cfg, slots=slots, max_len=max_len,
                        block_size=4, prefill_chunk=chunk, paged=True,
                        kv_block=B, kv_pool_blocks=pool)
    f_reqs = [Request(prompt=p,
                      max_new_tokens=f_budgets[i % len(f_budgets)])
              for i, p in enumerate(f_prompts)]
    for r in f_reqs:
        f_solo.submit(r)
    f_done = f_solo.run_until_drained()
    f_refs = [f_done[r.id].tokens for r in f_reqs]

    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           # slow each turn so the prefill leg stays in flight long
           # enough for a genuinely MID-transfer kill
           "TONY_TEST_SERVING_STEP_DELAY_MS": "25"}
    env.pop("XLA_FLAGS", None)

    class Srv:
        def __init__(self, name, role, trace_dir):
            self.name, self.role, self.trace_dir = name, role, trace_dir
            self.proc = self.port = None
            self.spawn()

        def spawn(self):
            self.proc = subprocess.Popen(
                [sys.executable, "-m", "tony_tpu.cli.main", "serve",
                 "--port", "0", "--vocab", "256", "--d-model", "64",
                 "--n-layers", "2", "--n-heads", "4",
                 "--d-ff", "128", "--dtype", "float32",
                 "--seed", "0", "--slots", str(slots),
                 "--max-len", str(max_len), "--block-size", "4",
                 "--prefill-chunk", str(chunk), "--paged-kv",
                 "--kv-block", str(B), "--kv-pool-blocks", str(pool),
                 "--role", self.role, "--trace-dir", self.trace_dir],
                cwd=REPO, env=env, text=True,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
            self.port = None

        def await_ready(self, timeout=240.0):
            deadline = _time.time() + timeout
            while self.port is None and _time.time() < deadline:
                line = self.proc.stdout.readline()
                m = _re.search(r"http://[\d.]+:(\d+)", line or "")
                if m:
                    self.port = int(m.group(1))
            assert self.port, f"{self.name} never printed its port"
            threading.Thread(target=self.proc.stdout.read,
                             daemon=True).start()
            while _time.time() < deadline:
                try:
                    with urllib.request.urlopen(
                            f"http://127.0.0.1:{self.port}/healthz",
                            timeout=2) as r:
                        if r.status == 200:
                            return
                except Exception:
                    _time.sleep(0.2)
            raise AssertionError(f"{self.name} never became healthy")

        def stats(self):
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{self.port}/stats",
                    timeout=10) as r:
                return json.loads(r.read().decode())

        def stop(self):
            if self.proc.poll() is None:
                self.proc.kill()
            self.proc.wait(timeout=15)

    td = _tempfile.mkdtemp(prefix="tony-disagg-bench-")
    pre_s = Srv("pre", "prefill", os.path.join(td, "pre"))
    dec_s = Srv("dec", "decode", os.path.join(td, "dec"))
    router = None
    try:
        pre_s.await_ready()
        dec_s.await_ready()
        router = FleetRouter(
            [("pre", "127.0.0.1", pre_s.port),
             ("dec", "127.0.0.1", dec_s.port)],
            prefill_chunk=chunk, health_interval_s=0.15,
            stats_every=1, seed=0)
        router.start()
        deadline = _time.time() + 30
        while _time.time() < deadline:
            st = router.stats()["replicas"]
            if st.get("pre", {}).get("role") == "prefill" \
                    and st.get("dec", {}).get("role") == "decode":
                break
            _time.sleep(0.1)

        fleet_results: dict[int, object] = {}

        def call(i):
            try:
                fleet_results[i] = router.generate(
                    f_prompts[i],
                    max_new_tokens=f_budgets[i % len(f_budgets)],
                    timeout_s=300)
            except Exception as e:
                fleet_results[i] = e

        t0 = _time.time()
        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(f_requests)]
        for t in threads:
            t.start()
            _time.sleep(0.05)
        # kill the prefill specialist once the transfer path has
        # genuinely moved blocks (>=1 completed handoff) AND a prefill
        # leg is in flight — a mid-transfer death, not a cold one
        deadline = _time.time() + 120
        killed = False
        while _time.time() < deadline:
            rs = router.stats()
            if rs["disagg_handoffs"] >= 1 and rs["disagg_requests"] \
                    > rs["disagg_handoffs"] + rs["disagg_fallbacks"]:
                os.kill(pre_s.stats()["pid"], _signal.SIGKILL)
                killed = True
                break
            _time.sleep(0.02)
        assert killed, "the transfer path never reached a kill window"
        for t in threads:
            t.join(timeout=600)
        fleet_wall = _time.time() - t0
        assert not any(t.is_alive() for t in threads), "hung callers"
        failed = [i for i, r in fleet_results.items()
                  if not isinstance(r, dict)]
        assert not failed, (
            f"disagg SIGKILL leg failed requests: "
            f"{[(i, fleet_results[i]) for i in failed]}")
        mismatch = [i for i in range(f_requests)
                    if fleet_results[i]["tokens"] != f_refs[i]]
        assert not mismatch, (
            f"disagg fleet diverged from solo greedy on: {mismatch}")
        rstats = router.stats()
        assert rstats["failed"] == 0
        assert rstats["disagg_handoffs"] >= 1, (
            "no handoff completed before the kill")
        assert rstats["disagg_fallbacks"] >= 1, (
            "the mid-transfer kill must exercise the replay fallback")
        dec_stats = dec_s.stats()
        kv_imported = dec_stats["paged_kv"]["kv_imports"]
    finally:
        if router is not None:
            router.shutdown()
        for s in (pre_s, dec_s):
            try:
                s.stop()
            except Exception:
                pass

    out = {
        "metric": "disagg_decode_tpot_p99_speedup_vs_both",
        "value": round(speedup, 3),
        "unit": "x (1 prefill + 1 decode vs 2x role=both at equal "
                "hardware; gate >= 1.2x)",
        "kv_block": B,
        "pool_blocks_per_replica": pool,
        "mixed_workload": {
            "cohort": {"n": n_cohort, "prompt_len": 8,
                       "max_new": cohort_new},
            "storm": {"n": n_storm, "prompt_len": 48,
                      "max_new": storm_new},
        },
        "both_tpot_p99_s": round(tpot_both, 4),
        "disagg_tpot_p99_s": round(tpot_disagg, 4),
        "byte_identical_vs_solo": True,
        "zero_failed_requests": True,
        "sigkill_leg": {
            "requests": f_requests,
            "failed": 0,
            "byte_identical": True,
            "handoffs_before_kill": rstats["disagg_handoffs"],
            "replay_fallbacks": rstats["disagg_fallbacks"],
            "decode_kv_imports": kv_imported,
            "wall_s": round(fleet_wall, 3),
            "chaos_step_delay_ms": 25,
        },
        "num_devices": jax.device_count(),
    }
    print(json.dumps(out))
    return 0


def run_shared_prefix_bench() -> int:
    """Prefix-cache serving benchmark (one JSON line; see module
    docstring). Submission order, budgets, and slot scheduling are
    identical between the cold and warm servers, so the only difference
    is WHERE prompt-body KV comes from — recomputed (cold) or copied out
    of the shared pool (warm). The bench asserts the completions are
    byte-identical: prefix reuse is a pure data-movement optimization,
    never a numerics change (int8 pools store the quantized bytes)."""
    import time as _time

    sys.path.insert(0, str(REPO))
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tony_tpu.models import transformer
    from tony_tpu.models.serving import Request, SlotServer

    cfg = transformer.TransformerConfig(
        vocab_size=2048, d_model=256, n_layers=4, n_heads=8, n_kv_heads=4,
        d_ff=1024, max_seq_len=512,
        dtype=jnp.bfloat16 if jax.default_backend() == "tpu"
        else jnp.float32,
    )
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    slots, max_len, chunk = 8, 512, 64
    n_requests, template_len = 24, 192          # template = 3 full chunks
    suffix_cycle = [9, 13, 17, 21]
    budgets = [32, 48, 24, 40]
    rng = np.random.default_rng(7)
    template = rng.integers(0, cfg.vocab_size, size=template_len,
                            dtype=np.int32)
    prompts = [
        np.concatenate([template, rng.integers(
            0, cfg.vocab_size, size=suffix_cycle[i % len(suffix_cycle)],
            dtype=np.int32)])
        for i in range(n_requests)
    ]
    body_tokens = sum(p.size - 1 for p in prompts)

    def serve(*, blocks):
        srv = SlotServer(params, cfg, slots=slots, max_len=max_len,
                         block_size=16, prefill_chunk=chunk,
                         prefix_cache_blocks=blocks)
        reqs = [Request(prompt=p, max_new_tokens=budgets[i % len(budgets)])
                for i, p in enumerate(prompts)]
        for r in reqs:
            srv.submit(r)
        t0 = _time.time()
        done = srv.run_until_drained()
        wall = _time.time() - t0
        toks = {i: done[r.id].tokens for i, r in enumerate(reqs)}
        n_tokens = sum(len(t) for t in toks.values())
        return {
            "wall_s": round(wall, 3),
            "tokens_per_sec": round(n_tokens / wall, 1),
            "useful_tokens": n_tokens,
            "admission_dispatches": srv.admission_dispatches,
            "prefill_tokens_computed": srv.prefill_tokens_computed,
            "prefill_tokens_reused": srv.prefill_tokens_reused,
            **({"prefix_cache": srv.stats()["prefix_cache"]} if blocks
               else {}),
        }, toks

    pool_blocks = 32
    serve(blocks=0)                              # compile warm-up
    cold, toks_cold = serve(blocks=0)
    serve(blocks=pool_blocks)                    # warm the hit-path too
    hit, toks_hit = serve(blocks=pool_blocks)
    assert toks_hit == toks_cold, (
        "prefix cache changed completions — reuse must be byte-identical")
    reused_frac = hit["prefill_tokens_reused"] / body_tokens
    out = {
        "metric": "prefix_cache_serving_reused_token_fraction",
        "value": round(reused_frac, 4),
        "unit": "fraction of prompt-body tokens served from cache",
        "slots": slots,
        "n_requests": n_requests,
        "template_len": template_len,
        "suffix_cycle": suffix_cycle,
        "budgets_cycle": budgets,
        "prefill_chunk": chunk,
        "prefix_cache_blocks": pool_blocks,
        "body_tokens_total": body_tokens,
        "completions_identical_hit_vs_cold": True,
        "cold": cold,
        "hit": hit,
        "num_devices": jax.device_count(),
    }
    print(json.dumps(out))
    return 0


def _scrape_ttft_hist(base_url: str):
    """Reconstruct the serving_ttft_seconds histogram from a replica's
    /metrics exposition (cumulative ``le`` buckets) into an
    observability.Histogram — scraped before and after a timed pass, the
    bucket DELTA gives that pass's quantiles with no warm-up pollution."""
    import re as _re
    import urllib.request

    from tony_tpu.observability import Histogram

    with urllib.request.urlopen(base_url + "/metrics", timeout=10) as r:
        text = r.read().decode()
    cum = []
    for m in _re.finditer(
            r'^serving_ttft_seconds_bucket\{le="([^"]+)"\} (\d+)$',
            text, _re.M):
        cum.append((m.group(1), int(m.group(2))))
    h = Histogram()
    assert len(cum) == len(h.counts), "ttft bucket layout drifted"
    prev = 0
    for i, (_, c) in enumerate(cum):
        h.counts[i] = c - prev
        prev = c
    h.count = prev
    return h


def _hist_delta(before, after):
    """after - before as a fresh Histogram (per-pass bucket deltas;
    merge the per-replica results before taking fleet-wide quantiles —
    max-of-per-replica-p99s would overstate the tail under uneven
    load)."""
    from tony_tpu.observability import Histogram

    d = Histogram()
    d.counts = [a - b for a, b in zip(after.counts, before.counts)]
    d.count = after.count - before.count
    return d


def run_serving_fleet_bench() -> int:
    """Fleet benchmark (one JSON line; ISSUE 7): a 2-3 replica
    SlotServer fleet of real serve processes (PR 2 shape, prefix
    caches ON — the production path) behind the FleetRouter, on
    forced-CPU host devices with one replica pinned per core (one
    replica per accelerator host; an unpinned XLA CPU server would
    spread over every core and the "N replicas vs 1" comparison would
    measure contention, not capacity). Two comparisons, enforced
    rather than just reported:

    - **capacity scaling**: closed-loop, concurrency-matched,
      best-of-`trials` per arm after a discarded steady-state pass —
      fleet capacity must exceed 1.5x one replica. Closed loop because
      per-pass open-loop throughput at these wall times swings ~3x
      with scheduler placement (every arrival-rate calibration scheme
      measured the arrival process or the noise, not the fleet). The
      headroom is compute AND cache capacity: the per-replica trie
      budget holds 2/3 of the template working set, so the
      affinity-routed fleet holds it collectively while the single
      replica churns it through LRU eviction. Open-loop CAPACITY arms
      ride along: Poisson arrivals offered at each arm's own measured
      capacity, best-of-`trials`, enforcing a softer 1.3x fleet
      advantage (per-pass open-loop walls swing with placement).
      Poisson OPEN-LOOP passes at 1.2x the measured fleet capacity are
      reported alongside (the lone replica collapses into deep
      queueing at fleet-rate traffic).
    - **prefix-affinity vs random routing**: the same open-loop
      schedule routed sticky vs least-loaded, after an untimed
      steady-state prepass per policy. Affinity must beat random on
      the fleet-wide reused-token fraction. p99 TTFT (per-replica
      serving_ttft_seconds bucket deltas over the timed pass, MERGED
      fleet-wide) is reported for both.
    """
    import re as _re
    import subprocess
    import threading
    import urllib.request
    import numpy as np

    sys.path.insert(0, str(REPO))
    from tony_tpu.router import FleetRouter

    # the PR 2 bench shape (d256/L4, chunk 64): heavy enough that the
    # REPLICAS are the measured bottleneck. At toy shapes (d128) a
    # single replica plus the router/load-generator saturate the whole
    # host and both arms measure the client, not the fleet; and the
    # prefix-COPY path only beats recomputing prefill once the model is
    # this large (docs/performance.md "Fleet serving").
    slots, max_len, chunk = 6, 512, 64
    n_requests, max_new = 64, 8
    trials = 3      # best-of per throughput arm: short walls on a shared
    #                 2-core host swing; the max is the capacity
    # enough distinct templates that rendezvous hashing balances them
    # over 2-3 replicas (6 keys over 2 bins can land 5/1; 12 rarely do)
    templates = 12
    # per-replica trie budget: 2/3 of the template working set (12
    # templates x 4 chunks = 48 blocks): an affinity-routed FLEET's
    # per-replica share (~24 blocks) fits with headroom, while a single
    # replica — or a randomly-routed fleet whose every replica sees
    # every template — churns all 48 through LRU eviction and recomputes
    # 256-token prefills. Fleet serving scales cache capacity, not just
    # compute. (Exact-fit budgets thrash: ref-pinned in-use paths block
    # eviction, so size the fitting arm with slack.)
    cache_blocks = 32

    def serve_args(blocks: int) -> list[str]:
        out = [
            sys.executable, "-m", "tony_tpu.cli.main", "serve",
            "--port", "0", "--host", "127.0.0.1",
            "--vocab", "2048", "--d-model", "256", "--n-layers", "4",
            "--n-heads", "8", "--d-ff", "1024", "--dtype", "float32",
            "--seed", "0", "--slots", str(slots),
            "--max-len", str(max_len), "--block-size", "16",
            "--prefill-chunk", str(chunk), "--drain-timeout-s", "2",
        ]
        if blocks:
            out += ["--prefix-cache-blocks", str(blocks)]
        return out

    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)      # each replica is a single-device server
    ncpu = os.cpu_count() or 2
    n_fleet = 3 if ncpu >= 3 else 2

    class Replica:
        def __init__(self, name, core: int, blocks: int):
            self.name = name
            self.proc = subprocess.Popen(
                serve_args(blocks), cwd=REPO, env=env, text=True,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
            try:
                os.sched_setaffinity(self.proc.pid, {core % ncpu})
            except OSError:
                pass        # affinity is best-effort off-Linux
            self.port = None

        def await_ready(self, timeout=180.0):
            deadline = time.time() + timeout
            line = ""
            while self.port is None and time.time() < deadline:
                line = self.proc.stdout.readline()
                m = _re.search(r"http://[\d.]+:(\d+)", line or "")
                if m:
                    self.port = int(m.group(1))
            assert self.port, f"{self.name} never printed its port: {line}"
            # drain stdout on a thread so the serve process never blocks
            # on a full pipe
            threading.Thread(target=self.proc.stdout.read,
                             daemon=True).start()
            while time.time() < deadline:
                try:
                    with urllib.request.urlopen(
                            self.base_url + "/healthz", timeout=2) as r:
                        if r.status == 200:
                            return
                except Exception:
                    time.sleep(0.2)
            raise AssertionError(f"{self.name} never became healthy")

        @property
        def base_url(self):
            return f"http://127.0.0.1:{self.port}"

        def stats(self):
            with urllib.request.urlopen(self.base_url + "/stats",
                                        timeout=10) as r:
                return json.loads(r.read().decode())

        def stop(self):
            self.proc.terminate()
            try:
                self.proc.wait(timeout=15)
            except subprocess.TimeoutExpired:
                self.proc.kill()

    rng = np.random.default_rng(5)
    bodies = [rng.integers(0, 2048, size=4 * chunk, dtype=np.int32)
              for _ in range(templates)]
    prompts = [
        np.concatenate([bodies[i % templates],
                        rng.integers(0, 2048, size=4 + i % 9,
                                     dtype=np.int32)]).tolist()
        for i in range(n_requests)
    ]

    def warm(rep):
        """Compile every program shape the timed pass will hit (batched
        admission pads rows to powers of two: drive slots-wide bursts)
        WITHOUT seeding the prefix trie (cache_prompt off)."""
        def one(i):
            body = json.dumps({
                "prompt": rng.integers(0, 2048,
                                       size=2 * chunk + i).tolist(),
                "max_new_tokens": 8, "cache_prompt": False}).encode()
            req = urllib.request.Request(rep.base_url + "/generate",
                                         data=body)
            with urllib.request.urlopen(req, timeout=300) as r:
                r.read()
        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(2 * slots)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)

    def fresh_fleet(n, blocks=0):
        """A pass gets FRESH replica processes: each pass's prefix tries
        start cold, so reuse fractions compare routing policies, not
        which pass inherited a warm trie."""
        reps = [Replica(f"replica:{i}", core=i, blocks=blocks)
                for i in range(n)]
        for r in reps:
            r.await_ready()
        warmers = [threading.Thread(target=warm, args=(r,)) for r in reps]
        for t in warmers:
            t.start()
        for t in warmers:
            t.join(timeout=600)
        return reps

    def run_pass(reps, *, affinity, schedule, prepass=False):
        # spill_queue_depth: a sticky replica 3 slot-widths deep in
        # backlog spills to its rendezvous runner-up — affinity is worth
        # a queued beat, not an unbounded pile-up behind one replica.
        # Generous probe timeout + eject_after: a saturated pinned core
        # answers /healthz slowly, and this harness must not grade
        # health-probe churn.
        router = FleetRouter(
            [(r.name, "127.0.0.1", r.port) for r in reps],
            prefill_chunk=chunk, affinity=affinity,
            health_interval_s=0.25, spill_queue_depth=3 * slots,
            eject_after=4, probe_timeout_s=5.0, seed=0)
        router.start()

        def fire(sched):
            results: dict[int, object] = {}
            t_done: dict[int, float] = {}

            def call(i, at):
                time.sleep(max(0.0, t0 + at - time.time()))
                try:
                    results[i] = router.generate(prompts[i],
                                                 max_new_tokens=max_new,
                                                 timeout_s=600)
                    t_done[i] = time.time()
                except Exception as exc:
                    results[i] = exc
            threads = [threading.Thread(target=call, args=(i, at))
                       for i, at in enumerate(sched)]
            t0 = time.time()
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=900)
            failed = [i for i, r in results.items()
                      if not isinstance(r, dict)]
            assert not failed, f"fleet pass dropped requests: {failed}"
            return results, t_done, t0

        if prepass:
            # un-timed steady-state pass: populate each trie THE WAY THIS
            # ROUTING POLICY populates it, so the timed pass measures
            # steady state instead of cold-trie insert costs
            fire([0.0] * len(schedule))
        before = {r.name: (r.stats(), _scrape_ttft_hist(r.base_url))
                  for r in reps}
        results, t_done, t0 = fire(schedule)
        wall = max(t_done.values()) - t0
        tokens = sum(len(r["tokens"]) for r in results.values())
        computed = reused = 0
        ttft_fleet = None
        for r in reps:
            st_b, h_b = before[r.name]
            st_a, h_a = r.stats(), _scrape_ttft_hist(r.base_url)
            computed += (st_a["prefill_tokens_computed"]
                         - st_b["prefill_tokens_computed"])
            reused += (st_a["prefill_tokens_reused"]
                       - st_b["prefill_tokens_reused"])
            delta = _hist_delta(h_b, h_a)
            if ttft_fleet is None:
                ttft_fleet = delta
            else:
                ttft_fleet.merge(delta)
        ttft_p99 = ttft_fleet.quantile(0.99) if ttft_fleet else 0.0
        st = router.stats()
        router.shutdown()
        return {
            "wall_s": round(wall, 3),
            "tokens_per_sec": round(tokens / wall, 1),
            "useful_tokens": tokens,
            "prefill_reused_frac": round(
                reused / max(1, computed + reused), 4),
            "ttft_p99_s": round(ttft_p99, 4),
            "affinity_hit_ratio": st["affinity"]["hit_ratio"],
            "retries": sum(rep["retries"]
                           for rep in st["replicas"].values()),
            "shed_429": sum(rep["shed"]
                            for rep in st["replicas"].values()),
        }

    def closed_loop_capacity(reps, concurrency):
        """Arm capacity at a BOUNDED concurrency (2 slot-widths per
        replica): a classic K-worker closed loop, least-loaded so the
        work spreads. An all-at-once burst would measure the
        deep-backlog thrash regime (64 handler threads against a pinned
        core), not capacity."""
        router = FleetRouter(
            [(r.name, "127.0.0.1", r.port) for r in reps],
            prefill_chunk=chunk, affinity=True,
            spill_queue_depth=3 * slots, eject_after=4,
            probe_timeout_s=5.0, seed=0)
        it = iter(range(n_requests))
        lock = threading.Lock()
        tokens = [0]

        def worker():
            while True:
                with lock:
                    i = next(it, None)
                if i is None:
                    return
                resp = router.generate(prompts[i], max_new_tokens=max_new,
                                       timeout_s=600)
                with lock:
                    tokens[0] += len(resp["tokens"])
        t0 = time.time()
        threads = [threading.Thread(target=worker)
                   for _ in range(concurrency)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=900)
        wall = time.time() - t0
        router.shutdown()
        return tokens[0] / wall

    # ---- throughput scaling (cache ON — the production path) --------
    # Separate replica PROCESSES per arm so each arm's prefix tries
    # evolve under its own policy: the single arm's one replica churns
    # the whole template working set through its half-sized trie; the
    # affinity-routed fleet holds it collectively. Arms alternate,
    # best-of-`trials` each — adjacent in time like the mnist bench's
    # A/B pairs, so host noise hits both arms alike. The single-arm
    # replica shares core 0 with one fleet replica; only one arm is
    # ever driven at a time (an idle serve loop costs ~nothing).
    single_arm = fresh_fleet(1, blocks=cache_blocks)
    fleet = fresh_fleet(n_fleet, blocks=cache_blocks)
    try:
        # capacity = best-of-`trials` closed-loop measurements per arm,
        # concurrency matched to each arm's slot budget, arms alternated
        # so host noise hits both alike. The SPEEDUP is the capacity
        # ratio: per-pass open-loop throughput on this class of host
        # swings ~3x run to run (scheduler placement against the pinned
        # replicas), which defeated every arrival-rate calibration
        # scheme — closed loops self-pace and need none.
        # one discarded closed-loop pass per arm brings each arm's tries
        # to ITS policy's steady state before anything is measured
        closed_loop_capacity(single_arm, concurrency=2 * slots)
        closed_loop_capacity(fleet, concurrency=2 * slots * n_fleet)
        single_runs, fleet_runs = [], []
        for _ in range(trials):
            single_runs.append(closed_loop_capacity(
                single_arm, concurrency=2 * slots))
            fleet_runs.append(closed_loop_capacity(
                fleet, concurrency=2 * slots * n_fleet))
        cap_single = max(single_runs)
        cap_fleet = max(fleet_runs)
        # open-loop CAPACITY arms (ISSUE 16): the same capacity
        # question asked the way traffic actually arrives — seeded
        # Poisson arrivals offered at each arm's OWN measured
        # closed-loop capacity, best-of-`trials`. Per-pass open-loop
        # walls on this host class swing with scheduler placement (the
        # ~3x above), so the enforced ratio here is softer (1.3x) than
        # the closed-loop 1.5x; the closed-loop number stays the
        # headline capacity.
        def open_loop_capacity(reps, cap):
            sched = np.cumsum(rng.exponential(
                scale=max_new / cap, size=n_requests)).tolist()
            return run_pass(reps, affinity=True,
                            schedule=sched)["tokens_per_sec"]
        ol_single_runs, ol_fleet_runs = [], []
        for _ in range(trials):
            ol_single_runs.append(
                open_loop_capacity(single_arm, cap_single))
            ol_fleet_runs.append(open_loop_capacity(fleet, cap_fleet))
        ol_single = max(ol_single_runs)
        ol_fleet = max(ol_fleet_runs)
        # the open-loop (Poisson) passes run at 1.2x the measured FLEET
        # capacity: the single arm is then deeply saturated (the
        # open-loop collapse a lone replica suffers at fleet-rate
        # traffic), the fleet just-saturated — both walls are reported
        interarrival = max_new / (cap_fleet * 1.2)
        schedule = np.cumsum(rng.exponential(
            scale=interarrival, size=n_requests)).tolist()
        single = run_pass(single_arm, affinity=True, schedule=schedule)
        fleet_pass = run_pass(fleet, affinity=True, schedule=schedule)
        # affinity open-loop pass: the fleet's tries are already in the
        # affinity-policy steady state from the capacity trials
        affinity_pass = run_pass(fleet, affinity=True, schedule=schedule,
                                 prepass=True)
    finally:
        for r in single_arm + fleet:
            r.stop()
    fleet = fresh_fleet(n_fleet, blocks=cache_blocks)
    try:
        random_pass = run_pass(fleet, affinity=False, schedule=schedule,
                               prepass=True)
    finally:
        for r in fleet:
            r.stop()

    print(f"# capacity single {cap_single:.0f} {single_runs} | fleet "
          f"{cap_fleet:.0f} {fleet_runs} | open-loop capacity single "
          f"{ol_single_runs} fleet {ol_fleet_runs} | "
          f"open-loop single {single} | "
          f"fleet {fleet_pass} | affinity {affinity_pass} | "
          f"random {random_pass}", file=sys.stderr)
    speedup = round(cap_fleet / cap_single, 3)
    assert speedup > 1.5, (
        f"fleet speedup {speedup} <= 1.5x single replica")
    speedup_open_loop = round(ol_fleet / ol_single, 3)
    assert speedup_open_loop > 1.3, (
        f"open-loop fleet speedup {speedup_open_loop} <= 1.3x single "
        f"replica (single {ol_single_runs}, fleet {ol_fleet_runs})")
    assert (affinity_pass["prefill_reused_frac"]
            > random_pass["prefill_reused_frac"]), (
        "prefix-affinity routing must beat random routing on trie reuse")
    out = {
        "metric": "serving_fleet_speedup_vs_single_replica",
        "value": speedup,
        "unit": "x capacity (closed-loop, concurrency-matched, "
                "best-of-trials per arm)",
        "replicas": n_fleet,
        "slots_per_replica": slots,
        "n_requests": n_requests,
        "templates": templates,
        "max_new_tokens": max_new,
        "prefill_chunk": chunk,
        "poisson_interarrival_s": round(interarrival, 4),
        "one_core_per_replica": True,
        "throughput_trials_per_arm": trials,
        "capacity_single_tokens_per_sec": round(cap_single, 1),
        "capacity_fleet_tokens_per_sec": round(cap_fleet, 1),
        "capacity_single_all_trials": [round(v, 1) for v in single_runs],
        "capacity_fleet_all_trials": [round(v, 1) for v in fleet_runs],
        "speedup_open_loop": speedup_open_loop,
        "capacity_single_open_loop_tokens_per_sec": round(ol_single, 1),
        "capacity_fleet_open_loop_tokens_per_sec": round(ol_fleet, 1),
        "open_loop_capacity_all_trials": {
            "single": [round(v, 1) for v in ol_single_runs],
            "fleet": [round(v, 1) for v in ol_fleet_runs],
        },
        "open_loop_single_replica": single,
        "open_loop_fleet": fleet_pass,
        "prefix_cache_blocks_per_replica": cache_blocks,
        "fleet_affinity": affinity_pass,
        "fleet_random": random_pass,
        "affinity_gain": {
            "reused_frac": [affinity_pass["prefill_reused_frac"],
                            random_pass["prefill_reused_frac"]],
            "ttft_p99_s": [affinity_pass["ttft_p99_s"],
                           random_pass["ttft_p99_s"]],
            "affinity_hit_ratio": affinity_pass["affinity_hit_ratio"],
        },
    }
    print(json.dumps(out))
    return 0


def run_router_ha_bench() -> int:
    """Router-tier HA gate (one JSON line -> PERF.json `router_ha`;
    docs/serving.md "Router tier HA"): a REAL driver gang-launches 2
    serving replicas AND 2 shared-nothing front doors — the `router`
    framework, each executor supervising a real `tony-tpu route` child
    on the task's published port — then
    TONY_TEST_ROUTER_SIGKILL_AT_REQUEST deterministically SIGKILLs
    door 0 on receipt of its Nth front-door POST, mid-burst. Enforced
    rather than reported:

    - **zero failed requests**: every client whose door died re-POSTs
      the same ``request_id`` on the surviving door and completes (the
      replica-journaled ``req:<id>`` progress key makes resume
      portable across doors);
    - **byte-identical responses**: every rerouted request's tokens
      equal a fresh undisturbed run of the same prompt — buffered AND
      streamed (the SSE relay of the same prompt yields the same
      token sequence);
    - **affinity preserved**: both doors, probed live, route the same
      keyed prompt to the same replica (shared-nothing rendezvous
      agreement, after one door was relaunched);
    - **the driver relaunches the dead door** on its restart budget
      (journal: router:0 restarts == 1, replicas untouched) and the
      relaunched door serves.

    Router death is a latency cost: the reported value is the p50
    latency of the requests that lost their front door over the p50 of
    the undisturbed ones."""
    import signal as _signal
    import statistics as _stats
    import tempfile as _tempfile
    import threading
    import urllib.request

    sys.path.insert(0, str(REPO))
    import numpy as np

    from tony_tpu import constants as c
    from tony_tpu.client import TonyClient
    from tony_tpu.conf import TonyConf
    from tony_tpu.events.driver_journal import load_state
    from tony_tpu.router import DriverDiscovery

    e = dict(vocab=64, d_model=16, n_layers=1, n_heads=2, d_ff=32,
             slots=4, max_len=96, block_size=4, prefill_chunk=8)
    MAX_NEW = 8
    STEP_DELAY_MS = 30      # ~0.25s of decode per request: the SIGKILL
    #                         catches real relays in flight
    N_REQUESTS = 48
    KILL_AT = 10            # door 0 dies on its 10th front-door POST

    td = _tempfile.mkdtemp(prefix="tony-router-ha-bench-")
    root = Path(td)
    serve_cmd = (
        f"{sys.executable} -m tony_tpu.cli.main serve "
        "--port $TONY_SERVE_PORT --host 127.0.0.1 "
        f"--vocab {e['vocab']} --d-model {e['d_model']} "
        f"--n-layers {e['n_layers']} --n-heads {e['n_heads']} "
        f"--d-ff {e['d_ff']} --dtype float32 --seed 0 "
        f"--slots {e['slots']} --max-len {e['max_len']} "
        f"--block-size {e['block_size']} "
        f"--prefill-chunk {e['prefill_chunk']} "
        "--max-queue 64 --drain-timeout-s 5")
    route_cmd = (
        f"{sys.executable} -m tony_tpu.cli.main route "
        "--port $TONY_SERVE_PORT --host 127.0.0.1 "
        "--job-dir $TONY_JOB_DIR --role replica "
        f"--prefill-chunk {e['prefill_chunk']} "
        "--health-interval-s 0.3 --probe-timeout-s 5.0 "
        "--discovery-min-interval-s 0.5 --stats-every 2 "
        "--drain-timeout-s 10")
    conf = TonyConf({
        "tony.staging.dir": str(root / "staging"),
        "tony.history.location": str(root / "history"),
        "tony.history.intermediate": str(root / "history/intermediate"),
        "tony.history.finished": str(root / "history/finished"),
        "tony.am.monitor-interval-ms": 100,
        "tony.application.framework": "serving",
        "tony.task.registration-poll-interval-ms": 100,
        "tony.task.heartbeat-interval-ms": 250,
        "tony.serving.healthz-interval-ms": 200,
        "tony.replica.instances": 2,
        "tony.replica.command": serve_cmd,
        "tony.replica.max-restarts": 1,
        "tony.router.instances": 2,
        "tony.router.command": route_cmd,
        "tony.router.framework": "router",
        "tony.router.max-restarts": 2,
        # the injection env reaches every child; only route processes
        # read it, and only the one whose TONY_TASK_INDEX matches dies.
        # NOTE: the RELAUNCHED door 0 carries the same spec — the
        # post-burst probes below stay well under KILL_AT posts.
        "tony.execution.env": " ".join([
            f"PYTHONPATH={REPO}", "JAX_PLATFORMS=cpu",
            f"{c.TEST_SERVING_STEP_DELAY_MS}={STEP_DELAY_MS}",
            f"{c.TEST_ROUTER_SIGKILL_AT_REQUEST}=0#{KILL_AT}"]),
    })
    t_bench = time.time()
    client = TonyClient(conf, poll_interval_s=0.2)
    client.submit()
    job_dir = Path(client.job_dir)
    disco_router = DriverDiscovery(str(job_dir), role="router",
                                   token=client.token)
    disco_replica = DriverDiscovery(str(job_dir), role="replica",
                                    token=client.token)

    def endpoints(disco):
        try:
            return {tid: (host, port) for tid, host, port in disco()}
        except Exception:
            return {}

    def post(port, payload, timeout=120):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate",
            data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return json.loads(r.read().decode())

    def sse_tokens(port, payload, timeout=120):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate?stream=true",
            data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        toks, final = [], None
        with urllib.request.urlopen(req, timeout=timeout) as r:
            for raw in r:
                line = raw.decode().strip()
                if not line.startswith("data: "):
                    continue
                frame = json.loads(line[len("data: "):])
                if "finish_reason" in frame:
                    final = frame
                else:
                    toks.extend(frame.get("tokens", []))
        return toks, final

    rng = np.random.default_rng(17)
    chunk = e["prefill_chunk"]
    template = rng.integers(0, e["vocab"], size=2 * chunk,
                            dtype=np.int32)
    prompts = [np.concatenate(
        [template, rng.integers(0, e["vocab"], size=1 + i % 5,
                                dtype=np.int32)]).tolist()
        for i in range(N_REQUESTS)]

    results: dict[int, object] = {}
    latencies: dict[int, float] = {}
    retried: set[int] = set()
    marks: dict[str, float] = {}
    try:
        deadline = time.time() + 240
        doors = reps = {}
        while time.time() < deadline:
            doors = endpoints(disco_router)
            reps = endpoints(disco_replica)
            if len(doors) == 2 and len(reps) == 2:
                break
            time.sleep(0.3)
        assert len(doors) == 2, f"router tier never fully up: {doors}"
        assert len(reps) == 2, f"replica fleet never fully up: {reps}"
        door_ports = [doors["router:0"][1], doors["router:1"][1]]
        dead_port = door_ports[0]

        # ---- the burst: round-robined across both doors; door 0
        # SIGKILLs itself on its KILL_AT-th POST. A client whose door
        # died (mid-flight or refused) re-POSTs the SAME request_id on
        # the other door; alternation also covers the relaunch window.
        def call(i):
            payload = {"prompt": prompts[i], "max_new_tokens": MAX_NEW,
                       "request_id": f"burst-{i}"}
            t0 = time.time()
            attempt, last = 0, None
            while time.time() - t0 < 180:
                port = door_ports[(i + attempt) % 2]
                try:
                    results[i] = post(port, payload)
                    latencies[i] = time.time() - t0
                    return
                except Exception as exc:
                    last = exc
                    retried.add(i)
                    if "died" not in marks:
                        marks["died"] = time.time()
                    attempt += 1
                    time.sleep(0.05)
            results[i] = last

        threads = []
        t_burst = time.time()
        for i in range(N_REQUESTS):
            th = threading.Thread(target=call, args=(i,))
            th.start()
            threads.append(th)
            time.sleep(0.03)
        for th in threads:
            th.join(timeout=300)
        marks["burst_done"] = time.time()

        # ---- gate 1: zero failed requests
        failed = {i: r for i, r in results.items()
                  if not isinstance(r, dict)}
        assert not failed, (
            f"{len(failed)} requests failed across the door kill: "
            f"{dict(list(failed.items())[:3])}")
        assert len(results) == N_REQUESTS
        assert retried, (
            "the SIGKILL never disrupted a request — the burst "
            "finished before door 0's kill threshold?")
        assert "died" in marks

        # ---- gate 2: the driver relaunches the dead door, and it
        # serves (the route child exited on SIGKILL; the adapter's
        # nonzero exit spent one unit of router:0's restart budget)
        relaunched_port = None
        deadline = time.time() + 180
        while time.time() < deadline:
            doors = endpoints(disco_router)
            if "router:0" in doors and doors["router:0"][1]:
                try:
                    r0 = post(doors["router:0"][1],
                              {"prompt": prompts[0],
                               "max_new_tokens": MAX_NEW}, timeout=30)
                    if isinstance(r0, dict) and r0.get("tokens"):
                        relaunched_port = doors["router:0"][1]
                        marks["relaunched"] = time.time()
                        break
                except Exception:
                    pass
            time.sleep(0.5)
        assert relaunched_port is not None, (
            "driver never relaunched the SIGKILLed door")
        survivor = door_ports[1]

        # ---- gate 3: byte-identical responses for every rerouted
        # request — buffered re-runs on the survivor, plus the SSE
        # relay of the same prompt on BOTH doors (streams included)
        checked = sorted(retried)[:12]
        for i in checked:
            ref = post(survivor, {"prompt": prompts[i],
                                  "max_new_tokens": MAX_NEW,
                                  "request_id": f"ref-{i}"})
            assert ref["tokens"] == results[i]["tokens"], (
                f"request {i} rerouted mid-kill diverged: "
                f"{results[i]['tokens']} vs fresh {ref['tokens']}")
            assert ref["finish_reason"] == results[i]["finish_reason"]
        s_toks, s_final = sse_tokens(
            survivor, {"prompt": prompts[checked[0]],
                       "max_new_tokens": MAX_NEW})
        r_toks, r_final = sse_tokens(
            relaunched_port, {"prompt": prompts[checked[0]],
                              "max_new_tokens": MAX_NEW})
        assert s_toks == r_toks == results[checked[0]]["tokens"], (
            f"streamed relays diverged: {s_toks} vs {r_toks} vs "
            f"buffered {results[checked[0]]['tokens']}")
        assert s_final and s_final["finish_reason"] == "length"
        assert r_final and r_final["finish_reason"] == "length"

        # ---- gate 4: live affinity agreement — both doors (one of
        # them freshly relaunched with a cold replica view) route the
        # same keyed prompt to the same replica, with zero coordination
        probes = [np.concatenate(
            [rng.integers(0, e["vocab"], size=2 * chunk,
                          dtype=np.int32),
             rng.integers(0, e["vocab"], size=2, dtype=np.int32)]
            ).tolist() for _ in range(3)]
        disagreements = []
        for k, probe in enumerate(probes):
            a = post(survivor, {"prompt": probe,
                                "max_new_tokens": 1})
            b = post(relaunched_port, {"prompt": probe,
                                       "max_new_tokens": 1})
            if a.get("replica") != b.get("replica"):
                disagreements.append((k, a.get("replica"),
                                      b.get("replica")))
        assert not disagreements, (
            f"shared-nothing doors disagreed on affinity owners: "
            f"{disagreements}")

        # ---- forensics: the kill spent router:0's budget, nothing
        # else moved; the survivor harvested journaled progress
        state = load_state(job_dir / c.DRIVER_JOURNAL_FILE)
        r0_restarts = state.tasks["router:0"].restarts
        assert r0_restarts == 1, (
            f"router:0 restarts {r0_restarts} != 1")
        other = {tid: t.restarts for tid, t in state.tasks.items()
                 if tid != "router:0" and t.restarts}
        assert not other, f"collateral restarts: {other}"
        with urllib.request.urlopen(
                f"http://127.0.0.1:{survivor}/stats", timeout=10) as r:
            surv_stats = json.loads(r.read().decode())
        assert surv_stats["failed"] == 0, surv_stats
    finally:
        proc = client._driver_proc
        if proc is not None and proc.poll() is None:
            try:
                os.killpg(proc.pid, _signal.SIGTERM)
            except (ProcessLookupError, PermissionError):
                pass
            try:
                proc.wait(timeout=30)
            except Exception:
                try:
                    os.killpg(proc.pid, _signal.SIGKILL)
                except (ProcessLookupError, PermissionError):
                    pass

    smooth = [latencies[i] for i in latencies if i not in retried]
    disrupted = [latencies[i] for i in retried if i in latencies]
    p50_smooth = _stats.median(smooth)
    p50_disrupted = _stats.median(disrupted)
    out = {
        "metric": "router_ha_latency_cost",
        "value": round(p50_disrupted / p50_smooth, 2),
        "unit": "x p50 latency for requests that lost their front door "
                "(vs undisturbed; zero failed)",
        "doors": 2,
        "replicas": 2,
        "requests": N_REQUESTS,
        "failed_requests": 0,
        "rerouted_requests": len(retried),
        "byte_identical_reroutes_checked": len(checked),
        "streams_byte_identical": True,
        "affinity_agreement_probes": len(probes),
        "kill_at_request": KILL_AT,
        "router0_restarts": 1,
        "collateral_restarts": 0,
        "survivor_resumed_tokens": surv_stats.get("resumed_tokens", 0),
        "survivor_failed": 0,
        "p50_latency_s_undisturbed": round(p50_smooth, 3),
        "p50_latency_s_rerouted": round(p50_disrupted, 3),
        "p99_latency_s_rerouted": round(
            sorted(disrupted)[int(0.99 * (len(disrupted) - 1))], 3),
        "door_relaunch_s": round(
            marks["relaunched"] - marks["died"], 1),
        "burst_wall_s": round(marks["burst_done"] - t_burst, 1),
        "wall_s": round(time.time() - t_bench, 1),
    }
    print(json.dumps(out))
    return 0


def run_serving_spec_bench() -> int:
    """Speculative decoding inside continuous batching + multi-model
    hot-swap (one JSON line -> PERF.json `speculative_serving`).

    Arm A/B — spec off vs on, REAL acceptance: a target and a 12x-
    smaller draft are trained on the same Markov corpus (the bench_
    transformer speculative methodology: same-distribution alignment,
    not a modeled parameter), then the identical request burst serves
    through a plain SlotServer and a draft-speculating one. Gates:
    byte-identical completions (speculation is never a numerics
    change), >= 1.3x tokens/s, acceptance histogram populated.

    Arm C — multi-model + roll hot-swap: a serve subprocess registers
    TWO models, takes a concurrent two-model burst, and is SIGTERM-
    drained mid-burst (the PR 7 roll path) and relaunched with one
    model's checkpoint SWAPPED under the same name + the same journal
    dir. Clients retry through the roll; the gate is zero failed
    requests and both models serving after the swap."""
    import re as _re
    import signal as _signal
    import threading
    import urllib.request

    sys.path.insert(0, str(REPO))
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench_transformer import _markov_batch
    from tony_tpu.models import transformer
    from tony_tpu.models.generate import prepare_decode
    from tony_tpu.models.serving import Request, SlotServer
    from tony_tpu.parallel import MeshSpec, build_mesh
    from tony_tpu.train import create_train_step

    V = 1024
    # d512/L6: deep enough into the weight-streaming regime that the
    # (gamma+1)-wide verify genuinely amortizes the stream even on CPU
    # (at d384 the verify is compute-bound and the measured speedup sat
    # within noise of the 1.3x gate; at d512 the acceptance-0 floor
    # alone measures ~0.49x, putting full-acceptance headroom near 2x)
    cfg = transformer.TransformerConfig(
        vocab_size=V, d_model=512, n_layers=6, n_heads=8, n_kv_heads=8,
        d_ff=2048, max_seq_len=256, dtype=jnp.float32)
    draft_cfg = transformer.TransformerConfig(
        vocab_size=V, d_model=128, n_layers=2, n_heads=4, n_kv_heads=4,
        d_ff=512, max_seq_len=256, dtype=jnp.float32)
    rng = np.random.default_rng(0)
    # 0.9-primary chain: predictable enough that a trained draft's
    # greedy continuation tracks the trained target's (the condition a
    # production draft/target pair has), noisy enough that nothing is
    # memorized verbatim
    succ = rng.integers(0, V, (V, 2)).astype(np.int32)

    def markov(r, batch, seq):
        x = np.empty((batch, seq + 1), np.int32)
        x[:, 0] = r.integers(0, V, batch)
        for t in range(seq):
            pick = r.random(batch) < 0.9
            x[:, t + 1] = np.where(pick, succ[x[:, t], 0],
                                   succ[x[:, t], 1])
        return x[:, :-1], x[:, 1:]

    def train(model_cfg, steps, seed):
        mesh = build_mesh(MeshSpec(data=-1, fsdp=1))
        bundle = create_train_step(model_cfg, mesh,
                                   key=jax.random.PRNGKey(seed))
        params, opt = bundle.params, bundle.opt_state
        r = np.random.default_rng(seed)
        m = None
        for chunk in range(steps // 50):
            for _ in range(50):
                tk, tg = markov(r, 8, 64)
                params, opt, m = bundle.step_fn(
                    params, opt, jnp.asarray(tk), jnp.asarray(tg))
            float(m["loss"])            # sync per 50-step window
        return params, float(m["loss"])

    t0 = time.time()
    tp_raw, t_loss = train(cfg, 300, seed=0)
    dp_raw, d_loss = train(draft_cfg, 300, seed=1)
    train_s = time.time() - t0
    tp = prepare_decode(tp_raw, cfg)
    dp = prepare_decode(dp_raw, draft_cfg)
    del tp_raw, dp_raw

    # held-out prompts from the same chain
    er = np.random.default_rng(99)
    prompts = [markov(er, 1, 32)[0][0] for _ in range(24)]
    budget = 48

    def serve_arm(draft=None, spec_gamma=0):
        kw = {}
        if draft is not None:
            # gamma ceiling 8: at the measured ~0.99 acceptance the
            # autotuner rides the ceiling, and the wider window is
            # where the weight-stream amortization pays (knob sweep:
            # 1.58x at gamma_max 4 -> 2.2x at 8). pipeline_depth 1:
            # speculation runs the sync (EOS-style) scheduler, where a
            # freed slot waits a full pipeline lag for re-admission —
            # at ~5 tokens/round that lag is whole requests, and CPU
            # compute is serial anyway so the deeper runway buys
            # nothing (plain predictive serving keeps its default).
            kw = dict(draft=draft, draft_cfg=draft_cfg,
                      spec_gamma=spec_gamma, spec_gamma_max=8,
                      pipeline_depth=1)
        srv = SlotServer(tp, cfg, slots=8, max_len=128, block_size=8,
                         prefill_chunk=32, **kw)

        def one_pass():
            reqs = [Request(prompt=p, max_new_tokens=budget)
                    for p in prompts]
            for r in reqs:
                srv.submit(r)
            t0 = time.time()
            done = srv.run_until_drained()
            wall = time.time() - t0
            toks = {i: done[r.id].tokens for i, r in enumerate(reqs)}
            n = sum(len(t) for t in toks.values())
            return n / wall, wall, toks

        one_pass()                      # compile + autotune warm-up
        best, best_wall, toks = 0.0, 0.0, None
        for _ in range(3):
            rate, wall, t = one_pass()
            if rate > best:
                best, best_wall, toks = rate, wall, t
        st = srv.stats()
        srv.shutdown()
        return {"tokens_per_sec": round(best, 1),
                "wall_s": round(best_wall, 3)}, toks, st

    plain, toks_plain, _ = serve_arm()
    spec, toks_spec, spec_st = serve_arm(draft=dp)
    assert toks_plain == toks_spec, (
        "speculation changed completions — the byte-identity contract "
        "is broken")
    speedup = round(spec["tokens_per_sec"] / plain["tokens_per_sec"], 3)
    sstats = spec_st["speculative"]
    assert sstats["acceptance"]["count"] > 0, (
        "acceptance histogram empty — the gate has nothing to stand on")
    assert speedup >= 1.3, (
        f"speculative serving speedup {speedup} < 1.3x gate "
        f"(acceptance_ewma {sstats['acceptance_ewma']})")
    # the honest worst case alongside: a random draft (~0 acceptance)
    # pays gamma draft steps per correction token — still byte-exact,
    # gamma pinned so the autotuner can't rescue the number
    dp0 = prepare_decode(
        jax.jit(lambda k: transformer.init(k, draft_cfg))(
            jax.random.PRNGKey(7)), draft_cfg)
    floor, toks_floor, floor_st = serve_arm(draft=dp0, spec_gamma=4)
    assert toks_floor == toks_plain, "floor arm broke byte-identity"

    # ---- arm C: multi-model serve + roll hot-swap, zero failed ----
    import socket

    def free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    def spawn_serve(port, trace_dir, main_spec):
        args = [sys.executable, "-m", "tony_tpu.cli.main", "serve",
                "--port", str(port), "--vocab", "256",
                "--d-model", "64", "--n-layers", "2", "--n-heads", "4",
                "--d-ff", "128", "--dtype", "float32",
                "--slots", "4", "--max-len", "64", "--block-size", "4",
                "--prefill-chunk", "8",
                "--model", f"main={main_spec}",
                "--model", "alt=random:7",
                "--trace-dir", str(trace_dir),
                "--drain-timeout-s", "60"]
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.Popen(args, env=env, stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT, text=True)
        deadline = time.time() + 240
        while time.time() < deadline:
            line = proc.stdout.readline()
            if _re.search(r"http://[\d.]+:\d+", line or ""):
                threading.Thread(target=proc.stdout.read,
                                 daemon=True).start()
                return proc
        raise RuntimeError("serve never became ready")

    with tempfile.TemporaryDirectory(prefix="tony-spec-bench-") as td:
        port = free_port()
        proc = spawn_serve(port, td, "random:0")
        n_req, failed, succeeded = 24, [], []
        client_retries = [0]
        lock = threading.Lock()

        def call(i):
            model = "main" if i % 2 == 0 else "alt"
            body = json.dumps({
                "prompt": [(i * 7 + j) % 256 for j in range(6)],
                "max_new_tokens": 8, "model": model,
                "timeout_s": 240}).encode()
            deadline = time.time() + 240
            while time.time() < deadline:
                try:
                    req = urllib.request.Request(
                        f"http://127.0.0.1:{port}/generate", data=body)
                    with urllib.request.urlopen(req, timeout=240) as r:
                        json.loads(r.read())
                        with lock:
                            succeeded.append(i)
                        return
                except Exception:
                    # the roll window: refused/5xx/cut mid-request —
                    # the router would retry elsewhere; the bench
                    # client retries the same (only) endpoint
                    with lock:
                        client_retries[0] += 1
                    time.sleep(0.3)
            with lock:
                failed.append(i)

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(n_req)]
        t_roll0 = time.time()
        for i, t in enumerate(threads):
            t.start()
            if i == n_req // 3:
                # mid-burst: the roll (PR 7 semantics = SIGTERM drain;
                # in-flight finish, then the process exits cleanly)
                proc.send_signal(_signal.SIGTERM)
        proc.wait(timeout=300)
        # relaunch with main's checkpoint SWAPPED under the same name,
        # same journal dir (recovery finishes anything the drain cut)
        proc2 = spawn_serve(port, td, "random:5")
        for t in threads:
            t.join(timeout=300)
        roll_wall = time.time() - t_roll0
        # both models serve after the swap
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/stats", timeout=10) as r:
            st2 = json.loads(r.read())
        proc2.terminate()
        proc2.wait(timeout=60)
        assert not failed, f"roll dropped requests: {failed}"
        assert len(succeeded) == n_req
        assert set(st2["models"]) == {"main", "alt"}, st2.get("models")

    out = {
        "metric": "speculative_serving_speedup",
        "value": speedup,
        "unit": "x tokens/s vs spec-off serving",
        "target_params_m": round(
            transformer.num_params(tp.params) / 1e6, 1),
        "draft_params_m": round(
            transformer.num_params(dp.params) / 1e6, 1),
        "trained_on": f"markov chain V={V} (0.9 primary), 300 steps "
                      f"each (losses {t_loss:.3f} / {d_loss:.3f}, "
                      f"{train_s:.0f}s)",
        "byte_identical": True,
        "slots": 8,
        "n_requests": len(prompts),
        "budget": budget,
        "plain": plain,
        "speculative": spec,
        "gamma": sstats["gamma"],
        "gamma_autotuned": not sstats["gamma_pinned"],
        "acceptance_ewma": sstats["acceptance_ewma"],
        "accepted_tokens": sstats["accepted_tokens"],
        "proposed_tokens": sstats["proposed_tokens"],
        "verify_rounds": sstats["rounds"],
        "acceptance_zero_floor": {
            **floor,
            "ratio_vs_plain": round(
                floor["tokens_per_sec"] / plain["tokens_per_sec"], 3),
            "acceptance_ewma": floor_st["speculative"]["acceptance_ewma"],
        },
        "multi_model": {
            "requests": n_req,
            "failed": 0,
            "client_retries_through_roll": client_retries[0],
            "roll_wall_s": round(roll_wall, 1),
            "models_after_swap": sorted(st2["models"]),
            "swapped": "main random:0 -> random:5 (same name, same "
                       "journal dir, SIGTERM drain between)",
        },
        "num_devices": jax.device_count(),
    }
    print(json.dumps(out))
    return 0


def run_serving_robustness_bench(chaos: bool) -> int:
    """Overload + chaos serving benchmark (one JSON line; see module
    docstring). The submission burst is 64 requests against 8 slots and
    an 8-deep queue, so shedding MUST engage; with ``chaos`` the server
    additionally eats seeded injected dispatch failures at 5% per
    scheduling turn and must recover via SlotServer.reset() under the
    ServeApp restart budget. The bench enforces the acceptance
    invariants (zero hung waiters, every request terminates, recovery
    within budget) rather than just reporting them."""
    import statistics as _stats
    import threading
    import time as _time

    sys.path.insert(0, str(REPO))
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tony_tpu import constants as c
    from tony_tpu.models import transformer
    from tony_tpu.models.serving import (
        Completion, QueueFullError, Request, SlotServer,
    )

    cfg = transformer.TransformerConfig(
        vocab_size=2048, d_model=256, n_layers=4, n_heads=8, n_kv_heads=4,
        d_ff=1024, max_seq_len=512,
        dtype=jnp.bfloat16 if jax.default_backend() == "tpu"
        else jnp.float32,
    )
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    slots, max_len, max_queue = 8, 512, 8
    n_requests = 64
    fail_rate = 0.05 if chaos else 0.0
    prompt_lens = [16, 48, 96]
    budgets = [32, 64, 48, 24]
    rng = np.random.default_rng(3)
    prompts = [
        rng.integers(0, cfg.vocab_size, size=prompt_lens[i % len(prompt_lens)],
                     dtype=np.int32)
        for i in range(n_requests)
    ]

    # compile every program variant BEFORE injection turns on (the chaos
    # knobs are read at construction): the measured pass then exercises
    # scheduling + recovery, not XLA compilation
    warm = SlotServer(params, cfg, slots=slots, max_len=max_len,
                      block_size=16, prefill_chunk=64)
    for i in range(slots):
        warm.submit(Request(prompt=prompts[i], max_new_tokens=8))
    warm.run_until_drained()
    del warm    # the jit cache is what the warm-up buys; its KV ring
    #             would otherwise double serving HBM for the whole run

    knobs = {c.TEST_SERVING_DISPATCH_FAIL_RATE: str(fail_rate),
             c.TEST_SERVING_CHAOS_SEED: "1234"}
    saved = {k: os.environ.get(k) for k in knobs}
    os.environ.update(knobs)
    try:
        from tony_tpu.cli.serve import ServeApp

        srv = SlotServer(params, cfg, slots=slots, max_len=max_len,
                         block_size=16, prefill_chunk=64,
                         max_queue=max_queue)
        app = ServeApp(srv, max_loop_restarts=16, loop_backoff_s=0.05)
        app.start()
        results: dict[int, object] = {}
        latencies: dict[int, float] = {}

        def call(i):
            t0 = _time.time()
            try:
                comp = app.generate(prompts[i],
                                    budgets[i % len(budgets)], timeout=300)
                results[i] = comp
                latencies[i] = _time.time() - t0
            except Exception as e:      # shed / lost / expired
                results[i] = e

        t_start = _time.time()
        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(n_requests)]
        for t in threads:
            t.start()
            # sustained overload, not a one-shot firehose: arrivals spread
            # over ~2.5s against ~10s of service demand, so the queue
            # oscillates around full — some requests shed, most serve —
            # instead of 7/8 of the burst bouncing off a cold queue
            _time.sleep(0.04)
        for t in threads:
            t.join(timeout=600)
        wall = _time.time() - t_start
        hung = sum(t.is_alive() for t in threads)
        app.shutdown()
    finally:
        for k, v in saved.items():
            os.environ.pop(k, None) if v is None else os.environ.update(
                {k: v})

    completed = {i: r for i, r in results.items()
                 if isinstance(r, Completion)}
    shed = sum(isinstance(r, QueueFullError) for r in results.values())
    expired = sum(isinstance(r, TimeoutError) for r in results.values())
    failed = (len(results) - len(completed) - shed - expired)
    goodput_tokens = sum(len(r.tokens) for r in completed.values())
    # the acceptance invariants, enforced: a bench that silently records
    # a hang would grade the exact failure this harness exists to catch
    assert hung == 0, f"{hung} waiters hung"
    assert len(results) == n_requests, "a request vanished without outcome"
    assert app.status != "down", "restart budget exhausted mid-bench"
    if chaos:
        assert srv.chaos_faults_injected >= 1, "chaos never fired"
        assert app.loop_restarts >= 1, "no recovery exercised"
    out = {
        "metric": "serving_robustness_goodput_tokens_per_sec",
        "value": round(goodput_tokens / wall, 1),
        "unit": "tokens/s of COMPLETED requests, chaos+overload included",
        "chaos": chaos,
        "dispatch_fail_rate": fail_rate,
        "chaos_seed": 1234,
        "slots": slots,
        "max_queue": max_queue,
        "submitted": n_requests,
        "completed": len(completed),
        "shed_429": shed,
        "failed_loop_error": failed,
        "expired_or_timed_out": expired,
        "hung_waiters": hung,
        "every_request_terminated": True,
        "p50_latency_s_completed": round(
            _stats.median(latencies.values()), 3) if latencies else None,
        "wall_s": round(wall, 3),
        "chaos_faults_injected": srv.chaos_faults_injected,
        "loop_failures": app.loop_failures,
        "loop_restarts": app.loop_restarts,
        "engine_resets": srv.resets,
        "cancelled": srv.cancelled_requests,
        "num_devices": jax.device_count(),
    }
    print(json.dumps(out))
    return 0


def run_serving_replay_bench() -> int:
    """Request-durability gate (one JSON line -> PERF.json
    `serving_replay`; docs/serving.md "Request durability & replay").
    Three arms, invariants ENFORCED rather than reported:

    A) **Loop-crash replay** (in-process): an uninterrupted run is the
       byte-reference; a second run eats two DETERMINISTIC mid-decode
       loop crashes (TONY_TEST_SERVING_CRASH_AT_BLOCKS) and must
       deliver ZERO failed requests with byte-identical completions,
       with replay recompute bounded by one re-prefill of
       prompt+emitted per replay (the prefix is never re-decoded).
    B) **Fail-fast preserved**: the same crash with replay disabled
       must FAIL the in-flight set (the pre-journal contract) — the
       journal-off path keeps its semantics.
    C) **Fleet SIGKILL failover + journal recovery** (subprocess): two
       TINY serve replicas with file journals behind a FleetRouter;
       one replica is SIGKILLed with requests in flight — zero failed
       requests, byte-identical to an in-process reference, at least
       one resume-carrying failover — and the killed replica
       RESTARTED against the same --trace-dir recovers its journal and
       finishes the orphaned requests (stats replays >= 1,
       attrs.recovered_from in its trace file).
    """
    import re as _re
    import signal as _signal
    import subprocess
    import threading
    import urllib.request

    sys.path.insert(0, str(REPO))
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tony_tpu import constants as c
    from tony_tpu.models import transformer
    from tony_tpu.models.serving import Completion, Request, SlotServer

    # ---- arm A/B: in-process loop-crash replay (robustness shape) ----
    cfg = transformer.TransformerConfig(
        vocab_size=2048, d_model=256, n_layers=4, n_heads=8, n_kv_heads=4,
        d_ff=1024, max_seq_len=512,
        dtype=jnp.bfloat16 if jax.default_backend() == "tpu"
        else jnp.float32,
    )
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    slots, max_len, n_requests = 8, 512, 16
    rng = np.random.default_rng(7)
    prompt_lens = [16, 48, 96]
    # MIXED budgets: short requests complete early, which forces the
    # open-loop pipeline to process — so the journal holds PARTIAL
    # emitted prefixes for the long requests when the crash lands, and
    # the replay arm demonstrably carries tokens across the boundary
    budgets = [16, 64, 32, 48]
    prompts = [rng.integers(0, cfg.vocab_size,
                            size=prompt_lens[i % len(prompt_lens)],
                            dtype=np.int32)
               for i in range(n_requests)]
    srv_kw = dict(slots=slots, max_len=max_len, block_size=16,
                  prefill_chunk=64)

    def run_arm(extra_env: dict, replay: bool):
        from tony_tpu.cli.serve import ServeApp, ServingLoopError

        saved = {k: os.environ.get(k) for k in extra_env}
        os.environ.update(extra_env)
        try:
            srv = SlotServer(params, cfg, replay=replay, **srv_kw)
            app = ServeApp(srv, max_loop_restarts=16, loop_backoff_s=0.02)
            app.start()
            results: dict[int, object] = {}

            def call(i):
                try:
                    results[i] = app.generate(
                        prompts[i], budgets[i % len(budgets)],
                        timeout=600)
                except Exception as e:
                    results[i] = e

            t0 = time.time()
            threads = [threading.Thread(target=call, args=(i,))
                       for i in range(n_requests)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=900)
            wall = time.time() - t0
            assert not any(t.is_alive() for t in threads), "hung waiters"
            app.shutdown()
            return srv, app, results, wall
        finally:
            for k, v in saved.items():
                (os.environ.pop(k, None) if v is None
                 else os.environ.update({k: v}))

    # byte-reference: uninterrupted
    ref_srv, _, ref_results, ref_wall = run_arm({}, replay=True)
    assert all(isinstance(r, Completion) for r in ref_results.values())
    refs = {i: ref_results[i].tokens for i in range(n_requests)}

    # arm A: two mid-decode crashes, journal ON — ordinals deep enough
    # that short requests have completed (their processing revealed the
    # long requests' partial prefixes to the journal)
    srv, app, results, crash_wall = run_arm(
        {c.TEST_SERVING_CRASH_AT_BLOCKS: "3,7"}, replay=True)
    failed = [i for i, r in results.items()
              if not isinstance(r, Completion)]
    assert not failed, f"replay arm failed requests: {failed}"
    mismatched = [i for i in range(n_requests)
                  if results[i].tokens != refs[i]]
    assert not mismatched, f"replay diverged on requests: {mismatched}"
    assert srv.chaos_faults_injected == 2 and app.loop_restarts >= 1
    assert srv.replays >= 1, "crashes hit in-flight work; must replay"
    # recompute bound: the extra prefill vs the uninterrupted run is at
    # most one prompt+prefix re-prefill per replay — the emitted prefix
    # re-prefills, it is NEVER re-decoded
    extra_prefill = (srv.prefill_tokens_computed
                     - ref_srv.prefill_tokens_computed)
    bound = srv.replays * max(len(p) for p in prompts) \
        + srv.replayed_tokens
    assert extra_prefill <= bound, (
        f"replay recompute {extra_prefill} exceeds the "
        f"prompt+emitted-prefix bound {bound}")

    # arm B: same crash, replay OFF -> fail-fast preserved
    from tony_tpu.cli.serve import ServingLoopError

    srv_off, app_off, results_off, _ = run_arm(
        {c.TEST_SERVING_CRASH_AT_BLOCKS: "2"}, replay=False)
    failed_off = [i for i, r in results_off.items()
                  if isinstance(r, ServingLoopError)]
    assert failed_off, (
        "journal-off crash must fail the in-flight set (fail-fast)")
    assert srv_off.replays == 0

    # ---- arm C: fleet SIGKILL failover + journal recovery ----
    import tempfile as _tempfile

    from tony_tpu.router import FleetRouter

    tiny = dict(vocab=256, d_model=64, n_layers=2, n_heads=4, d_ff=128)
    t_slots, t_max_len, t_chunk, t_block = 4, 128, 8, 4
    t_requests = 12
    # mixed budgets: early completions force the open-loop pipeline to
    # process, revealing the long requests' partial prefixes to the
    # journal (same trick as arm A) — so the /progress polls have real
    # prefixes to journal before the kill
    t_budgets = [16, 48, 32, 64]
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           # slow each scheduling turn so the burst stays in flight
           # long enough for progress polls + a mid-decode kill (the
           # TINY model would otherwise drain the burst in a beat)
           "TONY_TEST_SERVING_STEP_DELAY_MS": "25"}
    env.pop("XLA_FLAGS", None)

    tiny_cfg = transformer.TransformerConfig(
        vocab_size=tiny["vocab"], d_model=tiny["d_model"],
        n_layers=tiny["n_layers"], n_heads=tiny["n_heads"],
        n_kv_heads=tiny["n_heads"], d_ff=tiny["d_ff"],
        dtype=jnp.float32)
    tiny_params = transformer.init(jax.random.PRNGKey(0), tiny_cfg)
    t_rng = np.random.default_rng(11)
    template = t_rng.integers(0, tiny["vocab"], size=t_chunk,
                              dtype=np.int32)
    t_prompts = [np.concatenate(
        [template, t_rng.integers(0, tiny["vocab"], size=2 + i % 5,
                                  dtype=np.int32)]).tolist()
        for i in range(t_requests)]
    ref2_srv = SlotServer(tiny_params, tiny_cfg, slots=t_slots,
                          max_len=t_max_len, block_size=t_block,
                          prefill_chunk=t_chunk)
    ref2_reqs = [Request(prompt=p,
                         max_new_tokens=t_budgets[i % len(t_budgets)])
                 for i, p in enumerate(t_prompts)]
    for r in ref2_reqs:
        ref2_srv.submit(r)
    ref2_done = ref2_srv.run_until_drained()
    t_refs = [ref2_done[r.id].tokens for r in ref2_reqs]

    class Srv:
        def __init__(self, name, trace_dir):
            self.name, self.trace_dir = name, trace_dir
            self.proc = self.port = None
            self.spawn()

        def spawn(self):
            self.proc = subprocess.Popen(
                [sys.executable, "-m", "tony_tpu.cli.main", "serve",
                 "--port", "0", "--vocab", str(tiny["vocab"]),
                 "--d-model", str(tiny["d_model"]),
                 "--n-layers", str(tiny["n_layers"]),
                 "--n-heads", str(tiny["n_heads"]),
                 "--d-ff", str(tiny["d_ff"]), "--dtype", "float32",
                 "--seed", "0", "--slots", str(t_slots),
                 "--max-len", str(t_max_len),
                 "--block-size", str(t_block),
                 "--prefill-chunk", str(t_chunk),
                 "--trace-dir", self.trace_dir],
                cwd=REPO, env=env, text=True,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
            self.port = None

        def await_ready(self, timeout=240.0):
            deadline = time.time() + timeout
            while self.port is None and time.time() < deadline:
                line = self.proc.stdout.readline()
                m = _re.search(r"http://[\d.]+:(\d+)", line or "")
                if m:
                    self.port = int(m.group(1))
            assert self.port, f"{self.name} never printed its port"
            threading.Thread(target=self.proc.stdout.read,
                             daemon=True).start()
            while time.time() < deadline:
                try:
                    with urllib.request.urlopen(
                            f"http://127.0.0.1:{self.port}/healthz",
                            timeout=2) as r:
                        if r.status == 200:
                            return
                except Exception:
                    time.sleep(0.2)
            raise AssertionError(f"{self.name} never became healthy")

        def stats(self):
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{self.port}/stats",
                    timeout=10) as r:
                return json.loads(r.read().decode())

        def stop(self):
            if self.proc.poll() is None:
                self.proc.kill()
            self.proc.wait(timeout=15)

    td = _tempfile.mkdtemp(prefix="tony-replay-bench-")
    reps = [Srv("a", os.path.join(td, "a")),
            Srv("b", os.path.join(td, "b"))]
    router = None
    try:
        for rep in reps:
            rep.await_ready()
        router = FleetRouter(
            [(rep.name, "127.0.0.1", rep.port) for rep in reps],
            prefill_chunk=t_chunk, health_interval_s=0.15,
            stats_every=2, seed=0)
        router.start()
        fleet_results: dict[int, object] = {}

        def call2(i):
            try:
                fleet_results[i] = router.generate(
                    t_prompts[i],
                    max_new_tokens=t_budgets[i % len(t_budgets)],
                    timeout_s=300)
            except Exception as e:
                fleet_results[i] = e

        t0 = time.time()
        threads = [threading.Thread(target=call2, args=(i,))
                   for i in range(t_requests)]
        for t in threads:
            t.start()
            time.sleep(0.03)
        # kill the affinity-sticky replica once it genuinely has this
        # burst's requests in flight (the template keys every request to
        # ONE replica, so the kill always interrupts real decode work)
        # ... ideally once the health loop's /progress polls have also
        # journaled a nonempty emitted prefix, so the failover
        # demonstrably CARRIES tokens — bounded wait; having ANY
        # outstanding work is the hard requirement, the prefix is
        # opportunistic (compile warm-up emits nothing for a while)
        victim = None
        deadline = time.time() + 60
        prefix_deadline = time.time() + 20
        while time.time() < deadline:
            with router._lock:
                names = set(router._outstanding.values())
                have_prefix = any(router._resume.values())
            cand = next((rep for rep in reps if rep.name in names), None)
            if cand is not None:
                victim = cand
                if have_prefix or time.time() >= prefix_deadline:
                    break
            time.sleep(0.02)
        assert victim is not None, "no request ever went in flight"
        victim_pid = victim.stats()["pid"]
        os.kill(victim_pid, _signal.SIGKILL)
        for t in threads:
            t.join(timeout=600)
        fleet_wall = time.time() - t0
        assert not any(t.is_alive() for t in threads), "hung callers"
        fleet_failed = [i for i, r in fleet_results.items()
                        if not isinstance(r, dict)]
        assert not fleet_failed, (
            f"fleet SIGKILL arm failed requests: "
            f"{[(i, fleet_results[i]) for i in fleet_failed]}")
        fleet_mismatch = [i for i in range(t_requests)
                          if fleet_results[i]["tokens"] != t_refs[i]]
        assert not fleet_mismatch, (
            f"fleet failover diverged on requests: {fleet_mismatch}")
        rstats = router.stats()
        assert rstats["failed"] == 0
        assert rstats["failovers"] >= 1, (
            "the SIGKILL interrupted in-flight work; failover must fire")

        # the killed replica restarts against the SAME trace dir and
        # finishes the orphaned requests from its file journal
        victim.stop()
        victim.spawn()
        victim.await_ready()
        deadline = time.time() + 300
        recovered_stats = None
        while time.time() < deadline:
            st = victim.stats()
            if (st.get("replays", 0) >= 1
                    and st.get("journal", {}).get("entries", 1) == 0
                    and st.get("active", 1) == 0):
                recovered_stats = st
                break
            time.sleep(0.25)
        assert recovered_stats is not None, (
            "restarted replica never finished its journal recovery")
        from tony_tpu.events.trace import read_traces

        recs = read_traces(os.path.join(victim.trace_dir,
                                        "requests.trace.jsonl"))
        recovered = [r for r in recs
                     if r["attrs"].get("recovered_from") is not None
                     and r["spans"] and r["spans"][-1][0] == "finished"]
        assert recovered, "no recovered_from trace in the restarted replica"
    finally:
        if router is not None:
            router.shutdown()
        for rep in reps:
            try:
                rep.stop()
            except Exception:
                pass

    out = {
        "metric": "serving_replay_zero_failed_requests",
        "value": 0,
        "unit": "failed requests across loop-crash and replica-SIGKILL "
                "arms (byte-identical completions enforced)",
        "loop_crash": {
            "requests": n_requests,
            "crashes_injected": srv.chaos_faults_injected,
            "loop_restarts": app.loop_restarts,
            "replays": srv.replays,
            "replayed_tokens": srv.replayed_tokens,
            "byte_identical": True,
            "replay_recompute_prefill_tokens": int(extra_prefill),
            "replay_recompute_bound": int(bound),
            "extra_decode_blocks": int(srv.blocks_dispatched
                                       - ref_srv.blocks_dispatched),
            "uninterrupted_wall_s": round(ref_wall, 3),
            "crash_wall_s": round(crash_wall, 3),
            "replay_catchup_p99_s": round(
                srv.telemetry.hist["replay_catchup_s"].quantile(0.99), 3),
        },
        "fail_fast_preserved": {
            "replay_off_failed_requests": len(failed_off),
            "replays": srv_off.replays,
        },
        "fleet_sigkill": {
            "requests": t_requests,
            "failed": 0,
            "byte_identical": True,
            "router_failovers": rstats["failovers"],
            "resumed_tokens": rstats["resumed_tokens"],
            "wall_s": round(fleet_wall, 3),
            "restart_recovered_requests": len(recovered),
            "restart_replays": recovered_stats["replays"],
        },
        "num_devices": jax.device_count(),
    }
    print(json.dumps(out))
    return 0


def run_distributed_tracing_bench() -> int:
    """Distributed-tracing gate (one JSON line -> PERF.json
    `distributed_tracing`; docs/observability.md "Distributed
    tracing"). Runs the disagg + router-SIGKILL story end to end: a
    prefill + a decode replica (--paged-kv) behind two router front
    doors, all four processes dumping --trace-dir JSONL; door 0 is
    SIGKILLed upon receiving its Nth /generate mid-burst, clients
    re-POST the same request_id at door 1, and the bench merges every
    tier's trace file with TraceCollector and enforces the four gates
    documented in docs/observability.md "Distributed tracing"."""
    import re as _re
    import tempfile as _tempfile
    import threading
    import urllib.error
    import urllib.request

    import jax
    import numpy as np

    from tony_tpu import constants as c
    from tony_tpu.events.trace import (
        TRACE_FILE,
        TraceCollector,
        coverage_s,
    )
    from tony_tpu.observability import (
        TRACE_ID_RESPONSE_HEADER,
        TraceContext,
    )

    e = dict(vocab=64, d_model=16, n_layers=1, n_heads=2, d_ff=32)
    SLOTS, MAX_LEN, CHUNK, BLOCK = 4, 96, 8, 4
    N_REQUESTS, MAX_NEW, KILL_AT = 24, 8, 8
    STEP_DELAY_MS = 40      # slow decode so the kill hits in-flight work
    STAGGER_S = 0.02        # burst spacing: #KILL_AT arrives ~0.15s in
    DEADLINE_S = 240.0
    # the documented bound on e2e time the merged span tree may leave
    # unaccounted: client->door network, the dead door's pre-relay
    # work, and the failover client's detect+re-POST beat
    GAP_BOUND_S = 2.0

    rng = np.random.default_rng(17)
    prompts = [rng.integers(0, e["vocab"], size=10 + i % 6,
                            dtype=np.int32).tolist()
               for i in range(N_REQUESTS)]

    base_env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    base_env.pop("XLA_FLAGS", None)
    base_env.pop(c.TEST_ROUTER_SIGKILL_AT_REQUEST, None)
    serve_env = {**base_env,
                 c.TEST_SERVING_STEP_DELAY_MS: str(STEP_DELAY_MS)}

    td = _tempfile.mkdtemp(prefix="tony-tracing-bench-")

    class Proc:
        """One tier process (serve replica or route front door); both
        print their endpoint as '... on http://host:port ...'."""

        def __init__(self, name, argv, env):
            self.name = name
            self.trace_dir = os.path.join(td, name)
            self.proc = subprocess.Popen(
                argv + ["--trace-dir", self.trace_dir],
                cwd=REPO, env=env, text=True,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
            self.port = None

        def await_ready(self, timeout=240.0):
            deadline = time.time() + timeout
            while self.port is None and time.time() < deadline:
                line = self.proc.stdout.readline()
                if line == "" and self.proc.poll() is not None:
                    break
                m = _re.search(r" on http://[\d.]+:(\d+)", line or "")
                if m:
                    self.port = int(m.group(1))
            assert self.port, f"{self.name} never printed its endpoint"
            threading.Thread(target=self.proc.stdout.read,
                             daemon=True).start()
            while time.time() < deadline:
                try:
                    with urllib.request.urlopen(
                            f"http://127.0.0.1:{self.port}/healthz",
                            timeout=2) as r:
                        if r.status == 200:
                            return
                except Exception:
                    pass        # 503 until the fleet is in rotation
                time.sleep(0.2)
            raise AssertionError(f"{self.name} never became healthy")

        def get_json(self, path):
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{self.port}{path}",
                    timeout=10) as r:
                return json.loads(r.read().decode())

        def stop(self):
            if self.proc.poll() is None:
                self.proc.kill()
            self.proc.wait(timeout=15)

    def serve_argv(role):
        return [sys.executable, "-m", "tony_tpu.cli.main", "serve",
                "--port", "0", "--vocab", str(e["vocab"]),
                "--d-model", str(e["d_model"]),
                "--n-layers", str(e["n_layers"]),
                "--n-heads", str(e["n_heads"]),
                "--d-ff", str(e["d_ff"]), "--dtype", "float32",
                "--seed", "0", "--slots", str(SLOTS),
                "--max-len", str(MAX_LEN), "--block-size", str(BLOCK),
                "--prefill-chunk", str(CHUNK),
                "--paged-kv", "--role", role]

    def route_argv(replicas):
        argv = [sys.executable, "-m", "tony_tpu.cli.main", "route",
                "--port", "0", "--prefill-chunk", str(CHUNK),
                "--health-interval-s", "0.15", "--stats-every", "1"]
        for rep in replicas:
            argv += ["--replica", f"127.0.0.1:{rep.port}"]
        return argv

    reps = doors = []
    results: dict[int, object] = {}
    try:
        reps = [Proc("prefill", serve_argv("prefill"), serve_env),
                Proc("decode", serve_argv("decode"), serve_env)]
        for rep in reps:
            rep.await_ready()
        doors = [
            Proc("door0", route_argv(reps),
                 {**base_env,
                  c.TEST_ROUTER_SIGKILL_AT_REQUEST: str(KILL_AT)}),
            Proc("door1", route_argv(reps), base_env)]
        for door in doors:
            door.await_ready()
        # both doors must have POLLED the replicas' role advertisements
        # before the burst, or the early requests route classically and
        # the disagg story never runs
        for door in doors:
            deadline = time.time() + 60
            while time.time() < deadline:
                st = door.get_json("/stats")
                roles = {r.get("role")
                         for r in st["replicas"].values()
                         if r.get("up")}
                if {"prefill", "decode"} <= roles:
                    break
                time.sleep(0.1)
            else:
                raise AssertionError(
                    f"{door.name} never discovered both roles")

        def post(door, body, timeout):
            req = urllib.request.Request(
                f"http://127.0.0.1:{door.port}/generate",
                data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=timeout) as r:
                return (json.loads(r.read().decode()),
                        r.headers.get(TRACE_ID_RESPONSE_HEADER))

        # warm both legs' compiles through door 1 so the timed burst
        # (and its kill window) isn't dominated by first-call tracing;
        # warmup trace_ids are distinct so the gates ignore them
        post(doors[1], {"prompt": prompts[0], "max_new_tokens": MAX_NEW,
                        "timeout_s": DEADLINE_S,
                        "request_id": "warmup-0"}, DEADLINE_S)

        def call(i):
            body = {"prompt": prompts[i], "max_new_tokens": MAX_NEW,
                    "timeout_s": DEADLINE_S,
                    "request_id": f"burst-{i}"}
            t0 = time.time()
            attempt = 0
            while True:
                door = doors[attempt % 2]   # door 0 first, then flip
                try:
                    resp, tid = post(door, body,
                                     max(1.0, t0 + DEADLINE_S
                                         - time.time()))
                    results[i] = {"resp": resp, "trace_id": tid,
                                  "e2e_s": time.time() - t0}
                    return
                except Exception as err:
                    attempt += 1
                    if time.time() - t0 > DEADLINE_S:
                        results[i] = err
                        return
                    time.sleep(0.25)

        t0 = time.time()
        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(N_REQUESTS)]
        for t in threads:
            t.start()
            time.sleep(STAGGER_S)
        for t in threads:
            t.join(timeout=600)
        burst_wall = time.time() - t0
        assert not any(t.is_alive() for t in threads), "hung callers"
        assert doors[0].proc.poll() is not None, (
            "door 0 survived its SIGKILL injection")
        failed = [i for i, r in results.items()
                  if not isinstance(r, dict)]
        assert not failed, (
            f"failed requests: {[(i, results[i]) for i in failed]}")

        # drain the orphans: the dead door's relays keep decoding on
        # the replicas and must SEAL their spans before the sweep
        deadline = time.time() + 120
        while time.time() < deadline:
            if all(rep.get_json("/stats").get("active", 1) == 0
                   for rep in reps):
                break
            time.sleep(0.25)

        leg_counts = {m.group(1): int(m.group(2)) for m in _re.finditer(
            r'router_leg_seconds_count\{leg="(\w+)"\} (\d+)',
            urllib.request.urlopen(
                f"http://127.0.0.1:{doors[1].port}/metrics",
                timeout=10).read().decode())}
    finally:
        for p in list(doors) + list(reps):
            try:
                p.stop()
            except Exception:
                pass

    # ---- the merge + the four gates ----
    collector = TraceCollector()
    for name in ("prefill", "decode", "door0", "door1"):
        path = os.path.join(td, name, TRACE_FILE)
        if os.path.exists(path):
            collector.add_file(path)
    assert collector.files_read == 4, (
        f"expected 4 tier trace files, read {collector.files_read}")
    merged = collector.merged()

    # gate 1: every completed request -> exactly ONE merged trace,
    # keyed by the deterministic request_id-derived trace_id that the
    # front door's response header echoed back
    expected = {i: TraceContext.for_request_id(f"burst-{i}").trace_id
                for i in range(N_REQUESTS)}
    bad_echo = [i for i in range(N_REQUESTS)
                if results[i]["trace_id"] != expected[i]]
    assert not bad_echo, (
        f"response header trace_id mismatch on requests: {bad_echo}")
    missing = [i for i in range(N_REQUESTS)
               if expected[i] not in merged]
    assert not missing, f"no merged trace for requests: {missing}"
    burst = {i: merged[expected[i]] for i in range(N_REQUESTS)}

    # gate 2: zero orphan spans — every span's parent produced a
    # record, INCLUDING children of the SIGKILLed door (its write-ahead
    # open records are the parents)
    orphans = sum(len(t["orphans"]) for t in burst.values())
    assert orphans == 0, (
        f"{orphans} orphan spans: "
        f"{[(i, t['orphans']) for i, t in burst.items() if t['orphans']]}")

    # gate 3: the failover story is VISIBLE — >= 1 trace carries router
    # spans from two distinct door nonces (door 0's unsealed open
    # record + door 1's sealed relay), and the dead door left >= 1
    # unsealed span for the merge to surface
    def routers_of(trace):
        return {s["attrs"].get("router") for s in trace["spans"]
                if s["attrs"].get("service") == "router"} - {None}

    two_door = [i for i, t in burst.items() if len(routers_of(t)) >= 2]
    assert two_door, ("no trace shows both doors: the kill either hit "
                      "an idle door or the open records were lost")
    unsealed = sum(
        1 for t in burst.values() for s in t["spans"]
        if s["attrs"].get("service") == "router"
        and s["terminal"] is None)
    assert unsealed >= 1, "the SIGKILLed door left no unsealed span"
    assert collector.superseded >= 1, (
        "no open record was superseded by its sealed twin; the "
        "write-ahead path is not exercising the merge fence")

    # the disagg handoff is ONE trace: the prefill leg (a serve span
    # finishing "prefilled") and the decode import leg (a serve span
    # with imported_blocks) both sit under a single trace_id
    def disagg_legs(trace):
        serves = [s["attrs"] for s in trace["spans"]
                  if s["attrs"].get("service") == "serve"]
        return (any(a.get("finish_reason") == "prefilled"
                    for a in serves)
                and any(a.get("imported_blocks") for a in serves))

    disagg_traces = [i for i, t in burst.items() if disagg_legs(t)]
    assert disagg_traces, "no trace spans both disagg replicas"
    assert leg_counts.get("prefill", 0) >= 1, leg_counts
    assert leg_counts.get("decode", 0) >= 1, leg_counts

    # gate 4: the span-union coverage accounts for the client-observed
    # e2e within the documented bound (failover detect+re-POST and
    # client->door network are the only permitted dark time)
    gaps = {i: results[i]["e2e_s"] - coverage_s(burst[i])
            for i in range(N_REQUESTS)}
    max_gap = max(gaps.values())
    assert max_gap <= GAP_BOUND_S, (
        f"unaccounted e2e gap {max_gap:.3f}s exceeds the "
        f"{GAP_BOUND_S}s bound: {sorted(gaps.items(), key=lambda kv: -kv[1])[:4]}")

    out = {
        "metric": "distributed_tracing_one_trace_per_request",
        "value": len(burst),
        "unit": "merged cross-tier traces for a 24-request disagg "
                "burst surviving a router SIGKILL (exactly one per "
                "completed request)",
        "requests": N_REQUESTS,
        "failed": 0,
        "trace_files_merged": collector.files_read,
        "spans_total": sum(len(t["spans"]) for t in burst.values()),
        "orphan_spans": 0,
        "header_echo_verified": True,
        "failover_two_door_traces": len(two_door),
        "unsealed_router_spans": unsealed,
        "superseded_open_records": collector.superseded,
        "torn_or_identityless_skipped": collector.skipped,
        "disagg_two_replica_traces": len(disagg_traces),
        "router_leg_counts": leg_counts,
        "max_unaccounted_gap_s": round(max_gap, 3),
        "gap_bound_s": GAP_BOUND_S,
        "burst_wall_s": round(burst_wall, 3),
        "num_devices": jax.device_count(),
    }
    print(json.dumps(out))
    return 0


def run_serving_streaming_bench() -> int:
    """Streaming-serving gate (one JSON line -> PERF.json
    `streaming_serving`; docs/serving.md "Streaming & OpenAI
    compatibility"). An open-loop POISSON arrival process at fleet
    scale, every request streamed per-token through the router, with
    one mid-stream replica SIGKILL. ENFORCED invariants:

    - zero failed requests (the kill becomes latency via router
      stream-failover, never an error);
    - every request's CONCATENATED stream is byte-identical to the
      non-streamed greedy completion (in-process SlotServer reference)
      — including the requests whose stream moved replicas mid-flight;
    - at least one stream failover actually fired (the kill landed on
      live streams) with the resume prefix harvested from the stream;
    - per-token inter-token-latency quantiles measured CLIENT-side
      (per-token arrival timestamps; tokens of one SSE chunk share an
      arrival instant, so intra-chunk gaps are genuine zeros).
    """
    import re as _re
    import signal as _signal
    import subprocess
    import threading
    import urllib.request

    sys.path.insert(0, str(REPO))
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tony_tpu.models import transformer
    from tony_tpu.models.serving import Request, SlotServer
    from tony_tpu.router import FleetRouter

    tiny = dict(vocab=256, d_model=64, n_layers=2, n_heads=4, d_ff=128)
    slots, max_len, chunk, block = 4, 128, 8, 4
    n_requests = 24
    budgets = [16, 48, 32, 64]
    mean_interarrival_s = 0.08          # open-loop Poisson, seeded
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           # slow each scheduling turn so streams stay live long enough
           # for a genuinely MID-stream kill on the TINY model
           "TONY_TEST_SERVING_STEP_DELAY_MS": "20"}
    env.pop("XLA_FLAGS", None)

    cfg = transformer.TransformerConfig(
        vocab_size=tiny["vocab"], d_model=tiny["d_model"],
        n_layers=tiny["n_layers"], n_heads=tiny["n_heads"],
        n_kv_heads=tiny["n_heads"], d_ff=tiny["d_ff"], dtype=jnp.float32)
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(17)
    template = rng.integers(0, tiny["vocab"], size=chunk, dtype=np.int32)
    prompts = [np.concatenate(
        [template, rng.integers(0, tiny["vocab"], size=2 + i % 5,
                                dtype=np.int32)]).tolist()
        for i in range(n_requests)]
    arrivals = np.cumsum(rng.exponential(mean_interarrival_s,
                                         size=n_requests))

    # non-streamed greedy reference: the byte-identity target
    ref_srv = SlotServer(params, cfg, slots=slots, max_len=max_len,
                         block_size=block, prefill_chunk=chunk)
    ref_reqs = [Request(prompt=p,
                        max_new_tokens=budgets[i % len(budgets)])
                for i, p in enumerate(prompts)]
    for r in ref_reqs:
        ref_srv.submit(r)
    ref_done = ref_srv.run_until_drained()
    refs = [ref_done[r.id].tokens for r in ref_reqs]

    class Srv:
        def __init__(self, name):
            self.name = name
            self.proc = self.port = None
            self.proc = subprocess.Popen(
                [sys.executable, "-m", "tony_tpu.cli.main", "serve",
                 "--port", "0", "--vocab", str(tiny["vocab"]),
                 "--d-model", str(tiny["d_model"]),
                 "--n-layers", str(tiny["n_layers"]),
                 "--n-heads", str(tiny["n_heads"]),
                 "--d-ff", str(tiny["d_ff"]), "--dtype", "float32",
                 "--seed", "0", "--slots", str(slots),
                 "--max-len", str(max_len), "--block-size", str(block),
                 "--prefill-chunk", str(chunk)],
                cwd=REPO, env=env, text=True,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT)

        def await_ready(self, timeout=240.0):
            deadline = time.time() + timeout
            while self.port is None and time.time() < deadline:
                line = self.proc.stdout.readline()
                m = _re.search(r"http://[\d.]+:(\d+)", line or "")
                if m:
                    self.port = int(m.group(1))
            assert self.port, f"{self.name} never printed its port"
            threading.Thread(target=self.proc.stdout.read,
                             daemon=True).start()
            while time.time() < deadline:
                try:
                    with urllib.request.urlopen(
                            f"http://127.0.0.1:{self.port}/healthz",
                            timeout=2) as r:
                        if r.status == 200:
                            return
                except Exception:
                    time.sleep(0.2)
            raise AssertionError(f"{self.name} never became healthy")

        def pid(self):
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{self.port}/stats",
                    timeout=10) as r:
                return json.loads(r.read().decode())["pid"]

        def stop(self):
            if self.proc.poll() is None:
                self.proc.kill()
            self.proc.wait(timeout=15)

    reps = [Srv("a"), Srv("b")]
    router = None
    try:
        for rep in reps:
            rep.await_ready()
        router = FleetRouter(
            [(rep.name, "127.0.0.1", rep.port) for rep in reps],
            prefill_chunk=chunk, health_interval_s=0.15, stats_every=2,
            seed=0)
        router.start()

        # warm both replicas' compiled programs off the clock
        for rep_i in range(2):
            router.generate(prompts[rep_i], max_new_tokens=4,
                            timeout_s=300)

        results: dict[int, object] = {}
        stamps: dict[int, list[float]] = {}     # per-token arrival t

        def call(i, delay):
            time.sleep(delay)
            ts = stamps[i] = []

            def on_tokens(toks):
                now = time.monotonic()
                ts.extend([now] * len(toks))

            try:
                results[i] = router.generate(
                    prompts[i],
                    max_new_tokens=budgets[i % len(budgets)],
                    timeout_s=600, on_tokens=on_tokens)
            except Exception as e:
                results[i] = e

        t0 = time.time()
        threads = [threading.Thread(target=call,
                                    args=(i, float(arrivals[i])))
                   for i in range(n_requests)]
        for t in threads:
            t.start()
        # SIGKILL the replica the streams are sticky to, once tokens
        # are demonstrably flowing through live relayed streams —
        # ideally once at least one of the VICTIM's own streams has a
        # harvested prefix, so the failover demonstrably carries
        # tokens (bounded wait; live outstanding streams are the hard
        # requirement, the prefix is opportunistic)
        victim = None
        deadline = time.time() + 120
        prefix_deadline = time.time() + 20
        while time.time() < deadline:
            with router._lock:
                names = set(router._outstanding.values())
                flowing = router.streamed_tokens_total > 0
            cand = next((rep for rep in reps if rep.name in names), None)
            if cand is not None and flowing:
                victim = cand
                # does the VICTIM itself carry a harvestable prefix
                # (its own outstanding streams, not just anyone's)?
                with router._lock:
                    victim_has_prefix = any(
                        router._resume.get(rid)
                        for rid, name in router._outstanding.items()
                        if name == cand.name)
                if victim_has_prefix or time.time() >= prefix_deadline:
                    break
            time.sleep(0.02)
        assert victim is not None, "no live stream to kill under"
        os.kill(victim.pid(), _signal.SIGKILL)
        for t in threads:
            t.join(timeout=900)
        wall = time.time() - t0
        assert not any(t.is_alive() for t in threads), "hung streams"

        failed = [i for i, r in results.items()
                  if not isinstance(r, dict)]
        assert not failed, (
            f"streaming arm failed requests: "
            f"{[(i, results[i]) for i in failed]}")
        # byte-identity, TWICE over: the per-token stream the client
        # assembled AND the final response both equal the non-streamed
        # greedy reference
        mismatched = [i for i in range(n_requests)
                      if results[i]["tokens"] != refs[i]]
        assert not mismatched, (
            f"streamed output diverged from non-streamed greedy on: "
            f"{mismatched}")
        per_token_counts = [len(stamps[i]) for i in range(n_requests)]
        assert per_token_counts == [len(r) for r in refs], (
            "client-side token stream lengths diverged from refs")
        rstats = router.stats()
        assert rstats["failed"] == 0
        assert rstats["stream_failovers"] >= 1, (
            "the SIGKILL must land on live streams")
        assert rstats["stream_disconnects"] == 0

        # client-observed latency: TTFT (arrival->first token) is not
        # derivable from stamps alone here, so report ITL only — the
        # per-token gaps INCLUDING intra-chunk zeros (what a client
        # sees), plus the nonzero chunk-gap view
        gaps = []
        for i in range(n_requests):
            ts = stamps[i]
            gaps.extend(b - a for a, b in zip(ts, ts[1:]))
        gaps.sort()

        def q(p):
            return gaps[min(len(gaps) - 1,
                            int(p * (len(gaps) - 1)))] if gaps else 0.0

        chunk_gaps = sorted(g for g in gaps if g > 0)

        def cq(p):
            return chunk_gaps[min(len(chunk_gaps) - 1,
                                  int(p * (len(chunk_gaps) - 1)))] \
                if chunk_gaps else 0.0

        out = {
            "metric": "streaming_serving_zero_failed_requests",
            "value": 0,
            "unit": "failed requests across an open-loop Poisson "
                    "streamed burst with one mid-stream replica "
                    "SIGKILL (byte-identity to non-streamed greedy "
                    "enforced)",
            "requests": n_requests,
            "poisson_mean_interarrival_s": mean_interarrival_s,
            "byte_identical": True,
            "streamed_tokens": rstats["streamed_tokens"],
            "stream_failovers": rstats["stream_failovers"],
            "failovers": rstats["failovers"],
            "resumed_tokens": rstats["resumed_tokens"],
            "stream_disconnects": rstats["stream_disconnects"],
            "itl_p50_s": round(q(0.50), 4),
            "itl_p99_s": round(q(0.99), 4),
            "chunk_gap_p50_s": round(cq(0.50), 4),
            "chunk_gap_p99_s": round(cq(0.99), 4),
            "wall_s": round(wall, 3),
            "num_devices": jax.device_count(),
        }
        print(json.dumps(out))
        return 0
    finally:
        if router is not None:
            router.shutdown()
        for rep in reps:
            try:
                rep.stop()
            except Exception:
                pass


def run_elastic_bench() -> int:
    """Elastic-training robustness benchmark (docs/training-robustness.md),
    run TWICE — warm pool off, then on — so the recovery bound shows what
    adoption buys: a real 2-worker local job runs
    examples/elastic_train.py (tiny deterministic jitted update,
    overlapped orbax checkpoints every SAVE_INTERVAL steps, full
    preemption-drain contract) while the driver's seeded chaos harness
    SIGKILLs containers at KILL_RATE per monitor tick and fires one
    preemption drain when the gang reaches PREEMPT_AT_STEP. Elasticity
    is ON with a restart budget, so every loss is either a budgeted
    restart, a budget-free preempt relaunch, or a gang resize — never a
    failed job.

    Each arm ENFORCES the acceptance invariants rather than just
    reporting them: the job must SUCCEED (zero failed jobs), at least
    one chaos kill and the preemption must actually have fired, every
    worker's StepTimer JSONL must show ≤ SAVE_INTERVAL recomputed steps
    per recovery and NO silent step skips, and each recovery's
    loss→running wall time is read off tasks.trace.jsonl. On top, the
    per-recovery loss→first-step-after-relaunch gap is read off the
    per-step JSONL wall clocks (the gap across each step REWIND), and
    the pool-on arm must show at least one adopted relaunch
    (child_adopted in the traces) — the adopted relaunch skips the
    child's import/backend bill (`backend_and_data_s` in the launch
    waterfall), which is exactly the step-gap delta between the arms."""
    off = _run_elastic_arm(warm_pool=False)
    on = _run_elastic_arm(warm_pool=True)
    assert on["adopted_relaunches"] >= 1, (
        "the pool-on arm never adopted a relaunch; warm pool broken?")
    out = {
        "metric": "training_robustness_elastic_chaos",
        "value": off["value"],
        "unit": off["unit"],
        "job_status": "SUCCEEDED",
        "failed_jobs": 0,
        "chaos": off["chaos"],
        "total_steps": off["total_steps"],
        "save_interval": off["save_interval"],
        "step_ms": off["step_ms"],
        "warm_pool_off": off,
        "warm_pool_on": on,
    }
    print(json.dumps(out))
    return 0


def _run_elastic_arm(warm_pool: bool) -> dict:
    import tempfile as _tempfile

    sys.path.insert(0, str(REPO))
    from tony_tpu import constants as c
    from tony_tpu.api import JobStatus
    from tony_tpu.client import TonyClient
    from tony_tpu.conf import TonyConf
    from tony_tpu.events.trace import TASK_TRACE_FILE, read_traces

    SAVE_INTERVAL = 5
    TOTAL_STEPS = 150
    STEP_MS = 50
    KILL_RATE = 0.006           # per 100ms monitor tick; E[kills] ~ 2
    PREEMPT_AT = 60
    SEED = 1234
    workers = 2

    chaos_env = {
        c.TEST_DRIVER_KILL_RATE: str(KILL_RATE),
        c.TEST_DRIVER_PREEMPT_AT_STEP: str(PREEMPT_AT),
        c.TEST_DRIVER_CHAOS_SEED: str(SEED),
    }
    td = _tempfile.mkdtemp(prefix="tony-elastic-bench-")
    root = Path(td)
    cmd = (f"{sys.executable} -m tony_tpu.examples.elastic_train "
           f"--steps {TOTAL_STEPS} --save-interval {SAVE_INTERVAL} "
           f"--ckpt-dir {root}/ckpt_$TONY_TASK_INDEX")
    conf = TonyConf({
        "tony.staging.dir": str(root / "staging"),
        "tony.history.location": str(root / "history"),
        "tony.history.intermediate": str(root / "history/intermediate"),
        "tony.history.finished": str(root / "history/finished"),
        "tony.am.monitor-interval-ms": 100,
        "tony.task.registration-poll-interval-ms": 100,
        "tony.task.heartbeat-interval-ms": 250,
        "tony.task.metrics-interval-ms": 500,
        "tony.task.preempt-grace-ms": 4000,
        "tony.worker.instances": workers,
        "tony.worker.command": cmd,
        "tony.worker.max-restarts": 3,
        "tony.train.elastic-enabled": True,
        "tony.train.elastic-min-instances": 1,
        "tony.train.rescale-retry-ms": 3000,
        # pool-on: every relaunch (budgeted restart, preempt, resize)
        # adopts a pre-warmed standby instead of paying the cold child
        # bill again — the driver seeds the pool at prepare and the
        # executors replenish after each adoption
        "tony.warmpool.size": workers if warm_pool else 0,
        "tony.execution.env": " ".join(
            [f"ELASTIC_TRAIN_STEP_MS={STEP_MS}", "JAX_PLATFORMS=cpu"]
            # chaos kills arrive seconds apart: replenish fast enough
            # that back-to-back recoveries still find a standby
            + (["TONY_WARMPOOL_REPLENISH_DELAY_S=1"] if warm_pool else [])
            + [f"{k}={v}" for k, v in chaos_env.items()]),
    })
    # the chaos knobs must reach the DRIVER process (it reads them at
    # construction); the client launches the driver with its own env
    old_env = {k: os.environ.get(k) for k in chaos_env}
    os.environ.update(chaos_env)
    t0 = time.time()
    try:
        client = TonyClient(conf, poll_interval_s=0.2)
        client.submit()
        status = client.monitor()
    finally:
        for k, v in old_env.items():
            os.environ.pop(k, None) if v is None else os.environ.__setitem__(k, v)
    wall = time.time() - t0

    assert status == JobStatus.SUCCEEDED, (
        f"elastic job FAILED under chaos: {client.final_state}")

    # ---- recovery forensics from the task traces
    inter = (root / "history/intermediate" / client.app_id)
    recs = {r["id"]: r for r in read_traces(inter / TASK_TRACE_FILE)}
    kills = preempts = resizes = adopted = 0
    recoveries = []     # (task, kind, loss->running seconds)
    for task_id, rec in recs.items():
        spans = rec["spans"]
        resizes = max(resizes, sum(1 for n, *_ in spans if n == "resized"))
        # adopted RELAUNCHES only: a first-attempt adoption (the driver
        # seeds the pool at prepare) must not satisfy the recovery gate
        names = [n for n, *_ in spans]
        first_loss = next((i for i, n in enumerate(names) if n in
                           ("restarted", "preempted", "resized")),
                          len(names))
        adopted += sum(1 for n in names[first_loss:]
                       if n == "child_adopted")
        for i, (name, t_mark) in enumerate(spans):
            if name not in ("restarted", "preempted", "resized"):
                continue
            if name == "restarted":
                kills += 1
            elif name == "preempted":
                preempts += 1
            t_run = next((t for n, t in spans[i + 1:] if n == "running"),
                         None)
            if t_run is not None:
                recoveries.append(
                    {"task": task_id, "kind": name,
                     "loss_to_running_s": round(t_run - t_mark, 3)})
    assert preempts >= 1, "the seeded preemption never fired"
    assert kills + preempts + resizes >= 2, (
        f"chaos too quiet to gate on (kills={kills} preempts={preempts} "
        f"resizes={resizes}); raise KILL_RATE")

    # ---- recompute bound + continuity from the per-step StepTimer JSONLs,
    # plus the loss->first-step-after-relaunch gap: consecutive per-step
    # records share one worker wall clock, so the ts delta across each
    # step REWIND is the full recovery — kill detection, relaunch, child
    # startup (the part adoption removes), restore, first new step
    per_worker = {}
    step_gaps = []
    for w in range(workers):
        log_path = Path(client.job_dir) / "logs" / f"worker_{w}.steps.jsonl"
        steps = []
        for line in log_path.read_text().splitlines():
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if isinstance(rec.get("train_step"), int):
                steps.append((rec["train_step"], rec.get("ts")))
        recomputed, worst = 0, 0
        gaps = []
        for (prev, prev_ts), (cur, cur_ts) in zip(steps, steps[1:]):
            if cur <= prev:
                recomputed += prev - cur + 1
                worst = max(worst, prev - cur + 1)
                if isinstance(prev_ts, (int, float)) and isinstance(
                        cur_ts, (int, float)):
                    gaps.append(round(cur_ts - prev_ts, 3))
            else:
                assert cur == prev + 1, (
                    f"worker_{w}: silent step skip {prev}->{cur}")
        assert worst <= SAVE_INTERVAL, (
            f"worker_{w} recomputed {worst} steps in one recovery "
            f"> save_interval {SAVE_INTERVAL}")
        step_gaps += gaps
        per_worker[f"worker_{w}"] = {
            "records": len(steps),
            "last_step": steps[-1][0] if steps else None,
            "recomputed_steps_total": recomputed,
            "worst_single_recovery_recompute": worst,
            "recovery_step_gaps_s": gaps,
        }
    survivors_finished = [w for w, d in per_worker.items()
                          if d["last_step"] == TOTAL_STEPS - 1]
    assert survivors_finished, "no worker reached the final step"

    rec_times = [r["loss_to_running_s"] for r in recoveries]
    return {
        "value": round(max(rec_times), 3) if rec_times else None,
        "unit": "worst loss->running recovery seconds under seeded chaos",
        "warm_pool": warm_pool,
        "job_status": status.value,
        "chaos": {"kill_rate_per_tick": KILL_RATE,
                  "preempt_at_step": PREEMPT_AT, "seed": SEED},
        "total_steps": TOTAL_STEPS,
        "save_interval": SAVE_INTERVAL,
        "step_ms": STEP_MS,
        "budgeted_restarts": kills,
        "preemptions": preempts,
        "gang_resizes": resizes,
        "adopted_relaunches": adopted,
        "recoveries": recoveries,
        "loss_to_first_step_s_worst": max(step_gaps) if step_gaps else None,
        "loss_to_first_step_s_all": sorted(step_gaps),
        "per_worker": per_worker,
        "wall_s": round(wall, 1),
    }


def run_launch_path_bench() -> int:
    """Launch-path benchmark (docs/performance.md "Launch path"): the
    same 1-worker mnist job submitted three ways, all in one run on one
    host, waterfalls split the same way as `launch_cold`/`launch_warm`:

      cold     first-ever submit: cold XLA disk cache, cold child
               (pays import + backend init + data staging + compile)
      warm     resubmit, pool OFF: warm disk caches, still a cold child
      adopted  resubmit, pool ON: the task ADOPTS a pre-warmed standby
               (jax imported, backend up, warmup hook ran) from a
               host-level pool seeded before submit

    Asserts the adopted arm actually adopted (child_adopted in the task
    trace), that training results are identical to the cold child
    (same final loss + accuracy — adoption must not change the math),
    and reports cold/adopted speedup — the PERF.json `launch_path`
    gate. The warmup hook (`tony.warmpool.warmup-module`) is
    examples/warmup_mnist: the standby also prepays optax/model imports
    and one staged device transfer, the data-staging half of the bill."""
    import shutil
    import tempfile as _tempfile

    sys.path.insert(0, str(REPO))
    from tony_tpu import warmpool
    from tony_tpu.client import TonyClient
    from tony_tpu.conf import TonyConf
    from tony_tpu.events.trace import TASK_TRACE_FILE, read_traces

    # TINY first block: on CPU the 1000-step scan of the main bench puts
    # ~10s of block EXECUTION inside compile_first_block_s, drowning the
    # launch signal this bench exists to measure (on the TPU bench shape
    # the block is milliseconds); 20 steps keeps the phase ~pure compile
    STEPS, SPC, BATCH_ = 80, 20, 256
    td = Path(_tempfile.mkdtemp(prefix="tony-launch-bench-"))
    # the cold arm starts from an emptied FIXED cache directory; the
    # warm and adopted arms read what it (and the standby) wrote
    cache_env = _compile_cache_env("launch", cold=True)
    pool_dir = td / "warmpool"

    def run_arm(name: str, pool: bool) -> dict:
        out = td / f"{name}.json"
        conf = TonyConf({
            "tony.staging.dir": str(td / f"staging-{name}"),
            "tony.history.location": str(td / "hist"),
            "tony.history.intermediate": str(td / "hist/intermediate"),
            "tony.history.finished": str(td / "hist/finished"),
            "tony.am.monitor-interval-ms": 50,
            "tony.task.registration-poll-interval-ms": 50,
            "tony.worker.instances": 1,
            "tony.worker.command": (
                f"{sys.executable} -m tony_tpu.examples.mnist_jax "
                f"--steps {STEPS} --steps-per-call {SPC} "
                f"--batch-size {BATCH_} --metrics-out {out}"),
            "tony.execution.env": ",".join(
                f"{k}={v}" for k, v in cache_env.items()),
            "tony.warmpool.size": 1 if pool else 0,
            "tony.warmpool.dir": str(pool_dir) if pool else "",
            "tony.warmpool.warmup-module": "tony_tpu.examples.warmup_mnist",
        })
        client = TonyClient(conf, poll_interval_s=0.05)
        t_submit = time.time()
        client.submit()
        status = client.monitor()
        if status.value != "SUCCEEDED":
            for p in sorted(Path(client.job_dir).rglob("*.std*")):
                print(f"==== {p} ====\n{p.read_text()[-2000:]}",
                      file=sys.stderr)
            raise RuntimeError(f"{name} arm finished {status}")
        m = json.loads(out.read_text())
        bd = _launch_breakdown(m, t_submit)
        recs = read_traces(td / "hist/intermediate" / client.app_id
                           / TASK_TRACE_FILE)
        bd["adopted"] = any(
            n == "child_adopted" for r in recs for n, *_ in r["spans"])
        bd["final_loss"] = m["final_loss"]
        bd["accuracy"] = round(m["accuracy"], 4)
        return bd

    try:
        cold = run_arm("cold", pool=False)
        warm = run_arm("warm", pool=False)
        # pre-warm a HOST-level pool (what an operator keeps running),
        # then let the job adopt from it — this is the path every
        # relaunch/resize/roll takes with a per-job pool too
        pool = warmpool.WarmPool(
            pool_dir, size=1,
            warmup_module="tony_tpu.examples.warmup_mnist",
            # the hook prepays the workload's own staging AND train-block
            # compile (mnist_jax.build_train_block) at the job's shapes,
            # into the job's shared persistent cache
            spawn_env={"TONY_WARMUP_MNIST_SPC": str(SPC),
                       "TONY_WARMUP_MNIST_BATCH": str(BATCH_),
                       **cache_env})
        pool.ensure()
        deadline = time.time() + 300
        while warmpool.count_ready(pool_dir) < 1:
            if time.time() > deadline:
                raise RuntimeError(
                    "standby never became ready; see "
                    + (pool_dir / "spawn.log").read_text()[-2000:])
            time.sleep(0.2)
        adopted = run_arm("adopted", pool=True)
        assert adopted["adopted"], "the adopted arm never adopted"
        assert not cold["adopted"] and not warm["adopted"]
        # adoption must not change the training math
        assert adopted["final_loss"] == cold["final_loss"], (
            cold["final_loss"], adopted["final_loss"])
        assert adopted["accuracy"] == cold["accuracy"]
        speedup = (cold["total_submit_to_first_step_s"]
                   / adopted["total_submit_to_first_step_s"])
        # the acceptance gate, enforced like the fleet bench's 1.5x:
        # adoption must prepay enough of the cold bill to be >=3x
        assert speedup >= 3.0, (
            f"adopted path only {speedup:.2f}x vs cold (gate: 3x); "
            f"cold={cold} adopted={adopted}")
        print(
            f"# launch path: cold "
            f"{cold['total_submit_to_first_step_s']:.1f}s | warm "
            f"{warm['total_submit_to_first_step_s']:.1f}s | adopted "
            f"{adopted['total_submit_to_first_step_s']:.1f}s "
            f"({speedup:.2f}x vs cold)", file=sys.stderr)
        print(json.dumps({
            "metric": "launch_path",
            "value": round(speedup, 2),
            "unit": "cold/adopted submit->first-step speedup",
            "cold": cold,
            "warm": warm,
            "adopted": adopted,
            "warmup_module": "tony_tpu.examples.warmup_mnist",
            "workload": {"steps": STEPS, "steps_per_call": SPC,
                         "batch": BATCH_},
        }))
    finally:
        try:
            warmpool.WarmPool(pool_dir, size=0).reap()
        except Exception:
            pass
        shutil.rmtree(td, ignore_errors=True)
    return 0


def run_autoscale_bench() -> int:
    """Closed-loop autoscaling + multi-tenant arbitration gate (module
    docstring; one JSON line -> PERF.json `autoscaling`)."""
    import signal as _signal
    import tempfile as _tempfile
    import threading

    sys.path.insert(0, str(REPO))
    import numpy as np

    from tony_tpu import constants as c
    from tony_tpu.client import TonyClient
    from tony_tpu.conf import TonyConf
    from tony_tpu.events.driver_journal import load_state
    from tony_tpu.events.trace import TASK_TRACE_FILE, read_traces
    from tony_tpu.router import DriverDiscovery, FleetRouter

    # the TINY fleet shape (the gate is the control loop, not model
    # throughput); the step delay sets a KNOWN single-replica capacity
    # so the ramp reliably breaches the queue SLO
    e = dict(vocab=64, d_model=16, n_layers=1, n_heads=2, d_ff=32,
             slots=2, max_len=96, block_size=4, prefill_chunk=8)
    MAX_NEW = 16
    STEP_DELAY_MS = 100
    SAVE_INTERVAL = 5
    TRAIN_STEPS = 900
    STEP_MS = 150
    QUEUE_SLO = 6
    COOLDOWN_S = 6.0
    # ramp: a seeded Poisson burst floods the single replica, then a
    # sustained tail keeps traffic flowing while the scaled-up fleet
    # drains the backlog (the post-scale TTFT window)
    BURST_REQS, BURST_MEAN_S = 36, 0.08
    TAIL_REQS, TAIL_MEAN_S = 100, 0.35

    td = _tempfile.mkdtemp(prefix="tony-autoscale-bench-")
    root = Path(td)
    serve_cmd = (
        f"{sys.executable} -m tony_tpu.cli.main serve "
        "--port $TONY_SERVE_PORT --host 127.0.0.1 "
        f"--vocab {e['vocab']} --d-model {e['d_model']} "
        f"--n-layers {e['n_layers']} --n-heads {e['n_heads']} "
        f"--d-ff {e['d_ff']} --dtype float32 --seed 0 "
        f"--slots {e['slots']} --max-len {e['max_len']} "
        f"--block-size {e['block_size']} "
        f"--prefill-chunk {e['prefill_chunk']} "
        "--max-queue 64 --drain-timeout-s 10")
    train_cmd = (f"{sys.executable} -m tony_tpu.examples.elastic_train "
                 f"--steps {TRAIN_STEPS} --save-interval {SAVE_INTERVAL} "
                 f"--ckpt-dir {root}/ckpt_$TONY_TASK_INDEX")
    conf = TonyConf({
        "tony.staging.dir": str(root / "staging"),
        "tony.history.location": str(root / "history"),
        "tony.history.intermediate": str(root / "history/intermediate"),
        "tony.history.finished": str(root / "history/finished"),
        "tony.am.monitor-interval-ms": 100,
        "tony.application.framework": "serving",
        # job success = the TRAINING role's outcome; replicas serve for
        # the life of the job and are torn down with it
        "tony.application.untracked.jobtypes": "replica",
        "tony.task.registration-poll-interval-ms": 100,
        "tony.task.heartbeat-interval-ms": 250,
        "tony.task.driver-outage-grace-ms": 60000,
        "tony.serving.healthz-interval-ms": 200,
        "tony.replica.instances": 2,
        "tony.replica.command": serve_cmd,
        "tony.replica.max-restarts": 1,
        "tony.worker.instances": 2,
        "tony.worker.command": train_cmd,
        "tony.worker.max-restarts": 1,
        "tony.worker.framework": "jax",
        "tony.worker.priority-class": "batch",
        "tony.train.elastic-enabled": True,
        "tony.train.elastic-min-instances": 1,
        "tony.train.rescale-retry-ms": 300,
        "tony.train.checkpoint-dir": f"{root}/ckpt_$TONY_TASK_INDEX",
        "tony.warmpool.size": 1,
        "tony.autoscale.enabled": True,
        "tony.autoscale.role": "replica",
        "tony.autoscale.min": 1,
        "tony.autoscale.queue-depth-slo": QUEUE_SLO,
        "tony.autoscale.cooldown-s": COOLDOWN_S,
        "tony.autoscale.interval-s": 0.5,
        "tony.autoscale.breach-ticks": 2,
        "tony.quota.pool-slots": 3,
        "tony.execution.env": " ".join([
            f"PYTHONPATH={REPO}", "JAX_PLATFORMS=cpu",
            f"{c.TEST_SERVING_STEP_DELAY_MS}={STEP_DELAY_MS}",
            f"ELASTIC_TRAIN_STEP_MS={STEP_MS}"]),
    })
    t0 = time.time()
    client = TonyClient(conf, poll_interval_s=0.2)
    client.submit()
    job_dir = Path(client.job_dir)
    router = FleetRouter(
        [], prefill_chunk=e["prefill_chunk"],
        discover=DriverDiscovery(str(job_dir), role="replica",
                                 token=client.token),
        health_interval_s=0.3, eject_after=3, stats_every=2, seed=0)
    results: dict[int, object] = {}
    marks: dict[str, float] = {}
    rec = logf = None
    try:
        deadline = time.time() + 240
        while time.time() < deadline:
            router.health_tick()
            if router.stats()["live"] >= 1:
                break
            time.sleep(0.3)
        assert router.stats()["live"] == 1, (
            f"expected exactly replica:0 up (slot 1 parked): "
            f"{router.stats()}")
        router.start()

        # ---- kill watcher: the moment the scaled-up replica is LIVE
        # (scale-up journaled + actuated + serving), SIGKILL the driver
        # and relaunch it with --recover, mid-ramp
        stop_watch = threading.Event()

        def watch():
            nonlocal rec, logf
            while not stop_watch.wait(0.3):
                if router.stats()["live"] >= 2:
                    marks["live2"] = time.time()
                    os.kill(client._driver_proc.pid, _signal.SIGKILL)
                    client._driver_proc.wait(timeout=10)
                    marks["killed"] = time.time()
                    rec, logf = _spawn_recovered_driver(job_dir,
                                                        strip_env=[])
                    return

        watcher = threading.Thread(target=watch, daemon=True)
        watcher.start()

        rng = np.random.default_rng(11)
        chunk = e["prefill_chunk"]
        template = rng.integers(0, e["vocab"], size=chunk,
                                dtype=np.int32)
        n_total = BURST_REQS + TAIL_REQS
        prompts = [np.concatenate(
            [template, rng.integers(0, e["vocab"], size=1 + i % 3,
                                    dtype=np.int32)]).tolist()
            for i in range(n_total)]
        waits = np.concatenate([
            rng.exponential(BURST_MEAN_S, BURST_REQS),
            rng.exponential(TAIL_MEAN_S, TAIL_REQS)])

        def call(i):
            t_submit = time.time()
            first = {"t": None}

            def on_toks(_new):
                if first["t"] is None:
                    first["t"] = time.time()

            try:
                r = router.generate(prompts[i], max_new_tokens=MAX_NEW,
                                    timeout_s=240, on_tokens=on_toks)
                r["t_submit"] = t_submit
                r["ttft_s"] = ((first["t"] or time.time()) - t_submit)
                results[i] = r
            except Exception as exc:
                results[i] = exc

        threads = []
        t_traffic = time.time()
        for i in range(n_total):
            th = threading.Thread(target=call, args=(i,))
            th.start()
            threads.append(th)
            time.sleep(float(waits[i]))
        for th in threads:
            th.join(timeout=300)
        marks["traffic_done"] = time.time()
        watcher.join(timeout=60)
        assert "live2" in marks, (
            "the autoscaler never brought the second replica live "
            f"under the ramp: {router.stats()}")

        # ---- zero failed serving requests, across donation, scale-up,
        # the driver outage, and the scale-down drain
        failed = {i: r for i, r in results.items()
                  if not isinstance(r, dict)}
        assert not failed, (
            f"{len(failed)} requests failed across the ramp: "
            f"{dict(list(failed.items())[:3])}")
        assert len(results) == n_total

        # ---- TTFT recovery: requests submitted while one replica ate
        # the backlog vs requests submitted once the scaled-up fleet
        # was live and settled
        state = load_state(job_dir / c.DRIVER_JOURNAL_FILE)
        ups = [op for op in state.scale_ops if op["dir"] == "up"]
        assert len(ups) == 1, (
            f"expected exactly one journaled scale-up: {state.scale_ops}")
        t_up = float(ups[0]["t"])
        pre = sorted(r["ttft_s"] for r in results.values()
                     if r["t_submit"] < t_up)
        post = sorted(r["ttft_s"] for r in results.values()
                      if r["t_submit"] > marks["live2"] + 2.0)
        assert len(pre) >= 5 and len(post) >= 5, (
            f"phase windows too thin to gate on: pre={len(pre)} "
            f"post={len(post)}")

        def p99(xs):
            return xs[min(len(xs) - 1, int(0.99 * (len(xs) - 1)))]

        pre_p99, post_p99 = p99(pre), p99(post)
        assert post_p99 < 0.8 * pre_p99, (
            f"TTFT p99 never recovered after the scale-up: breach "
            f"window {pre_p99:.2f}s vs post-scale {post_p99:.2f}s")
        by_replica: dict[str, int] = {}
        for r in results.values():
            by_replica[r["replica"]] = by_replica.get(r["replica"], 0) + 1
        assert len(by_replica) == 2, (
            f"the scaled-up replica never took traffic: {by_replica}")

        # ---- ramp-down: fleet scales back, batch reclaims the slot,
        # training SUCCEEDS
        final = _wait_recovered_terminal(job_dir, rec, client.token,
                                         timeout_s=420)
        rec.wait(timeout=60)
        assert final["status"] == "SUCCEEDED", final
    finally:
        router.shutdown()
        for proc in (rec, client._driver_proc):
            if proc is not None and proc.poll() is None:
                try:
                    os.killpg(proc.pid, _signal.SIGTERM)
                except (ProcessLookupError, PermissionError):
                    pass
        if rec is not None:
            try:
                rec.wait(timeout=20)
            except subprocess.TimeoutExpired:
                os.killpg(rec.pid, _signal.SIGKILL)
        if logf is not None:
            logf.close()
    wall = time.time() - t0

    # ---- journal forensics: the ledger shows exactly one up and one
    # down across the driver SIGKILL — no duplicate, no flap — and the
    # donation round-tripped
    state = load_state(job_dir / c.DRIVER_JOURNAL_FILE)
    dirs = [op["dir"] for op in state.scale_ops]
    assert dirs == ["up", "down"], (
        f"scale ledger flapped or duplicated across recovery: {dirs}")
    assert state.recoveries >= 1, "driver recovery not journaled"
    assert len(state.parked) == 1 and all(
        t.startswith("replica:") for t in state.parked), state.parked
    assert state.donated == set() and state.donations == {}, (
        f"donated slot never reclaimed: {state.donated} "
        f"{state.donations}")
    replica_restarts = sum(
        t.restarts for tid, t in state.tasks.items()
        if tid.startswith("replica:"))
    assert replica_restarts == 0, (
        f"replicas spent restart budget: {replica_restarts}")

    # ---- trace forensics. The scale-up and donation marks were made
    # by the driver incarnation the bench SIGKILLs, and unsealed trace
    # records die with their driver (PR 12 semantics: the JOURNAL is
    # the durable decision record — asserted above); the marks made by
    # the RECOVERED driver must be in the file.
    trace_path = None
    for base in (root / "history/intermediate",
                 root / "history/finished"):
        for cand in base.glob(f"{client.app_id}*/{TASK_TRACE_FILE}"):
            trace_path = cand
    assert trace_path is not None, "tasks.trace.jsonl not found"
    spans_by_task: dict[str, list] = {}
    for rec_ in read_traces(trace_path):
        spans_by_task[rec_["id"]] = [n for n, *_ in rec_["spans"]]
    all_spans = [n for names in spans_by_task.values() for n in names]
    for mark in ("scaled_down", "reclaimed", "ckpt_prestaged"):
        assert mark in all_spans, (
            f"'{mark}' trace mark missing; spans: {spans_by_task}")
    donor = next(t for t, names in spans_by_task.items()
                 if "reclaimed" in names)
    assert donor.startswith("worker:"), (
        f"reclaim landed on a non-batch task: {donor}")
    assert "ckpt_prestaged" in spans_by_task[donor], (
        f"reclaimed {donor} came back without the checkpoint "
        f"prestaged: {spans_by_task[donor]}")
    adopted_relaunches = sum(
        1 for t, names in spans_by_task.items()
        if t.startswith("worker:")
        for i, n in enumerate(names)
        if n == "child_adopted" and any(
            m in names[:i] for m in ("resized", "reclaimed", "donated")))

    # ---- recompute bound: each drain (donation, survivor resizes,
    # reclaim) rewinds at most save_interval steps
    per_worker = {}
    for w in range(2):
        log_path = job_dir / "logs" / f"worker_{w}.steps.jsonl"
        steps = []
        for line in log_path.read_text().splitlines():
            try:
                rec_ = json.loads(line)
            except ValueError:
                continue
            if isinstance(rec_.get("train_step"), int):
                steps.append(rec_["train_step"])
        recomputed, worst = 0, 0
        for prev, cur in zip(steps, steps[1:]):
            if cur <= prev:
                recomputed += prev - cur + 1
                worst = max(worst, prev - cur + 1)
            else:
                assert cur == prev + 1, (
                    f"worker_{w}: silent step skip {prev}->{cur}")
        assert worst <= SAVE_INTERVAL, (
            f"worker_{w} recomputed {worst} steps in one recovery "
            f"> save_interval {SAVE_INTERVAL}")
        assert steps and steps[-1] == TRAIN_STEPS - 1, (
            f"worker_{w} never reached the final step")
        per_worker[f"worker_{w}"] = {
            "records": len(steps), "last_step": steps[-1],
            "recomputed_steps_total": recomputed,
            "worst_single_recovery_recompute": worst}

    out = {
        "metric": "autoscaling",
        "value": round(pre_p99 / post_p99, 2),
        "unit": "x TTFT-p99 recovery (breach window vs post-scale-up "
                "window, client-observed through the router)",
        "job_status": "SUCCEEDED",
        "requests": n_total,
        "failed_requests": 0,
        "ttft_p99_breach_s": round(pre_p99, 3),
        "ttft_p99_post_scale_s": round(post_p99, 3),
        "ttft_p50_breach_s": round(pre[len(pre) // 2], 3),
        "ttft_p50_post_scale_s": round(post[len(post) // 2], 3),
        "queue_depth_slo": QUEUE_SLO,
        "scale_ops": dirs,
        "scale_up_to_live_s": round(marks["live2"] - t_up, 1),
        "driver_killed_mid_ramp": True,
        "driver_recoveries": state.recoveries,
        "replica_restarts": 0,
        "donations": 1,
        "reclaims": 1,
        "donor": donor,
        "ckpt_prestaged": True,
        "adopted_relaunches": adopted_relaunches,
        "save_interval": SAVE_INTERVAL,
        "per_worker": per_worker,
        "per_replica_requests": by_replica,
        "traffic_wall_s": round(marks["traffic_done"] - t_traffic, 1),
        "wall_s": round(wall, 1),
    }
    print(json.dumps(out))
    return 0


def run_slo_bench() -> int:
    """Fleet metrics pipeline + SLO burn-rate alerting gate (module
    docstring; one JSON line -> PERF.json `slo_alerting`)."""
    import signal as _signal
    import tempfile as _tempfile
    import threading
    import urllib.request

    sys.path.insert(0, str(REPO))
    import numpy as np

    from tony_tpu import constants as c
    from tony_tpu.client import TonyClient
    from tony_tpu.conf import TonyConf
    from tony_tpu.observability import parse_prom_text
    from tony_tpu.router import DriverDiscovery

    e = dict(vocab=64, d_model=16, n_layers=1, n_heads=2, d_ff=32,
             slots=2, max_len=96, block_size=4, prefill_chunk=8)
    MAX_NEW = 8
    STEP_DELAY_MS = 100     # slow decode: the lone survivor's capacity
    #                         sits far below the incident arrival rate
    # availability SLO: W=120s -> fast pair (20s, 2s) @ 14.4x burn
    # (error rate > 14.4% in BOTH trailing windows), slow pair
    # (120s, 20s) @ 6x. The burst overloads the survivor hard enough
    # that the fast pair fires within a few 0.5s scrape rounds; only
    # the FAST alert's clear is gated (the slow pair needs the
    # incident to age out of the full 120s window).
    TARGET, WINDOW_S, SCRAPE_S = 0.99, 120.0, 0.5
    WARMUP_REQS, WARMUP_GAP_S = 15, 0.2
    PRESSURE_MEAN_S = 0.02      # ~50 req/s of sustained incident load

    td = _tempfile.mkdtemp(prefix="tony-slo-bench-")
    root = Path(td)
    serve_cmd = (
        f"{sys.executable} -m tony_tpu.cli.main serve "
        "--port $TONY_SERVE_PORT --host 127.0.0.1 "
        f"--vocab {e['vocab']} --d-model {e['d_model']} "
        f"--n-layers {e['n_layers']} --n-heads {e['n_heads']} "
        f"--d-ff {e['d_ff']} --dtype float32 --seed 0 "
        f"--slots {e['slots']} --max-len {e['max_len']} "
        f"--block-size {e['block_size']} "
        f"--prefill-chunk {e['prefill_chunk']} "
        # deep enough that the cold-start compile stall never sheds the
        # healthy warm-up (a shed is a REAL bad event and would burn
        # budget before the incident); the sustained incident load
        # still fills it behind a lone survivor within a few seconds
        "--max-queue 64 --drain-timeout-s 5")
    route_cmd = (
        f"{sys.executable} -m tony_tpu.cli.main route "
        "--port $TONY_SERVE_PORT --host 127.0.0.1 "
        "--job-dir $TONY_JOB_DIR --role replica "
        f"--prefill-chunk {e['prefill_chunk']} "
        "--health-interval-s 0.3 --probe-timeout-s 5.0 "
        "--discovery-min-interval-s 0.5 --stats-every 2 "
        "--drain-timeout-s 10")
    conf = TonyConf({
        "tony.staging.dir": str(root / "staging"),
        "tony.history.location": str(root / "history"),
        "tony.history.intermediate": str(root / "history/intermediate"),
        "tony.history.finished": str(root / "history/finished"),
        "tony.am.monitor-interval-ms": 100,
        "tony.application.framework": "serving",
        "tony.task.registration-poll-interval-ms": 100,
        "tony.task.heartbeat-interval-ms": 250,
        "tony.task.driver-outage-grace-ms": 60000,
        "tony.serving.healthz-interval-ms": 200,
        "tony.replica.instances": 2,
        "tony.replica.command": serve_cmd,
        "tony.replica.max-restarts": 1,
        "tony.router.instances": 1,
        "tony.router.command": route_cmd,
        "tony.router.framework": "router",
        "tony.router.max-restarts": 1,
        # the hub scrapes the named serving role's replicas even with
        # the autoscaler off (autoscale.enabled stays false)
        "tony.autoscale.role": "replica",
        "tony.slo.availability.objective": "availability",
        "tony.slo.availability.target": TARGET,
        "tony.slo.availability.window-s": WINDOW_S,
        "tony.slo.scrape-interval-s": SCRAPE_S,
        "tony.execution.env": " ".join([
            f"PYTHONPATH={REPO}", "JAX_PLATFORMS=cpu",
            f"{c.TEST_SERVING_STEP_DELAY_MS}={STEP_DELAY_MS}"]),
    })
    t_bench = time.time()
    client = TonyClient(conf, poll_interval_s=0.2)
    client.submit()
    job_dir = Path(client.job_dir)
    disco_router = DriverDiscovery(str(job_dir), role="router",
                                   token=client.token)
    disco_replica = DriverDiscovery(str(job_dir), role="replica",
                                    token=client.token)

    def endpoints(disco):
        try:
            return {tid: (host, port) for tid, host, port in disco()}
        except Exception:
            return {}

    def get_json(url, timeout=10):
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return json.loads(r.read().decode())

    def slo_snap(want_pid=None):
        """The live driver's /slo snapshot via driver.json; None while
        the endpoint (or the wanted driver incarnation) isn't up."""
        try:
            info = json.loads((job_dir / c.DRIVER_INFO_FILE).read_text())
            if want_pid is not None and info.get("pid") != want_pid:
                return None
            port = info["metrics_port"]
            return get_json(f"http://127.0.0.1:{port}/slo", timeout=5)
        except Exception:
            return None

    def fast_alert(snap):
        if not snap or not snap.get("evaluated"):
            return None
        for a in snap["alerts"]:
            if a["slo"] == "availability" and a["severity"] == "fast":
                return a["firing"]
        return None

    def journal_alert_records():
        recs = []
        for line in (job_dir / c.DRIVER_JOURNAL_FILE).read_text(
                ).splitlines():
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if rec.get("op") == "slo_alert":
                recs.append(rec)
        return recs

    results: dict[int, str] = {}
    marks: dict[str, float] = {}
    rec = logf = None
    try:
        deadline = time.time() + 240
        doors = reps = {}
        while time.time() < deadline:
            doors = endpoints(disco_router)
            reps = endpoints(disco_replica)
            if len(doors) == 1 and len(reps) == 2:
                break
            time.sleep(0.3)
        assert len(doors) == 1, f"front door never up: {doors}"
        assert len(reps) == 2, f"replica fleet never fully up: {reps}"
        door_port = doors["router:0"][1]

        chunk = e["prefill_chunk"]

        def prompt(i):
            # per-call generator: prompt() runs on many client threads
            # at once and a shared numpy Generator is not thread-safe
            return np.random.default_rng(1000 + i).integers(
                0, e["vocab"], size=chunk + 1 + i % 3,
                dtype=np.int32).tolist()

        def call(i, tag):
            try:
                req = urllib.request.Request(
                    f"http://127.0.0.1:{door_port}/generate",
                    data=json.dumps({"prompt": prompt(i),
                                     "max_new_tokens": MAX_NEW}).encode(),
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=120) as r:
                    json.loads(r.read().decode())
                results[i] = "ok"
            except Exception:
                # shed/failed during the incident — the SLO's bad events
                results[i] = f"{tag}_err"

        # ---- phase 1: healthy warm-up — ZERO alerts
        t_first_request = time.time()
        warm = [threading.Thread(target=call, args=(i, "warm"))
                for i in range(WARMUP_REQS)]
        for th in warm:
            th.start()
            time.sleep(WARMUP_GAP_S)
        for th in warm:
            th.join(timeout=120)
        assert all(results[i] == "ok" for i in range(WARMUP_REQS)), (
            f"healthy warm-up had failures: {results}")
        deadline = time.time() + 30
        snap = None
        while time.time() < deadline:
            snap = slo_snap()
            if snap and snap.get("evaluated"):
                break
            time.sleep(0.3)
        assert snap and snap.get("evaluated"), "SLO engine never evaluated"
        assert snap["history"] == [], (
            f"alerts fired on a HEALTHY warm-up: {snap['history']}")
        assert all(not a["firing"] for a in snap["alerts"]), snap["alerts"]

        # ---- phase 2: replica SIGKILL + SUSTAINED Poisson overload ->
        # the survivor sheds, the fast pair must fire inside its
        # window. The pressure keeps flowing until the recovered
        # driver confirms the resumed alert: the fast pair's SHORT
        # window empties ~2s after sheds stop, and a cleared alert
        # would make the driver kill land post-incident.
        victim_stats = get_json(
            f"http://127.0.0.1:{reps['replica:0'][1]}/stats")
        os.kill(victim_stats["pid"], _signal.SIGKILL)
        marks["replica_killed"] = time.time()
        stop_pressure = threading.Event()
        pressure_n = {"i": WARMUP_REQS}
        pressure_rng = np.random.default_rng(29)

        def pressure():
            # ~50 req/s against a shedding survivor (and still past the
            # relaunched 2-replica fleet's capacity): bad events flow
            # continuously across the replica kill, the driver kill,
            # and the recovery
            while not stop_pressure.is_set():
                i = pressure_n["i"]
                pressure_n["i"] += 1
                threading.Thread(target=call, args=(i, "incident"),
                                 daemon=True).start()
                time.sleep(float(pressure_rng.exponential(
                    PRESSURE_MEAN_S)))

        pressure_t = threading.Thread(target=pressure, daemon=True)
        pressure_t.start()
        fired_at = None
        deadline = time.time() + 60
        while time.time() < deadline:
            if fast_alert(slo_snap()) is True:
                fired_at = time.time()
                break
            time.sleep(0.2)
        assert fired_at is not None, (
            "fast burn-rate alert never fired under the overload "
            f"incident: {slo_snap()}")
        marks["alert_fired"] = fired_at
        firings = [r for r in journal_alert_records()
                   if r["severity"] == "fast" and r["state"] == "firing"]
        assert len(firings) == 1, firings

        # ---- phase 3: driver SIGKILL + --recover MID-INCIDENT — the
        # replayed tsdb + journal-seeded alert state must RESUME the
        # firing alert without a duplicate transition
        os.kill(client._driver_proc.pid, _signal.SIGKILL)
        client._driver_proc.wait(timeout=10)
        marks["driver_killed"] = time.time()
        rec, logf = _spawn_recovered_driver(job_dir, strip_env=[])
        resumed = None
        deadline = time.time() + 90
        while time.time() < deadline:
            resumed = fast_alert(slo_snap(want_pid=rec.pid))
            if resumed is not None:
                break
            time.sleep(0.3)
        assert resumed is True, (
            "recovered driver did not resume the mid-incident firing "
            f"alert: {slo_snap(want_pid=rec.pid)}")
        marks["alert_resumed"] = time.time()
        fast_recs = [r for r in journal_alert_records()
                     if r["severity"] == "fast"]
        assert [r["state"] for r in fast_recs] == ["firing"], (
            f"duplicate/flapped firing transition across recovery: "
            f"{fast_recs}")

        # ---- phase 4: the SIGKILLed replica relaunches on its restart
        # budget; end the incident — the alert must CLEAR and healthy
        # service resume
        deadline = time.time() + 120
        relaunched = False
        while time.time() < deadline:
            reps = endpoints(disco_replica)
            if len(reps) == 2:
                try:
                    pids = {tid: get_json(
                        f"http://127.0.0.1:{p}/stats", timeout=5)["pid"]
                        for tid, (_, p) in reps.items()}
                    if pids["replica:0"] != victim_stats["pid"]:
                        relaunched = True
                        break
                except Exception:
                    pass
            time.sleep(0.5)
        assert relaunched, f"SIGKILLed replica never relaunched: {reps}"
        stop_pressure.set()
        pressure_t.join(timeout=10)
        marks["incident_over"] = time.time()
        cleared_at = None
        deadline = time.time() + 90
        while time.time() < deadline:
            if fast_alert(slo_snap(want_pid=rec.pid)) is False:
                cleared_at = time.time()
                break
            time.sleep(0.3)
        assert cleared_at is not None, (
            "fast alert never cleared after the incident ended: "
            f"{slo_snap(want_pid=rec.pid)}")
        marks["alert_cleared"] = cleared_at
        fast_recs = [r for r in journal_alert_records()
                     if r["severity"] == "fast"]
        assert [r["state"] for r in fast_recs] == ["firing", "clear"], (
            f"fast alert transition ledger wrong: {fast_recs}")
        # healthy service restored through the relaunched fleet
        probe_i = pressure_n["i"] + 1
        call(probe_i, "post")
        assert results[probe_i] == "ok", (
            "fleet did not serve healthily after the incident")

        # ---- phase 5: budget exactness — the engine's availability
        # accounting must equal (failed+shed)/total from the router's
        # own exposition, bit-for-bit. Valid only while ALL traffic is
        # inside the trailing SLO window (counters born at zero).
        assert time.time() - t_first_request < WINDOW_S - 5, (
            f"bench overran the SLO window "
            f"({time.time() - t_first_request:.0f}s of "
            f"{WINDOW_S:g}s): the budget-exactness gate would see "
            "traffic age out")
        def router_metrics_text():
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{door_port}/metrics",
                    timeout=10) as r:
                return r.read().decode()

        def counter_triple():
            fams = parse_prom_text(router_metrics_text())
            return tuple(
                sum(fams[name].values()) if name in fams else 0.0
                for name in ("router_requests_total",
                             "router_shed_total",
                             "router_requests_failed_total"))

        # in-flight stragglers may still land: wait for the router's
        # counters to go static, then let the hub scrape them
        prev = counter_triple()
        deadline = time.time() + 30
        while time.time() < deadline:
            time.sleep(1.0)
            cur = counter_triple()
            if cur == prev:
                break
            prev = cur
        time.sleep(3 * SCRAPE_S)   # let the hub land the final counters
        requests_total, shed_total, failed_total = prev
        snap = slo_snap(want_pid=rec.pid)
        avail = next(s for s in snap["eval"]["slos"]
                     if s["name"] == "availability")
        assert abs(avail["total"] - requests_total) < 1e-9, (
            f"engine total {avail['total']} != router "
            f"{requests_total}")
        assert abs(avail["bad"] - (shed_total + failed_total)) < 1e-9, (
            f"engine bad {avail['bad']} != shed+failed "
            f"{shed_total + failed_total}")
        expected_rate = (shed_total + failed_total) / requests_total
        assert abs(avail["error_rate"] - expected_rate) < 1e-9, (
            f"budget spend {avail['error_rate']} != (failed+shed)/total "
            f"{expected_rate}")
    finally:
        for proc in (rec, client._driver_proc):
            if proc is not None and proc.poll() is None:
                try:
                    os.killpg(proc.pid, _signal.SIGTERM)
                except (ProcessLookupError, PermissionError):
                    pass
        if rec is not None:
            try:
                rec.wait(timeout=20)
            except subprocess.TimeoutExpired:
                try:
                    os.killpg(rec.pid, _signal.SIGKILL)
                except (ProcessLookupError, PermissionError):
                    pass
        if logf is not None:
            logf.close()

    n_err = sum(1 for v in results.values() if v != "ok")
    out = {
        "metric": "slo_alerting",
        "value": round(marks["alert_fired"] - marks["replica_killed"], 1),
        "unit": "s replica-SIGKILL -> fast burn-rate alert firing "
                "(multi-window, journaled, resumed across a driver "
                "SIGKILL + --recover mid-incident)",
        "objective": "availability",
        "target": TARGET,
        "window_s": WINDOW_S,
        "requests": len(results),
        "bad_requests_client_observed": n_err,
        "router_requests_total": requests_total,
        "router_bad_total": shed_total + failed_total,
        "error_rate": round(expected_rate, 6),
        "error_budget_remaining": round(
            avail["error_budget_remaining"], 4),
        "budget_accounting_exact": True,
        "warmup_alerts": 0,
        "fast_transitions": ["firing", "clear"],
        "duplicate_firing_transitions": 0,
        "alert_fire_s": round(
            marks["alert_fired"] - marks["replica_killed"], 1),
        "alert_resume_after_recover_s": round(
            marks["alert_resumed"] - marks["driver_killed"], 1),
        "alert_clear_s": round(
            marks["alert_cleared"] - marks["replica_killed"], 1),
        "driver_killed_mid_incident": True,
        "wall_s": round(time.time() - t_bench, 1),
    }
    print(json.dumps(out))
    return 0


def run_driver_failover_bench() -> int:
    """Control-plane robustness gate (module docstring; one JSON line ->
    PERF.json `control_plane_robustness`): driver death must be a
    latency cost for BOTH workload kinds — training keeps stepping and
    re-adopts, serving keeps answering from the router's last-known
    fleet."""
    training = _failover_training_arm()
    fleet = _failover_fleet_arm()
    out = {
        "metric": "control_plane_robustness",
        "value": training["recovery_to_first_heartbeat_s_worst"],
        "unit": "worst driver-recovery -> first re-attached worker "
                "heartbeat seconds (training arm)",
        "job_status": "SUCCEEDED",
        "outage_attributable_worker_restarts": 0,
        "training": training,
        "fleet": fleet,
    }
    print(json.dumps(out))
    return 0


def _wait_recovered_terminal(job_dir: Path, rec_proc, token: str,
                             timeout_s: float = 180.0) -> dict:
    """Poll the RECOVERED driver (through the rewritten driver.json) to
    a terminal application state, then ack finish_application so it can
    exit. Returns the final state dict."""
    from tony_tpu import constants as c
    from tony_tpu.rpc import RpcClient
    from tony_tpu.rpc.protocol import derive_role_key

    deadline = time.time() + timeout_s
    while time.time() < deadline:
        if rec_proc.poll() is not None:
            raise AssertionError(
                f"recovered driver exited early (code {rec_proc.returncode})"
                f"; see {job_dir / 'driver.log'}")
        try:
            info = json.loads((job_dir / c.DRIVER_INFO_FILE).read_text())
            if info.get("pid") != rec_proc.pid:
                time.sleep(0.3)
                continue
            rpc = RpcClient(info["host"], info["port"],
                            token=derive_role_key(token, "client"),
                            role="client", max_retries=2)
            state = rpc.call("get_application_state")
            if state["status"] in ("SUCCEEDED", "FAILED", "KILLED"):
                rpc.call("finish_application")
                rpc.close()
                return state
            rpc.close()
        except Exception:
            pass
        time.sleep(0.3)
    raise AssertionError("recovered driver never reached a terminal state")


def _spawn_recovered_driver(job_dir: Path, strip_env: list[str]):
    """Relaunch the driver with --recover (journal replay), WITHOUT the
    chaos knob that killed its predecessor."""
    env = {k: v for k, v in os.environ.items() if k not in strip_env}
    pkg = str(REPO)
    env["PYTHONPATH"] = pkg + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    logf = open(job_dir / "driver.log", "ab")
    proc = subprocess.Popen(
        [sys.executable, "-S", "-m", "tony_tpu.driver",
         "--job-dir", str(job_dir), "--recover"],
        env=env, stdout=logf, stderr=subprocess.STDOUT,
        start_new_session=True)
    return proc, logf


def _failover_training_arm() -> dict:
    import tempfile as _tempfile

    sys.path.insert(0, str(REPO))
    from tony_tpu import constants as c
    from tony_tpu.client import TonyClient
    from tony_tpu.conf import TonyConf
    from tony_tpu.events.driver_journal import load_state
    from tony_tpu.events.trace import TASK_TRACE_FILE, read_traces

    SAVE_INTERVAL = 5
    TOTAL_STEPS = 200
    STEP_MS = 50
    SIGKILL_AT = 40
    workers = 2

    td = _tempfile.mkdtemp(prefix="tony-failover-bench-")
    root = Path(td)
    cmd = (f"{sys.executable} -m tony_tpu.examples.elastic_train "
           f"--steps {TOTAL_STEPS} --save-interval {SAVE_INTERVAL} "
           f"--ckpt-dir {root}/ckpt_$TONY_TASK_INDEX")
    conf = TonyConf({
        "tony.staging.dir": str(root / "staging"),
        "tony.history.location": str(root / "history"),
        "tony.history.intermediate": str(root / "history/intermediate"),
        "tony.history.finished": str(root / "history/finished"),
        "tony.am.monitor-interval-ms": 100,
        "tony.task.registration-poll-interval-ms": 100,
        "tony.task.heartbeat-interval-ms": 250,
        "tony.task.metrics-interval-ms": 500,
        # the whole point: executors must outlive the driver by far more
        # than the kill->recover gap
        "tony.task.driver-outage-grace-ms": 60000,
        "tony.worker.instances": workers,
        "tony.worker.command": cmd,
        "tony.worker.max-restarts": 1,
        "tony.execution.env": " ".join(
            [f"ELASTIC_TRAIN_STEP_MS={STEP_MS}", "JAX_PLATFORMS=cpu"]),
    })
    # the SIGKILL knob must reach the DRIVER process only; the recovered
    # driver is spawned with it stripped (or it would re-fire: the gang
    # is already past the trigger step)
    os.environ[c.TEST_DRIVER_SIGKILL_AT_STEP] = str(SIGKILL_AT)
    t0 = time.time()
    try:
        client = TonyClient(conf, poll_interval_s=0.2)
        client.submit()
        client._driver_proc.wait(timeout=180)
    finally:
        os.environ.pop(c.TEST_DRIVER_SIGKILL_AT_STEP, None)
    t_kill = time.time()
    assert client._driver_proc.returncode == -9, (
        f"driver did not SIGKILL itself (rc "
        f"{client._driver_proc.returncode})")
    job_dir = Path(client.job_dir)

    rec, logf = _spawn_recovered_driver(
        job_dir, strip_env=[c.TEST_DRIVER_SIGKILL_AT_STEP])
    try:
        final = _wait_recovered_terminal(job_dir, rec, client.token)
        rec.wait(timeout=60)
    finally:
        if rec.poll() is None:
            import signal as _signal

            os.killpg(rec.pid, _signal.SIGKILL)
        logf.close()
    wall = time.time() - t0
    assert final["status"] == "SUCCEEDED", final

    # ---- forensics: re-adoption, zero outage-attributable restarts
    inter = root / "history/intermediate" / client.app_id
    last = {}
    all_spans = []
    for rec_ in read_traces(inter / TASK_TRACE_FILE):
        last[rec_["id"]] = rec_
        all_spans += [n for n, *_ in rec_["spans"]]
    assert all_spans.count("readopted") == workers, (
        f"expected {workers} readopted tasks, spans: {all_spans}")
    for bad in ("restarted", "preempted", "resized"):
        assert bad not in all_spans, (
            f"outage-attributable '{bad}' relaunch: {all_spans}")
    recoveries = []
    for tid, rec_ in last.items():
        spans = rec_["spans"]
        names = [n for n, *_ in spans]
        assert names[0] == "readopted" and names[-1] == "finished", names
        t_adopt = spans[0][1]
        t_beat = next(t for n, t in spans[1:] if n == "first_heartbeat")
        recoveries.append(
            {"task": tid,
             "readopt_to_first_heartbeat_s": round(t_beat - t_adopt, 3)})
    worst = max(r["readopt_to_first_heartbeat_s"] for r in recoveries)
    assert worst <= 15.0, (
        f"recovery->first-heartbeat {worst}s exceeds the bound")

    # ---- zero recompute: the children never stopped stepping
    per_worker = {}
    for w in range(workers):
        log_path = job_dir / "logs" / f"worker_{w}.steps.jsonl"
        steps = []
        for line in log_path.read_text().splitlines():
            try:
                rec_ = json.loads(line)
            except ValueError:
                continue
            if isinstance(rec_.get("train_step"), int):
                steps.append(rec_["train_step"])
        for prev, cur in zip(steps, steps[1:]):
            assert cur == prev + 1, (
                f"worker_{w}: step discontinuity {prev}->{cur} — the "
                f"outage cost training work")
        assert steps and steps[-1] == TOTAL_STEPS - 1, (
            f"worker_{w} never reached the final step")
        per_worker[f"worker_{w}"] = {"records": len(steps),
                                     "last_step": steps[-1]}
    state = load_state(job_dir / "driver.journal.jsonl")
    assert state is not None and state.recoveries >= 1

    return {
        "job_status": final["status"],
        "sigkill_at_step": SIGKILL_AT,
        "total_steps": TOTAL_STEPS,
        "step_ms": STEP_MS,
        "save_interval": SAVE_INTERVAL,
        "tasks_readopted": workers,
        "worker_restarts": 0,
        "recomputed_steps": 0,
        "recoveries": recoveries,
        "recovery_to_first_heartbeat_s_worst": worst,
        "kill_to_job_success_s": round(time.time() - t_kill, 1),
        "per_worker": per_worker,
        "wall_s": round(wall, 1),
    }


def _failover_fleet_arm() -> dict:
    import signal as _signal
    import tempfile as _tempfile
    import threading

    sys.path.insert(0, str(REPO))
    from tony_tpu import constants as c
    from tony_tpu.client import TonyClient
    from tony_tpu.conf import TonyConf
    from tony_tpu.events.driver_journal import load_state
    from tony_tpu.router import DriverDiscovery, FleetRouter

    # the TINY shape the router e2e uses: the gate is request survival
    # across a control-plane outage, not model throughput
    e = dict(vocab=64, d_model=16, n_layers=1, n_heads=2, d_ff=32,
             slots=2, max_len=96, block_size=4, prefill_chunk=8)
    REQUESTS = 48
    MAX_NEW = 24
    td = _tempfile.mkdtemp(prefix="tony-failover-fleet-")
    root = Path(td)
    serve_cmd = (
        f"{sys.executable} -m tony_tpu.cli.main serve "
        "--port $TONY_SERVE_PORT --host 127.0.0.1 "
        f"--vocab {e['vocab']} --d-model {e['d_model']} "
        f"--n-layers {e['n_layers']} --n-heads {e['n_heads']} "
        f"--d-ff {e['d_ff']} --dtype float32 --seed 0 "
        f"--slots {e['slots']} --max-len {e['max_len']} "
        f"--block-size {e['block_size']} "
        f"--prefill-chunk {e['prefill_chunk']} "
        "--max-queue 64 --drain-timeout-s 2")
    conf = TonyConf({
        "tony.staging.dir": str(root / "staging"),
        "tony.history.location": str(root / "history"),
        "tony.history.intermediate": str(root / "history/intermediate"),
        "tony.history.finished": str(root / "history/finished"),
        "tony.am.monitor-interval-ms": 100,
        "tony.application.framework": "serving",
        "tony.task.heartbeat-interval-ms": 250,
        "tony.task.driver-outage-grace-ms": 60000,
        "tony.serving.healthz-interval-ms": 200,
        "tony.replica.instances": 2,
        "tony.replica.command": serve_cmd,
        "tony.replica.max-restarts": 1,
        # slow each scheduling turn so the burst genuinely spans the
        # driver's death + recovery window
        "tony.execution.env": " ".join([
            f"PYTHONPATH={REPO}", "JAX_PLATFORMS=cpu",
            f"{c.TEST_SERVING_STEP_DELAY_MS}=10"]),
    })
    client = TonyClient(conf, poll_interval_s=0.2)
    client.submit()
    job_dir = Path(client.job_dir)
    router = FleetRouter(
        [], prefill_chunk=e["prefill_chunk"],
        discover=DriverDiscovery(str(job_dir), role="replica",
                                 token=client.token),
        health_interval_s=0.3, eject_after=2, stats_every=2, seed=0)
    results: dict[int, object] = {}
    stale_seen = {"high": False, "cleared_after_high": False}
    rec = logf = None
    try:
        deadline = time.time() + 240
        while time.time() < deadline:
            router.health_tick()
            if router.stats()["live"] == 2:
                break
            time.sleep(0.3)
        assert router.stats()["live"] == 2, (
            f"fleet never came up: {router.stats()}")
        router.start()

        import numpy as np

        rng = np.random.default_rng(7)
        chunk = e["prefill_chunk"]
        templates = [rng.integers(0, e["vocab"], size=chunk,
                                  dtype=np.int32),
                     rng.integers(0, e["vocab"], size=2 * chunk,
                                  dtype=np.int32)]
        prompts = [np.concatenate(
            [templates[i % 2],
             rng.integers(0, e["vocab"], size=1 + i % 3,
                          dtype=np.int32)]).tolist()
            for i in range(REQUESTS)]

        def call(i):
            try:
                results[i] = router.generate(
                    prompts[i], max_new_tokens=MAX_NEW, timeout_s=300)
            except Exception as exc:
                results[i] = exc

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(REQUESTS)]
        t_burst = time.time()
        for i, t in enumerate(threads):
            t.start()
            time.sleep(0.08)
            if i == REQUESTS // 3:
                # mid-burst: SIGKILL the driver. The replicas (own
                # sessions) keep serving; the router flies blind on its
                # last-known fleet until the recovered driver answers.
                os.kill(client._driver_proc.pid, _signal.SIGKILL)
                client._driver_proc.wait(timeout=10)
                t_kill = time.time()
            if i == REQUESTS // 3 + 4:
                # a few requests into the outage: discovery must be
                # marked stale while requests keep completing
                router.health_tick()
                stale_seen["high"] = router.stats()["discovery_stale"]
                rec, logf = _spawn_recovered_driver(job_dir, strip_env=[])
        for t in threads:
            t.join(timeout=300)
        t_done = time.time()
        # recovered driver up + discovery clear again
        deadline = time.time() + 60
        while time.time() < deadline:
            st = router.stats()
            if not st["discovery_stale"] and st["live"] == 2:
                stale_seen["cleared_after_high"] = True
                break
            time.sleep(0.3)
        failed = {i: r for i, r in results.items()
                  if not isinstance(r, dict)}
        assert not failed, (
            f"{len(failed)} requests failed across the driver outage: "
            f"{dict(list(failed.items())[:3])}")
        assert len(results) == REQUESTS
        assert stale_seen["high"], (
            "router never marked discovery stale during the outage")
        assert stale_seen["cleared_after_high"], (
            "discovery never recovered after the driver came back")
        state = load_state(job_dir / "driver.journal.jsonl")
        restarts = sum(t.restarts for t in state.tasks.values())
        assert restarts == 0, (
            f"replicas restarted across the outage: {restarts}")
        by_replica: dict[str, int] = {}
        for r in results.values():
            by_replica[r["replica"]] = by_replica.get(r["replica"], 0) + 1
        return {
            "requests": REQUESTS,
            "failed_requests": 0,
            "replica_restarts": 0,
            "discovery_stale_observed": True,
            "discovery_recovered": True,
            "kill_to_burst_done_s": round(t_done - t_kill, 1),
            "burst_wall_s": round(t_done - t_burst, 1),
            "per_replica_requests": by_replica,
            "driver_recoveries": state.recoveries,
        }
    finally:
        router.shutdown()
        # teardown: SIGTERM the recovered driver (its signal path stops
        # every container, adopted handles included), then hard-reap
        for proc in (rec, client._driver_proc):
            if proc is not None and proc.poll() is None:
                try:
                    os.killpg(proc.pid, _signal.SIGTERM)
                except (ProcessLookupError, PermissionError):
                    pass
        if rec is not None:
            try:
                rec.wait(timeout=20)
            except subprocess.TimeoutExpired:
                os.killpg(rec.pid, _signal.SIGKILL)
        if logf is not None:
            logf.close()


def main() -> int:
    if "--autoscale" in sys.argv:
        return run_autoscale_bench()
    if "--driver-failover" in sys.argv:
        return run_driver_failover_bench()
    if "--launch-path" in sys.argv:
        return run_launch_path_bench()
    if "--elastic" in sys.argv:
        return run_elastic_bench()
    if "--serving" in sys.argv:
        if "--slo" in sys.argv:
            return run_slo_bench()
        if "--router-ha" in sys.argv:
            return run_router_ha_bench()
        if "--tracing" in sys.argv:
            return run_distributed_tracing_bench()
        if "--paged-kv" in sys.argv:
            return run_paged_kv_bench()
        if "--disagg" in sys.argv:
            return run_disagg_bench()
        if "--streaming" in sys.argv:
            return run_serving_streaming_bench()
        if "--spec" in sys.argv:
            return run_serving_spec_bench()
        if "--replay" in sys.argv:
            return run_serving_replay_bench()
        if "--fleet" in sys.argv:
            return run_serving_fleet_bench()
        if "--overload" in sys.argv or "--chaos" in sys.argv:
            return run_serving_robustness_bench(
                chaos="--chaos" in sys.argv)
        if "--shared-prefix" in sys.argv:
            return run_shared_prefix_bench()
        return run_serving_bench()
    plain_runs, orch_runs, submits = [], [], []
    loads = []
    wall = 0.0
    _compile_cache_env("mnist", cold=True)      # pair 0 compiles cold
    with tempfile.TemporaryDirectory(prefix="tony-bench-") as td:
        tmp = Path(td)
        for rep in range(PAIRS):
            # pair 0 runs orchestrated first so its launch breakdown is
            # genuinely COLD (a preceding plain run would warm the shared
            # compile cache); later pairs alternate so within-pair drift
            # (link warming, cache effects) hits each arm first equally
            if rep % 2 == 0:
                orch, wall, t_submit, ol = run_orchestrated(tmp, rep)
                plain, pl = run_plain(tmp, rep)
            else:
                plain, pl = run_plain(tmp, rep)
                orch, wall, t_submit, ol = run_orchestrated(tmp, rep)
            orch_runs.append(orch)
            plain_runs.append(plain)
            submits.append(t_submit)
            loads.append({"orchestrated": ol, "plain": pl,
                          "order": "orch_first" if rep % 2 == 0 else "plain_first"})

    plain_all = [round(r["steps_per_sec"], 2) for r in plain_runs]
    orch_all = [round(r["steps_per_sec"], 2) for r in orch_runs]
    plain_sps = max(plain_all)
    orch_sps = max(orch_all)
    # score the MEDIAN of paired ratios: each pair's runs are adjacent in
    # time so the ratio cancels slow device drift, and the median is
    # robust to a bad pair in either direction. The per-run rate is the
    # two-point device rate (see module docstring) — the wall-rate pairing
    # is recorded alongside for continuity with r01-r04.
    paired = [
        round(o["steps_per_sec"] / p["steps_per_sec"], 4)
        for o, p in zip(orch_runs, plain_runs)
    ]
    paired_wall = [
        round(o["steps_per_sec_wall"] / p["steps_per_sec_wall"], 4)
        for o, p in zip(orch_runs, plain_runs)
    ]
    vs_baseline = round(statistics.median(paired), 4)
    best_orch = max(orch_runs, key=lambda r: r["steps_per_sec"])
    launch_cold = _launch_breakdown(orch_runs[0], submits[0])
    warm_i = min(range(1, PAIRS),
                 key=lambda i: orch_runs[i]["time_to_first_step_s"],
                 default=0)
    launch_warm = _launch_breakdown(orch_runs[warm_i], submits[warm_i])
    print(
        f"# plain: {plain_sps:.1f} steps/s {plain_all} | "
        f"orchestrated: {orch_sps:.1f} steps/s {orch_all} | "
        f"paired {paired} wall-paired {paired_wall} | "
        f"launch cold: {launch_cold['total_submit_to_first_step_s']:.1f}s "
        f"(orchestration {launch_cold['orchestration_submit_to_exec_s']:.1f}s) | "
        f"warm: {launch_warm['total_submit_to_first_step_s']:.1f}s | "
        f"last job wall: {wall:.1f}s | devices: {best_orch['num_devices']} | "
        f"acc: {best_orch['accuracy']:.3f}",
        file=sys.stderr,
    )
    print(json.dumps({
        "metric": "mnist_steps_per_sec_per_chip_orchestrated",
        "value": round(orch_sps, 2),
        "unit": "steps/s",
        "vs_baseline": vs_baseline,
        "vs_baseline_paired_all": paired,
        "vs_baseline_paired_wall_rate": paired_wall,
        "vs_baseline_max_over_max": round(orch_sps / plain_sps, 4),
        "plain_steps_per_sec_all": plain_all,
        "orchestrated_steps_per_sec_all": orch_all,
        "call_overhead_s_orchestrated": [
            r.get("call_overhead_s") for r in orch_runs],
        "call_overhead_s_plain": [
            r.get("call_overhead_s") for r in plain_runs],
        # any True here means that run's two-point fit was jitter-swamped
        # and fell back to its wall rate — inspect before trusting the pair
        "two_point_degenerate": [
            [r.get("two_point_degenerate") for r in orch_runs],
            [r.get("two_point_degenerate") for r in plain_runs]],
        "host_load_per_pair": loads,
        "launch_cold": launch_cold,
        "launch_warm": launch_warm,
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
