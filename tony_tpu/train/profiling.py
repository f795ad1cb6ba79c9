"""Profiling/tracing hooks.

The reference has NO tracing subsystem (SURVEY.md §5: "Tracing/profiling:
none"); its closest asset is TensorBoard wiring. Here the slot is filled
properly: JAX profiler traces (xplane protos viewable in TensorBoard's
profile plugin or Perfetto) captured per-step-window, plus a lightweight
step-timing log the portal can serve alongside job history.
"""

from __future__ import annotations

import contextlib
import json
import logging
import time
from pathlib import Path

from ..metrics import sample_tpu_metrics

log = logging.getLogger(__name__)


@contextlib.contextmanager
def trace(log_dir: str | Path, enabled: bool = True):
    """Capture a JAX profiler trace (xplane) into log_dir/plugins/profile."""
    if not enabled:
        yield
        return
    import jax

    Path(log_dir).mkdir(parents=True, exist_ok=True)
    jax.profiler.start_trace(str(log_dir))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _start_profiler(log_dir: str) -> None:
    """jax.profiler.start_trace behind one seam (tests stub the jax
    functions; product code never needs jax imported until a capture
    actually starts)."""
    import jax

    Path(log_dir).mkdir(parents=True, exist_ok=True)
    jax.profiler.start_trace(log_dir)


def _stop_profiler() -> None:
    import jax

    jax.profiler.stop_trace()


class StepTimer:
    """Rolling step-time stats written as JSONL next to the job's history
    events — cheap always-on tracing for launch-latency and throughput
    regressions. Durations come from ``time.monotonic()`` — the wall
    clock can JUMP (NTP slew, manual set) and a backward jump used to
    corrupt step durations (negative dt poisoning the rolling window);
    the record's ``ts`` stays wall-clock, it only labels the line. Same
    clock contract as the serving traces (observability.RequestTrace)."""

    def __init__(self, out_path: str | Path | None = None, window: int = 50,
                 compile_warm_on_step: bool = True):
        from ..observability import Histogram, install_compile_telemetry

        self._out = Path(out_path) if out_path else None
        self._window = window
        # whether a first measured step draws the process's compile
        # warmup line. True for training loops (step 1 ran every
        # program shape). ServeApp's loop-TURN timer passes False: its
        # turns start ticking before any request compiled anything, and
        # the serving warm line belongs to the first DELIVERED
        # completion (ServeApp._deliver) — marking it here would count
        # the legitimate warm-up compiles as a recompile storm.
        self._compile_warm_on_step = compile_warm_on_step
        self._t_last: float | None = None
        self._times: list[float] = []
        # cumulative step-time distribution (the rolling window forgets;
        # skew detection needs the tail): quantiles ride the JSONL record,
        # which the executor's TaskMonitor samples into the metrics push —
        # per-worker step skew becomes visible on the driver's /metrics
        self.hist = Histogram()
        self.step = 0
        # compile-time visibility: every StepTimer owner (training loops,
        # the serving scheduling loop) gets the process-global
        # jax.monitoring listener installed; the JSONL records then carry
        # the compile snapshot so XLA compile time per worker rides the
        # same channel as step quantiles (TaskMonitor._sample_step_log)
        self._compile = install_compile_telemetry()
        # on-demand profiler capture (docs/observability.md): when this
        # timer writes a step log, `<out_path>.profile` is the flag file
        # the executor drops to request a capture; polled at record
        # cadence (every `window` steps — never per step)
        self._profile_stop_t: float | None = None
        self._atexit_armed = False
        # preemption drain (docs/training-robustness.md): the executor
        # drops `<out_path>.preempt` when the driver relays a notice (or
        # the executor itself is SIGTERMed); the poll is TIME-gated
        # (every ~0.25s, not per step — a 50k-steps/s loop must not pay
        # 50k stat() calls) and `preempt_requested` tells the training
        # loop to checkpoint at this step boundary and exit.
        self.preempt_requested = False
        self._preempt_poll_t = 0.0
        # checkpoint recency (note_checkpoint): rides the JSONL records
        # so the driver can render driver_checkpoint_age_s centrally
        self._ckpt_step: int | None = None
        self._ckpt_ts: float | None = None

    def tick(self, **extra) -> float | None:
        """Call once per training step; returns the last step's duration."""
        now = time.monotonic()
        dt = None
        if self._t_last is not None:
            dt = now - self._t_last
            self._times.append(dt)
            if len(self._times) > self._window:
                self._times.pop(0)
            self.hist.observe(dt)
            # one full measured step means warmup compiles are behind us:
            # compiles from here on are recompiles (idempotent; only the
            # process's first measured step draws the line)
            if self._compile_warm_on_step:
                self._compile.mark_warm()
        self._t_last = now
        self.step += 1
        if self._profile_stop_t is not None and now >= self._profile_stop_t:
            self._finish_profile()
        if now - self._preempt_poll_t >= 0.25:
            self._preempt_poll_t = now
            self._poll_preempt_flag()
        if self._out and dt is not None and self.step % self._window == 0:
            rec = {
                "step": self.step,
                "mean_step_s": sum(self._times) / len(self._times),
                "steps_per_sec": len(self._times) / sum(self._times),
                "p50_s": round(self.hist.quantile(0.5), 6),
                "p99_s": round(self.hist.quantile(0.99), 6),
                "ts": time.time(),
                **extra,
            }
            snap = self._compile.snapshot()
            rec["xla_compiles"] = snap["compiles"]
            rec["xla_compile_time_s"] = snap["compile_time_s"]
            rec["xla_recompiles_post_warm"] = snap["recompiles_post_warm"]
            if self._ckpt_step is not None:
                rec["last_ckpt_step"] = self._ckpt_step
                rec["last_ckpt_ts"] = self._ckpt_ts
            # this process owns the chip, so it is the one that may ask
            # libtpu about it; the executor's TaskMonitor reads the
            # numbers from this record ({} off the TPU)
            rec.update(sample_tpu_metrics())
            # best-effort, like the rest of the telemetry path: a missing
            # log dir (remote executor, no logs/ in the unpacked archive)
            # or a full disk must not kill the training loop
            try:
                self._out.parent.mkdir(parents=True, exist_ok=True)
                with open(self._out, "a") as f:
                    f.write(json.dumps(rec) + "\n")
            except OSError as e:
                log.warning("step log write failed: %s", e)
            self._poll_profile_flag()
        return dt

    def note_checkpoint(self, step: int) -> None:
        """Tell the timer a checkpoint for ``step`` just finished (or was
        handed to the async writer): the next JSONL record carries
        ``last_ckpt_step``/``last_ckpt_ts`` so checkpoint recency is
        centrally visible as ``driver_checkpoint_age_s``."""
        self._ckpt_step = int(step)
        self._ckpt_ts = time.time()

    # --------------------------------------------------- preemption drain
    def _poll_preempt_flag(self) -> None:
        """Check for the executor's ``<out>.preempt`` drain notice
        (tmp+rename written, so never torn). Sticky once seen: the loop
        reads ``preempt_requested`` at its step boundary, checkpoints,
        and exits constants.EXIT_PREEMPTED."""
        if self.preempt_requested or self._out is None:
            return
        from .. import constants as c

        flag = self._out.with_name(self._out.name + c.PREEMPT_REQUEST_SUFFIX)
        try:
            present = flag.exists()
        except OSError:
            return
        if not present:
            return
        try:
            flag.unlink()
        except OSError:
            # presence IS the signal; a failed unlink only risks a
            # second (idempotent) notice
            pass
        log.warning("preemption notice received: checkpoint-and-exit at "
                    "this step boundary")
        self.preempt_requested = True

    # ------------------------------------------- on-demand profiler capture
    @property
    def _flag_path(self) -> Path | None:
        """`$TONY_STEP_LOG.profile` — the flag-file contract the executor
        uses to relay a driver profile command into this process."""
        if self._out is None:
            return None
        from .. import constants as c

        return self._out.with_name(self._out.name + c.PROFILE_REQUEST_SUFFIX)

    def _poll_profile_flag(self) -> None:
        flag = self._flag_path
        if flag is None or self._profile_stop_t is not None:
            return
        try:
            if not flag.exists():
                return
            req = json.loads(flag.read_text())
            flag.unlink()
            # extraction stays inside the tolerant block: valid JSON
            # that is not a dict, or a non-numeric "seconds", must be
            # dropped like a torn flag, not crash the training loop
            seconds = float(req.get("seconds", 5.0))
            out_dir = str(req.get("out_dir")
                          or self._out.parent / "profiles"
                          / f"step{self.step}")
        except (OSError, ValueError, TypeError, AttributeError) as e:
            # a torn or unreadable request must not kill the training
            # loop; drop the flag so it doesn't wedge future requests
            log.warning("profile request unreadable: %s", e)
            try:
                flag.unlink()
            except OSError:
                pass
            return
        try:
            _start_profiler(out_dir)
        except Exception:
            log.exception("profiler capture failed to start")
            return
        self._profile_stop_t = time.monotonic() + max(0.0, seconds)
        # the training loop may END inside the capture window (job
        # finishes, window longer than the remaining run): without a
        # stop the xplane buffer is never flushed and the dump is
        # silently empty. close() handles the explicit path; atexit
        # covers loops that just return.
        if not self._atexit_armed:
            import atexit

            atexit.register(self.close)
            self._atexit_armed = True
        log.info("profiler capture started (%.1fs) -> %s", seconds, out_dir)

    def _finish_profile(self) -> None:
        self._profile_stop_t = None
        try:
            _stop_profiler()
            log.info("profiler capture finished")
        except Exception:
            log.exception("profiler capture failed to stop")

    def close(self) -> None:
        """Stop an in-progress profiler capture early so the xplane dump
        flushes (idempotent). Called at training-loop end and via atexit
        — a capture window outliving the job must still produce a usable
        dump, cut short at the point the work stopped."""
        if self._profile_stop_t is not None:
            log.info("capture window outlived the loop: stopping early")
            self._finish_profile()

    def reset_interval(self) -> None:
        """Forget the last tick instant (the rolling window survives).
        For callers whose steps are not back-to-back — a serving loop
        that idles between requests must not record the idle gap as one
        giant 'step' when work resumes."""
        self._t_last = None

    @property
    def steps_per_sec(self) -> float:
        if not self._times:
            return 0.0
        return len(self._times) / sum(self._times)
