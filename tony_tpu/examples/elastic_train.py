"""Elastic-training drill: a TINY checkpointed trainer built to be killed.

The workload behind ``bench.py --elastic`` and the robustness e2e tests
(docs/training-robustness.md): a deterministic jitted update on a small
state, checkpointed every ``--save-interval`` steps through
``CheckpointManager.save_async`` (overlapped, donation-safe), with the
full drain contract wired up:

- SIGTERM (cloud preemption / driver resize drain) → checkpoint at the
  next step boundary, exit ``EXIT_PREEMPTED``;
- the executor-relayed ``$TONY_STEP_LOG.preempt`` flag (driver preempt
  command) → same, via ``StepTimer.preempt_requested``;
- on relaunch, resume from ``latest_step()+1`` — never step 0.

Every step ticks the StepTimer with ``train_step=<global step>`` at
``window=1``, so the JSONL is a per-step record stream: recovery tests
assert step-counter continuity (no silent skips, ≤ save_interval steps
recomputed) straight from it. Deliberately NO ``jax.distributed``: the
drill exercises the orchestration contract on any host, including the
CPU-only CI container where multiprocess XLA collectives come and go
(ROADMAP known flakes).

Fault hooks (env, mirroring the TEST_* style):
  ELASTIC_TRAIN_KILL=<task_index>:<step>   SIGKILL *self* at that step —
      but only once per job: the marker file ELASTIC_TRAIN_KILL_ONCE
      guards it so the relaunched attempt survives.
  ELASTIC_TRAIN_STEP_MS=<ms>               per-step sleep (gives the
      driver time to observe/kill mid-train; also the straggler lever —
      a per-task override rides tony.<role>.env).
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import time


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=60,
                        help="total global steps (resume-aware: a "
                             "relaunch continues toward the same total)")
    parser.add_argument("--ckpt-dir", required=True)
    parser.add_argument("--save-interval", type=int, default=5)
    parser.add_argument("--dim", type=int, default=64)
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from tony_tpu.constants import (
        ENV_GANG_GENERATION, ENV_STEP_LOG, ENV_TASK_INDEX, EXIT_PREEMPTED,
    )
    from tony_tpu.train.checkpoint import CheckpointManager
    from tony_tpu.train.profiling import StepTimer

    task_index = int(os.environ.get(ENV_TASK_INDEX, "0"))
    generation = int(os.environ.get(ENV_GANG_GENERATION, "0"))
    step_ms = float(os.environ.get("ELASTIC_TRAIN_STEP_MS", "0") or 0)
    kill_spec = os.environ.get("ELASTIC_TRAIN_KILL", "")
    kill_once = os.environ.get("ELASTIC_TRAIN_KILL_ONCE", "")
    kill_at = -1
    if kill_spec:
        try:
            idx, at = kill_spec.split(":")
            if int(idx) == task_index:
                kill_at = int(at)
        except ValueError:
            print(f"bad ELASTIC_TRAIN_KILL spec: {kill_spec}",
                  file=sys.stderr)

    @jax.jit
    def update(state):
        # deterministic, step-dependent: a resumed run recomputes the
        # exact same trajectory, so the final value proves continuity
        return {"w": state["w"] * 0.999 + jnp.sin(state["step"]),
                "step": state["step"] + 1}

    mgr = CheckpointManager(args.ckpt_dir, save_interval=args.save_interval)
    state = {"w": jnp.zeros(args.dim, jnp.float32),
             "step": jnp.int32(0)}
    start_step = 0
    latest = mgr.latest_step()
    if latest is not None:
        state = mgr.restore(template=state)
        start_step = latest + 1
        print(f"resumed from checkpoint step {latest}")

    timer = StepTimer(os.environ.get(ENV_STEP_LOG) or None, window=1)
    preempted = {"flag": False}
    signal.signal(signal.SIGTERM,
                  lambda *_: preempted.__setitem__("flag", True))

    def drain_exit(step_i: int) -> int:
        mgr.save_async(step_i, state)
        timer.note_checkpoint(step_i)
        mgr.wait()
        mgr.close()
        timer.close()
        print(f"preempted: checkpointed step {step_i}, exiting")
        return EXIT_PREEMPTED

    # priming tick: StepTimer only records once a duration exists, and
    # the continuity assertions need a record for EVERY training step of
    # every attempt — including each attempt's first
    timer.tick()
    for step_i in range(start_step, args.steps):
        if step_i == kill_at and (not kill_once
                                  or not os.path.exists(kill_once)):
            if kill_once:
                with open(kill_once + ".tmp", "w") as f:
                    f.write(str(step_i))
                os.replace(kill_once + ".tmp", kill_once)
            print(f"fault injection: SIGKILLing self at step {step_i}",
                  file=sys.stderr, flush=True)
            os.kill(os.getpid(), signal.SIGKILL)
        state = update(state)
        if step_ms:
            time.sleep(step_ms / 1000)
        timer.tick(train_step=step_i, generation=generation)
        if preempted["flag"] or timer.preempt_requested:
            return drain_exit(step_i)
        if step_i % args.save_interval == 0 and step_i > 0:
            mgr.save_async(step_i, state)
            timer.note_checkpoint(step_i)

    mgr.save_async(args.steps - 1, state)
    timer.note_checkpoint(args.steps - 1)
    mgr.wait()
    mgr.close()
    timer.close()
    result = {"final_step": int(state["step"]),
              "final_w0": float(state["w"][0]),
              "task_index": task_index}
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
