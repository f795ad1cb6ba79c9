"""Distributed MNIST in JAX under the tony_tpu orchestrator.

The rebuild's answer to the reference's flagship example
(tony-examples/mnist-tensorflow/mnist_distributed.py, which needs
CLUSTER_SPEC/JOB_NAME/TASK_INDEX plumbing and a TF PS strategy): here the
worker calls ``tony_tpu.train.init()`` once, shards the batch over
``jax.devices()``, and XLA handles the gradient psum.

Also the benchmark workload: --metrics-out writes steps/sec + time-to-first
-step for bench.py. The loop is written the TPU way — the dataset lives in
HBM, batches are sliced on-device, and ``--steps-per-call`` training steps
run inside one ``lax.scan`` dispatch — so the measured rate reflects device
throughput, not per-step host dispatch latency.

Throughput is a TWO-POINT fit (same pattern as bench_transformer's decode
rows): time scan blocks of N and N/2 steps, interleaved so drift hits both
equally, and divide the step delta by the median-time delta. A single
short call is mostly fixed dispatch/sync cost, and the run-to-run spread
of a wall rate is the spread of that cost, not of training speed. The
subtraction isolates the per-step device cost; the wall rate is still
reported alongside.
"""

from __future__ import annotations

import argparse
import functools
import json
import statistics
import time


@functools.lru_cache(maxsize=8)
def build_train_block(n_steps: int, nb: int, lr: float = 1e-3):
    """The jitted ``n_steps``-step train scan over a staged ``(nb, bs,
    ...)`` dataset. Module-level (not a main() closure) so the warm-pool
    warmup hook (examples/warmup_mnist.py) can build the IDENTICAL
    program and prepay its backend compile into the persistent
    compilation cache before a task is ever adopted — the adopted
    entrypoint's compile is then a cache hit. (The adopted run executes
    this file afresh via runpy as ``__main__``, a new module namespace,
    so the jit OBJECT itself does not carry over and tracing is still
    paid; the memoization only dedupes builds within one namespace.)"""
    import jax
    import jax.numpy as jnp
    import optax

    from tony_tpu.models.mnist import loss_fn

    opt = optax.adam(lr)

    @jax.jit
    def run_block(params, opt_state, xb_all, yb_all, start):
        def body(carry, i):
            params, opt_state = carry
            j = (start + i) % nb
            xb = jax.lax.dynamic_index_in_dim(xb_all, j, keepdims=False)
            yb = jax.lax.dynamic_index_in_dim(yb_all, j, keepdims=False)
            loss, grads = jax.value_and_grad(loss_fn)(params, xb, yb)
            updates, opt_state = opt.update(grads, opt_state)
            return (optax.apply_updates(params, updates), opt_state), loss

        (params, opt_state), losses = jax.lax.scan(
            body, (params, opt_state), jnp.arange(n_steps)
        )
        return params, opt_state, losses[-1]

    return run_block


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=1000)
    parser.add_argument("--steps-per-call", type=int, default=50)
    parser.add_argument("--batch-size", type=int, default=512)
    parser.add_argument("--lr", type=float, default=1e-3)
    parser.add_argument("--metrics-out", default="")
    args = parser.parse_args(argv)

    t_start = time.time()
    from tony_tpu.utils.jaxenv import place_compile_cache

    place_compile_cache()
    import jax
    import jax.numpy as jnp
    import optax

    from tony_tpu import train
    from tony_tpu.models.mnist import accuracy, init_mlp, synthetic_mnist
    from tony_tpu.parallel import MeshSpec, build_mesh

    t_import = time.time()

    info = train.init()
    mesh = build_mesh(MeshSpec(data=-1, fsdp=1))
    P = jax.sharding.PartitionSpec
    repl = jax.sharding.NamedSharding(mesh, P())

    bs = args.batch_size
    x, y = synthetic_mnist(jax.random.PRNGKey(0), n=8192)
    nb = x.shape[0] // bs
    # Dataset staged once into HBM as (nb, batch, ...) with each batch
    # sharded over the data axis; per-step slicing happens on-device.
    batch_sharding = jax.sharding.NamedSharding(mesh, P(None, "data"))
    xb_all = jax.device_put(x[: nb * bs].reshape(nb, bs, -1), batch_sharding)
    yb_all = jax.device_put(y[: nb * bs].reshape(nb, bs), batch_sharding)

    params = jax.device_put(init_mlp(jax.random.PRNGKey(1)), repl)
    opt = optax.adam(args.lr)
    opt_state = jax.device_put(opt.init(params), repl)
    # block on EVERY staged buffer: device_put is async and independent
    # transfers have no ordering, so without this the dataset upload leaks
    # into the compile phase of the launch breakdown
    jax.block_until_ready((params, opt_state, xb_all, yb_all))
    t_ready = time.time()  # backend up, data staged in HBM

    spc = min(args.steps_per_call, args.steps)
    spc_short = max(1, spc // 2)

    # the dataset is an ARGUMENT, not a closure capture: captured device
    # arrays get baked into the executable as constants, which bloated the
    # cached program to 53MB and made even a persistent-cache HIT pay
    # seconds of executable load (the round-3 bench's "warm relaunch
    # still compiles 13s").
    # As an argument the program is ~1MB and a warm relaunch loads fast.
    # (Builder hoisted to module level — build_train_block — so the
    # warm-pool warmup hook can prepay the identical program's compile.)
    run_long = build_train_block(spc, nb, args.lr)
    run_short = build_train_block(spc_short, nb, args.lr)

    # warm-up/compile call (excluded from throughput, included in launch
    # latency — the block runs spc steps, but compile dominates its cost).
    # float() is the sync, here and in the timed loop: a device->host
    # transfer of the result is the hard sync.
    params, opt_state, loss = run_long(params, opt_state, xb_all, yb_all,
                                       jnp.int32(0))
    float(loss)
    t_first_step = time.time()
    # the short block is measurement apparatus, not the user's first step:
    # compile it after the launch clock stops
    params, opt_state, loss = run_short(params, opt_state, xb_all, yb_all,
                                        jnp.int32(spc))
    float(loss)

    n_rounds = max(1, args.steps // spc)
    times_long, times_short = [], []
    step = spc + spc_short

    def timed(block, start):
        t0 = time.time()
        p, o, loss = block(params, opt_state, xb_all, yb_all, jnp.int32(start))
        lv = float(loss)  # hard sync
        return time.time() - t0, p, o, lv

    for _ in range(n_rounds):
        # long/short adjacent within a round: link drift cancels in the diff
        dt, params, opt_state, final_loss = timed(run_long, step)
        times_long.append(dt)
        step += spc
        dt, params, opt_state, final_loss = timed(run_short, step)
        times_short.append(dt)
        step += spc_short

    median_long = statistics.median(times_long)
    median_short = statistics.median(times_short)
    # two-point fit: per-step device seconds from the step delta; the fixed
    # per-call cost (dispatch + host sync) cancels out. A
    # non-positive delta means host jitter swamped the device signal — fall
    # back to the (pessimistic) wall rate and FLAG it rather than emitting
    # a ~1e9 steps/s artifact that would poison the bench gate silently.
    # spc == spc_short (--steps-per-call 1: 1 // 2 floors to the same block
    # size) has no step delta to fit AT ALL — same fallback, not a
    # ZeroDivisionError.
    delta = median_long - median_short
    degenerate = delta <= 0 or spc == spc_short
    step_s = (median_long / spc) if degenerate else delta / (spc - spc_short)
    acc = float(accuracy(params, x[:2048], y[:2048]))
    metrics = {
        "steps_per_sec": 1.0 / step_s,
        "two_point_degenerate": degenerate,
        "steps_per_sec_wall": spc / median_long,
        "call_overhead_s": round(median_long - spc * step_s, 5),
        "window_call_times_s": [round(t, 5) for t in times_long],
        "window_call_times_short_s": [round(t, 5) for t in times_short],
        "steps_per_call": spc,
        "steps_per_call_short": spc_short,
        "time_to_first_step_s": t_first_step - t_start,
        # launch-latency breakdown (BASELINE.md metric 2 diagnosis): process
        # start epoch lets the submitter compute its orchestration share
        # (same-host clocks), the phases split the in-process remainder
        "t_start_epoch": t_start,
        "import_s": t_import - t_start,
        "backend_and_data_s": t_ready - t_import,
        "compile_first_block_s": t_first_step - t_ready,
        "final_loss": final_loss,
        "accuracy": acc,
        "num_devices": jax.device_count(),
        "process": info,
    }
    print(json.dumps(metrics))
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(metrics, f)
    return 0 if acc > 0.5 else 1


if __name__ == "__main__":
    raise SystemExit(main())
