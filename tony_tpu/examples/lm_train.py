"""Flagship-model training job: sharded transformer LM with checkpoint/resume.

Demonstrates the full TPU-native stack in one script:
- ``tony_tpu.train.init()`` joins the multi-host job (env contract)
- mesh + rule table from a CLI string ("data=2,fsdp=2,tensor=2" or
  "seq=8" for ring-attention long-context)
- jitted train step with FSDP/TP/SP/EP shardings
- orbax checkpointing with resume-from-latest (so driver retry continues
  training instead of restarting — beyond the reference's re-run semantics)
- step timing + optional JAX profiler trace

Run standalone:      python -m tony_tpu.examples.lm_train --steps 50
Run under tony-tpu:  tony-tpu local --command "python -m tony_tpu.examples.lm_train"
"""

from __future__ import annotations

import argparse
import json
import os
import time


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=100)
    parser.add_argument("--batch-size", type=int, default=16)
    parser.add_argument("--seq-len", type=int, default=256)
    parser.add_argument("--mesh", default="fsdp=-1",
                        help="e.g. 'data=2,fsdp=2,tensor=2' or 'seq=8'")
    parser.add_argument("--d-model", type=int, default=256)
    parser.add_argument("--n-layers", type=int, default=4)
    parser.add_argument("--n-heads", type=int, default=8)
    parser.add_argument("--d-ff", type=int, default=1024)
    parser.add_argument("--vocab", type=int, default=4096)
    parser.add_argument("--n-experts", type=int, default=0)
    parser.add_argument("--dtype", default="bfloat16")
    parser.add_argument("--remat", action="store_true")
    parser.add_argument("--remat-policy", default="full",
                        choices=("full", "dots", "attn"),
                        help="with --remat: 'full' recomputes everything; "
                             "'dots' saves matmul outputs; 'attn' saves the "
                             "flash kernel's out+lse so the backward never "
                             "re-runs the attention forward (the long-"
                             "context choice: +7-17% at L>=8k)")
    parser.add_argument("--checkpoint-dir", default="")
    parser.add_argument("--checkpoint-every", type=int, default=50)
    parser.add_argument("--profile-dir", default="")
    parser.add_argument("--metrics-out", default="")
    parser.add_argument("--data", default="",
                        help="token .bin file (tony_tpu.data); empty = synthetic")
    parser.add_argument("--data-seed", type=int, default=0)
    parser.add_argument("--data-raw-dtype", default="uint16",
                        help="dtype for headerless (nanoGPT-style) token files")
    parser.add_argument("--eval-every", type=int, default=0,
                        help="evaluate on a held-out tail split every N steps (0=off; needs --data)")
    parser.add_argument("--eval-frac", type=float, default=0.05)
    parser.add_argument("--eval-batches", type=int, default=8)
    args = parser.parse_args(argv)

    from tony_tpu.utils.jaxenv import device_report, place_compile_cache

    place_compile_cache()
    import jax
    import jax.numpy as jnp

    from tony_tpu import train
    from tony_tpu.constants import ENV_STEP_LOG
    from tony_tpu.models import transformer
    from tony_tpu.parallel import (
        DP_RULES, EP_RULES, FSDP_TP_RULES, merge_rules, mesh_from_string,
    )
    from tony_tpu.train.profiling import StepTimer, trace

    info = train.init()
    mesh = mesh_from_string(args.mesh)
    use_ring = mesh.shape.get("seq", 1) > 1
    rules = merge_rules(
        DP_RULES if use_ring else FSDP_TP_RULES,
        EP_RULES if args.n_experts else {},
    )

    cfg = transformer.TransformerConfig(
        vocab_size=args.vocab, d_model=args.d_model, n_layers=args.n_layers,
        n_heads=args.n_heads, n_kv_heads=args.n_heads, d_ff=args.d_ff,
        max_seq_len=args.seq_len, n_experts=args.n_experts,
        dtype=getattr(jnp, args.dtype), remat=args.remat,
        remat_policy=args.remat_policy,
    )
    bundle = train.create_train_step(cfg, mesh, rules=rules)
    params, opt_state = bundle.params, bundle.opt_state
    n_params = transformer.num_params(params)
    if info["process_id"] == 0:
        print(f"model: {n_params/1e6:.1f}M params | mesh {dict(mesh.shape)} | "
              f"ring={use_ring} | devices {jax.device_count()}")

    start_step = 0
    mgr = None
    if args.checkpoint_dir:
        from tony_tpu.train.checkpoint import CheckpointManager

        mgr = CheckpointManager(args.checkpoint_dir, save_interval=args.checkpoint_every)
        latest = mgr.latest_step()
        if latest is not None:
            from tony_tpu.train.checkpoint import sharded_restore_template

            # every shard restores straight onto the sharding the train
            # step expects, from shapes alone: the fresh initialisation is
            # dropped first, because a chip that holds one copy of
            # parameters + optimizer state at a realistic size does not
            # hold two (the step then fails to load: RESOURCE_EXHAUSTED)
            template = {
                "params": sharded_restore_template(
                    params, bundle.param_shardings),
                "opt_state": sharded_restore_template(
                    opt_state, bundle.opt_shardings),
            }
            params = opt_state = bundle.params = bundle.opt_state = None
            restored = mgr.restore(template=template)
            params, opt_state = restored["params"], restored["opt_state"]
            start_step = latest + 1
            print(f"resumed from checkpoint step {latest}")

    loader = None
    if args.data:
        from tony_tpu.data import (
            PrefetchLoader, ShardedBatchLoader, TokenDataset,
            device_put_sharded_batch, loader_shard_info, seq_shard_info,
        )

        from tony_tpu.data.dataset import has_ttpu_magic

        if has_ttpu_magic(args.data):
            # TTPU header present: parse it strictly (a bad version/dtype
            # must error, not be reinterpreted as raw garbage tokens)
            dataset = TokenDataset.from_bin(args.data)
        else:
            # headerless raw stream (nanoGPT/llm.c style)
            import numpy as _np
            dataset = TokenDataset.from_raw(
                args.data, getattr(_np, args.data_raw_dtype))
        corpus_max = dataset.max_token()
        if corpus_max >= args.vocab:
            raise SystemExit(
                f"--data contains token id {corpus_max} >= --vocab "
                f"{args.vocab}; retokenize or raise --vocab"
            )
        val_dataset = None
        if args.eval_every > 0:
            dataset, val_dataset = dataset.split(args.eval_frac)
        # per-process shards when a batch axis is mesh-sharded; on a
        # seq/tensor-only mesh every host loads the identical full batch —
        # EXCEPT along a multi-host seq axis, where each host reads only
        # its sequence slice (ring/Ulysses long-context data plane)
        pi, pc = loader_shard_info(
            mesh, info["process_id"], info["num_processes"], rules=bundle.rules)
        si, sc = seq_shard_info(mesh, info["process_id"], rules=bundle.rules)
        if sc > 1 and pc > 1:
            # loader_shard_info assumes the batch axes span all processes
            # (rows p::P), which contradicts a cross-host seq axis — the
            # row split would misalign with the device layout. Fail loudly
            # rather than train on silently wrong data.
            raise SystemExit(
                "unsupported data layout: batch axes and the seq axis both "
                "span hosts; put the batch axes within hosts (or drop to a "
                "seq-only cross-host mesh) for sequence-sharded loading"
            )
        loader = PrefetchLoader(ShardedBatchLoader(
            dataset, args.batch_size, args.seq_len, seed=args.data_seed,
            process_index=pi, process_count=pc, start_step=start_step,
            seq_shard_index=si, seq_shard_count=sc,
        ))
        if val_dataset is not None:
            try:
                val_loader = ShardedBatchLoader(
                    val_dataset, args.batch_size, args.seq_len, seed=0,
                    process_index=pi, process_count=pc,
                    seq_shard_index=si, seq_shard_count=sc,
                )
            except ValueError as e:
                raise SystemExit(
                    f"eval split too small for evaluation ({e}); raise "
                    "--eval-frac or lower --batch-size/--seq-len"
                ) from e

    def next_batch(step_i):
        if loader is None:
            return train.synthetic_lm_batch(
                jax.random.PRNGKey(step_i), args.batch_size, args.seq_len,
                args.vocab,
            )
        return device_put_sharded_batch(
            next(loader), mesh, sharding=bundle.tok_sharding,
            global_batch=args.batch_size, global_seq=args.seq_len)

    def run_eval(params) -> float:
        """Mean held-out loss over a fixed deterministic batch set."""
        import math
        n = min(args.eval_batches, val_loader.steps_per_epoch)
        total = 0.0
        for i in range(n):
            vt, vy = device_put_sharded_batch(
                val_loader.batch_at(i), mesh, sharding=bundle.tok_sharding,
                global_batch=args.batch_size, global_seq=args.seq_len)
            total += float(bundle.eval_fn(params, vt, vy))
        loss = total / max(n, 1)
        if info["process_id"] == 0:
            print(f"  eval: loss {loss:.4f} ppl {math.exp(min(loss, 30)):.2f}")
        return loss

    # TONY_STEP_LOG (set by the executor): step-time JSONL the
    # TaskMonitor samples so per-worker step quantiles reach the driver's
    # /metrics — running standalone (no executor) leaves it off
    timer = StepTimer(os.environ.get(ENV_STEP_LOG) or None)

    # preemption drain (docs/training-robustness.md): a SIGTERM to this
    # process — the cloud reclaiming the host, or the driver draining the
    # gang for an elastic resize — checkpoints at the NEXT step boundary
    # and exits EXIT_PREEMPTED so the relaunch is budget-free and resumes
    # at most one step behind. The executor-relayed notice arrives the
    # same way via timer.preempt_requested (the .preempt flag file).
    import signal as _signal

    preempted = {"flag": False}
    _signal.signal(_signal.SIGTERM,
                   lambda *_: preempted.__setitem__("flag", True))

    def _drain_exit(step_i: int) -> int:
        from tony_tpu.constants import EXIT_PREEMPTED

        if mgr is not None:
            mgr.save_async(step_i, {"params": params, "opt_state": opt_state})
            timer.note_checkpoint(step_i)
            mgr.wait()
            mgr.close()
        timer.close()
        print(f"preempted: checkpointed step {step_i}, exiting")
        return EXIT_PREEMPTED

    losses = []
    last_eval = None
    last_eval_step = -1
    t0 = time.time()
    try:
        with trace(args.profile_dir, enabled=bool(args.profile_dir)):
            for step_i in range(start_step, start_step + args.steps):
                tokens, targets = next_batch(step_i)
                params, opt_state, metrics = bundle.step_fn(
                    params, opt_state, tokens, targets
                )
                timer.tick(train_step=step_i)
                if preempted["flag"] or timer.preempt_requested:
                    return _drain_exit(step_i)
                if step_i % 20 == 0:
                    loss = float(metrics["loss"])  # sync point
                    losses.append(loss)
                    if info["process_id"] == 0:
                        print(f"step {step_i}: loss {loss:.4f} "
                              f"({timer.steps_per_sec:.2f} steps/s)")
                if mgr is not None and step_i % args.checkpoint_every == 0 and step_i > 0:
                    # overlapped: the host snapshot happens here, the disk
                    # write happens behind the next steps
                    mgr.save_async(step_i,
                                   {"params": params, "opt_state": opt_state})
                    timer.note_checkpoint(step_i)
                if (loader is not None and args.eval_every > 0
                        and step_i > start_step
                        and step_i % args.eval_every == 0):
                    last_eval = run_eval(params)
                    last_eval_step = step_i
    finally:
        if loader is not None:
            loader.close()
    final_loss = float(metrics["loss"])
    wall = time.time() - t0
    # final eval — unless the last loop step just ran the identical one
    if (loader is not None and args.eval_every > 0
            and last_eval_step != start_step + args.steps - 1):
        last_eval = run_eval(params)
    if mgr is not None:
        mgr.save_async(start_step + args.steps - 1,
                       {"params": params, "opt_state": opt_state})
        timer.note_checkpoint(start_step + args.steps - 1)
        mgr.wait()
        mgr.close()

    tokens_per_step = args.batch_size * args.seq_len
    result = {
        "final_loss": final_loss,
        "steps_per_sec": args.steps / wall,
        "tokens_per_sec": args.steps * tokens_per_step / wall,
        "n_params": n_params,
        "mesh": {k: int(v) for k, v in dict(mesh.shape).items()},
        "device": device_report(),
    }
    if last_eval is not None:
        import math
        result["eval_loss"] = last_eval
        result["eval_ppl"] = math.exp(min(last_eval, 30))
    if info["process_id"] == 0:
        print(json.dumps(result))
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(result, f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
