"""Generate from a checkpoint trained by lm_train — the serve-side half of
the flagship model (KV-cache decode, models/generate.py).

    # train with checkpoints, then:
    python -m tony_tpu.examples.lm_generate \
        --checkpoint-dir /ckpt --vocab 4096 --d-model 256 --n-layers 4 \
        --n-heads 8 --d-ff 1024 --prompt "1 2 3 4" --max-new 64

Model hyperparams must match the training run (checkpoints store only
weights). Prompts are whitespace-separated token ids — tokenizers live
outside the framework, same stance as the data plane. Also reports decode
throughput (tokens/sec), the serving-side counterpart of lm_train's
tokens/sec.

No reference counterpart: TonY has no model layer (SURVEY.md §2.3).
"""

from __future__ import annotations

import argparse
import json
import time


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--checkpoint-dir", default="",
                        help="orbax dir from lm_train; empty = random init")
    parser.add_argument("--d-model", type=int, default=256)
    parser.add_argument("--n-layers", type=int, default=4)
    parser.add_argument("--n-heads", type=int, default=8)
    parser.add_argument("--d-ff", type=int, default=1024)
    parser.add_argument("--vocab", type=int, default=4096)
    parser.add_argument("--n-experts", type=int, default=0,
                        help="must match the training run's --n-experts")
    parser.add_argument("--dtype", default="bfloat16")
    parser.add_argument("--prompt", default="1 2 3 4 5 6 7 8",
                        help="whitespace-separated token ids")
    parser.add_argument("--max-new", type=int, default=64)
    parser.add_argument("--temperature", type=float, default=0.0)
    parser.add_argument("--top-k", type=int, default=0)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--kv-dtype", default="native",
                        choices=("native", "int8"),
                        help="'int8' quantizes the KV cache: half the HBM "
                             "capacity and faster long-context decode, at "
                             "the cost of bit-exactness vs the full forward")
    parser.add_argument("--weight-dtype", default="native",
                        choices=("native", "int8"),
                        help="'int8' (w8a16, dense models) streams int8 "
                             "decode weights — ~1.5x decode throughput on "
                             "the bandwidth-bound step, within int8 "
                             "resolution of the native output")
    parser.add_argument("--stop-tokens", default="",
                        help="whitespace-separated token ids that end a "
                             "sequence (EOS); decode exits as soon as every "
                             "row has stopped")
    parser.add_argument("--pad-id", type=int, default=0,
                        help="fill value after a row's stop token")
    parser.add_argument("--tensor-parallel", type=int, default=1,
                        help=">1 runs mesh-sharded decode: weights + KV "
                             "cache sharded over the first N devices "
                             "(models/generate.py TP path)")
    parser.add_argument("--hf-checkpoint", default="",
                        help="local HuggingFace Llama/Mistral checkpoint "
                             "dir: weights are imported into the flagship "
                             "model (models/hf_import.py) and the model "
                             "hyperparam flags are ignored")
    parser.add_argument("--draft-hf-checkpoint", default="",
                        help="local HF checkpoint dir for a DRAFT model: "
                             "decodes speculatively (greedy only, batch 1; "
                             "output identical to plain decode — "
                             "models/speculative.py)")
    parser.add_argument("--draft-checkpoint-dir", default="",
                        help="orbax dir of an lm_train-trained DRAFT "
                             "(e.g. a small model trained on the same "
                             "data); decodes speculatively. Shape it with "
                             "the --draft-* hyperparam flags")
    parser.add_argument("--draft-d-model", type=int, default=128)
    parser.add_argument("--draft-n-layers", type=int, default=2)
    parser.add_argument("--draft-n-heads", type=int, default=4)
    parser.add_argument("--draft-d-ff", type=int, default=512)
    parser.add_argument("--metrics-out", default="")
    args = parser.parse_args(argv)

    from tony_tpu.utils.jaxenv import device_report, place_compile_cache

    place_compile_cache()
    import jax
    import jax.numpy as jnp

    from tony_tpu.models import transformer
    from tony_tpu.models.generate import generate

    import functools

    hf_params = None
    if args.hf_checkpoint:
        if args.checkpoint_dir:
            raise SystemExit(
                "--hf-checkpoint and --checkpoint-dir are exclusive")
        from tony_tpu.models.hf_import import load_hf

        hf_params, cfg = load_hf(args.hf_checkpoint,
                                 dtype=getattr(jnp, args.dtype))
        args.vocab = cfg.vocab_size
        print(f"imported HF checkpoint: {cfg.n_layers}L d{cfg.d_model} "
              f"{cfg.n_heads}h/{cfg.n_kv_heads}kv vocab {cfg.vocab_size}")
    else:
        cfg = transformer.TransformerConfig(
            vocab_size=args.vocab, d_model=args.d_model,
            n_layers=args.n_layers, n_heads=args.n_heads,
            n_kv_heads=args.n_heads, d_ff=args.d_ff,
            n_experts=args.n_experts, dtype=getattr(jnp, args.dtype),
        )

    mesh = pshard = None
    if args.tensor_parallel > 1:
        from tony_tpu.parallel import MeshSpec, TP_DECODE_RULES, build_mesh
        from tony_tpu.parallel.sharding import tree_shardings

        mesh = build_mesh(
            MeshSpec(fsdp=1, tensor=args.tensor_parallel),
            devices=jax.devices()[:args.tensor_parallel],
        )
        pshard = tree_shardings(
            mesh, transformer.param_logical_axes(cfg), TP_DECODE_RULES
        )

    init_fn = functools.partial(transformer.init, cfg=cfg)
    if hf_params is not None:
        params = hf_params          # prepare_decode shards under a mesh
    elif args.checkpoint_dir:
        from tony_tpu.train.checkpoint import (
            CheckpointManager, sharded_restore_template,
        )
        from tony_tpu.train.step import _opt_state_shardings, make_optimizer

        mgr = CheckpointManager(args.checkpoint_dir)
        latest = mgr.latest_step()
        if latest is None:
            raise SystemExit(f"no checkpoint found in {args.checkpoint_dir}")
        # lm_train checkpoints {params, opt_state}; restore needs the full
        # tree structure even though only params matter here
        if mesh is not None:
            abstract = jax.eval_shape(init_fn, jax.random.PRNGKey(args.seed))
            opt_abstract = jax.eval_shape(make_optimizer().init, abstract)
            # restore every shard DIRECTLY to its device: a model bigger
            # than one chip's HBM never materializes whole anywhere
            # (opt_state restores sharded too — orbax can't skip a saved
            # subtree — and is dropped immediately)
            oshard = _opt_state_shardings(opt_abstract, abstract, pshard,
                                          mesh)
            template = {
                "params": sharded_restore_template(abstract, pshard),
                "opt_state": sharded_restore_template(opt_abstract, oshard),
            }
        else:
            p0 = transformer.init(jax.random.PRNGKey(args.seed), cfg)
            template = {"params": p0, "opt_state": make_optimizer().init(p0)}
        restored = mgr.restore(template=template)
        params = restored["params"]
        mgr.close()
        print(f"restored checkpoint step {latest}")
    elif mesh is not None:
        # random init directly sharded (same no-single-device guarantee)
        params = jax.jit(init_fn, out_shardings=pshard)(
            jax.random.PRNGKey(args.seed))
    else:
        params = init_fn(jax.random.PRNGKey(args.seed))

    prompt_ids = [int(t) for t in args.prompt.split()]
    bad = [t for t in prompt_ids if not 0 <= t < args.vocab]
    if bad:
        raise SystemExit(f"prompt ids out of vocab range: {bad}")
    prompt = jnp.asarray([prompt_ids], jnp.int32)
    stop_tokens = tuple(int(t) for t in args.stop_tokens.split())

    from tony_tpu.models.generate import prepare_decode
    prepared = prepare_decode(
        params, cfg, weight_dtype=args.weight_dtype, mesh=mesh
    )

    draft = None
    if args.draft_hf_checkpoint and args.draft_checkpoint_dir:
        raise SystemExit("--draft-hf-checkpoint and --draft-checkpoint-dir "
                         "are exclusive")
    if args.draft_hf_checkpoint or args.draft_checkpoint_dir:
        if mesh is not None or args.temperature > 0:
            raise SystemExit("speculative decode is single-device greedy "
                             "(drop --tensor-parallel / --temperature)")
        if args.draft_hf_checkpoint:
            from tony_tpu.models.hf_import import load_hf

            d_params, d_cfg = load_hf(args.draft_hf_checkpoint,
                                      dtype=getattr(jnp, args.dtype))
        else:
            # an lm_train-trained draft: same vocab as the target (the
            # draft proposes the target's token ids)
            from tony_tpu.train.checkpoint import CheckpointManager
            from tony_tpu.train.step import make_optimizer

            d_cfg = transformer.TransformerConfig(
                vocab_size=args.vocab, d_model=args.draft_d_model,
                n_layers=args.draft_n_layers, n_heads=args.draft_n_heads,
                n_kv_heads=args.draft_n_heads, d_ff=args.draft_d_ff,
                dtype=getattr(jnp, args.dtype),
            )
            mgr = CheckpointManager(args.draft_checkpoint_dir)
            if mgr.latest_step() is None:
                raise SystemExit(
                    f"no checkpoint found in {args.draft_checkpoint_dir}")
            p0 = transformer.init(jax.random.PRNGKey(args.seed), d_cfg)
            restored = mgr.restore(template={
                "params": p0, "opt_state": make_optimizer().init(p0)})
            mgr.close()
            d_params = restored["params"]
        draft = (prepare_decode(d_params, d_cfg), d_cfg)
        print(f"speculative draft: {d_cfg.n_layers}L d{d_cfg.d_model}")

    def run():
        if draft is not None:
            from tony_tpu.models.speculative import speculative_generate

            d_prep, d_cfg = draft
            out, stats = speculative_generate(
                prepared, cfg, d_prep, d_cfg, prompt, args.max_new,
                kv_dtype=args.kv_dtype, stop_tokens=stop_tokens,
                pad_id=args.pad_id, return_stats=True,
            )
            jax.block_until_ready(out)
            # rounds = verify forwards; emitted = accepted + rounds (+ 1)
            return out, stats["accepted"] + stats["rounds"]
        out, steps = generate(
            prepared, cfg, prompt, args.max_new,
            temperature=args.temperature, top_k=args.top_k,
            key=jax.random.PRNGKey(args.seed), kv_dtype=args.kv_dtype,
            stop_tokens=stop_tokens, pad_id=args.pad_id, mesh=mesh,
            return_steps=True,
        )
        jax.block_until_ready(out)
        return out, steps

    run()                               # exclude compile from timing
    t0 = time.time()
    out, steps = run()
    wall = time.time() - t0

    tokens = [int(t) for t in out[0]]
    if stop_tokens:
        # trim the pad tail (the stop token itself stays)
        for i, t in enumerate(tokens):
            if t in stop_tokens:
                tokens = tokens[:i + 1]
                break
    if draft is not None:
        # speculative rounds can overshoot max_new and draft past a stop;
        # count the tokens actually DELIVERED, not `produced`
        n_generated = len(tokens)
    else:
        # prefill emitted 1 token + `steps` decode forwards; with
        # stop_tokens the loop exits early, so max_new would overstate
        # throughput
        n_generated = int(steps) + 1
    result = {
        "tokens": tokens,
        "decode_tokens_per_sec": n_generated / wall,
        "generated_tokens": n_generated,
        "backend": jax.default_backend(),
        "device": device_report(),
        "kv_dtype": args.kv_dtype,
        "weight_dtype": args.weight_dtype,
        "tensor_parallel": args.tensor_parallel,
        "stop_tokens": list(stop_tokens),
    }
    print(" ".join(str(t) for t in tokens))
    print(f"# {n_generated} tokens in {wall:.2f}s "
          f"({result['decode_tokens_per_sec']:.1f} tok/s)")
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(result, f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
