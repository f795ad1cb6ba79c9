"""Flagship decoder-only transformer, TPU-first.

Design choices driven by the hardware (SURVEY.md §7):
- all heavy math is batched matmuls in bf16 -> MXU; params kept in f32
- layers are stacked and iterated with `lax.scan` (one trace, fast compile,
  params carry a leading "layers" logical axis)
- attention is pluggable: fused Pallas flash kernel (ops/attention.py) on a
  single device's sequence, or ring attention (parallel/ring_attention.py)
  when the sequence is sharded over the `seq` mesh axis
- optional MoE MLP (parallel/expert.py) with experts sharded over `expert`
- every parameter carries logical axes so DP/FSDP/TP/EP placement is a
  rule-table choice (parallel/sharding.py), not a model edit
- `jax.checkpoint` on the layer body trades FLOPs for HBM when remat=True

Plain functional style: params are a pytree, `init` builds them,
`param_logical_axes` mirrors the tree with logical-axis tuples, `apply` is a
pure function ready for jit/grad.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from ..parallel.expert import load_balancing_loss, moe_ffn
from ..parallel.ring_attention import reference_attention


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 8           # < n_heads => GQA
    d_ff: int = 2048
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    # ("llama3", factor, low_freq_factor, high_freq_factor, original_max
    # _position_embeddings) or None — Llama-3.x context-extension rope
    # (a tuple, not a dict: the config is a static jit argument)
    rope_scaling: tuple | None = None
    dtype: Any = jnp.bfloat16     # activation dtype
    param_dtype: Any = jnp.float32
    # MoE: n_experts=0 => dense SwiGLU MLP everywhere
    n_experts: int = 0
    expert_top_k: int = 2
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    # attention implementation: "flash" (pallas), "ref" (XLA), "ring" /
    # "ulysses" (sequence-parallel over the `seq` mesh axis), or "auto"
    attn_impl: str = "auto"
    # per-step kernel inside the ring SP path: "auto" (flash on TPU when the
    # shape fits the envelope, else XLA blocks), or force "flash"/"xla" —
    # "flash" off-TPU runs the Pallas kernel in interpret mode, which is how
    # the multichip dryrun covers the kernel x SP composition on a CPU mesh
    sp_kernel: str = "auto"
    # sliding-window (local) attention: each position sees its last
    # attn_window positions inclusive; 0 = full causal. Supported by the
    # flash and ref paths (block-pruned O(L*window) in the kernel)
    attn_window: int = 0
    # RMSNorm epsilon — HF Llama uses 1e-6, Mistral 1e-5; must match the
    # source model for imported checkpoints (models/hf_import.py)
    norm_eps: float = 1e-6
    # causal=False turns the stack into a bidirectional ENCODER (BERT-style:
    # every position attends everywhere). Pair with -1-masked targets for
    # masked-LM training (token_nll scores only the unmasked positions);
    # KV-cache generation requires causal=True
    causal: bool = True
    remat: bool = False
    # remat policy when remat=True: "full" rematerializes everything
    # (lowest memory, ~1 extra fwd of recompute); "dots" saves matmul
    # outputs and recomputes only elementwise ops (jax dots_saveable) —
    # most of full-remat's memory saving at a fraction of its FLOPs cost
    remat_policy: str = "full"
    # cross-entropy: "dense" materializes [B,L,V] logits; "blockwise" forms
    # loss and gradients a chunk of rows at a time (ops/cross_entropy.py) so
    # nothing of size [N,V] is ever live; "auto" goes blockwise at vocab >=
    # 16384 unless the mesh has a tensor axis (vocab-sharded dense wins there)
    ce_impl: str = "auto"

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


# ------------------------------------------------------------------ building

def _dense_init(key, shape, in_axis_size, dtype):
    scale = in_axis_size ** -0.5
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def init(key: jax.Array, cfg: TransformerConfig) -> dict:
    """Build the parameter pytree. Layer params are stacked [n_layers, ...]."""
    pd = cfg.param_dtype
    hd = cfg.head_dim
    keys = iter(jax.random.split(key, 16))

    def layer_stack(shape, in_size):
        k = next(keys)
        return _dense_init(k, (cfg.n_layers,) + shape, in_size, pd)

    params: dict = {
        "embed": _dense_init(next(keys), (cfg.vocab_size, cfg.d_model), cfg.d_model, pd),
        "layers": {
            "attn_norm": jnp.ones((cfg.n_layers, cfg.d_model), pd),
            "wq": layer_stack((cfg.d_model, cfg.n_heads, hd), cfg.d_model),
            "wk": layer_stack((cfg.d_model, cfg.n_kv_heads, hd), cfg.d_model),
            "wv": layer_stack((cfg.d_model, cfg.n_kv_heads, hd), cfg.d_model),
            "wo": layer_stack((cfg.n_heads, hd, cfg.d_model), cfg.n_heads * hd),
            "mlp_norm": jnp.ones((cfg.n_layers, cfg.d_model), pd),
        },
        "final_norm": jnp.ones((cfg.d_model,), pd),
        "unembed": _dense_init(next(keys), (cfg.d_model, cfg.vocab_size), cfg.d_model, pd),
    }
    if cfg.n_experts > 0:
        params["layers"].update({
            "router": layer_stack((cfg.d_model, cfg.n_experts), cfg.d_model),
            "w_in": layer_stack((cfg.n_experts, cfg.d_model, cfg.d_ff), cfg.d_model),
            "w_out": layer_stack((cfg.n_experts, cfg.d_ff, cfg.d_model), cfg.d_ff),
        })
    else:
        params["layers"].update({
            "w_gate": layer_stack((cfg.d_model, cfg.d_ff), cfg.d_model),
            "w_up": layer_stack((cfg.d_model, cfg.d_ff), cfg.d_model),
            "w_down": layer_stack((cfg.d_ff, cfg.d_model), cfg.d_ff),
        })
    return params


def param_logical_axes(cfg: TransformerConfig) -> dict:
    """Mirror of init()'s tree with logical-axis tuples for
    parallel/sharding.py rule tables."""
    layers: dict = {
        "attn_norm": ("layers", None),
        "wq": ("layers", "embed", "heads", None),
        "wk": ("layers", "embed", "kv", None),
        "wv": ("layers", "embed", "kv", None),
        "wo": ("layers", "heads", None, "embed"),
        "mlp_norm": ("layers", None),
    }
    if cfg.n_experts > 0:
        layers.update({
            "router": ("layers", "embed", None),
            "w_in": ("layers", "expert", "embed", "mlp"),
            "w_out": ("layers", "expert", "mlp", "embed"),
        })
    else:
        layers.update({
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
        })
    return {
        "embed": ("vocab", "embed"),
        "layers": layers,
        "final_norm": (None,),
        "unembed": ("embed", "vocab"),
    }


# ------------------------------------------------------------------- pieces

def rms_norm(x, weight, eps=1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) * weight.astype(x.dtype)


def rope(x, positions, theta, scaling=None):
    """Rotary position embedding; x: [B, L, H, D].

    ``scaling`` — ("llama3", factor, low_freq_factor, high_freq_factor,
    original_max_position_embeddings) — applies Llama-3.x's context
    extension: frequencies whose wavelength exceeds the original context
    are slowed by ``factor``, short wavelengths are untouched, and the
    band between interpolates smoothly (the HF _compute_llama3_parameters
    rule). Every Llama 3.1+ checkpoint ships this; without it long-range
    positions are rotated off the manifold the weights were trained on."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if scaling is not None:
        kind, factor, low_f, high_f, orig_max = scaling
        if kind != "llama3":
            raise ValueError(f"unsupported rope scaling kind {kind!r}")
        wavelen = 2.0 * jnp.pi / freqs
        low_wl = orig_max / low_f          # longest unscaled wavelength
        high_wl = orig_max / high_f
        smooth = jnp.clip(
            (orig_max / wavelen - low_f) / (high_f - low_f), 0.0, 1.0)
        interp = (1.0 - smooth) * freqs / factor + smooth * freqs
        freqs = jnp.where(
            wavelen < high_wl, freqs,
            jnp.where(wavelen > low_wl, freqs / factor, interp))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, L, half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def _partition_over_batch_and_heads(attn, mesh, shape):
    """GSPMD cannot partition a Mosaic kernel ("wrap the call in a
    shard_map"): on a mesh of several devices the flash kernel runs under a
    shard_map of its own. Attention is independent per batch row and per
    head, so each device takes its rows over the batch axes (data, fsdp)
    and its heads over ``tensor`` — the layout every rule table in
    parallel/sharding.py already gives the activations, so nothing is
    resharded. [B, L, H, D] in/out."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    b, _, h, _ = shape
    batch_axes = tuple(a for a in ("data", "fsdp") if mesh.shape.get(a, 1) > 1)
    n_batch = math.prod(mesh.shape[a] for a in batch_axes)
    n_heads = mesh.shape.get("tensor", 1)
    if b % n_batch or h % n_heads:
        raise ValueError(
            f"flash attention on mesh {dict(mesh.shape)}: batch {b} must "
            f"divide over {batch_axes or '()'} ({n_batch}) and heads {h} "
            f"over tensor ({n_heads})")
    spec = P(batch_axes or None, None, "tensor" if n_heads > 1 else None, None)
    return shard_map(attn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)


def _attention(q, k, v, cfg: TransformerConfig, mesh):
    """[B, L, H, D] in/out; dispatch on attn_impl."""
    impl = cfg.attn_impl
    if cfg.attn_window < 0:
        raise ValueError(
            f"attn_window must be >= 0 (0 = full causal), got {cfg.attn_window}"
        )
    window = cfg.attn_window or None
    if window is not None and not cfg.causal:
        raise ValueError("attn_window requires causal=True")
    if impl == "auto":
        impl = "flash" if jax.default_backend() == "tpu" else "ref"
    if window is not None and impl in ("ring", "ulysses"):
        raise ValueError(
            f"attn_window is not supported with attn_impl={impl!r} "
            "(sequence-parallel paths are full-causal)"
        )
    if impl == "flash":
        from ..ops.attention import attention_blhd

        attn = functools.partial(attention_blhd, causal=cfg.causal,
                                 window=window)
        if mesh is not None and mesh.size > 1:
            attn = _partition_over_batch_and_heads(attn, mesh, q.shape)
        return attn(q, k, v)
    if impl == "ring":
        if mesh is None:
            raise ValueError("attn_impl='ring' requires a mesh")
        from ..parallel.ring_attention import make_ring_attention

        return make_ring_attention(
            mesh, causal=cfg.causal,
            impl=None if cfg.sp_kernel == "auto" else cfg.sp_kernel,
        )(q, k, v)
    if impl == "ulysses":
        if mesh is None:
            raise ValueError("attn_impl='ulysses' requires a mesh")
        from ..parallel.ulysses import make_ulysses_attention

        attn_fn = None  # auto: flash on TPU, reference elsewhere
        if cfg.sp_kernel == "flash":
            from ..ops.attention import attention_blhd

            attn_fn = functools.partial(attention_blhd, causal=cfg.causal)
        elif cfg.sp_kernel == "xla":
            attn_fn = functools.partial(
                reference_attention, causal=cfg.causal
            )
        elif cfg.sp_kernel != "auto":  # match the ring path's validation
            raise ValueError(
                f"sp_kernel must be 'auto', 'flash', or 'xla', got "
                f"{cfg.sp_kernel!r}"
            )
        return make_ulysses_attention(
            mesh, causal=cfg.causal, attn_fn=attn_fn
        )(q, k, v)
    return reference_attention(q, k, v, causal=cfg.causal, window=window)


def _qkv(cfg: TransformerConfig, h, positions, lp):
    """Projections + rope for a block of hidden states; k/v stay at
    n_kv_heads (GQA repeat happens at attention time).

    The weight formats a layer's ``lp`` may carry — this function,
    `_attn_out` and `_mlp` are where they are read;
    `generate._fuse_decode_weights` is the one producer of the fused and
    int8 forms:

    - training: ``wq`` / ``wk`` / ``wv`` [d, heads, hd], ``wo`` [heads, hd,
      d], ``w_gate`` / ``w_up`` [d, f], ``w_down`` [f, d] (MoE: ``router``,
      ``w_in`` [E, d, f], ``w_out`` [E, f, d]), cast to cfg.dtype at use;
    - fused (decode): ``wqkv`` [d, (heads + 2 kv) * hd] and ``w_gu``
      [d, 2 f], the concatenations of the above (same values, one skinny
      matmul for three / two);
    - int8 (w8a16 decode): any of ``wqkv``, ``wo`` (flattened to [heads *
      hd, d]), ``w_gu``, ``w_down``, ``w_in``, ``w_out`` as int8 with a
      per-output-channel scale ``<name>_s`` [.., 1, d_out] beside it, which
      multiplies the matmul's OUTPUT so the streamed operand stays int8."""
    dt = cfg.dtype
    if "wqkv" in lp:
        b, l, _ = h.shape
        hd = cfg.head_dim
        nq, nkv = cfg.n_heads * hd, cfg.n_kv_heads * hd
        qkv = jnp.einsum("bld,de->ble", h, lp["wqkv"].astype(dt))
        if "wqkv_s" in lp:
            qkv = qkv * lp["wqkv_s"]
        q = qkv[..., :nq].reshape(b, l, cfg.n_heads, hd)
        k = qkv[..., nq:nq + nkv].reshape(b, l, cfg.n_kv_heads, hd)
        v = qkv[..., nq + nkv:].reshape(b, l, cfg.n_kv_heads, hd)
    else:
        q = jnp.einsum("bld,dhk->blhk", h, lp["wq"].astype(dt))
        k = jnp.einsum("bld,dhk->blhk", h, lp["wk"].astype(dt))
        v = jnp.einsum("bld,dhk->blhk", h, lp["wv"].astype(dt))
    q = rope(q, positions, cfg.rope_theta, cfg.rope_scaling)
    k = rope(k, positions, cfg.rope_theta, cfg.rope_scaling)
    return q, k, v


def _repeat_kv(cfg: TransformerConfig, k, v):
    if cfg.n_kv_heads != cfg.n_heads:
        rep = cfg.n_heads // cfg.n_kv_heads
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return k, v


def _attn_out(cfg: TransformerConfig, attn, lp):
    """The output projection of attention [B, L, H, D] -> [B, L, d]."""
    dt = cfg.dtype
    if "wo_s" in lp:
        b, l = attn.shape[:2]
        return jnp.einsum("ble,ed->bld", attn.reshape(b, l, -1),
                          lp["wo"].astype(dt)) * lp["wo_s"]
    return jnp.einsum("blhk,hkd->bld", attn, lp["wo"].astype(dt))


def _mlp(cfg: TransformerConfig, h, lp):
    """Post-attention MLP (dense SwiGLU or MoE) -> (out, aux_loss)."""
    dt = cfg.dtype
    aux = jnp.float32(0)
    if cfg.n_experts > 0:
        b, l, d = h.shape
        flat = h.reshape(b * l, d)
        router_logits = flat.astype(jnp.float32) @ lp["router"].astype(jnp.float32)
        out = moe_ffn(
            flat, lp["router"].astype(dt), lp["w_in"].astype(dt),
            lp["w_out"].astype(dt), k=cfg.expert_top_k,
            capacity_factor=cfg.capacity_factor, activation=jax.nn.silu,
            w_in_scale=lp.get("w_in_s"), w_out_scale=lp.get("w_out_s"),
        )
        aux = load_balancing_loss(router_logits, cfg.expert_top_k)
        return out.reshape(b, l, d), aux
    if "w_gu" in lp:
        gu = jnp.einsum("bld,de->ble", h, lp["w_gu"].astype(dt))
        if "w_gu_s" in lp:
            gu = gu * lp["w_gu_s"]
        act = jax.nn.silu(gu[..., :cfg.d_ff]) * gu[..., cfg.d_ff:]
    else:
        gate = jax.nn.silu(jnp.einsum("bld,df->blf", h, lp["w_gate"].astype(dt)))
        act = gate * jnp.einsum("bld,df->blf", h, lp["w_up"].astype(dt))
    out = jnp.einsum("blf,fd->bld", act, lp["w_down"].astype(dt))
    if "w_down_s" in lp:
        out = out * lp["w_down_s"]
    return out, aux


def decoder_layer(cfg: TransformerConfig, x, positions, lp, attend, kv=None):
    """One decoder block, the only one: norm -> _qkv -> attend -> wo ->
    norm -> _mlp. lp = this layer's params (stack dim removed).

    ``attend(kv, q, k, v) -> (attn [B, L, H, D], kv)`` is the one decision
    the block's callers differ in: how this layer's K/V are stored and what
    the attention then reads. It gets q roped [B, L, H, D] and k, v roped
    and UN-repeated [B, L, kvH, D]; ``kv`` is whatever state the caller
    threads through the layers (None in training; the cache buffers when
    decoding). Returns (x, aux_loss, kv)."""
    h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    q, k, v = _qkv(cfg, h, positions, lp)
    attn, kv = attend(kv, q, k, v)
    x = x + _attn_out(cfg, attn, lp)
    mlp_out, aux = _mlp(cfg, rms_norm(x, lp["mlp_norm"], cfg.norm_eps), lp)
    return x + mlp_out, aux, kv


def _layer(cfg: TransformerConfig, mesh, x, positions, lp):
    """The training block: nothing stored, attention over the block's own
    K/V through the model's kernel."""
    def attend(kv, q, k, v):
        k, v = _repeat_kv(cfg, k, v)
        return _attention(q, k, v, cfg, mesh), kv

    x, aux, _ = decoder_layer(cfg, x, positions, lp, attend)
    return x, aux


def apply_hidden(
    params: dict,
    tokens: jax.Array,          # [B, L] int32
    cfg: TransformerConfig,
    mesh=None,
) -> tuple[jax.Array, jax.Array]:
    """Forward pass up to (and including) the final norm -> (hidden
    [B, L, D], aux_loss scalar). The unembed projection is left to the
    caller so the loss can stream it blockwise."""
    dt = cfg.dtype
    b, l = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(l), (b, l))
    x = params["embed"].astype(dt)[tokens]

    layer_fn = functools.partial(_layer, cfg, mesh)
    if cfg.remat:
        if cfg.remat_policy == "full":
            policy = None
        elif cfg.remat_policy == "dots":
            policy = jax.checkpoint_policies.dots_saveable
        elif cfg.remat_policy == "attn":
            # save ONLY the attention output + its logsumexp (named inside
            # the flash custom_vjp forward rule, ops/attention.py — they
            # are exactly the kernel's backward residuals) so the remat
            # backward recomputes the cheap elementwise/matmul ops but
            # never re-runs the flash forward, whose cost grows
            # quadratically with L while everything else is linear
            policy = jax.checkpoint_policies.save_only_these_names(
                "attn_out", "attn_lse"
            )
        else:
            raise ValueError(
                f"remat_policy must be 'full', 'dots', or 'attn', got "
                f"{cfg.remat_policy!r}"
            )
        layer_fn = jax.checkpoint(layer_fn, policy=policy)

    def scan_body(carry, lp):
        x = carry
        x, aux = layer_fn(x, positions, lp)
        return x, aux

    x, auxes = jax.lax.scan(scan_body, x, params["layers"])
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, jnp.sum(auxes) * cfg.aux_loss_weight


def apply(
    params: dict,
    tokens: jax.Array,          # [B, L] int32
    cfg: TransformerConfig,
    mesh=None,
) -> tuple[jax.Array, jax.Array]:
    """Forward pass -> (logits [B, L, V] f32, aux_loss scalar)."""
    x, aux = apply_hidden(params, tokens, cfg, mesh)
    logits = jnp.einsum(
        "bld,dv->blv", x, params["unembed"].astype(cfg.dtype)
    ).astype(jnp.float32)
    return logits, aux


def _use_blockwise_ce(cfg: TransformerConfig, mesh=None, rules=None) -> bool:
    if cfg.ce_impl not in ("auto", "dense", "blockwise"):
        raise ValueError(
            f"ce_impl must be 'auto', 'dense', or 'blockwise', got {cfg.ce_impl!r}"
        )
    if cfg.ce_impl == "blockwise":
        return True
    if cfg.ce_impl == "dense":
        return False
    # auto: blockwise pays at large vocab, EXCEPT when the unembed's vocab
    # dim is mesh-sharded (tensor parallelism) — there the dense einsum keeps
    # the logits vocab-sharded, a device's share of them is small, and the
    # blockwise op's chunk loop would leave the vocab axis to GSPMD inside
    # every chunk (see ops/cross_entropy.py sharding note). The rules
    # table's "vocab" row is the source of truth for which axis that is;
    # default "tensor".
    from ..parallel.sharding import mesh_shards_rule

    if mesh_shards_rule(mesh, rules, "vocab", default=("tensor",)):
        return False
    return cfg.vocab_size >= 16384


def _rows_shard(mesh, rules):
    """What ``blockwise_cross_entropy`` needs to know of the mesh: the axes
    that shard the rows' [B, L] (batch, and sequence under SP), or None
    where every device holds every row. The op then chunks each device's
    own rows and sums dW across them once (ops/cross_entropy.py)."""
    from ..parallel.sharding import mesh_shards_rule

    batch = mesh_shards_rule(mesh, rules, "batch", default=("data", "fsdp"))
    seq = mesh_shards_rule(mesh, rules, "act_seq")
    return (mesh, (batch or None, seq or None)) if batch + seq else None


def token_nll(x, unembed, targets, cfg: TransformerConfig, mesh=None,
              rules=None, reduction: str = "mean"):
    """Masked mean next-token NLL from final hidden states, dispatching on
    cfg.ce_impl: blockwise CE runs the unembed matmul + softmax (and, under
    grad, both gradient products) a chunk of rows at a time so the [B, L, V]
    logits tensor never materializes (forward or backward); dense CE is the
    materializing reference path. ``auto``
    also inspects the mesh/rules: with the vocab dim mesh-sharded the dense
    path stays vocab-sharded and wins.

    x: [B, L, D] hidden (post final norm), unembed: [D, V], targets: [B, L]
    int with -1 = pad (masked out here) -> scalar mean NLL (f32).
    ``reduction="sum"`` leaves the division to the caller's own (e.g.
    global) valid count — the pipelined head path, where per-microbatch
    means would up-weight pad-heavy microbatches.
    """
    valid = targets >= 0
    safe_targets = jnp.where(valid, targets, 0)
    count = 1 if reduction == "sum" else jnp.maximum(valid.sum(), 1)
    if _use_blockwise_ce(cfg, mesh, rules):
        from ..ops.cross_entropy import blockwise_cross_entropy
        return blockwise_cross_entropy(
            x, unembed.astype(cfg.dtype), safe_targets,
            valid.astype(jnp.float32) / count, shard=_rows_shard(mesh, rules))
    from ..ops.cross_entropy import dense_cross_entropy
    nll = dense_cross_entropy(
        x.reshape(-1, x.shape[-1]), unembed.astype(cfg.dtype),
        safe_targets.reshape(-1),
    ).reshape(targets.shape)
    return (nll * valid).sum() / count


def loss_fn(params, tokens, targets, cfg: TransformerConfig, mesh=None,
            rules=None):
    """Next-token cross entropy (+ MoE aux); targets [B, L] with -1 = pad.

    With blockwise CE (cfg.ce_impl, default at large vocab) the [B, L, V]
    logits tensor is never materialized — the unembed matmul, the softmax
    and the loss's gradients go a chunk of rows at a time."""
    x, aux = apply_hidden(params, tokens, cfg, mesh)
    return token_nll(x, params["unembed"], targets, cfg, mesh, rules) + aux


def num_params(params) -> int:
    return sum(p.size for p in jax.tree.leaves(params))
