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


LAYER_KINDS = ("full", "linear", "latent")
# the MLP's form a layer (cfg.mlp_kinds): SwiGLU at d_ff, or routed experts
MLP_KINDS = ("dense", "routed")
# under the root of the linear mixer's L2 normalisation of q and k
LIN_L2_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 8           # < n_heads => GQA
    d_ff: int = 2048
    max_seq_len: int = 2048
    # None = no rotary embedding at all (a NoPE layer stack)
    rope_theta: float | None = 10000.0
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
    # layer pattern: None = the uniform stack of full-attention blocks (one
    # stacked tree, one lax.scan); else one of LAYER_KINDS per layer, the
    # parameters one stack PER KIND under params["layers"][kind] and the
    # forward a walk over the pattern. "linear" is the gated-delta-rule
    # mixer (`linear_mixer`, ops/gated_delta.py): lin_heads heads of a
    # float32 state [lin_key_dim, lin_value_dim] a sequence, fed through a
    # depthwise causal convolution of lin_conv taps
    layer_kinds: tuple | None = None
    lin_heads: int = 0
    lin_key_dim: int = 0
    lin_value_dim: int = 0
    lin_conv: int = 4
    # RMSNorm on q and k over the whole projected width, before the heads
    # are split (full-attention layers)
    qk_norm: bool = False
    # "pre": x + Mixer(Norm(x)) (Llama); "post": x + Norm(Mixer(x))
    norm_order: str = "pre"
    # "latent" layers (multi-head latent attention, `latent_mixer`): q
    # through a bottleneck of lat_q_rank, a head's q and k [lat_nope_dim |
    # lat_rope_dim] and its v lat_v_dim wide; what a position keeps is
    # [c_kv | k_r], lat_kv_rank + lat_rope_dim values for all heads, from
    # which a per-head matrix expands k_nope and v
    lat_q_rank: int = 0
    lat_kv_rank: int = 0
    lat_nope_dim: int = 0
    lat_rope_dim: int = 0
    lat_v_dim: int = 0
    # rotate the adjacent pairs (2i, 2i+1) in place, not the halves
    rope_interleave: bool = False
    # the MLP's form a layer: None = d_ff SwiGLU (or the n_experts toy)
    # everywhere; else one of MLP_KINDS per layer (needs layer_kinds), the
    # MLPs' parameters then a LIST a layer under params["layers"][form].
    # "routed" is parallel/routed_experts.py: moe_experts sigmoid-scored
    # experts of width moe_ff, moe_top_k a token chosen on score + bias and
    # weighted by the scores alone times moe_scale, moe_shared shared
    # experts beside them; moe_held = (first, count) is the range of the
    # experts this chip holds (None = all)
    mlp_kinds: tuple | None = None
    moe_experts: int = 0
    moe_top_k: int = 0
    moe_ff: int = 0
    moe_shared: int = 0
    moe_scale: float = 1.0
    moe_held: tuple | None = None

    def __post_init__(self):
        kinds = self.layer_kinds
        if kinds is not None and (len(kinds) != self.n_layers
                                  or set(kinds) - set(LAYER_KINDS)):
            raise ValueError(
                f"layer_kinds must name one of {LAYER_KINDS} for each of "
                f"the {self.n_layers} layers, got {kinds!r}")
        forms = self.mlp_kinds
        if forms is not None and (kinds is None or len(forms) != self.n_layers
                                  or set(forms) - set(MLP_KINDS)):
            raise ValueError(
                f"mlp_kinds must name one of {MLP_KINDS} for each of the "
                f"{self.n_layers} layers of a config with layer_kinds, got "
                f"{forms!r}")
        if self.n_routed_layers and (
                self.n_experts or min(self.moe_experts, self.moe_top_k,
                                      self.moe_ff) < 1
                or self.moe_top_k > self.moe_experts):
            raise ValueError(
                "routed layers need moe_experts >= moe_top_k >= 1, moe_ff "
                "and n_experts == 0")
        if self.n_latent_layers and (
                not self.causal or self.rope_theta is None
                or self.lat_rope_dim % 2
                or min(self.lat_q_rank, self.lat_kv_rank, self.lat_nope_dim,
                       self.lat_rope_dim, self.lat_v_dim) < 1):
            raise ValueError(
                "latent layers need lat_q_rank, lat_kv_rank, lat_nope_dim, "
                "an even lat_rope_dim, lat_v_dim, a rope_theta and a causal "
                "model")
        if self.norm_order not in ("pre", "post"):
            raise ValueError(f"norm_order must be 'pre' or 'post', got "
                             f"{self.norm_order!r}")
        if self.n_linear_layers and (
                self.n_experts or not self.causal
                or min(self.lin_heads, self.lin_key_dim, self.lin_value_dim,
                       self.lin_conv - 1) < 1):
            raise ValueError(
                "linear layers need lin_heads, lin_key_dim, lin_value_dim, "
                "lin_conv >= 2, a causal model and a dense MLP")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def n_linear_layers(self) -> int:
        return (self.layer_kinds or ()).count("linear")

    @property
    def n_latent_layers(self) -> int:
        return (self.layer_kinds or ()).count("latent")

    @property
    def n_routed_layers(self) -> int:
        return (self.mlp_kinds or ()).count("routed")

    @property
    def n_attn_layers(self) -> int:
        """Layers that hold K/V: the full-attention ones."""
        return self.n_layers - self.n_linear_layers - self.n_latent_layers

    @property
    def lat_row_dim(self) -> int:
        """What a position keeps for a latent layer: [c_kv | k_r]."""
        return self.lat_kv_rank + self.lat_rope_dim

    @property
    def experts_held(self) -> tuple:
        """(first, count) of the routed experts this chip holds."""
        return self.moe_held or (0, self.moe_experts)

    @property
    def lin_channels(self) -> int:
        """Channels of the linear mixer's convolution: q, k and v."""
        return self.lin_heads * (2 * self.lin_key_dim + self.lin_value_dim)


# ------------------------------------------------------------------ building

def _dense_init(key, shape, in_axis_size, dtype):
    scale = in_axis_size ** -0.5
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def _attention_stack(keys, cfg: TransformerConfig, n: int) -> dict:
    pd, hd, d = cfg.param_dtype, cfg.head_dim, cfg.d_model
    shapes = (("wq", (d, cfg.n_heads, hd), d), ("wk", (d, cfg.n_kv_heads, hd), d),
              ("wv", (d, cfg.n_kv_heads, hd), d),
              ("wo", (cfg.n_heads, hd, d), cfg.n_heads * hd))
    out = {"attn_norm": jnp.ones((n, d), pd), "mlp_norm": jnp.ones((n, d), pd)}
    for name, shape, fan_in in shapes:
        out[name] = _dense_init(next(keys), (n,) + shape, fan_in, pd)
    if cfg.qk_norm:
        out["q_norm"] = jnp.ones((n, cfg.n_heads * hd), pd)
        out["k_norm"] = jnp.ones((n, cfg.n_kv_heads * hd), pd)
    return out


def _mlp_stack(keys, cfg: TransformerConfig, n: int) -> dict:
    pd, d, f = cfg.param_dtype, cfg.d_model, cfg.d_ff
    if cfg.n_experts > 0:
        shapes = (("router", (d, cfg.n_experts), d),
                  ("w_in", (cfg.n_experts, d, f), d),
                  ("w_out", (cfg.n_experts, f, d), f))
    else:
        shapes = (("w_gate", (d, f), d), ("w_up", (d, f), d),
                  ("w_down", (f, d), f))
    return {name: _dense_init(next(keys), (n,) + shape, fan_in, pd)
            for name, shape, fan_in in shapes}


def _linear_stack(keys, cfg: TransformerConfig, n: int) -> dict:
    """The gated-delta-rule mixer's parameters (`linear_mixer` reads them):
    q, k and v projected by ONE matrix ``w_qkv`` [d, H (2 d_k + d_v)] (the
    channels of the convolution ``conv_w`` [taps, channels], q then k then
    v), the output gate ``w_g`` [d, H d_v], the decay and correction gates
    ``w_ab`` [d, 2 H] (a then b) with ``A_log`` / ``dt_bias`` [H], the
    gated output norm ``o_norm`` [d_v] and ``wo`` [H d_v, d]. One format
    for training, prefill and decode: nothing to fuse later."""
    pd, d, h = cfg.param_dtype, cfg.d_model, cfg.lin_heads
    hv = h * cfg.lin_value_dim
    out = {"attn_norm": jnp.ones((n, d), pd), "mlp_norm": jnp.ones((n, d), pd),
           "o_norm": jnp.ones((n, cfg.lin_value_dim), pd)}
    for name, shape, fan_in in (
            ("w_qkv", (d, cfg.lin_channels), d), ("w_g", (d, hv), d),
            ("w_ab", (d, 2 * h), d), ("wo", (hv, d), hv),
            ("conv_w", (cfg.lin_conv, cfg.lin_channels), cfg.lin_conv)):
        out[name] = _dense_init(next(keys), (n,) + shape, fan_in, pd)
    # decay rates exp(A_log) in [1, 16) and step sizes log-uniform in
    # [1e-3, 1e-1], stored through the inverse softplus (the published
    # layer's initializer)
    out["A_log"] = jnp.log(jax.random.uniform(
        next(keys), (n, h), minval=1.0, maxval=16.0)).astype(pd)
    dt = jnp.exp(jax.random.uniform(
        next(keys), (n, h), minval=math.log(1e-3), maxval=math.log(1e-1)))
    out["dt_bias"] = (dt + jnp.log(-jnp.expm1(-dt))).astype(pd)
    return out


def _latent_stack(keys, cfg: TransformerConfig, n: int) -> dict:
    """The latent-attention mixer's parameters (`latent_project` and its
    neighbours read them): ``wq_a`` [d, q_rank] and ``wq_b`` [q_rank, H,
    nope + rope] around the norm ``q_a_norm``; ``wkv_a`` [d, kv_rank +
    rope] (the latent, then the one rotary key all heads share) with
    ``kv_a_norm`` [kv_rank]; ``wkv_b`` [kv_rank, H, nope + v] (a head's
    k_nope then its v: the absorbed form contracts q and the output with
    its two halves, the expanded form the latent with all of it); ``wo``
    [H, v, d]."""
    pd, d, h = cfg.param_dtype, cfg.d_model, cfg.n_heads
    qr, kr = cfg.lat_q_rank, cfg.lat_kv_rank
    nope, rp, v = cfg.lat_nope_dim, cfg.lat_rope_dim, cfg.lat_v_dim
    out = {"attn_norm": jnp.ones((n, d), pd), "mlp_norm": jnp.ones((n, d), pd),
           "q_a_norm": jnp.ones((n, qr), pd),
           "kv_a_norm": jnp.ones((n, kr), pd)}
    for name, shape, fan_in in (
            ("wq_a", (d, qr), d), ("wq_b", (qr, h, nope + rp), qr),
            ("wkv_a", (d, kr + rp), d), ("wkv_b", (kr, h, nope + v), kr),
            ("wo", (h, v, d), h * v)):
        out[name] = _dense_init(next(keys), (n,) + shape, fan_in, pd)
    return out


def _routed_mlp(keys, cfg: TransformerConfig) -> dict:
    """ONE routed layer's MLP (parallel/routed_experts.py reads it): the
    float32 ``router`` [d, E] and selection bias ``router_bias`` [E] (a
    zero buffer that training moves), the experts held ``we_gu`` [count,
    d, 2 f] (gate then up) and ``we_down`` [count, f, d], the shared
    experts as one SwiGLU ``ws_gu`` [d, 2 S f] / ``ws_down`` [S f, d]."""
    pd, d, f = cfg.param_dtype, cfg.d_model, cfg.moe_ff
    count = cfg.experts_held[1]
    out = {"router": _dense_init(next(keys), (d, cfg.moe_experts), d,
                                 jnp.float32),
           "router_bias": jnp.zeros((cfg.moe_experts,), jnp.float32),
           "we_gu": _dense_init(next(keys), (count, d, 2 * f), d, pd),
           "we_down": _dense_init(next(keys), (count, f, d), f, pd)}
    if cfg.moe_shared:
        sf = cfg.moe_shared * f
        out["ws_gu"] = _dense_init(next(keys), (d, 2 * sf), d, pd)
        out["ws_down"] = _dense_init(next(keys), (sf, d), sf, pd)
    return out


def _mlp_lists(key, cfg: TransformerConfig) -> dict:
    """The MLPs of a config with ``mlp_kinds``: {form: [a layer's params,
    ...]} over that form's layers in the model's order. A LIST a layer,
    not a stack: nothing scans over them, and a routed layer's experts
    are the operand of a grouped-matmul kernel, to which a slice of a
    stack would be a copy of the layer a step."""
    out: dict = {}
    for i, form in enumerate(cfg.mlp_kinds):
        keys = iter(jax.random.split(jax.random.fold_in(key, i), 8))
        if form == "routed":
            one = _routed_mlp(keys, cfg)
        else:
            one = jax.tree.map(lambda a: a[0], _mlp_stack(keys, cfg, 1))
        out.setdefault(form, []).append(one)
    return out


def init(key: jax.Array, cfg: TransformerConfig) -> dict:
    """Build the parameter pytree. Layer params are stacked [n_layers, ...];
    with ``cfg.layer_kinds`` one such stack per kind, each over that
    kind's layers in the model's order; with ``cfg.mlp_kinds`` the stacks
    hold the mixers and the norms only and the MLPs lie beside them
    (`_mlp_lists`)."""
    pd = cfg.param_dtype
    keys = iter(jax.random.split(key, 32 if cfg.layer_kinds else 16))
    n_full, n_linear = cfg.n_attn_layers, cfg.n_linear_layers
    embed = _dense_init(next(keys), (cfg.vocab_size, cfg.d_model), cfg.d_model, pd)
    # attention, unembedding, MLP: the order the uniform tree draws its keys
    attn = _attention_stack(keys, cfg, n_full)
    unembed = _dense_init(next(keys), (cfg.d_model, cfg.vocab_size), cfg.d_model, pd)
    by_layer = cfg.mlp_kinds is not None
    mlp = (lambda n: {}) if by_layer else (
        lambda n: _mlp_stack(keys, cfg, n))
    layers = {**attn, **mlp(n_full)}
    if cfg.layer_kinds is not None:
        layers = {"full": layers} if n_full else {}
        if n_linear:
            layers["linear"] = {**_linear_stack(keys, cfg, n_linear),
                                **mlp(n_linear)}
        if cfg.n_latent_layers:
            # keys of its own: the other kinds draw what they always drew
            lkeys = iter(jax.random.split(jax.random.fold_in(key, 1), 8))
            layers["latent"] = {
                **_latent_stack(lkeys, cfg, cfg.n_latent_layers),
                **mlp(cfg.n_latent_layers)}
        if by_layer:
            layers.update(_mlp_lists(jax.random.fold_in(key, 2), cfg))
    return {"embed": embed, "layers": layers,
            "final_norm": jnp.ones((cfg.d_model,), pd), "unembed": unembed}


def param_logical_axes(cfg: TransformerConfig) -> dict:
    """Mirror of init()'s tree with logical-axis tuples for
    parallel/sharding.py rule tables."""
    attn: dict = {
        "attn_norm": ("layers", None),
        "wq": ("layers", "embed", "heads", None),
        "wk": ("layers", "embed", "kv", None),
        "wv": ("layers", "embed", "kv", None),
        "wo": ("layers", "heads", None, "embed"),
        "mlp_norm": ("layers", None),
    }
    if cfg.qk_norm:
        attn.update({"q_norm": ("layers", None), "k_norm": ("layers", None)})
    if cfg.n_experts > 0:
        mlp = {
            "router": ("layers", "embed", None),
            "w_in": ("layers", "expert", "embed", "mlp"),
            "w_out": ("layers", "expert", "mlp", "embed"),
        }
    else:
        mlp = {
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
        }
    by_layer = cfg.mlp_kinds is not None
    if by_layer:        # the MLPs lie beside the stacks, a list a layer
        dense, mlp = {k: v[1:] for k, v in mlp.items()}, {}
    layers: dict = {**attn, **mlp}
    if cfg.layer_kinds is not None:
        # the linear mixer's heads are not split over a mesh yet (no
        # caller shards a recurrent config): everything but the embed dim
        # replicated
        linear = {
            "attn_norm": ("layers", None), "mlp_norm": ("layers", None),
            "o_norm": ("layers", None), "A_log": ("layers", None),
            "dt_bias": ("layers", None), "conv_w": ("layers", None, None),
            "w_qkv": ("layers", "embed", None), "w_g": ("layers", "embed", None),
            "w_ab": ("layers", "embed", None), "wo": ("layers", None, "embed"),
            **mlp,
        }
        # the latent mixer's heads and the routed experts are not split
        # over a mesh either (the serving engine refuses one)
        latent = {
            "attn_norm": ("layers", None), "mlp_norm": ("layers", None),
            "q_a_norm": ("layers", None), "kv_a_norm": ("layers", None),
            "wq_a": ("layers", "embed", None),
            "wq_b": ("layers", None, None, None),
            "wkv_a": ("layers", "embed", None),
            "wkv_b": ("layers", None, None, None),
            "wo": ("layers", None, None, "embed"),
            **mlp,
        }
        layers = {kind: tree for kind, tree in
                  (("full", layers), ("linear", linear), ("latent", latent))
                  if kind in cfg.layer_kinds}
        if by_layer:
            routed = {"router": ("embed", None), "router_bias": (None,),
                      "we_gu": (None, "embed", None),
                      "we_down": (None, None, "embed")}
            if cfg.moe_shared:
                routed.update({"ws_gu": ("embed", None),
                               "ws_down": (None, "embed")})
            for form, one in (("dense", dense), ("routed", routed)):
                n = cfg.mlp_kinds.count(form)
                if n:
                    layers[form] = [dict(one) for _ in range(n)]
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


def rope(x, positions, theta, scaling=None, interleave=False):
    """Rotary position embedding; x: [B, L, H, D]. ``interleave`` rotates
    the adjacent pairs (2i, 2i+1) in place instead of pairing dim i with
    dim i + D/2 (the frequencies are the same).

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
    if interleave:
        x1, x2 = x[..., 0::2], x[..., 1::2]
        out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                        axis=-1).reshape(x.shape)
        return out.astype(x.dtype)
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
    if cfg.qk_norm:
        def whole(x, w):    # over the projected width, heads not yet split
            flat = x.reshape(x.shape[:2] + (-1,))
            return rms_norm(flat, w, cfg.norm_eps).reshape(x.shape)

        q, k = whole(q, lp["q_norm"]), whole(k, lp["k_norm"])
    if cfg.rope_theta is not None:
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
    """Post-attention MLP (dense SwiGLU or MoE) -> (out, aux): the toy
    MoE's load-balancing loss, 0 for a dense MLP, and for a routed layer
    (``lp`` carries ``router_bias``: cfg.mlp_kinds) the experts its tokens
    chose [B, L, k] int32, which the serving programs hand on to whoever
    asked for them."""
    dt = cfg.dtype
    aux = jnp.float32(0)
    if "router_bias" in lp:
        from ..parallel.routed_experts import route, routed_ffn

        b, l, d = h.shape
        flat = h.reshape(b * l, d)
        chosen, w = route(flat, lp["router"], lp["router_bias"],
                          top_k=cfg.moe_top_k, scale=cfg.moe_scale)
        out = routed_ffn(flat, chosen, w, lp["we_gu"].astype(dt),
                         lp["we_down"].astype(dt), held=cfg.experts_held)
        if cfg.moe_shared:      # what every chip computes alike
            sf = cfg.moe_shared * cfg.moe_ff
            gu = jnp.einsum("td,de->te", flat, lp["ws_gu"].astype(dt))
            out = out + jnp.einsum(
                "tf,fd->td", jax.nn.silu(gu[:, :sf]) * gu[:, sf:],
                lp["ws_down"].astype(dt))
        return out.reshape(b, l, d), chosen.reshape(b, l, -1)
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


def linear_mixer(cfg: TransformerConfig, h, lp, state, tail, n_valid=None,
                 step=None):
    """The gated-delta-rule mixer over a block of positions: h [B, L, d] ->
    (out [B, L, d], new state, new tail).

    ``state`` [B, H, d_k, d_v] float32 and ``tail`` [B, taps - 1,
    channels] (the convolution's last inputs) are what a sequence carries
    from its earlier positions: zeros at its start. ``n_valid`` [B] int
    (None = all L): only a row's first ``n_valid`` positions advance its
    state and tail, which come back as they were for a row with none. The
    rest of the block is a chunk's pad tail or an idle decode row, and its
    ``out`` is unspecified. ``step`` (L = 1 only) is the caller's own
    `gated_delta_step` where it holds the state some other way than as this
    layer's rows: it is called as that is, and what it returns as the state
    is handed back as it is (`generate._forward_with_cache`'s kernel on the
    cache's whole stack).

    z = h w_qkv through the depthwise causal convolution and SiLU gives q',
    k', v by head; q and k are L2-normalised (q also scaled by d_k^-1/2);
    beta = 2 sigmoid(h w_b), log alpha = -exp(A_log) softplus(h w_a +
    dt_bias); the recurrence is ops/gated_delta.py's (one step for L = 1,
    the chunkwise form otherwise); the output is RMSNorm_dv(o) * silu(h w_g)
    by head, through wo. Convolution, normalisations, gates and the
    recurrence are float32; the projections run in cfg.dtype."""
    from ..ops.gated_delta import gated_delta_chunk, gated_delta_step

    dt, f32 = cfg.dtype, jnp.float32
    b, l, _ = h.shape
    nh, dk, dv, taps = (cfg.lin_heads, cfg.lin_key_dim, cfg.lin_value_dim,
                        cfg.lin_conv)
    z = jnp.einsum("bld,dc->blc", h, lp["w_qkv"].astype(dt))
    zz = jnp.concatenate([tail.astype(dt), z], axis=1)      # [B, taps-1+L, C]
    w = lp["conv_w"].astype(f32)
    c = jax.nn.silu(sum(w[j] * zz[:, j:j + l].astype(f32)
                        for j in range(taps)))
    q = c[..., :nh * dk].reshape(b, l, nh, dk)
    k = c[..., nh * dk:2 * nh * dk].reshape(b, l, nh, dk)
    v = c[..., 2 * nh * dk:].reshape(b, l, nh, dv)

    def unit(x):
        return x * jax.lax.rsqrt(
            jnp.sum(x * x, axis=-1, keepdims=True) + LIN_L2_EPS)

    q, k = unit(q) * dk ** -0.5, unit(k)
    ab = jnp.einsum("bld,dc->blc", h, lp["w_ab"].astype(dt)).astype(f32)
    beta = 2.0 * jax.nn.sigmoid(ab[..., nh:])    # eigenvalues in (-1, 1)
    log_alpha = -jnp.exp(lp["A_log"].astype(f32)) * jax.nn.softplus(
        ab[..., :nh] + lp["dt_bias"].astype(f32))
    if l == 1:
        o, state = (step or gated_delta_step)(
            q[:, 0], k[:, 0], v[:, 0], log_alpha[:, 0], beta[:, 0], state,
            None if n_valid is None else n_valid > 0)
        o = o[:, None]
    else:
        valid = (None if n_valid is None
                 else jnp.arange(l)[None, :] < n_valid[:, None])
        o, state = gated_delta_chunk(q, k, v, log_alpha, beta, state, valid)
    if n_valid is None:
        tail = zz[:, l:]
    else:       # the taps - 1 inputs before position n_valid of the block
        idx = n_valid[:, None] + jnp.arange(taps - 1)[None, :]
        tail = jnp.take_along_axis(zz, idx[..., None], axis=1)
    var = jnp.mean(o * o, axis=-1, keepdims=True)
    o = o * jax.lax.rsqrt(var + cfg.norm_eps) * lp["o_norm"].astype(f32)
    gate = jnp.einsum("bld,de->ble", h, lp["w_g"].astype(dt))
    o = o * jax.nn.silu(gate.astype(f32).reshape(b, l, nh, dv))
    out = jnp.einsum("ble,ed->bld", o.reshape(b, l, nh * dv).astype(dt),
                     lp["wo"].astype(dt))
    return out, state, tail


def linear_state_zeros(cfg: TransformerConfig, batch: int):
    """(state, tail) of ``batch`` sequences at their start."""
    return (jnp.zeros((batch, cfg.lin_heads, cfg.lin_key_dim,
                       cfg.lin_value_dim), jnp.float32),
            jnp.zeros((batch, cfg.lin_conv - 1, cfg.lin_channels), cfg.dtype))


def latent_project(cfg: TransformerConfig, h, positions, lp):
    """A latent layer's projections of a block h [B, L, d] -> (q_nope [B,
    L, H, nope], q_rope [B, L, H, rope] rotated, row [B, L, kv_rank +
    rope]). ``row`` = [c_kv | k_r] is ALL a position keeps for the layer:
    the latent after its norm and the one rotary key every head shares
    after its rotation: what the serving cache stores, and what both
    forms of the attention below read.

    c_q = RMSNorm(h W_qa), q = c_q W_qb by head; [c_kv | k_r] = h W_kva,
    c_kv = RMSNorm(c_kv). The expanded form (`latent_expand`: training,
    and the statement the absorbed one is tested against) makes a head's
    k_nope and v from c_kv with ``wkv_b`` and attends as any attention
    does, q and k at nope + rope wide, v at lat_v_dim. The absorbed form
    (`latent_absorb`, `latent_out`: every serving program) never makes
    them: q_nope goes through W_kvb^K^T to the latent's width, scores and
    the weighted sum are taken against the rows themselves, one read of a
    row for all heads, and the sum goes through W_kvb^V. The same numbers
    but for rounding."""
    dt, eps = cfg.dtype, cfg.norm_eps
    nope, kr = cfg.lat_nope_dim, cfg.lat_kv_rank
    turn = functools.partial(rope, positions=positions, theta=cfg.rope_theta,
                             scaling=cfg.rope_scaling,
                             interleave=cfg.rope_interleave)
    c_q = rms_norm(jnp.einsum("bld,dr->blr", h, lp["wq_a"].astype(dt)),
                   lp["q_a_norm"], eps)
    q = jnp.einsum("blr,rhk->blhk", c_q, lp["wq_b"].astype(dt))
    kv = jnp.einsum("bld,dc->blc", h, lp["wkv_a"].astype(dt))
    c_kv = rms_norm(kv[..., :kr], lp["kv_a_norm"], eps)
    k_r = turn(kv[:, :, None, kr:])[:, :, 0]
    return (q[..., :nope], turn(q[..., nope:]),
            jnp.concatenate([c_kv, k_r], axis=-1))


def latent_scale(cfg: TransformerConfig) -> float:
    return (cfg.lat_nope_dim + cfg.lat_rope_dim) ** -0.5


def latent_expand(cfg: TransformerConfig, q_nope, q_rope, row, lp):
    """The expanded form's q, k [B, L, H, nope + rope] and v [B, L, H, v]
    of a block, from its own rows."""
    nope, kr = cfg.lat_nope_dim, cfg.lat_kv_rank
    kvb = jnp.einsum("blc,chk->blhk", row[..., :kr],
                     lp["wkv_b"].astype(cfg.dtype))
    k_r = jnp.broadcast_to(row[:, :, None, kr:],
                           q_rope.shape[:2] + (cfg.n_heads, cfg.lat_rope_dim))
    return (jnp.concatenate([q_nope, q_rope], axis=-1),
            jnp.concatenate([kvb[..., :nope], k_r], axis=-1),
            kvb[..., nope:])


def latent_absorb(cfg: TransformerConfig, q_nope, q_rope, lp):
    """The absorbed form's query, against the rows themselves: [q_nope
    W_kvb^K^T | q_rope] [B, L, H, kv_rank + rope]."""
    w_k = lp["wkv_b"].astype(cfg.dtype)[..., :cfg.lat_nope_dim]
    return jnp.concatenate(
        [jnp.einsum("blhn,chn->blhc", q_nope, w_k), q_rope], axis=-1)


def latent_out(cfg: TransformerConfig, o_lat, lp):
    """The absorbed form's end: o_lat [B, L, H, >= kv_rank] = sum p row
    (its first kv_rank values are sum p c_kv; a caller that weighed whole
    rows hands the rotary part along, unread) through W_kvb^V and wo."""
    w_v = lp["wkv_b"].astype(cfg.dtype)[..., cfg.lat_nope_dim:]
    o = jnp.einsum("blhc,chv->blhv", o_lat[..., :cfg.lat_kv_rank], w_v)
    return jnp.einsum("blhv,hvd->bld", o, lp["wo"].astype(cfg.dtype))


def layer_at(cfg: TransformerConfig, layers, i: int, extra=None):
    """Layer ``i`` of the model -> (its kind, its index among the layers of
    that kind, its params with the stack dim removed). ``extra`` is a second
    tree of per-layer leaves laid out as ``layers`` (the fused decode forms),
    merged over it. With ``cfg.mlp_kinds`` the layer's MLP comes from its
    form's list (`_mlp_lists`)."""
    kinds = cfg.layer_kinds
    if kinds is None:
        kind, j, stack = "full", i, {**layers, **(extra or {})}
    else:
        kind = kinds[i]
        j = kinds[:i].count(kind)
        stack = {**layers[kind], **(extra or {}).get(kind, {})}
    lp = jax.tree.map(lambda a: a[j], stack)
    if cfg.mlp_kinds is not None:
        form = cfg.mlp_kinds[i]
        lp = {**lp, **layers[form][cfg.mlp_kinds[:i].count(form)]}
    return kind, j, lp


def decoder_layer(cfg: TransformerConfig, x, positions, lp, attend, kv=None,
                  recur=None):
    """One decoder block, the only one: norm -> mixer -> norm -> _mlp
    (``norm_order`` "pre"; "post" norms each branch's OUTPUT instead). lp =
    this layer's params (stack dim removed). The caller names the mixer by
    what it hands in: a full-attention layer (``recur`` None) is _qkv ->
    attend -> wo, a linear layer is ``recur``.

    ``attend(kv, q, k, v) -> (attn [B, L, H, D], kv)`` is the one decision
    the block's callers differ in: how this layer's K/V are stored and what
    the attention then reads. It gets q roped [B, L, H, D] and k, v roped
    and UN-repeated [B, L, kvH, D]; ``kv`` is whatever state the caller
    threads through the layers (None in training; the cache buffers when
    decoding). ``recur(kv, h, lp) -> (out [B, L, d], kv)`` is the same
    decision for a linear layer: where its state and convolution tail come
    from and go to around `linear_mixer`; a latent layer's mixer is handed
    in the same way (`latent_project` and its neighbours, around the
    caller's rows). Returns (x, aux, kv), ``aux`` as `_mlp` has it."""
    pre = cfg.norm_order == "pre"
    h = rms_norm(x, lp["attn_norm"], cfg.norm_eps) if pre else x
    if recur is not None:
        mixed, kv = recur(kv, h, lp)
    else:
        q, k, v = _qkv(cfg, h, positions, lp)
        attn, kv = attend(kv, q, k, v)
        mixed = _attn_out(cfg, attn, lp)
    if not pre:
        mixed = rms_norm(mixed, lp["attn_norm"], cfg.norm_eps)
    x = x + mixed
    if pre:
        mlp_out, aux = _mlp(cfg, rms_norm(x, lp["mlp_norm"], cfg.norm_eps), lp)
    else:
        mlp_out, aux = _mlp(cfg, x, lp)
        mlp_out = rms_norm(mlp_out, lp["mlp_norm"], cfg.norm_eps)
    return x + mlp_out, aux, kv


def _layer(cfg: TransformerConfig, mesh, x, positions, lp, *, kind="full"):
    """The training block: nothing stored, attention over the block's own
    K/V through the model's kernel, a linear layer from a zero state."""
    def attend(kv, q, k, v):
        k, v = _repeat_kv(cfg, k, v)
        return _attention(q, k, v, cfg, mesh), kv

    def recur(kv, h, lp):
        out, _, _ = linear_mixer(cfg, h, lp,
                                 *linear_state_zeros(cfg, h.shape[0]))
        return out, kv

    def latent(kv, h, lp):
        # the expanded form through XLA: q and k are wider than v, which
        # the flash kernels do not take (train/step.py refuses the kind)
        q, k, v = latent_expand(
            cfg, *latent_project(cfg, h, positions, lp), lp)
        attn = reference_attention(q, k, v, causal=True,
                                   scale=latent_scale(cfg))
        return jnp.einsum("blhv,hvd->bld", attn,
                          lp["wo"].astype(cfg.dtype)), kv

    x, aux, _ = decoder_layer(
        cfg, x, positions, lp, attend,
        recur={"linear": recur, "latent": latent}.get(kind))
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

    layer_fns = {kind: functools.partial(_layer, cfg, mesh, kind=kind)
                 for kind in LAYER_KINDS}
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
        layer_fns = {kind: jax.checkpoint(fn, policy=policy)
                     for kind, fn in layer_fns.items()}

    def scan_body(carry, lp):
        x = carry
        x, aux = layer_fns["full"](x, positions, lp)
        return x, aux

    if cfg.layer_kinds is None:
        x, auxes = jax.lax.scan(scan_body, x, params["layers"])
    else:       # a walk over the pattern: the kinds' stacks differ in shape
        auxes = jnp.float32(0)
        for i in range(cfg.n_layers):
            kind, _, lp = layer_at(cfg, params["layers"], i)
            x, _ = layer_fns[kind](x, positions, lp)
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
