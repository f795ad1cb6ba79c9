"""Autoregressive generation with a KV cache for the flagship transformer.

The decode path the training stack doesn't need but users do. TPU-first
choices:

- **Static shapes everywhere.** The cache is allocated once at
  ``prompt_len + max_new_tokens`` (or a pinned ``max_len``) and written in
  place with ``dynamic_update_slice``; decode steps score against the full
  static cache buffer with an index mask (positions ``> current`` masked to
  -inf) instead of growing tensors — so the whole generate loop is one
  ``lax.scan`` under one jit, no per-step recompilation. Prefill is the
  exception: the cache is empty there, so it runs plain causal attention
  over the prompt via the model's own kernel (flash on TPU) rather than
  scoring against the whole buffer.
- **GQA-aware cache.** K/V are cached at ``n_kv_heads`` (the GQA-compressed
  width); heads are repeated at attention time, so cache HBM scales with
  kv-heads, not query heads.
- **One `_forward_with_cache` for prefill and decode** — same projections,
  cache writes, and unembed; they differ in the attention read (prefill:
  the model's own kernel over the prompt; decode: `_cached_attention` over
  the static buffer — see above). Dense models run fused q/k/v and gate/up
  projections (one skinny GEMV each instead of 3+2 — decode is
  weight-streaming-bound); the fusion is a concatenation of the training
  weights, so values match the `transformer._qkv`/`_mlp` path exactly.
  Weights are pre-cast to cfg.dtype once per call (identical rounding to
  the forward's per-use casts; the f32 MoE router excepted).
  `kv_dtype="int8"` and `weight_dtype="int8"` are the two opt-ins that
  genuinely change numerics vs the full forward (within int8 resolution).
  The flash-decode kernel (auto-dispatched at M>=4096 on TPU, for the
  lockstep path and the serving ring alike; it reads only the cache
  blocks that hold a visible position) computes
  softmax+PV in f32 like the einsum formulation, but its blockwise online
  softmax accumulates in a different ORDER — greedy tokens across the
  kernel gate agree to float tolerance, not provably bit-for-bit (a logit
  tie at f32 resolution could in principle flip; never observed in tests).

Sampling: greedy (temperature=0), temperature, and top-k. ``stop_tokens``
adds EOS semantics: a per-sequence finished mask plus a `lax.while_loop`
that exits as soon as every row has stopped, so a batch never pays decode
steps past its slowest sequence.

- **Mesh-sharded decode.** ``generate(..., mesh=..., rules=...)`` runs the
  whole loop under tensor parallelism: params are placed by the same
  logical-axis rule tables training uses (`parallel/sharding.py`), and the
  KV cache is sharded over `n_kv_heads` on the rules' "kv" axes — so a
  model bigger than one chip's HBM decodes across the mesh with the
  single-controller API unchanged. GQA models whose kv-head count doesn't
  divide the kv axes are rejected with a clear error (a split kv head has
  no layout). Use `prepare_decode` to shard + cast the weights once and
  serve many requests.

No reference counterpart: TonY has no model/inference layer (SURVEY.md
§2.3); part of the TPU-native capability layer.
"""

from __future__ import annotations

import functools
import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from . import transformer
from .transformer import TransformerConfig, rms_norm

NEG_INF = -1e30


class KVCache(NamedTuple):
    k: jax.Array      # [n_layers, B, n_kv_heads, max_len, head_dim]
    v: jax.Array
    length: jax.Array  # scalar int32: number of valid positions
    # int8 mode only: per-(layer, batch, kv-head, position) dequant scales
    # ([n_layers, B, n_kv_heads, max_len]); None when the cache is native
    k_scale: jax.Array | None = None
    v_scale: jax.Array | None = None
    # a config with linear layers only (cfg.layer_kinds): what those layers
    # carry a sequence instead of K/V, the recurrent state [n_linear, B,
    # lin_heads, lin_key_dim, lin_value_dim] float32 and the convolution's
    # last inputs [n_linear, B, lin_conv - 1, lin_channels]; k and v above
    # then hold the full-attention layers only
    state: jax.Array | None = None
    conv: jax.Array | None = None
    # a config with latent layers only: what those layers keep of a
    # position instead of per-head K and V, the row [c_kv | k_r] after its
    # norm and rotation (`transformer.latent_project`), position-major
    # (one row serves every head, so there is no head axis to put outside
    # it): [n_latent, B, max_len, lat_kv_rank + lat_rope_dim]
    latent: jax.Array | None = None


def _rec_buffers(cfg: TransformerConfig, batch: int, max_len: int) -> dict:
    """The zeroed ``state`` / ``conv`` fields of a cache of ``batch``
    sequences (none for a config without linear layers) and its ``latent``
    field (none without latent layers)."""
    out = {}
    if cfg.n_linear_layers:
        state, tail = transformer.linear_state_zeros(cfg, batch)
        n = cfg.n_linear_layers
        out = {"state": jnp.zeros((n,) + state.shape, state.dtype),
               "conv": jnp.zeros((n,) + tail.shape, tail.dtype)}
    if cfg.n_latent_layers:
        out["latent"] = jnp.zeros(
            (cfg.n_latent_layers, batch, max_len, cfg.lat_row_dim), cfg.dtype)
    return out


def init_cache(cfg: TransformerConfig, batch: int, max_len: int,
               kv_dtype: str = "native") -> KVCache:
    """kv_dtype "native" stores cfg.dtype (exact); "int8" stores
    per-token-per-head symmetric int8 with bf16 scales — half the cache's
    HBM capacity (2x the context per GB) and, with the scale-folded
    attention reads (_cached_attention), less cache bandwidth per step
    (+16% decode throughput at max_len 1024, more at longer contexts) —
    at the cost of quantization rounding (generation is no longer
    bit-exact vs the full forward).

    Layout puts the position axis INSIDE the head axis ([..., kvH, M, D]):
    decode attention reads one head's whole history at a time, and with
    position outermost that read is strided by kvH*D — measured ~3x below
    streaming bandwidth on v5e. Head-major, each head's [M, D] block is
    contiguous."""
    shape = (cfg.n_attn_layers, batch, cfg.n_kv_heads, max_len, cfg.head_dim)
    rec = _rec_buffers(cfg, batch, max_len)
    if kv_dtype == "int8":
        if cfg.n_latent_layers:
            raise ValueError(
                "kv_dtype='int8' is not implemented for latent layers (the "
                "cached rows are stored in the activation dtype)")
        return KVCache(
            k=jnp.zeros(shape, jnp.int8),
            v=jnp.zeros(shape, jnp.int8),
            length=jnp.int32(0),
            k_scale=jnp.zeros(shape[:-1], jnp.bfloat16),
            v_scale=jnp.zeros(shape[:-1], jnp.bfloat16),
            **rec,
        )
    if kv_dtype != "native":
        raise ValueError(f"kv_dtype must be 'native' or 'int8', got {kv_dtype!r}")
    return KVCache(
        k=jnp.zeros(shape, cfg.dtype),
        v=jnp.zeros(shape, cfg.dtype),
        length=jnp.int32(0),
        **rec,
    )


class PrefixPool(NamedTuple):
    """Device-resident shared KV block pool for the serving prefix cache
    (models/serving.py): ``n_blocks`` chunk-sized KV blocks, each holding
    ``chunk`` consecutive positions of some cached prompt prefix.

    Layout mirrors the slot cache with the block axis where the slot axis
    sits ([layers, N, kvH, chunk, D], head-major positions inside) so a
    block copies to/from a slot ring with pure gathers/scatters — no
    transpose through a different layout on the admission hot path — and
    so a mesh shards it with the cache's own ("batch", "kv") rule: blocks
    over the batch axes, kv heads over the tensor axes. dtype matches the
    slot cache (``kv_dtype``): an int8 pool stores the QUANTIZED values
    plus their scales, so a cache hit replays byte-identical reads."""
    k: jax.Array       # [n_layers, n_blocks, n_kv_heads, chunk, head_dim]
    v: jax.Array
    k_scale: jax.Array | None = None   # int8 mode: [n_layers, n_blocks,
    v_scale: jax.Array | None = None   #             n_kv_heads, chunk]


def init_prefix_pool(cfg: TransformerConfig, n_blocks: int, chunk: int,
                     kv_dtype: str = "native") -> PrefixPool:
    """Allocate the shared prefix-cache block pool (HBM budget =
    n_blocks x the per-block KV bytes; see docs/serving.md for the
    arithmetic). Same dtype rules as init_cache."""
    shape = (cfg.n_layers, n_blocks, cfg.n_kv_heads, chunk, cfg.head_dim)
    if kv_dtype == "int8":
        return PrefixPool(
            k=jnp.zeros(shape, jnp.int8),
            v=jnp.zeros(shape, jnp.int8),
            k_scale=jnp.zeros(shape[:-1], jnp.bfloat16),
            v_scale=jnp.zeros(shape[:-1], jnp.bfloat16),
        )
    if kv_dtype != "native":
        raise ValueError(f"kv_dtype must be 'native' or 'int8', got {kv_dtype!r}")
    return PrefixPool(
        k=jnp.zeros(shape, cfg.dtype),
        v=jnp.zeros(shape, cfg.dtype),
    )


def _symmetric_int8(x, axis: int):
    """Symmetric int8 quantization over `axis` -> (int8 values, f32 scales
    with `axis` kept as size 1)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axis, keepdims=True)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(
        jnp.round(x.astype(jnp.float32) / scale), -127, 127
    ).astype(jnp.int8)
    return q, scale


def _quantize_kv(x):
    """[B, kvH, L, D] -> (int8 values, [B, kvH, L] scales): symmetric
    per-token-per-head quantization over the head_dim vector."""
    q, scale = _symmetric_int8(x, axis=-1)
    return q, scale[..., 0].astype(jnp.bfloat16)


def _store_kv(kv, k, v, put):
    """One layer's new K/V [B, L, kvH, D] into the cache buffers ``kv`` =
    (k, v, k_scale, v_scale): head-major, in the cache's dtype, quantised
    where that is int8 (the scales then go to their own buffers), each
    stored by the caller's ``put(buf, new)`` with new [B, kvH, L(, D)] — a
    shared-offset dynamic_update_slice (`_forward_with_cache`) or a
    per-row ring scatter (`serving._rows_forward`)."""
    ck, cv, ks_buf, vs_buf = kv
    k_hm = k.transpose(0, 2, 1, 3)
    v_hm = v.transpose(0, 2, 1, 3)
    if ck.dtype == jnp.int8:
        k_w, ks = _quantize_kv(k_hm)
        v_w, vs = _quantize_kv(v_hm)
        ks_buf = put(ks_buf, ks)
        vs_buf = put(vs_buf, vs)
    else:
        k_w, v_w = k_hm.astype(ck.dtype), v_hm.astype(cv.dtype)
    return put(ck, k_w), put(cv, v_w), ks_buf, vs_buf


def decode_kernel_engages(cfg, m_cap: int) -> bool:
    """Whether a single-token decode step over an ``m_cap``-position cache
    may run the Pallas kernel (ops/decode_attention.py) instead of the
    einsum below: the one gate on what the code observes, which the
    serving engine's ``kv_blocks_read`` count asks too. Below ~4k
    positions the einsum wins (one kernel launch a layer of fixed cost vs
    a small cache read: measured crossover between M=2048 and 4096 on
    v5e); the CPU keeps the einsum. A caller under a mesh does not ask: a
    GSPMD-sharded decode would need a shard_map around the call."""
    return (cfg.attn_impl != "ref" and m_cap >= 4096
            and jax.default_backend() == "tpu")


def state_kernel_engages(l_new: int, sharded: bool) -> bool:
    """Whether a linear layer's recurrence over ``l_new`` new positions a
    row may run the Pallas kernel (ops/gated_delta.py
    ``gated_delta_decode``: the live rows' state tiles read and written
    once, in place in the cache's stack) instead of ``gated_delta_step``
    on a slice of it: the one gate on what the code observes, which the
    serving engine's ``state_rows_read`` count asks too. One new token a
    row (a block of positions is the chunkwise form's), on the TPU (the
    CPU keeps the ``jax.numpy`` statement the kernel is tested against),
    and not under a mesh (as ``decode_kernel_engages``: a shard_map away)."""
    return l_new == 1 and not sharded and jax.default_backend() == "tpu"


def _cached_attention(cfg, q, ck, cv, cache_len, l_new,
                      k_scale=None, v_scale=None, ring_offsets=None,
                      allow_kernel=True, layer_idx=None, active=None,
                      scale=None):
    """q: [B, L, H, D] for the L new positions (absolute offsets cache_len..
    cache_len+L-1); ck/cv: [B, kvH, max_len, D] full cache buffers (already
    containing the new keys). Scores run against the whole static buffer;
    invalid/future positions are masked by index. ``cache_len`` is a scalar
    (all rows at the same offset — generate) or a [B] vector (each row at
    its own offset — the serving slot pool, models/serving.py).

    GQA is a grouped einsum — query heads are folded to [kvH, rep] and
    contracted against the UN-repeated cache, so no n_heads-wide copy of
    the cache is ever materialized (that copy would undo the compressed
    cache's HBM saving on every decode step).

    int8 caches arrive with per-token-per-head scales. The dequant scales
    are FOLDED OUT of the [M, D] operands: K's scale multiplies the score
    matrix columns after the matmul, V's pre-multiplies the (tiny) prob
    matrix — so the only op left on the cache operand is the int8->bf16
    convert, which XLA fuses into the matmul's operand read. (A naive
    `cache * scale[..., None]` materializes a full dequantized buffer per
    step and erases int8's bandwidth saving.)

    ``ring_offsets`` [B] (serving slot pool): each row's buffer is a RING
    whose index m holds logical position (m - offset_b) mod M. Offsets are
    chosen at admission so every active row's next write lands at the same
    global cursor index (see models/serving.py) — the mask maps indices to
    logical positions per row; nothing else changes.

    A single-token step (L == 1) with ``allow_kernel`` (no mesh, the
    whole cache stack in hand) where ``decode_kernel_engages`` runs
    ``flash_decode`` instead, lockstep and ring alike: the same (length,
    offset) contract, but only the KV blocks that hold a visible position
    are read, and a row that is not ``active`` ([B] bool, None = all)
    reads nothing and returns zeros. The einsum ignores ``active`` (an
    idle row attends over its stale positions; its output is dropped).
    ``scale`` (None = head_dim ** -0.5) is the scores' factor where q and
    k are not head_dim wide (`_latent_attention`)."""
    b, l, h, d = q.shape
    kvh = ck.shape[1 if layer_idx is None else 2]
    rep = h // kvh
    if allow_kernel and l == 1 and decode_kernel_engages(cfg, ck.shape[-2]):
        # with layer_idx the kernel indexes the full cache stack itself
        # (slicing a pallas operand is a real copy)
        from ..ops.decode_attention import flash_decode

        out = flash_decode(
            q.reshape(b, kvh, rep, d), ck, cv, cache_len,
            k_scale, v_scale, ring_offsets=ring_offsets, active=active,
            window=cfg.attn_window or 0, layer=layer_idx,
        )
        return out.reshape(b, 1, h, d)
    if layer_idx is not None:           # einsum path works on the slice
        ck, cv = ck[layer_idx], cv[layer_idx]
        if k_scale is not None:
            k_scale, v_scale = k_scale[layer_idx], v_scale[layer_idx]
    q5 = q.reshape(b, l, kvh, rep, d)
    if scale is None:
        scale = cfg.head_dim ** -0.5
    s = jnp.einsum(
        "blgrd,bgmd->bgrlm", q5, ck.astype(cfg.dtype),
        preferred_element_type=jnp.float32,
    ) * scale                                           # [B, kvH, rep, L, M]
    if k_scale is not None:
        # per-position column scale: [B, kvH, M] -> [B, kvH, 1, 1, M]
        s = s * k_scale.astype(jnp.float32)[:, :, None, None, :]
    key_pos = jnp.arange(ck.shape[2])                   # [max_len]
    if ring_offsets is not None:
        # ring buffers: index m holds logical position (m - offset) mod M
        key_log = (key_pos[None, :] - ring_offsets[:, None]) % ck.shape[2]
    else:
        key_log = key_pos[None, :]
    if jnp.ndim(cache_len) == 0:
        q_pos = cache_len + jnp.arange(l_new)           # [L] absolute
        mask_bc = (None, None, None)                    # -> [1,1,1,L,M]
    else:
        q_pos = cache_len[:, None] + jnp.arange(l_new)  # [B, L] per-row
        mask_bc = (slice(None), None, None)             # -> [B,1,1,L,M]
    if ring_offsets is not None:
        mask = key_log[:, None, :] <= q_pos[..., :, None]
        if cfg.attn_window:
            mask &= key_log[:, None, :] > q_pos[..., :, None] - cfg.attn_window
        mask_bc = (slice(None), None, None)
    else:
        mask = key_log <= q_pos[..., :, None]           # causal + validity
        if cfg.attn_window:
            # sliding-window models must decode with the same band they
            # trained with, or generation attends to positions the model
            # never saw
            mask &= key_log > q_pos[..., :, None] - cfg.attn_window
    s = jnp.where(mask[mask_bc], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if v_scale is not None:
        p = p * v_scale.astype(jnp.float32)[:, :, None, None, :]
    out = jnp.einsum(
        "bgrlm,bgmd->blgrd", p.astype(cfg.dtype), cv.astype(cfg.dtype)
    )
    return out.reshape(b, l, h, d)


def _latent_attention(cfg, q_lat, rows, cache_len, l_new, ring_offsets=None):
    """The absorbed form's attention: q_lat [B, L, H, R] (`transformer
    .latent_absorb`) against a latent layer's cached rows [B, M, R], which
    already hold the new positions -> sum p row [B, L, H, R]. To
    `_cached_attention` this is attention with ONE K/V head R wide that
    every query head shares and whose values are its keys: the same
    masks, lengths and ring offsets, the einsum always (the decode kernel
    is built for per-head K and V of head_dim)."""
    kv = rows[:, None]
    return _cached_attention(
        cfg, q_lat, kv, kv, cache_len, l_new, ring_offsets=ring_offsets,
        allow_kernel=False, scale=transformer.latent_scale(cfg))


def _prefill_cfg(cfg: TransformerConfig) -> TransformerConfig:
    """The config used for the prefill attention dispatch: the model's own
    impl, except sequence-parallel impls (ring/ulysses need a mesh and a
    seq-sharded layout decode doesn't have) fall back to single-device
    auto dispatch."""
    if cfg.attn_impl in ("ring", "ulysses"):
        import dataclasses

        return dataclasses.replace(cfg, attn_impl="auto")
    return cfg


def moe_dropfree(cfg: TransformerConfig) -> TransformerConfig:
    """Decode routes B*1 tokens at a time; the training capacity formula
    (cf * tokens * k / E) would then drop any token that collides with
    another on the same expert. E/k guarantees capacity >= token count ->
    drop-free decode (and drop-free prefill, so cached generation matches
    the full forward whenever that forward doesn't drop). The ONE place
    this bound lives — generate and speculative_generate both call it, and
    their output-exactness contract depends on them agreeing."""
    if cfg.n_experts <= 0:
        return cfg
    import dataclasses

    return dataclasses.replace(
        cfg, capacity_factor=max(cfg.capacity_factor,
                                 cfg.n_experts / cfg.expert_top_k),
    )


def _cast_decode_params(params, cfg: TransformerConfig):
    """Pre-cast f32 master weights to the activation dtype once per
    generate call. Decode is weight-bandwidth-bound — every step reads the
    full parameter set, and the training-path convention of casting at use
    (`.astype(dt)` per op) makes each step read 2x the bytes AND write a
    copy. Numerically identical to the full forward for every weight the
    forward reads at cfg.dtype (same f32->bf16 rounding; the per-use casts
    become no-ops). The MoE ROUTER is the one exception — `_mlp`
    deliberately reads it at f32 so expert choice isn't perturbed by
    rounding — so it keeps its dtype."""
    if cfg.dtype == jnp.float32:
        return params
    router = params["layers"].get("router") if cfg.n_experts > 0 else None
    routed = params["layers"].get("routed") if cfg.n_routed_layers else None
    params = jax.tree.map(
        lambda a: a.astype(cfg.dtype) if a.dtype == jnp.float32 else a,
        params,
    )
    if router is not None:
        params["layers"]["router"] = router
    if routed is not None:      # a routed layer's router and selection bias
        params["layers"]["routed"] = [
            {**cast, "router": lp["router"], "router_bias": lp["router_bias"]}
            for cast, lp in zip(params["layers"]["routed"], routed)]
    return params


def _quantize_weight(w):
    """[..., d_in, d_out] -> (int8, scales [..., 1, d_out]): symmetric
    per-output-channel quantization over the contraction axis. The scale
    folds OUT of the matmul — y = (x @ W_int8) * s — so the weight operand
    streamed from HBM is pure int8 (half the bytes of bf16), and only the
    tiny activation row pays the multiply."""
    return _symmetric_int8(w, axis=-2)


def _fuse_decode_weights(params, cfg: TransformerConfig,
                         weight_dtype: str = "native"):
    """Concatenate per-layer q/k/v and gate/up projection weights into one
    matrix each ([L, d, h*hd + 2*kvh*hd] and [L, d, 2*f]). Decode-step
    matmuls are skinny GEMVs whose cost is streaming the weight matrix;
    fusing 3+2 of them into 1+1 halves the kernel count per layer and
    streams bigger contiguous blocks. Built once per generate call
    (amortized over all decode steps); dense MLP only.

    weight_dtype="int8" additionally quantizes EVERY large decode matrix
    per-output-channel — decode is weight-bandwidth-bound, so halving the
    streamed bytes buys ~that much step time; numerics change within the
    int8 resolution (opt-in). Dense models quantize the fused qkv, gate/up,
    wo, w_down, and unembed; MoE models quantize qkv/wo/unembed plus EVERY
    expert's w_in/w_out with per-expert per-output-channel scales — the
    einsum-dispatch MoE streams all E experts' weights every decode step
    (static shapes; routing picks capacity slots, not which weights load),
    so expert weights dominate the stream and quantize just as profitably
    as dense ones. The scales fold out of the matmuls (parallel/expert.py
    moe_ffn) so the streamed operand stays pure int8.

    HBM note: the fused (and, in w8 mode, quantized) copies live ALONGSIDE
    the master params for the duration of the generate call — roughly the
    attention+MLP weight bytes of extra peak residency. Servers sized
    tightly should build them ONCE with `prepare_decode` and drop the
    master params; then no per-call copies are made at all."""
    d = cfg.d_model
    dt = cfg.dtype
    if cfg.layer_kinds is not None:
        # one entry per kind, laid out as params["layers"]: the linear
        # mixer's projections are one matrix already (transformer
        # ._linear_stack), so a linear layer fuses its MLP only
        if weight_dtype == "int8":
            raise ValueError(
                "weight_dtype='int8' is not implemented for a config with "
                "layer_kinds (the int8 forms are the uniform stack's)")
        if cfg.mlp_kinds is not None:
            # the MLPs lie a layer in a list and are read in the one format
            # they are made in: a fused copy of a routed layer's experts
            # would be a second residency of nearly the whole model
            return {}
        out = {}
        for kind, lp in params["layers"].items():
            out[kind] = {
                "w_gu": jnp.concatenate([lp["w_gate"], lp["w_up"]], axis=-1)}
            if kind == "full":
                n = lp["wq"].shape[0]
                out[kind]["wqkv"] = jnp.concatenate(
                    [lp[w].reshape(n, d, -1) for w in ("wq", "wk", "wv")],
                    axis=-1)
        return out
    L = cfg.n_layers
    lp = params["layers"]
    wqkv = jnp.concatenate([
        lp["wq"].reshape(L, d, -1),
        lp["wk"].reshape(L, d, -1),
        lp["wv"].reshape(L, d, -1),
    ], axis=-1)
    moe = cfg.n_experts > 0
    if not moe:
        w_gu = jnp.concatenate([lp["w_gate"], lp["w_up"]], axis=-1)
    if weight_dtype != "int8":
        return {"wqkv": wqkv} if moe else {"wqkv": wqkv, "w_gu": w_gu}
    big = [
        ("wqkv", wqkv),
        ("wo", lp["wo"].reshape(L, cfg.n_heads * cfg.head_dim, d)),
        ("unembed", params["unembed"]),
    ]
    if moe:
        big += [("w_in", lp["w_in"]), ("w_out", lp["w_out"])]
    else:
        big += [("w_gu", w_gu), ("w_down", lp["w_down"])]
    out = {}
    for name, w in big:
        q, s = _quantize_weight(w)
        out[name] = q
        out[name + "_s"] = s.astype(dt)
    return out


def _forward_with_cache(params, cfg: TransformerConfig, tokens, cache: KVCache,
                        fused: dict | None = None, prefill: bool = False,
                        shardings: "DecodeShardings | None" = None,
                        all_logits: bool = False, ring: tuple | None = None,
                        routes: bool = False):
    """Run L new tokens (absolute positions cache.length..+L-1) through the
    stack, reading/writing the cache -> (last-position logits [B, V] f32,
    new cache) — or ([B, L, V], new cache) with ``all_logits=True`` (the
    speculative verify forward, models/speculative.py). ``cache.length``
    may be a [B] vector — every row then decodes at its OWN logical
    position (rope positions and attention masks per-row), which is the
    decode step of the continuous-batching slot pool (models/serving.py).
    Per-row mode requires ``ring=(cursor, offsets, active)``: each row's
    buffer is a ring where logical position p lives at index
    (p + offset_b) mod M, and the offsets are chosen at admission so every
    row's CURRENT write lands at the same scalar ``cursor`` index — the
    K/V write is then the same cheap shared-offset dynamic_update_slice as
    the lockstep path (per-row-offset writes lower to TPU scatters that
    cost more than the whole step), and only the attention pays the
    index→logical remap arithmetic. Active rows advance one position per
    step exactly as the cursor does, so a live row never wraps onto its
    own data; ``active`` [B] tells the decode kernel which rows to read
    the cache for at all (_cached_attention). Scalar
    length (all rows in lockstep) is the generate() path; l > 1 per-row
    is unsupported (serving prefill has its own program). By default only
    the LAST position is projected through the unembed — generation never
    needs earlier logits, and a full [B, L, V] prefill projection would be
    a pure HBM bonfire at long prompts / large vocab (the same tensor the
    blockwise-CE training path exists to avoid); all_logits callers keep L
    small.

    The layer loop is UNROLLED (Python loop), not a lax.scan: a scan would
    have to thread the cache as per-layer xs/ys, which makes XLA re-read and
    re-write the ENTIRE cache buffer every decode step — ~2x the cache's
    footprint in pure overhead traffic on a path that is HBM-bound. Unrolled,
    the cache stays one carried buffer that each layer updates in place with
    a dynamic_update_slice of just the L new positions (donation keeps it
    zero-copy across decode steps); measured ~1.7x decode throughput on the
    flagship model at batch 8.

    A linear layer (``cfg.layer_kinds``) goes from and to ``cache.state`` /
    ``.conv`` (`recur` below). Its recurrence runs one of three ways, by
    what the code observes and by no option: a block of positions (L > 1)
    is the chunkwise form on the layer's slice; one new token a row on the
    TPU outside a mesh (``state_kernel_engages``) is the Pallas kernel
    ``gated_delta_decode`` on the WHOLE state stack, which reads and writes
    the live rows' tiles of that layer once, in place (the slot pool's
    decode block: a quarter of its rows live, and six such layers a step);
    elsewhere (the CPU, a mesh) ``gated_delta_step`` on the slice, set
    back with ``.at[layer].set``, the statement the kernel is tested
    against.

    A latent layer (``cfg.layer_kinds``) stores its positions' rows [c_kv
    | k_r] in ``cache.latent`` at the same shared offset and attends in the
    absorbed form (`_latent_attention`: one read of a row for all heads),
    over the block's own rows on a prefill's empty cache. ``routes=True``
    adds a third result: the experts each of the L positions chose in each
    routed layer (``cfg.mlp_kinds``), [routed layers, B, L, k] int32.

    ``prefill=True`` asserts the cache is EMPTY (generate's first call):
    attention over (cache + new) then reduces to causal attention within
    the block itself and runs through the model's own _attention (the
    flash kernel on TPU, O(block) memory; numerics identical to the
    training forward) instead of scoring q against the whole max_len
    buffer, whose f32 [.., L, max_len] scores OOM at long prompts (~18GB
    at L=8192, batch 8 on the flagship). A chunked-prefill caller feeding
    L > 1 into a NON-empty cache must pass prefill=False to get the
    general cached-attention path."""
    dt = cfg.dtype
    b, l = tokens.shape
    per_row = jnp.ndim(cache.length) == 1   # serving slot pool: [B] lengths
    if per_row:
        if ring is None or l != 1:
            raise ValueError(
                "per-row cache lengths require ring=(cursor, offsets, "
                "active) and single-token steps (the serving decode "
                "contract)")
        ring_cursor, ring_offsets, ring_active = ring
        positions = cache.length[:, None] + jnp.arange(l)
    else:
        ring_cursor = ring_offsets = ring_active = None
        positions = jnp.broadcast_to(cache.length + jnp.arange(l), (b, l))
    x = params["embed"].astype(dt)[tokens]
    if shardings is not None:
        # pin activations batch-sharded / model-dim-replicated so GSPMD
        # keeps the Megatron layout (psum after wo / w_down) instead of
        # resharding mid-layer
        x = lax.with_sharding_constraint(x, shardings.act)

    p_cfg = _prefill_cfg(cfg) if prefill else None
    w8 = fused is not None and "wqkv_s" in fused  # int8 decode weights
    int8_cache = cache.k.dtype == jnp.int8
    zero = jnp.int32(0)
    # the per-layer entries of the fused / int8 forms; the rest (the int8
    # unembedding) is read after the loop
    fused_layers = {k: w for k, w in (fused or {}).items()
                    if not k.startswith("unembed")}

    def attend(layer, carry, q, k, v):
        """Store this layer's K/V at ONE shared scalar offset for every row
        (cache.length on the lockstep path, the ring cursor on the per-row
        path: that is the point of the ring layout, see the docstring),
        then read the whole stack — or, on the empty cache of a prefill,
        the block itself. ``layer`` counts the layers that hold K/V."""
        kv, rec, lat = carry
        offset = cache.length if ring_cursor is None else ring_cursor

        def put(buf, new):  # buf [Ly, B, kvH, M(, D)], new [B, kvH, L(, D)]
            idx = (jnp.int32(layer), zero, zero, offset)
            return lax.dynamic_update_slice(
                buf, new[None], idx + (zero,) * (new.ndim - 3))

        kv = _store_kv(kv, k, v, put)
        if prefill:
            kr, vr = transformer._repeat_kv(cfg, k, v)
            return (transformer._attention(q, kr, vr, p_cfg, None),
                    (kv, rec, lat))
        ck, cv, ks_buf, vs_buf = kv
        attn = _cached_attention(
            cfg, q, ck, cv, cache.length, l, ks_buf, vs_buf,
            ring_offsets=ring_offsets,
            # a pallas call inside the GSPMD-sharded decode would need
            # a shard_map wrapper; the sharded path keeps the einsum
            allow_kernel=shardings is None,
            layer_idx=layer, active=ring_active,
        )
        return attn, (kv, rec, lat)

    def recur(layer, carry, h, lp):
        """A linear layer from and to the cache's ``state`` / ``conv``
        (``layer`` counts the linear layers). On the per-row path only a
        row that is ``active`` advances: an idle row's garbage step, which
        K/V beyond a length can take, would be wrong for a state. Where
        ``state_kernel_engages`` the recurrence gets the whole stack and
        no slice of it is made or set back (the docstring above)."""
        kv, (state, conv), lat = carry
        n_valid = (None if ring_active is None
                   else ring_active.astype(jnp.int32))
        if state_kernel_engages(l, shardings is not None):
            from ..ops.gated_delta import gated_delta_decode

            out, state, new_tail = transformer.linear_mixer(
                cfg, h, lp, state, conv[layer], n_valid,
                functools.partial(gated_delta_decode, layer=layer))
        else:
            out, new_state, new_tail = transformer.linear_mixer(
                cfg, h, lp, state[layer], conv[layer], n_valid)
            state = state.at[layer].set(new_state)
        return out, (kv, (state, conv.at[layer].set(new_tail)), lat)

    def latent(layer, carry, h, lp):
        """A latent layer (``layer`` counts them): the block's rows into
        ``cache.latent`` at the shared offset, then the absorbed form over
        the layer's rows (on a prefill's empty cache: over the block's)."""
        kv, rec, lat = carry
        q_nope, q_rope, row = transformer.latent_project(cfg, h, positions, lp)
        offset = cache.length if ring_cursor is None else ring_cursor
        lat = lax.dynamic_update_slice(
            lat, row[None].astype(lat.dtype),
            (jnp.int32(layer), zero, offset, zero))
        q_lat = transformer.latent_absorb(cfg, q_nope, q_rope, lp)
        if prefill:
            o = _latent_attention(cfg, q_lat, row, zero, l)
        else:
            o = _latent_attention(cfg, q_lat, lat[layer], cache.length, l,
                                  ring_offsets)
        return transformer.latent_out(cfg, o, lp), (kv, rec, lat)

    mixers = {"linear": recur, "latent": latent}
    carry = ((cache.k, cache.v, cache.k_scale, cache.v_scale),
             (cache.state, cache.conv), cache.latent)
    picks = []
    for i in range(cfg.n_layers):
        kind, j, lp = transformer.layer_at(cfg, params["layers"], i,
                                           fused_layers)
        x, aux, carry = transformer.decoder_layer(
            cfg, x, positions, lp, functools.partial(attend, j), carry,
            functools.partial(mixers[kind], j) if kind in mixers else None)
        if cfg.mlp_kinds is not None and cfg.mlp_kinds[i] == "routed":
            picks.append(aux)
    (ck, cv, ks_buf, vs_buf), (state, conv), lat = carry

    # all_logits=True projects EVERY position ([B, L, V]) — the speculative
    # verify forward needs the target's prediction after each drafted
    # token; L there is the small draft window, so the projection stays
    # tiny. Default projects only the last position (generation never
    # needs earlier logits; a full [B, L, V] prefill projection would be
    # a pure HBM bonfire at long prompts / large vocab).
    x_out = rms_norm(x if all_logits else x[:, -1], params["final_norm"],
                     cfg.norm_eps)
    eq = "bld,dv->blv" if all_logits else "bd,dv->bv"
    if w8:
        logits = (
            jnp.einsum(eq, x_out, fused["unembed"].astype(dt))
            * fused["unembed_s"][0]
        ).astype(jnp.float32)
    else:
        logits = jnp.einsum(
            eq, x_out, params["unembed"].astype(dt)
        ).astype(jnp.float32)
    if shardings is not None:
        logits = lax.with_sharding_constraint(logits, shardings.act)
        ck = lax.with_sharding_constraint(ck, shardings.cache)
        cv = lax.with_sharding_constraint(cv, shardings.cache)
        if int8_cache:
            ks_buf = lax.with_sharding_constraint(ks_buf, shardings.scale)
            vs_buf = lax.with_sharding_constraint(vs_buf, shardings.scale)
    new_cache = KVCache(k=ck, v=cv, length=cache.length + l,
                        k_scale=ks_buf, v_scale=vs_buf, state=state, conv=conv,
                        latent=lat)
    if routes:
        return logits, new_cache, jnp.stack(picks)
    return logits, new_cache


def sample_token(logits, key, temperature=0.0, top_k=0):
    """logits [B, V] -> token ids [B]. temperature=0 => greedy.

    ``temperature`` may be a [B] ARRAY (the serving slot pool: each row
    decodes at its own request's temperature) — rows at 0 take the greedy
    argmax, others sample; the select is traced, so one compiled program
    serves mixed greedy/sampled traffic. ``top_k`` likewise: a static int
    applies one threshold to every row (the O(V log k) lax.top_k path); a
    [B] int32 ARRAY gives each row its own k (0 = unfiltered) via a
    per-row kth-value threshold from one full-vocab sort — costlier than
    lax.top_k, so the serving loop only dispatches this variant when some
    admitted request actually overrides the server k."""
    if not isinstance(temperature, jax.Array):
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        temps = None
        scaled = logits / temperature
    else:
        temps = temperature
        scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    if isinstance(top_k, jax.Array):
        v = scaled.shape[-1]
        srt = jnp.sort(scaled, axis=-1)             # ascending
        # row r keeps values >= the top_k[r]-th largest = srt[r, V - k];
        # k <= 0 (or k >= V) keeps everything
        idx = jnp.clip(v - top_k, 0, v - 1).astype(jnp.int32)
        kth = jnp.take_along_axis(srt, idx[:, None], axis=-1)
        keep = (top_k[:, None] <= 0) | (scaled >= kth)
        scaled = jnp.where(keep, scaled, NEG_INF)
    elif top_k > 0:
        # O(V log k) threshold, no sorted full-vocab copy on the hot path
        kth = jax.lax.top_k(scaled, top_k)[0][:, -1][:, None]
        scaled = jnp.where(scaled >= kth, scaled, NEG_INF)
    sampled = jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)
    if temps is None:
        return sampled
    return jnp.where(temps > 0, sampled,
                     jnp.argmax(logits, axis=-1).astype(jnp.int32))


class DecodeShardings(NamedTuple):
    """Static (hashable) sharding triple threaded through the jitted decode:
    cache = KV buffers [layers, B, kvH, M, D], scale = int8 scale buffers
    [layers, B, kvH, M], act = activations/logits (batch axes only)."""
    cache: jax.sharding.NamedSharding
    scale: jax.sharding.NamedSharding
    act: jax.sharding.NamedSharding


class DecodeWeights(NamedTuple):
    """Decode-ready weights built once by `prepare_decode`: pre-cast (and
    pre-fused / pre-quantized / mesh-sharded) so repeated generate calls
    make no per-call weight copies. Pass in place of raw params.

    `weight_dtype` and `mesh` record what the weights were built FOR;
    generate() rejects calls whose arguments contradict them (a silently
    ignored mismatch would serve the wrong numerics or layout). `rules` is
    the logical-axis rule table the mesh placement used — consumers
    (generate, SlotServer) that are handed prepared weights recover the
    cache/activation shardings from it instead of guessing a table that
    might not match the weight layout."""
    params: Any
    fused: dict | None
    weight_dtype: str = "native"
    mesh: Any = None
    rules: Any = None


def _decode_shardings(mesh, rules) -> DecodeShardings:
    from ..parallel.sharding import sharding_for

    return DecodeShardings(
        cache=sharding_for(mesh, (None, "batch", "kv", None, None), rules),
        scale=sharding_for(mesh, (None, "batch", "kv", None), rules),
        act=sharding_for(mesh, ("batch",), rules),
    )


def _rule_size(mesh, rules, name: str) -> int:
    """Product of mesh-axis sizes sharding rule-table row `name`."""
    from ..parallel.sharding import mesh_shards_rule

    shape = dict(mesh.shape)
    return math.prod(shape[a] for a in mesh_shards_rule(mesh, rules, name))


def _validate_decode_mesh(cfg: TransformerConfig, mesh, rules) -> None:
    """Head counts must divide their sharding axes: a split head has no
    layout (the [M, D] cache block and the per-head softmax are atomic)."""
    t_kv = _rule_size(mesh, rules, "kv")
    if cfg.n_kv_heads % t_kv:
        raise ValueError(
            f"mesh-sharded decode: n_kv_heads={cfg.n_kv_heads} is not "
            f"divisible by the 'kv' mesh axes (size {t_kv}) — a GQA model "
            "with fewer kv heads than the tensor axis cannot shard its KV "
            "cache. Shrink the tensor axis, or set rules['kv'] = None to "
            "replicate the cache."
        )
    t_h = _rule_size(mesh, rules, "heads")
    if cfg.n_heads % t_h:
        raise ValueError(
            f"mesh-sharded decode: n_heads={cfg.n_heads} is not divisible "
            f"by the 'heads' mesh axes (size {t_h})"
        )


def prepare_decode(
    params,
    cfg: TransformerConfig,
    *,
    weight_dtype: str = "native",
    mesh=None,
    rules=None,
) -> DecodeWeights:
    """Build decode-ready weights ONCE, outside generate.

    Casts f32 masters to cfg.dtype, fuses qkv / gate-up (dense models),
    optionally quantizes (``weight_dtype="int8"``), and — when a mesh is
    given — device_puts every parameter by the logical-axis rule table
    (`transformer.param_logical_axes` x `parallel/sharding.py`), so the
    result is laid out exactly as the jitted decode wants it. Callers that
    drop their f32 masters after this hold only ONE resident copy of the
    model; per-request generate calls then make no weight copies at all
    (the in-call cast/fuse path costs roughly the attention+MLP weight
    bytes of extra peak HBM per call).

    Under a mesh whose rules shard heads/kv/mlp, the qkv and gate/up
    fusions are skipped: concatenating differently-sharded matrices would
    force GSPMD to reshuffle them every step, and TP decode is already
    per-device-bandwidth-bound on the sharded weights themselves
    (``weight_dtype="int8"`` is rejected there for the same reason — the
    w8a16 path streams the fused layout)."""
    if weight_dtype not in ("native", "int8"):
        raise ValueError(
            f"weight_dtype must be 'native' or 'int8', got {weight_dtype!r}"
        )
    sharded_tp = False
    if mesh is not None:
        if rules is None:
            from ..parallel.sharding import TP_DECODE_RULES
            rules = TP_DECODE_RULES
        _validate_decode_mesh(cfg, mesh, rules)
        sharded_tp = any(
            _rule_size(mesh, rules, r) > 1 for r in ("heads", "kv", "mlp")
        )
        if sharded_tp and weight_dtype == "int8":
            raise ValueError(
                "weight_dtype='int8' decode is single-device: the w8a16 "
                "path streams the fused qkv/gate-up layout, which conflicts "
                "with head/mlp-sharded weights"
            )
        from ..parallel.sharding import shard_params
        params = shard_params(
            mesh, params, transformer.param_logical_axes(cfg), rules
        )
    params = _cast_decode_params(params, cfg)
    fused = (None if sharded_tp
             else _fuse_decode_weights(params, cfg, weight_dtype))
    return DecodeWeights(params=params, fused=fused,
                         weight_dtype=weight_dtype, mesh=mesh, rules=rules)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "max_new_tokens", "temperature", "top_k",
                     "kv_dtype", "max_len", "weight_dtype", "build_fused",
                     "stop_tokens", "pad_id", "shardings", "return_cache"),
    donate_argnames=("cache_in",),
)
def _generate_jit(
    params,
    fused,
    prompt,
    key,
    cache_in,
    *,
    cfg: TransformerConfig,
    max_new_tokens: int,
    temperature: float,
    top_k: int,
    kv_dtype: str,
    max_len: int,
    weight_dtype: str,
    build_fused: bool,
    stop_tokens: tuple,
    pad_id: int,
    shardings: DecodeShardings | None,
    return_cache: bool,
):
    """The whole generate loop under one jit: prefill once, then either a
    lax.scan of decode steps (no stop tokens: fixed trip count) or a
    lax.while_loop with a per-sequence finished mask (stop tokens: exits
    as soon as EVERY row has emitted a stop, so the batch pays for the
    slowest sequence, not for max_new_tokens). Returns
    (tokens [B, max_new], decode_steps scalar int32, final cache | None).

    ``cache_in`` continues from a previous call's returned cache (the
    prompt chunk is ingested through the general cached-attention path —
    the cache isn't empty, so the true-prefill fast path doesn't apply);
    it is DONATED, so the buffers update in place across turns. With
    ``return_cache`` the final emitted token is ingested too, so the
    returned cache holds prompt+ALL emitted tokens and the next turn's
    chunk is just the new tokens."""
    params = _cast_decode_params(params, cfg)   # no-op on prepared weights
    if build_fused:
        fused = _fuse_decode_weights(params, cfg, weight_dtype)
    b, _ = prompt.shape
    if cache_in is None:
        cache = init_cache(cfg, b, max_len, kv_dtype)
        logits, cache = _forward_with_cache(
            params, cfg, prompt, cache, fused, prefill=True,
            shardings=shardings)
    else:
        cache = cache_in
        logits, cache = _forward_with_cache(
            params, cfg, prompt, cache, fused, shardings=shardings)
    key, sub = jax.random.split(key)
    first = sample_token(logits, sub, temperature, top_k)

    def finalize(cache, last_tok):
        if not return_cache:
            return None
        # ingest the final emitted token so the cache holds the WHOLE
        # conversation so far (one extra forward, only on this path)
        _, cache = _forward_with_cache(
            params, cfg, last_tok[:, None], cache, fused,
            shardings=shardings)
        return cache

    if not stop_tokens:
        def step(carry, _):
            tok, cache, key = carry
            key, sub = jax.random.split(key)
            logits, cache = _forward_with_cache(
                params, cfg, tok[:, None], cache, fused, shardings=shardings
            )
            nxt = sample_token(logits, sub, temperature, top_k)
            return (nxt, cache, key), nxt

        # emit the sampled token so exactly max_new_tokens - 1 decode
        # forwards run (the prefill already produced the first token)
        (last, cache, _), rest = lax.scan(
            step, (first, cache, key), None, length=max_new_tokens - 1
        )
        toks = jnp.concatenate([first[None], rest], axis=0)
        return (jnp.moveaxis(toks, 0, 1), jnp.int32(max_new_tokens - 1),
                finalize(cache, last))

    stops = jnp.asarray(stop_tokens, jnp.int32)
    out = jnp.full((b, max_new_tokens), pad_id, jnp.int32)
    out = lax.dynamic_update_slice(out, first[:, None], (0, 0))
    finished = jnp.isin(first, stops)

    def cond(carry):
        i, _, _, _, finished, _ = carry
        return (i < max_new_tokens - 1) & ~jnp.all(finished)

    def body(carry):
        i, tok, cache, key, finished, out = carry
        key, sub = jax.random.split(key)
        logits, cache = _forward_with_cache(
            params, cfg, tok[:, None], cache, fused, shardings=shardings
        )
        nxt = sample_token(logits, sub, temperature, top_k)
        # finished rows emit pad and stay finished (pad may equal a stop id;
        # the OR below keeps them finished either way)
        nxt = jnp.where(finished, jnp.int32(pad_id), nxt)
        finished = finished | jnp.isin(nxt, stops)
        out = lax.dynamic_update_slice(out, nxt[:, None], (0, i + 1))
        return (i + 1, nxt, cache, key, finished, out)

    steps, last, cache, _, _, out = lax.while_loop(
        cond, body, (jnp.int32(0), first, cache, key, finished, out)
    )
    return out, steps, finalize(cache, last)


def generate(
    params,
    cfg: TransformerConfig,
    prompt: jax.Array,          # [B, Lp] int32, unpadded
    max_new_tokens: int,
    *,
    temperature: float = 0.0,
    top_k: int = 0,
    key: jax.Array | None = None,
    kv_dtype: str = "native",
    max_len: int | None = None,
    weight_dtype: str = "native",
    stop_tokens: tuple = (),
    pad_id: int = 0,
    mesh=None,
    rules=None,
    return_steps: bool = False,
    cache: KVCache | None = None,
    return_cache: bool = False,
):
    """Generate max_new_tokens continuations -> [B, max_new_tokens] int32.

    Whole loop is jitted: prefill once, then single-token decode steps
    against the in-place cache (a fixed-length lax.scan, or a while_loop
    with early exit when ``stop_tokens`` is given).

    ``params`` may be a raw parameter pytree or a `DecodeWeights` from
    `prepare_decode` (servers: build once, drop the f32 masters, no
    per-call weight copies).

    ``kv_dtype="int8"`` stores the KV cache quantized (per-token-per-head
    symmetric int8, bf16 scales) — half the cache's HBM capacity and
    faster decode at long contexts; "native" (default) is bit-exact vs
    the full forward.

    ``weight_dtype="int8"`` (w8a16) quantizes every large decode matrix
    per-output-channel, halving the ~0.5GB/step weight stream that floors
    decode — the scales fold out of the matmuls so the streamed operand is
    pure int8. MoE models quantize every expert's w_in/w_out with
    per-expert scales (all E experts stream every step under einsum
    dispatch, so they dominate the stream). Numerics change within the
    int8 resolution; the master params are untouched (quantized once per
    call).

    ``max_len`` fixes the cache capacity independently of this call's
    prompt+new length (servers that reuse one compiled program across
    request lengths want one capacity; attention cost scales with it).

    ``stop_tokens`` (EOS): rows that emit any listed token stop; their
    remaining positions are ``pad_id``. The emitted stop token itself IS
    included in the output. Decode exits when all rows have stopped, so
    the step count is bounded by the slowest sequence. ``return_steps=True``
    additionally returns the number of decode forwards executed.

    ``mesh`` + ``rules`` run the whole loop tensor-parallel: weights placed
    by the training rule tables (default `TP_DECODE_RULES`), the KV cache
    sharded over kv heads on the rules' "kv" axes, activations psum'd after
    wo / w_down exactly as in Megatron-style training. n_kv_heads (and
    n_heads) must divide their sharding axes — GQA models with fewer kv
    heads than the tensor axis are rejected. qkv/gate-up fusion and w8a16
    are single-device-only and disabled/rejected under a sharded mesh.

    ``return_cache=True`` additionally returns the KV cache holding
    prompt + ALL emitted tokens; pass it back as ``cache=`` on the next
    call with only the NEW tokens as the prompt — multi-turn chat never
    re-prefills history, and greedy continuation is token-exact vs a
    one-shot generate over the concatenated conversation (tested). The
    passed cache is DONATED (updated in place — jnp.copy it first to fan
    several continuations out of one shared prefix), so ``cache=`` requires
    ``return_cache=True``: without it the conversation state would be
    consumed with no replacement returned; its capacity must
    hold the new chunk + max_new_tokens, so size the FIRST call's
    ``max_len`` for the whole conversation. After an EOS stop, finished
    rows' caches contain the pad tail — continuing them is meaningless."""
    if max_new_tokens < 1:
        raise ValueError(
            f"max_new_tokens must be >= 1, got {max_new_tokens}"
        )
    if not cfg.causal:
        raise ValueError(
            "generate requires causal=True (a bidirectional encoder has no "
            "autoregressive decode)"
        )
    if weight_dtype not in ("native", "int8"):
        raise ValueError(
            f"weight_dtype must be 'native' or 'int8', got {weight_dtype!r}"
        )
    if key is None:
        key = jax.random.PRNGKey(0)
    b, lp_len = prompt.shape
    if cache is not None:
        if not return_cache:
            raise ValueError(
                "cache= requires return_cache=True: the passed cache is "
                "donated (updated in place), so without returning it the "
                "conversation state would be irrecoverably consumed. On a "
                "final turn, pass return_cache=True and drop the result."
            )
        cap = cache.k.shape[3]
        if cache.k.shape[1] != b:
            raise ValueError(
                f"continuation batch {b} != cache batch {cache.k.shape[1]}"
            )
        used = int(cache.length)
        if used + lp_len + max_new_tokens > cap:
            raise ValueError(
                f"cache capacity {cap} cannot hold {used} cached + "
                f"{lp_len} new prompt + {max_new_tokens} generated tokens "
                "— size the first call's max_len for the whole conversation"
            )
        if max_len is not None and max_len != cap:
            raise ValueError(
                f"max_len={max_len} conflicts with the passed cache's "
                f"capacity {cap} (omit max_len when continuing)"
            )
        max_len = cap
        cache_kv = "int8" if cache.k.dtype == jnp.int8 else "native"
        if kv_dtype != "native" and kv_dtype != cache_kv:
            raise ValueError(
                f"kv_dtype={kv_dtype!r} conflicts with the passed cache "
                f"({cache_kv})"
            )
        kv_dtype = cache_kv
    elif max_len is None:
        max_len = lp_len + max_new_tokens
    elif max_len < lp_len + max_new_tokens:
        raise ValueError(
            f"max_len={max_len} < prompt ({lp_len}) + max_new_tokens "
            f"({max_new_tokens})"
        )

    shardings = None
    if mesh is not None:
        if rules is None and isinstance(params, DecodeWeights):
            # prepared weights remember the rule table their layout used;
            # defaulting to a different table here would make GSPMD
            # reshard them every call
            rules = params.rules
        if rules is None:
            from ..parallel.sharding import TP_DECODE_RULES
            rules = TP_DECODE_RULES
        _validate_decode_mesh(cfg, mesh, rules)
        t_b = _rule_size(mesh, rules, "batch")
        if b % t_b:
            raise ValueError(
                f"mesh-sharded decode: batch {b} is not divisible by the "
                f"'batch' mesh axes (size {t_b})"
            )
        shardings = _decode_shardings(mesh, rules)
        # commit the inputs so jit doesn't guess a placement: prompt batch-
        # sharded like the activations, key replicated
        prompt = jax.device_put(prompt, shardings.act)
        key = jax.device_put(
            key, jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
        )

    if isinstance(params, DecodeWeights):
        prepared = params
        build_fused = False
        if weight_dtype != "native" and weight_dtype != prepared.weight_dtype:
            raise ValueError(
                f"weight_dtype={weight_dtype!r} requested but the prepared "
                f"weights were built with {prepared.weight_dtype!r} — pass "
                "weight_dtype to prepare_decode instead"
            )
        prep_mesh = prepared.mesh
        if (mesh is None) != (prep_mesh is None) or (
            mesh is not None and mesh != prep_mesh
        ):
            raise ValueError(
                "mesh mismatch: prepared weights were built "
                + ("without a mesh" if prep_mesh is None
                   else "for a different mesh")
                + (" but generate was called with one" if prep_mesh is None
                   else f" ({prep_mesh} != {mesh})")
                + " — rebuild with prepare_decode(..., mesh=...) matching "
                "the generate call"
            )
    elif mesh is not None:
        prepared = prepare_decode(
            params, cfg, weight_dtype=weight_dtype, mesh=mesh, rules=rules
        )
        build_fused = False
    else:
        prepared = DecodeWeights(params=params, fused=None)
        build_fused = True

    cfg = moe_dropfree(cfg)

    out, steps, cache_out = _generate_jit(
        prepared.params, prepared.fused, prompt, key, cache,
        cfg=cfg, max_new_tokens=max_new_tokens, temperature=temperature,
        top_k=top_k, kv_dtype=kv_dtype, max_len=max_len,
        weight_dtype=weight_dtype, build_fused=build_fused,
        stop_tokens=tuple(int(t) for t in stop_tokens), pad_id=int(pad_id),
        shardings=shardings, return_cache=return_cache,
    )
    result = (out,)
    if return_steps:
        result += (steps,)
    if return_cache:
        result += (cache_out,)
    return result if len(result) > 1 else out


__all__ = [
    "KVCache", "init_cache", "generate", "sample_token",
    "prepare_decode", "DecodeWeights", "moe_dropfree",
    "PrefixPool", "init_prefix_pool",
]
