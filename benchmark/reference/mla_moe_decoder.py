"""The plain reference of a latent-attention decoder with routed experts
(the DeepSeek-V3-shaped block: ``model_type`` ``joyai_llm_flash``).

Straightforward ``jax.numpy`` in float32 at matmul precision "highest". No
kernels, no cache, no absorbed form, no sorting of tokens by expert: the
attention is the expanded form over one sequence and one head at a time,
the expert layer a loop over the experts, each applied to every token and
weighted by that token's (mostly zero) weight for it. It imports nothing of
the program and takes nothing the program has made: weights come from
``weights/mla_moe_decoder.py`` and the seed, one layer at a time.

The layers (``h`` a branch's input: the RMSNorm of the residual stream,
pre-norm; H heads of widths nope, rope, v):

- latent attention, every layer: ``c_q = RMSNorm(h W_qa)``; ``q = c_q
  W_qb``, a head ``[q_nope | q_rope]``; ``[c_kv | k_r] = h W_kva``; ``c_kv
  = RMSNorm(c_kv)``; ``k_r = RoPE(k_r)``, one for all heads; ``q_rope =
  RoPE(q_rope)`` (``rope_interleave``: the rotated pairs are the adjacent
  dims (2i, 2i+1), each pair rotated in place; ``rope_scaling`` null);
  ``[k_nope | v]`` a head ``= c_kv W_kvb``; scores ``(q_nope . k_nope +
  q_rope . k_r) / sqrt(nope + rope)``, causal softmax, ``sum p v``, ``W_o``.
- MLP: a layer below ``first_k_dense_replace`` is SwiGLU at
  ``intermediate_size``. A routed layer: ``s = sigmoid(h W_r)`` in float32
  over the E experts; the ``num_experts_per_tok`` experts with the largest
  ``s + b`` (``n_group`` 1, ``topk_group`` 1: no group limit); weights
  ``s_e / (sum_sel s + 1e-20) * routed_scaling_factor`` (``norm_topk_prob``),
  the bias not in them; ``y = sum_sel w_e SwiGLU_e(h) + SwiGLU_shared(h)``.
- untied head over the final RMSNorm.

Only what the one configuration that names this reference states is
written down; another scoring function, a group limit, a rope scaling or a
bias in the attention raise. ``held`` = (first, count) computes the part of
a routed layer's result that those experts give (routing is over all E);
the shared expert is what every chip computes alike and is counted by the
caller.

``routes`` [n, L, routed layers, k] puts the experts a server chose in the
place of the reference's own selection (the weights for them stay the
reference's own, from its own scores), and ``margin`` then says how far
below the reference's k-th best ``s + b`` the worst of those lies: 0 where
the two sets agree. ``lowp`` and ``fault`` put the reference in the
program's place for the controls that have to fail ``correct``: "w8"
rounds every matrix to int8 per output channel; ``fault``
"weights_with_bias" takes the experts' weights from ``s + b``,
"select_without_bias" leaves the bias out of the selection.
"""

from __future__ import annotations

import importlib.util
import pathlib

import jax
import jax.numpy as jnp

_HERE = pathlib.Path(__file__).resolve().parent


def _load(rel: str, name: str):
    spec = importlib.util.spec_from_file_location(name, _HERE.parent / rel)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


weights = _load("weights/mla_moe_decoder.py", "bench_weights_mla_moe_decoder")

F32 = jnp.float32
FAULTS = (None, "weights_with_bias", "select_without_bias")


def _round_int8(x, axis):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax / 127.0, 1e-12)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _mm(x, w, lowp):
    """x [..., K] @ w [K, N] in float32; "w8" rounds the matrix."""
    if lowp == "w8":
        w = _round_int8(w, axis=0)
    return jnp.matmul(x, w)


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def rope(x, positions, theta):
    """x [L, ..., D]: the adjacent pairs (2i, 2i+1) rotated in place."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[:, None] * freqs[None, :]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _check(cfg: dict) -> None:
    if (cfg["scoring_func"] != "sigmoid" or cfg["topk_method"] != "noaux_tc"
            or cfg["n_group"] != 1 or cfg["topk_group"] != 1
            or not cfg["norm_topk_prob"] or not cfg["rope_interleave"]
            or cfg["rope_scaling"] is not None or cfg["attention_bias"]
            or cfg["hidden_act"] != "silu" or cfg["tie_word_embeddings"]):
        raise ValueError("only the stated form of the layers is written down")


def latent_rows(cfg: dict, a, lw, lowp=None):
    """What a position keeps of the sequence a [L, hidden] (the attention
    branch's normed input): ``[c_kv | k_r]`` after the norm and the
    rotation -> [L, kv_lora_rank + rope]."""
    kr = cfg["kv_lora_rank"]
    kv = _mm(a, lw["wkv_a"], lowp)
    c_kv = rms_norm(kv[:, :kr], lw["kv_a_norm"], cfg["rms_norm_eps"])
    k_r = rope(kv[:, kr:], jnp.arange(a.shape[0]), float(cfg["rope_theta"]))
    return jnp.concatenate([c_kv, k_r], axis=-1)


def _attend(q, k, v):
    """One head: q, k [L, D], v [L, Dv], causal."""
    l = q.shape[0]
    s = (q @ k.T) * q.shape[-1] ** -0.5
    s = jnp.where(jnp.arange(l)[:, None] >= jnp.arange(l)[None, :],
                  s, -jnp.inf)
    return jax.nn.softmax(s, axis=-1) @ v


def latent_attention(cfg: dict, a, lw, lowp=None):
    """The expanded form over one sequence a [L, hidden] -> [L, hidden]."""
    l = a.shape[0]
    h, nope, rp, vd = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                       cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    kr, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    pos = jnp.arange(l)
    c_q = rms_norm(_mm(a, lw["wq_a"], lowp), lw["q_a_norm"], eps)
    q = _mm(c_q, lw["wq_b"], lowp).reshape(l, h, nope + rp)
    q = jnp.concatenate(
        [q[..., :nope], rope(q[..., nope:], pos, float(cfg["rope_theta"]))],
        axis=-1)
    row = latent_rows(cfg, a, lw, lowp)
    kv = _mm(row[:, :kr], lw["wkv_b"], lowp).reshape(l, h, nope + vd)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(row[:, None, kr:], (l, h, rp))],
        axis=-1)
    o = jax.lax.map(lambda t: _attend(*t), (
        q.transpose(1, 0, 2), k.transpose(1, 0, 2),
        kv[..., nope:].transpose(1, 0, 2)))
    return _mm(o.transpose(1, 0, 2).reshape(l, h * vd), lw["wo"], lowp)


def _swiglu(x, gate, up, down, lowp):
    return _mm(jax.nn.silu(_mm(x, gate, lowp)) * _mm(x, up, lowp), down, lowp)


def route(cfg: dict, m, lw, chosen=None, fault=None):
    """Tokens m [T, hidden] -> (chosen [T, k] int32, their weights [T, k],
    margin [T]). With ``chosen`` given the selection is the caller's."""
    k = cfg["num_experts_per_tok"]
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(jnp.matmul(m, lw["router"]))
    biased = s + lw["router_bias"]
    own_vals, own = jax.lax.top_k(
        s if fault == "select_without_bias" else biased, k)
    if chosen is None:
        chosen = own.astype(jnp.int32)
    took = jnp.take_along_axis(biased, chosen, axis=-1)
    margin = jnp.maximum(own_vals[:, -1] - jnp.min(took, axis=-1), 0.0)
    w = jnp.take_along_axis(
        biased if fault == "weights_with_bias" else s, chosen, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) \
        * cfg["routed_scaling_factor"]
    return chosen, w, margin


def routed_experts(cfg: dict, m, lw, chosen, w, lowp=None, held=None):
    """sum_sel w_e SwiGLU_e(m) over the experts ``held`` -> [T, hidden]."""
    first, count = held or (0, cfg["n_routed_experts"])

    def one(y, t):
        e, gate, up, down = t
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)     # [T]
        return y + w_e[:, None] * _swiglu(m, gate, up, down, lowp), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(m), (
        first + jnp.arange(count), lw["experts_gate"], lw["experts_up"],
        lw["experts_down"]))
    return y


def shared_expert(cfg: dict, m, lw, lowp=None):
    return _swiglu(m, lw["shared_gate"], lw["shared_up"], lw["shared_down"],
                   lowp)


def layer_forward(cfg: dict, kind: str, x, lw, lowp=None, chosen=None,
                  fault=None):
    """One decoder block over sequences x [n, L, hidden] -> (x, the experts
    chosen [n, L, k], the margin [n, L]); the last two None for a dense
    layer."""
    eps = cfg["rms_norm_eps"]
    n, l, d = x.shape
    x = x + jax.lax.map(lambda row: latent_attention(
        cfg, rms_norm(row, lw["attn_norm"], eps), lw, lowp), x)
    m = rms_norm(x, lw["mlp_norm"], eps).reshape(n * l, d)
    if kind == "dense":
        y = _swiglu(m, lw["w_gate"], lw["w_up"], lw["w_down"], lowp)
        return x + y.reshape(n, l, d), None, None
    k = cfg["num_experts_per_tok"]
    chosen, w, margin = route(
        cfg, m, lw, None if chosen is None else chosen.reshape(n * l, k),
        fault)
    y = routed_experts(cfg, m, lw, chosen, w, lowp) \
        + shared_expert(cfg, m, lw, lowp)
    return (x + y.reshape(n, l, d), chosen.reshape(n, l, k),
            margin.reshape(n, l))


def _f32(tree):
    """The weights as served, in float32: exactly the numbers their dtype
    holds. ``reduce_precision`` says so; the pair of converts that
    ``astype(F32)`` would complete, inside the program that has just made
    the weights, is one a compiler may skip (the TPU's does: "excess
    precision"), and the reference would then compute with weights that
    were never rounded, which the program never had."""
    def served(a):
        info = jnp.finfo(a.dtype)
        return jax.lax.reduce_precision(a.astype(F32), info.nexp, info.nmant)

    return jax.tree.map(served, tree)


def hidden(cfg: dict, seed: int, weight_dtype, tokens, routes=None,
           lowp=None, fault=None):
    """Full forward over ``tokens`` [n, L] up to the last block's output
    -> (x [n, L, hidden], chosen [n, L, routed layers, k], margin [n, L,
    routed layers]). Weights are made from the seed in ``weight_dtype``
    (as served) one layer at a time and used in float32."""
    _check(cfg)
    if fault not in FAULTS:
        raise ValueError(f"fault must be one of {FAULTS}, got {fault!r}")
    key = weights.seed_key(seed)
    dt = jnp.dtype(weight_dtype)

    # the key is an argument of every program: closed over it would be a
    # constant, and every seed would compile anew
    @jax.jit
    def embed(tokens, key):
        return _f32(weights.embed(key, cfg, dt))[tokens]

    @jax.jit
    def dense(x, index, key):
        lw = _f32(weights.layer(key, cfg, index, "dense", dt))
        return layer_forward(cfg, "dense", x, lw, lowp)[0]

    @jax.jit
    def routed(x, index, key, chosen):
        lw = _f32(weights.layer(key, cfg, index, "routed", dt))
        return layer_forward(cfg, "routed", x, lw, lowp, chosen, fault)

    with jax.default_matmul_precision("highest"):
        x = embed(jnp.asarray(tokens, jnp.int32), key)
        picks, margins = [], []
        for i, kind in enumerate(weights.layer_kinds(cfg)):
            if kind == "dense":
                x = dense(x, jnp.int32(i), key)
                continue
            given = (None if routes is None else jnp.asarray(
                routes, jnp.int32)[:, :, len(picks)])
            x, chosen, margin = routed(x, jnp.int32(i), key, given)
            picks.append(chosen)
            margins.append(margin)
    return x, jnp.stack(picks, axis=2), jnp.stack(margins, axis=2)


def served_scores(cfg: dict, seed: int, weight_dtype, tokens, positions,
                  served, routes=None, lowp=None, fault=None,
                  block: int = 512) -> dict:
    """What the comparison needs of the logits at ``positions`` [n, P],
    without holding [n, P, vocab]: ``best`` (the largest logit), ``first``
    (its token), ``got`` (the logit of ``served`` [n, P]) and ``got_next``
    (of the token id after it, modulo the vocabulary: what one altered
    token would read), each [n, P]; with them ``chosen`` and ``margin`` of
    `hidden`, over all positions."""
    dt = jnp.dtype(weight_dtype)
    x, chosen, margin = hidden(cfg, seed, weight_dtype, tokens, routes,
                               lowp, fault)

    @jax.jit
    def head(x, positions, served, key):
        rows = jnp.take_along_axis(x, positions[..., None], axis=1)
        rows = rms_norm(rows, _f32(weights.final_norm(cfg, dt)),
                        cfg["rms_norm_eps"])
        w = _f32(weights.unembed(key, cfg, dt))
        if lowp == "w8":
            w = _round_int8(w, axis=0)
        n, p, d = rows.shape
        pad = -p % block

        def some(t):
            r, tok = t                                  # [n, block, ...]
            logits = jnp.matmul(r, w)
            at = lambda ids: jnp.take_along_axis(
                logits, ids[..., None], axis=-1)[..., 0]
            return (logits.max(-1), logits.argmax(-1).astype(jnp.int32),
                    at(tok), at((tok + 1) % logits.shape[-1]))

        split = lambda a: jnp.moveaxis(jnp.pad(
            a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)).reshape(
                (n, -1, block) + a.shape[2:]), 1, 0)
        outs = jax.lax.map(some, (split(rows), split(served)))
        join = lambda a: jnp.moveaxis(a, 0, 1).reshape(n, -1)[:, :p]
        return tuple(join(a) for a in outs)

    with jax.default_matmul_precision("highest"):
        best, first, got, got_next = head(
            x, jnp.asarray(positions, jnp.int32),
            jnp.asarray(served, jnp.int32), weights.seed_key(seed))
    return {"best": best, "first": first, "got": got, "got_next": got_next,
            "chosen": chosen, "margin": margin}


def served_logits(cfg: dict, seed: int, weight_dtype, tokens, positions,
                  lowp=None, routes=None):
    """Full forward over ``tokens`` [n, L] (each row a prompt followed by
    the tokens that were served, then padding) -> logits [n, P, vocab] at
    ``positions`` [n, P], as the other references have it; for a vocabulary
    and a P whose product fits (a test's). ``routes`` as in `hidden`."""
    dt = jnp.dtype(weight_dtype)
    x, _, _ = hidden(cfg, seed, weight_dtype, tokens, routes, lowp)

    @jax.jit
    def head(x, positions, key):
        rows = jnp.take_along_axis(x, positions[..., None], axis=1)
        rows = rms_norm(rows, _f32(weights.final_norm(cfg, dt)),
                        cfg["rms_norm_eps"])
        return _mm(rows, _f32(weights.unembed(key, cfg, dt)), lowp)

    with jax.default_matmul_precision("highest"):
        return head(x, jnp.asarray(positions, jnp.int32),
                    weights.seed_key(seed))
