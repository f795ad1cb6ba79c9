"""The plain reference of a decoder of sliding-window and global attention
layers in a parallel block with routed and shared experts (the
``cohere2_moe`` block).

Straightforward ``jax.numpy`` in float32 at matmul precision "highest". No
kernels, no cache, no ring, no sorting of tokens by expert: the attention
is taken over one sequence, one KV head's group of query heads and one
block of queries at a time against every position with a mask (so a 10k-
position sequence at 128 heads fits), the expert layer is a loop over the
experts, each applied to every token and weighted by that token's (mostly
zero) weight for it. It imports nothing of the program and takes nothing
the program has made: weights come from ``weights/window_moe_decoder.py``
and the seed, one layer at a time, rounded to the served dtype.

The layer (``layer_types`` names each layer sliding or full):

- ``h = LN(x)``: LayerNorm with a weight and no bias, eps
  ``layer_norm_eps`` (``rms_norm_eps`` is null), one norm a layer.
- ``q = h W_q`` [H heads x hd], ``k = h W_k``, ``v = h W_v`` [KV heads x
  hd]; query head j reads KV head floor(j / (H / KV)).
- sliding layers rotate q and k with the adjacent pairs (2i, 2i+1)
  (``rope_gptj``, ``rotary_pct`` 1, theta ``rope_theta``) and a position
  sees the last ``sliding_window`` positions, itself included; full layers
  are NoPE and causal.
- ``attn = concat_heads(softmax(q k^T / sqrt(hd)) v) W_o``.
- ``s = sigmoid(h W_r)`` float32 over all E experts; the ``num_experts_
  per_tok`` largest chosen (no selection bias), ``g_e = s_e / sum_chosen
  s`` (``norm_topk_prob``, scale 1); ``moe = sum_{chosen, held} g_e
  SwiGLU_e(h) + mean_j SwiGLU_shared_j(h)``
  (``shared_expert_combination_strategy`` "average").
- ``x' = x + attn + moe`` (``use_parallel_block``).
- logits ``LN_f(x) E^T``: the embedding tied, ``logit_scale`` 1.

``held`` = (first, count) computes the part of a layer's result that those
experts give (routing is over all E): the file's ``experts_held``.

``routes`` [n, L, layers, k] puts the experts a server chose in the place
of the reference's own selection (their weights stay the reference's own),
and ``margin`` then says how far below the reference's k-th best score the
worst of those lies: 0 where the two sets agree. ``lowp`` puts the
reference in a lower precision for the controls that have to fail
``correct``: "w8" rounds every matrix to int8 per output channel, "f8" to
float8 e4m3. "a16" is no such control: it keeps the matrices and rounds
every activation to bfloat16 where the program stores one, the witness of
what the served dtype alone moves. ``fault`` is a router fault:
"weights_unnormalized" weighs by the raw scores, "select_held_only" chooses
among the experts held here only; under the names ``drivers/serve_mla_moe.py``'s controls use
("weights_with_bias", "select_without_bias": this router has no selection
bias) they are these two.
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


weights = _load("weights/window_moe_decoder.py",
                "bench_weights_window_moe_decoder")

F32 = jnp.float32
FAULTS = (None, "weights_unnormalized", "select_held_only")
FAULT_OF = {"weights_with_bias": "weights_unnormalized",
            "select_without_bias": "select_held_only"}
QUERY_BLOCK = 256


def _check(cfg: dict) -> None:
    if (cfg["model_type"] != "cohere2_moe"
            or cfg["expert_selection_fn"] != "sigmoid"
            or not cfg["norm_topk_prob"] or cfg["first_k_dense_replace"] != 0
            or cfg["position_embedding_type"] != "rope_gptj"
            or cfg["rotary_pct"] != 1 or not cfg["use_parallel_block"]
            or cfg["shared_expert_combination_strategy"] != "average"
            or cfg["hidden_act"] != "silu" or not cfg["use_gated_activation"]
            or cfg["attention_bias"] or cfg["use_qk_norm"]
            or not cfg["tie_word_embeddings"] or cfg["logit_scale"] != 1
            or cfg["rms_norm_eps"] is not None
            or cfg["rope_parameters"]["rope_type"] != "default"
            or cfg["order_of_interleaved_layers"] != "local_attn_first"):
        raise ValueError("only the stated form of the layers is written down")


def _round(w, lowp):
    """A matrix [K, N] as the control holds it: per output channel int8
    ("w8"), or float8 e4m3 ("f8"); "a16" keeps the matrices."""
    if lowp == "w8":
        amax = jnp.max(jnp.abs(w), axis=0, keepdims=True)
        scale = jnp.maximum(amax / 127.0, 1e-12)
        return jnp.clip(jnp.round(w / scale), -127, 127) * scale
    if lowp == "f8":
        return w.astype(jnp.float8_e4m3fn).astype(F32)
    if lowp not in (None, "a16"):
        raise ValueError(
            f"lowp must be None, 'w8', 'f8' or 'a16', got {lowp!r}")
    return w


def _act(x, lowp):
    """An activation as the lower precision holds it: "a16" rounds it to
    bfloat16 (the served activations' dtype), where the program stores
    one; the other precisions keep it."""
    if lowp == "a16":
        return jax.lax.reduce_precision(x, 8, 7)
    return x


def _mm(x, w, lowp):
    """x [..., K] @ w [K, N] in float32."""
    return _act(jnp.matmul(x, _round(w, lowp)), lowp)


def layer_norm(x, w, eps):
    c = x - jnp.mean(x, axis=-1, keepdims=True)
    return c * jax.lax.rsqrt(jnp.mean(c * c, axis=-1, keepdims=True) + eps) * w


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


def attention(cfg: dict, kind: str, h, lw, lowp=None):
    """One sequence's attention branch, h [L, hidden] (the normed input)
    -> [L, hidden]."""
    l = h.shape[0]
    nh, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    rep = nh // kv
    pos = jnp.arange(l)
    q = _mm(h, lw["wq"], lowp).reshape(l, kv, rep, hd)
    k = _mm(h, lw["wk"], lowp).reshape(l, kv, hd)
    v = _mm(h, lw["wv"], lowp).reshape(l, kv, hd)
    if kind == "window":
        theta = float(cfg["rope_theta"])
        q, k = _act(rope(q, pos, theta), lowp), _act(rope(k, pos, theta), lowp)
    pad = -l % QUERY_BLOCK
    blocks = jnp.pad(q, ((0, pad),) + ((0, 0),) * 3).reshape(
        -1, QUERY_BLOCK, kv, rep, hd)
    window = cfg["sliding_window"] if kind == "window" else None

    def group(t):
        """One KV head's group of query heads: qg [n_blocks, B, rep, hd]
        against kg, vg [L, hd]."""
        qg, kg, vg = t

        def block(u):
            qb, start = u
            s = jnp.einsum("qrd,kd->rqk", qb, kg) * hd ** -0.5
            qp = start + jnp.arange(QUERY_BLOCK)[:, None]
            ok = pos[None, :] <= qp
            if window is not None:
                ok &= pos[None, :] > qp - window
            p = jax.nn.softmax(jnp.where(ok[None], s, -1e30), axis=-1)
            return jnp.einsum("rqk,kd->qrd", p, vg)

        return jax.lax.map(block, (
            qg, jnp.arange(qg.shape[0]) * QUERY_BLOCK))

    o = jax.lax.map(group, (jnp.moveaxis(blocks, 2, 0),
                            jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))
    o = jnp.moveaxis(o, 0, 2).reshape(-1, nh * hd)[:l]
    return _mm(_act(o, lowp), lw["wo"], lowp)


def _swiglu(x, gate, up, down, lowp):
    return _mm(_act(jax.nn.silu(_mm(x, gate, lowp)) * _mm(x, up, lowp), lowp),
               down, lowp)


def route(cfg: dict, m, lw, chosen=None, fault=None):
    """Tokens m [T, hidden] -> (chosen [T, k] int32, their weights [T, k],
    margin [T]). With ``chosen`` given the selection is the caller's."""
    k = cfg["num_experts_per_tok"]
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(jnp.matmul(m, lw["router"]))
    first, count = weights.experts_held(cfg)
    if fault == "select_held_only":
        e = jnp.arange(s.shape[-1])
        held = (e >= first) & (e < first + count)
        own_vals, own = jax.lax.top_k(jnp.where(held, s, -1.0), k)
    else:
        own_vals, own = jax.lax.top_k(s, k)
    if chosen is None:
        chosen = own.astype(jnp.int32)
    took = jnp.take_along_axis(s, chosen, axis=-1)
    margin = jnp.maximum(own_vals[:, -1] - jnp.min(took, axis=-1), 0.0)
    w = took
    if fault != "weights_unnormalized":
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return chosen, w, margin


def routed_experts(cfg: dict, m, lw, chosen, w, lowp=None, held=None):
    """sum_chosen g_e SwiGLU_e(m) over the experts ``held`` (None: the
    file's share) -> [T, hidden]."""
    first, count = held or weights.experts_held(cfg)

    def one(y, t):
        e, gate, up, down = t
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)     # [T]
        return y + w_e[:, None] * _swiglu(m, gate, up, down, lowp), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(m), (
        first + jnp.arange(count), lw["experts_gate"], lw["experts_up"],
        lw["experts_down"]))
    return y


def shared_experts(cfg: dict, m, lw, lowp=None):
    """The mean of the shared experts' SwiGLUs, one at a time."""
    n, f = cfg["num_shared_experts"], cfg["intermediate_size"]

    def one(y, j):
        part = lambda w, ax: jax.lax.dynamic_slice_in_dim(w, j * f, f, ax)
        return y + _swiglu(m, part(lw["shared_gate"], 1),
                           part(lw["shared_up"], 1),
                           part(lw["shared_down"], 0), lowp), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(m), jnp.arange(n))
    return y / n


def layer_forward(cfg: dict, kind: str, x, lw, lowp=None, chosen=None,
                  fault=None):
    """One parallel block over sequences x [n, L, hidden], a sequence at a
    time -> (x, the experts chosen [n, L, k], the margin [n, L])."""
    eps = cfg["layer_norm_eps"]

    def one(t):
        row, given = t
        h = _act(layer_norm(row, lw["attn_norm"], eps), lowp)
        picks, g, margin = route(cfg, h, lw, given, fault)
        moe = routed_experts(cfg, h, lw, picks, g, lowp) \
            + shared_experts(cfg, h, lw, lowp)
        return (_act(row + attention(cfg, kind, h, lw, lowp) + moe, lowp),
                picks, margin)

    if chosen is None:
        return jax.lax.map(lambda row: one((row, None)), x)
    return jax.lax.map(one, (x, chosen))


def _f32(tree):
    """The weights as served, in float32: exactly the numbers their dtype
    holds (``reduce_precision``: a pair of converts inside the program that
    made the weights is one a compiler may skip)."""
    def served(a):
        info = jnp.finfo(a.dtype)
        return jax.lax.reduce_precision(a.astype(F32), info.nexp, info.nmant)

    return jax.tree.map(served, tree)


def hidden(cfg: dict, seed: int, weight_dtype, tokens, routes=None,
           lowp=None, fault=None):
    """Full forward over ``tokens`` [n, L] up to the last block's output
    -> (x [n, L, hidden], chosen [n, L, layers, k], margin [n, L,
    layers]). Weights are made from the seed in ``weight_dtype`` (as
    served) one layer at a time and used in float32."""
    _check(cfg)
    fault = FAULT_OF.get(fault, fault)
    if fault not in FAULTS:
        raise ValueError(f"fault must be one of {FAULTS}, got {fault!r}")
    key = weights.seed_key(seed)
    dt = jnp.dtype(weight_dtype)

    # the key is an argument of every program: closed over it would be a
    # constant, and every seed would compile anew
    @jax.jit
    def embed(tokens, key):
        return _f32(weights.embed(key, cfg, dt))[tokens]

    def block(kind):
        @jax.jit
        def run(x, index, key, chosen):
            lw = _f32(weights.layer(key, cfg, index, dt))
            return layer_forward(cfg, kind, x, lw, lowp, chosen, fault)
        return run

    blocks = {kind: block(kind) for kind in set(weights.layer_kinds(cfg))}
    with jax.default_matmul_precision("highest"):
        x = embed(jnp.asarray(tokens, jnp.int32), key)
        picks, margins = [], []
        for i, kind in enumerate(weights.layer_kinds(cfg)):
            given = (None if routes is None
                     else jnp.asarray(routes, jnp.int32)[:, :, i])
            x, chosen, margin = blocks[kind](x, jnp.int32(i), key, given)
            picks.append(chosen)
            margins.append(margin)
    return x, jnp.stack(picks, axis=2), jnp.stack(margins, axis=2)


def _head(cfg: dict, x, positions, key, dt, lowp):
    """The final norm at ``positions`` [n, P] -> rows [n, P, hidden] and
    the tied unembedding [hidden, vocab]."""
    rows = jnp.take_along_axis(x, positions[..., None], axis=1)
    rows = _act(layer_norm(rows, _f32(weights.final_norm(cfg, dt)),
                           cfg["layer_norm_eps"]), lowp)
    return rows, _round(_f32(weights.embed(key, cfg, dt)).T, lowp)


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
        rows, w = _head(cfg, x, positions, key, dt, lowp)
        n, p, _ = rows.shape
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
    """Full forward over ``tokens`` [n, L] -> logits [n, P, vocab] at
    ``positions`` [n, P]; for a vocabulary and a P whose product fits (a
    test's). ``routes`` as in `hidden`."""
    dt = jnp.dtype(weight_dtype)
    x, _, _ = hidden(cfg, seed, weight_dtype, tokens, routes, lowp)

    @jax.jit
    def head(x, positions, key):
        rows, w = _head(cfg, x, positions, key, dt, lowp)
        return jnp.matmul(rows, w)

    with jax.default_matmul_precision("highest"):
        return head(x, jnp.asarray(positions, jnp.int32),
                    weights.seed_key(seed))
