"""The plain reference of a latent-attention decoder with routed experts and
manifold-constrained hyper-connections, with YaRN rope (``model_type``
``xing4_0``).

Straightforward ``jax.numpy`` in float32 at matmul precision "highest". No
kernels, no cache, no absorbed form, no sorting of tokens by expert: a
layer runs over one sequence at a time, its attention in the expanded form
one head and one block of queries at a time against every position with a
causal mask (so a 10k-position sequence fits), the expert layer a loop
over the experts, each applied to every token and weighted by that token's
(mostly zero) weight for it. It imports nothing of the program and takes
nothing the program has made: weights come from
``weights/hyper_mla_moe_decoder.py`` and the seed, one layer at a time,
rounded to the served dtype; the router's selection and weights are
``reference/mla_moe_decoder.py``'s ``route``.

The residual is n = ``hc_mult`` streams X [n, C] a position (C hidden).
Each layer has two sites, the attention branch's (0) and the MLP's (1);
at a site, with phi, alpha, b its weights (the configuration's
``assumed`` says which choices are not published):

- ``x^ = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)``, no weight;
  ``t = x^ phi`` [2n + n^2];
- ``H_pre = sigmoid(alpha_pre t[:n] + b[:n]) + hc_eps``;
  ``H_post = 2 sigmoid(alpha_post t[n:2n] + b[n:2n])``;
  ``H_res = Sinkhorn(exp(clip(alpha_res mat(t[2n:]) + mat(b[2n:]),
  mhc_h_res_clamp_min, _max)))``, ``hc_sinkhorn_iters`` alternations of
  column then row normalisation, each denominator + ``hc_eps``;
- the branch ``y = F(RMSNorm(sum_i H_pre[i] X[i]))``;
- ``X <- H_res X + H_post^T y``.

``X_0`` is the embedding in every stream; the streams are summed before
the final RMSNorm and the untied head.

F is the DeepSeek-V3 block's (H heads of widths nope, rope, v):

- latent attention: ``c_q = RMSNorm(h W_qa)``; ``q = c_q W_qb``, a head
  ``[q_nope | q_rope]``; ``[c_kv | k_r] = h W_kva``; ``c_kv =
  RMSNorm(c_kv)``; ``k_r`` and ``q_rope`` rotated (the adjacent pairs (2i,
  2i+1), ``rope_interleave``) at YaRN's frequencies: pair i of d_r/2 at
  ``theta^(-2i/d_r) (1 - ramp_i + ramp_i / factor)``, ``ramp_i = clip((i -
  low) / (high - low), 0, 1)``, low = floor and high = ceil of ``d_r
  ln(original / (beta 2 pi)) / (2 ln theta)`` at beta_fast and
  beta_slow, cos and sin times ``mscale(mscale) / mscale(mscale_all_dim)``
  (``mscale(m) = 0.1 m ln(factor) + 1``); ``[k_nope | v]`` a head ``=
  c_kv W_kvb``; scores ``(q . k) / sqrt(nope + rope) *
  mscale(mscale_all_dim)^2``, causal softmax, ``sum p v``, ``W_o``;
- MLP: SwiGLU at ``intermediate_size`` below ``first_k_dense_replace``;
  after, sigmoid scores over the experts, the ``num_experts_per_tok``
  largest ``s + b`` chosen, weights ``s_e / sum_sel s *
  routed_scaling_factor``, ``y = sum_sel w_e SwiGLU_e(h) +
  SwiGLU_shared(h)``.

``routes`` and ``margin`` are as in ``reference/mla_moe_decoder.py``: the
server's choices of experts in the place of the reference's own, and how
far below its own k-th best ``s + b`` the worst of them lies. ``lowp``
puts the reference in a lower precision for the control that has to fail
``correct``: "w8" rounds every matrix of the bf16 weights to int8 per
output channel (phi and the router stay float32, as stated). "a16" is no
such control: it rounds every activation to bfloat16 where the program
stores one (the streams included), the witness of what the served dtype
alone moves. ``fault`` puts a fault in the reference's place: the
router's two of ``reference/mla_moe_decoder.py``, "h_res_identity" (every
stream mix the identity: the plain residual repeated n times) and
"sinkhorn_one" (one alternation of the projection).
"""

from __future__ import annotations

import functools
import importlib.util
import json
import math
import pathlib

import jax
import jax.numpy as jnp

_HERE = pathlib.Path(__file__).resolve().parent


def _load(rel: str, name: str):
    spec = importlib.util.spec_from_file_location(name, _HERE.parent / rel)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


weights = _load("weights/hyper_mla_moe_decoder.py",
                "bench_weights_hyper_mla_moe_decoder")
_routing = _load("reference/mla_moe_decoder.py",
                 "bench_reference_hyper_mla_routing")

F32 = jnp.float32
FAULTS = (None, "weights_with_bias", "select_without_bias",
          "h_res_identity", "sinkhorn_one")
_ROUTER_FAULTS = ("weights_with_bias", "select_without_bias")
QUERY_BLOCK = 512
_f32 = _routing._f32


def _check(cfg: dict) -> None:
    sc = cfg["rope_scaling"]
    if (cfg["scoring_func"] != "sigmoid" or cfg["topk_method"] != "noaux_tc"
            or cfg["n_group"] != 1 or cfg["topk_group"] != 1
            or not cfg["norm_topk_prob"] or not cfg["rope_interleave"]
            or not sc or sc.get("type") != "yarn" or cfg["attention_bias"]
            or cfg["hidden_act"] != "silu" or cfg["tie_word_embeddings"]
            or cfg["hc_mult"] < 1 or cfg["moe_layer_freq"] != 1
            or cfg["mhc_h_res_clamp_min"] != -cfg["mhc_h_res_clamp_max"]):
        raise ValueError("only the stated form of the layers is written down")


def _round(w, lowp):
    """A bf16 matrix [K, N] as the control holds it: per output channel
    int8 ("w8"); "a16" and None keep it."""
    if lowp == "w8":
        amax = jnp.max(jnp.abs(w), axis=0, keepdims=True)
        scale = jnp.maximum(amax / 127.0, 1e-12)
        return jnp.clip(jnp.round(w / scale), -127, 127) * scale
    if lowp not in (None, "a16"):
        raise ValueError(f"lowp must be None, 'w8' or 'a16', got {lowp!r}")
    return w


def _act(x, lowp):
    """An activation as "a16" holds it: rounded to bfloat16."""
    if lowp == "a16":
        return jax.lax.reduce_precision(x, 8, 7)
    return x


def _mm(x, w, lowp):
    return _act(jnp.matmul(x, _round(w, lowp)), lowp)


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(cfg: dict):
    """The rotated pairs' frequencies [rope / 2] under YaRN."""
    sc, base = cfg["rope_scaling"], float(cfg["rope_theta"])
    dim = cfg["qk_rope_head_dim"]

    def correction(rotations):
        return (dim * math.log(sc["original_max_position_embeddings"]
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction(sc["beta_fast"])), 0)
    high = min(math.ceil(correction(sc["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    pos_freqs = base ** (jnp.arange(0, dim, 2, dtype=F32) / dim)
    extra, inter = 1.0 / pos_freqs, 1.0 / (sc["factor"] * pos_freqs)
    keep = 1.0 - jnp.clip((jnp.arange(dim // 2, dtype=F32) - low)
                          / (high - low), 0.0, 1.0)
    return inter * (1.0 - keep) + extra * keep


def softmax_scale(cfg: dict) -> float:
    sc = cfg["rope_scaling"]
    m = _mscale(sc["factor"], sc["mscale_all_dim"])
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def rope(cfg: dict, x, positions):
    """x [L, ..., rope]: the adjacent pairs rotated in place at YaRN's
    frequencies, cos and sin at YaRN's gain."""
    sc = cfg["rope_scaling"]
    gain = _mscale(sc["factor"], sc["mscale"]) / _mscale(
        sc["factor"], sc["mscale_all_dim"])
    half = x.shape[-1] // 2
    ang = positions.astype(F32)[:, None] * yarn_inv_freq(cfg)[None, :]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang) * gain, jnp.sin(ang) * gain
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _attend_head(q, k, v, scale):
    """One head over one sequence, a block of queries at a time: q, k
    [L, D], v [L, Dv], causal."""
    l = q.shape[0]
    pad = -l % QUERY_BLOCK
    blocks = jnp.pad(q, ((0, pad), (0, 0))).reshape(-1, QUERY_BLOCK,
                                                    q.shape[-1])
    pos = jnp.arange(l)

    def block(t):
        qb, start = t
        s = (qb @ k.T) * scale
        ok = pos[None, :] <= start + jnp.arange(QUERY_BLOCK)[:, None]
        return jax.nn.softmax(jnp.where(ok, s, -1e30), axis=-1) @ v

    o = jax.lax.map(block, (blocks, jnp.arange(blocks.shape[0])
                            * QUERY_BLOCK))
    return o.reshape(-1, v.shape[-1])[:l]


def latent_attention(cfg: dict, a, lw, lowp=None):
    """The expanded form over one sequence a [L, hidden] -> [L, hidden]."""
    l = a.shape[0]
    h, nope, rp, vd = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                       cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    kr, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    pos = jnp.arange(l)
    c_q = _act(rms_norm(_mm(a, lw["wq_a"], lowp), lw["q_a_norm"], eps), lowp)
    q = _mm(c_q, lw["wq_b"], lowp).reshape(l, h, nope + rp)
    q = jnp.concatenate(
        [q[..., :nope], _act(rope(cfg, q[..., nope:], pos), lowp)], axis=-1)
    kv = _mm(a, lw["wkv_a"], lowp)
    c_kv = _act(rms_norm(kv[:, :kr], lw["kv_a_norm"], eps), lowp)
    k_r = _act(rope(cfg, kv[:, kr:], pos), lowp)
    kvb = _mm(c_kv, lw["wkv_b"], lowp).reshape(l, h, nope + vd)
    k = jnp.concatenate(
        [kvb[..., :nope], jnp.broadcast_to(k_r[:, None], (l, h, rp))], -1)
    scale = softmax_scale(cfg)
    o = jax.lax.map(lambda t: _attend_head(*t, scale), (
        q.transpose(1, 0, 2), k.transpose(1, 0, 2),
        kvb[..., nope:].transpose(1, 0, 2)))
    o = _act(o.transpose(1, 0, 2).reshape(l, h * vd), lowp)
    return _mm(o, lw["wo"], lowp)


def _swiglu(x, gate, up, down, lowp):
    return _mm(_act(jax.nn.silu(_mm(x, gate, lowp)) * _mm(x, up, lowp),
                    lowp), down, lowp)


def routed_experts(cfg: dict, m, lw, chosen, w, lowp=None):
    """sum_sel w_e SwiGLU_e(m) over every expert -> [T, hidden]."""
    def one(y, t):
        e, gate, up, down = t
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)     # [T]
        return y + w_e[:, None] * _swiglu(m, gate, up, down, lowp), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(m), (
        jnp.arange(cfg["n_routed_experts"]), lw["experts_gate"],
        lw["experts_up"], lw["experts_down"]))
    return y


def sinkhorn(m, iters: int, eps: float):
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
    return m


def coefficients(cfg: dict, x, lw, site: int, fault=None):
    """A site's H_pre [L, n], H_post [L, n] and H_res [L, n, n] for one
    sequence's streams x [L, n, hidden]."""
    n, eps = cfg["hc_mult"], cfg["hc_eps"]
    l = x.shape[0]
    flat = x.reshape(l, -1)
    flat = flat * jax.lax.rsqrt(jnp.mean(flat * flat, axis=-1, keepdims=True)
                                + cfg["rms_norm_eps"])
    t = flat @ lw["hc_phi"][site]
    alpha, b = lw["hc_alpha"][site], lw["hc_bias"][site]
    pre = jax.nn.sigmoid(alpha[0] * t[:, :n] + b[:n]) + eps
    post = 2.0 * jax.nn.sigmoid(alpha[1] * t[:, n:2 * n] + b[n:2 * n])
    res = jnp.clip(alpha[2] * t[:, 2 * n:] + b[2 * n:],
                   cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"])
    res = sinkhorn(jnp.exp(res).reshape(l, n, n),
                   1 if fault == "sinkhorn_one" else cfg["hc_sinkhorn_iters"],
                   eps)
    if fault == "h_res_identity":
        res = jnp.broadcast_to(jnp.eye(n, dtype=F32), res.shape)
    return pre, post, res


def _branch(cfg: dict, x, lw, site, fault, lowp, branch):
    """X <- H_res X + H_post^T branch(sum_i H_pre[i] X[i]) -> (X, aux)."""
    pre, post, res = coefficients(cfg, x, lw, site, fault)
    y, aux = branch(_act(jnp.einsum("ln,lnc->lc", pre, x), lowp))
    out = jnp.einsum("lij,ljc->lic", res, x) + post[..., None] * y[:, None]
    return _act(out, lowp), aux


def layer_forward(cfg: dict, kind: str, x, lw, lowp=None, chosen=None,
                  fault=None):
    """One decoder block over the streams x [n_seq, L, n, hidden], a
    sequence at a time -> (x, the experts chosen [n_seq, L, k], the
    margin [n_seq, L]); the last two None for a dense layer."""
    eps = cfg["rms_norm_eps"]
    router_fault = fault if fault in _ROUTER_FAULTS else None

    def attend(a):
        h = _act(rms_norm(a, lw["attn_norm"], eps), lowp)
        return latent_attention(cfg, h, lw, lowp), None

    def one(t):
        row, given = t
        row, _ = _branch(cfg, row, lw, 0, fault, lowp, attend)

        def mlp(a):
            m = _act(rms_norm(a, lw["mlp_norm"], eps), lowp)
            if kind == "dense":
                return _swiglu(m, lw["w_gate"], lw["w_up"], lw["w_down"],
                               lowp), None
            picks, w, margin = _routing.route(cfg, m, lw, given,
                                              router_fault)
            y = routed_experts(cfg, m, lw, picks, w, lowp) + _swiglu(
                m, lw["shared_gate"], lw["shared_up"], lw["shared_down"],
                lowp)
            return y, (picks, margin)

        row, aux = _branch(cfg, row, lw, 1, fault, lowp, mlp)
        return (row,) if aux is None else (row,) + aux

    if kind == "dense":
        return jax.lax.map(lambda row: one((row, None)), x)[0], None, None
    if chosen is None:
        return jax.lax.map(lambda row: one((row, None)), x)
    return jax.lax.map(one, (x, chosen))


@functools.lru_cache(maxsize=None)
def _programs(cfg_text: str, dtype: str, lowp, fault) -> dict:
    """`hidden`'s programs for one configuration (its JSON text), weight
    dtype, precision and fault, made once in a process: the seed's key is
    an argument of every program (closed over it would be a constant, and
    every seed would compile anew), so every seed reuses them."""
    cfg = json.loads(cfg_text)
    dt = jnp.dtype(dtype)
    bcfg = weights.block_cfg(cfg)

    @jax.jit
    def embed(tokens, key):
        e = _f32(weights.embed(key, bcfg, dt))[tokens]
        return jnp.broadcast_to(e[:, :, None],
                                e.shape[:2] + (cfg["hc_mult"], e.shape[-1]))

    def block(kind):
        @jax.jit
        def run(x, index, key, chosen):
            lw = _f32(weights.layer(key, cfg, index, kind, dt))
            return layer_forward(cfg, kind, x, lw, lowp, chosen, fault)
        return run

    return {"embed": embed, **{kind: block(kind) for kind in weights.KINDS},
            "sum": jax.jit(lambda x: jnp.sum(x, axis=-2))}


def hidden(cfg: dict, seed: int, weight_dtype, tokens, routes=None,
           lowp=None, fault=None):
    """Full forward over ``tokens`` [n, L] up to the sum of the streams
    -> (x [n, L, hidden], chosen [n, L, routed layers, k], margin [n, L,
    routed layers]). Weights are made from the seed in ``weight_dtype``
    (as served) one layer at a time and used in float32."""
    _check(cfg)
    if fault not in FAULTS:
        raise ValueError(f"fault must be one of {FAULTS}, got {fault!r}")
    key = weights.seed_key(seed)
    run = _programs(json.dumps(cfg, sort_keys=True),
                    jnp.dtype(weight_dtype).name, lowp, fault)
    with jax.default_matmul_precision("highest"):
        x = run["embed"](jnp.asarray(tokens, jnp.int32), key)
        picks, margins = [], []
        for i, kind in enumerate(weights.layer_kinds(cfg)):
            given = (None if routes is None or kind == "dense" else
                     jnp.asarray(routes, jnp.int32)[:, :, len(picks)])
            x, chosen, margin = run[kind](x, jnp.int32(i), key, given)
            if kind != "dense":
                picks.append(chosen)
                margins.append(margin)
        x = run["sum"](x)
    return x, jnp.stack(picks, axis=2), jnp.stack(margins, axis=2)


def _head_rows(cfg: dict, x, positions, dt, lowp):
    rows = jnp.take_along_axis(x, positions[..., None], axis=1)
    return _act(rms_norm(rows, _f32(weights.final_norm(cfg, dt)),
                         cfg["rms_norm_eps"]), lowp)


@functools.lru_cache(maxsize=None)
def _heads(cfg_text: str, dtype: str, lowp, block: int) -> dict:
    """The final norm and the head at given positions, made once in a
    process as `_programs` are: "scores" (`served_scores`' numbers a
    ``block`` of positions at a time) and "logits" (`served_logits`)."""
    cfg = json.loads(cfg_text)
    dt = jnp.dtype(dtype)
    bcfg = weights.block_cfg(cfg)

    @jax.jit
    def scores(x, positions, served, key):
        rows = _head_rows(cfg, x, positions, dt, lowp)
        w = _round(_f32(weights.unembed(key, bcfg, dt)), lowp)
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

    @jax.jit
    def logits(x, positions, key):
        rows = _head_rows(cfg, x, positions, dt, lowp)
        return _mm(rows, _f32(weights.unembed(key, bcfg, dt)), lowp)

    return {"scores": scores, "logits": logits}


def served_scores(cfg: dict, seed: int, weight_dtype, tokens, positions,
                  served, routes=None, lowp=None, fault=None,
                  block: int = 512) -> dict:
    """What the comparison needs of the logits at ``positions`` [n, P],
    without holding [n, P, vocab]: ``best`` (the largest logit), ``first``
    (its token), ``got`` (the logit of ``served`` [n, P]) and ``got_next``
    (of the token id after it, modulo the vocabulary: what one altered
    token would read), each [n, P]; with them ``chosen`` and ``margin`` of
    `hidden`, over all positions."""
    x, chosen, margin = hidden(cfg, seed, weight_dtype, tokens, routes,
                               lowp, fault)
    head = _heads(json.dumps(cfg, sort_keys=True),
                  jnp.dtype(weight_dtype).name, lowp, block)["scores"]
    with jax.default_matmul_precision("highest"):
        best, first, got, got_next = head(
            x, jnp.asarray(positions, jnp.int32),
            jnp.asarray(served, jnp.int32), weights.seed_key(seed))
    return {"best": best, "first": first, "got": got, "got_next": got_next,
            "chosen": chosen, "margin": margin}


def served_logits(cfg: dict, seed: int, weight_dtype, tokens, positions,
                  lowp=None, routes=None, fault=None):
    """Full forward over ``tokens`` [n, L] -> logits [n, P, vocab] at
    ``positions`` [n, P], for a vocabulary and a P whose product fits (a
    test's). ``routes`` as in `hidden`."""
    x, _, _ = hidden(cfg, seed, weight_dtype, tokens, routes, lowp, fault)
    head = _heads(json.dumps(cfg, sort_keys=True),
                  jnp.dtype(weight_dtype).name, lowp, 512)["logits"]
    with jax.default_matmul_precision("highest"):
        return head(x, jnp.asarray(positions, jnp.int32),
                    weights.seed_key(seed))
