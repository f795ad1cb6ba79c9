"""The plain reference of a hybrid decoder: gated-delta-rule layers beside
full-attention layers, picked by ``cfg["layer_types"]``.

Straightforward ``jax.numpy`` in float32 at matmul precision "highest". No
kernels, no cache, no chunking: the delta rule is one ``lax.scan`` over
the positions of a sequence. It imports nothing of the program and takes
nothing the program has made: weights come from
``weights/hybrid_decoder.py`` and the seed, one layer at a time.

The layers, as the configuration states them (``x_t`` in R^hidden):

- block (both kinds), ``norm_order`` "post":  ``h = x + Norm_a(Mixer(x))``,
  ``y = h + Norm_m(MLP(h))``. ``MLP(h) = W_down(silu(W_gate h) * W_up h)``;
  logits = ``W_unembed RMSNorm(y_last)``.
- ``full_attention``: ``q = W_q x``, ``k = W_k x`` (with ``qk_norm`` an
  RMSNorm over the whole projected width, before the heads are split),
  ``v = W_v x``, as many KV heads as heads; no rotary positions
  (``rope_parameters.rope_theta`` null); causal ``softmax(q k^T /
  sqrt(head_dim)) v``; ``W_o``.
- ``linear_attention`` (H heads, d_k, d_v, kernel K): ``z = [W_q x; W_k x;
  W_v x]``; depthwise causal convolution over time, no bias, then SiLU:
  ``c_t = silu(sum_j w_j z_{t-K+1+j})``, ``z_{<0} = 0``; split into q', k',
  v by head; ``q = q' / sqrt(sum q'^2 + eps) * d_k^-1/2``, ``k`` likewise
  without the scale (eps = ``linear_l2_eps``); per head ``beta = 2
  sigmoid(W_b x)`` (``linear_allow_neg_eigval``; else no 2), ``alpha =
  exp(-exp(A_log) softplus(W_a x + dt_bias))``; state S [d_k, d_v], zero
  at the start: ``S <- alpha S; u = beta (v - S^T k); S <- S + k u^T;
  o = S^T q``; output ``RMSNorm_dv(o) * silu((W_g x)_head)``, heads
  concatenated, then ``W_o``.

Only what the one configuration that names this reference states is
written down; another norm order, a rotary embedding or grouped KV heads
raise. Departure for memory only: attention is computed one sequence and
one head at a time.

``lowp`` puts the reference in a lower precision, for the controls that
have to fail ``correct``: "w8" rounds every matrix to int8 per output
channel (as ``dense_decoder``); "s16" keeps the recurrent state in
bfloat16 between positions, the nearest precision below the float32 the
configuration states for it.
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


weights = _load("weights/hybrid_decoder.py", "bench_weights_hybrid_decoder")

F32 = jnp.float32


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


def _attend(q, k, v):
    """One head: q, k, v [L, D], causal."""
    l = q.shape[0]
    s = (q @ k.T) * q.shape[-1] ** -0.5
    s = jnp.where(jnp.arange(l)[:, None] >= jnp.arange(l)[None, :],
                  s, -jnp.inf)
    return jax.nn.softmax(s, axis=-1) @ v


def full_mixer(cfg: dict, x, lw, lowp=None):
    l = x.shape[0]
    h, hd = cfg["num_attention_heads"], cfg["head_dim"]
    if (cfg["num_key_value_heads"] != h
            or cfg["rope_parameters"]["rope_theta"] is not None):
        raise ValueError("only the stated form of the layer is written down")
    eps = cfg["rms_norm_eps"]
    q, k = _mm(x, lw["wq"], lowp), _mm(x, lw["wk"], lowp)
    if cfg["qk_norm"]:
        q, k = rms_norm(q, lw["q_norm"], eps), rms_norm(k, lw["k_norm"], eps)
    q, k, v = (t.reshape(l, h, hd) for t in (q, k, _mm(x, lw["wv"], lowp)))
    o = jax.lax.map(lambda t: _attend(*t), tuple(
        t.transpose(1, 0, 2) for t in (q, k, v)))
    return _mm(o.transpose(1, 0, 2).reshape(l, h * hd), lw["wo"], lowp)


def linear_mixer(cfg: dict, x, lw, lowp=None, length=None):
    """-> (the mixer's output [L, hidden], the state [H, d_k, d_v] after
    the sequence's first ``length`` positions: all of them where None)."""
    l = x.shape[0]
    h, dk, dv, kernel = weights.linear_sizes(cfg)
    if cfg["linear_conv_bias"] or cfg["linear_gate"] != "A_log_dt_bias" \
            or cfg["linear_output_norm"] != "gated_rms":
        raise ValueError("only the stated form of the layer is written down")
    z = jnp.concatenate([_mm(x, lw[n], lowp) for n in ("wq", "wk", "wv")], -1)
    zz = jnp.concatenate([jnp.zeros((kernel - 1, z.shape[1]), F32), z], 0)
    c = jax.nn.silu(sum(lw["conv"][j] * zz[j:j + l] for j in range(kernel)))
    q = c[:, :h * dk].reshape(l, h, dk)
    k = c[:, h * dk:2 * h * dk].reshape(l, h, dk)
    v = c[:, 2 * h * dk:].reshape(l, h, dv)
    eps = cfg["linear_l2_eps"]
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + eps) * dk ** -0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + eps)
    beta = jax.nn.sigmoid(_mm(x, lw["wb"], lowp))
    if cfg["linear_allow_neg_eigval"]:
        beta = 2.0 * beta
    alpha = jnp.exp(-jnp.exp(lw["A_log"]) * jax.nn.softplus(
        _mm(x, lw["wa"], lowp) + lw["dt_bias"]))

    def keep(s):        # what is kept of the state between positions
        # reduce_precision, not a pair of converts: the TPU compiler is
        # allowed to skip those ("excess precision") and then rounds nothing
        return jax.lax.reduce_precision(s, 8, 7) if lowp == "s16" else s

    def position(carry, t):
        s, held = carry
        q_t, k_t, v_t, a_t, b_t, index = t
        s = a_t[:, None, None] * s
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t))
        s = s + k_t[:, :, None] * u[:, None, :]
        s = keep(s)
        return (s, jnp.where(index < length, s, held)), \
            jnp.einsum("hkv,hk->hv", s, q_t)

    zero = jnp.zeros((h, dk, dv), F32)
    length = l if length is None else length
    (_, held), o = jax.lax.scan(position, (zero, zero),
                                (q, k, v, alpha, beta, jnp.arange(l)))
    gate = jax.nn.silu(_mm(x, lw["wg"], lowp).reshape(l, h, dv))
    o = rms_norm(o, lw["o_norm"], cfg["rms_norm_eps"]) * gate
    return _mm(o.reshape(l, h * dv), lw["wo"], lowp), held


def layer_forward(cfg: dict, kind: str, x, lw, lowp=None):
    """One decoder block over one sequence x [L, hidden]."""
    eps = cfg["rms_norm_eps"]
    if cfg["norm_order"] != "post":
        raise ValueError(f"norm_order {cfg['norm_order']!r}")
    if kind == "full_attention":
        mixed = full_mixer(cfg, x, lw, lowp)
    else:
        mixed, _ = linear_mixer(cfg, x, lw, lowp)
    x = x + rms_norm(mixed, lw["attn_norm"], eps)
    gate = jax.nn.silu(_mm(x, lw["w_gate"], lowp))
    mlp = _mm(gate * _mm(x, lw["w_up"], lowp), lw["w_down"], lowp)
    return x + rms_norm(mlp, lw["mlp_norm"], eps)


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


def served_logits(cfg: dict, seed: int, weight_dtype, tokens, positions,
                  lowp=None):
    """Full forward over ``tokens`` [n, L] (each row a prompt followed by
    the tokens that were served, then padding) -> logits [n, P, vocab] at
    ``positions`` [n, P]. Weights are made from the seed in
    ``weight_dtype`` (as served) one layer at a time and used in float32."""
    key = weights.seed_key(seed)
    dt = jnp.dtype(weight_dtype)

    # the key is an argument of every program: closed over it would be a
    # constant, and every seed would compile anew
    @jax.jit
    def embed(tokens, key):
        return _f32(weights.embed(key, cfg, dt))[tokens]

    def block(kind):
        @jax.jit
        def run(x, index, key):
            lw = _f32(weights.layer(key, cfg, index, kind, dt))
            return jax.lax.map(
                lambda row: layer_forward(cfg, kind, row, lw, lowp), x)
        return run

    blocks = {kind: block(kind) for kind in weights.KINDS}

    @jax.jit
    def head(x, positions, key):
        rows = jnp.take_along_axis(x, positions[..., None], axis=1)
        rows = rms_norm(rows, _f32(weights.final_norm(cfg, dt)),
                        cfg["rms_norm_eps"])
        return _mm(rows, _f32(weights.unembed(key, cfg, dt)), lowp)

    with jax.default_matmul_precision("highest"):
        x = embed(jnp.asarray(tokens, jnp.int32), key)
        for i, kind in enumerate(cfg["layer_types"]):
            x = blocks[kind](x, jnp.int32(i), key)
        return head(x, jnp.asarray(positions, jnp.int32), key)


def served_states(cfg: dict, seed: int, weight_dtype, tokens, lengths,
                  lowp=None):
    """The state of the model's first layer, which has to be a linear one,
    after the first ``lengths`` [n] positions of each row of ``tokens``
    [n, L] -> [n, H, d_k, d_v]. That layer reads the embedding, the same
    numbers on every side, so a gap in its state is the recurrence's own
    and not what the layers below have rounded."""
    if cfg["layer_types"][0] != "linear_attention":
        raise ValueError("the first layer is not a linear one")
    dt = jnp.dtype(weight_dtype)

    @jax.jit
    def run(tokens, lengths, key):
        x = _f32(weights.embed(key, cfg, dt))[tokens]
        lw = _f32(weights.layer(key, cfg, 0, "linear_attention", dt))
        return jax.lax.map(
            lambda t: linear_mixer(cfg, t[0], lw, lowp, t[1])[1],
            (x, lengths))

    with jax.default_matmul_precision("highest"):
        return run(jnp.asarray(tokens, jnp.int32),
                   jnp.asarray(lengths, jnp.int32), weights.seed_key(seed))
