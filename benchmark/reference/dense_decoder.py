"""The plain reference of a dense decoder (Mistral/Llama-shaped).

Straightforward ``jax.numpy`` in float32 at matmul precision "highest":
RMSNorm, rotary positions (rotate-half, as the published models), grouped
query attention with a causal (and optionally sliding-window) mask, SwiGLU,
an untied unembedding; for training the mean next-token cross-entropy,
its gradient, a global-norm clip and AdamW. No kernels, no cache, no
batching tricks. It imports nothing of the program and takes nothing the
program has made: weights come from ``weights/dense_decoder.py`` and the
seed, one layer at a time where the whole would not fit.

Departures from the published description, each for memory only and none
changing a result beyond float32 rounding: attention is computed one
sequence (serving) or one group of heads (training) at a time, the
training loss in blocks of rows, and the training layers are
re-materialised in the backward pass.

``lowp`` puts the reference in a lower precision, for the control that
has to fail ``correct``: "w8" rounds every matrix to int8 per output
channel, "w8a8" also rounds each matmul's activations to int8 per row.
Rounding is straight-through, so gradients exist.
"""

from __future__ import annotations

import functools
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

_HERE = pathlib.Path(__file__).resolve().parent


def _load(rel: str, name: str):
    spec = importlib.util.spec_from_file_location(name, _HERE.parent / rel)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


weights = _load("weights/dense_decoder.py", "bench_weights_dense_decoder")

F32 = jnp.float32


# ------------------------------------------------------------ lower precision

def _round_int8(x, axis):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax / 127.0, 1e-12)
    q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return x + jax.lax.stop_gradient(q - x)


def _mm(x, w, lowp):
    """x [..., K] @ w [K, N] in float32; ``lowp`` rounds the operands."""
    if lowp in ("w8", "w8a8"):
        w = _round_int8(w, axis=0)
    if lowp == "w8a8":
        x = _round_int8(x, axis=-1)
    return jnp.matmul(x, w)


# ------------------------------------------------------------------- pieces

def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def rope(x, positions, theta):
    """x [L, H, D]; rotate-half convention."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, window):
    """q [L, G, D] (a group of query heads sharing k, v [L, D])."""
    l = q.shape[0]
    s = jnp.einsum("lgd,md->glm", q, k) * q.shape[-1] ** -0.5
    i, j = jnp.arange(l)[:, None], jnp.arange(l)[None, :]
    mask = j <= i
    if window:
        mask &= j > i - window
    s = jnp.where(mask[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("glm,md->lgd", p, v)


def layer_forward(cfg: dict, x, lw, lowp=None):
    """One decoder block over one sequence x [L, hidden]."""
    l = x.shape[0]
    h, kvh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    window = cfg.get("sliding_window") or 0
    pos = jnp.arange(l)
    a = rms_norm(x, lw["attn_norm"], eps)
    q = rope(_mm(a, lw["wq"], lowp).reshape(l, h, hd), pos, theta)
    k = rope(_mm(a, lw["wk"], lowp).reshape(l, kvh, hd), pos, theta)
    v = _mm(a, lw["wv"], lowp).reshape(l, kvh, hd)
    qg = q.reshape(l, kvh, h // kvh, hd).transpose(1, 0, 2, 3)
    # one group of query heads (and its k, v) at a time: the [G, L, L]
    # scores of one group are what is live, not all heads'
    o = jax.lax.map(lambda t: _attend(t[0], t[1], t[2], window),
                    (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    o = o.transpose(1, 0, 2, 3).reshape(l, h * hd)
    x = x + _mm(o, lw["wo"], lowp)
    m = rms_norm(x, lw["mlp_norm"], eps)
    gate = jax.nn.silu(_mm(m, lw["w_gate"], lowp))
    return x + _mm(gate * _mm(m, lw["w_up"], lowp), lw["w_down"], lowp)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


# ------------------------------------------------------------------ serving

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
        return weights.embed(key, cfg, dt).astype(F32)[tokens]

    @jax.jit
    def block(x, index, key):
        lw = _f32(weights.layer(key, cfg, index, dt))
        return jax.lax.map(
            lambda row: layer_forward(cfg, row, lw, lowp), x)

    @jax.jit
    def head(x, positions, key):
        rows = jnp.take_along_axis(x, positions[..., None], axis=1)
        rows = rms_norm(rows, weights.final_norm(cfg, dt).astype(F32),
                        cfg["rms_norm_eps"])
        return _mm(rows, weights.unembed(key, cfg, dt).astype(F32), lowp)

    with jax.default_matmul_precision("highest"):
        x = embed(jnp.asarray(tokens, jnp.int32), key)
        for i in range(cfg["num_hidden_layers"]):
            x = block(x, jnp.int32(i), key)
        return head(x, jnp.asarray(positions, jnp.int32), key)


# ----------------------------------------------------------------- training

def _nll_rows(x, unembed, targets, lowp, block):
    """Mean NLL of rows x [N, hidden] against targets [N], ``block`` rows
    at a time so that no [N, vocab] logits are live."""
    n = x.shape[0]
    xb = x.reshape(n // block, block, -1)
    tb = targets.reshape(n // block, block)

    @jax.checkpoint
    def one(args):
        xr, tr = args
        logits = _mm(xr, unembed, lowp)
        lse = jax.nn.logsumexp(logits, axis=-1)
        hit = jnp.take_along_axis(logits, tr[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - hit)

    return jnp.sum(jax.lax.map(one, (xb, tb))) / n


def loss_fn(cfg: dict, params, tokens, targets, lowp=None):
    """Mean next-token cross-entropy of tokens/targets [B, L]."""
    b, l = tokens.shape
    x = params["embed"][tokens]                       # [B, L, hidden]
    n_layers = params["layers"]["wq"].shape[0]
    step = jax.checkpoint(
        lambda row, lw: layer_forward(cfg, row, lw, lowp))
    for i in range(n_layers):
        lw = jax.tree.map(lambda a: a[i], params["layers"])
        x = jax.lax.map(lambda row: step(row, lw), x)
    x = rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])
    block = 512 if (b * l) % 512 == 0 else l
    return _nll_rows(x.reshape(b * l, -1), params["unembed"],
                     targets.reshape(-1), lowp, block)


def leaf_names(params) -> list:
    """'embed', 'layers/wq', ... in the tree's own (sorted-key) order."""
    paths = jax.tree_util.tree_flatten_with_path(params)[0]
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in paths]


def leaf_norms(tree) -> dict:
    return dict(zip(leaf_names(tree),
                    (jnp.sqrt(jnp.sum(jnp.square(a.astype(F32))))
                     for a in jax.tree.leaves(tree))))


def leaf_projections(tree, k: int = 1024) -> dict:
    """A sketch of each leaf: its elements, each under a fixed random sign
    (the same for any tree of these leaves), summed into ``k`` buckets by
    position modulo ``k``. The squared distance between two sides'
    sketches estimates the squared norm of the leaves' difference (to
    about sqrt(2 / k) of it) without both leaves in one place: of the
    first order in the difference, where a gap of norms is of the second,
    so rounding that leaves every norm in place still shows here."""
    out = {}
    for i, (name, a) in enumerate(zip(leaf_names(tree),
                                      jax.tree.leaves(tree))):
        flat = a.astype(F32).reshape(-1)
        signs = jax.random.rademacher(
            jax.random.fold_in(jax.random.PRNGKey(7), i), flat.shape, F32)
        flat = jnp.pad(flat * signs, (0, -flat.size % k))
        out[name] = flat.reshape(-1, k).sum(0)
    return out


def train_steps(cfg: dict, seed: int, opt: dict, batches, lowp=None,
                frozen: bool = False) -> dict:
    """Follow the first ``len(batches)`` steps of clip + AdamW from the
    seed's weights (float32). Returns ``losses`` (one a step), and per
    leaf ``grad_norm`` (the first gradient as the optimizer's moments get
    it, after the global-norm clip), ``grad_proj`` (that gradient's
    ``leaf_projections``), ``grad_norm_raw`` (the global norm before the
    clip) and ``delta_norm`` (norm of the parameters' change after the
    last step). ``frozen`` plants the fault of a step that returns its
    parameters unchanged."""
    key = weights.seed_key(seed)
    lr, b1, b2, eps = opt["lr"], opt["b1"], opt["b2"], opt["eps"]
    wd, clip = opt["weight_decay"], opt["grad_clip"]

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, mu, nu, t, tokens, targets):
        loss, g = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, tokens, targets, lowp))(params)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(a))
                             for a in jax.tree.leaves(g)))
        g = jax.tree.map(
            lambda a: jnp.where(gnorm < clip, a, a / gnorm * clip), g)
        gn, gp = leaf_norms(g), leaf_projections(g)
        mu = jax.tree.map(lambda m, a: b1 * m + (1 - b1) * a, mu, g)
        nu = jax.tree.map(lambda v, a: b2 * v + (1 - b2) * a * a, nu, g)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t

        def upd(p, m, v):
            if frozen:
                return p
            return p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps) + wd * p)

        return (jax.tree.map(upd, params, mu, nu), mu, nu, loss, gnorm,
                gn, gp)

    make = jax.jit(lambda key: weights.whole(key, cfg, F32))
    delta = jax.jit(lambda p, key: leaf_norms(
        jax.tree.map(lambda a, b: a - b, p, weights.whole(key, cfg, F32))))
    with jax.default_matmul_precision("highest"):
        params = make(key)
        mu = jax.tree.map(jnp.zeros_like, params)
        nu = jax.tree.map(jnp.zeros_like, params)
        out = {"losses": []}
        for t, (tokens, targets) in enumerate(batches, start=1):
            params, mu, nu, loss, gnorm, gn, gp = step(
                params, mu, nu, jnp.float32(t), jnp.asarray(tokens),
                jnp.asarray(targets))
            out["losses"].append(float(loss))
            if t == 1:
                out["grad_norm_raw"] = float(gnorm)
                out["grad_norm"] = {k: float(v) for k, v in gn.items()}
                out["grad_proj"] = {k: np.asarray(v) for k, v in gp.items()}
        out["delta_norm"] = {k: float(v) for k, v in delta(params, key).items()}
    return out
