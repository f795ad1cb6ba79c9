"""Weights of a hybrid decoder (gated-delta-rule layers beside full-attention
layers), made on the device from ``--seed``.

The benchmark makes the weights and hands them to the program; the plain
reference makes the same ones again, layer by layer, from the same seed.
Nothing here imports the program. ``cfg["layer_types"][i]`` picks layer
i's kind. Matrices are 2-D in the published sense, input dimension first:

    both kinds   w_gate, w_up [hidden, intermediate]  w_down [intermediate, hidden]
                 attn_norm, mlp_norm [hidden]
    full         wq [hidden, heads*head_dim]  wk, wv [hidden, kv_heads*head_dim]
                 wo [heads*head_dim, hidden]  q_norm [heads*head_dim]
                 k_norm [kv_heads*head_dim]
    linear       wq, wk [hidden, H*d_k]  wv, wg [hidden, H*d_v]  wa, wb [hidden, H]
                 wo [H*d_v, hidden]  conv [kernel, 2*H*d_k + H*d_v] (q, k, v channels)
                 A_log, dt_bias [H]  o_norm [d_v]

The initializer (the configuration's ``assumed``): every matrix is
normal(0, fan_in ** -0.5), norm weights are 1, convolution weights
normal(0, kernel ** -0.5), ``A_log`` = log(U(1, 16)), ``dt_bias`` =
softplus^-1(dt) with dt log-uniform in [1e-3, 1e-1]. A layer's numbers
depend on (seed, layer index, name) alone, so a stack made in one call and a
layer made on its own hold the same numbers.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

KINDS = ("full_attention", "linear_attention")
_EMBED, _UNEMBED = 1000, 1001   # fold-in ids beyond any layer index
_MLP = ("w_gate", "w_up", "w_down")


def seed_key(seed: int) -> jax.Array:
    """A key for any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed % (2 ** 31))
    return jax.random.fold_in(key, seed // (2 ** 31))


def linear_sizes(cfg: dict) -> tuple:
    """-> (heads, d_k, d_v, kernel) of the linear mixer."""
    if cfg["linear_num_key_heads"] != cfg["linear_num_value_heads"]:
        raise ValueError("key and value head counts differ: not written down")
    return (cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
            cfg["linear_value_head_dim"], cfg["linear_conv_kernel_dim"])


def conv_channels(cfg: dict) -> int:
    h, dk, dv, _ = linear_sizes(cfg)
    return 2 * h * dk + h * dv


def matrix_shapes(cfg: dict, kind: str) -> dict:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    mlp = {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}
    if kind == "full_attention":
        hd = cfg["head_dim"]
        q = cfg["num_attention_heads"] * hd
        kv = cfg["num_key_value_heads"] * hd
        return {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
                **mlp}
    h, dk, dv, _ = linear_sizes(cfg)
    return {"wq": (d, h * dk), "wk": (d, h * dk), "wv": (d, h * dv),
            "wg": (d, h * dv), "wa": (d, h), "wb": (d, h),
            "wo": (h * dv, d), **mlp}


def _normal(key, shape, dtype, fan_in=None):
    scale = (shape[0] if fan_in is None else fan_in) ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def layer(key: jax.Array, cfg: dict, index, kind: str, dtype) -> dict:
    """One layer's weights (traceable in ``index``; ``kind`` is static)."""
    lkey = jax.random.fold_in(key, index)
    out = {name: _normal(jax.random.fold_in(lkey, i), shape, dtype)
           for i, (name, shape) in enumerate(matrix_shapes(cfg, kind).items())}
    d = cfg["hidden_size"]
    out["attn_norm"] = jnp.ones((d,), dtype)
    out["mlp_norm"] = jnp.ones((d,), dtype)
    if kind == "full_attention":
        hd = cfg["head_dim"]
        out["q_norm"] = jnp.ones((cfg["num_attention_heads"] * hd,), dtype)
        out["k_norm"] = jnp.ones((cfg["num_key_value_heads"] * hd,), dtype)
        return out
    h, _, dv, kernel = linear_sizes(cfg)
    ckey, akey, dkey = (jax.random.fold_in(lkey, 100 + i) for i in range(3))
    out["conv"] = _normal(ckey, (kernel, conv_channels(cfg)), dtype)
    out["A_log"] = jnp.log(jax.random.uniform(
        akey, (h,), jnp.float32, 1.0, 16.0)).astype(dtype)
    dt = jnp.exp(jax.random.uniform(
        dkey, (h,), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
    out["dt_bias"] = (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    out["o_norm"] = jnp.ones((dv,), dtype)
    return out


def layer_indices(cfg: dict, kind: str) -> list:
    kinds = cfg["layer_types"]
    if len(kinds) != cfg["num_hidden_layers"] or set(kinds) - set(KINDS):
        raise ValueError("layer_types does not name every layer's kind")
    return [i for i, k in enumerate(kinds) if k == kind]


def stack(key: jax.Array, cfg: dict, kind: str, dtype) -> dict:
    """Every layer of one kind at once, each leaf with a leading axis over
    those layers (in the order the model has them)."""
    idx = jnp.asarray(layer_indices(cfg, kind), jnp.int32)
    return jax.vmap(lambda i: layer(key, cfg, i, kind, dtype))(idx)


def embed(key: jax.Array, cfg: dict, dtype) -> jax.Array:
    shape = (cfg["vocab_size"], cfg["hidden_size"])
    return _normal(jax.random.fold_in(key, _EMBED), shape, dtype,
                   fan_in=cfg["hidden_size"])


def unembed(key: jax.Array, cfg: dict, dtype) -> jax.Array:
    shape = (cfg["hidden_size"], cfg["vocab_size"])
    return _normal(jax.random.fold_in(key, _UNEMBED), shape, dtype)


def final_norm(cfg: dict, dtype) -> jax.Array:
    return jnp.ones((cfg["hidden_size"],), dtype)


def whole(key: jax.Array, cfg: dict, dtype) -> dict:
    """The whole model: ``{"embed", "layers": {kind: stack}, "final_norm",
    "unembed"}``."""
    return {"embed": embed(key, cfg, dtype),
            "layers": {kind: stack(key, cfg, kind, dtype) for kind in KINDS
                       if layer_indices(cfg, kind)},
            "final_norm": final_norm(cfg, dtype),
            "unembed": unembed(key, cfg, dtype)}
