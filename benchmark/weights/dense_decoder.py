"""Weights of a dense decoder, made on the device from ``--seed``.

The benchmark makes the weights and hands them to the program; the plain
reference makes the same ones again, layer by layer, from the same seed.
Nothing here imports the program. Matrices are 2-D in the published
(``config.json``) sense, input dimension first:

    wq [hidden, heads*head_dim]   wk, wv [hidden, kv_heads*head_dim]
    wo [heads*head_dim, hidden]   w_gate, w_up [hidden, intermediate]
    w_down [intermediate, hidden] embed [vocab, hidden]  unembed [hidden, vocab]

Every matrix is normal(0, fan_in ** -0.5); norm weights are 1. A layer's
matrices depend on (seed, layer index, name) alone, so a stack made in
one call and a layer made on its own hold the same numbers.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
_EMBED, _UNEMBED = 1000, 1001   # fold-in ids beyond any layer index


def seed_key(seed: int) -> jax.Array:
    """A key for any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed % (2 ** 31))
    return jax.random.fold_in(key, seed // (2 ** 31))


def matrix_shapes(cfg: dict) -> dict:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hd = cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
            "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}


def _normal(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32)
            * shape[0] ** -0.5).astype(dtype)


def layer(key: jax.Array, cfg: dict, index, dtype) -> dict:
    """One layer's matrices and norm weights (traceable in ``index``)."""
    lkey = jax.random.fold_in(key, index)
    out = {name: _normal(jax.random.fold_in(lkey, i), shape, dtype)
           for i, (name, shape) in enumerate(matrix_shapes(cfg).items())}
    d = cfg["hidden_size"]
    out["attn_norm"] = jnp.ones((d,), dtype)
    out["mlp_norm"] = jnp.ones((d,), dtype)
    return out


def stack(key: jax.Array, cfg: dict, dtype) -> dict:
    """Every layer at once, each leaf with a leading layer axis."""
    n = cfg["num_hidden_layers"]
    return jax.vmap(lambda i: layer(key, cfg, i, dtype))(jnp.arange(n))


def embed(key: jax.Array, cfg: dict, dtype) -> jax.Array:
    shape = (cfg["vocab_size"], cfg["hidden_size"])
    return (jax.random.normal(jax.random.fold_in(key, _EMBED), shape,
                              jnp.float32)
            * cfg["hidden_size"] ** -0.5).astype(dtype)


def unembed(key: jax.Array, cfg: dict, dtype) -> jax.Array:
    shape = (cfg["hidden_size"], cfg["vocab_size"])
    return _normal(jax.random.fold_in(key, _UNEMBED), shape, dtype)


def final_norm(cfg: dict, dtype) -> jax.Array:
    return jnp.ones((cfg["hidden_size"],), dtype)


def whole(key: jax.Array, cfg: dict, dtype) -> dict:
    """The whole model: ``{"embed", "layers", "final_norm", "unembed"}``."""
    return {"embed": embed(key, cfg, dtype),
            "layers": stack(key, cfg, dtype),
            "final_norm": final_norm(cfg, dtype),
            "unembed": unembed(key, cfg, dtype)}
