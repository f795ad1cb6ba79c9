"""Weights of a decoder of sliding-window and global attention layers in a
parallel block with routed experts (the ``cohere2_moe`` block), made on the
device from ``--seed``.

The benchmark makes the weights and hands them to the program; the plain
reference makes the same ones again, layer by layer, from the same seed.
Nothing here imports the program. Matrices are 2-D in the published sense,
input dimension first (H query heads and KV heads of width hd; E experts
of width f, of which this chip holds ``experts_held`` = (first, count); S
shared experts):

    every layer  attn_norm [hidden]                  (the block's one norm)
                 wq [hidden, H*hd]  wk, wv [hidden, KV*hd]  wo [H*hd, hidden]
                 router [hidden, E] float32
                 experts_gate, experts_up [count, hidden, f]
                 experts_down [count, f, hidden]
                 shared_gate, shared_up [hidden, S*f]  shared_down [S*f, hidden]
    the model    embed [vocab, hidden] (tied: the unembedding is its
                 transpose), final_norm [hidden]

The initializer (the configuration's ``assumed``): every matrix is
normal(0, fan_in ** -0.5) but ``wq``, drawn ``assumed.query_init_scale``
times wider (a head's scores then spread as a trained model's do, not
nearly uniformly), norm weights are 1; the router is float32 whatever
``dtype`` is. A layer's numbers depend on (seed, layer index,
name) alone, and an expert's on its index too, so a chip that holds a
range of the experts holds the numbers the whole layer has for them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

KINDS = {"sliding_attention": "window", "full_attention": "full"}
_EMBED = 1000                   # a fold-in id beyond any layer index
_ROUTER, _EXPERTS = 200, 300    # beyond any matrix's number


def seed_key(seed: int) -> jax.Array:
    """A key for any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed % (2 ** 31))
    return jax.random.fold_in(key, seed // (2 ** 31))


def layer_kinds(cfg: dict) -> list:
    """The program's kind of each layer: "window" or "full"."""
    types = cfg["layer_types"]
    if len(types) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types does not name every layer")
    return [KINDS[t] for t in types]


def n_experts(cfg: dict) -> int:
    """The experts the router scores: the published count where the file
    holds a share of them."""
    return cfg.get("published", {}).get("num_experts", cfg["num_experts"])


def experts_held(cfg: dict) -> tuple:
    held = cfg.get("experts_held")
    return (0, cfg["num_experts"]) if held is None else tuple(held)


def matrix_shapes(cfg: dict) -> dict:
    """The layer's plain matrices (the router and the experts apart)."""
    d, h, kv = (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    hd = cfg["head_dim"]
    sf = cfg["num_shared_experts"] * cfg["intermediate_size"]
    return {"wq": (d, h * hd), "wk": (d, kv * hd), "wv": (d, kv * hd),
            "wo": (h * hd, d), "shared_gate": (d, sf), "shared_up": (d, sf),
            "shared_down": (sf, d)}


def _normal(key, shape, dtype, fan_in=None):
    scale = (shape[0] if fan_in is None else fan_in) ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def experts(lkey: jax.Array, cfg: dict, dtype, held=None) -> dict:
    """The experts ``held`` = (first, count) of one layer (None: the file's
    share), one expert at a time under a ``lax.map`` so that the float32
    draw of a whole stack is never live."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    first, count = held or experts_held(cfg)
    ekey = jax.random.fold_in(lkey, _EXPERTS)

    def one(e):
        k = jax.random.fold_in(ekey, e)
        return {"experts_gate": _normal(jax.random.fold_in(k, 0), (d, f), dtype),
                "experts_up": _normal(jax.random.fold_in(k, 1), (d, f), dtype),
                "experts_down": _normal(jax.random.fold_in(k, 2), (f, d), dtype)}

    return jax.lax.map(one, first + jnp.arange(count))


def layer(key: jax.Array, cfg: dict, index, dtype, held=None) -> dict:
    """One layer's weights (traceable in ``index``): a window and a full
    layer hold the same matrices."""
    lkey = jax.random.fold_in(key, index)
    d = cfg["hidden_size"]
    # wq drawn as if its fan-in were scale^2 times smaller: scale times wider
    scale = cfg.get("assumed", {}).get("query_init_scale", 1)
    fan_in = {"wq": d / scale ** 2}
    out = {name: _normal(jax.random.fold_in(lkey, i), shape, dtype,
                         fan_in.get(name))
           for i, (name, shape) in enumerate(matrix_shapes(cfg).items())}
    out["attn_norm"] = jnp.ones((d,), dtype)
    out["router"] = _normal(jax.random.fold_in(lkey, _ROUTER),
                            (d, n_experts(cfg)), jnp.float32)
    out.update(experts(lkey, cfg, dtype, held))
    return out


def embed(key: jax.Array, cfg: dict, dtype) -> jax.Array:
    shape = (cfg["vocab_size"], cfg["hidden_size"])
    return _normal(jax.random.fold_in(key, _EMBED), shape, dtype,
                   fan_in=cfg["hidden_size"])


def final_norm(cfg: dict, dtype) -> jax.Array:
    return jnp.ones((cfg["hidden_size"],), dtype)
