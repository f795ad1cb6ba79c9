"""Weights of a latent-attention decoder with routed experts (the
DeepSeek-V3-shaped block), made on the device from ``--seed``.

The benchmark makes the weights and hands them to the program; the plain
reference makes the same ones again, layer by layer, from the same seed.
Nothing here imports the program. Matrices are 2-D in the published sense,
input dimension first (H heads; nope, rope, v the head widths; E experts of
width f; a layer is dense while its index is below
``first_k_dense_replace`` and routed after):

    every layer  attn_norm, mlp_norm [hidden]
                 wq_a [hidden, q_lora_rank]        q_a_norm [q_lora_rank]
                 wq_b [q_lora_rank, H*(nope+rope)] (a head: nope then rope)
                 wkv_a [hidden, kv_lora_rank+rope] kv_a_norm [kv_lora_rank]
                 wkv_b [kv_lora_rank, H*(nope+v)]  (a head: k_nope then v)
                 wo [H*v, hidden]
    dense        w_gate, w_up [hidden, intermediate]  w_down [intermediate, hidden]
    routed       router [hidden, E] float32   router_bias [E] float32
                 experts_gate, experts_up [E, hidden, f]  experts_down [E, f, hidden]
                 shared_gate, shared_up [hidden, S*f]     shared_down [S*f, hidden]

The initializer (the configuration's ``assumed``): every matrix is
normal(0, fan_in ** -0.5), norm weights are 1, ``router_bias`` (the
published ``e_score_correction_bias``, a zero buffer that training moves)
is normal(``assumed.router_bias_mean``, ``assumed.router_bias_std``): a
common offset, which the selection ignores and a weight must not see, and
a spread, which the selection follows and a weight must not see either. The router and its bias are float32
whatever ``dtype`` is. A layer's numbers depend on (seed, layer index,
name) alone, so a layer made on its own and one made beside the others
hold the same numbers; an expert's depend on its index too, so a chip
that holds a range of the experts (``experts=(first, count)``) holds the
numbers the whole layer has for them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

KINDS = ("dense", "routed")
_EMBED, _UNEMBED = 1000, 1001   # fold-in ids beyond any layer index
_ROUTER, _BIAS, _EXPERTS = 200, 201, 300   # beyond any matrix's number


def seed_key(seed: int) -> jax.Array:
    """A key for any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed % (2 ** 31))
    return jax.random.fold_in(key, seed // (2 ** 31))


def kind_of(cfg: dict, index: int) -> str:
    if cfg["moe_layer_freq"] != 1:
        raise ValueError("moe_layer_freq other than 1 is not written down")
    return "dense" if index < cfg["first_k_dense_replace"] else "routed"


def layer_kinds(cfg: dict) -> list:
    return [kind_of(cfg, i) for i in range(cfg["num_hidden_layers"])]


_ATTN = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo")
_MLP = {"dense": ("w_gate", "w_up", "w_down"),
        "routed": ("shared_gate", "shared_up", "shared_down")}


def matrix_shapes(cfg: dict, kind: str) -> dict:
    """The layer's plain matrices (the router and the experts apart)."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    if cfg["qk_head_dim"] != nope + rope:
        raise ValueError("qk_head_dim is not qk_nope_head_dim + qk_rope_head_dim")
    qr, kr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    f = (cfg["intermediate_size"] if kind == "dense"
         else cfg["n_shared_experts"] * cfg["moe_intermediate_size"])
    gate, up, down = _MLP[kind]
    return {"wq_a": (d, qr), "wq_b": (qr, h * (nope + rope)),
            "wkv_a": (d, kr + rope), "wkv_b": (kr, h * (nope + v)),
            "wo": (h * v, d), gate: (d, f), up: (d, f), down: (f, d)}


def _normal(key, shape, dtype, fan_in=None):
    scale = (shape[0] if fan_in is None else fan_in) ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def experts(lkey: jax.Array, cfg: dict, dtype, held=None) -> dict:
    """The experts ``held`` = (first, count) of one routed layer (None: all
    of them), one expert at a time under a ``lax.map`` so that the float32
    draw of a whole stack is never live."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    first, count = held or (0, cfg["n_routed_experts"])
    ekey = jax.random.fold_in(lkey, _EXPERTS)

    def one(e):
        k = jax.random.fold_in(ekey, e)
        return {"experts_gate": _normal(jax.random.fold_in(k, 0), (d, f), dtype),
                "experts_up": _normal(jax.random.fold_in(k, 1), (d, f), dtype),
                "experts_down": _normal(jax.random.fold_in(k, 2), (f, d), dtype)}

    return jax.lax.map(one, first + jnp.arange(count))


def layer(key: jax.Array, cfg: dict, index, kind: str, dtype,
          held=None) -> dict:
    """One layer's weights (traceable in ``index``; ``kind`` is static)."""
    lkey = jax.random.fold_in(key, index)
    shapes = matrix_shapes(cfg, kind)
    # a matrix's number is its place among the attention's, or 10 + its
    # place in the MLP: the attention of a dense and of a routed layer
    # draw alike
    numbers = {**{n: i for i, n in enumerate(_ATTN)},
               **{n: 10 + i for i, n in enumerate(_MLP[kind])}}
    out = {name: _normal(jax.random.fold_in(lkey, numbers[name]), shape, dtype)
           for name, shape in shapes.items()}
    d = cfg["hidden_size"]
    out["attn_norm"] = jnp.ones((d,), dtype)
    out["mlp_norm"] = jnp.ones((d,), dtype)
    out["q_a_norm"] = jnp.ones((cfg["q_lora_rank"],), dtype)
    out["kv_a_norm"] = jnp.ones((cfg["kv_lora_rank"],), dtype)
    if kind == "dense":
        return out
    e = cfg["n_routed_experts"]
    out["router"] = _normal(jax.random.fold_in(lkey, _ROUTER), (d, e),
                            jnp.float32)
    out["router_bias"] = cfg["assumed"]["router_bias_mean"] \
        + cfg["assumed"]["router_bias_std"] * jax.random.normal(
            jax.random.fold_in(lkey, _BIAS), (e,), jnp.float32)
    out.update(experts(lkey, cfg, dtype, held))
    return out


def embed(key: jax.Array, cfg: dict, dtype) -> jax.Array:
    shape = (cfg["vocab_size"], cfg["hidden_size"])
    return _normal(jax.random.fold_in(key, _EMBED), shape, dtype,
                   fan_in=cfg["hidden_size"])


def unembed(key: jax.Array, cfg: dict, dtype) -> jax.Array:
    shape = (cfg["hidden_size"], cfg["vocab_size"])
    return _normal(jax.random.fold_in(key, _UNEMBED), shape, dtype)


def final_norm(cfg: dict, dtype) -> jax.Array:
    return jnp.ones((cfg["hidden_size"],), dtype)
