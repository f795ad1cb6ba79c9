"""Operations and least bytes of a latent-attention decoder with routed
experts (the DeepSeek-V3-shaped block), from its shapes alone. Nothing here
imports the program. ``cfg`` is a configuration file's dictionary with the
published key names; a layer is dense while its index is below
``first_k_dense_replace`` and routed after.
"""

from __future__ import annotations


def n_layers(cfg: dict, kind: str) -> int:
    dense = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    return dense if kind == "dense" else cfg["num_hidden_layers"] - dense


def row_width(cfg: dict) -> int:
    """Values a position keeps for one layer: [c_kv | k_r]."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def attention_params(cfg: dict) -> int:
    """W_qa, W_qb, W_kva, W_kvb, W_o of one layer."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    qr, kr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return (d * qr + qr * h * (nope + rope) + d * (kr + rope)
            + kr * h * (nope + v) + h * v * d)


def expert_params(cfg: dict) -> int:
    """One expert's gate, up and down (a shared expert's too)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_mlp_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["n_routed_experts"]


def experts_held(cfg: dict) -> int:
    held = cfg.get("experts_held")
    return cfg["n_routed_experts"] if held is None else held[1]


def n_params(cfg: dict) -> int:
    d = cfg["hidden_size"]
    norms = 2 * d + cfg["q_lora_rank"] + cfg["kv_lora_rank"]
    routed = (router_params(cfg) + cfg["n_routed_experts"]
              + (experts_held(cfg) + cfg["n_shared_experts"])
              * expert_params(cfg))
    return (cfg["num_hidden_layers"] * (attention_params(cfg) + norms)
            + n_layers(cfg, "dense") * dense_mlp_params(cfg)
            + n_layers(cfg, "routed") * routed
            + d + 2 * d * cfg["vocab_size"])


def _mlp_flops_token(cfg: dict) -> float:
    """Two per weight a token passes through: the dense layers' MLP; a
    routed layer's router, its ``num_experts_per_tok`` experts and its
    shared ones."""
    routed = router_params(cfg) + (
        cfg["num_experts_per_tok"] + cfg["n_shared_experts"]
    ) * expert_params(cfg)
    return 2.0 * (n_layers(cfg, "dense") * dense_mlp_params(cfg)
                  + n_layers(cfg, "routed") * routed)


def prefill_flops(cfg: dict, n_tokens: int, start: int = 0) -> float:
    """Forward FLOPs of ``n_tokens`` prompt tokens at positions
    start..start+n-1, by the expanded form: two per weight of the
    attention's matrices (W_kvb once a token), scores over nope + rope and
    values over v a head and a position of context. The unembedding is not
    applied to prompt tokens."""
    h = cfg["num_attention_heads"]
    ctx = start + (n_tokens + 1) / 2.0
    attend = 2.0 * ctx * h * (cfg["qk_nope_head_dim"]
                              + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])
    layer = 2.0 * attention_params(cfg) + attend
    return n_tokens * (cfg["num_hidden_layers"] * layer
                       + _mlp_flops_token(cfg))


def decode_flops(cfg: dict, context: float) -> float:
    """Forward FLOPs of one generated token at ``context`` live positions,
    by the absorbed form (what a cached position keeps is the latent, so
    W_kvb meets the query and the output, not the positions): the down and
    up projections of q, the kv down projection and W_o at two per weight,
    q_nope into the latent's width and the weighted latent out of it,
    scores over kv_rank + rope and values over kv_rank a head and a
    position; ``num_experts_per_tok`` + shared experts; the unembedding."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    qr, kr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    project = d * qr + qr * h * (nope + rope) + d * (kr + rope) + h * v * d
    absorb = h * nope * kr + h * kr * v
    attend = context * h * ((kr + rope) + kr)
    layer = 2.0 * (project + absorb + attend)
    return (cfg["num_hidden_layers"] * layer + _mlp_flops_token(cfg)
            + 2.0 * d * cfg["vocab_size"])


def expert_bytes(cfg: dict, bytes_per_weight: int = 2) -> float:
    return float(expert_params(cfg) * bytes_per_weight)


def other_weight_bytes_step(cfg: dict, bytes_per_weight: int = 2,
                            bytes_per_router_weight: int = 4) -> float:
    """Bytes of every weight a decode step reads whatever was routed: the
    attention of every layer, the dense layers' MLP, each routed layer's
    router and shared experts, the unembedding."""
    n = (cfg["num_hidden_layers"] * attention_params(cfg)
         + n_layers(cfg, "dense") * dense_mlp_params(cfg)
         + n_layers(cfg, "routed") * cfg["n_shared_experts"]
         * expert_params(cfg)
         + cfg["hidden_size"] * cfg["vocab_size"])
    return float(n * bytes_per_weight + n_layers(cfg, "routed")
                 * router_params(cfg) * bytes_per_router_weight)


def latent_bytes_position(cfg: dict, bytes_per_value: int = 2) -> float:
    """Bytes of one position's rows over all layers."""
    return float(row_width(cfg) * cfg["num_hidden_layers"] * bytes_per_value)


def decode_least_bytes(cfg: dict, context: float, rows: float,
                       experts_touched: float, bytes_per_weight: int = 2,
                       bytes_per_value: int = 2) -> float:
    """Least bytes of one decode step over all slots: the weights that do
    not depend on the routing once, the weights of the
    ``experts_touched`` (layer, expert) pairs the step's live rows routed
    to (a count the program reports, not a formula: the routing decides
    it), the latent rows of the ``context`` live positions (summed over
    the rows), and an embedding row for each of the ``rows`` live rows."""
    return (other_weight_bytes_step(cfg, bytes_per_weight)
            + experts_touched * expert_bytes(cfg, bytes_per_weight)
            + context * latent_bytes_position(cfg, bytes_per_value)
            + rows * cfg["hidden_size"] * bytes_per_weight)
