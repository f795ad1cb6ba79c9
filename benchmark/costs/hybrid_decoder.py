"""Operations and least bytes of a hybrid decoder (gated-delta-rule layers
beside full-attention layers), from its shapes alone. Nothing here imports
the program. ``cfg`` is a configuration file's dictionary with the
published key names; ``cfg["layer_types"]`` picks each layer's kind.
"""

from __future__ import annotations


def _linear(cfg: dict) -> tuple:
    return (cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
            cfg["linear_value_head_dim"], cfg["linear_conv_kernel_dim"])


def conv_channels(cfg: dict) -> int:
    h, dk, dv, _ = _linear(cfg)
    return 2 * h * dk + h * dv


def n_layers(cfg: dict, kind: str) -> int:
    return sum(k == kind for k in cfg["layer_types"])


def layer_matmul_params(cfg: dict, kind: str) -> int:
    """Weights of one layer's matrices (norms, gates' vectors and the
    convolution's taps are not matrices: ``layer_other_params``)."""
    d = cfg["hidden_size"]
    mlp = 3 * d * cfg["intermediate_size"]
    if kind == "full_attention":
        q = cfg["num_attention_heads"] * cfg["head_dim"]
        kv = cfg["num_key_value_heads"] * cfg["head_dim"]
        return d * (q + 2 * kv) + q * d + mlp
    h, dk, dv, _ = _linear(cfg)
    # q, k [d, H d_k]; v, g [d, H d_v]; a, b [d, H]; o [H d_v, d]
    return d * (2 * h * dk + 2 * h * dv + 2 * h) + h * dv * d + mlp


def layer_other_params(cfg: dict, kind: str) -> int:
    d = cfg["hidden_size"]
    if kind == "full_attention":        # two block norms, q and k norms
        return 2 * d + (cfg["num_attention_heads"]
                        + cfg["num_key_value_heads"]) * cfg["head_dim"]
    h, _, dv, kernel = _linear(cfg)
    return 2 * d + kernel * conv_channels(cfg) + 2 * h + dv


def n_params(cfg: dict) -> int:
    d = cfg["hidden_size"]
    layers = sum(n_layers(cfg, kind) * (layer_matmul_params(cfg, kind)
                                        + layer_other_params(cfg, kind))
                 for kind in ("full_attention", "linear_attention"))
    return layers + d + 2 * d * cfg["vocab_size"]


def fwd_flops_token(cfg: dict, context: float, unembed: bool = True) -> float:
    """Forward FLOPs of one token: two per weight of every matrix it passes
    through; a full layer adds scores and values over ``context`` positions
    (2 * 2 * context * heads * head_dim), a linear layer the convolution
    (2 * kernel a channel) and the recurrence on its state: the decay, S^T k,
    the rank-one update and S^T q, 2 * d_k * d_v each a head. The same count
    for a prompt token (the chunkwise form's own extra products are how the
    program computes it, not model FLOPs)."""
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    h, dk, dv, kernel = _linear(cfg)
    full = 2 * layer_matmul_params(cfg, "full_attention") + 4 * context * q
    linear = (2 * layer_matmul_params(cfg, "linear_attention")
              + 2 * kernel * conv_channels(cfg) + 8 * h * dk * dv)
    out = (n_layers(cfg, "full_attention") * full
           + n_layers(cfg, "linear_attention") * linear)
    if unembed:
        out += 2 * cfg["hidden_size"] * cfg["vocab_size"]
    return float(out)


def prefill_flops(cfg: dict, n_tokens: int, start: int = 0) -> float:
    """Forward FLOPs of ``n_tokens`` prompt tokens at positions
    start..start+n-1. The unembedding is not applied to prompt tokens."""
    mean_ctx = start + (n_tokens + 1) / 2.0
    return n_tokens * fwd_flops_token(cfg, mean_ctx, unembed=False)


def decode_flops(cfg: dict, context: float) -> float:
    """Forward FLOPs of one generated token at ``context`` live positions."""
    return fwd_flops_token(cfg, context, unembed=True)


def weight_bytes_step(cfg: dict, bytes_per_weight: int) -> float:
    """Bytes of every weight a decode step has to read once: the layers'
    matrices and the unembedding (one embedding row per slot is nothing)."""
    n = sum(n_layers(cfg, kind) * layer_matmul_params(cfg, kind)
            for kind in ("full_attention", "linear_attention"))
    return float((n + cfg["hidden_size"] * cfg["vocab_size"])
                 * bytes_per_weight)


def kv_bytes_position(cfg: dict, bytes_per_value: int) -> float:
    """Bytes of K and V of one position over the full-attention layers."""
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return float(2 * kv * n_layers(cfg, "full_attention") * bytes_per_value)


def state_bytes_slot(cfg: dict, bytes_per_state: int = 4,
                     bytes_per_value: int = 2) -> float:
    """Bytes one slot keeps for its linear layers: the recurrent state and
    the convolution's last kernel - 1 inputs."""
    h, dk, dv, kernel = _linear(cfg)
    one = (h * dk * dv * bytes_per_state
           + (kernel - 1) * conv_channels(cfg) * bytes_per_value)
    return float(n_layers(cfg, "linear_attention") * one)


def decode_least_bytes(cfg: dict, live_positions: float, live_slots: float,
                       bytes_per_weight: int = 2, bytes_per_value: int = 2,
                       bytes_per_state: int = 4) -> float:
    """Least bytes of one decode step over all slots: every weight once, K
    and V of the live positions of the full layers, state and convolution
    tail of the live slots read and written once."""
    return (weight_bytes_step(cfg, bytes_per_weight)
            + live_positions * kv_bytes_position(cfg, bytes_per_value)
            + 2 * live_slots * state_bytes_slot(cfg, bytes_per_state,
                                                bytes_per_value))
