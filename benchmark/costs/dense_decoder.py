"""Operations and least bytes of a dense decoder, from its shapes alone.

The arithmetic of ``bench_transformer.py``'s ``train_flops_per_token``
(a copy; the original is listed in PERF.md for a later PR to delete),
extended to serving and to the least bytes a decode step has to read.
Nothing here imports the program. ``cfg`` is a configuration file's
dictionary with the published key names.
"""

from __future__ import annotations


def layer_matmul_params(cfg: dict) -> int:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    return d * (q + 2 * kv) + q * d + 3 * d * cfg["intermediate_size"]


def n_params(cfg: dict) -> int:
    d = cfg["hidden_size"]
    per_layer = layer_matmul_params(cfg) + 2 * d
    return (cfg["num_hidden_layers"] * per_layer + d
            + 2 * d * cfg["vocab_size"])


def fwd_flops_token(cfg: dict, context: float, unembed: bool = True) -> float:
    """Forward FLOPs of one token that attends to ``context`` positions:
    two per weight of every matrix it passes through, plus scores and
    values (2 * 2 * context * heads * head_dim a layer)."""
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    per_layer = 2 * layer_matmul_params(cfg) + 4 * context * q
    out = cfg["num_hidden_layers"] * per_layer
    if unembed:
        out += 2 * cfg["hidden_size"] * cfg["vocab_size"]
    return float(out)


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward (2x forward) of a causal sequence of ``seq_len``
    tokens, per token: the mean context is seq_len / 2. Recomputation is
    not counted."""
    return 3.0 * fwd_flops_token(cfg, seq_len / 2.0)


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
    n = (cfg["num_hidden_layers"] * layer_matmul_params(cfg)
         + cfg["hidden_size"] * cfg["vocab_size"])
    return float(n * bytes_per_weight)


def kv_bytes_position(cfg: dict, bytes_per_value: int) -> float:
    """Bytes of K and V of one position over all layers."""
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return float(2 * kv * cfg["num_hidden_layers"] * bytes_per_value)


def decode_least_bytes(cfg: dict, live_positions: float,
                       bytes_per_weight: int = 2,
                       bytes_per_value: int = 2) -> float:
    """Least bytes of one decode step over all slots: every weight once,
    K and V of the live positions only."""
    return (weight_bytes_step(cfg, bytes_per_weight)
            + live_positions * kv_bytes_position(cfg, bytes_per_value))


def flash_flops(batch: int, heads: int, seq: int, head_dim: int,
                backward: bool) -> float:
    """Causal attention over one [batch, heads, seq, head_dim] call: half
    of the seq*seq tiles. Forward: scores and values, 4*seq*seq*head_dim/2.
    Backward: five matrix products (scores again, dP, dV, dQ, dK) = 2.5x."""
    fwd = 4.0 * batch * heads * seq * seq * head_dim / 2.0
    return fwd * 2.5 if backward else fwd


def flash_bytes(batch: int, heads: int, seq: int, head_dim: int,
                backward: bool, bytes_per_value: int = 2) -> float:
    """Least bytes: q, k, v read and o written once (forward); q, k, v, o,
    do read and dq, dk, dv written once (backward)."""
    one = batch * heads * seq * head_dim * bytes_per_value
    return float(one * (8 if backward else 4))
