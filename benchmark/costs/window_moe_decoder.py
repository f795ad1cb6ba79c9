"""Operations and least bytes of a decoder of sliding-window and global
attention layers in a parallel block with routed and shared experts (the
``cohere2_moe`` block), from its shapes alone. Nothing here imports the
program. ``cfg`` is a configuration file's dictionary with the published
key names; ``num_experts`` is the experts this chip holds and the router
scores the published count.
"""

from __future__ import annotations

import functools


@functools.cache
def _weights():
    """``weights/window_moe_decoder.py``: the layer pattern and the expert
    counts as the weights read them."""
    import importlib.util
    import pathlib

    path = pathlib.Path(__file__).resolve().parent.parent \
        / "weights/window_moe_decoder.py"
    spec = importlib.util.spec_from_file_location(
        "bench_costs_window_moe_weights", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def n_layers(cfg: dict, kind: str) -> int:
    """Layers of a kind: "window" (sliding) or "full" (global)."""
    return _weights().layer_kinds(cfg).count(kind)


def attention_params(cfg: dict) -> int:
    """W_q, W_k, W_v, W_o of one layer."""
    d, h, kv, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"])
    return 2 * d * h * hd + 2 * d * kv * hd


def expert_params(cfg: dict) -> int:
    """One expert's gate, up and down (a shared expert's too)."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * _weights().n_experts(cfg)


def experts_held(cfg: dict) -> int:
    return _weights().experts_held(cfg)[1]


def n_params(cfg: dict) -> int:
    """The chip's share: every layer's attention, norm, router, shared
    experts and the experts held; the tied embedding once, the final
    norm."""
    d = cfg["hidden_size"]
    layer = (attention_params(cfg) + d + router_params(cfg)
             + (experts_held(cfg) + cfg["num_shared_experts"])
             * expert_params(cfg))
    return cfg["num_hidden_layers"] * layer + d + d * cfg["vocab_size"]


def _attend_flops(cfg: dict, context: float, layers: dict) -> float:
    """Scores and weighted sum a position, over ``context`` positions of a
    full layer and min(context, window) of a window layer."""
    per_position = 4.0 * cfg["num_attention_heads"] * cfg["head_dim"]
    window = min(context, cfg["sliding_window"])
    return per_position * (layers["full"] * context + layers["window"] * window)


def _token_flops(cfg: dict) -> float:
    """Two per weight a token passes through: the attention's matrices,
    the router, ``num_experts_per_tok`` experts and the shared ones."""
    per_layer = (attention_params(cfg) + router_params(cfg)
                 + (cfg["num_experts_per_tok"] + cfg["num_shared_experts"])
                 * expert_params(cfg))
    return 2.0 * cfg["num_hidden_layers"] * per_layer


def prefill_flops(cfg: dict, n_tokens: int, start: int = 0) -> float:
    """Forward FLOPs of ``n_tokens`` prompt tokens at positions
    start..start+n-1 (a token at position p sees p + 1 positions; the
    unembedding is not applied to prompt tokens)."""
    layers = {k: n_layers(cfg, k) for k in ("full", "window")}
    attend = sum(_attend_flops(cfg, start + i + 1.0, layers)
                 for i in range(n_tokens))
    return n_tokens * _token_flops(cfg) + attend


def decode_flops(cfg: dict, context: float) -> float:
    """Forward FLOPs of one generated token at ``context`` positions: its
    matrices, its attention and the tied unembedding over the slice."""
    layers = {k: n_layers(cfg, k) for k in ("full", "window")}
    return (_token_flops(cfg) + _attend_flops(cfg, context, layers)
            + 2.0 * cfg["hidden_size"] * cfg["vocab_size"])


def expert_bytes(cfg: dict, bytes_per_weight: int = 2) -> float:
    return float(expert_params(cfg) * bytes_per_weight)


def other_weight_bytes_step(cfg: dict, bytes_per_weight: int = 2,
                            bytes_per_router_weight: int = 4) -> float:
    """Bytes of every weight a decode step reads whatever was routed: the
    attention, the norm and the shared experts of every layer, the routers
    (float32), the final norm and the unembedding's slice."""
    d = cfg["hidden_size"]
    n = (cfg["num_hidden_layers"] * (attention_params(cfg) + d
                                     + cfg["num_shared_experts"]
                                     * expert_params(cfg))
         + d + d * cfg["vocab_size"])
    return float(n * bytes_per_weight + cfg["num_hidden_layers"]
                 * router_params(cfg) * bytes_per_router_weight)


def kv_bytes_position(cfg: dict, bytes_per_value: int = 2) -> float:
    """K and V of one position in one layer."""
    return float(2 * cfg["num_key_value_heads"] * cfg["head_dim"]
                 * bytes_per_value)


def decode_least_bytes(cfg: dict, live_positions: float,
                       window_positions: float, rows: float,
                       experts_touched: float, bytes_per_weight: int = 2,
                       bytes_per_value: int = 2) -> float:
    """Least bytes of one decode step over all slots: the weights that do
    not depend on the routing once; the weights of the
    ``experts_touched`` (layer, expert) pairs the step's live rows routed
    to (a count the program reports: the routing decides it); the K/V of
    every live row's positions in each full layer (``live_positions``,
    summed over the rows) and of min(length, window) of them in each window
    layer (``window_positions``); an embedding row for each of the
    ``rows`` live rows."""
    kv = kv_bytes_position(cfg, bytes_per_value)
    return (other_weight_bytes_step(cfg, bytes_per_weight)
            + experts_touched * expert_bytes(cfg, bytes_per_weight)
            + kv * (n_layers(cfg, "full") * live_positions
                    + n_layers(cfg, "window") * window_positions)
            + rows * cfg["hidden_size"] * bytes_per_weight)
