"""Operations and least bytes of a latent-attention decoder with routed
experts and manifold-constrained hyper-connections (``model_type``
``xing4_0``), from its shapes alone. Nothing here imports the program.

The block is ``costs/mla_moe_decoder.py``'s, whose arithmetic this module
adds to; the hyper-connections add, at each of a layer's two sites, the
projection phi [n C, 2n + n^2] (float32, read once a step whatever was
routed), its gains and biases, and the work on the n streams of C: the
norm of the n C values, the pre-mix (n C multiply-adds), the stream mix
(n^2 C) and the post-spread (n C). The 20 alternations of the Sinkhorn
projection act on n x n values a token and are left out of the FLOPs.
"""

from __future__ import annotations

import importlib.util
import pathlib


def _load(rel: str, name: str):
    path = pathlib.Path(__file__).resolve().parent.parent / rel
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_mla = _load("costs/mla_moe_decoder.py", "bench_costs_hyper_mla_base")

n_layers = _mla.n_layers
attention_params = _mla.attention_params
expert_params = _mla.expert_params
experts_held = _mla.experts_held


def site_width(cfg: dict) -> int:
    n = cfg["hc_mult"]
    return 2 * n + n * n


def hyper_params(cfg: dict) -> int:
    """One layer's hyper-connections: two sites of phi, bias and three
    gains."""
    w = site_width(cfg)
    return 2 * (cfg["hc_mult"] * cfg["hidden_size"] * w + w + 3)


def hyper_bytes_step(cfg: dict, bytes_per_value: int = 4) -> float:
    """Bytes of every layer's hyper-connection weights (float32)."""
    return float(cfg["num_hidden_layers"] * hyper_params(cfg)
                 * bytes_per_value)


def n_params(cfg: dict) -> int:
    return _mla.n_params(cfg) + cfg["num_hidden_layers"] * hyper_params(cfg)


def _hyper_flops_token(cfg: dict) -> float:
    """Two per multiply-add a token at every site: the projection, the
    pre-mix, the stream mix and the post-spread."""
    n, c = cfg["hc_mult"], cfg["hidden_size"]
    site = 2.0 * (n * c * site_width(cfg) + n * c + n * n * c + n * c)
    return 2 * cfg["num_hidden_layers"] * site


def prefill_flops(cfg: dict, n_tokens: int, start: int = 0) -> float:
    return (_mla.prefill_flops(cfg, n_tokens, start)
            + n_tokens * _hyper_flops_token(cfg))


def decode_flops(cfg: dict, context: float) -> float:
    return _mla.decode_flops(cfg, context) + _hyper_flops_token(cfg)


def decode_least_bytes(cfg: dict, context: float, rows: float,
                       experts_touched: float, bytes_per_weight: int = 2,
                       bytes_per_value: int = 2) -> float:
    """``costs/mla_moe_decoder.py``'s least bytes of one decode step (the
    weights no routing spares once, the ``experts_touched`` (layer,
    expert) pairs' weights, the latent rows of the ``context`` live
    positions, an embedding row a live row) and the hyper-connections'
    weights once."""
    return (_mla.decode_least_bytes(cfg, context, rows, experts_touched,
                                    bytes_per_weight, bytes_per_value)
            + hyper_bytes_step(cfg))
