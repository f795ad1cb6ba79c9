"""Weights of a latent-attention decoder with routed experts and
manifold-constrained hyper-connections (``model_type`` ``xing4_0``), made on
the device from ``--seed``.

The benchmark makes the weights and hands them to the program; the plain
reference makes the same ones again, layer by layer, from the same seed.
Nothing here imports the program. The block's own weights are
``weights/mla_moe_decoder.py``'s, drawn by that module's rule from the same
key (its layout, initializer and selection bias; ``qk_head_dim`` is nope +
rope). Beside them, each layer has two hyper-connection sites, 0 before the
attention branch and 1 before the MLP branch, with n = ``hc_mult`` streams
of C = ``hidden_size`` and w = 2n + n^2 outputs a site (the pre-mix's n, the
post-spread's n, the stream mix's n x n, row-major):

    hc_phi [2, n*C, w] float32   normal(0, (n*C) ** -0.5)
    hc_alpha [2, 3] float32      the pre and post gains uniform(
                                 assumed.hc_alpha_min, _max), the mix's
                                 uniform(assumed.hc_res_alpha_min, _max)
    hc_bias [2, w] float32       pre and post normal(0, assumed.hc_bias_std);
                                 the mix normal(0, assumed.hc_res_bias_std)
                                 plus assumed.hc_res_diag on its diagonal

so that a site's stream mix is neither the identity nor uniform: the
diagonal leans each stream to itself (the published initializer's
direction) and the spread mixes them by a seed's own amounts. The mix's
logits spread by several units, as a trained model's may, so that its 20
alternations matter: one alternation leaves its columns about 20% off
(twenty leave a median token's within 1e-5). They are
float32 whatever ``dtype`` is, as the router is. A layer's numbers depend
on (seed, layer index, name) alone.
"""

from __future__ import annotations

import importlib.util
import pathlib

import jax
import jax.numpy as jnp


def _load(rel: str, name: str):
    path = pathlib.Path(__file__).resolve().parent.parent / rel
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_mla = _load("weights/mla_moe_decoder.py", "bench_weights_hyper_mla_base")
_PHI, _ALPHA, _BIAS = 400, 401, 402     # beyond the block's own numbers

KINDS = _mla.KINDS
seed_key = _mla.seed_key
embed = _mla.embed
unembed = _mla.unembed
final_norm = _mla.final_norm


def block_cfg(cfg: dict) -> dict:
    """The configuration as ``weights/mla_moe_decoder.py`` reads it."""
    return {**cfg, "qk_head_dim": cfg["qk_nope_head_dim"]
            + cfg["qk_rope_head_dim"]}


def layer_kinds(cfg: dict) -> list:
    return _mla.layer_kinds(cfg)


def sites_width(cfg: dict) -> int:
    n = cfg["hc_mult"]
    return 2 * n + n * n


def hyper(key: jax.Array, cfg: dict, index) -> dict:
    """One layer's hyper-connection weights (traceable in ``index``)."""
    lkey = jax.random.fold_in(key, index)
    n, c, w = cfg["hc_mult"], cfg["hidden_size"], sites_width(cfg)
    a = cfg["assumed"]
    phi = jax.random.normal(jax.random.fold_in(lkey, _PHI), (2, n * c, w),
                            jnp.float32) * (n * c) ** -0.5
    u = jax.random.uniform(jax.random.fold_in(lkey, _ALPHA), (2, 3),
                           jnp.float32)
    lo = jnp.asarray([a["hc_alpha_min"]] * 2 + [a["hc_res_alpha_min"]])
    hi = jnp.asarray([a["hc_alpha_max"]] * 2 + [a["hc_res_alpha_max"]])
    alpha = lo + (hi - lo) * u
    noise = jax.random.normal(jax.random.fold_in(lkey, _BIAS), (2, w),
                              jnp.float32)
    res = (noise[:, 2 * n:] * a["hc_res_bias_std"]
           + a["hc_res_diag"] * jnp.eye(n, dtype=jnp.float32).reshape(-1))
    bias = jnp.concatenate([noise[:, :2 * n] * a["hc_bias_std"], res], -1)
    return {"hc_phi": phi, "hc_alpha": alpha, "hc_bias": bias}


def layer(key: jax.Array, cfg: dict, index, kind: str, dtype,
          held=None) -> dict:
    """One layer's weights: the block's and its hyper-connections'."""
    out = _mla.layer(key, block_cfg(cfg), index, kind, dtype, held)
    out.update(hyper(key, cfg, index))
    return out
