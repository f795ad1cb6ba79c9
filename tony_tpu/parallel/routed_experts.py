"""A routed expert layer as deployed: sigmoid scores, a selection bias that
does not weigh, gated (SwiGLU) experts, no capacity and no dropped token.

Beside ``parallel/expert.py`` (the einsum-dispatch toy: softmax top-k,
capacity slots, non-gated experts), which stays as it is. This one is what
the serving programs run for a config whose ``mlp_kinds`` names a "routed"
layer (models/transformer.py):

- **Routing** (`route`) is float32 whatever the activations are: ``s =
  sigmoid(x W_r)`` over all ``E`` experts, the ``k`` experts with the
  largest ``s + b`` chosen (``b`` the selection bias, ``router_bias``:
  DeepSeek-V3's ``e_score_correction_bias``), their weights ``s_e /
  (sum_sel s + 1e-20) * scale`` taken from the scores WITHOUT the bias.
- **Dispatch** is a sort, not capacity slots: the ``T k`` (token, expert)
  assignments are ordered by expert (a stable argsort), the tokens
  gathered in that order, and one grouped matmul (``jax.lax.ragged_dot``)
  runs each expert over its own contiguous rows. Every assignment has a
  row, whatever the load: if all ``T`` tokens pick one expert its group is
  ``T`` rows and the others' are empty. Nothing is dropped, and no
  ``[E, capacity, d]`` buffer exists. A prefill of 4096 tokens (about 128
  an expert at E = 256, k = 8) and a decode step of 32 (0-3 an expert) go
  the same way.
- **The experts held** are an argument: ``held = (first, count)`` says
  which contiguous range of the ``E`` experts ``w_gu`` / ``w_down`` carry
  (a chip's share under expert parallelism). Routing is over all ``E``
  whatever is held; assignments to an expert held elsewhere sort past the
  last group, get no weight, and add nothing: the layer returns the part
  of the result that its own experts give, and the parts of all the shares
  add up to the whole layer's (tests/test_mla_moe_serving.py). The
  exchange that would bring those tokens to their chip is not here: one
  chip runs without it.

Weights: ``w_gu`` [count, d, 2 f] (gate then up, one grouped matmul for
the two) and ``w_down`` [count, f, d], in the activation dtype; ``router``
[d, E] and ``router_bias`` [E] float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def route(x, router, router_bias, *, top_k: int, scale: float):
    """Tokens x [T, d] -> (chosen [T, k] int32, weights [T, k] float32).
    The scores, the selection and the weights are float32."""
    logits = jnp.matmul(x.astype(jnp.float32), router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(s + router_bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * scale
    return chosen.astype(jnp.int32), w


def expert_load(chosen, live=None, *, n_experts: int):
    """Assignments an expert [E] int32 of ``chosen`` [T, k]; ``live`` [T]
    bool leaves the other tokens' out."""
    ones = jnp.ones(chosen.shape, jnp.int32)
    if live is not None:
        ones = ones * live.astype(jnp.int32)[:, None]
    return jnp.zeros((n_experts,), jnp.int32).at[chosen.reshape(-1)].add(
        ones.reshape(-1))


def routed_ffn(x, chosen, weights, w_gu, w_down, *, held: tuple):
    """sum over a token's chosen experts of w_e SwiGLU_e(x), for the
    experts ``held`` = (first, count) -> [T, d] in x's dtype. x [T, d],
    chosen / weights [T, k] from `route`."""
    t, d = x.shape
    k = chosen.shape[1]
    first, count = held
    f = w_down.shape[1]
    local = chosen.reshape(-1) - first                      # [T k]
    mine = (local >= 0) & (local < count)
    # an expert held elsewhere sorts past the last group
    group = jnp.where(mine, local, count)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.zeros((count + 1,), jnp.int32).at[group].add(1)[:count]
    xs = x[order // k]                                      # [T k, d]
    gu = jax.lax.ragged_dot(xs, w_gu.astype(x.dtype), sizes)
    act = jax.nn.silu(gu[:, :f]) * gu[:, f:]
    ys = jax.lax.ragged_dot(act, w_down.astype(x.dtype), sizes)
    # back to token order; rows past the last group hold nothing defined
    w = jnp.where(mine, weights.reshape(-1), 0.0)
    inverse = jnp.zeros_like(order).at[order].set(jnp.arange(t * k))
    ys = jnp.where(mine[:, None], ys[inverse].astype(jnp.float32), 0.0)
    return jnp.sum((ys * w[:, None]).reshape(t, k, d), axis=1).astype(x.dtype)
