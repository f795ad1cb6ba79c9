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
  gathered in that order, and one grouped matmul runs each expert over its
  own contiguous rows. Every assignment has a row, whatever the load: if
  all ``T`` tokens pick one expert its group is ``T`` rows and the others'
  are empty. Nothing is dropped, and no ``[E, capacity, d]`` buffer
  exists. The grouped matmul takes one of two paths, by what the caller
  observes (``generate.expert_kernel_engages``): a decode step (one new
  token a row, 0-3 assignments an expert at 32 rows) on the TPU outside a
  mesh runs `grouped_matmul`, the Pallas grouped matmul of
  ``jax.experimental.pallas.ops.tpu.megablox`` at a row tile of
  ``TILE_ROWS`` and an expert's whole matrix as its weight block, so a
  touched expert's weights cross HBM once and a visit multiplies 16 rows,
  not 256; a prefill
  (4-32 an expert), the CPU and a mesh keep ``jax.lax.ragged_dot``,
  which XLA lowers to its own grouped matmul at a 256-row tile. Both
  visit only the (row tile, expert) pairs that hold rows.
- **Parts of a step** (``observability.STEP_PARTS``): the scoring, the
  selection, the sort, the gathers and the combine back into token order
  are "router"; the two grouped matmuls and the SwiGLU between them are
  "experts".
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
from jax.experimental.pallas.ops.tpu.megablox import gmm

# the decode kernel's row tile: a decode step gives an expert 0-3 rows,
# and bfloat16's native tile is 16 sublanes
TILE_ROWS = 16
# the most bytes of one expert's weights a grid step of the decode kernel
# holds: twice, double-buffered, beside its rows, inside a Mosaic kernel's
# 16 MiB of scoped VMEM (whole matrices of 2048 x 1536, an N tile of 512
# of 4096 x 8192)
WEIGHT_BLOCK_BYTES = 6 << 20


def weight_tile(k: int, n: int, itemsize: int) -> int:
    """The decode kernel's N tile for an expert's [k, n] matrix: the whole
    of n where it fits ``WEIGHT_BLOCK_BYTES``, else n halved until it does
    (while the half stays a multiple of 128 lanes)."""
    tile = n
    while k * tile * itemsize > WEIGHT_BLOCK_BYTES and tile % 256 == 0:
        tile //= 2
    return tile


def route(x, router, router_bias, *, top_k: int, scale: float):
    """Tokens x [T, d] -> (chosen [T, k] int32, weights [T, k] float32).
    The scores, the selection and the weights are float32."""
    with jax.named_scope("router"):
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


def grouped_matmul(lhs, rhs, sizes):
    """``jax.lax.ragged_dot``'s statement for rows sorted by group: lhs
    [m, K], rhs [G, K, N], sizes [G] -> [m, N] in lhs's dtype, float32
    accumulation; rows past ``sum(sizes)`` are left undefined (the
    caller masks them). The megablox kernel at a ``TILE_ROWS`` row tile,
    its K tile the whole of K and its N tile `weight_tile`'s (the whole of
    N where an expert's matrix fits VMEM twice): a weight block's index is
    then the group's and the N tile's, so a group that straddles two row
    tiles keeps its resident block and each touched group's weights are
    read once (an N tile of 256-768 ran as fast on a v5e and only adds
    grid steps). Interpreted off the TPU (the tests)."""
    m, k = lhs.shape
    n = rhs.shape[2]
    pad = -m % TILE_ROWS
    out = gmm(jnp.pad(lhs, ((0, pad), (0, 0))), rhs, sizes,
              preferred_element_type=lhs.dtype,
              tiling=(TILE_ROWS, k, weight_tile(k, n, rhs.dtype.itemsize)),
              interpret=jax.default_backend() != "tpu")
    return out[:m]


def routed_ffn(x, chosen, weights, w_gu, w_down, *, held: tuple,
               kernel: bool = False):
    """sum over a token's chosen experts of w_e SwiGLU_e(x), for the
    experts ``held`` = (first, count) -> [T, d] in x's dtype. x [T, d],
    chosen / weights [T, k] from `route`. ``kernel`` runs the grouped
    matmuls through `grouped_matmul` (a decode step's few rows an expert:
    ``generate.expert_kernel_engages``), else ``jax.lax.ragged_dot``."""
    t, d = x.shape
    k = chosen.shape[1]
    first, count = held
    f = w_down.shape[1]
    with jax.named_scope("router"):
        local = chosen.reshape(-1) - first                  # [T k]
        mine = (local >= 0) & (local < count)
        # an expert held elsewhere sorts past the last group
        group = jnp.where(mine, local, count)
        order = jnp.argsort(group, stable=True)
        sizes = jnp.zeros((count + 1,), jnp.int32).at[group].add(1)[:count]
        xs = x[order // k]                                  # [T k, d]
    matmul = grouped_matmul if kernel else jax.lax.ragged_dot
    with jax.named_scope("experts"):
        gu = matmul(xs, w_gu.astype(x.dtype), sizes)
        act = jax.nn.silu(gu[:, :f]) * gu[:, f:]
        ys = matmul(act, w_down.astype(x.dtype), sizes)
    with jax.named_scope("router"):
        # back to token order; rows past the last group hold nothing defined
        w = jnp.where(mine, weights.reshape(-1), 0.0)
        inverse = jnp.zeros_like(order).at[order].set(jnp.arange(t * k))
        ys = jnp.where(mine[:, None], ys[inverse].astype(jnp.float32), 0.0)
        return jnp.sum((ys * w[:, None]).reshape(t, k, d),
                       axis=1).astype(x.dtype)
