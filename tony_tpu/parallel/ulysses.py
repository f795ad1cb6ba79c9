"""Ulysses-style sequence parallelism: all-to-all head-sharded attention.

The alternative long-context strategy to ring attention (SURVEY.md §7 item 7;
absent from the reference, which never touches model math — SURVEY.md §2.3).
Activations arrive sequence-sharded over the ``seq`` mesh axis; two
``lax.all_to_all`` reshards bracket the attention op:

    [B, L/n, H, D] --all_to_all--> [B, L, H/n, D]   (gather seq, scatter heads)
        full-sequence attention on H/n local heads
    [B, L, H/n, D] --all_to_all--> [B, L/n, H, D]   (scatter seq, gather heads)

Inside the bracket every device sees the *whole* sequence for its head slice,
so any single-device attention kernel (the Pallas flash kernel included) works
unchanged — no streaming-softmax rewrite as in ring attention. The trade-off
vs the ring: two all-to-alls of the full activation instead of n K/V-block
ppermutes, and head count bounds the parallel degree (H % n == 0). Both
collectives ride ICI when ``seq`` maps to an intra-slice mesh axis.

Shapes follow the JAX attention convention: [batch, seq, heads, head_dim].
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from .ring_attention import reference_attention


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str = "seq",
    causal: bool = True,
    scale: float | None = None,
    attn_fn: Callable | None = None,
) -> jax.Array:
    """Call inside shard_map with q/k/v sequence-sharded over `axis_name`.

    `attn_fn(q, k, v)` runs on full-sequence, head-sliced blocks; the default
    is the Pallas flash kernel on TPU (O(block) memory — the whole point at
    long context) and plain attention elsewhere. Requires heads % axis_size
    == 0 (GQA K/V are repeated to H heads before dispatch —
    models/transformer.py `_layer`).
    """
    n = lax.psum(1, axis_name)
    h = q.shape[2]
    if h % n != 0:
        raise ValueError(f"ulysses needs heads ({h}) divisible by axis size ({n})")
    if attn_fn is None:
        from ..ops.attention import attention_blhd, _on_tpu

        if _on_tpu():
            # flash_attention itself falls back (with a warning) for shapes
            # outside the kernel envelope
            attn_fn = functools.partial(attention_blhd, causal=causal, scale=scale)
        else:
            attn_fn = functools.partial(
                reference_attention, causal=causal, scale=scale
            )

    # gather sequence, scatter heads: chunks concatenate in device order, so
    # axis order (global seq / original head order) is preserved both ways
    qh = lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1, tiled=True)
    kh = lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1, tiled=True)
    vh = lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1, tiled=True)
    out = attn_fn(qh, kh, vh)
    return lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2, tiled=True)


def make_ulysses_attention(
    mesh: Mesh,
    axis_name: str = "seq",
    causal: bool = True,
    attn_fn: Callable | None = None,
) -> Callable:
    """shard_map-wrapped Ulysses attention: takes globally-shaped [B,L,H,D]
    arrays sequence-sharded over `axis_name`, returns same."""
    spec = P(None, axis_name, None, None)

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    def _fn(q, k, v):
        return ulysses_attention(
            q, k, v, axis_name=axis_name, causal=causal, attn_fn=attn_fn
        )

    return _fn
