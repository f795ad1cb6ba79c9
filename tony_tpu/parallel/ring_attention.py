"""Ring attention: exact attention over sequence-sharded activations.

Long-context capability the reference cannot express (SURVEY.md §5
"long-context/sequence parallelism: absent"). Q/K/V are sharded along the
sequence over the ``seq`` mesh axis; K/V blocks circulate the ring via
``lax.ppermute`` (neighbor exchange -> rides ICI) while each device folds
every block into its local queries with streaming flash-style softmax
accumulation, so the full L x L score matrix never materializes and per-device
memory stays O(L/n). Compute for step t overlaps with the ppermute of step
t+1 under XLA's async collectives.

Shapes follow the JAX attention convention: [batch, seq, heads, head_dim].
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

NEG_INF = -1e30


def _block_attn(q, k, v, scale, mask):
    """Scores + masked stable partial softmax for one (q-block, kv-block)
    pair; returns (m, l, o) partials in f32."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    s = s * scale
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1)                      # [b,h,q]
    p = jnp.exp(s - m[..., None])
    if mask is not None:
        # fully-masked rows: keep exp at 0, m at NEG_INF handled by caller
        p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1)                      # [b,h,q]
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return m, l, o


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str = "seq",
    causal: bool = True,
    scale: float | None = None,
) -> jax.Array:
    """Call inside shard_map with q/k/v sequence-sharded over `axis_name`.

    Every device runs `n` steps; at step t it holds the K/V block that
    started on device (me - t) mod n, so global causal masking reduces to a
    comparison of block indices plus an intra-block triangular mask when the
    block is its own.
    """
    n = lax.psum(1, axis_name)
    me = lax.axis_index(axis_name)
    b, lq, h, d = q.shape
    scale = (d ** -0.5) if scale is None else scale
    q32 = q.astype(jnp.float32)

    # intra-block causal mask (positions are block-local; global offsets equal
    # for q and kv when the block is the device's own)
    tri = jnp.tril(jnp.ones((lq, k.shape[1]), dtype=bool))[None, None]

    def step(carry, t):
        k_blk, v_blk, m, l, o = carry
        src = (me - t) % n  # original owner of the circulating block

        if causal:
            # src <  me: fully visible;  src == me: triangular;  src > me: hidden
            full = jnp.broadcast_to(src < me, tri.shape)
            diag = jnp.broadcast_to(src == me, tri.shape) & tri
            mask = full | diag
        else:
            mask = None

        bm, bl, bo = _block_attn(q32, k_blk, v_blk, scale, mask)
        m_new = jnp.maximum(m, bm)
        corr = jnp.exp(m - m_new)
        bcorr = jnp.exp(bm - m_new)
        l_new = l * corr + bl * bcorr
        o_new = o * corr[..., None].transpose(0, 2, 1, 3) \
            + bo * bcorr[..., None].transpose(0, 2, 1, 3)
        # rotate K/V to the next neighbor (ring over ICI)
        k_nxt = lax.ppermute(k_blk, axis_name, [(i, (i + 1) % n) for i in range(n)])
        v_nxt = lax.ppermute(v_blk, axis_name, [(i, (i + 1) % n) for i in range(n)])
        return (k_nxt, v_nxt, m_new, l_new, o_new), None

    m0 = jnp.full((b, h, lq), NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((b, h, lq), dtype=jnp.float32)
    o0 = jnp.zeros((b, lq, h, d), dtype=jnp.float32)
    (_, _, m, l, o), _ = lax.scan(
        step, (k, v, m0, l0, o0), jnp.arange(n)
    )
    # normalize; fully-masked rows (l==0) return 0
    l_safe = jnp.where(l > 0, l, 1.0)
    out = o / l_safe[..., None].transpose(0, 2, 1, 3)
    return out.astype(q.dtype)


def ring_flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str = "seq",
    causal: bool = True,
    scale: float | None = None,
) -> jax.Array:
    """Ring attention with the fused Pallas flash kernel as the per-step
    block computation (ops/attention.py). Same ring as :func:`ring_attention`
    — K/V circulate via ``lax.ppermute`` — but each step runs the flash
    kernel on (local Q, circulating KV block) and returns ``(out_t, lse_t)``;
    partials merge as a streaming logaddexp-weighted sum. Per-device memory
    is O(kernel block), not O(L_local x L_block) — the XLA path materializes
    the per-pair score matrix, which at L=128k/8 devices is a 1GB+ f32
    tensor per head; this path never does.

    The kernel's lse output is differentiable (its cotangent folds into the
    flash backward's delta residual), so ``jax.grad`` through scan + ppermute
    + merge is exact. Visibility per step is a 3-way ``lax.switch``: blocks
    from earlier devices run the kernel non-causally, the device's own block
    runs it causally, later blocks skip compute entirely (out=0, lse=-inf).
    """
    from ..ops.attention import flash_attention_with_lse

    n = lax.psum(1, axis_name)
    me = lax.axis_index(axis_name)
    b, lq, h, d = q.shape
    # kernel layout [b, h, l, d]; K/V carried (and ppermuted) in this layout
    # so the transpose happens once, not per ring step
    qt = q.transpose(0, 2, 1, 3)
    kt0 = k.transpose(0, 2, 1, 3)
    vt0 = v.transpose(0, 2, 1, 3)

    def step(carry, t):
        kt, vt, out, lse = carry
        src = (me - t) % n

        def full(_):
            o, s = flash_attention_with_lse(qt, kt, vt, False, scale)
            return o.astype(jnp.float32), s

        def diag(_):
            o, s = flash_attention_with_lse(qt, kt, vt, True, scale)
            return o.astype(jnp.float32), s

        def skip(_):
            return jnp.zeros_like(out), jnp.full_like(lse, NEG_INF)

        if causal:
            case = jnp.where(src < me, 0, jnp.where(src == me, 1, 2))
            o_t, lse_t = lax.switch(case, [full, diag, skip], None)
        else:
            o_t, lse_t = full(None)

        lse_new = jnp.logaddexp(lse, lse_t)
        w_old = jnp.exp(lse - lse_new)[..., None]           # [b,h,lq,1]
        w_t = jnp.exp(lse_t - lse_new)[..., None]
        out_new = out * w_old + o_t * w_t
        k_nxt = lax.ppermute(kt, axis_name, [(i, (i + 1) % n) for i in range(n)])
        v_nxt = lax.ppermute(vt, axis_name, [(i, (i + 1) % n) for i in range(n)])
        return (k_nxt, v_nxt, out_new, lse_new), None

    out0 = jnp.zeros((b, h, lq, d), dtype=jnp.float32)
    lse0 = jnp.full((b, h, lq), NEG_INF, dtype=jnp.float32)
    (_, _, out, _), _ = lax.scan(step, (kt0, vt0, out0, lse0), jnp.arange(n))
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def make_ring_attention(
    mesh: Mesh,
    axis_name: str = "seq",
    causal: bool = True,
    impl: str | None = None,
) -> Callable:
    """shard_map-wrapped ring attention: takes globally-shaped [B,L,H,D]
    arrays sequence-sharded over `axis_name`, returns same.

    ``impl``: "flash" (Pallas kernel per ring step), "xla" (einsum blocks),
    or None to auto-select flash when on TPU and the head dim is inside the
    kernel envelope (multiple of 128 — see ops.attention.flash_supported);
    off-TPU the kernel would run in the Pallas interpreter, so auto keeps
    the XLA path (tests opt into interpret coverage with impl="flash")."""
    if impl not in (None, "flash", "xla"):
        raise ValueError(f"impl must be None, 'flash', or 'xla', got {impl!r}")
    spec = P(None, axis_name, None, None)

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    def _fn(q, k, v):
        from ..ops.attention import _on_tpu, flash_supported
        chosen = impl
        if chosen is None:
            chosen = "flash" if (_on_tpu() and flash_supported(q)) else "xla"
        elif chosen == "flash" and _on_tpu() and not flash_supported(q):
            # surface the envelope constraint instead of an opaque Mosaic
            # tiling failure deep inside Pallas
            raise ValueError(
                f"impl='flash' requires head_dim % 128 == 0 on TPU, got "
                f"head_dim={q.shape[-1]}; use impl=None or 'xla'"
            )
        if chosen == "flash":
            return ring_flash_attention(q, k, v, axis_name=axis_name, causal=causal)
        return ring_attention(q, k, v, axis_name=axis_name, causal=causal)

    return _fn


def reference_attention(q, k, v, causal: bool = True, scale: float | None = None,
                        window: int | None = None):
    """Plain full attention (for tests and the no-SP path); optional
    sliding window (last `window` positions inclusive, causal only)."""
    if window is not None and not causal:
        raise ValueError("window requires causal=True")
    d = q.shape[-1]
    scale = (d ** -0.5) if scale is None else scale
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    if causal:
        lq, lk = q.shape[1], k.shape[1]
        rows = jnp.arange(lq)[:, None]
        cols = jnp.arange(lk)[None, :]
        mask = rows >= cols
        if window is not None:
            mask &= cols > rows - window
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(p.dtype)).astype(q.dtype)
