"""Flash-decode: split-KV cached attention for single-token decode steps,
reading only the cache blocks a row's query may see.

The XLA einsum formulation of decode attention (generate._cached_attention)
scores every row against the whole [kvH, M, D] buffer and masks: at 16k
context on v5e it measured ~4.3x its HBM bound, and in the serving slot
pool it streamed all 16 x 4096 ring positions a step with 2% of them live
(PERF.md, PR 27). This kernel is the decode-side counterpart of the
training flash kernel (ops/attention.py): each grid step streams one
[kvH, block_k, D] block of K and of V through the online-softmax update
with f32 running (m, l, acc) state in VMEM scratch, writing the
normalized output on the row's last block. Pallas's grid pipeline
overlaps the HBM block fetches with compute.

Which blocks: row b's query sits at logical position ``length_b`` and
sees logical ``max(0, length_b - window + 1) .. length_b``, which live at
buffer indices ``(p + offset_b) mod M`` — the serving ring's layout
(models/serving.py), the paged engine's gathered view, and with offsets 0
and equal lengths generate()'s lockstep path. ``live_kv_blocks`` turns
(offsets, lengths, active) into each row's (first block, count), and the
grid IS that work list: one step a live block, row after row, its length
the sum of the counts — a runtime value (Mosaic takes a dynamic grid
bound), so there is one compiled program whatever the lengths and no
step that does nothing. The list rides in as scalar-prefetch operands and
the index maps turn a step into (row, block), wrapping across M. A row
with no live block has no step: its output keeps the zeros the output
buffer starts as. Inside a block, positions are masked by index, exactly
as the einsum's mask computes them.

GQA folds the q heads to [kvH, rep, D]; a step's matmuls are kvH batched
[rep, D] x [D, block_k] — skinny on the MXU, but decode attention is
bandwidth-bound, so the streamed cache bytes are the cost that matters.

int8 caches stream as int8 (HALF the bytes — the entire point of the
quantized cache) and dequantize per block in VMEM: K's per-position
scales fold into the score columns AFTER the matmul, V's scales
pre-multiply the (tiny) probability row — the same scale-folding
discipline as the XLA path, so no dequantized copy of the cache ever
exists anywhere.

``flash_decode_latent`` walks the same work list in the absorbed form of
latent attention (models/transformer.py ``latent_absorb``): one cached
row [c_kv | k_r] a position that every head reads as its key and whose
first kv_rank features are the value, so a grid step streams one block
of a row's latent rows once for all heads. It has its own kernel body
and shares the work list, the mask and ``live_kv_blocks``.

The current token's K/V must already be written to the cache (the
write-then-attend order generate uses).

No reference counterpart: TonY has no compute layer (SURVEY.md §2.3).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# Bytes of K (and as many of V) a grid step streams: 256 positions of
# 8 x 128 bf16 heads, 512 of an int8 cache. Chosen on the v5e (PERF.md §6,
# PR 27): at the serving cell's shapes 256 beat 128 and 512 for 3 short
# live rows and for 16, and tied 512 on 16 full rings; the int8 16k
# lockstep cache ran 1.36x its bytes' time at 512, 1.67x at 256.
KV_BLOCK_BYTES = 512 * 1024


def kv_block_k(m_cap: int, kvh: int, d: int, itemsize: int) -> int:
    """KV positions a grid step streams from a cache of ``m_cap`` positions
    of ``kvh`` heads of ``d``: KV_BLOCK_BYTES' worth in whole 128s, the
    whole cache when it is smaller (a block larger than the array is
    illegal; equal is). The serving engine's ``kv_blocks_read`` count
    (models/serving.py) is in this unit."""
    fit = max(128, KV_BLOCK_BYTES // (kvh * d * itemsize) // 128 * 128)
    return min(fit, m_cap)


def live_kv_blocks(offsets, lengths, active, *, block_k: int, m_cap: int,
                   window: int = 0, xp=np):
    """Which ``block_k``-blocks of each row's ring hold a position the
    row's query may see -> (first block [B], block count [B]); row b reads
    blocks ``(first_b + j) mod n_blocks`` for j < count_b.

    The one statement of the contract ``flash_decode`` reads by: row b's
    query sits at logical position ``lengths[b]`` (its K/V already
    written) and sees logical ``max(0, length - window + 1) .. length``,
    which live at ring indices ``(p + offsets[b]) mod m_cap``; a row that
    is not ``active`` reads nothing. ``xp`` is numpy on the host (the
    serving engine's ``kv_blocks_read`` count) and jax.numpy inside
    ``flash_decode``, whose index maps walk exactly these blocks."""
    n_blocks = -(-m_cap // block_k)
    lo = (xp.maximum(lengths - window + 1, 0) if window
          else xp.zeros_like(lengths))
    n_pos = xp.minimum(lengths - lo + 1, m_cap)
    start = (lo + offsets) % m_cap
    end = start + n_pos - 1             # unwrapped: >= m_cap when it wraps
    first = start // block_k
    count = xp.where(end >= m_cap,
                     n_blocks - first + (end - m_cap) // block_k + 1,
                     end // block_k - first + 1)
    # a range that wraps back into its own first block visits it once:
    # positions are masked by index, not by range
    count = xp.where(active, xp.minimum(count, n_blocks), 0)
    return first, count


def _wrap(blk, n_blocks):
    """Block ``first + j`` of a ring of ``n_blocks`` (one wrap at most)."""
    return jnp.where(blk >= n_blocks, blk - n_blocks, blk)


def _visible(shape, dim, *, blk, row, off_ref, length, block_k, m_cap,
             window):
    """Mask of block ``blk``'s ring indices (along ``dim`` of ``shape``)
    whose logical position (index - offset) mod M the query of ``row`` at
    ``length`` may see."""
    idx = blk * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, dim)
    pos = idx - off_ref[row]
    pos = jnp.where(pos < 0, pos + m_cap, pos)
    ok = pos <= length
    if window:
        ok &= pos > length - window
    if m_cap % block_k:
        ok &= idx < m_cap                               # the ragged tail
    return ok


def _work_list(length, ring_offsets, active, *, b, block_k, m_cap, window):
    """The grid of a decode kernel -> (row lengths [B], offsets [B], row
    of each step [B * n_blocks], each row's block count [B] and first
    block [B], the rows' running count of steps [B]). Grid step s is
    block (s - (ends - count)[b]) of row b = ``row_of[s]``: the blocks
    ``live_kv_blocks`` names, row after row, ``ends[-1]`` steps."""
    n_blocks = pl.cdiv(m_cap, block_k)
    lengths = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (b,))
    offsets = (jnp.zeros((b,), jnp.int32) if ring_offsets is None
               else ring_offsets.astype(jnp.int32))
    live = jnp.ones((b,), bool) if active is None else active
    first, count = live_kv_blocks(offsets, lengths, live, block_k=block_k,
                                  m_cap=m_cap, window=window, xp=jnp)
    ends = jnp.cumsum(count)
    steps = jnp.arange(b * n_blocks)
    row_of = jnp.minimum(
        jnp.sum(steps[:, None] >= ends[None, :], axis=1), b - 1)
    return lengths, offsets, row_of, count, first, ends


def _block_of(s, row_ref, start_ref, count_ref, first_ref, *_, n_blocks):
    """(row, ring block) that grid step ``s`` of the work list streams."""
    row = row_ref[s]
    return row, _wrap(first_ref[row] + s - start_ref[row], n_blocks)


def _decode_kernel(row_ref, start_ref, count_ref, first_ref, len_ref, off_ref,
                   q_ref, k_ref, v_ref, ks_ref, vs_ref, zero_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, scale, block_k, n_blocks,
                   m_cap, window):
    """One grid step = one live KV block of one row, all kv heads at once:
    the grid is the work list ``flash_decode`` builds, row after row. The
    steps run in order, so the f32 (m, l, acc) scratch carries across a
    row's blocks: init at its first, one online-softmax update a block,
    normalize and emit at its last."""
    del zero_ref                    # the output's initial value, aliased
    b = row_ref[pl.program_id(0)]
    j = pl.program_id(0) - start_ref[b]
    count = count_ref[b]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j < count)
    def _block():
        length = len_ref[b]
        blk = _wrap(first_ref[b] + j, n_blocks)
        q = q_ref[...]                                  # [kvH, rep, D]
        s = jnp.einsum(
            "hrd,hkd->hrk", q, k_ref[...].astype(q.dtype),
            preferred_element_type=jnp.float32,
        ) * scale                                       # [kvH, rep, block_k]
        if ks_ref is not None:
            s = s * ks_ref[...].astype(jnp.float32)     # [kvH, 1, block_k]

        visible = functools.partial(
            _visible, blk=blk, row=b, off_ref=off_ref, length=length,
            block_k=block_k, m_cap=m_cap, window=window)
        mask = visible(s.shape, 2)
        s = jnp.where(mask, s, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        # the softmax denominator sums the RAW probabilities; V's dequant
        # scale applies only to the value accumulation below
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        if vs_ref is not None:
            p = p * vs_ref[...].astype(jnp.float32)
        # the PV accumulation keeps p in f32 (v upcast too): casting the
        # probabilities to bf16 here made greedy tokens drift vs the XLA
        # einsum path (f32-accumulated) right where the M>=4096 kernel gate
        # engages. The matmul is cache-bandwidth-bound — the [rep, block_k]
        # prob operand is tiny, so the f32 MXU pass costs nothing measurable.
        v_blk = v_ref[...].astype(jnp.float32)          # [kvH, block_k, D]
        if m_cap % block_k:
            # the ragged tail block's out-of-bounds lanes hold unspecified
            # values; p is 0 there but 0 * NaN = NaN, so V (and its scale)
            # are zeroed at those columns. The [.., block_k, 1] mask is
            # built with its own iota — Mosaic cannot transpose an i1
            # vector ("insertion of minor dim" is 32-bit-only).
            if vs_ref is not None:
                p = jnp.where(mask, p, 0.0)
            v_blk = jnp.where(visible(v_blk.shape[:2] + (1,), 1), v_blk, 0.0)
        acc_ref[...] = acc_ref[...] * corr + jnp.einsum(
            "hrk,hkd->hrd", p, v_blk, preferred_element_type=jnp.float32)

    @pl.when(j == count - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)

    @pl.when(count == 0)            # the one step of an empty work list
    def _nothing():
        o_ref[...] = jnp.zeros_like(o_ref)


def _kernel_no_scale(row_ref, start_ref, count_ref, first_ref, len_ref,
                     off_ref, q_ref, k_ref, v_ref, zero_ref, o_ref,
                     m_ref, l_ref, acc_ref, **kw):
    _decode_kernel(row_ref, start_ref, count_ref, first_ref, len_ref, off_ref,
                   q_ref, k_ref, v_ref, None, None, zero_ref, o_ref,
                   m_ref, l_ref, acc_ref, **kw)


@functools.partial(
    jax.jit, static_argnames=("window", "block_k", "layer", "interpret"))
def flash_decode(q, ck, cv, length, k_scale=None, v_scale=None, *,
                 ring_offsets=None, active=None, window: int = 0,
                 block_k: int | None = None, layer: int | None = None,
                 interpret: bool = False):
    """Cached decode attention for ONE new token per sequence.

    q: [B, kvH, rep, D] current-position queries, grouped by kv head
    ck/cv: [B, kvH, M, D] cache buffers (bf16, or int8 with scales) — or
        the FULL [Ly, B, kvH, M, D] stack with ``layer`` set: the kernel
        then indexes the layer in its BlockSpecs, so the caller's
        per-layer slice never materializes (an XLA slice feeding a pallas
        operand is a real copy — 34MB/layer at 16k, measured ~0.6ms/step
        of pure overhead across the flagship's 12 layers)
    length: int32 scalar (every row at the same position: generate's
        lockstep path) or [B] (each row at its own: the serving slot
        pool) — the new token's logical position, its K/V already written
    ring_offsets: [B] int32 or None (zeros) — row b's logical position p
        lives at buffer index (p + offset_b) mod M
    active: [B] bool or None (all) — a row that is not active reads
        nothing and returns zeros
    k_scale/v_scale: [B, kvH, M] scales ([Ly, B, kvH, M] with ``layer``)
    -> [B, kvH, rep, D] attention output in q's dtype.

    Only the blocks ``live_kv_blocks`` names are streamed from HBM: the
    grid is the list of them, row after row (its length, the sum of the
    counts, is a runtime value), handed to the index maps as
    scalar-prefetch operands. ``block_k`` defaults to ``kv_block_k``'s
    choice. The KV length M need not divide block_k: the tail block's
    out-of-bounds lanes load unspecified values that the index mask
    discards.
    """
    b, kvh, rep, d = q.shape
    m_cap = ck.shape[-2]
    block_k = (kv_block_k(m_cap, kvh, d, ck.dtype.itemsize)
               if block_k is None else min(block_k, m_cap))
    n_blocks = pl.cdiv(m_cap, block_k)
    int8 = k_scale is not None
    lengths, offsets, row_of, count, first, ends = _work_list(
        length, ring_offsets, active, b=b, block_k=block_k, m_cap=m_cap,
        window=window)
    kv_block = functools.partial(_block_of, n_blocks=n_blocks)

    def kv_index(s, *refs):
        row, blk = kv_block(s, *refs)
        return (row, 0, blk, 0) if layer is None else (layer, row, 0, blk, 0)

    def sc_index(s, *refs):
        row, blk = kv_block(s, *refs)
        return ((row, 0, 0, blk) if layer is None
                else (layer, row, 0, 0, blk))

    lead = (None,) if layer is None else (None, None)
    kv_spec = pl.BlockSpec(lead + (kvh, block_k, d), kv_index)
    q_spec = pl.BlockSpec((None, kvh, rep, d),
                          lambda s, row_ref, *_: (row_ref[s], 0, 0, 0))
    in_specs = [q_spec, kv_spec, kv_spec]
    args = [q, ck, cv]
    if int8:
        # [.., kvH, 1, M] so the streamed [kvH, 1, block_k] block is
        # TPU-legal and broadcasts over the rep axis of the scores
        sc_spec = pl.BlockSpec(lead + (kvh, 1, block_k), sc_index)
        in_specs += [sc_spec, sc_spec]
        args += [k_scale[..., None, :], v_scale[..., None, :]]
    # rows with no live block are never visited: the output starts as
    # zeros (an operand left in HBM, aliased to it) and stays so for them
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    args.append(jnp.zeros_like(q))

    kernel = functools.partial(
        _decode_kernel if int8 else _kernel_no_scale,
        scale=d ** -0.5, block_k=block_k, n_blocks=n_blocks, m_cap=m_cap,
        window=window,
    )
    n_prefetch = 6
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_prefetch,
            grid=(jnp.maximum(ends[-1], 1),),
            in_specs=in_specs,
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((kvh, rep, 1), jnp.float32),
                pltpu.VMEM((kvh, rep, 1), jnp.float32),
                pltpu.VMEM((kvh, rep, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kvh, rep, d), q.dtype),
        input_output_aliases={n_prefetch + len(args) - 1: 0},
        interpret=interpret,
    )(row_of.astype(jnp.int32), ends - count, count, first, lengths, offsets,
      *args)


def _latent_kernel(row_ref, start_ref, count_ref, first_ref, len_ref,
                   off_ref, layer_ref, q_ref, c_ref, zero_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, scale, v_dim, block_k, n_blocks,
                   m_cap, window):
    """``_decode_kernel``'s walk in the absorbed form of latent attention:
    a grid step streams one [R, block_k] block of a row's latent rows
    (positions along the lanes), which are the keys of every head, and
    whose first ``v_dim`` features are their values. The probabilities
    enter the weighted sum in the rows' dtype, accumulated in f32, as the
    einsum of the absorbed form does."""
    del zero_ref, layer_ref         # the output's start; the index maps'
    b = row_ref[pl.program_id(0)]
    j = pl.program_id(0) - start_ref[b]
    count = count_ref[b]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j < count)
    def _block():
        blk = _wrap(first_ref[b] + j, n_blocks)
        c = c_ref[...]                                  # [R, block_k]
        s = jnp.einsum("hr,rk->hk", q_ref[...], c,
                       preferred_element_type=jnp.float32) * scale
        mask = _visible(s.shape, 1, blk=blk, row=b, off_ref=off_ref,
                        length=len_ref[b], block_k=block_k, m_cap=m_cap,
                        window=window)
        s = jnp.where(mask, s, NEG_INF)                 # [H, block_k]
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        v = c[:v_dim]
        if m_cap % block_k:
            # the ragged tail block's positions past M hold unspecified
            # values (p is 0 there, but 0 * NaN = NaN): zeroed
            idx = blk * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            v = jnp.where(idx < m_cap, v, jnp.zeros_like(v))
        acc_ref[...] = acc_ref[...] * corr + jnp.einsum(
            "hk,vk->hv", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)

    @pl.when(j == count - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)

    @pl.when(count == 0)            # the one step of an empty work list
    def _nothing():
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(
    jax.jit,
    static_argnames=("v_dim", "scale", "window", "block_k", "interpret"))
def flash_decode_latent(q, rows, length, layer, *, v_dim: int, scale: float,
                        ring_offsets=None, active=None, window: int = 0,
                        block_k: int | None = None, interpret: bool = False):
    """The absorbed form of latent attention for ONE new token per
    sequence, over the live blocks of each row's latent rows only.

    q: [B, H, R] the heads' queries against the rows themselves
        (``transformer.latent_absorb``; R = kv_rank + rope)
    rows: the WHOLE [Ly, B, M, R] stack of latent rows (``KVCache
        .latent``), ``layer`` (an int32 scalar, traced: one kernel for
        every layer) picking the layer inside the BlockSpec, so no slice
        of the stack is made; the rows are the keys of every head, and
        their first ``v_dim`` features the values. The kernel reads the
        stack as [Ly, B, R, M], positions along the lanes: that is how
        the TPU lays out a stack whose R (576) is not a multiple of 128
        and M is, so the transposition is a view and not a copy
    length, ring_offsets, active: ``flash_decode``'s contract
    -> [B, H, v_dim] sum p row[:v_dim] in q's dtype (zeros for a row that
    is not active). ``scale`` is the scores' factor; ``block_k`` defaults
    to ``kv_block_k`` of one head R wide, the unit the serving engine's
    ``kv_blocks_read`` counts in.
    """
    b, h, r = q.shape
    m_cap = rows.shape[-2]
    rows = jnp.swapaxes(rows, -1, -2)                   # [Ly, B, R, M]
    block_k = (kv_block_k(m_cap, 1, r, rows.dtype.itemsize)
               if block_k is None else min(block_k, m_cap))
    n_blocks = pl.cdiv(m_cap, block_k)
    lengths, offsets, row_of, count, first, ends = _work_list(
        length, ring_offsets, active, b=b, block_k=block_k, m_cap=m_cap,
        window=window)

    def row_index(s, *refs):
        row, blk = _block_of(s, *refs, n_blocks=n_blocks)
        return refs[6][0], row, 0, blk

    out_spec = pl.BlockSpec((None, h, v_dim),
                            lambda s, row_ref, *_: (row_ref[s], 0, 0))
    kernel = functools.partial(
        _latent_kernel, scale=scale, v_dim=v_dim, block_k=block_k,
        n_blocks=n_blocks, m_cap=m_cap, window=window)
    n_prefetch = 7
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_prefetch,
            grid=(jnp.maximum(ends[-1], 1),),
            in_specs=[
                pl.BlockSpec((None, h, r),
                             lambda s, row_ref, *_: (row_ref[s], 0, 0)),
                pl.BlockSpec((None, None, r, block_k), row_index),
                # rows with no live block are never visited: the output
                # starts as these zeros (aliased) and stays so for them
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=out_spec,
            scratch_shapes=[
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, v_dim), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, v_dim), q.dtype),
        input_output_aliases={n_prefetch + 2: 0},
        interpret=interpret,
    )(row_of.astype(jnp.int32), ends - count, count, first, lengths, offsets,
      jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)), q, rows,
      jnp.zeros((b, h, v_dim), q.dtype))


# Rows a grid step of ``write_latent_rows`` rewrites. A step holds each
# row's [R, 128] lane tile in and out, double-buffered: 4 x 8 x 576 x 128
# x 2 B = 4.7 MB of a bf16 stack, inside Mosaic's 16 MiB of scoped VMEM
# with room for the f32 broadcast of the new rows (2.4 MB)
LATENT_WRITE_ROWS = 8


def _row_write_kernel(at_ref, new_ref, tile_ref, out_ref):
    """Lane ``cursor % lanes`` of each row's [R, lanes] tile becomes the
    row's new latent row; the other lanes are the tile as it was."""
    lanes = tile_ref.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, tile_ref.shape, 2)
    new = jnp.broadcast_to(new_ref[...][:, :, None], tile_ref.shape)
    out_ref[...] = jnp.where(lane == at_ref[1] % lanes,
                             new.astype(out_ref.dtype), tile_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def write_latent_rows(rows, new, layer, cursor, *, interpret: bool = False):
    """Every row's new latent row into the WHOLE [Ly, B, M, R] stack
    (``KVCache.latent``) at position ``cursor`` of layer ``layer`` (both
    int32 scalars, traced: one kernel for every layer and step), in place:
    ``lax.dynamic_update_slice(rows, new[None, :, None], (layer, 0,
    cursor, 0))`` bit for bit, with ``new`` [B, R].

    The stack is read as ``flash_decode_latent`` reads it, [Ly, B, R, M],
    positions along the lanes: there one position of a row is one lane in
    each of its R / 16 (16, 128) tiles, which XLA's update writes as that
    many partial-tile read-modify-writes. Here a grid step brings the [R,
    128] tile holding the cursor's lane of ``LATENT_WRITE_ROWS`` rows into
    VMEM, sets that lane and writes the tiles back whole, to where they
    came from (the stack is aliased to the output): 2 x 147 KB a row of a
    bf16 stack 576 wide. Every row is written, an idle row's dead write
    included, as the update writes it."""
    _, b, m_cap, r = rows.shape
    lanes = min(128, m_cap)
    rb = min(LATENT_WRITE_ROWS, b)
    tile = pl.BlockSpec((None, rb, r, lanes),
                        lambda i, at: (at[0], i, 0, at[1] // lanes))
    out = pl.pallas_call(
        _row_write_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pl.cdiv(b, rb),),
            in_specs=[pl.BlockSpec((rb, r), lambda i, at: (i, 0)), tile],
            out_specs=tile,
        ),
        out_shape=jax.ShapeDtypeStruct(rows.shape[:2] + (r, m_cap),
                                       rows.dtype),
        input_output_aliases={2: 0},
        interpret=interpret,
    )(jnp.stack([jnp.asarray(layer, jnp.int32),
                 jnp.asarray(cursor, jnp.int32)]),
      # a 32-bit operand: its rows go from the lanes to the sublanes of
      # the tile in VMEM (Mosaic inserts a minor dim of 32-bit values
      # only); the value is the stack's own, so the write is exact
      new.astype(rows.dtype).astype(jnp.float32),
      jnp.swapaxes(rows, -1, -2))
    return jnp.swapaxes(out, -1, -2)


__all__ = ["flash_decode", "flash_decode_latent", "kv_block_k",
           "live_kv_blocks", "write_latent_rows"]
