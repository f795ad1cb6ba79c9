"""Fused flash attention (forward + backward) as Pallas TPU kernels.

The hot op of the flagship model, tiered by sequence length:

- **VMEM-resident** (L <= 2048): one program per (batch, head), whole
  q/k/v/o in VMEM, fully static tile loops, fused dQ/dK/dV backward.
- **Fused streaming** (L <= 8192): K/V blocks stream HBM -> VMEM with
  double-buffered async DMA and online softmax; the backward is ONE
  kv-block sweep computing dK/dV and accumulating dQ in an [L, D] f32
  VMEM block revisited across the grid — scores/exp recomputed once per
  tile.
- **Split streaming** (beyond): the same forward, with the classic
  two-kernel backward (dQ sweeps KV blocks, dK/dV sweep Q blocks from the
  diagonal down) whose memory stays O(block) — sequence length is bounded
  by HBM, not the 16MB VMEM, which is what makes long-context training
  viable (XLA autodiff of naive attention materializes L x L residuals:
  34GB at L=32k). This tier defaults to a 1024-row q block (measured -14%
  fwd+bwd at 16k vs the 512 the shorter tiers use). Raising the fused
  tier to 16k compiles (8MB dq accumulator) but measured no faster than
  split with the retuned blocks, and 32k blows VMEM — so the boundary
  stays at 8192.

For training, pair long L with `remat_policy="attn"` (models/transformer):
the flash custom_vjp names its (out, lse) residuals so remat saves them
and the backward never re-runs the forward kernel — +7.5%/+14%/+17% step
throughput at L=8k/16k/32k, neutral at 2k.

Forward saves only O and the per-row logsumexp (standard flash
recomputation). Causal masking prunes the KV sweep to lower-triangular
blocks, skipping both the compute AND the DMA of masked blocks (~half the
FLOPs and bytes).

Layout is [B, H, L, D], length tiled to MXU-friendly blocks, scores in f32.
On non-TPU backends the same kernels run in interpreter mode (tests).

No reference counterpart: TonY has no compute layer at all (SURVEY.md §2.3);
this is the TPU-native capability layer of the rebuild.
"""

from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..parallel.ring_attention import reference_attention

NEG_INF = -1e30
# block sizes from fwd+bwd sweeps on v5e (B=4 H=8 L=2048 D=128, chained
# dependent iterations): 512/512 beats 256/512 by ~8% total and 128/256 by
# ~20%; VMEM stays far under budget (k+v double buffers ~0.5MB at 512x128)
BLOCK_Q = 512
BLOCK_K = 512


def _causal_mask(qi, bq, j, bk, window=None):
    rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = rows >= cols
    if window is not None:
        # sliding window: each row attends to its last `window` positions
        # (inclusive of itself)
        mask &= cols > rows - window
    return mask


def _attn_mask(qi, bq, j, bk, causal, kv_len, window=None):
    """Combined causal/sliding-window + ragged-KV mask for one [bq, bk]
    score tile, or None when every position is valid (the even, non-causal
    fast path)."""
    mask = _causal_mask(qi, bq, j, bk, window) if causal else None
    if kv_len is not None:
        cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        valid = cols < kv_len
        mask = valid if mask is None else (mask & valid)
    return mask


def _n_full_blocks(qi, bq, block_k, hi, causal, kv_len, window):
    """First kv-block index that needs masking, for q-block qi: blocks in
    [lo, n_full) are fully visible and run a mask-free loop body; blocks in
    [n_full, hi) run the masked body. Masked tiles cost ~2x an unmasked
    tile in VPU passes (iota, compare, where) and most causal tiles are
    fully below the diagonal, so the static split wins back real kernel
    time (a runtime cond can't: Mosaic predicates both paths).

    Returns None when the split doesn't apply (sliding window — the band
    has partial tiles on BOTH edges, handled by the single masked loop)."""
    if window is not None:
        return None
    n_full = hi
    if causal:
        # tile j fully visible iff min_row >= max_col:
        # qi*bq >= (j+1)*block_k - 1
        n_full = jnp.minimum(n_full, (qi * bq + 1) // block_k)
    if kv_len is not None:
        n_full = jnp.minimum(n_full, kv_len // block_k)
    return n_full


def _window_lo(qi, bq, block_k, window):
    """First KV block intersecting q-block qi's window band (traced)."""
    if window is None:
        return 0
    return jnp.maximum(0, (qi * bq - window + 1) // block_k)


def _validate_window(causal, window):
    """The band pruning (_window_lo) only matches the mask when causal —
    a non-causal windowed call would skip blocks WITHOUT masking the rest,
    silently corrupting the softmax. Validate at every public entry."""
    if window is not None:
        if not causal:
            raise ValueError("window requires causal=True")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")


class _Streamer:
    """Double-buffered HBM->VMEM block pipeline over one or more arrays
    (the guide's double-buffering pattern, generalized to N streams that
    advance in lockstep)."""

    def __init__(self, hbm_refs, bufs, sems, batch, block, lo, hi):
        self._hbm = hbm_refs      # list of HBM refs [BH, L, d_i]
        self._bufs = bufs         # list of VMEM scratch [2, block, d_i]
        self._sems = sems         # DMA sems [n_streams, 2]
        self._batch = batch
        self._block = block
        self._lo = lo
        self._hi = hi

    def _dma(self, stream, slot, j):
        return pltpu.make_async_copy(
            self._hbm[stream].at[self._batch, pl.ds(j * self._block, self._block), :],
            self._bufs[stream].at[slot],
            self._sems.at[stream, slot],
        )

    def start(self):
        @pl.when(self._lo < self._hi)
        def _():
            for s in range(len(self._hbm)):
                self._dma(s, 0, self._lo).start()

    def step(self, j):
        """Prefetch j+1, wait for j, return the j blocks (VMEM views)."""
        rel = j - self._lo
        slot = jax.lax.rem(rel, 2)
        nxt = jax.lax.rem(rel + 1, 2)

        @pl.when(j + 1 < self._hi)
        def _():
            for s in range(len(self._hbm)):
                self._dma(s, nxt, j + 1).start()

        for s in range(len(self._hbm)):
            self._dma(s, slot, j).wait()
        return [buf[slot] for buf in self._bufs]


# ------------------------------------------------------------ shared tiles
# The numerically delicate per-tile math lives ONCE here and serves both
# kernel families (streaming and VMEM-resident): a fix in the rescale or
# masking logic cannot diverge between paths.

def _fwd_tile_update(q, k_blk, v_blk, carry, scale, mask, remask):
    """One online-softmax tile: carry = (m, l, acc) f32 running state.
    Operands stay in storage dtype (bf16) into the MXU with f32
    accumulation — upcasting first costs ~4x in matmul passes."""
    m, l, acc = carry
    s = jax.lax.dot_general(
        q, k_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale                                          # [BQ, BK] f32
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    # rows with no valid column in sight (ragged tails; rows whose window
    # band starts past the first swept block) must produce p == 0, which
    # exp(s - m_new) alone can't when m_new is itself NEG_INF — re-mask p.
    # Plain causal never has such rows (kv block 0 is fully valid for every
    # row), so its callers pass remask=False and skip the pass.
    if mask is not None and remask:
        p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * corr + jax.lax.dot_general(
        p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return m_new, l_new, acc_new


def _bwd_tile(q_j, do_j, k_blk, v_blk, lse_j, delta_j, scale, mask,
              want_dq=True, want_dkv=True):
    """One backward tile: recompute p = exp(s - lse), ds = p*(dO V^T - delta),
    emitting only the requested gradient pieces so each kernel pays exactly
    its own matmuls. Returns (dq_inc, dk_inc, dv_inc), None where unwanted."""
    s = scale * jax.lax.dot_general(
        q_j, k_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    p = jnp.exp(s - lse_j)
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    dv_inc = None
    if want_dkv:
        dv_inc = jax.lax.dot_general(
            p.astype(do_j.dtype), do_j, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    dp = jax.lax.dot_general(
        do_j, v_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    ds = (p * (dp - delta_j)).astype(q_j.dtype)
    dk_inc = None
    if want_dkv:
        dk_inc = scale * jax.lax.dot_general(
            ds, q_j, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    dq_inc = None
    if want_dq:
        dq_inc = scale * jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    return dq_inc, dk_inc, dv_inc


# ------------------------------------------------------------------ forward

def _fwd_kernel(q_ref, k_hbm, v_hbm, o_ref, lse_ref, k_buf, v_buf, sems,
                *, scale, causal, block_k, kv_len=None, window=None):
    """One (batch*head, q-block) program: stream KV blocks, online softmax.
    Also writes the per-row logsumexp residual for the backward. A sliding
    window additionally prunes blocks BELOW the band — DMA and compute both
    skip everything outside [row-window, row], so cost is O(L*window)."""
    b_ = pl.program_id(0)
    qi = pl.program_id(1)
    # inputs stay in their storage dtype (bf16): the MXU's native mode is
    # low-precision multiply with f32 accumulation (preferred_element_type);
    # upcasting before the dot would force ~4x-slower f32 matmul passes
    q = q_ref[0]                                      # [BQ, D]
    bq, d = q.shape
    nk = k_hbm.shape[1] // block_k
    hi = (
        jnp.minimum(((qi + 1) * bq + block_k - 1) // block_k, nk)
        if causal else nk
    )
    lo = _window_lo(qi, bq, block_k, window)
    stream = _Streamer([k_hbm, v_hbm], [k_buf, v_buf], sems, b_, block_k, lo, hi)
    stream.start()

    remask = window is not None or kv_len is not None

    def make_body(masked):
        def body(j, carry):
            k_blk, v_blk = stream.step(j)
            mask = (
                _attn_mask(qi, bq, j, block_k, causal, kv_len, window)
                if masked else None
            )
            return _fwd_tile_update(q, k_blk, v_blk, carry, scale, mask, remask)
        return body

    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)
    carry = (m0, l0, acc0)
    n_full = _n_full_blocks(qi, bq, block_k, hi, causal, kv_len, window)
    if n_full is None:
        carry = jax.lax.fori_loop(lo, hi, make_body(True), carry)
    else:
        # mask-free sweep over fully-visible tiles, masked sweep for the rest
        n_full = jnp.maximum(n_full, lo)
        carry = jax.lax.fori_loop(lo, n_full, make_body(False), carry)
        carry = jax.lax.fori_loop(n_full, hi, make_body(True), carry)
    m, l, acc = carry
    l_safe = jnp.where(l > 0, l, 1.0)
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    # lse stored lane-major [1, bq]: a [L, 1] layout pads every row to 128
    # lanes in VMEM (16MB at L=32k); [1, L] costs sublane padding only (1MB)
    lse_ref[0, 0] = jnp.where(l[:, 0] > 0, m[:, 0] + jnp.log(l_safe[:, 0]), NEG_INF)


# ------------------------------------------------------------------ backward

def _dq_kernel(q_ref, k_hbm, v_hbm, do_ref, lse_ref, delta_ref, dq_ref,
               k_buf, v_buf, sems, *, scale, causal, block_k, kv_len=None,
               window=None):
    """dQ for one q block: sweep KV blocks.
    ds = p * (dO@V^T - delta); dQ = scale * ds @ K."""
    b_ = pl.program_id(0)
    qi = pl.program_id(1)
    q = q_ref[0]                                       # [BQ, D] storage dtype
    do = do_ref[0]
    lse = lse_ref[0, 0][:, None]                       # [BQ, 1]
    delta = delta_ref[0, 0][:, None]
    bq, d = q.shape
    nk = k_hbm.shape[1] // block_k
    hi = (
        jnp.minimum(((qi + 1) * bq + block_k - 1) // block_k, nk)
        if causal else nk
    )
    lo = _window_lo(qi, bq, block_k, window)
    stream = _Streamer([k_hbm, v_hbm], [k_buf, v_buf], sems, b_, block_k, lo, hi)
    stream.start()

    def make_body(masked):
        def body(j, dq):
            k_blk, v_blk = stream.step(j)
            mask = (
                _attn_mask(qi, bq, j, block_k, causal, kv_len, window)
                if masked else None
            )
            dq_inc, _, _ = _bwd_tile(
                q, do, k_blk, v_blk, lse, delta, scale, mask, want_dkv=False
            )
            return dq + dq_inc
        return body

    dq = jnp.zeros((bq, d), jnp.float32)
    n_full = _n_full_blocks(qi, bq, block_k, hi, causal, kv_len, window)
    if n_full is None:
        dq = jax.lax.fori_loop(lo, hi, make_body(True), dq)
    else:
        n_full = jnp.maximum(n_full, lo)
        dq = jax.lax.fori_loop(lo, n_full, make_body(False), dq)
        dq = jax.lax.fori_loop(n_full, hi, make_body(True), dq)
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _kv_sweep_kernel(q_hbm, k_ref, v_ref, do_hbm, lse_ref, delta_ref, *refs,
                     scale, causal, block_q, kv_len=None, window=None,
                     fused=False):
    """One (batch*head, kv-block) program sweeping Q blocks — BOTH streaming
    backward tiers share this body:

    - split (fused=False): emits dK/dV only (refs = dk, dv, scratch). The
      companion _dq_kernel recomputes scores for dQ; memory stays O(block).
    - fused (fused=True): refs also lead with a dq accumulator whose block
      index map is constant along the kv grid dim, so Pallas keeps it
      VMEM-resident across the sequential revisits (race-free: TPU grid
      iterations execute in order on the core). Each tile's scores/exp are
      recomputed ONCE instead of once per split kernel, at the price of an
      [L, D] f32 dq block (FUSED_STREAM_MAX_L bounds it).

    Sweep bounds: from the diagonal down when causal; a sliding window also
    bounds the sweep from ABOVE — rows past col+window can't see this
    block. dV = p^T @ dO; dK = scale * ds^T @ Q; dQ += scale * ds @ K.
    Q/dO stream from HBM; lse/delta are 4B/row and ride in VMEM whole."""
    if fused:
        dq_ref, dk_ref, dv_ref, q_buf, do_buf, sems = refs
    else:
        dq_ref = None
        dk_ref, dv_ref, q_buf, do_buf, sems = refs
    b_ = pl.program_id(0)
    ki = pl.program_id(1)
    k_blk = k_ref[0]                                   # [BK, D] storage dtype
    v_blk = v_ref[0]
    bk, d = k_blk.shape
    nq = q_hbm.shape[1] // block_q

    if fused:
        @pl.when(ki == 0)
        def _init_dq():
            dq_ref[0] = jnp.zeros(dq_ref.shape[1:], dq_ref.dtype)

    lo = (ki * bk) // block_q if causal else 0
    hi = nq
    if window is not None:
        # rows seeing col c satisfy row < c + window; last col of this
        # block is ki*bk + bk - 1
        hi = jnp.minimum(nq, (ki * bk + bk - 1 + window + block_q - 1) // block_q)
    stream = _Streamer(
        [q_hbm, do_hbm], [q_buf, do_buf], sems, b_, block_q, lo, hi,
    )
    stream.start()

    # the split tier never masks padded KV columns here (its dk/dv rows for
    # padded positions are sliced away by the caller) — but the fused tier's
    # dQ really consumes them, so it must
    kv_len_eff = kv_len if fused else None

    def make_body(masked):
        def body(j, carry):
            dk, dv = carry
            q_j, do_j = stream.step(j)
            lse_j = lse_ref[0, 0, pl.ds(j * block_q, block_q)][:, None]   # [BQ, 1]
            delta_j = delta_ref[0, 0, pl.ds(j * block_q, block_q)][:, None]
            mask = (
                _attn_mask(j, block_q, ki, bk, causal, kv_len_eff, window)
                if masked else None
            )
            dq_inc, dk_inc, dv_inc = _bwd_tile(
                q_j, do_j, k_blk, v_blk, lse_j, delta_j, scale, mask,
                want_dq=fused,
            )
            if fused:
                cur = dq_ref[0, pl.ds(j * block_q, block_q), :]
                dq_ref[0, pl.ds(j * block_q, block_q), :] = (
                    cur + dq_inc.astype(dq_ref.dtype)
                )
            return dk + dk_inc, dv + dv_inc
        return body

    dk0 = jnp.zeros((bk, d), jnp.float32)
    dv0 = jnp.zeros((bk, d), jnp.float32)
    carry = (dk0, dv0)
    always_mask = kv_len_eff is not None
    if not causal:
        dk, dv = jax.lax.fori_loop(lo, hi, make_body(always_mask), carry)
    elif window is not None or always_mask:
        # band-pruned sweep (partial tiles on both edges) or ragged-KV dq
        # masking: single masked loop
        dk, dv = jax.lax.fori_loop(lo, hi, make_body(True), carry)
    else:
        # roles swapped vs the fwd/dq sweeps: rows are q blocks (j), cols
        # this kv block (ki). Masked (diagonal) tiles come FIRST in the
        # sweep; q blocks past the diagonal see the whole kv block.
        m_end = jnp.minimum(
            hi, -(-((ki + 1) * bk - 1) // block_q)  # ceil division
        )
        carry = jax.lax.fori_loop(lo, m_end, make_body(True), carry)
        dk, dv = jax.lax.fori_loop(m_end, hi, make_body(False), carry)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


# ---------------------------------------------------- VMEM-resident kernels
#
# At L <= RESIDENT_MAX_L (and D <= 128) one (batch, head)'s whole q/k/v/o —
# plus the f32 dq accumulator in the backward — fits VMEM, so the kernel
# needs NO per-block DMA choreography at all: grid (B*H,), Pallas pipelines
# whole [L, D] blocks between grid steps, and the tile loops are plain
# Python loops over static slices (every causal/ragged/window decision is
# resolved at trace time — full tiles compile with zero masking code).
# The backward is additionally FUSED: one sweep computes dK, dV and dQ,
# recomputing scores/exp once per tile instead of once in each of the
# dq/dkv kernels. Longer sequences fall back to the streaming kernels
# above, which keep O(block) VMEM.

# 2048: at 4096 the fully-unrolled tile loops blow Mosaic's scoped-VMEM
# stack (~40MB of live temporaries vs the 16MB budget)
RESIDENT_MAX_L = 2048
# mid tier for the backward: one FUSED streaming sweep (dq accumulated in a
# VMEM output block revisited across the kv grid dimension) instead of the
# split dq/dkv kernels — saves one score/exp recompute per tile. The dq
# accumulator is [L, D] f32 per (batch, head): 4MB at L=8192; beyond that
# the split O(block)-memory kernels take over.
FUSED_STREAM_MAX_L = 8192


def _static_tile_kind(qi, bq, j, bk, causal, kv_len, window):
    """Python-level (static) classification of tile (qi, j): 'skip' (fully
    masked — don't emit code), 'full' (no mask), or 'partial'."""
    row_lo, row_hi = qi * bq, (qi + 1) * bq - 1
    col_lo, col_hi = j * bk, (j + 1) * bk - 1
    if causal and col_lo > row_hi:
        return "skip"
    if window is not None and col_hi < row_lo - window + 1:
        return "skip"
    if kv_len is not None and col_lo >= kv_len:
        return "skip"
    full = True
    if causal and col_hi > row_lo:
        full = False
    if window is not None and col_lo < row_hi - window + 1:
        full = False
    if kv_len is not None and col_hi >= kv_len:
        full = False
    return "full" if full else "partial"


def _fwd_kernel_resident(q_ref, k_ref, v_ref, o_ref, lse_ref,
                         *, scale, causal, block_q, block_k,
                         kv_len=None, window=None):
    """One (batch*head) program: everything VMEM-resident, static tile loops."""
    lq, d = q_ref.shape[1], q_ref.shape[2]
    lk = k_ref.shape[1]
    nq, nk = lq // block_q, lk // block_k

    remask = window is not None or kv_len is not None
    for qi in range(nq):
        q = q_ref[0, qi * block_q:(qi + 1) * block_q, :]
        carry = (
            jnp.full((block_q, 1), NEG_INF, jnp.float32),
            jnp.zeros((block_q, 1), jnp.float32),
            jnp.zeros((block_q, d), jnp.float32),
        )
        for j in range(nk):
            kind = _static_tile_kind(
                qi, block_q, j, block_k, causal, kv_len, window
            )
            if kind == "skip":
                continue
            k_blk = k_ref[0, j * block_k:(j + 1) * block_k, :]
            v_blk = v_ref[0, j * block_k:(j + 1) * block_k, :]
            mask = (
                _attn_mask(qi, block_q, j, block_k, causal, kv_len, window)
                if kind == "partial" else None
            )
            carry = _fwd_tile_update(q, k_blk, v_blk, carry, scale, mask,
                                     remask)
        m, l, acc = carry
        l_safe = jnp.where(l > 0, l, 1.0)
        o_ref[0, qi * block_q:(qi + 1) * block_q, :] = (
            (acc / l_safe).astype(o_ref.dtype)
        )
        lse_ref[0, 0, qi * block_q:(qi + 1) * block_q] = jnp.where(
            l[:, 0] > 0, m[:, 0] + jnp.log(l_safe[:, 0]), NEG_INF
        )


def _bwd_kernel_resident(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dk_ref, dv_ref,
                         *, scale, causal, block_q, block_k,
                         kv_len=None, window=None):
    """Fused dQ/dK/dV for one (batch*head): a single sweep recomputes each
    tile's scores/exp ONCE (the split dq/dkv kernels do it twice) and
    accumulates dQ in the f32 VMEM output ref across kv blocks."""
    lq, d = q_ref.shape[1], q_ref.shape[2]
    lk = k_ref.shape[1]
    nq, nk = lq // block_q, lk // block_k

    dq_ref[0] = jnp.zeros((lq, d), dq_ref.dtype)
    for ki in range(nk):
        k_blk = k_ref[0, ki * block_k:(ki + 1) * block_k, :]
        v_blk = v_ref[0, ki * block_k:(ki + 1) * block_k, :]
        dk = jnp.zeros((block_k, d), jnp.float32)
        dv = jnp.zeros((block_k, d), jnp.float32)
        for j in range(nq):
            kind = _static_tile_kind(
                j, block_q, ki, block_k, causal, kv_len, window
            )
            if kind == "skip":
                continue
            sl = slice(j * block_q, (j + 1) * block_q)
            q_j = q_ref[0, sl, :]
            do_j = do_ref[0, sl, :]
            lse_j = lse_ref[0, 0, sl][:, None]
            delta_j = delta_ref[0, 0, sl][:, None]
            mask = (
                _attn_mask(j, block_q, ki, block_k, causal, kv_len, window)
                if kind == "partial" else None
            )
            dq_inc, dk_inc, dv_inc = _bwd_tile(
                q_j, do_j, k_blk, v_blk, lse_j, delta_j, scale, mask
            )
            dk = dk + dk_inc
            dv = dv + dv_inc
            dq_ref[0, sl, :] += dq_inc.astype(dq_ref.dtype)
        dk_ref[0, ki * block_k:(ki + 1) * block_k, :] = dk.astype(dk_ref.dtype)
        dv_ref[0, ki * block_k:(ki + 1) * block_k, :] = dv.astype(dv_ref.dtype)


def _use_resident(lq, lk, d):
    """Whole-sequence VMEM residency budget (see section comment)."""
    return lq <= RESIDENT_MAX_L and lk <= RESIDENT_MAX_L and d <= 128


def _block(block, l):
    """Kernel block size for a length-l axis: the configured block, shrunk for
    short sequences but kept a multiple of 128 — Mosaic requires sliced-ref
    shapes aligned to the (8, 128) tiling (HBM row slices AND the lane-major
    lse/delta lane slices), so arbitrary l (e.g. 300) cannot be a block."""
    return min(block, max(128, -(-l // 128) * 128))


def _pad_to(x, axis, multiple):
    size = x.shape[axis]
    rem = size % multiple
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, multiple - rem)
    return jnp.pad(x, pad)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "block_q", "block_k", "interpret", "window"),
)
def _flash_fwd(q, k, v, causal, scale, block_q=BLOCK_Q, block_k=BLOCK_K,
               interpret=False, window=None):
    """q,k,v: [B, H, L, D] -> (out [B,H,L,D], lse [B,H,L] f32)."""
    b, h, lq, d = q.shape
    lk = k.shape[2]
    scale = (d ** -0.5) if scale is None else scale
    block_q = _block(block_q, lq)
    block_k = _block(block_k, lk)
    qp = _pad_to(q, 2, block_q)
    kp = _pad_to(k, 2, block_k)
    vp = _pad_to(v, 2, block_k)
    # ragged L_k: kernel masks padded KV columns (kv_len is static -> the
    # even case compiles with no mask at all)
    kv_len = lk if kp.shape[2] != lk else None

    bh = b * h
    qf = qp.reshape(bh, qp.shape[2], d)
    kf = kp.reshape(bh, kp.shape[2], d)
    vf = vp.reshape(bh, vp.shape[2], d)
    nq = qf.shape[1] // block_q

    if _use_resident(qf.shape[1], kf.shape[1], d):
        out, lse = pl.pallas_call(
            functools.partial(
                _fwd_kernel_resident, scale=scale, causal=causal,
                block_q=block_q, block_k=block_k, kv_len=kv_len,
                window=window,
            ),
            grid=(bh,),
            in_specs=[
                pl.BlockSpec((1, qf.shape[1], d), lambda b_: (b_, 0, 0)),
                pl.BlockSpec((1, kf.shape[1], d), lambda b_: (b_, 0, 0)),
                pl.BlockSpec((1, kf.shape[1], d), lambda b_: (b_, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, qf.shape[1], d), lambda b_: (b_, 0, 0)),
                pl.BlockSpec((1, 1, qf.shape[1]), lambda b_: (b_, 0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct(qf.shape, q.dtype),
                jax.ShapeDtypeStruct((bh, 1, qf.shape[1]), jnp.float32),
            ],
            interpret=interpret,
        )(qf, kf, vf)
    else:
        out, lse = pl.pallas_call(
            functools.partial(_fwd_kernel, scale=scale, causal=causal,
                              block_k=block_k, kv_len=kv_len, window=window),
            grid=(bh, nq),
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda b_, i: (b_, i, 0)),
                pl.BlockSpec(memory_space=pl.ANY),   # K stays in HBM, DMA'd
                pl.BlockSpec(memory_space=pl.ANY),   # V stays in HBM, DMA'd
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, d), lambda b_, i: (b_, i, 0)),
                pl.BlockSpec((1, 1, block_q), lambda b_, i: (b_, 0, i)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct(qf.shape, q.dtype),
                jax.ShapeDtypeStruct((bh, 1, qf.shape[1]), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((2, block_k, d), k.dtype),
                pltpu.VMEM((2, block_k, d), v.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
            interpret=interpret,
        )(qf, kf, vf)
    out = out.reshape(b, h, qf.shape[1], d)[:, :, :lq, :]
    lse = lse.reshape(b, h, qf.shape[1])[:, :, :lq]
    return out, lse


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "block_q", "block_k", "interpret", "window"),
)
def _flash_bwd(q, k, v, o, lse, g, causal, scale,
               block_q=BLOCK_Q, block_k=BLOCK_K, interpret=False, g_lse=None,
               window=None):
    b, h, lq, d = q.shape
    lk = k.shape[2]
    scale = (d ** -0.5) if scale is None else scale
    if (block_q, block_k) == (BLOCK_Q, BLOCK_K) and lq > FUSED_STREAM_MAX_L:
        # long-context split tier: doubling the q block amortizes per-tile
        # overhead over more rows — measured fwd+bwd 27.3 -> 23.4 ms/iter
        # (-14%) at L=16384 and -5% at L=32768 on v5e (1024x512; both-1024
        # and k-1024 measured no better, and bigger blocks blow VMEM)
        block_q = 1024
    block_q = _block(block_q, lq)
    block_k = _block(block_k, lk)

    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)  # [B,H,L]
    if g_lse is not None:
        # cotangent on the lse output: d lse_i/d s_ij = p_ij, so the extra
        # ds term is g_lse_i * p_ij — absorbed as delta' = delta - g_lse in
        # ds = p * (dp - delta'). dV is untouched (no lse dependence).
        delta = delta - g_lse.astype(jnp.float32)

    qp, gp = _pad_to(q, 2, block_q), _pad_to(g, 2, block_q)
    kp, vp = _pad_to(k, 2, block_k), _pad_to(v, 2, block_k)
    kv_len = lk if kp.shape[2] != lk else None
    # padded q rows: lse=+big -> p = exp(s - lse) = 0; delta=0
    # (NEG_INF here would make p = exp(s + 1e30) = inf -> NaN dK/dV)
    lsep = _pad_to(lse, 2, block_q)
    deltap = _pad_to(delta, 2, block_q)
    if lsep.shape[2] != lse.shape[2]:
        pad_rows = lsep.shape[2] - lse.shape[2]
        lsep = lsep.at[:, :, -pad_rows:].set(-NEG_INF)
    # lane-major layout (see _fwd_kernel note)

    bh = b * h
    lqp, lkp = qp.shape[2], kp.shape[2]
    qf = qp.reshape(bh, lqp, d)
    kf = kp.reshape(bh, lkp, d)
    vf = vp.reshape(bh, lkp, d)
    gf = gp.reshape(bh, lqp, d)
    lsef = lsep.reshape(bh, 1, lqp)
    deltaf = deltap.reshape(bh, 1, lqp)

    nq = lqp // block_q
    nk = lkp // block_k

    if _use_resident(lqp, lkp, d):
        # fused resident backward: dq accumulates in f32 (the in-ref
        # accumulation across kv blocks must not round in bf16)
        dq, dk, dv = pl.pallas_call(
            functools.partial(
                _bwd_kernel_resident, scale=scale, causal=causal,
                block_q=block_q, block_k=block_k, kv_len=kv_len,
                window=window,
            ),
            grid=(bh,),
            in_specs=[
                pl.BlockSpec((1, lqp, d), lambda b_: (b_, 0, 0)),
                pl.BlockSpec((1, lkp, d), lambda b_: (b_, 0, 0)),
                pl.BlockSpec((1, lkp, d), lambda b_: (b_, 0, 0)),
                pl.BlockSpec((1, lqp, d), lambda b_: (b_, 0, 0)),
                pl.BlockSpec((1, 1, lqp), lambda b_: (b_, 0, 0)),
                pl.BlockSpec((1, 1, lqp), lambda b_: (b_, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, lqp, d), lambda b_: (b_, 0, 0)),
                pl.BlockSpec((1, lkp, d), lambda b_: (b_, 0, 0)),
                pl.BlockSpec((1, lkp, d), lambda b_: (b_, 0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, lqp, d), jnp.float32),
                jax.ShapeDtypeStruct(kf.shape, k.dtype),
                jax.ShapeDtypeStruct(vf.shape, v.dtype),
            ],
            interpret=interpret,
        )(qf, kf, vf, gf, lsef, deltaf)
        dq = dq.astype(q.dtype)
        dq = dq.reshape(b, h, lqp, d)[:, :, :lq, :]
        dk = dk.reshape(b, h, lkp, d)[:, :, :lk, :]
        dv = dv.reshape(b, h, lkp, d)[:, :, :lk, :]
        return dq, dk, dv

    if lqp <= FUSED_STREAM_MAX_L and lkp <= FUSED_STREAM_MAX_L and d <= 128:
        dq, dk, dv = pl.pallas_call(
            functools.partial(
                _kv_sweep_kernel, scale=scale, causal=causal,
                block_q=block_q, kv_len=kv_len, window=window, fused=True,
            ),
            grid=(bh, nk),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),   # Q in HBM, streamed
                pl.BlockSpec((1, block_k, d), lambda b_, i: (b_, i, 0)),
                pl.BlockSpec((1, block_k, d), lambda b_, i: (b_, i, 0)),
                pl.BlockSpec(memory_space=pl.ANY),   # dO in HBM, streamed
                pl.BlockSpec((1, 1, lqp), lambda b_, i: (b_, 0, 0)),
                pl.BlockSpec((1, 1, lqp), lambda b_, i: (b_, 0, 0)),
            ],
            out_specs=[
                # constant index along the kv dim: VMEM-resident across the
                # revisits, flushed when b_ advances — the dq accumulator
                pl.BlockSpec((1, lqp, d), lambda b_, i: (b_, 0, 0)),
                pl.BlockSpec((1, block_k, d), lambda b_, i: (b_, i, 0)),
                pl.BlockSpec((1, block_k, d), lambda b_, i: (b_, i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, lqp, d), jnp.float32),
                jax.ShapeDtypeStruct(kf.shape, k.dtype),
                jax.ShapeDtypeStruct(vf.shape, v.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((2, block_q, d), q.dtype),
                pltpu.VMEM((2, block_q, d), g.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
            interpret=interpret,
        )(qf, kf, vf, gf, lsef, deltaf)
        dq = dq.astype(q.dtype).reshape(b, h, lqp, d)[:, :, :lq, :]
        dk = dk.reshape(b, h, lkp, d)[:, :, :lk, :]
        dv = dv.reshape(b, h, lkp, d)[:, :, :lk, :]
        return dq, dk, dv

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_k=block_k, kv_len=kv_len, window=window),
        grid=(bh, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b_, i: (b_, i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),   # K in HBM
            pl.BlockSpec(memory_space=pl.ANY),   # V in HBM
            pl.BlockSpec((1, block_q, d), lambda b_, i: (b_, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b_, i: (b_, 0, i)),
            pl.BlockSpec((1, 1, block_q), lambda b_, i: (b_, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b_, i: (b_, i, 0)),
        out_shape=jax.ShapeDtypeStruct(qf.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((2, block_k, d), k.dtype),
            pltpu.VMEM((2, block_k, d), v.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
        interpret=interpret,
    )(qf, kf, vf, gf, lsef, deltaf)

    dk, dv = pl.pallas_call(
        functools.partial(_kv_sweep_kernel, scale=scale, causal=causal,
                          block_q=block_q, window=window),
        grid=(bh, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),   # Q in HBM
            pl.BlockSpec((1, block_k, d), lambda b_, i: (b_, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b_, i: (b_, i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),   # dO in HBM
            pl.BlockSpec((1, 1, lqp), lambda b_, i: (b_, 0, 0)),  # lse (tiny)
            pl.BlockSpec((1, 1, lqp), lambda b_, i: (b_, 0, 0)),  # delta (tiny)
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b_, i: (b_, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b_, i: (b_, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(kf.shape, k.dtype),
            jax.ShapeDtypeStruct(vf.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, block_q, d), q.dtype),
            pltpu.VMEM((2, block_q, d), g.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
        interpret=interpret,
    )(qf, kf, vf, gf, lsef, deltaf)

    dq = dq.reshape(b, h, lqp, d)[:, :, :lq, :]
    dk = dk.reshape(b, h, lkp, d)[:, :, :lk, :]
    dv = dv.reshape(b, h, lkp, d)[:, :, :lk, :]
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention_with_lse(q, k, v, causal=True, scale=None, window=None):
    """Flash attention that also returns the per-row logsumexp, [B, H, L, D]
    layout -> (out [B,H,L,D], lse [B,H,L] f32).

    The lse output is differentiable (the backward folds its cotangent into
    the delta residual), which is what makes flash blocks composable: a
    caller can merge partial results from disjoint KV shards as
    ``logaddexp``-weighted sums — ring attention does exactly that — and
    autodiff still produces exact gradients. No fallback: callers must check
    ``flash_supported`` (ring attention does)."""
    _validate_window(causal, window)
    return _flash_fwd(q, k, v, causal, scale, interpret=not _on_tpu(),
                      window=window)


def _lse_vjp_fwd(q, k, v, causal, scale, window):
    out, lse = _flash_fwd(q, k, v, causal, scale, interpret=not _on_tpu(),
                          window=window)
    # name the residuals the backward actually consumes so a remat policy
    # (models.transformer remat_policy="attn") can pin them: with out+lse
    # saved, the rematerialized backward's recompute of this forward is
    # dead code (all its outputs are known) and the flash kernel runs once
    # per step instead of twice
    out = checkpoint_name(out, "attn_out")
    lse = checkpoint_name(lse, "attn_lse")
    return (out, lse), (q, k, v, out, lse)


def _lse_vjp_bwd(causal, scale, window, res, g):
    q, k, v, o, lse = res
    g_out, g_lse = g
    return _flash_bwd(
        q, k, v, o, lse, g_out, causal, scale,
        interpret=not _on_tpu(), g_lse=g_lse, window=window,
    )


flash_attention_with_lse.defvjp(_lse_vjp_fwd, _lse_vjp_bwd)


def flash_supported(q: jax.Array) -> bool:
    """Support envelope of the Pallas kernels, [B, H, L, D] layout: head
    dims the kernels have been compiled for AND checked against
    ``reference_attention`` on a chip — multiples of the 128-wide lane
    tiling, and 64 (the head size of the small published Llamas), which
    the installed Mosaic compiles to a half-filled lane tile
    (chip_smoke.py's kernels phase; tests/test_chip_compile.py guards the
    compile). Ragged lengths are handled in-kernel (padded KV columns
    masked, padded Q rows zeroed via the lse residual)."""
    d = q.shape[-1]
    return d == 64 or d % 128 == 0


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    scale: float | None = None,
    window: int | None = None,
) -> jax.Array:
    """Fused attention, [B, H, L, D] layout. Pallas-compiled on TPU,
    interpreted elsewhere; flash backward (O(block) memory both ways).

    ``window`` enables sliding-window (local) attention: each position
    attends to its last `window` positions inclusive; block pruning skips
    the DMA and compute of everything outside the band, so cost becomes
    O(L * window) instead of O(L^2). Requires causal=True.

    Shapes outside the kernel envelope (see flash_supported) fall back to
    naive XLA attention — full L x L scores, O(L^2) memory — with a one-time
    warning, since at long context that is a real memory cliff."""
    _validate_window(causal, window)
    tiling_ok = not _on_tpu() or flash_supported(q)  # interpret: no tiling
    if not tiling_ok:
        warnings.warn(
            f"flash_attention: shape q={q.shape} causal={causal} is outside "
            "the Pallas kernel envelope (head_dim 64 or a multiple of 128); "
            "falling back to "
            "naive XLA attention with full L x L scores — expect O(L^2) "
            "memory",
            stacklevel=2,
        )
        out = reference_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=causal, scale=scale,
            window=window,
        )
        return out.transpose(0, 2, 1, 3)
    # single custom_vjp path; the unused lse cotangent arrives as zeros and
    # costs one elementwise subtract in the backward
    return flash_attention_with_lse(q, k, v, causal, scale, window)[0]


def attention_blhd(
    q: jax.Array, k: jax.Array, v: jax.Array,
    causal: bool = True, scale: float | None = None,
    window: int | None = None,
) -> jax.Array:
    """Convenience wrapper for the [B, L, H, D] model layout."""
    out = flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=causal, scale=scale, window=window,
    )
    return out.transpose(0, 2, 1, 3)


def chunked_reference_attention(q, k, v, causal=True, q_block: int = 512):
    """The strongest long-context attention plain XLA can offer without a
    fused kernel: queries processed in blocks (lax.map) with jax.checkpoint
    on the per-block body, so neither forward nor backward materializes the
    [L, L] score matrix — only per-block [B, H, bq, L] scores, recomputed
    in the backward. The materializing `reference_attention` is
    uncompilable at L=16k on a 16GB chip (its L x L f32 residuals exceed
    HBM); this is the honest XLA baseline the flash kernel is benchmarked
    against there (bench_transformer.py), and a usable fallback for
    platforms without Pallas. q/k/v: [B, H, L, D]."""
    b, h, L, d = q.shape
    nb = L // q_block
    if nb * q_block != L:
        raise ValueError(f"L={L} not divisible by q_block={q_block}")
    scale = d ** -0.5

    @jax.checkpoint
    def block(qb, offset):
        s = jnp.einsum(
            "bhqd,bhkd->bhqk", qb, k, preferred_element_type=jnp.float32
        ) * scale
        if causal:
            qpos = offset + jnp.arange(L // nb)
            mask = jnp.arange(L)[None, :] <= qpos[:, None]
            s = jnp.where(mask[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v)

    qb = q.reshape(b, h, nb, q_block, d).transpose(2, 0, 1, 3, 4)
    offs = jnp.arange(nb) * q_block
    out = jax.lax.map(lambda args: block(*args), (qb, offs))
    return out.transpose(1, 2, 0, 3, 4).reshape(b, h, L, d)


__all__ = [
    "flash_attention", "flash_attention_with_lse", "flash_supported",
    "attention_blhd", "reference_attention", "chunked_reference_attention",
]
