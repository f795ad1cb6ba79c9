"""Continuous batching: a slot-pool server over the static KV cache.

``generate()`` serves one fixed batch to completion — fine for offline
eval, wrong for a live service where requests arrive at different times
with different lengths: the batch drains to its slowest row while finished
rows' cache slots sit idle. This module is the TPU-first re-design of the
reference's only long-lived-service story (the notebook path it proxies,
tony-cli/.../NotebookSubmitter.java:71-133 + tony-proxy/.../ProxyServer
.java:27-39 — TonY keeps a service alive and routes to it; it has no model
layer, so WHAT to serve is this framework's capability extension).

Design — everything stays one compiled program over static shapes:

- **Fixed slot pool, ring-aligned.** The KV cache is allocated once as
  [layers, S, kvH, max_len, D] for S slots; ``cache.length`` is a [S]
  VECTOR of logical lengths. Each slot's buffer is a RING: logical
  position p lives at index (p + offset_slot) mod max_len, with the
  offset chosen at admission so that every active slot's NEXT write
  lands at one shared global cursor index. The decode K/V write is then
  the same cheap shared-offset dynamic_update_slice the lockstep
  generate() path uses — per-row-offset writes lower to TPU scatters
  that cost more than the whole step — and only the attention pays the
  index→logical remap (a latent layer's rows, laid out positions-minor
  on the TPU, are written there as whole lane tiles by one Pallas call,
  ``write_latent_rows``, where the decode kernel engages;
  ``latent_rows_in_place`` on the bookkeep span counts them). Active
  rows advance one position per step exactly as the cursor does, so a
  live row never wraps onto its own data. No tensor ever changes shape
  when requests come and go.
- **One decode step for all slots.** Every block runs ``block_size``
  single-token steps for ALL S slots under one jit (a lax.scan) — active
  or not. Inactive slots compute garbage that is never read: masking rows
  would need dynamic shapes, and a masked row costs the same HBM stream
  the active rows already pay (decode is weight-bound; the weight read is
  shared). The cache read is NOT shared: on a TPU with a ring of 4096 or
  more the step's attention is ``flash_decode`` (ops/decode_attention.py;
  ``flash_decode_latent`` for a latent layer's rows), which takes each
  slot's (length, offset, active) and streams only the KV blocks that
  hold a position the slot's query may see — nothing for an inactive
  slot. Per-row EOS/budget masks freeze finished rows' lengths
  in-device so a row that stops mid-block stays exactly where it stopped.
- **Chunked prefill into a slot's ring, one dispatch per chunk round.**
  A new request's prompt (all but its last token) is fed through the
  cached-attention path in fixed-size chunks that scatter K/V at the
  slot's ring indices — other slots are untouched, nothing recompiles
  for a new prompt length, and the padded tail's writes are DROPPED
  (out-of-bounds indices + mode="drop"; wrapping them would corrupt the
  slot's own earliest positions). The final chunk also commits the
  slot's decode state (fed token, active, budget, offset) in the same
  dispatch. The prompt's LAST token is not prefilled: it becomes the
  slot's first fed token, so the first sampled token falls out of the
  normal decode step with no special logits plumbing.
- **Tensor-parallel serving, same scheduler.** Construct with a
  ``prepare_decode(..., mesh=...)`` bundle (or ``mesh=`` directly) and
  every dispatched program runs under GSPMD: the slot-pool KV cache
  shards over ("batch", "kv") by the logical-axis rule table — slots
  over the batch axes (slots must divide them), kv heads over the
  tensor axes — and the per-slot state vectors shard over the batch
  axes, so a model bigger than one chip's HBM serves live traffic. What
  replicates: weights' norm/embed rows per the rule table, the PRNG key,
  and every scalar (cursor, chunk starts). The ring write stays the
  shared-cursor dynamic_update_slice: one scalar cursor means the
  update spans the FULL (sharded) slot and kv-head dims at one
  replicated M index, which GSPMD partitions without any cross-device
  traffic — per-row-offset writes would lower to per-shard scatters
  exactly as they would single-device. Attention keeps the einsum
  formulation under a mesh (the kernel gate already requires
  ``shardings is None``). Greedy completions are token-identical to the
  single-device server (tested at f32; at bf16 the TP psum's different
  reduction order can flip a greedy near-tie, exactly as on generate's
  TP path).
- **One admission program, whatever the burst.** `_admit` collects the
  whole burst of admissible (slot, request) pairs — all ring offsets
  derive from the same cursor, so batching changes no layout decision —
  and dispatches ONE `_prefill_batch` program per chunk round (rows
  padded to a power of two; finished/padding rows write nowhere via
  out-of-bounds indices + mode="drop"); a burst of one is the same
  program at one row (on the chip within 0.5% of the one-slot program
  it replaced: PERF.md, PR 31). A burst of K arrivals costs max-chunks
  dispatches instead of sum-of-chunks, so no serial dispatch train
  stalls the next decode block behind a burst. The trade is garbage
  FLOPs for the padded rows. K rows leave exactly the state K
  successive one-row dispatches would (tested).
- **Chunk-aligned prefix cache: shared prompts prefill once.** Real
  traffic is dominated by shared prefixes (system prompts, few-shot
  templates, multi-turn histories); ``prefix_cache_blocks=N`` keeps a
  host-managed TRIE keyed on ``prefill_chunk``-sized token blocks whose
  nodes own KV blocks in a device-resident shared pool (separate from
  the slot rings; same ("batch", "kv") sharding rule, blocks where slots
  sit). Admission walks the trie for the longest cached chunk-aligned
  prefix, copies its blocks into the slot ring with ONE batched
  gather/scatter program per admission burst (ring-wrap handled by the
  same mod-M indexing prefill uses), then prefills only the suffix; the
  request's own new full chunks are gathered back into fresh pool blocks
  in one more program, dispatched at ADMISSION time — right after the
  suffix prefill, before any decode block — because a frozen slot's ring
  keeps taking the shared-cursor garbage write, so by the time a
  completion is *processed* the prompt body may already be overwritten
  (insert-at-admission is also what lets the next burst hit a template
  the previous burst introduced). Nodes are ref-counted while an
  admitted request holds its matched path (admission -> processed
  completion) and unreferenced LEAVES are LRU-evicted when the block
  budget is exhausted — interior nodes are unreachable without their
  ancestors, so eviction peels the trie from the leaves and can never
  orphan a reachable block. KV at position p depends only on tokens
  <= p, so a cached block is bit-identical to what the cold prefill
  would have written — including int8: the pool stores the QUANTIZED
  values + scales, hit and cold paths read the same bytes, completions
  are token-identical either way (tested; lookups within one admission
  burst see the trie as of the burst start, so two same-template
  requests admitted together both prefill — the second burst hits).
- **A second kind of slot state: linear layers' recurrent state.** A
  config with ``layer_kinds`` (models/transformer.py) mixes
  full-attention layers with gated-delta-rule layers, which keep no K/V:
  a slot holds for each a float32 state and the convolution's last
  inputs (``KVCache.state`` / ``.conv``), and the K/V rings exist for
  the full layers only. The two kinds of state part ways in two places.
  Admission (`_rows_forward`) starts a row from zeros where its chunk
  starts at position 0, so a reused slot needs no clearing, lets only a
  chunk's valid positions advance state and tail, and carries them
  between the rounds of a multi-chunk prompt. The decode block advances
  a row's state only where the row is ``active``: K/V tolerates an idle
  row's garbage write because it lands beyond the row's length, and a
  state has no "beyond". Journal replay re-prefills from tokens and is
  unchanged. What would need a state it cannot get yet (prefix trie,
  paged pool, speculation, KV handoff, a mesh, an int8 ring) is refused
  at construction. The decode block reports which rows' state it changed
  (one more column of its packed result, read off the first linear
  layer's state before and after the block): the counter ``state_rows``,
  which rides the bookkeep span, is the device's own account of the mask.
  Where ``state_kernel_engages`` (one chip, the TPU) a decode step's
  recurrence is ``gated_delta_decode`` on the cache's whole stack: the
  live rows' tiles read and written once, in place, a frozen row's not
  touched; ``state_rows_read`` / ``state_rows_held`` (same span) say how
  many of the slots' states a processed block's last step streams.
- **The device never waits on the host.** Per-slot state vectors
  (tokens/active/lengths) are DEVICE-carried: block N+1 consumes block
  N's output arrays without the host seeing them. Without stop tokens
  every completion is deterministic, so the host schedules OPEN-LOOP
  from an exact model — zero mid-run syncs, one packed transfer at the
  end (a device→host transfer is a sync point whatever its size;
  dispatches pipeline freely). With
  stop tokens, blocks sync in single-transfer bursts behind a
  ``pipeline_depth`` lag, and each block's admissions are logged against
  it so the lagging bookkeeping replays them in order — bounded slot
  idleness, never wrong output.

Exactness: a request's greedy tokens equal a solo ``generate()`` run —
same forward, same cache layout, same masks (tested, tests/test_serving
.py). kv_dtype/weight_dtype wire through identically, but their
server-vs-solo agreement is within quantization tolerance rather than
bit-exact: serving chunk-prefills the prompt body through the QUANTIZED
cache (and raw prefill weights) where generate's true prefill attends
raw K/V (and the w8-fused weights) — a near-tie at int8 resolution can
flip a greedy token. Measured
(PERF.json continuous_batching): 1.08-1.25x the strongest static
batching generate() supports on a mixed-length workload, wall-clock
with all scheduling included.
"""

from __future__ import annotations

import base64
import collections
import functools
import hashlib
import itertools
import logging
import math
import os
import random
import signal
import time
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import constants as c
from ..events.journal import RequestJournal
from ..observability import (
    PHASE_ADMIT,
    PHASE_BOOKKEEP,
    PHASE_DISPATCH,
    PHASE_SYNC,
    DispatchTracker,
    Histogram,
    RequestTrace,
    ServiceRateEstimator,
    ServingTelemetry,
    TraceContext,
    phase,
    register_program,
)
from .registry import ModelEntry, ModelRegistry

log = logging.getLogger(__name__)

# What a DELIVERED Completion.finish_reason can say. "stop"/"length" are
# the natural endings (trace terminal "finished"); "cancelled"/"expired"
# are early exits that still build a Completion (empty or partial
# tokens); "shed" is a QUEUED batch-tier request displaced by an
# interactive arrival under queue pressure (empty Completion — the
# request never reached a slot; a shed at submit() still raises
# QueueFullError with no Completion); "prefilled" is a prefill-role
# replica's terminal (disaggregated serving): the KV is computed and
# exported, decode happens on another replica after ``import_blocks``.
COMPLETION_FINISH_REASONS = ("stop", "length", "cancelled", "expired",
                             "shed", "prefilled")
# The full trace-level finish_reason vocabulary adds "failed" (in-flight
# state lost with no replay — ServingLoopError / HTTP 503), which
# terminates a request's TRACE without ever building a Completion.
# Pinned against code, docstrings, docs/serving.md, and the router's
# HTTP mapping by tests/test_observability.py's finish-reason lint.
FINISH_REASONS = COMPLETION_FINISH_REASONS + ("failed",)

# Engine-level admission tiers, best first. "interactive" is the
# latency-sensitive default; "batch" is sheddable throughput work that
# 429s at a LOWER queue threshold (``batch_queue_frac``) and, under a
# full queue, is displaced by interactive arrivals (finish_reason
# "shed"). In paged-KV mode each class can also carry a block budget
# (``class_budgets``) so batch prefills cannot starve interactive
# admissions of pool blocks.
PRIORITY_CLASSES = ("interactive", "batch")

# per-request logprobs cap: one compiled decode-block variant carries
# this many top entries whenever ANY busy slot asked for logprobs (a
# per-request k would compile a program per distinct k; requests just
# slice down to what they asked for)
LOGPROBS_MAX = 8


def _normalize_stop(stop) -> list[tuple[int, ...]]:
    """Validate/normalize Request.stop: a list of token-id sequences
    (a flat int list reads as ONE sequence). Raises ValueError on
    empty sequences or non-ints."""
    if not isinstance(stop, (list, tuple)) or not stop:
        raise ValueError("stop must be a non-empty list")
    if all(isinstance(t, (int, np.integer)) for t in stop):
        stop = [stop]
    out = []
    for seq in stop:
        if not isinstance(seq, (list, tuple)) or not seq:
            raise ValueError("each stop sequence must be a non-empty "
                             "list of token ids")
        out.append(tuple(int(t) for t in seq))
    if len(out) > 16:
        raise ValueError("at most 16 stop sequences per request")
    return out


def _stop_match_end(tokens, stop_seqs, start: int = 0) -> int | None:
    """Earliest end index (exclusive) of a stop-sequence match that
    ENDS after ``start`` — tokens before ``start`` were already
    delivered/journaled and are never retracted, but a match may BEGIN
    inside them (sequences span block boundaries). None = no match."""
    best = None
    n = len(tokens)
    for seq in stop_seqs or ():
        m = len(seq)
        if m == 0 or n < m:
            continue
        lo = max(0, start - m + 1)
        for i in range(lo, n - m + 1):
            end = i + m
            if end <= start:
                continue
            if tuple(int(t) for t in tokens[i:end]) == tuple(seq):
                if best is None or end < best:
                    best = end
                break       # earliest match of THIS sequence found
    return best

from .generate import (
    NEG_INF,
    DecodeShardings,
    DecodeWeights,
    KVCache,
    PrefixPool,
    _cached_attention,
    _cast_decode_params,
    _decode_shardings,
    _forward_with_cache,
    _fuse_decode_weights,
    _latent_attention,
    _rule_size,
    _store_kv,
    _validate_decode_mesh,
    decode_kernel_engages,
    init_cache,
    init_prefix_pool,
    moe_dropfree,
    prepare_decode,
    sample_token,
    state_kernel_engages,
    window_ring,
    window_view,
)
from ..ops.decode_attention import kv_block_k, live_kv_blocks
from ..ops.gated_delta import live_state_rows
from ..parallel.routed_experts import expert_load
from .transformer import TransformerConfig
from . import transformer


@dataclass
class Request:
    """One generation request. ``prompt`` is a token-id sequence (>= 1
    token); ``max_new_tokens`` bounds the emission; stop tokens end it
    early (the stop token itself is included in the output, matching
    generate()). ``temperature`` and ``top_k`` override the server
    defaults per request (temperature 0 = greedy, top_k 0 = unfiltered) —
    sampling is per-row in the decode step, so greedy, sampled, and
    top-k-filtered requests share one pool. ``cache_prompt`` overrides
    the server's ``cache_prompts`` default: whether this prompt's body
    chunks are inserted into the prefix cache at admission (None = server
    default; lookups always run when the cache is enabled).

    ``deadline`` is an absolute ``time.monotonic()`` instant: a request
    still QUEUED past its deadline is never admitted — it completes with
    finish_reason "expired" instead of burning prefill+decode for a
    client that already gave up. (A request already decoding is stopped
    via ``SlotServer.cancel``, the caller's job — the server cannot know
    the waiter left.) None = no deadline.

    ``resume_tokens`` teacher-forces an already-emitted prefix: the
    server admits with effective context ``prompt + resume_tokens``
    (riding the normal chunked-prefill path, prefix-cache eligible),
    resumes decoding with the remaining ``max_new_tokens -
    len(resume_tokens)`` budget, and the delivered Completion's tokens
    are ``resume_tokens`` + the continuation — for a greedy request,
    byte-identical to the uninterrupted stream. This is the replay
    primitive behind ``SlotServer.reset()`` recovery, ``serve`` journal
    recovery, and the router's mid-request failover (docs/serving.md
    "Request durability & replay"). A prefix that already satisfies the
    request (budget reached, or it ends in a stop token) completes
    immediately without taking a slot.

    ``stop`` is a per-request list of stop SEQUENCES (token-id lists; a
    flat int list reads as one sequence): the emission ends at the
    first completed match, checked host-side at the processing instant
    — the matched sequence itself is included in the output (the
    engine's stop-token convention) and the device slot is freed like
    a cancel. Matches may span block boundaries and work in every mode
    (predictive, EOS, speculative); the journal is truncated at the
    match, so replay/failover/streaming never deliver past it. The
    server-wide ``stop_tokens`` stays the default and both apply
    independently.

    ``logprobs`` (0 = off, <= LOGPROBS_MAX) asks for the top-k
    log-probabilities of every emitted token, read off the SAME logits
    row the token was sampled from (no second forward). Rejected under
    speculative serving (rejected drafts never existed host-side, so
    per-token logits rows don't either). A replayed request's
    teacher-forced prefix carries ``None`` placeholders — those
    positions were prefilled, not decoded, by this process.

    ``routes`` (a config with routed expert layers only) asks for the
    experts every consumed position chose in every routed layer, from the
    prefill and the decode steps alike: ``Completion.routes``. What a
    checker needs to redo the forward with the server's own selection
    (a near-tie between the k-th and the next expert flips on rounding,
    and a reference left to its own routing then computes another layer)."""
    prompt: Any
    max_new_tokens: int
    temperature: float | None = None
    top_k: int | None = None
    cache_prompt: bool | None = None
    deadline: float | None = None
    resume_tokens: list | None = None
    stop: list | None = None
    logprobs: int = 0
    routes: bool = False
    # multi-model serving: which registry entry should serve this
    # request. The engine itself is single-model (the ServeApp routes
    # by name to the right engine); the field rides the Request so the
    # HTTP payload's model= survives into traces and the journal.
    model: str | None = None
    # admission tier ("interactive" | "batch"). The batch tier is the
    # engine's load-shed buffer: it sheds at a LOWER queue threshold,
    # a full queue displaces its youngest queued batch request to seat
    # an interactive one, and (paged mode) its concurrent KV blocks
    # are capped by its class budget — the engine-side counterpart of
    # the driver's ResourceArbiter tiers (autoscale.py).
    priority: str = "interactive"
    # distributed-trace identity (observability.TraceContext, or its
    # as_dict() form): minted/adopted at the HTTP layer and attached to
    # the lifecycle trace + journal entry at submit, so replays,
    # journal recovery, and disagg handoffs stay in the originating
    # trace. None = untraced (direct engine use, test stubs).
    trace: Any = None
    id: int = field(default_factory=itertools.count().__next__)


@dataclass
class Completion:
    id: int
    tokens: list[int]
    finish_reason: str    # one of COMPLETION_FINISH_REASONS:
    #                       "stop" | "length" | "cancelled" | "expired" |
    #                       "shed" (a queued batch-tier request displaced
    #                       by an interactive arrival; empty tokens).
    #                       Failed requests never build a Completion —
    #                       see FINISH_REASONS.
    # the request's lifecycle trace (observability.RequestTrace.to_dict():
    # host-monotonic span events + attrs) — None only for engines that
    # don't record traces (test stubs)
    trace: dict | None = None
    # per-emitted-token log-probabilities (Request.logprobs > 0): one
    # {"token", "logprob", "top": [[ids], [logprobs]]} per token, in
    # stream order; teacher-forced resume positions carry logprob=None
    logprobs: list | None = None
    # the experts chosen (Request.routes): int32 [positions, routed
    # layers, k] for the positions the request consumed, in order: its
    # prompt and every emitted token but the last (never fed)
    routes: Any = None


class QueueFullError(RuntimeError):
    """Admission refused: the wait queue is at ``max_queue``. The shed
    request was never accepted — the caller should surface backpressure
    (HTTP 429 + Retry-After) rather than let an unbounded queue push
    every admitted request's latency past its deadline."""


@dataclass
class _Admission:
    """One (slot, request) pair of an admission burst, with the layout
    decisions made at collection time: ring offset, budget target,
    sampling overrides, the chunk-aligned cached-prefix length (0 when
    the prefix cache is off or missed) and the matched trie path, and
    the suffix chunk starts the prefill programs will feed."""
    slot: int
    req: Request
    body: np.ndarray
    offset: int
    target: int
    temp: float
    topk: int
    chunk_starts: list
    last: int = 0               # the first fed token: full context's last
    prefix_len: int = 0
    hit_path: list = field(default_factory=list)


def routed_columns(cfg: TransformerConfig, block: int) -> int:
    """Columns a config's routed expert layers add to a decode block's
    packed result (`_decode_block`): the experts chosen, then three
    counts. 0 without such layers."""
    if not cfg.n_routed_layers:
        return 0
    return block * cfg.n_routed_layers * cfg.moe_top_k + 3


def _pow2_rows(n: int) -> int:
    """Rows of a batched program, padded to the next power of two so the
    compiled widths stay O(log slots)."""
    return 1 << (n - 1).bit_length()


def _constrain_pool(shardings, cache, *vecs):
    """Pin the slot pool's carried state to its mesh layout at a jitted
    program's boundary: KV buffers over ("batch", "kv"), scale buffers
    alongside, and every per-slot [S] vector over the batch axes. Without
    the output constraint GSPMD is free to replicate a program's results,
    and the donated buffers would bounce layouts between dispatches."""
    if shardings is None:
        return (cache, *vecs)
    c = lax.with_sharding_constraint
    cache = cache._replace(
        k=c(cache.k, shardings.cache), v=c(cache.v, shardings.cache),
        length=c(cache.length, shardings.act),
        k_scale=(None if cache.k_scale is None
                 else c(cache.k_scale, shardings.scale)),
        v_scale=(None if cache.v_scale is None
                 else c(cache.v_scale, shardings.scale)),
    )
    return (cache, *(c(v, shardings.act) for v in vecs))


class _PrefixNode:
    """One trie node = one ``prefill_chunk``-sized token block owning one
    pool block. ``refs`` counts admitted requests whose matched path runs
    through this node (held admission -> processed completion) plus a
    transient insert-ref protecting a just-allocated node until its
    gather program is dispatched; ``tick`` is the LRU clock."""
    __slots__ = ("children", "parent", "key", "block", "refs", "tick")

    def __init__(self, parent, key, block):
        self.children: dict[bytes, _PrefixNode] = {}
        self.parent = parent
        self.key = key
        self.block = block
        self.refs = 0
        self.tick = 0


class PrefixCache:
    """Host-side bookkeeping for the shared prefix pool: a trie keyed on
    chunk-sized token blocks + a block allocator with LRU eviction of
    unreferenced leaves. Pure host data structure (device programs are
    the SlotServer's job), so the ref-count/eviction contract is unit-
    testable without a model.

    Invariants:
    - every trie node owns exactly one pool block; free blocks are owned
      by nobody.
    - eviction only ever takes a LEAF with refs == 0 (an interior node's
      children are unreachable without it; a referenced node's block is
      aliased by an admitted slot's pending copy). ``alloc`` returns None
      when the budget is exhausted and nothing is evictable — callers
      skip insertion rather than fail.

    With ``allocator=`` (paged-KV mode) the trie stops owning a private
    free list: blocks come from the shared ``BlockAllocator`` and every
    trie node holds one allocator ref on its block. Sharing is
    copy-on-write with no writer — a block adopted into the trie is a
    fully-written prefill chunk that neither the donating slot nor any
    hit slot ever writes again — so "sharing" is just refcounts: the
    block frees when the LAST holder (trie node or slot table) unrefs.
    Eviction then only takes leaves whose block the trie SOLELY owns
    (allocator refcount 1): a block still in some slot's table must not
    be handed to a new writer mid-read. ``n_blocks`` stays as a soft cap
    on trie size so cached prefixes can't squat the whole pool.
    """

    def __init__(self, n_blocks: int, chunk: int, allocator=None):
        if n_blocks < 1:
            raise ValueError(f"prefix cache needs >= 1 block, got {n_blocks}")
        if chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        self.n_blocks = n_blocks
        self.chunk = chunk
        self.root = _PrefixNode(None, b"", -1)
        self._allocator = allocator
        self._free = ([] if allocator is not None
                      else list(range(n_blocks - 1, -1, -1)))
        self._owned: set[_PrefixNode] = set()
        self._tick = 0
        self.hits = 0           # admissions matching >= 1 chunk
        self.misses = 0         # admissions matching none
        self.evictions = 0
        self.inserted_blocks = 0

    @property
    def blocks_used(self) -> int:
        return len(self._owned)

    def _touch(self, node: _PrefixNode) -> None:
        self._tick += 1
        node.tick = self._tick

    def lookup(self, body: np.ndarray) -> list["_PrefixNode"]:
        """Longest cached chunk-aligned prefix of ``body`` -> the matched
        node path (block ids via node.block). Counts a hit/miss and
        touches the path's LRU clocks; does NOT take refs (acquire)."""
        node, path = self.root, []
        c = self.chunk
        for c0 in range(0, len(body) - c + 1, c):
            child = node.children.get(body[c0:c0 + c].tobytes())
            if child is None:
                break
            path.append(child)
            node = child
        for n in path:
            self._touch(n)
        if path:
            self.hits += 1
        else:
            self.misses += 1
        return path

    def acquire(self, path) -> None:
        for n in path:
            n.refs += 1

    def release(self, path) -> None:
        for n in path:
            n.refs -= 1
            assert n.refs >= 0, "prefix-cache ref underflow"

    def _evict_one(self) -> int | None:
        """Reclaim the least-recently-used unreferenced leaf's block.
        The trie's ref on the block transfers to the caller (reuse or
        ``reclaim``); blocks still shared with a live slot table
        (allocator refcount > 1) are skipped — handing one to a new
        writer would corrupt the reader's KV."""
        victim = None
        for node in self._owned:
            if node.children or node.refs > 0:
                continue
            if (self._allocator is not None
                    and self._allocator.refs[node.block] > 1):
                continue
            if victim is None or node.tick < victim.tick:
                victim = node
        if victim is None:
            return None
        del victim.parent.children[victim.key]
        self._owned.discard(victim)
        self.evictions += 1
        return victim.block

    def alloc(self) -> int | None:
        if self._allocator is not None:
            block = self._allocator.take()
            if block is not None:
                return block
            return self._evict_one()
        if self._free:
            return self._free.pop()
        return self._evict_one()

    def reclaim(self, n: int) -> int:
        """Paged mode: hand up to ``n`` blocks back to the shared
        allocator by evicting unreferenced sole-owner leaves. Called
        when a slot admission comes up short of pool blocks — cached
        prefixes are the reclaimable tier, in-flight tables are not."""
        assert self._allocator is not None, "reclaim needs an allocator"
        got = 0
        while got < n:
            block = self._evict_one()
            if block is None:
                break
            self._allocator.unref(block)
            got += 1
        return got

    def adopt(self, body: np.ndarray, blocks: dict) -> int:
        """Paged mode insert: record a slot's own freshly-prefilled
        blocks in the trie with ZERO device copies. ``blocks`` maps
        chunk index -> pool block id for the full chunk-aligned span the
        slot prefilled itself; each newly-created node takes an
        allocator ref, so the block is now shared between the slot's
        table and the trie and frees only when both let go. Existing
        nodes win (a burst-mate adopted the same chunk first); the walk
        stops at the soft cap or a gap. Returns the node count added."""
        assert self._allocator is not None, "adopt needs an allocator"
        node, adopted = self.root, 0
        c = self.chunk
        for c0 in range(0, len(body) - c + 1, c):
            key = body[c0:c0 + c].tobytes()
            child = node.children.get(key)
            if child is None:
                block = blocks.get(c0 // c)
                if block is None or len(self._owned) >= self.n_blocks:
                    break
                child = _PrefixNode(node, key, block)
                node.children[key] = child
                self._owned.add(child)
                self._allocator.ref(block)
                self.inserted_blocks += 1
                adopted += 1
            self._touch(child)
            node = child
        return adopted

    def insert(self, body: np.ndarray) -> list[tuple[int, "_PrefixNode"]]:
        """Add ``body``'s full chunks to the trie, reusing existing nodes
        (first writer wins — a burst-mate may have created them moments
        ago) and allocating blocks for new ones. Returns only the NEW
        (chunk_index, node) pairs (their blocks need the device gather);
        each node carries one insert-ref the caller must ``release``
        after dispatching it, so a later insert in the same burst can't
        evict a block whose gather hasn't been dispatched yet. Stops
        early (still a valid prefix chain) when the budget is
        exhausted."""
        node, created = self.root, []
        c = self.chunk
        for c0 in range(0, len(body) - c + 1, c):
            key = body[c0:c0 + c].tobytes()
            child = node.children.get(key)
            if child is None:
                block = self.alloc()
                if block is None:
                    break
                child = _PrefixNode(node, key, block)
                node.children[key] = child
                self._owned.add(child)
                child.refs = 1          # insert-ref, released post-dispatch
                created.append((c0 // c, child))
                self.inserted_blocks += 1
            self._touch(child)
            node = child
        return created


@functools.partial(
    jax.jit,
    static_argnames=("shardings",),
    donate_argnames=("cache",),
)
def _copy_prefix_blocks(pool, cache, slots, blocks, chunk_idx, offsets,
                        *, shardings: DecodeShardings | None = None):
    """Cache-hit path: scatter ``T`` pool blocks into their slots' rings —
    row t copies pool block ``blocks[t]`` to slot ``slots[t]``'s ring
    indices for logical positions [chunk_idx[t]*C, ..+C) (mod-M, so a
    prefix spanning the ring boundary wraps exactly as prefill's writes
    would). One dispatch per admission BURST: rows are padded to a power
    of two with OUT-OF-BOUNDS slot ids whose writes drop, same as
    `_prefill_batch`'s padding rows. Pure data movement — the copied
    bytes are exactly what the cold prefill wrote (int8 pools carry the
    quantized values + scales), so the hit path is token-identical."""
    C = pool.k.shape[3]
    m_cap = cache.k.shape[3]
    n_blocks = pool.k.shape[1]
    pos = chunk_idx[:, None] * C + jnp.arange(C)[None, :]       # [T, C]
    ring_idx = (offsets[:, None] + pos) % m_cap
    gb = jnp.minimum(blocks, n_blocks - 1)      # clamp pad rows for gather
    swr = dict(unique_indices=True, mode="drop")
    # gather [L, T, kvH, C(, D)] -> update layout [T, C, L, kvH(, D)]
    # (advanced indices at axes 1 and 3 are separated by the kvH slice,
    # so the broadcast dims lead)
    ck = cache.k.at[:, slots[:, None], :, ring_idx, :].set(
        pool.k[:, gb].transpose(1, 3, 0, 2, 4), **swr)
    cv = cache.v.at[:, slots[:, None], :, ring_idx, :].set(
        pool.v[:, gb].transpose(1, 3, 0, 2, 4), **swr)
    ks_buf, vs_buf = cache.k_scale, cache.v_scale
    if pool.k_scale is not None:
        ks_buf = ks_buf.at[:, slots[:, None], :, ring_idx].set(
            pool.k_scale[:, gb].transpose(1, 3, 0, 2), **swr)
        vs_buf = vs_buf.at[:, slots[:, None], :, ring_idx].set(
            pool.v_scale[:, gb].transpose(1, 3, 0, 2), **swr)
    cache = KVCache(k=ck, v=cv, length=cache.length,
                    k_scale=ks_buf, v_scale=vs_buf)
    # fence: a runtime-dependent scalar output the DispatchTracker can
    # block_until_ready — every REAL output here is donated into a later
    # dispatch within the same admission burst, whose donation deletes
    # the host handle before the reaper can touch it
    fence = jnp.sum(ring_idx).astype(jnp.int32)
    return _constrain_pool(shardings, cache)[0], fence


@functools.partial(
    jax.jit,
    static_argnames=("shardings",),
    donate_argnames=("pool",),
)
def _insert_prefix_blocks(pool, cache, slots, blocks, chunk_idx, offsets,
                          *, shardings: DecodeShardings | None = None):
    """Trie insertion's device half: gather ``T`` freshly-prefilled
    chunks out of their slots' rings into pool blocks — row t reads slot
    ``slots[t]``'s ring at logical [chunk_idx[t]*C, ..+C) into block
    ``blocks[t]``. Dispatched at admission immediately after the suffix
    prefill (before any decode block can lay garbage over a frozen
    ring); padding rows carry OUT-OF-BOUNDS block ids (writes drop) and
    clamped slot ids (gather garbage nobody keeps)."""
    C = pool.k.shape[3]
    m_cap = cache.k.shape[3]
    n_slots = cache.k.shape[1]
    pos = chunk_idx[:, None] * C + jnp.arange(C)[None, :]       # [T, C]
    ring_idx = (offsets[:, None] + pos) % m_cap
    gs = jnp.minimum(slots, n_slots - 1)
    swr = dict(unique_indices=True, mode="drop")
    # gather -> [T, C, L, kvH(, D)]; pool wants [L, T, kvH, C(, D)]
    pk = pool.k.at[:, blocks].set(
        cache.k[:, gs[:, None], :, ring_idx, :].transpose(2, 0, 3, 1, 4),
        **swr)
    pv = pool.v.at[:, blocks].set(
        cache.v[:, gs[:, None], :, ring_idx, :].transpose(2, 0, 3, 1, 4),
        **swr)
    pks, pvs = pool.k_scale, pool.v_scale
    if pks is not None:
        pks = pks.at[:, blocks].set(
            cache.k_scale[:, gs[:, None], :, ring_idx].transpose(2, 0, 3, 1),
            **swr)
        pvs = pvs.at[:, blocks].set(
            cache.v_scale[:, gs[:, None], :, ring_idx].transpose(2, 0, 3, 1),
            **swr)
    pool = PrefixPool(k=pk, v=pv, k_scale=pks, v_scale=pvs)
    if shardings is not None:
        c = lax.with_sharding_constraint
        pool = PrefixPool(
            k=c(pool.k, shardings.cache), v=c(pool.v, shardings.cache),
            k_scale=(None if pool.k_scale is None
                     else c(pool.k_scale, shardings.scale)),
            v_scale=(None if pool.v_scale is None
                     else c(pool.v_scale, shardings.scale)),
        )
    # dispatch-tracker fence (see _copy_prefix_blocks): the pool itself
    # is donated into the next burst's insert
    fence = jnp.sum(ring_idx).astype(jnp.int32)
    return pool, fence


# rows of a chunk round whose latent attention is taken at once
LATENT_ROWS_AT_ONCE = 8
# bytes of one row's float32 scores against its whole ring above which a
# chunk round's attention walks the ring's live key blocks instead
ROW_SCORES_BYTES = 256 << 20
# bytes of the float32 scores of the rows a chunk round's latent attention
# takes at once against their whole rings, above which it walks the rings'
# live blocks a row at a time instead (8 rows of a 4096-position ring at
# 32 heads hold 512 MiB and keep the einsum; 4 of a 12288-position ring
# walk)
LATENT_SCORES_BYTES = 512 << 20


def _rows_attention(cfg, q, ck, cv, starts, l, ks, vs, offsets, window=None):
    """`_cached_attention` of a chunk round's rows: q [K, L, H, D] at
    positions ``starts[r]..`` against their slots' rings [K, kvH, M, D].

    Its einsum scores every row against the whole ring, [K, H, L, M]
    float32: 64 MB a row at the widths of a few thousand positions and
    30-32 heads, which keep it, and a gigabyte a row at 128 heads over
    16k, whose softmax then holds several such arrays at once. Where a
    row's scores would pass ``ROW_SCORES_BYTES`` the rows go one at a time
    over the blocks of positions that hold a position
    some query of the chunk may see (``live_kv_blocks``: from the window's
    start or 0 to the chunk's last position; a block is ``kv_block_k``'s,
    the decode kernel's unit and the counters'), with an online softmax: the
    same mask on the same logical positions, in float32, a block's scores
    at a time, and only the live part of the ring read."""
    k_rows, _, h, d = q.shape
    m_cap = ck.shape[2]
    window = cfg.attn_window if window is None else window
    if h * l * m_cap * 4 <= ROW_SCORES_BYTES or ks is not None:
        # the einsum, also for a draft's single-token steps: the rows are
        # a slice of the cache, which as a Pallas operand would be a copy
        return _cached_attention(cfg, q, ck, cv, starts, l, ks, vs,
                                 ring_offsets=offsets, allow_kernel=False,
                                 window=window)
    return _rows_walk(cfg, q, ck, cv, starts, l, offsets, window,
                      q.shape[-1] ** -0.5)


def _rows_walk(cfg, q, ck, cv, starts, l, offsets, window, scale):
    """`_rows_attention`'s walk: the rows one at a time over the blocks of
    their rings that hold a position some query of the chunk may see,
    with an online softmax in float32, the scores times ``scale``. Also
    the latent admission's (``cv`` is then ``ck``: the rows are their own
    values)."""
    k_rows, _, h, d = q.shape
    m_cap = ck.shape[2]
    f32, kvh = jnp.float32, ck.shape[1]
    rep = h // kvh
    bk = math.gcd(m_cap, kv_block_k(m_cap, kvh, d, ck.dtype.itemsize))
    n_blocks = m_cap // bk
    first, count = live_kv_blocks(
        offsets, starts + l - 1, jnp.ones((k_rows,), bool), block_k=bk,
        m_cap=m_cap, window=window + l - 1 if window else 0, xp=jnp)

    def row(t):
        q, ck, cv, start, offset, first, count = t
        qg = q.reshape(l, kvh, rep, d).transpose(1, 2, 0, 3)    # [g, r, L, D]
        q_pos = start + jnp.arange(l)

        def block(j, carry):
            m, total, acc = carry
            blk = (first + j) % n_blocks
            k, v = (lax.dynamic_slice_in_dim(c, blk * bk, bk, axis=1)
                    for c in (ck, cv))
            s = jnp.einsum("grld,gkd->grlk", qg, k.astype(cfg.dtype),
                           preferred_element_type=f32) * scale
            pos = (blk * bk + jnp.arange(bk) - offset) % m_cap
            ok = pos[None, :] <= q_pos[:, None]
            if window:
                ok &= pos[None, :] > q_pos[:, None] - window
            s = jnp.where(ok, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.where(ok, jnp.exp(s - m_new[..., None]), 0.0)
            corr = jnp.exp(m - m_new)
            acc = acc * corr[..., None] + jnp.einsum(
                "grlk,gkd->grld", p.astype(cfg.dtype), v.astype(cfg.dtype),
                preferred_element_type=f32)
            return m_new, total * corr + jnp.sum(p, axis=-1), acc

        m, total, acc = lax.fori_loop(0, count, block, (
            jnp.full((kvh, rep, l), NEG_INF, f32),
            jnp.zeros((kvh, rep, l), f32), jnp.zeros((kvh, rep, l, d), f32)))
        # a pad position may see nothing: zeros, as for an idle decode row
        o = acc / jnp.where(total > 0, total, 1.0)[..., None]
        return o.transpose(2, 0, 1, 3).reshape(l, h, d).astype(q.dtype)

    return lax.map(row, (q, ck, cv, starts, offsets, first, count))


def _rows_forward(params, cfg, tokens, bufs, rows, starts, offsets, write_ok):
    """The one multi-token, per-row-position forward of the serving
    programs: ``tokens`` [K, L] run through the stack at logical positions
    ``starts[r]..starts[r]+L-1``, row r against slot ``rows[r]``'s ring —
    the forward the shared-cursor decode step deliberately avoids
    (per-row-offset writes lower to scatters), paid once per admission
    chunk round (`_prefill_batch`) or speculative round (`_spec_block`).

    ``bufs`` = the cache's (k, v, k_scale, v_scale) buffers [layers, S,
    kvH, M(, D)], followed by its (state, conv) buffers where the config
    has linear layers, by its latent buffer [layers, S, M, R] where it
    has latent ones and by its (win_k, win_v) where it has window ones
    (below). A slot's buffer is a RING: logical
    position p lives at index (p + offsets[r]) mod M, and each layer's
    K/V scatter there where ``write_ok`` [K, L] holds. Every other position — a chunk's pad tail, a
    row with nothing to write, a window overhanging the row's budget — gets
    a distinct OUT-OF-BOUNDS index and mode="drop": written nowhere at all.
    (Wrapping them with the mod would land them on the slot's own EARLIEST
    positions, which the mask legitimately reads.) So does every write of a
    row whose ``rows[r]`` is out of bounds (padding rows); such a row reads
    through the clamped gather and computes garbage that touches nothing,
    exactly like an inactive decode row. ``rows=None``: row r IS slot r,
    all S of them, and the attention reads the buffers as they stand, no
    gather. Attention reads only the row's own slot (the per-row-vector
    cache_len + ring_offsets branch of `_cached_attention`), so rows never
    disturb one another or a decoding slot.

    A linear layer (``cfg.layer_kinds``) runs `transformer.linear_mixer`
    a row from the slot's stored state and convolution tail, or from zeros
    where ``starts[r] == 0`` (an admission's first chunk: a slot reused
    after a completion starts clean); only the positions ``write_ok`` holds
    (a prefix of the row) advance them, so a chunk's pad tail leaves no
    trace and a multi-chunk prompt carries its state from round to round;
    a padding row's state goes nowhere (mode="drop").

    A window layer scatters its K/V into its own ring of R positions, at
    (p + offsets[r]) mod R under the same rule, and attends over its
    ``win_size`` positions with the ring seen through `window_view`.

    A round whose rows' scores against the whole ring would pass
    ``ROW_SCORES_BYTES`` a row takes its attention over the rings' live
    key blocks, a row at a time (`_rows_attention`).

    A latent layer scatters its positions' rows [c_kv | k_r] into the
    slot's ring under the same ``write_ok`` / out-of-bounds rule as K/V and
    attends in the ABSORBED form over the row's own slot
    (`_latent_attention`), the form the decode step uses: one statement of
    the cached attention for both programs, and no [K, M, H, nope + v]
    expansion of a ring that is mostly not the chunk's. Where the scores
    of the rows it takes at once would pass ``LATENT_SCORES_BYTES`` (a
    long ring) it walks each row's live blocks instead (`_rows_walk`).

    With hyper-connections the embedding is copied into the streams and
    the streams are summed before the return (`transformer.hyper_expand`
    / `hyper_collapse`).

    No fused/quantized weights: prefill is MXU-bound (the fusions are
    decode, weight-streaming, optimizations) and the speculative verify
    must keep the raw-weight numerics. Returns (hidden states [K, L, d]
    before the final norm, bufs, the experts the positions chose in the
    routed layers [routed layers, K, L, k], None without such layers)."""
    dt = cfg.dtype
    k_rows, l = tokens.shape
    n_rec = 2 if cfg.n_linear_layers else 0
    n_lat = 4 + n_rec + (1 if cfg.n_latent_layers else 0)
    bufs, rec, lat, win = (bufs[:4], bufs[4:4 + n_rec], bufs[4 + n_rec:n_lat],
                           bufs[n_lat:])
    n_slots, m_cap = bufs[0].shape[1], bufs[0].shape[3]
    positions = starts[:, None] + jnp.arange(l)[None, :]        # [K, L]
    ring_idx = jnp.where(write_ok, (offsets[:, None] + positions) % m_cap,
                         m_cap + jnp.arange(l)[None, :])
    if rows is None:
        rows, read_rows = jnp.arange(k_rows), None
    else:
        read_rows = jnp.minimum(rows, n_slots - 1)  # clamp padding rows

    def scatter(layer, idx):
        def put(buf, new):
            # advanced indices [K,1] x [K,L] around the kvH slice put the
            # broadcast dims first: the updates arrive [K, L, kvH(, D)]
            return buf.at[layer, rows[:, None], :, idx].set(
                jnp.moveaxis(new, 1, 2), unique_indices=True, mode="drop")
        return put

    def rows_of(layer, buf):    # what the rows attend over: [K, kvH, M(, D)]
        if buf is None:
            return None
        return buf[layer] if read_rows is None else buf[layer][read_rows]

    def attend(layer, carry, q, k, v):
        bufs, rec, lat, win = carry
        bufs = _store_kv(bufs, k, v, scatter(layer, ring_idx))
        ck, cv, ks, vs = (rows_of(layer, b) for b in bufs)
        attn = _rows_attention(cfg, q, ck, cv, starts, l, ks, vs, offsets)
        return attn, (bufs, rec, lat, win)

    def attend_window(layer, carry, q, k, v):
        bufs, rec, lat, (wk, wv) = carry
        ring = wk.shape[3]
        idx = jnp.where(write_ok, (offsets[:, None] + positions) % ring,
                        ring + jnp.arange(l)[None, :])
        wk, wv, _, _ = _store_kv((wk, wv, None, None), k, v,
                                 scatter(layer, idx))
        w_starts, w_offsets = window_view(starts, offsets, ring, l)
        attn = _rows_attention(cfg, q, rows_of(layer, wk), rows_of(layer, wv),
                               w_starts, l, None, None, w_offsets,
                               window=cfg.win_size)
        return attn, (bufs, rec, lat, (wk, wv))

    def recur(layer, carry, h, lp):
        bufs, (state, conv), lat, win = carry

        def of_rows(buf):       # the rows' own, zero at a sequence's start
            mine = buf[layer] if read_rows is None else buf[layer][read_rows]
            fresh = (starts == 0).reshape((-1,) + (1,) * (mine.ndim - 1))
            return jnp.where(fresh, 0, mine)

        out, new_state, new_tail = transformer.linear_mixer(
            cfg, h, lp, of_rows(state), of_rows(conv),
            jnp.sum(write_ok, axis=1, dtype=jnp.int32))
        swr = dict(unique_indices=True, mode="drop")
        return out, (bufs, (state.at[layer, rows].set(new_state, **swr),
                            conv.at[layer, rows].set(new_tail, **swr)), lat,
                     win)

    def latent(layer, carry, h, lp):
        bufs, rec, (lat,), win = carry
        q_nope, q_rope, row = transformer.latent_project(cfg, h, positions, lp)
        lat = lat.at[layer, rows[:, None], ring_idx].set(
            row.astype(lat.dtype), unique_indices=True, mode="drop")
        mine = lat[layer] if read_rows is None else lat[layer][read_rows]
        q_lat = transformer.latent_absorb(cfg, q_nope, q_rope, lp)
        at_once = min(k_rows, LATENT_ROWS_AT_ONCE)
        if at_once * cfg.n_heads * l * mine.shape[1] * 4 > LATENT_SCORES_BYTES:
            # a long ring: its rows are one K/V head whose values are its
            # keys, walked over the live blocks (the whole rows weighed:
            # `latent_out` reads their first kv_rank values)
            o = _rows_walk(cfg, q_lat, mine[:, None], mine[:, None], starts,
                           l, offsets, 0, transformer.latent_scale(cfg))
        elif k_rows > LATENT_ROWS_AT_ONCE:
            # a wide burst's scores [K, H, L, M] float32 would be
            # gigabytes beside weights that fill the chip: a few rows at
            # a time (K is a power of two)
            def some(t):
                return _latent_attention(cfg, t[0], t[1], t[2], l, t[3])

            o = lax.map(some, jax.tree.map(
                lambda a: a.reshape((-1, LATENT_ROWS_AT_ONCE) + a.shape[1:]),
                (q_lat, mine, starts, offsets)))
            o = o.reshape((k_rows,) + o.shape[2:])
        else:
            o = _latent_attention(cfg, q_lat, mine, starts, l, offsets)
        return transformer.latent_out(cfg, o, lp), (bufs, rec, (lat,), win)

    mixers = {"linear": recur, "latent": latent}
    attends = {"window": attend_window}
    x = transformer.hyper_expand(cfg, params["embed"].astype(dt)[tokens])
    carry = (bufs, rec, lat, win)
    picks = []
    for i in range(cfg.n_layers):
        kind, j, lp = transformer.layer_at(cfg, params["layers"], i)
        x, aux, carry = transformer.decoder_layer(
            cfg, x, positions, lp,
            functools.partial(attends.get(kind, attend), j), carry,
            functools.partial(mixers[kind], j) if kind in mixers else None,
            kind=kind)
        if cfg.mlp_kinds is not None and cfg.mlp_kinds[i] == "routed":
            picks.append(aux)
    return (transformer.hyper_collapse(cfg, x),
            carry[0] + carry[1] + carry[2] + carry[3],
            jnp.stack(picks) if picks else None)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "shardings"),
    donate_argnames=("cache", "d_tokens", "d_active", "d_target",
                     "d_offsets", "d_temps", "d_topks"),
)
def _prefill_batch(params, cache, d_tokens, d_active, d_target, d_offsets,
                   d_temps, d_topks, tokens, slots, starts, offsets, n_valids,
                   last_tokens, targets, temps, topks, fin,
                   *, cfg: TransformerConfig,
                   shardings: DecodeShardings | None = None):
    """The admission program: ONE dispatch feeds chunk tokens [K, C]
    (padded past ``n_valids``) into K slots' cache rows at once through
    `_rows_forward`; a burst of one is K = 1. An admission burst of K
    requests with up to R chunks each is R dispatches, one program per
    chunk ROUND, not a serial train of K x R that stalls the next decode
    block behind every arrival burst.

    Row r writes slot ``slots[r]`` at logical positions ``starts[r]..``
    and leaves that slot's length at ``starts[r] + n_valids[r]`` (others
    untouched); the padded tail is dropped. Rows whose request has no
    chunk this round (shorter prompts in the burst, or power-of-two
    padding — K is padded so compiled variants stay O(log slots)) carry
    n_valid=0 and an OUT-OF-BOUNDS slot id: every one of their writes —
    KV scatter, length, decode-state commit — falls off the end and is
    dropped (mode="drop"). ``fin`` [K] bool marks each request's LAST chunk
    (including the degenerate zero-valid chunk of a 1-token prompt): only
    those rows commit the slot's decode state — fed token, active, budget
    target, ring offset, temperature, top-k — in the same dispatch, via
    scatter indices diverted out of bounds for non-final rows (the indices
    stay pairwise distinct, so the scatters keep unique_indices). An
    admission is then exactly one dispatch per chunk round: separate
    .at[].set pokes measured ~8ms of host dispatch work per admission, a
    third of the whole serving loop's host cost.

    A config with routed expert layers adds one result after the fence:
    the experts the chunk's positions chose, [K, C, routed layers, k]
    int32 (a request that asked for its routes reads its row of it)."""
    params = _cast_decode_params(params, cfg)
    k_rows, l = tokens.shape
    n_slots = cache.k.shape[1]
    write_ok = jnp.arange(l)[None, :] < n_valids[:, None]
    more = [f for f in ("state", "conv", "latent", "win_k", "win_v")
            if getattr(cache, f) is not None]
    _, (ck, cv, ks_buf, vs_buf, *bufs), picks = _rows_forward(
        params, cfg, tokens,
        (cache.k, cache.v, cache.k_scale, cache.v_scale,
         *(getattr(cache, f) for f in more)),
        slots, starts, offsets, write_ok)
    swr = dict(unique_indices=True, mode="drop")
    new_len = cache.length.at[slots].set(
        (starts + n_valids).astype(jnp.int32), **swr)
    cache = KVCache(ck, cv, new_len, ks_buf, vs_buf, **dict(zip(more, bufs)))
    # non-final rows' commit indices divert out of bounds; all indices
    # stay pairwise distinct (final rows hold distinct real slots < S,
    # the rest n_slots+row), so unique_indices holds
    commit = jnp.where(fin, slots, n_slots + jnp.arange(k_rows))
    d_tokens = d_tokens.at[commit].set(last_tokens, **swr)
    d_active = d_active.at[commit].set(True, **swr)
    d_target = d_target.at[commit].set(targets, **swr)
    d_offsets = d_offsets.at[commit].set(offsets, **swr)
    d_temps = d_temps.at[commit].set(temps, **swr)
    d_topks = d_topks.at[commit].set(topks, **swr)
    # dispatch-tracker fence (see _copy_prefix_blocks): chunk rounds
    # dispatch back-to-back, each donating the previous round's outputs
    fence = jnp.sum(new_len).astype(jnp.int32)
    out = (*_constrain_pool(shardings, cache, d_tokens, d_active, d_target,
                            d_offsets, d_temps, d_topks), fence)
    if picks is not None:
        out += (jnp.transpose(picks, (1, 2, 0, 3)),)
    return out


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "block", "stop_tokens", "pad_id",
                     "top_k", "per_row_topk", "weight_dtype", "build_fused",
                     "all_greedy", "lp_k", "shardings"),
    donate_argnames=("cache",),
)
def _decode_block(params, fused, cache, tokens, active, target_len,
                  offsets, cursor, temps, topks, key,
                  *, cfg: TransformerConfig, block: int, stop_tokens: tuple,
                  pad_id: int, top_k: int, per_row_topk: bool,
                  weight_dtype: str, build_fused: bool, all_greedy: bool,
                  lp_k: int = 0,
                  shardings: DecodeShardings | None = None):
    """``block`` single-token decode steps for ALL slots under one scan.
    Per-row masks freeze finished slots: their length stops advancing (the
    K/V garbage an idle row computes lands at its frozen length, beyond
    which the mask never reads, and admission overwrites it from 0), and
    their fed token stops changing. Returns (cache, tokens, active,
    packed) where ``packed`` [S, block+2] int32 is the emitted token
    matrix with the final lengths and active mask as its last two columns
    — ONE array so the host pays ONE device->host transfer per processed
    block (each transfer is a host sync whatever its size; three separate
    fetches tripled the serving loop's wall time). A cache with a recurrent
    state adds one last column: 1 where the block changed the row's state.
    Emitted rows are pad past a slot's stop; the host slices by length
    delta instead of trusting pad.

    ``lp_k`` (static; nonzero iff some busy slot asked for logprobs)
    widens ``packed`` to [S, block+2+block*(2*lp_k+1)]: after the
    length/active columns come each step's CHOSEN-token logprob (f32
    bitcast to int32), the top-``lp_k`` token ids, and their logprobs
    (bitcast) — read off the SAME log-softmax row the token was sampled
    from, still one transfer.

    A config with routed expert layers (``cfg.mlp_kinds``) widens it by
    `routed_columns`: the experts each row's fed token chose at each step
    in each routed layer ([S, block * layers * k], step-major), then three
    columns whose FIRST row carries a count of the whole block, summed
    over its steps and routed layers, of the experts this chip holds: the
    distinct experts the rows live at a step routed to, the distinct
    experts ANY row routed to (an idle
    row computes on its stale token and routes it like any other: those
    are the experts whose weights the grouped matmul streams), and the
    most assignments of live rows that one expert got at one step of one
    layer (a maximum, not a sum). They come before the recurrent state's
    column.

    The sample (and the logprobs) are the step's part "unembed", and the
    count columns, routed and recurrent, its part "counts"
    (``observability.STEP_PARTS``): what the instrumentation costs the
    device."""
    params = _cast_decode_params(params, cfg)
    if build_fused:
        fused = _fuse_decode_weights(params, cfg, weight_dtype)
    stop_arr = (jnp.asarray(list(stop_tokens), jnp.int32)
                if stop_tokens else None)

    m_cap = cache.k.shape[3]

    routed = cfg.n_routed_layers > 0

    def step(carry, _):
        cache, tokens, active, cursor, key = carry
        logits, new_cache, *picks = _forward_with_cache(
            params, cfg, tokens[:, None], cache, fused,
            ring=(cursor, offsets, active), shardings=shardings,
            routes=routed)
        key, sub = jax.random.split(key)
        # per-ROW sampling: each slot decodes at its own request's
        # temperature (0 = greedy) and top_k, so mixed traffic shares one
        # pool; all_greedy / per_row_topk (static, host-known) compile
        # the argmax-only / static-threshold programs whenever no busy
        # row actually needs the costlier variant
        with jax.named_scope("unembed"):
            nxt = sample_token(logits, sub,
                               0.0 if all_greedy else temps,
                               topks if per_row_topk else top_k)
        emitted = jnp.where(active, nxt, pad_id).astype(jnp.int32)
        if lp_k:
            # the raw-distribution logprobs of the row the sample came
            # from (pre temperature/top-k filtering — the model's own
            # distribution, the OpenAI convention)
            with jax.named_scope("unembed"):
                lp_full = jax.nn.log_softmax(logits.astype(jnp.float32),
                                             axis=-1)
                top_vals, top_ids = lax.top_k(lp_full, lp_k)
                chosen = jnp.take_along_axis(
                    lp_full, nxt[:, None].astype(jnp.int32), axis=-1)[:, 0]
        # only rows active this step advance (staying ring-aligned with
        # the cursor); a frozen row keeps taking the shared-cursor garbage
        # write, but its data is dead — completions are extracted from the
        # emitted tokens, and re-admission rewrites the slot from scratch.
        # A linear layer's state and convolution tail take no such write:
        # `_forward_with_cache` advances them where ``active`` only
        new_len = jnp.where(active, new_cache.length, cache.length)
        new_cache = new_cache._replace(length=new_len)
        hit_stop = (jnp.isin(nxt, stop_arr) if stop_arr is not None
                    else jnp.zeros_like(active))
        still = active & ~hit_stop & (new_len < target_len)
        tokens = jnp.where(still, nxt, tokens)
        ys = ((emitted, chosen, top_ids.astype(jnp.int32), top_vals)
              if lp_k else emitted)
        if routed:
            picks = picks[0][:, :, 0]               # [layers, S, k]
            with jax.named_scope("counts"):
                load = functools.partial(expert_load,
                                         n_experts=cfg.moe_experts)
                live = jax.vmap(lambda c: load(c, active))(picks)
                every = jax.vmap(load)(picks)
                first, count = cfg.experts_held
                if count < cfg.moe_experts:     # a chip's share: its own
                    live = live[:, first:first + count]
                    every = every[:, first:first + count]
                counts = (jnp.sum(live > 0), jnp.sum(every > 0),
                          jnp.max(live))
            ys = (ys, (jnp.moveaxis(picks, 0, 1), *counts))
        return (new_cache, tokens, still, (cursor + 1) % m_cap, key), ys

    def state_mark(cache):
        """A row's mark of its recurrent state: the first linear layer's,
        summed (None without one). Equal states give equal marks bit for
        bit, and a step that changed a state moves its mark, so two marks
        say whether a block changed a row's state at the cost of one read
        of one layer's state each (holding the old state to compare it
        whole would copy every layer's: the scan updates them in place)."""
        if cache.state is None:
            return None
        with jax.named_scope("counts"):
            return jnp.sum(cache.state[0], axis=(1, 2, 3))

    mark_in = state_mark(cache)
    (cache, tokens, active, cursor, key), ys = lax.scan(
        step, (cache, tokens, active, cursor, key), None, length=block)
    if routed:
        ys, (picks, touched, read, busiest) = ys
    if lp_k:
        toks, chosen, ids, vals = ys
        s = toks.shape[1]
        extra = [
            lax.bitcast_convert_type(
                chosen.T.astype(jnp.float32), jnp.int32),
            jnp.transpose(ids, (1, 0, 2)).reshape(s, block * lp_k),
            lax.bitcast_convert_type(
                jnp.transpose(vals, (1, 0, 2)).astype(jnp.float32),
                jnp.int32).reshape(s, block * lp_k),
        ]
    else:
        toks, extra = ys, []
    if routed:
        n_slots = picks.shape[1]                    # picks [block, S, layers, k]
        with jax.named_scope("counts"):
            counts = jnp.stack([jnp.sum(touched), jnp.sum(read),
                                jnp.max(busiest)]).astype(jnp.int32)
        extra = extra + [
            jnp.moveaxis(picks, 0, 1).reshape(n_slots, -1),
            jnp.zeros((n_slots, 3), jnp.int32).at[0].set(counts)]
    if mark_in is not None:
        # the rows whose recurrent state this block changed, as the device
        # has it (a frozen row's is bit for bit what it was): the last column
        with jax.named_scope("counts"):
            moved = state_mark(cache) != mark_in
        extra = extra + [moved.astype(jnp.int32)[:, None]]
    packed = jnp.concatenate(
        [toks.T, cache.length[:, None], active.astype(jnp.int32)[:, None]]
        + extra, axis=1)
    cache, tokens, active, packed = _constrain_pool(
        shardings, cache, tokens, active, packed)
    return cache, tokens, active, packed


@functools.partial(
    jax.jit,
    static_argnames=("shardings",),
    donate_argnames=("active",),
)
def _cancel_slot(active, slot, *, shardings: DecodeShardings | None = None):
    """Deactivate one slot's device-carried active flag. Dispatched
    between blocks, so — dispatch order being device order — it takes
    effect exactly at its position in the event log: every block
    dispatched before the cancel still decodes the slot (those tokens
    are already paid for), every block after treats it as an idle row
    whose garbage is never read. The slot's length freezes with it, so
    re-admission rewrites the ring from scratch exactly as it would
    after a natural completion."""
    active = active.at[slot].set(False)
    if shardings is not None:
        active = lax.with_sharding_constraint(active, shardings.act)
    return active


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "draft_cfg", "gamma", "stop_tokens", "pad_id"),
    donate_argnames=("cache", "draft_cache", "d_tokens", "d_active"),
)
def _spec_block(params, draft_params, cache, draft_cache, d_tokens,
                d_active, d_target, d_offsets,
                *, cfg: TransformerConfig, draft_cfg: TransformerConfig,
                gamma: int, stop_tokens: tuple, pad_id: int):
    """One speculative round for ALL slots under one jit: the draft
    autoregressively proposes ``gamma`` tokens per row (gamma+1 cheap
    steps — the extra one ingests the last proposal so the all-accept
    case's draft cache is one-ahead, exactly the solo discipline,
    models/speculative.py), the target verifies every row's gamma+1
    positions in ONE forward (the same weight stream as a single decode
    step), and each row accepts its longest matching draft prefix plus
    the target's own correction/bonus token.

    **Exactness**: every emitted token is the target's greedy argmax
    given its prefix, so each request's stream is byte-identical to the
    plain `_decode_block` path (and to solo generate) for ANY draft —
    a broken draft costs speed, never correctness. Budget and stop-token
    clamps keep the identity at the boundaries: emissions are truncated
    to the remaining budget and cut after the first stop token, which is
    exactly where the plain path freezes the row.

    Rollback is a length write: both caches' stale suffix entries beyond
    the accepted prefix are overwritten by the next round's fed tokens
    before any query can read them (rounds always re-feed from the new
    length — the same argument the solo implementation rests on).

    Returns (cache, draft_cache, next_tokens, still_active, packed)
    where ``packed`` [S, gamma+4] int32 carries the emitted tokens
    (pad-filled past each row's count), the raw per-row acceptance
    count, and the final lengths/active mask — the host slices emissions
    by length delta exactly as it does for plain decode blocks, so the
    event-log replay (admissions, cancels, journal appends) is
    unchanged: accepted tokens reach the journal as ordinary host-known
    tokens and rejected drafts never exist host-side at all."""
    params = _cast_decode_params(params, cfg)
    draft_params = _cast_decode_params(draft_params, draft_cfg)
    s = cache.k.shape[1]
    len0 = cache.length                                  # [S]
    active = d_active
    tok = d_tokens

    def rows_logits(p, p_cfg, toks, bufs, lens):
        """``toks`` [S, L] at each row's ``lens[r]..`` through
        `_rows_forward` (every slot in order) -> every position's logits
        [S, L, V] f32 (the verify forward needs the target's prediction
        after each drafted token; L is the small draft window). Speculation
        amortizes the per-row scatter over up to gamma+1 tokens a dispatch
        and pays it back by streaming the target weights once per ROUND.

        Writes land only for active rows at positions < the row's target:
        without the shared cursor a row's ring holds logical position p at
        index (offset+p) mod M, and a verify window overhanging ``max_len``
        would wrap onto the row's own earliest prompt KV. No delivered
        emission ever needs KV at positions >= target (the row freezes
        there), so dropping those writes is exact, not lossy."""
        positions = lens[:, None] + jnp.arange(toks.shape[1])[None, :]
        write_ok = active[:, None] & (positions < d_target[:, None])
        x, bufs, _ = _rows_forward(p, p_cfg, toks, bufs, None, lens,
                                   d_offsets, write_ok)
        x = transformer.norm(p_cfg, x, p["final_norm"])
        logits = jnp.einsum("bld,dv->blv", x,
                            p["unembed"].astype(p_cfg.dtype))
        return logits.astype(jnp.float32), bufs

    def draft_step(carry, _):
        t, dbufs, dlen = carry
        lg, dbufs = rows_logits(draft_params, draft_cfg, t[:, None],
                                dbufs, dlen)
        nxt = jnp.argmax(lg[:, 0], axis=-1).astype(jnp.int32)
        return (nxt, dbufs, dlen + 1), t

    (_, (dk, dv, dks, dvs), _), drafted_in = lax.scan(
        draft_step,
        (tok, (draft_cache.k, draft_cache.v, draft_cache.k_scale,
               draft_cache.v_scale), draft_cache.length),
        None, length=gamma + 1)
    # drafted_in[i] = the token INGESTED at step i = [tok, d_1..d_gamma]
    d = jnp.moveaxis(drafted_in[1:], 0, 1)               # [S, gamma]

    # --- target verifies all gamma+1 positions in ONE forward
    verify_in = jnp.concatenate([tok[:, None], d], axis=1)
    lg, (ck, cv, cks, cvs) = rows_logits(
        params, cfg, verify_in,
        (cache.k, cache.v, cache.k_scale, cache.v_scale), len0)
    t_pred = jnp.argmax(lg, axis=-1).astype(jnp.int32)   # [S, gamma+1]

    matches = (d == t_pred[:, :gamma]).astype(jnp.int32)
    n_acc = jnp.cumprod(matches, axis=1).sum(axis=1)     # [S] in [0,gamma]
    idx = jnp.arange(gamma + 1)[None, :]
    correction = jnp.take_along_axis(t_pred, n_acc[:, None], axis=1)
    d_ext = jnp.concatenate([d, jnp.zeros((s, 1), jnp.int32)], axis=1)
    # cand[r] = the row's next n_acc+1 greedy tokens: accepted drafts,
    # then the target's correction (mismatch) or bonus (all accepted)
    cand = jnp.where(idx == n_acc[:, None], correction, d_ext)

    # per-row emission count: acceptance, clamped by the remaining
    # budget and cut after the first emitted stop token — the exact
    # boundaries where the plain decode path freezes the row
    room = jnp.maximum(d_target - len0, 0)
    n_budget = jnp.minimum(n_acc + 1, room)
    if stop_tokens:
        stops = jnp.asarray(list(stop_tokens), jnp.int32)
        hit = jnp.isin(cand, stops)
        stop_idx = jnp.min(jnp.where(hit & (idx < n_budget[:, None]),
                                     idx, gamma + 1), axis=1)
        stop_hit = active & (stop_idx < n_budget)
        n_emit = jnp.where(stop_hit, stop_idx + 1, n_budget)
    else:
        stop_hit = jnp.zeros((s,), bool)
        n_emit = n_budget
    n_emit = jnp.where(active, n_emit, 0)
    new_len = len0 + n_emit
    still = active & ~stop_hit & (new_len < d_target)
    # next fed token: the last emitted token (only read while still
    # active, in which case it is the unwritten correction/bonus)
    nxt_tok = jnp.take_along_axis(
        cand, jnp.maximum(n_emit - 1, 0)[:, None], axis=1)[:, 0]
    tok_out = jnp.where(still, nxt_tok, tok)
    emitted = jnp.where(idx < n_emit[:, None], cand, jnp.int32(pad_id))
    packed = jnp.concatenate(
        [emitted, n_acc[:, None], new_len[:, None],
         still.astype(jnp.int32)[:, None]], axis=1)
    new_cache = KVCache(k=ck, v=cv, length=new_len,
                        k_scale=cks, v_scale=cvs)
    new_draft = KVCache(k=dk, v=dv, length=new_len,
                        k_scale=dks, v_scale=dvs)
    return new_cache, new_draft, tok_out, still, packed


class BlockAllocator:
    """Host-side authority over the shared paged-KV pool: a free list +
    per-block refcounts + per-class accounting. Pure host bookkeeping
    (device programs only ever see block-id TABLES), so the lifecycle
    invariants are unit-testable without a model.

    A block's refcount counts its HOLDERS: each slot table entry that
    points at it and each trie node that owns it. Blocks free when the
    last holder lets go — that is the whole copy-on-write story, because
    shared blocks are never written again (prefill chunks are immutable
    once complete; decode writes only land in a slot's exclusively-owned
    tail blocks).

    ``class_budgets`` caps how many blocks each admission tier may hold
    EXCLUSIVELY at once (``alloc_for`` debits, ``credit`` at release);
    trie-shared blocks ride free — a cached prefix benefits every class.
    A class over budget defers at admission instead of starving the
    other tier of pool blocks."""

    def __init__(self, n_blocks: int, class_budgets: dict | None = None):
        if n_blocks < 1:
            raise ValueError(f"paged KV pool needs >= 1 block, "
                             f"got {n_blocks}")
        self.n_blocks = n_blocks
        self._free = list(range(n_blocks - 1, -1, -1))
        self.refs = np.zeros(n_blocks, np.int32)
        self.class_budgets: dict[str, int] = {}
        for cls, cap in (class_budgets or {}).items():
            if cls not in PRIORITY_CLASSES:
                raise ValueError(
                    f"unknown priority class {cls!r} in class_budgets "
                    f"(valid: {PRIORITY_CLASSES})")
            self.class_budgets[cls] = int(cap)
        self.class_used = {cls: 0 for cls in PRIORITY_CLASSES}
        self.peak_used = 0

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        return self.n_blocks - len(self._free)

    def take(self) -> int | None:
        """One class-unaccounted block (trie growth), refcount 1."""
        if not self._free:
            return None
        block = self._free.pop()
        self.refs[block] = 1
        self.peak_used = max(self.peak_used, self.used_blocks)
        return block

    def alloc_for(self, cls: str, n: int) -> list | None:
        """``n`` fresh blocks (refcount 1 each) debited to class ``cls``,
        all-or-nothing: None when the free list or the class budget
        comes up short (callers defer the admission, never partially
        admit)."""
        budget = self.class_budgets.get(cls)
        if budget is not None and self.class_used.get(cls, 0) + n > budget:
            return None
        if len(self._free) < n:
            return None
        blocks = [self._free.pop() for _ in range(n)]
        for block in blocks:
            self.refs[block] = 1
        if cls in self.class_used:
            self.class_used[cls] += n
        self.peak_used = max(self.peak_used, self.used_blocks)
        return blocks

    def ref(self, block: int) -> None:
        assert self.refs[block] >= 1, "ref on a free block"
        self.refs[block] += 1

    def unref(self, block: int) -> None:
        self.refs[block] -= 1
        assert self.refs[block] >= 0, "paged-KV block refcount underflow"
        if self.refs[block] == 0:
            self._free.append(block)

    def credit(self, cls: str, n: int) -> None:
        """Return ``n`` exclusively-held blocks to ``cls``'s budget (the
        refcounts are separate — a block credited back may live on,
        shared with the trie)."""
        if cls in self.class_used:
            self.class_used[cls] = max(0, self.class_used[cls] - n)

    def check(self) -> None:
        """Assert the refcount invariant (tests): every block is either
        on the free list with refcount 0 or off it with refcount >= 1 —
        no orphans, no double-frees, no referenced free blocks."""
        free = set(self._free)
        assert len(free) == len(self._free), "duplicate blocks on free list"
        for block in range(self.n_blocks):
            if block in free:
                assert self.refs[block] == 0, \
                    f"free block {block} still referenced"
            else:
                assert self.refs[block] >= 1, \
                    f"allocated block {block} unreferenced (orphan)"


@functools.partial(jax.jit, static_argnames=("shardings",))
def _gather_paged_view(pool, tables, lens, offsets, shardings=None):
    """Materialize the paged pool into a RING-ORDERED slot-pool view —
    view index (s, i) holds slot s's logical position (i - offsets[s])
    mod M, exactly where the ring engine would store it — so the
    existing prefill/decode programs run on the view UNCHANGED and the
    paged engine's outputs are byte-identical to the ring engine's by
    construction: same programs, same index arithmetic, same reduction
    orders. Table entries pointing at the pad block (the pool's last
    block, always zero) read zeros where the ring holds stale garbage —
    positions the attention mask weighs to exactly 0 either way.

    The view is TRANSIENT (alive gather -> program -> scatter, then
    donated away); persistent device memory is the pool, which is what
    lets concurrency exceed the slots x max_len ring bound."""
    n_pool = pool.k.shape[1]
    block = pool.k.shape[3]
    n_tbl = tables.shape[1]
    m_cap = n_tbl * block
    # ring index i holds logical position (i - offset) mod M
    p = (jnp.arange(m_cap)[None, :] - offsets[:, None]) % m_cap   # [S, M]
    blk = jnp.take_along_axis(tables, p // block, axis=1)         # [S, M]
    row = p % block
    # advanced indices separated by a slice -> result axes lead:
    # pool.k[L, N, kvH, B, D][:, blk, :, row] -> [S, M, L, kvH, D]
    k = pool.k[:, blk, :, row].transpose(2, 0, 3, 1, 4)
    v = pool.v[:, blk, :, row].transpose(2, 0, 3, 1, 4)
    ks = vs = None
    if pool.k_scale is not None:
        ks = pool.k_scale[:, blk, :, row].transpose(2, 0, 3, 1)
        vs = pool.v_scale[:, blk, :, row].transpose(2, 0, 3, 1)
    view = KVCache(k=k, v=v, length=lens, k_scale=ks, v_scale=vs)
    # mesh serving: the transient view carries the ring cache's layout,
    # so it takes the ring cache's shardings (pool stays sharded over
    # its block axis; GSPMD plans the block->slot redistribution)
    return _constrain_pool(shardings, view)[0]


@functools.partial(jax.jit, donate_argnames=("pool",),
                   static_argnames=("shardings",))
def _scatter_paged_rows(pool, view, tables, offsets, ring_ids, n_valids,
                        floors, shardings=None):
    """Commit a program's freshly-written view rows back into the pool:
    ``ring_ids`` [S, W] names the ring indices each slot's program wrote
    this dispatch (decode: the shared cursor window for every row;
    prefill: one slot's chunk span, other rows masked via ``n_valids``).
    Three guards divert a write to a dropped out-of-bounds id instead of
    committing it: column >= ``n_valids[s]`` (masked row / chunk pad
    tail), logical position < ``floors[s]`` (a pending/idle slot the
    decode program still writes garbage rows for — the ring engine
    buries those in the slot's private ring; here they must never reach
    a pool block another holder might share), and a pad-block target (an
    unmapped table entry). Diverted ids are DISTINCT per (slot, column)
    so ``unique_indices=True`` stays honest; real targets are unique
    because decode only ever writes a slot's exclusively-owned tail
    blocks (shared prefix blocks sit strictly below every write
    position). Returns the pool plus a dispatch-tracker fence scalar."""
    n_pool = pool.k.shape[1]
    block = pool.k.shape[3]
    n_tbl = tables.shape[1]
    m_cap = n_tbl * block
    n_slots, w = ring_ids.shape
    n_pad = n_pool - 1                      # pad block id
    p = (ring_ids - offsets[:, None]) % m_cap                     # [S, W]
    blk = jnp.take_along_axis(tables, p // block, axis=1)
    row = p % block
    j = jnp.arange(w)[None, :]
    bad = ((j >= n_valids[:, None]) | (p < floors[:, None])
           | (blk >= n_pad))
    divert = n_pool + jnp.arange(n_slots)[:, None] * w + j
    blk = jnp.where(bad, divert, blk)
    swr = dict(unique_indices=True, mode="drop")
    rows = jnp.arange(n_slots)[:, None]
    # view.k[L, S, kvH, M, D][:, rows, :, ring_ids] -> [S, W, L, kvH, D],
    # exactly the gather shape of pool.k[:, blk, :, row]
    pk = pool.k.at[:, blk, :, row].set(
        view.k[:, rows, :, ring_ids], **swr)
    pv = pool.v.at[:, blk, :, row].set(
        view.v[:, rows, :, ring_ids], **swr)
    pks, pvs = pool.k_scale, pool.v_scale
    if pks is not None:
        pks = pks.at[:, blk, :, row].set(
            view.k_scale[:, rows, :, ring_ids], **swr)
        pvs = pvs.at[:, blk, :, row].set(
            view.v_scale[:, rows, :, ring_ids], **swr)
    if shardings is not None:
        # pool [L, N, kvH, B, D] shards its block axis like the ring
        # cache's batch axis — same spec as _insert_prefix_blocks uses
        pk = jax.lax.with_sharding_constraint(pk, shardings.cache)
        pv = jax.lax.with_sharding_constraint(pv, shardings.cache)
        if pks is not None:
            pks = jax.lax.with_sharding_constraint(pks, shardings.scale)
            pvs = jax.lax.with_sharding_constraint(pvs, shardings.scale)
    fence = jnp.sum(blk).astype(jnp.int32)
    return PrefixPool(k=pk, v=pv, k_scale=pks, v_scale=pvs), fence


# ---------------------------------------------------------------------------
# KV block transfer protocol (disaggregated serving)
#
# Pool blocks store KV rows in LOGICAL order — position p lives at table
# entry p // B, row p % B, independent of the exporting slot's ring
# offset — so a block's bytes are portable between replicas whose
# cursors/offsets never agreed on anything. A prefill-role replica
# serializes the blocks covering [0, body_len) together with the
# request's journal entry (the PR 11 replay record: if the transfer
# dies, the prompt + emitted prefix re-prefills anywhere); a decode
# replica allocates blocks from its OWN pool, writes the payload in,
# installs the table row, and decodes byte-identically — the gather view
# makes imported blocks indistinguishable from locally-prefilled ones.
#
# Payload keys below are pinned by the api-contract lint
# (tests/test_streaming.py) against docs/serving.md "Disaggregated
# serving"; the sha256 checksum makes a torn/truncated transfer a loud
# ValueError at import, never a silently-wrong cache.
# ---------------------------------------------------------------------------

KV_TRANSFER_VERSION = 1

# every key a /kv/import payload carries (the api-contract lint pins
# this tuple against docs/serving.md both directions)
KV_IMPORT_KEYS = (
    "version", "model", "kv_block", "kv_dtype", "body_len", "n_blocks",
    "block_shape", "dtype", "scale_dtype", "blocks_k", "blocks_v",
    "scales_k", "scales_v", "checksum", "entry",
)

# the journal-entry fields that ride inside payload["entry"] — exactly
# the JournalEntry replay state minus the process-local deadline.
# "trace" is the prefill leg's distributed-trace identity
# (TraceContext.as_dict(), or null): the decode replica lands in the
# originating trace even when the payload arrives without headers
KV_ENTRY_KEYS = (
    "id", "prompt", "max_new_tokens", "temperature", "top_k",
    "cache_prompt", "seed", "emitted", "model", "stop", "logprobs",
    "priority", "trace",
)


def _b64(arr: np.ndarray) -> str:
    return base64.b64encode(
        np.ascontiguousarray(arr).tobytes()).decode("ascii")


def _transfer_checksum(*bufs: bytes) -> str:
    h = hashlib.sha256()
    for b in bufs:
        h.update(b)
    return h.hexdigest()


def serialize_kv_blocks(pool, ids, *, model, kv_block, kv_dtype,
                        body_len, entry) -> dict:
    """Snapshot the pool blocks ``ids`` (in table order) into a
    JSON-able transfer payload. Copies device->host, so the payload
    survives the exporter freeing/reusing the blocks immediately
    after. ``entry`` is the request's journal replay state (dict) —
    the receiver resubmits from it if the KV payload is unusable."""
    ids = np.asarray(ids, np.int32)
    k = np.asarray(pool.k[:, ids])          # [L, n, kvH, B, D]
    v = np.asarray(pool.v[:, ids])
    bufs = [np.ascontiguousarray(k).tobytes(),
            np.ascontiguousarray(v).tobytes()]
    scales_k = scales_v = None
    scale_dtype = None
    if pool.k_scale is not None:
        ks = np.asarray(pool.k_scale[:, ids])   # [L, n, kvH, B]
        vs = np.asarray(pool.v_scale[:, ids])
        bufs += [np.ascontiguousarray(ks).tobytes(),
                 np.ascontiguousarray(vs).tobytes()]
        scales_k, scales_v = _b64(ks), _b64(vs)
        scale_dtype = str(ks.dtype)
    return {
        "version": KV_TRANSFER_VERSION,
        "model": model,
        "kv_block": int(kv_block),
        "kv_dtype": str(kv_dtype),
        "body_len": int(body_len),
        "n_blocks": int(ids.size),
        "block_shape": [int(d) for d in k.shape],
        "dtype": str(k.dtype),
        "scale_dtype": scale_dtype,
        "blocks_k": base64.b64encode(bufs[0]).decode("ascii"),
        "blocks_v": base64.b64encode(bufs[1]).decode("ascii"),
        "scales_k": scales_k,
        "scales_v": scales_v,
        "checksum": _transfer_checksum(*bufs),
        "entry": dict(entry),
    }


def deserialize_kv_blocks(payload: dict) -> tuple[np.ndarray, np.ndarray,
                                                  np.ndarray | None,
                                                  np.ndarray | None]:
    """Decode + verify a transfer payload's KV buffers. Raises
    ValueError on any structural damage — wrong version, missing keys,
    truncated buffers, checksum mismatch — so a torn transfer is
    rejected loudly and the caller falls back to journal replay."""
    try:
        version = int(payload["version"])
        shape = tuple(int(d) for d in payload["block_shape"])
        dtype = np.dtype(payload["dtype"])
        raw_k = base64.b64decode(payload["blocks_k"], validate=True)
        raw_v = base64.b64decode(payload["blocks_v"], validate=True)
        checksum = payload["checksum"]
    except (KeyError, TypeError, ValueError) as e:
        raise ValueError(f"malformed KV transfer payload: {e}") from None
    if version != KV_TRANSFER_VERSION:
        raise ValueError(
            f"KV transfer version {version} != {KV_TRANSFER_VERSION}")
    if len(shape) != 5 or shape[1] != int(payload.get("n_blocks", -1)):
        raise ValueError("KV transfer block_shape/n_blocks mismatch")
    expect = int(np.prod(shape)) * dtype.itemsize
    if len(raw_k) != expect or len(raw_v) != expect:
        raise ValueError(
            f"truncated KV transfer payload: expected {expect} bytes "
            f"per buffer, got k={len(raw_k)} v={len(raw_v)}")
    bufs = [raw_k, raw_v]
    ks = vs = None
    if payload.get("scales_k") is not None:
        try:
            sdtype = np.dtype(payload["scale_dtype"])
            raw_ks = base64.b64decode(payload["scales_k"], validate=True)
            raw_vs = base64.b64decode(payload["scales_v"], validate=True)
        except (KeyError, TypeError, ValueError) as e:
            raise ValueError(
                f"malformed KV transfer scales: {e}") from None
        s_expect = int(np.prod(shape[:4])) * sdtype.itemsize
        if len(raw_ks) != s_expect or len(raw_vs) != s_expect:
            raise ValueError("truncated KV transfer scale payload")
        bufs += [raw_ks, raw_vs]
        ks = np.frombuffer(raw_ks, sdtype).reshape(shape[:4])
        vs = np.frombuffer(raw_vs, sdtype).reshape(shape[:4])
    if _transfer_checksum(*bufs) != checksum:
        raise ValueError("KV transfer payload checksum mismatch")
    k = np.frombuffer(raw_k, dtype).reshape(shape)
    v = np.frombuffer(raw_v, dtype).reshape(shape)
    return k, v, ks, vs


@functools.partial(jax.jit, donate_argnames=("pool",),
                   static_argnames=("shardings",))
def _write_pool_blocks(pool, ids, k, v, ks, vs, shardings=None):
    """Install imported block payloads at the receiver's block ids
    (one dispatch, pool donated — the import path's only device
    write)."""
    pk = pool.k.at[:, ids].set(k)
    pv = pool.v.at[:, ids].set(v)
    pks, pvs = pool.k_scale, pool.v_scale
    if pks is not None:
        pks = pks.at[:, ids].set(ks)
        pvs = pvs.at[:, ids].set(vs)
    if shardings is not None:
        pk = jax.lax.with_sharding_constraint(pk, shardings.cache)
        pv = jax.lax.with_sharding_constraint(pv, shardings.cache)
        if pks is not None:
            pks = jax.lax.with_sharding_constraint(pks, shardings.scale)
            pvs = jax.lax.with_sharding_constraint(pvs, shardings.scale)
    return PrefixPool(k=pk, v=pv, k_scale=pks, v_scale=pvs)


class SlotServer:
    """Continuous-batching server: S cache slots, requests admitted into
    freed slots while other slots keep decoding.

    >>> srv = SlotServer(params, cfg, slots=8, max_len=2048)
    >>> srv.submit(Request(prompt=[1, 5, 7], max_new_tokens=64))
    >>> done = srv.run_until_drained()          # {id: Completion}

    For a live service, call ``submit()`` from the request handler and
    ``step()`` on the serving loop; ``drain_completed()`` hands back
    finished requests after each step. Greedy by default; the server
    ``temperature`` is the default a request's own ``temperature``
    overrides (sampling is per-row, so greedy and sampled requests share
    one pool); ``top_k`` applies server-wide.

    ``params`` may be raw parameters or a ``prepare_decode`` result
    (servers should prepare once and drop the f32 masters). A prepared
    bundle built with ``mesh=`` — or a raw-params constructor call with
    ``mesh=`` (prepares internally) — serves TENSOR-PARALLEL: the slot
    pool's KV cache shards over ("batch", "kv") by the rule table (slots
    over the batch axes, kv heads over the tensor axes — so a model
    bigger than one chip's HBM serves live traffic), the per-slot state
    vectors shard over the batch axes, and every dispatched program
    (prefill chunks, batched admission, decode blocks) runs under GSPMD
    with the same single-controller scheduling as the one-device server.
    ``slots`` must divide by the batch axes' size. Greedy completions are
    token-identical to the single-device server (tested).

    A BURST of freed slots is admitted with one `_prefill_batch`
    dispatch per chunk round (a burst of one at one row) — K arrivals do
    not serialize K x chunks host dispatches in front of the next decode
    block. ``admission_dispatches`` counts prefill program dispatches.

    ``prefix_cache_blocks=N`` enables the chunk-aligned prefix cache
    (module docstring): N ``prefill_chunk``-sized KV blocks in a shared
    device pool (HBM budget = N x layers x kvH x chunk x head_dim x
    kv-dtype bytes, x2 for K+V), a host trie mapping token blocks to
    them, ref-counting while admitted requests hold their matched path,
    LRU eviction of unreferenced leaves. Admission then prefills only
    the uncached suffix of each prompt — token-identical completions
    either way (including int8 kv, where the pool stores the quantized
    bytes). ``cache_prompts`` is the server default for inserting
    admitted prompts' chunks back into the trie; ``Request.cache_prompt``
    overrides per request. 0 (default) disables the cache entirely.
    ``stats()`` reports the counters.

    A config with linear layers (``cfg.layer_kinds``) adds a recurrent
    state and a convolution tail per slot beside the full layers' K/V
    rings (module docstring; HBM = slots x n_linear x (4 x lin_heads x
    lin_key_dim x lin_value_dim + (lin_conv - 1) x lin_channels x
    activation bytes), ``stats()["recurrent_state"]``). Such a config
    serves on the ring engine on one device with native dtypes; together
    with ``prefix_cache_blocks > 0``, ``paged=True``, a ``draft`` /
    ``spec_gamma``, ``kv_dtype="int8"``, a ``mesh`` or a ``role`` other
    than "both" the constructor raises a ``ValueError`` naming which.

    Failure model (docs/serving.md "Failure model"):

    - ``max_queue=N`` bounds the wait queue: ``submit`` raises
      ``QueueFullError`` instead of queueing the N+1th request (0 =
      unbounded). Admission also skips requests whose ``deadline``
      already passed (finish_reason "expired") — dead work never takes
      a slot.
    - ``cancel(request_id)`` stops a request wherever it is: queued
      (dequeued), prefilling, or mid-decode (the slot's device-side
      active flag is dropped between blocks, freeing it for the next
      admission; a matched prefix-cache path is unpinned). The freed
      slot's next occupant is token-identical to a fresh server —
      re-admission rewrites the ring from scratch (tested).
    - ``reset()`` re-arms every serving buffer (KV ring, slot state,
      prefix pool) WITHOUT touching the weights after a loop failure;
      queued requests survive, and — with the journal on (the default) —
      admitted requests are REPLAYED instead of failed: each is
      re-queued with its journaled prompt + emitted-so-far prefix as
      ``resume_tokens``, so a loop crash costs latency, not requests
      (greedy continuations are byte-identical; see ``RequestJournal``).
      ``replay=False`` (or ``journal=None`` with ``replay=False``)
      preserves the fail-fast contract: admitted ids are returned as
      lost so the caller fails them upstream.
    - Chaos hooks (``TONY_TEST_SERVING_DISPATCH_FAIL_RATE`` /
      ``_STEP_DELAY_MS`` / ``_CHAOS_SEED`` /
      ``_CRASH_AT_BLOCKS`` (comma-separated decode-block ordinals at
      which the loop crashes mid-decode, each once) /
      ``_SIGKILL_AT_BLOCK`` (the PROCESS SIGKILLs itself at that decode
      block — the replica-death injection point) env, read at
      construction, seeded for reproducibility) inject failures/latency
      into production code paths, same contract as the driver's
      ``TEST_*`` knobs (constants.py)."""

    def __init__(self, params=None, cfg: TransformerConfig | None = None,
                 *, slots: int = 8,
                 max_len: int = 2048, block_size: int = 16,
                 prefill_chunk: int = 128, kv_dtype: str = "native",
                 weight_dtype: str = "native", temperature: float = 0.0,
                 top_k: int = 0, stop_tokens: tuple = (), pad_id: int = 0,
                 seed: int = 0, pipeline_depth: int = 2,
                 mesh=None, rules=None,
                 prefix_cache_blocks: int = 0, cache_prompts: bool = True,
                 max_queue: int = 0, trace_sink=None,
                 journal: RequestJournal | None = None,
                 replay: bool = True,
                 model: str = "default",
                 registry: ModelRegistry | None = None,
                 draft=None, draft_cfg: TransformerConfig | None = None,
                 spec_gamma: int = 0, spec_gamma_max: int = 4,
                 paged: bool = False, kv_block: int = 0,
                 kv_pool_blocks: int = 0,
                 class_budgets: dict | None = None,
                 prefill_interleave: int = 0,
                 batch_queue_frac: float = 0.5,
                 role: str = "both"):
        # ---- model registry (models/registry.py) ----
        # the weights singleton became a keyed registry: this server
        # SERVES one named entry (its slot-pool cache shape is that
        # entry's config), and the draft/target pair of speculative
        # decoding is just two entries. Construct with registry=/model=
        # to serve a pre-built registry entry, or the classic
        # (params, cfg) pair — which is registered under ``model`` so
        # every server exposes the same registry-backed surface.
        if registry is not None:
            self.registry = registry
            # the unchanged ctor default "default" means "the registry's
            # first entry"; any OTHER unregistered name is an error —
            # silently serving different weights than the caller named
            # is the one failure mode a registry exists to prevent
            if model in registry:
                entry = registry.get(model)
            elif model == "default":
                entry = registry.default
            else:
                entry = registry.get(model)     # raises with the names
            self.model = entry.name
            params, cfg = entry.weights, entry.cfg
            if draft is None and entry.draft is not None:
                draft = entry.draft
        else:
            if params is None or cfg is None:
                raise ValueError(
                    "SlotServer needs (params, cfg) or registry=/model=")
            self.registry = ModelRegistry()
            self.registry.register(str(model), params, cfg)
            self.model = str(model)
        if not cfg.causal:
            raise ValueError("serving requires a causal model")
        # ---- hyper-connections: n residual streams a position ----
        # the streams live inside one program's forward and nothing of them
        # is cached; what would carry or verify positions some other way
        # than the ring engine's forward is refused here, each by name
        if cfg.hc_mult:
            no = "a config with hyper-connections (hc_mult > 0) "
            for bad, why in (
                    (prefix_cache_blocks > 0,
                     "cannot use prefix_cache_blocks: the prefix trie is "
                     "not built for it"),
                    (paged, "cannot use paged=True: the paged pool is not "
                     "built for it"),
                    (draft is not None or spec_gamma,
                     "cannot use a draft / spec_gamma: the verify forward "
                     "is not built for it"),
                    (kv_dtype == "int8", "cannot use kv_dtype='int8': not "
                     "measured beside the streams"),
                    (weight_dtype == "int8", "cannot use weight_dtype='int8': "
                     "the int8 forms are the uniform stack's"),
                    (mesh is not None
                     or getattr(params, "mesh", None) is not None,
                     "cannot use a mesh: the streams are not sharded"),
                    (role != "both", f"cannot use role={role!r}: the KV "
                     "handoff (serialize_kv_blocks) is not built for it")):
                if bad:
                    raise ValueError(no + why)
        # ---- recurrent slot state (cfg.layer_kinds with "linear") ----
        # a linear layer carries a state and a convolution tail a slot
        # beside the full layers' K/V ring. The ring engine on one device
        # holds them; what would need a state it cannot give yet is
        # refused here, each by name, before anything is built
        self._recurrent = cfg.n_linear_layers > 0
        if self._recurrent:
            no = "a config with linear layers (a recurrent slot state) "
            if prefix_cache_blocks > 0:
                raise ValueError(
                    no + "cannot use prefix_cache_blocks: a cached prefix "
                    "would need the state's snapshot at its chunk boundary")
            if paged:
                raise ValueError(
                    no + "cannot use paged=True: the block pool holds K/V "
                    "blocks only")
            if draft is not None or spec_gamma:
                raise ValueError(
                    no + "cannot use a draft / spec_gamma: a rejected draft "
                    "token would need the state rolled back")
            if kv_dtype == "int8":
                raise ValueError(
                    no + "cannot use kv_dtype='int8': not measured beside a "
                    "float32 state")
            if mesh is not None or getattr(params, "mesh", None) is not None:
                raise ValueError(
                    no + "cannot use a mesh: the state's heads are not "
                    "sharded")
            if role != "both":
                raise ValueError(
                    no + f"cannot use role={role!r}: the KV handoff does "
                    "not carry a state")
        # ---- a latent slot cache / routed expert layers ----
        # a latent layer keeps a row [c_kv | k_r] a position where a full
        # layer keeps K and V by head, and a routed layer's experts lie a
        # layer in a list: the ring engine on one device with native
        # dtypes holds and reads both; what is not built for them is
        # refused here, each by name, as above
        self._routed = cfg.n_routed_layers > 0
        for what, no in (
                (cfg.n_latent_layers > 0,
                 "a config with latent layers (a latent slot cache) "),
                (self._routed,
                 "a config with routed expert layers ")):
            if not what:
                continue
            if prefix_cache_blocks > 0:
                raise ValueError(
                    no + "cannot use prefix_cache_blocks: the prefix pool "
                    "holds blocks of per-head K and V only")
            if paged:
                raise ValueError(
                    no + "cannot use paged=True: the block pool holds "
                    "blocks of per-head K and V only")
            if draft is not None or spec_gamma:
                raise ValueError(
                    no + "cannot use a draft / spec_gamma: the verify "
                    "forward is not built for it")
            if kv_dtype == "int8":
                raise ValueError(
                    no + "cannot use kv_dtype='int8': the cached rows are "
                    "stored in the activation dtype")
            if weight_dtype == "int8":
                raise ValueError(
                    no + "cannot use weight_dtype='int8': the int8 forms "
                    "are the uniform stack's")
            if mesh is not None or getattr(params, "mesh", None) is not None:
                raise ValueError(
                    no + "cannot use a mesh: neither the latent rows nor "
                    "the experts are sharded")
            if role != "both":
                raise ValueError(
                    no + f"cannot use role={role!r}: the KV handoff "
                    "carries blocks of per-head K and V only")
        # ---- window layers: a ring of their own beside the full one ----
        # a window layer's K/V ring is shorter than max_len and shares the
        # cursor (`window_ring`); what reads or moves blocks of max_len
        # rings only is refused here, each by name, as above
        if cfg.n_window_layers:
            no = ("a config with window layers (a window ring beside the "
                  "full one) ")
            for bad, why in (
                    (prefix_cache_blocks > 0,
                     "cannot use prefix_cache_blocks: the prefix pool copies "
                     "its blocks into max_len rings only"),
                    (paged, "cannot use paged=True: the block pool has no "
                     "window ring"),
                    (draft is not None or spec_gamma,
                     "cannot use a draft / spec_gamma: the verify forward "
                     "is not built for it"),
                    (kv_dtype == "int8", "cannot use kv_dtype='int8': the "
                     "window ring is stored in the activation dtype"),
                    (weight_dtype == "int8", "cannot use weight_dtype='int8': "
                     "the int8 forms are the uniform stack's"),
                    (mesh is not None
                     or getattr(params, "mesh", None) is not None,
                     "cannot use a mesh: the window ring is not sharded"),
                    (role != "both", f"cannot use role={role!r}: the KV "
                     "handoff carries max_len rings only")):
                if bad:
                    raise ValueError(no + why)
        if isinstance(params, DecodeWeights):
            if params.mesh is not None:
                if mesh is not None and mesh != params.mesh:
                    raise ValueError(
                        "mesh mismatch: the prepared weights were built "
                        "for a different mesh than the SlotServer's")
                mesh = params.mesh
                if rules is None:
                    rules = params.rules
            elif mesh is not None:
                raise ValueError(
                    "prepared weights were built without a mesh but the "
                    "SlotServer got one — rebuild with "
                    "prepare_decode(..., mesh=...)")
            self._params, self._fused = params.params, params.fused
            self._build_fused = False
            weight_dtype = params.weight_dtype
        elif mesh is not None:
            prepared = prepare_decode(
                params, cfg, weight_dtype=weight_dtype, mesh=mesh,
                rules=rules)
            rules = prepared.rules
            self._params, self._fused = prepared.params, prepared.fused
            self._build_fused = False
        else:
            self._params, self._fused = params, None
            self._build_fused = True
        self._shardings = None
        self._mesh = mesh
        if mesh is not None:
            if rules is None:
                from ..parallel.sharding import TP_DECODE_RULES
                rules = TP_DECODE_RULES
            _validate_decode_mesh(cfg, mesh, rules)
            t_b = _rule_size(mesh, rules, "batch")
            if slots % t_b:
                raise ValueError(
                    f"mesh-sharded serving: slots={slots} is not divisible "
                    f"by the 'batch' mesh axes (size {t_b}) — the slot pool "
                    "is the batch dimension of every decode block")
            self._shardings = _decode_shardings(mesh, rules)
        # ---- speculative decoding (draft-model proposals) ----
        # ``draft`` is a registry entry NAME or raw/prepared weights
        # (with draft_cfg). Greedy-only, single-device, native target
        # weights: the acceptance rule is the greedy-match rule (solo
        # speculative.py scope), the per-row-position spec programs are
        # not mesh-threaded, and the plain decode path's w8a16 numerics
        # would break spec-on/spec-off byte-identity (the spec verify
        # runs raw weights, like the prefill programs).
        self._spec = False
        self.draft_model: str | None = None
        if draft is not None:
            if isinstance(draft, str):
                dentry = self.registry.get(draft)
                draft_w, draft_cfg = dentry.weights, dentry.cfg
                self.draft_model = dentry.name
            else:
                if draft_cfg is None:
                    raise ValueError(
                        "draft weights need draft_cfg (or pass a "
                        "registry entry name)")
                draft_w = draft
                self.draft_model = "draft"
                self.registry.register(self.draft_model, draft, draft_cfg,
                                       source="inline")
            self.registry.get(self.model).draft = self.draft_model
            if isinstance(draft_w, DecodeWeights):
                if draft_w.mesh is not None:
                    raise ValueError(
                        "speculative serving is single-device; prepare "
                        "the draft without a mesh")
                draft_w = draft_w.params
            if mesh is not None:
                raise ValueError(
                    "speculative serving is single-device (the per-row-"
                    "position propose/verify programs are not mesh-"
                    "threaded); serve the draft pair without a mesh")
            if weight_dtype != "native":
                raise ValueError(
                    "speculative serving requires weight_dtype='native': "
                    "the verify forward runs raw weights (prefill "
                    "numerics), which would not match a w8a16 decode path")
            if temperature != 0.0:
                raise ValueError(
                    "speculative serving is greedy-only (temperature 0); "
                    "the greedy-match acceptance rule has no sampled "
                    "counterpart here (models/speculative.py scope)")
            if draft_cfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft and target must share a vocabulary "
                    f"({draft_cfg.vocab_size} != {cfg.vocab_size})")
            if not draft_cfg.causal:
                raise ValueError("speculative decode requires a causal "
                                 "draft")
            self._draft_params = draft_w
            self._draft_cfg = moe_dropfree(draft_cfg)
            self._spec = True
        # ---- paged KV allocator (tentpole) ----
        # paged=True swaps the slots x max_len ring cache for a shared
        # pool of kv_block-sized blocks: each slot carries a block TABLE
        # instead of a private ring, dispatches run gather -> (unchanged
        # ring program) -> scatter on a ring-ordered transient view, and
        # admission is gated on free POOL blocks, so concurrency is
        # bounded by actual KV bytes rather than worst-case length.
        self._paged = bool(paged)
        self.kv_block = int(kv_block) if kv_block else 0
        self.kv_pool_blocks = int(kv_pool_blocks) if kv_pool_blocks else 0
        self.prefill_interleave = max(0, int(prefill_interleave))
        self.batch_queue_frac = float(batch_queue_frac)
        self._class_budgets = dict(class_budgets or {})
        if self._paged:
            if not self.kv_block:
                self.kv_block = int(block_size)
            if max_len % self.kv_block:
                raise ValueError(
                    f"max_len={max_len} must be a multiple of "
                    f"kv_block={self.kv_block} (a slot's table has "
                    f"max_len/kv_block entries)")
            if prefill_chunk % self.kv_block:
                raise ValueError(
                    f"prefill_chunk={prefill_chunk} must be a multiple "
                    f"of kv_block={self.kv_block} (chunk boundaries must "
                    f"land on block boundaries for zero-copy trie "
                    f"adoption)")
            if not self.kv_pool_blocks:
                # same device bytes as the ring it replaces
                self.kv_pool_blocks = slots * (max_len // self.kv_block)
            if mesh is not None:
                # the pool's block axis shards over the 'batch' mesh
                # axes like the ring cache's slot axis; round the pool
                # up so (blocks + pad) divides evenly
                t_b = _rule_size(mesh, rules, "batch")
                n1 = self.kv_pool_blocks + 1        # + the pad block
                self.kv_pool_blocks = -(-n1 // t_b) * t_b - 1
        else:
            if self.prefill_interleave:
                raise ValueError(
                    "prefill_interleave requires paged=True (the ring "
                    "engine prefills whole admissions up front)")
            if self._class_budgets:
                raise ValueError(
                    "class_budgets requires paged=True (budgets are "
                    "pool-block budgets)")
        # ---- disaggregated serving role (docs/serving.md) ----
        # "prefill" runs admission + chunked prefill only, then exports
        # the finished block table (export_blocks) and completes the
        # request with finish_reason="prefilled"; "decode"/"both" serve
        # normally ("decode" is advisory — the router's phase-aware
        # dispatch prefers it for import legs, but it can still serve a
        # full /generate as the replay fallback).
        self.role = str(role or "both")
        if self.role not in ("prefill", "decode", "both"):
            raise ValueError(
                f"unknown serving role {role!r} (expected 'prefill', "
                "'decode', or 'both')")
        if self.role == "prefill" and not self._paged:
            raise ValueError(
                "role='prefill' requires paged=True (the transfer unit "
                "is the paged KV block; see docs/serving.md "
                "'Disaggregated serving')")
        if self.role == "prefill" and self._spec:
            raise ValueError(
                "role='prefill' is incompatible with speculative "
                "serving (a prefill specialist never decodes, so a "
                "draft has nothing to propose against)")
        # finished prefill payloads awaiting router pickup (bounded
        # FIFO: an unclaimed handoff ages out and costs the decode side
        # a journal-replay re-prefill, never a lost request)
        self._exports: collections.OrderedDict[int, dict] = \
            collections.OrderedDict()
        self._exports_cap = 64
        self.kv_exports = 0             # payloads serialized (stats())
        self.kv_imports = 0             # payloads installed (stats())
        self.kv_import_rejects = 0      # torn/invalid payloads refused
        self.admission_dispatches = 0   # prefill programs dispatched
        # prefix-cache dispatch + token counters (stats())
        self.prefix_copy_dispatches = 0
        self.prefix_insert_dispatches = 0
        self.prefill_tokens_computed = 0    # real (non-pad) prefill tokens
        self.prefill_tokens_reused = 0      # served from the prefix pool
        # failure-model counters (stats()) — cumulative across reset()
        self.shed_requests = 0          # refused at submit (queue full)
        #                                 or displaced from the queue
        self.shed_by_class = {cls: 0 for cls in PRIORITY_CLASSES}
        # paged-KV counters (stats())
        self.admission_defers = 0       # admissions deferred on pool
        #                                 blocks / class budget
        self.paged_gather_dispatches = 0
        self.paged_scatter_dispatches = 0
        self.prefill_chunks_interleaved = 0  # chunks deferred by the
        #                                      per-decode interleave cap
        self.cancelled_requests = 0     # cancel() reached the request
        self.expired_requests = 0       # deadline passed while queued
        self.resets = 0                 # reset() calls (loop recoveries)
        self.blocks_dispatched = 0      # decode blocks sent to the device
        # the variants of the device programs this server has dispatched,
        # each noted once in observability.PROGRAMS (`_register`)
        self._programs_seen: set = set()
        # how far the decode kernel's live-range reads engage: KV blocks
        # a processed block's last step streamed / those its rings hold
        self.kv_blocks_read = 0
        self.kv_blocks_ring = 0
        # latent rows the processed decode blocks wrote in place as whole
        # lane tiles (`write_latent_rows`): rows x latent layers x steps
        # where the decode kernel engages, else 0
        self.latent_rows_in_place = 0
        # rows x hyper-connection sites (two a layer) x steps of the
        # processed decode blocks, an idle row's included (every row runs
        # through the streams); 0 without hyper-connections
        self.hyper_rows = 0
        # window layers, at a processed block's last step: the blocks of a
        # window ring it streamed and those its live rows' whole contexts
        # span; the positions those rows hold and those a window layer's
        # query sees (their least K/V a step)
        self.window_blocks_read = 0
        self.window_blocks_context = 0
        self.live_positions = 0
        self.window_positions = 0
        # rows whose recurrent state the processed decode blocks changed,
        # by the device's own account (0 without linear layers)
        self.state_rows = 0
        # how far the recurrence's live-row reads engage: rows whose state
        # a processed block's last step streamed / the slots (_recurrent)
        self.state_rows_read = 0
        self.state_rows_held = 0
        # routed expert layers (_routed), over the processed decode blocks
        # by the device's own account (`_decode_block`): the distinct
        # experts the live rows routed to, summed over steps and routed
        # layers; those whose weights the steps streamed (the grouped
        # matmul visits the experts SOME row routed to, an idle row's
        # stale token included); the experts held x routed layers x steps;
        # the most assignments one expert got at one step of one layer,
        # and all assignments of live rows (their mean an expert is
        # stats()["experts"]["tokens_mean"])
        self.experts_touched = 0
        self.experts_read = 0
        self.experts_held = 0
        self.expert_tokens_max = 0
        self.expert_assignments = 0
        self.max_queue = int(max_queue)
        # ---- request durability (events/journal.py) ----
        # the journal records every accepted request's replay state
        # (prompt, sampling params, emitted-so-far); reset() replays
        # journaled in-flight requests instead of failing them, and a
        # file-backed journal (serve --trace-dir) survives process death
        # for recover_journal(). replay=False keeps the pre-journal
        # fail-fast reset contract.
        self.replay = bool(replay)
        self._journal = (journal if journal is not None
                         else (RequestJournal() if self.replay else None))
        self.replays = 0                # admissions with a resume prefix
        self.replayed_tokens = 0        # teacher-forced resume tokens
        # ---- streaming delivery (tony_tpu/api/stream.py) ----
        # request id -> attached TokenStream: fed host-known tokens at
        # every PROCESSED decode block (the journal's durability point,
        # so a streamed prefix never runs ahead of what failover can
        # resume), finished at the terminal, failed on reset loss.
        # Streams survive reset() — a replayed request keeps its id and
        # the absolute-position feed dedupes the re-emitted prefix.
        self._streams: dict[int, object] = {}
        self.streams_opened = 0         # streams ever attached
        self.stream_stalls = 0          # feeds that found the chunk
        #                                 queue full (consumer behind)
        # ---- request-level telemetry (observability.py) ----
        # every submitted request carries a RequestTrace from submit to
        # its terminal span; finished traces feed the latency histograms,
        # the Retry-After service-rate EWMA, and (when set) trace_sink —
        # a callable given each terminated trace's dict (the serve CLI
        # wires events.trace.TraceWriter.write here). All host-side.
        self.telemetry = ServingTelemetry()
        self.trace_sink = trace_sink
        self._traces: dict[int, RequestTrace] = {}
        self._rate = ServiceRateEstimator()
        # device-time attribution (observability.DispatchTracker): every
        # dispatched program registers an output buffer and a background
        # reaper measures dispatch→ready per program kind off the hot
        # path; _process turns the recorded ready instants into the
        # measured device_lag on request traces. reset() re-arms it
        # (stale ready-instants never cross a reset); shutdown() stops
        # the thread.
        self.dispatch_tracker = DispatchTracker()
        # drain support: ServeApp.shutdown(drain=True) parks admission so
        # in-flight slots finish while nothing new starts
        self.pause_admission = False
        # chaos hooks: seeded fault injection on the serving hot path,
        # the serving-side analogue of the driver's TEST_* env knobs.
        # Read once at construction (a server's failure behavior should
        # not drift mid-run); bad values degrade to "off", never crash.
        self._chaos_fail_rate = self._env_float(
            c.TEST_SERVING_DISPATCH_FAIL_RATE)
        self._chaos_delay_ms = self._env_float(c.TEST_SERVING_STEP_DELAY_MS)
        self._chaos_rng = random.Random(
            int(self._env_float(c.TEST_SERVING_CHAOS_SEED)))
        self.chaos_faults_injected = 0
        # deterministic injection points for the replay harness: crash
        # the loop (or the whole process) at exact decode-block ordinals
        # — mid-decode by construction, reproducible by construction
        self._chaos_crash_blocks: set[int] = set()
        raw = os.environ.get(c.TEST_SERVING_CRASH_AT_BLOCKS, "")
        if raw:
            try:
                self._chaos_crash_blocks = {
                    int(x) for x in raw.replace(",", " ").split()}
            except ValueError:
                log.error("bad %s value %r; ignoring",
                          c.TEST_SERVING_CRASH_AT_BLOCKS, raw)
        self._chaos_sigkill_block = int(
            self._env_float(c.TEST_SERVING_SIGKILL_AT_BLOCK))
        self.cfg = moe_dropfree(cfg)
        self.slots = slots
        self.max_len = max_len
        self.block_size = block_size
        self.prefill_chunk = prefill_chunk
        self.kv_dtype = kv_dtype
        # the unit of the kv_blocks_read / kv_blocks_ring counts, and
        # whether the decode block's attention reads by it at all (a
        # latent layer's ring is one "head" of its row's width, read by
        # flash_decode_latent in that unit)
        latent = self.cfg.n_latent_layers > 0
        self._kv_block_k = kv_block_k(
            max_len, 1 if latent else self.cfg.n_kv_heads,
            self.cfg.lat_row_dim if latent else self.cfg.head_dim,
            1 if kv_dtype == "int8" else jnp.dtype(self.cfg.dtype).itemsize)
        self._decode_kernel = (self._shardings is None
                               and decode_kernel_engages(self.cfg, max_len))
        # a window layer's ring (max_len where no shorter one holds a
        # window and a chunk), its unit and its kernel gate
        self._window_ring = window_ring(self.cfg, max_len, prefill_chunk)
        self._win_block_k = kv_block_k(
            self._window_ring, self.cfg.n_kv_heads, self.cfg.head_dim,
            jnp.dtype(self.cfg.dtype).itemsize)
        self._win_kernel = (self._shardings is None and decode_kernel_engages(
            self.cfg, self._window_ring))
        # whether the decode block's recurrence reads the live rows only
        self._state_kernel = state_kernel_engages(
            1, self._shardings is not None)
        self.weight_dtype = weight_dtype
        self.temperature = temperature
        self.top_k = top_k
        self.stop_tokens = tuple(int(t) for t in stop_tokens)
        self.pad_id = int(pad_id)
        self._seed = int(seed)          # journaled: the sampling stream's
        #                                 origin (replay determinism doc)
        self._key = jax.random.PRNGKey(seed)

        self.pipeline_depth = pipeline_depth
        # without stop tokens every completion is deterministic (budgets
        # only), so the host schedules OPEN-LOOP: admission decisions come
        # from an exact host model and the emitted tokens are fetched in
        # one packed transfer at the end — zero mid-run syncs. With stop
        # tokens the host must observe the device to see EOS, so blocks
        # sync (in bursts) behind a pipeline of in-flight blocks.
        # Speculation also forces sync mode: a round advances each slot
        # by a VARIABLE accepted count the host can only learn by
        # observing the packed result — no exact open-loop model exists.
        self._predictive = not self.stop_tokens and not self._spec
        # ---- speculative-serving state (tentpole) ----
        # gamma autotune: per-slot acceptance-rate EWMA over recent
        # verify rounds steers the NEXT round's draft window — high
        # agreement widens it (more tokens per target weight stream),
        # low agreement shrinks it toward 1 (a failing draft costs one
        # wasted step, never correctness). The dispatched gamma is the
        # busy slots' mean EWMA mapped through the expected-run-length
        # rule a/(1-a), snapped to a power of two so the compiled
        # program set stays O(log gamma_max). spec_gamma pins it.
        self._spec_gamma_pin = max(0, int(spec_gamma))
        self.spec_gamma_max = max(1, int(spec_gamma_max))
        if self._spec_gamma_pin:
            self.spec_gamma_max = max(self.spec_gamma_max,
                                      self._spec_gamma_pin)
        self._spec_ewma_alpha = 0.2
        self._accept_ewma = np.full((slots,), 0.6, np.float64)
        self.spec_rounds = 0            # verify rounds dispatched
        self.spec_proposed_tokens = 0   # draft proposals verified (host-
        #                                 observed, lags by the pipeline)
        self.spec_accepted_tokens = 0   # ... accepted by the target
        self.draft_prefill_tokens_reused = 0  # draft prefill skipped by
        #                                 prefix hits (COW draft pool)
        self.spec_accept_hist = Histogram(lo=0.01, hi=1.0)
        self.spec_rounds_hist = Histogram(lo=1.0, hi=512.0, per_decade=4)
        self._init_device_state()
        # ---- chunk-aligned prefix cache (module docstring) ----
        self.cache_prompts = cache_prompts
        self._prefix_cache: PrefixCache | None = None
        self._pool: PrefixPool | None = None
        self._draft_pool: PrefixPool | None = None
        # request id -> matched trie path, ref-held until the completion
        # is processed
        self._prefix_refs: dict[int, list] = {}
        self._prefix_blocks = 0
        if prefix_cache_blocks > 0:
            n_blocks = prefix_cache_blocks
            if mesh is not None:
                # the pool's block axis shards where the slot axis does;
                # round the budget up to a whole number of shards
                t_b = _rule_size(mesh, rules, "batch")
                n_blocks = -(-n_blocks // t_b) * t_b
            self._prefix_blocks = n_blocks
            if not self._paged:
                self._init_prefix_pool()
        if self._paged:
            self._init_paged_state()
        self._init_host_state()
        self._queue: collections.deque[Request] = collections.deque()
        self._done: dict[int, Completion] = {}

    @staticmethod
    def _env_float(name: str) -> float:
        """A bad chaos knob must degrade to 'off', not crash the server
        at construction (same contract as the driver's TEST_* parsing)."""
        raw = os.environ.get(name, "")
        if not raw:
            return 0.0
        try:
            return float(raw)
        except ValueError:
            log.error("bad %s value %r; ignoring", name, raw)
            return 0.0

    def _init_device_state(self) -> None:
        """(Re)create the device-resident slot pool + per-slot state
        vectors as FRESH buffers (weights untouched) and commit their
        mesh layout. Called at construction and by ``reset()`` — after a
        failed dispatch the old donated buffers may be dead, so recovery
        must never reuse them."""
        slots = self.slots
        if self._paged:
            # paged mode: no monolithic ring cache — per-slot KV lives in
            # the block pool (_init_paged_state); _d_lens is the
            # device-carried per-slot length vector the ring cache's
            # .length field would otherwise hold
            self._cache = None
            self._d_lens = jnp.zeros((slots,), jnp.int32)
        else:
            cache = init_cache(self.cfg, slots, self.max_len, self.kv_dtype,
                               window_ring=self._window_ring)
            # device-carried slot state: blocks consume the previous
            # block's outputs directly, never waiting on a host round trip
            self._cache = cache._replace(
                length=jnp.zeros((slots,), jnp.int32))
        self._d_tokens = jnp.zeros((slots,), jnp.int32)   # next fed token
        self._d_active = jnp.zeros((slots,), bool)
        self._d_target = jnp.zeros((slots,), jnp.int32)   # stop length
        # ring layout: slot b's logical position p lives at buffer index
        # (p + offset_b) mod max_len; offsets are picked at admission so
        # every active slot's next write is at the shared global cursor
        self._d_offsets = jnp.zeros((slots,), jnp.int32)
        # its host mirror, current as of the newest dispatch: the paged
        # engine's gather/scatter authority, and what each decode block's
        # kv_blocks_read count is taken from (_count_kv_blocks)
        self._np_offs = np.zeros((slots,), np.int32)
        self._d_temps = jnp.zeros((slots,), jnp.float32)  # per-request
        self._d_topks = jnp.zeros((slots,), jnp.int32)    # per-request
        if self._spec:
            # the draft model mirrors the slot pool with its OWN cache
            # (its config's shape), kept in per-row logical lockstep
            # with the target: admission prefills both, every spec
            # round advances/rolls both to the same lengths. Paged mode
            # keeps the draft KV in a mirrored block pool instead
            # (_init_paged_state) — only the length vector lives here.
            if self._paged:
                self._draft_cache = None
                self._d_draft_lens = jnp.zeros((slots,), jnp.int32)
            else:
                dcache = init_cache(self._draft_cfg, slots, self.max_len,
                                    self.kv_dtype)
                self._draft_cache = dcache._replace(
                    length=jnp.zeros((slots,), jnp.int32))
        if self._shardings is not None:
            # commit the pool's initial layout so the first dispatch (and
            # every donated successor) already sits where the programs'
            # output constraints keep it
            sh = self._shardings
            if self._paged:
                self._d_lens = jax.device_put(self._d_lens, sh.act)
            else:
                self._cache = KVCache(
                    k=jax.device_put(self._cache.k, sh.cache),
                    v=jax.device_put(self._cache.v, sh.cache),
                    length=jax.device_put(self._cache.length, sh.act),
                    k_scale=(None if self._cache.k_scale is None
                             else jax.device_put(self._cache.k_scale,
                                                 sh.scale)),
                    v_scale=(None if self._cache.v_scale is None
                             else jax.device_put(self._cache.v_scale,
                                                 sh.scale)),
                )
            self._d_tokens = jax.device_put(self._d_tokens, sh.act)
            self._d_active = jax.device_put(self._d_active, sh.act)
            self._d_target = jax.device_put(self._d_target, sh.act)
            self._d_offsets = jax.device_put(self._d_offsets, sh.act)
            self._d_temps = jax.device_put(self._d_temps, sh.act)
            self._d_topks = jax.device_put(self._d_topks, sh.act)
            self._key = jax.device_put(
                self._key, jax.sharding.NamedSharding(
                    self._mesh, jax.sharding.PartitionSpec()))

    def _init_prefix_pool(self) -> None:
        """(Re)create the shared prefix pool's device blocks (fresh
        buffers; the host trie is rebuilt by the caller)."""
        self._pool = init_prefix_pool(
            self.cfg, self._prefix_blocks, self.prefill_chunk, self.kv_dtype)
        self._prefix_cache = PrefixCache(self._prefix_blocks,
                                         self.prefill_chunk)
        # speculative serving: the draft model's cache blocks ride the
        # SAME trie — each node dual-indexes a target-pool block and a
        # draft-pool block (same block id, two pools), so a prefix hit
        # seeds both caches and the draft prefills only the suffix too
        self._draft_pool = (
            init_prefix_pool(self._draft_cfg, self._prefix_blocks,
                             self.prefill_chunk, self.kv_dtype)
            if self._spec else None)

    def _init_paged_state(self) -> None:
        """(Re)create the paged-KV pool, allocator, and per-slot block
        tables. The pool carries ``kv_pool_blocks`` allocatable
        kv_block-sized blocks plus ONE pad block (the last index):
        unmapped table entries point at it, gathers read its zeros
        (positions the attention mask never weighs), and the scatter
        diverts any write aimed at it. The prefix trie, when enabled,
        shares the same allocator — cached prefixes and slot tables hold
        refs on the same physical blocks (COW without a writer)."""
        n = self.kv_pool_blocks
        self._kv_pool = init_prefix_pool(
            self.cfg, n + 1, self.kv_block, self.kv_dtype)
        # speculative serving: the draft model's KV rides a MIRROR pool
        # with the same block geometry — one allocator owns both, a slot
        # table indexes both, and a trie node's block id is valid in
        # both (the draft bytes for a token prefix are as
        # prefix-deterministic as the target's)
        self._draft_kv_pool = (
            init_prefix_pool(self._draft_cfg, n + 1, self.kv_block,
                             self.kv_dtype)
            if self._spec else None)
        self._allocator = BlockAllocator(n, self._class_budgets)
        entries = self.max_len // self.kv_block
        self._np_tables = np.full((self.slots, entries), n, np.int32)
        self._d_tables = jnp.asarray(self._np_tables)
        self._tables_dirty = False
        # per-slot write floors (host mirror, beside _np_offs). floor = the
        # lowest logical position the scatter may commit for the slot:
        # max_len (= never) while the slot is idle or mid-prefill,
        # body.size once activated — the decode program writes garbage
        # rows for inactive slots, and those must never land in a block
        # the trie might share.
        self._np_floor = np.full((self.slots,), self.max_len, np.int32)
        # slot -> exclusively-owned block ids (decode tail + cold-filled
        # prefix chunks; refcount-1 holders unless adopted by the trie)
        # and trie-shared block ids (prefix hits; we hold one ref each)
        self._slot_blocks: list[list] = [[] for _ in range(self.slots)]
        self._slot_shared: list[list] = [[] for _ in range(self.slots)]
        self._slot_class = ["interactive"] * self.slots
        # admissions whose blocks are allocated but whose prefill is not
        # finished: [admission, next_chunk_start] pairs, drained by
        # _pump_prefill under the interleave budget
        self._pending_prefill: collections.deque = collections.deque()
        if self._prefix_blocks > 0:
            self._prefix_cache = PrefixCache(
                self._prefix_blocks, self.kv_block,
                allocator=self._allocator)
        if self._shardings is not None:
            sh = self._shardings
            self._kv_pool = PrefixPool(
                k=jax.device_put(self._kv_pool.k, sh.cache),
                v=jax.device_put(self._kv_pool.v, sh.cache),
                k_scale=(None if self._kv_pool.k_scale is None else
                         jax.device_put(self._kv_pool.k_scale, sh.scale)),
                v_scale=(None if self._kv_pool.v_scale is None else
                         jax.device_put(self._kv_pool.v_scale, sh.scale)),
            )

    def _init_host_state(self) -> None:
        """(Re)zero the host-side scheduling state: sampling mirrors, the
        exact model, the processing expectations, slot ownership, and the
        in-flight pipeline. The request QUEUE is deliberately not touched
        — queued requests were never started and survive a reset()."""
        slots = self.slots
        # host mirrors of the admitted temps/top_ks: when every busy slot
        # is greedy (or on the server-global k), blocks dispatch the
        # argmax-only / static-threshold program variants
        self._np_temps = np.zeros((slots,), np.float32)
        self._np_topks = np.full((slots,), self.top_k, np.int32)
        # per-slot requested logprobs k (0 = off): any nonzero busy slot
        # flips the block dispatch onto the lp-carrying program variant
        self._np_lp = np.zeros((slots,), np.int32)
        self._cursor = 0        # host-tracked, advances block per dispatch
        # exact host model of the device slot state as of the NEWEST
        # dispatched block — usable for scheduling only in predictive mode
        # (EOS can flip a slot inactive without the model knowing)
        self._model_len = np.zeros((slots,), np.int32)
        self._model_active = np.zeros((slots,), bool)
        self._model_target = np.zeros((slots,), np.int32)
        # bookkeeping expectations: the device state after the newest
        # PROCESSED block (+ replayed admissions); lags the device
        self._expect_len = np.zeros((slots,), np.int32)
        self._expect_active = np.zeros((slots,), bool)
        # busy from admission until the completion is PROCESSED
        self._host_busy = np.zeros((slots,), bool)
        # dispatched-but-unprocessed blocks: lazy packed results + the
        # admissions/cancellations dispatched after each
        self._pipeline: collections.deque = collections.deque()
        # processing-side slot ownership (replayed in dispatch order, so a
        # slot re-admitted while its previous request's blocks are still
        # unprocessed never mixes the two streams)
        self._requests: list[Request | None] = [None] * slots
        self._emitted: list[list[int]] = [[] for _ in range(slots)]
        # per-slot accumulated logprob entries, in lockstep with
        # _emitted (only populated while the slot's request asked)
        self._lp_acc: list[list] = [[] for _ in range(slots)]
        # the experts chosen, for a request that asked (Request.routes):
        # per slot the decode steps' [n, layers, k] pieces in order; per
        # request id the prefill's (device array, row, valid positions)
        # pieces, read only when the completion is built
        self._route_acc: list[list] = [[] for _ in range(slots)]
        self._route_prefill: dict[int, list] = {}
        # slots completed by a per-request STOP match whose device-side
        # deactivation hasn't been observed yet: blocks dispatched
        # before the cancel program still show the row active, and the
        # bookkeeping must keep skipping it until a block shows it
        # inactive — or an admit event re-occupies it for a new request
        self._stop_cancelled: set[int] = set()
        # dispatch-side views: which slot is CURRENTLY serving a request
        # id (cancel targeting — _requests lags by the pipeline depth),
        # and every admitted id whose completion hasn't been delivered
        # (reset() fails exactly these)
        self._slot_of: dict[int, int] = {}
        self._inflight: set[int] = set()
        # per-request speculative tallies (verify rounds + accepted
        # tokens), reset at each admission, observed at the completion
        # into spec_rounds_hist and the trace attrs
        self._spec_round_counts = np.zeros((slots,), np.int64)
        self._spec_accepted_counts = np.zeros((slots,), np.int64)

    # ------------------------------------------------------------- intake

    def submit(self, request: Request) -> int:
        prompt = np.asarray(request.prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        if request.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if prompt.size + request.max_new_tokens > self.max_len:
            raise ValueError(
                f"request needs {prompt.size} prompt + "
                f"{request.max_new_tokens} new tokens but slots hold "
                f"max_len={self.max_len}")
        if self._spec and request.temperature is not None \
                and float(request.temperature) > 0:
            raise ValueError(
                "speculative serving is greedy-only: per-request "
                "temperature overrides > 0 are rejected (the greedy-"
                "match acceptance rule has no sampled counterpart)")
        if request.model is not None and request.model != self.model:
            raise ValueError(
                f"request names model {request.model!r} but this engine "
                f"serves {self.model!r} (the ServeApp routes by model)")
        if request.stop is not None:
            request.stop = _normalize_stop(request.stop)
        request.logprobs = int(request.logprobs or 0)
        if not 0 <= request.logprobs <= LOGPROBS_MAX:
            raise ValueError(
                f"logprobs must be in [0, {LOGPROBS_MAX}]")
        if request.logprobs and self._spec:
            raise ValueError(
                "logprobs are unavailable under speculative serving "
                "(rejected drafts have no per-token logits rows)")
        request.routes = bool(request.routes)
        if request.routes and not self._routed:
            raise ValueError(
                "routes were asked of a config without routed expert "
                "layers: there are none to report")
        resume = request.resume_tokens
        if resume is not None:
            resume = [int(t) for t in np.asarray(resume, np.int32)]
            request.resume_tokens = resume
        tr = RequestTrace(request.id)
        tr.mark("submitted")
        # bind the distributed-trace identity BEFORE any early exit —
        # a shed or resume-satisfied request must still land in its
        # originating cross-tier trace
        ctx = request.trace if isinstance(request.trace, TraceContext) \
            else TraceContext.from_dict(request.trace)
        if ctx is not None:
            tr.bind(ctx)
            tr.attrs["service"] = "serve"
        if resume:
            tr.attrs["resume_tokens"] = len(resume)
            # a prefix that already satisfies the request (budget
            # reached, it ends in a stop token, or it completes a
            # per-request stop sequence) is a finished completion
            # someone failed to deliver — deliver it now, without a
            # slot, a prefill, or a decode step
            stop_end = bool(self.stop_tokens) and resume[-1] in \
                self.stop_tokens
            seq_end = _stop_match_end(resume, request.stop) \
                if request.stop else None
            if (len(resume) >= request.max_new_tokens or stop_end
                    or seq_end is not None):
                if seq_end is not None and \
                        seq_end <= request.max_new_tokens:
                    resume = resume[:seq_end]
                    stop_end = True
                toks = resume[:request.max_new_tokens]
                reason = "stop" if stop_end and toks and (
                    seq_end is not None
                    or toks[-1] in self.stop_tokens) else "length"
                self.replays += 1
                self.replayed_tokens += len(toks)
                self._traces[request.id] = tr
                self._done[request.id] = Completion(
                    request.id, toks, reason,
                    trace=self._finish_trace(request.id, "finished",
                                             n_tokens=len(toks),
                                             reason=reason))
                if self._journal is not None:
                    self._journal.finish(request.id)
                return request.id
        cls = str(request.priority or "interactive")
        if cls not in PRIORITY_CLASSES:
            raise ValueError(
                f"unknown priority {request.priority!r} "
                f"(valid: {PRIORITY_CLASSES})")
        request.priority = cls
        if self.max_queue:
            # shed at the door: an unbounded queue converts overload into
            # unbounded latency for EVERY admitted request; a bounded one
            # keeps admitted-request latency flat and tells the excess to
            # retry (HTTP 429 upstream). The batch tier backs off at a
            # LOWER threshold (batch_queue_frac of max_queue) so overload
            # sheds throughput work first and keeps queue headroom for
            # interactive arrivals.
            limit = self.max_queue
            if cls != "interactive":
                limit = max(1, int(self.max_queue * self.batch_queue_frac))
            if len(self._queue) >= limit:
                # Sweep expired corpses first — a queue full of requests
                # whose deadlines already passed is capacity the next
                # _admit would reclaim anyway, not load
                self._sweep_expired()
                if len(self._queue) >= limit and cls == "interactive":
                    # full queue, best tier: displace the youngest queued
                    # batch request instead of shedding the arrival
                    self._shed_queued_batch()
                if len(self._queue) >= limit:
                    self.shed_requests += 1
                    self.shed_by_class[cls] += 1
                    # a shed request still leaves a (two-span) trace:
                    # shedding must be as visible per-request as it is in
                    # the counters
                    self._seal_trace(tr, "shed")
                    err = QueueFullError(
                        f"queue full ({limit} {cls} waiting); "
                        f"request shed")
                    # ride the estimate on the error: the 429 handler
                    # already holds whatever lock guards this server —
                    # making it call back for the header would buy a
                    # second lock wait on the shed fast path, at peak load
                    err.retry_after_s = self.estimate_retry_after()
                    err.priority = cls
                    raise err
        request.prompt = prompt
        self._traces[request.id] = tr
        if self._journal is not None:
            # the journal entry's prompt is the ORIGINAL prompt; a
            # resume prefix pre-seeds the emitted record, so a second
            # failure replays from the full known prefix
            self._journal.submit(
                request.id, prompt.tolist(), request.max_new_tokens,
                temperature=request.temperature, top_k=request.top_k,
                cache_prompt=request.cache_prompt, seed=self._seed,
                deadline=request.deadline, emitted=resume,
                model=self.model,
                stop=[list(s) for s in request.stop]
                if request.stop else None,
                logprobs=request.logprobs,
                priority=request.priority,
                trace=ctx.as_dict() if ctx is not None else None)
        self._queue.append(request)
        return request.id

    def _shed_queued_batch(self) -> bool:
        """Displace the YOUNGEST queued batch-tier request to make room
        for an interactive arrival: it gets an empty
        Completion("shed") — it never reached a slot, so there is no
        partial work to deliver — and its waiter/stream unblocks with
        the same backpressure signal a submit-time shed raises (the
        ServeApp maps the reason to HTTP 429 + Retry-After). Youngest
        first: the most recently queued request has waited least, so
        displacing it wastes the least invested queue time."""
        for i in range(len(self._queue) - 1, -1, -1):
            req = self._queue[i]
            if req.priority == "interactive":
                continue
            del self._queue[i]
            self.shed_requests += 1
            self.shed_by_class[req.priority] += 1
            self._done[req.id] = Completion(
                req.id, [], "shed",
                trace=self._finish_trace(req.id, "shed"))
            self._finish_stream(req.id)
            if self._journal is not None:
                self._journal.finish(req.id)
            return True
        return False

    def _sweep_expired(self) -> None:
        """Deadline sweep: a request whose client already gave up must
        not take a slot (or hold a queue seat) — prefill + decode for a
        dead waiter is the purest form of wasted accelerator time under
        overload. Expired requests complete as "expired"."""
        if not self._queue:
            return
        now = time.monotonic()
        if not any(r.deadline is not None and now > r.deadline
                   for r in self._queue):
            return
        kept: collections.deque[Request] = collections.deque()
        for req in self._queue:
            if req.deadline is not None and now > req.deadline:
                self.expired_requests += 1
                # a queued REPLAY still owns its emitted prefix (same
                # contract as the queued-cancel path): those tokens were
                # delivered decode work, not queue residue
                out = [int(t) for t in (req.resume_tokens or ())]
                self._done[req.id] = Completion(
                    req.id, out, "expired",
                    trace=self._finish_trace(req.id, "expired",
                                             n_tokens=len(out)))
                self._finish_stream(req.id)
                if self._journal is not None:
                    self._journal.finish(req.id)
            else:
                kept.append(req)
        self._queue = kept

    def cancel(self, request_id: int) -> bool:
        """Stop a request wherever it is. Queued: dequeued (never takes a
        slot). Admitted (prefilling or decoding): the slot's device-side
        active flag drops between blocks — dispatch order is device
        order, so every block dispatched before the cancel still decodes
        it and every later block sees an idle row — and the cancellation
        is logged against the newest in-flight block so the lagging
        bookkeeping frees the slot, emits a Completion(finish_reason=
        "cancelled") with the tokens produced so far, and unpins any
        matched prefix-cache path at exactly the right replay position.
        Returns False when the request is unknown or already finished
        (its completion is on its way — too late to save the work). In
        EOS mode the host cannot see an un-synced device stop, so a True
        can race a natural completion; the delivered finish_reason is
        authoritative (the counter reconciles at replay)."""
        for i, req in enumerate(self._queue):
            if req.id == request_id:
                del self._queue[i]      # by index: Request's array field
                #                         makes == comparisons ambiguous
                self.cancelled_requests += 1
                # a queued REPLAY still owns its emitted prefix: those
                # tokens were delivered work, not queue residue
                out = [int(t) for t in (req.resume_tokens or [])]
                self._done[request_id] = Completion(
                    request_id, out, "cancelled",
                    trace=self._finish_trace(request_id, "cancelled",
                                             n_tokens=len(out)))
                self._finish_stream(request_id)
                if self._journal is not None:
                    self._journal.finish(request_id)
                return True
        if self._paged:
            # mid-prefill under chunked interleaving: the request holds
            # blocks and a slot but no decode has started — drop the
            # pending chunks and free the blocks promptly (the next
            # admission sweep can reuse them immediately)
            for i, pend in enumerate(self._pending_prefill):
                adm = pend[0]
                if adm.req.id != request_id:
                    continue
                del self._pending_prefill[i]
                self.cancelled_requests += 1
                self._host_busy[adm.slot] = False
                out = [int(t) for t in (adm.req.resume_tokens or [])]
                self._done[request_id] = Completion(
                    request_id, out, "cancelled",
                    trace=self._finish_trace(request_id, "cancelled",
                                             n_tokens=len(out)))
                self._finish_stream(request_id)
                self._release_request(request_id)
                return True
        slot = self._slot_of.get(request_id)
        if slot is None:
            return False
        if self._predictive and not self._model_active[slot]:
            return False        # already decoded to completion on device
        self._d_active = _cancel_slot(self._d_active, jnp.int32(slot),
                                      shardings=self._shardings)
        self._model_active[slot] = False
        self.cancelled_requests += 1
        ev = ("cancel", (slot, request_id))
        if self._pipeline:
            self._pipeline[-1]["events"].append(ev)
        else:                   # nothing in flight: applies now
            self._apply_cancel((slot, request_id))
        return True

    def reset(self) -> list[int]:
        """Re-arm the serving state after a loop failure WITHOUT touching
        the weights: fresh KV ring + slot-state buffers (a failed dispatch
        may have killed the donated old ones), fresh prefix pool + trie,
        pipeline and slot bookkeeping cleared. Queued requests survive —
        they were never started.

        Admitted-but-undelivered requests are REPLAYED when the journal
        is on (the default): their cache state died with the ring, but
        the journal holds everything an exact continuation needs — the
        prompt and the emitted-so-far prefix — so each is re-queued
        (ahead of the never-started queue, preserving admission order)
        with ``resume_tokens`` for a teacher-forced re-prefill + resumed
        decode. Unprocessed in-flight blocks re-decode (replay recompute
        is bounded by one re-prefill of the known prefix plus the
        pipeline-lag re-decode); greedy continuations are byte-identical
        to an uninterrupted run. Only ids with no journal entry (or with
        ``replay=False``) are returned as lost so the caller can fail
        them upstream instead of letting their waiters hang."""
        failed: list[int] = []
        replay_reqs: list[Request] = []
        for rid in sorted(self._inflight):
            entry = (self._journal.get(rid)
                     if self.replay and self._journal is not None else None)
            if entry is None:
                failed.append(rid)  # traces end here, not in a leak
                self._finish_trace(rid, "failed")
                self.fail_stream(
                    rid, f"request {rid} lost to a serving-loop failure "
                         "(no journal entry to replay)")
                if self._journal is not None:
                    self._journal.finish(rid)
                continue
            emitted = list(entry.emitted)
            stop_end = bool(self.stop_tokens) and bool(emitted) and \
                emitted[-1] in self.stop_tokens
            seq_end = _stop_match_end(emitted, entry.stop) \
                if entry.stop else None
            if (len(emitted) >= entry.max_new_tokens or stop_end
                    or seq_end is not None):
                # fully emitted but undelivered (the crash landed between
                # the finishing block's processing and delivery): deliver
                # the journaled stream, don't re-decode past the budget.
                # A journaled per-request stop match counts as fully
                # emitted the same way (the journal is truncated at the
                # match, so this only fires for pre-seeded prefixes).
                if seq_end is not None and seq_end <= entry.max_new_tokens:
                    emitted = emitted[:seq_end]
                    stop_end = True
                toks = emitted[:entry.max_new_tokens]
                self.replays += 1
                self.replayed_tokens += len(toks)
                self._done[rid] = Completion(
                    rid, toks, "stop" if stop_end else "length",
                    trace=self._finish_trace(
                        rid, "finished", n_tokens=len(toks),
                        reason="stop" if stop_end else "length"))
                self._finish_stream(rid)
                self._journal.finish(rid)
                continue
            tr = self._traces.get(rid)
            if tr is not None:
                tr.mark("replayed")
                tr.attrs["replays"] = int(tr.attrs.get("replays", 0)) + 1
                tr.attrs["replayed_tokens"] = len(entry.emitted)
            replay_reqs.append(Request(
                prompt=np.asarray(entry.prompt, np.int32),
                max_new_tokens=entry.max_new_tokens,
                temperature=entry.temperature, top_k=entry.top_k,
                cache_prompt=entry.cache_prompt, deadline=entry.deadline,
                resume_tokens=list(entry.emitted),
                stop=[list(s) for s in entry.stop]
                if entry.stop else None,
                logprobs=int(getattr(entry, "logprobs", 0) or 0),
                priority=str(getattr(entry, "priority", None)
                             or "interactive"),
                id=rid))
        self._prefix_refs.clear()
        # drop pending dispatch-tracker entries WITHOUT blocking on them
        # (their buffers may have died with the failed dispatch) and
        # re-arm the same reaper thread: no stale ready-instant can be
        # attributed to a post-reset dispatch, and resets never leak
        # threads. The cumulative dispatch→ready histograms survive,
        # same as the latency telemetry.
        self.dispatch_tracker.reset()
        self._init_device_state()
        if self._prefix_blocks and not self._paged:
            self._init_prefix_pool()
        if self._paged:
            # fresh pool + allocator + tables (the old donated pool may
            # be dead); pending prefills' requests are in _inflight, so
            # they replay with everything else
            self._init_paged_state()
        self._init_host_state()
        # replays go AHEAD of the never-started queue: they were
        # admitted first, and their waiters have been waiting longest
        for req in reversed(replay_reqs):
            self._queue.appendleft(req)
        self.resets += 1
        return failed

    def recover_journal(self, entries, compact: bool = True) -> int:
        """Resubmit another process's unfinished journal entries (see
        ``RequestJournal.recover``) as fresh requests resuming from
        their recorded prefixes — ``serve`` startup calls this so a
        SIGKILLed replica's budgeted restart finishes the dead
        process's requests. Fresh ids (the dead process's id namespace
        is gone with its waiters); ``attrs.recovered_from`` keeps the
        lineage on the trace. Returns how many were resubmitted;
        entries the bounded queue or validation refuses are logged and
        dropped, never fatal to startup. Once the resubmissions are
        journaled, the file compacts down to the live set — the dead
        process's records were the only copy until now, so dropping
        them earlier would lose requests on a crash mid-restart
        (post-compaction a double fault replays twice, never loses).

        Note the deliberate trade-off behind a router: the failover
        path may ALREADY have resumed these requests on another
        replica, so the restarted one can duplicate that decode work —
        completions with no waiter are recorded (traces/metrics/
        journal seal) and dropped. The journal cannot know whether a
        front door exists; finishing the recovered set is the
        durability contract, and it is bounded by the dead process's
        in-flight+queued set."""
        n = 0
        # recovery is exempt from max_queue: these requests were ALL
        # accepted by the dead process (its own queue bound admitted
        # them), so re-accepting them restores prior state rather than
        # taking new load — shedding here would drop up to `slots`
        # entries and the compaction below would erase the only durable
        # copy. Transient overshoot is bounded by the dead process's
        # slots and self-drains.
        saved_max_queue = self.max_queue
        self.max_queue = 0
        try:
            for entry in entries:
                req = Request(
                    prompt=np.asarray(entry.prompt, np.int32),
                    max_new_tokens=entry.max_new_tokens,
                    temperature=entry.temperature, top_k=entry.top_k,
                    cache_prompt=entry.cache_prompt,
                    resume_tokens=list(entry.emitted),
                    stop=[list(s) for s in entry.stop]
                    if entry.stop else None,
                    logprobs=int(getattr(entry, "logprobs", 0) or 0),
                    priority=str(getattr(entry, "priority", None)
                                 or "interactive"),
                    # reuse the dead attempt's EXACT span identity: the
                    # killed process may never have sealed its record,
                    # so minting a child here would orphan the subtree.
                    # If both records do land, the merge-time fence
                    # (TraceCollector) keeps the richer one.
                    trace=getattr(entry, "trace", None))
                try:
                    rid = self.submit(req)
                except ValueError as e:
                    # malformed beyond serving (shape drift across a
                    # version boundary): no future recovery could serve
                    # it either — dropping it from the compacted file
                    # is correct, but say so loudly
                    log.error("journal recovery dropped request %s "
                              "(unservable): %s", entry.id, e)
                    continue
                tr = self._traces.get(rid)
                if tr is not None:
                    tr.attrs["recovered_from"] = entry.id
                n += 1
        finally:
            self.max_queue = saved_max_queue
        if compact and self._journal is not None:
            # the resubmitted live set is durable: drop the dead
            # process's records now (see RequestJournal.compact).
            # ``compact=False`` defers this for callers recovering ONE
            # SHARED journal across several engines (multi-model serve):
            # compacting after the first engine's resubmission would
            # erase the only durable copy of the OTHER engines'
            # still-unrecovered entries — they compact once, at the end.
            self._journal.compact()
        return n

    def shutdown(self) -> None:
        """Stop the background dispatch-reaper thread (idempotent). The
        server remains usable for host-side queries afterwards, but no
        further dispatch→ready observations are recorded — call at
        process teardown (``ServeApp.shutdown`` does)."""
        self.dispatch_tracker.shutdown()
        if self._journal is not None:   # flush+close a file-backed journal
            self._journal.close()

    # --------------------------------------------------------- streaming

    def attach_stream(self, request_id: int, stream) -> None:
        """Register a per-request token channel (``api.stream.
        TokenStream``-shaped: ``feed(emitted)``, ``finish(reason)``,
        ``fail(message)``). Call under the serving lock, immediately
        after ``submit()`` (``ServeApp.submit_async`` does) — a request
        that already completed at submit (a resume prefix satisfying
        its budget) is delivered through the stream right here."""
        self.streams_opened += 1
        comp = self._done.get(request_id)
        if comp is not None:
            try:
                stream.feed(comp.tokens)
                stream.finish(comp.finish_reason)
            except Exception:
                log.exception("token stream attach-finish failed")
            return
        self._streams[request_id] = stream

    def fail_stream(self, request_id: int, message: str) -> None:
        """Terminal-error a request's stream WITHOUT a completion (the
        caller delivered a hard failure upstream — restart-budget
        exhaustion, drain timeout, replay-off reset loss). Idempotent;
        unknown ids are a no-op."""
        s = self._streams.pop(request_id, None)
        if s is not None:
            try:
                s.fail(str(message))
            except Exception:
                log.exception("token stream fail() failed")

    @property
    def streams_active(self) -> int:
        return len(self._streams)

    def _stream_feed(self, rid, emitted) -> None:
        """Push a request's absolute emitted-token list into its
        attached stream (no-op without one). The stream appends only
        the unseen suffix, so replays/resumes never double-deliver.
        Called at processing time — the journal's durability point."""
        s = self._streams.get(rid)
        if s is None:
            return
        try:
            n_new, stalled = s.feed(emitted)
        except Exception:       # delivery must never kill the loop
            log.exception("token stream feed failed")
            return
        if n_new:
            now = time.monotonic()
            if s.last_feed_t is not None:
                self.telemetry.observe("stream_itl_s",
                                       max(0.0, now - s.last_feed_t))
            s.last_feed_t = now
            if stalled:
                self.stream_stalls += 1

    def _finish_stream(self, rid: int) -> None:
        """Seal a request's stream from its Completion (every terminal
        that builds one calls this right after storing ``_done[rid]``)."""
        s = self._streams.pop(rid, None)
        if s is None:
            return
        comp = self._done.get(rid)
        try:
            if comp is not None:
                s.feed(comp.tokens)
                s.finish(comp.finish_reason)
            else:               # defensive: no completion -> hard error
                s.fail(f"request {rid} terminated without a completion")
        except Exception:
            log.exception("token stream finish failed")

    def seal_journal(self, request_id: int) -> None:
        """Seal a request's journal entry WITHOUT a completion: the
        caller delivered a terminal error upstream (restart-budget
        exhaustion, drain-timeout — the trace/HTTP 'failed' contract),
        so a later journal recovery must not resurrect and re-decode a
        request its client already saw fail. Idempotent; no-op with the
        journal off. (``ServeApp._fail_pending`` calls this.)"""
        if self._journal is not None:
            self._journal.finish(request_id)

    def fail_queued(self) -> list[Request]:
        """Drain the wait queue (requests never admitted) — the graceful-
        shutdown path: the caller owns telling their waiters why."""
        out = list(self._queue)
        self._queue.clear()
        for req in out:
            self._finish_trace(req.id, "failed")
            self.fail_stream(
                req.id, f"request {req.id} failed: server shutting down "
                        "before it was admitted")
            if self._journal is not None:
                self._journal.finish(req.id)
        return out

    def _release_request(self, request_id: int) -> None:
        """Drop the dispatch-side tracking of a finished/cancelled
        request, unpin its matched prefix-cache path, free its paged-KV
        blocks, and seal its journal entry (no replay after a delivered
        terminal)."""
        slot = self._slot_of.pop(request_id, None)
        self._inflight.discard(request_id)
        self._route_prefill.pop(request_id, None)
        if self._paged and slot is not None:
            # the id still OWNED the slot: a predictive re-admission
            # would have superseded the _slot_of mapping (and freed the
            # blocks) already, so this never double-frees
            self._free_slot_blocks(slot)
        path = self._prefix_refs.pop(request_id, None)
        if path is not None:
            self._prefix_cache.release(path)
        if self._journal is not None:
            self._journal.finish(request_id)

    # -------------------------------------------------------------- tracing

    def _seal_trace(self, tr: RequestTrace, terminal: str, *,
                    n_tokens: int = 0, reason: str | None = None) -> dict:
        """Close a trace with its terminal span, feed the latency
        histograms and (for requests that actually held a slot) the
        Retry-After service-rate EWMA, and hand the record to the sink.
        Returns the dict that rides ``Completion.trace``."""
        tr.attrs["n_tokens"] = n_tokens
        tr.attrs["finish_reason"] = reason if reason is not None else terminal
        tr.mark(terminal)
        self.telemetry.observe_trace(tr)
        svc = tr.dur("admitted", terminal)
        if svc is not None and svc >= 0:
            self._rate.observe(svc)
        record = tr.to_dict()
        if self.trace_sink is not None:
            try:        # telemetry must never take down the serving loop
                self.trace_sink(record)
            except Exception:
                log.exception("trace sink failed")
        return record

    def _finish_trace(self, request_id: int, terminal: str, *,
                      n_tokens: int = 0,
                      reason: str | None = None) -> dict | None:
        tr = self._traces.pop(request_id, None)
        if tr is None:          # engine driven without traces (reset races)
            return None
        return self._seal_trace(tr, terminal, n_tokens=n_tokens,
                                reason=reason)

    def progress(self, request_id: int) -> dict | None:
        """Replay-state snapshot of a LIVE request — the serve
        ``GET /progress`` payload a router's failover resume rides:
        the emitted-so-far prefix (host-processed tokens) plus the
        prompt length. None for unknown/terminal ids (the journal
        entry is sealed at the terminal) or with the journal off.
        Call under the serving lock (``ServeApp`` does)."""
        if self._journal is None:
            return None
        entry = self._journal.get(request_id)
        if entry is None:
            return None
        return {"tokens": list(entry.emitted),
                "prompt_tokens": len(entry.prompt)}

    def estimate_retry_after(self) -> int:
        """Data-driven ``Retry-After``: seconds until a queue seat frees,
        from the EWMA service time of recently served requests and the
        current backlog — clamped to [1, 60] integer seconds, monotone
        in queue depth (observability.ServiceRateEstimator)."""
        return self._rate.retry_after_s(len(self._queue), self.slots)

    @property
    def pending(self) -> int:
        return len(self._queue)

    @property
    def idle(self) -> bool:
        """Nothing queued, in flight, admitted-and-unfinished, or
        finished-but-undrained. The last term matters after a reset():
        completions that survived the failure sit in _done with no block
        ever coming — a serving loop that gates its drain on ``not idle``
        must keep turning until they are handed out, or their waiters
        hang to their timeouts."""
        return not (self._queue or self._pipeline
                    or self._host_busy.any() or self._done)

    @property
    def completions_ready(self) -> bool:
        """True when drain_completed() would (or could, after syncing)
        return something — lets a live serving loop avoid the predictive
        mode's forced sync on every tick (which would serialize device
        compute with the host round trip open-loop scheduling exists to
        hide). In predictive mode the model knows a request finished
        before its tokens are synced: busy slot, model says inactive."""
        if self._done:
            return True
        if self._predictive:
            return bool((self._host_busy & ~self._model_active).any())
        return False

    @property
    def n_active(self) -> int:
        """Slots holding an unfinished request (admission through
        processed completion; in-flight blocks may have finished some —
        the view lags by up to pipeline_depth blocks)."""
        return int(self._host_busy.sum())

    def slot_states(self, layer: int = 0) -> dict:
        """Host copies of what every slot holds of linear layer ``layer``
        (counted among the linear layers): ``length`` [S] (tokens the slot
        has consumed), ``state`` [S, H, d_k, d_v] float32 and ``conv`` [S,
        taps - 1, channels]. A finished request's slot keeps them as its
        last step left them until an admission reuses it
        (``Completion.trace["attrs"]["slot"]`` names the slot). Call it
        where nothing else steps the engine (the serving loop stopped, or
        under its lock): a dispatch donates the buffers read here. Blocks
        still in flight are waited for; they leave a frozen row as it is."""
        if not self._recurrent:
            raise ValueError("the config has no linear layers")
        cache = self._cache
        return {"length": np.asarray(cache.length),
                "state": np.asarray(cache.state[layer]),
                "conv": np.asarray(cache.conv[layer])}

    def slot_latent_rows(self, layer: int = 0) -> dict:
        """Host copies of what every slot holds of latent layer ``layer``
        (counted among the latent layers), in LOGICAL order: ``length``
        [S] and ``rows`` [S, max_len, R], row p of a slot its position p's
        [c_kv | k_r] (the ring turned back by the slot's offset; rows at
        and past the length are whatever the ring held). As `slot_states`:
        call it where nothing else steps the engine."""
        if not self.cfg.n_latent_layers:
            raise ValueError("the config has no latent layers")
        cache = self._cache
        idx = (np.arange(self.max_len)[None, :]
               + np.asarray(self._d_offsets)[:, None]) % self.max_len
        rows = np.asarray(cache.latent[layer])
        return {"length": np.asarray(cache.length),
                "rows": np.take_along_axis(rows, idx[..., None], axis=1)}

    def stats(self) -> dict:
        """Serving-load + prefix-cache counters, one flat snapshot (the
        ServeApp /stats payload and MetricsAccumulator feed). Token
        counters measure the prefill economy: ``prefill_tokens_reused``
        never touched the MXU — they were copied out of the shared pool —
        vs ``prefill_tokens_computed`` that ran the model."""
        out = {
            "model": self.model,
            "role": self.role,
            "registry": self.registry.names(),
            "slots": self.slots,
            "active": self.n_active,
            "queued": self.pending,
            "max_len": self.max_len,
            "block_size": self.block_size,
            "max_queue": self.max_queue,
            "admission_dispatches": self.admission_dispatches,
            "blocks_dispatched": self.blocks_dispatched,
            "prefill_tokens_computed": self.prefill_tokens_computed,
            "prefill_tokens_reused": self.prefill_tokens_reused,
            "latent_rows_in_place": self.latent_rows_in_place,
            "hyper_rows": self.hyper_rows,
            # failure-model counters: recovery/shedding must be VISIBLE
            # (a server that silently sheds reads as a server that lost
            # requests)
            "shed": self.shed_requests,
            "shed_by_class": dict(self.shed_by_class),
            "cancelled": self.cancelled_requests,
            "expired": self.expired_requests,
            "resets": self.resets,
            # request durability: how often death became latency instead
            # of a failed request, and how many emitted tokens were
            # carried across the boundary
            "replays": self.replays,
            "replayed_tokens": self.replayed_tokens,
            # streaming delivery: live per-request token channels plus
            # the backpressure accounting (stalls = feeds that found the
            # consumer's chunk queue full; coalesced, never dropped)
            "streams_active": self.streams_active,
            "streams_opened": self.streams_opened,
            "stream_stalls": self.stream_stalls,
            "chaos_faults_injected": self.chaos_faults_injected,
            # latency telemetry: per-histogram count + p50/p90/p99 (host-
            # monotonic; see docs/observability.md for the span schema)
            "latency": self.telemetry.snapshot(),
            "retry_after_s": self.estimate_retry_after(),
            # device-time attribution: per-kind dispatch→ready quantiles
            # + the measured in-flight dispatch depth (the real pipeline
            # depth, vs the host bookkeeping's documented bound)
            "device": self.dispatch_tracker.snapshot(),
        }
        if self._spec:
            out["speculative"] = {
                "draft_model": self.draft_model,
                "gamma": self._current_gamma(),
                "gamma_pinned": bool(self._spec_gamma_pin),
                "gamma_max": self.spec_gamma_max,
                "rounds": self.spec_rounds,
                "proposed_tokens": self.spec_proposed_tokens,
                "accepted_tokens": self.spec_accepted_tokens,
                "draft_prefill_tokens_reused":
                    self.draft_prefill_tokens_reused,
                "acceptance_ewma": round(
                    float(self._accept_ewma.mean()), 4),
                "acceptance": self.spec_accept_hist.snapshot(),
                "verify_rounds_per_request":
                    self.spec_rounds_hist.snapshot(),
            }
        if self._recurrent:
            c = self.cfg
            out["recurrent_state"] = {
                "bytes_resident": self.slots * c.n_linear_layers * (
                    4 * c.lin_heads * c.lin_key_dim * c.lin_value_dim
                    + (c.lin_conv - 1) * c.lin_channels
                    * jnp.dtype(c.dtype).itemsize),
                "rows_advanced": self.state_rows,
                "rows_read": self.state_rows_read,
                "rows_held": self.state_rows_held,
            }
        if self._routed:
            c = self.cfg
            out["experts"] = {
                "held": c.experts_held[1], "of": c.moe_experts,
                "routed_layers": c.n_routed_layers,
                "touched": self.experts_touched,
                "read": self.experts_read,
                "held_steps": self.experts_held,
                "tokens_max": self.expert_tokens_max,
                "tokens_mean": self._expert_tokens_mean(
                    self.expert_assignments, self.experts_held),
            }
        if self._journal is not None:
            out["journal"] = {
                "entries": len(self._journal),
                "durable": self._journal.path is not None,
                "write_errors": self._journal.write_errors,
                "replay": self.replay,
            }
        pc = self._prefix_cache
        if pc is not None:
            out["prefix_cache"] = {
                "hits": pc.hits,
                "misses": pc.misses,
                "evictions": pc.evictions,
                "inserted_blocks": pc.inserted_blocks,
                "blocks_used": pc.blocks_used,
                "blocks_total": pc.n_blocks,
                "copy_dispatches": self.prefix_copy_dispatches,
                "insert_dispatches": self.prefix_insert_dispatches,
            }
        if self._paged:
            alloc = self._allocator
            out["paged_kv"] = {
                "kv_block": self.kv_block,
                "pool_blocks_total": alloc.n_blocks,
                "pool_blocks_free": alloc.free_blocks,
                "pool_blocks_used": alloc.used_blocks,
                "pool_blocks_peak": alloc.peak_used,
                # occupancy by OWNER, not just used/free: "shared" blocks
                # are referenced by a slot table AND the trie at once (the
                # zero-copy prefix-hit path), so slot+trie+shared+free ==
                # total and pressure reads off one gauge family
                "pool_state": self._pool_state_counts(),
                "kv_exports": self.kv_exports,
                "kv_imports": self.kv_imports,
                "kv_import_rejects": self.kv_import_rejects,
                "class_used": dict(alloc.class_used),
                "class_budgets": dict(self._class_budgets or {}),
                "admission_defers": self.admission_defers,
                "gather_dispatches": self.paged_gather_dispatches,
                "scatter_dispatches": self.paged_scatter_dispatches,
                "prefill_chunks_interleaved":
                    self.prefill_chunks_interleaved,
                "prefill_interleave": self.prefill_interleave,
                "pending_prefill": len(self._pending_prefill),
            }
        return out

    def _pool_state_counts(self) -> dict:
        """Block-pool occupancy by owner: ``slot`` (referenced only by a
        slot table), ``trie`` (only by the prefix trie), ``shared``
        (both — the zero-copy prefix-hit blocks), ``free`` (allocator
        free list). The four buckets partition the pool."""
        slot_set: set[int] = set()
        for s in range(self.slots):
            slot_set.update(int(b) for b in self._slot_blocks[s])
            slot_set.update(int(b) for b in self._slot_shared[s])
        pc = self._prefix_cache
        trie_set = ({int(node.block) for node in pc._owned}
                    if pc is not None else set())
        shared = slot_set & trie_set
        return {
            "free": self._allocator.free_blocks,
            "slot": len(slot_set - trie_set),
            "trie": len(trie_set - slot_set),
            "shared": len(shared),
        }

    # ----------------------------------------------------------- the loop

    def _free_for_admission(self, slot: int) -> bool:
        # predictive: the model knows the slot's request finished even if
        # its blocks haven't been processed; re-admitting is safe because
        # the processing replay keeps successive requests' streams
        # separate. EOS mode: only a PROCESSED completion frees the slot.
        if self._paged and any(p[0].slot == slot
                               for p in self._pending_prefill):
            return False        # mid-prefill: not even model-active yet
        if self._predictive:
            return not self._model_active[slot]
        return not self._host_busy[slot]

    def _admit(self) -> None:
        """One admission pass (ring or paged, prefill dispatches
        included) as the turn's ``serve.step.admit`` phase."""
        if self.pause_admission:
            return
        with phase(PHASE_ADMIT, queued=len(self._queue)) as span:
            inflight = len(self._inflight)
            computed = self.prefill_tokens_computed
            if self._paged:
                self._admit_paged()
            else:
                self._admit_ring()
            span.set_metadata(
                admitted=len(self._inflight) - inflight,
                prefill_tokens=self.prefill_tokens_computed - computed)

    def _admit_ring(self) -> None:
        """Admit queued requests into free slots. Prefill + slot-state
        pokes are dispatched NOW (after every block dispatched so far) and
        logged against the newest in-flight block so the bookkeeping
        replays them in order.

        The whole burst of admissible (slot, request) pairs is collected
        FIRST — every pair's ring offset derives from the same cursor, so
        batching changes no layout decision — then dispatched in three
        phases whose device order is the correctness contract: (1) copy
        cached prefix blocks into the slot rings (one batched program),
        (2) prefill each request's uncached suffix (one `_prefill_batch`
        program per chunk round), (3) gather the burst's
        new full-body chunks into fresh pool blocks (one batched
        program). Prefix lookups all run against the trie as of the
        burst start — a same-burst template twin prefills too (its copy
        would otherwise be dispatched before the twin's insert) — so
        sharing begins one burst after a template first appears."""
        self._sweep_expired()
        C = self.prefill_chunk
        admissions: list[_Admission] = []
        for slot in range(self.slots):
            if not self._queue:
                break
            if not self._free_for_admission(slot):
                continue
            req = self._queue.popleft()
            # dispatch-side ownership: the slot now serves THIS id (a
            # predecessor whose blocks are still unprocessed keeps its
            # _requests/_inflight entries — only its cancel-target mapping
            # is superseded), and the id is in-flight until its completion
            # is delivered, even if a prefill dispatch dies mid-burst
            # (reset() fails exactly the _inflight set)
            for stale in [r for r, s in self._slot_of.items() if s == slot]:
                del self._slot_of[stale]
            self._slot_of[req.id] = slot
            self._inflight.add(req.id)
            prompt = req.prompt
            resume = req.resume_tokens
            if resume is not None:
                # replay/failover resume (possibly with an empty prefix
                # — a crash before any token was processed still rides
                # the replay machinery): teacher-force the known prefix
                # through the normal chunked-prefill path (prefix-cache
                # eligible) — the effective context is prompt + emitted,
                # and only the REMAINING budget decodes
                self.replays += 1
                self.replayed_tokens += len(resume)
            if resume:
                full = np.concatenate(
                    [prompt, np.asarray(resume, np.int32)])
            else:
                full = prompt
            # all but the last token is prefilled; the last becomes the
            # slot's first fed token so the first sample falls out of the
            # normal decode step
            body = full[:-1]
            # ring alignment: the slot's first decode write must land at
            # the cursor as of its first block, i.e. the current cursor
            # (admission dispatches after every block dispatched so far).
            # Speculative mode has no shared cursor (rounds advance each
            # slot by its own accepted count; writes are per-row scatters
            # with an explicit wrap guard), so the ring degenerates to
            # offset 0 — logical position == buffer index, bounded by the
            # submit-time prompt+budget <= max_len check.
            offset = (0 if self._spec
                      else (self._cursor - body.size) % self.max_len)
            self._np_offs[slot] = offset
            # each active step advances length by 1 and emits 1 token, so
            # the remaining emissions end at body + remaining budget —
            # for a fresh request exactly body + max_new (the last
            # emitted token is never fed/written, same as generate)
            target = body.size + req.max_new_tokens - len(resume or ())
            temp = (self.temperature if req.temperature is None
                    else float(req.temperature))
            topk = (self.top_k if req.top_k is None else int(req.top_k))
            prefix_len, path = 0, []
            if self._prefix_cache is not None:
                path = self._prefix_cache.lookup(body)
                prefix_len = len(path) * C
                if path:
                    # path blocks stay pinned (unevictable) until this
                    # request's completion is processed
                    self._prefix_cache.acquire(path)
                    self.prefill_tokens_reused += prefix_len
            chunk_starts = (list(range(prefix_len, body.size, C))
                            or [prefix_len])
            tr = self._traces.get(req.id)
            if tr is not None:
                tr.attrs["prompt_tokens"] = int(prompt.size)
                tr.attrs["prefix_hit_blocks"] = len(path)
                tr.attrs["slot"] = slot
                tr.mark("admitted")
            admissions.append(_Admission(
                slot=slot, req=req, body=body, offset=offset, target=target,
                temp=temp, topk=topk, chunk_starts=chunk_starts,
                last=int(full[-1]), prefix_len=prefix_len, hit_path=path))
        if not admissions:
            return
        self._dispatch_prefix_copy(admissions)
        self._prefill_burst(admissions)
        # draft prefill BEFORE the trie insert: the insert now mirrors
        # each new chunk into the draft pool too, reading the draft
        # cache the suffix prefill just wrote
        if self._spec:
            self._prefill_draft(admissions)
        self._dispatch_prefix_insert(admissions)
        for adm in admissions:
            slot, req, body = adm.slot, adm.req, adm.body
            tr = self._traces.get(req.id)
            if tr is not None:
                # host DISPATCH completion (programs are async): the span
                # measures how long admission kept the scheduling loop,
                # which is exactly what it costs live traffic
                tr.mark("prefill_done")
            self._host_busy[slot] = True
            self._np_temps[slot] = adm.temp
            self._np_topks[slot] = adm.topk
            self._np_lp[slot] = adm.req.logprobs
            self._model_len[slot] = body.size
            self._model_active[slot] = True
            self._model_target[slot] = adm.target
            if adm.hit_path:
                self._prefix_refs[req.id] = adm.hit_path
            admit = (slot, body.size, req)
            if self._pipeline:
                self._pipeline[-1]["events"].append(("admit", admit))
            else:                       # nothing in flight: applies now
                self._apply_admit(admit)

    def _dispatch_prefix_copy(self, admissions) -> None:
        """Phase 1 of admission: ONE `_copy_prefix_blocks` dispatch moves
        every matched pool block of the burst into its slot's ring (rows
        padded to a power of two; pad rows write nowhere). Must precede
        the suffix prefill, whose attention reads the copied prefix."""
        rows = [(a.slot, n.block, ci, a.offset)
                for a in admissions for ci, n in enumerate(a.hit_path)]
        if not rows:
            return
        self._cache, fence = _copy_prefix_blocks(
            self._pool, self._cache, *self._prefix_rows(rows, oob="slot"),
            shardings=self._shardings)
        self.prefix_copy_dispatches += 1
        self.dispatch_tracker.track("prefix_copy", fence)
        if self._draft_pool is not None:
            # COW sharing with the draft cache: the same trie path is
            # valid in the draft-shaped pool (inserts mirror every block
            # id into both pools), so a hit seeds the draft slot cache
            # too and the draft re-prefills only the suffix
            self._draft_cache, dfence = _copy_prefix_blocks(
                self._draft_pool, self._draft_cache,
                *self._prefix_rows(rows, oob="slot"), shardings=None)
            self.dispatch_tracker.track("draft_prefix_copy", dfence)

    def _dispatch_prefix_insert(self, admissions) -> None:
        """Phase 3 of admission: insert the burst's new full-body chunks
        into the trie and gather their just-prefilled KV out of the slot
        rings into pool blocks — ONE `_insert_prefix_blocks` dispatch.
        Runs strictly after the suffix prefill (the data source) and
        before any later decode block (whose shared-cursor garbage
        writes would eventually lap a frozen ring)."""
        if self._prefix_cache is None:
            return
        rows, created = [], []
        for a in admissions:
            want = (self.cache_prompts if a.req.cache_prompt is None
                    else a.req.cache_prompt)
            if not want:
                continue
            for ci, node in self._prefix_cache.insert(a.body):
                rows.append((a.slot, node.block, ci, a.offset))
                created.append(node)
        if rows:
            self._pool, fence = _insert_prefix_blocks(
                self._pool, self._cache,
                *self._prefix_rows(rows, oob="block"),
                shardings=self._shardings)
            self.prefix_insert_dispatches += 1
            self.dispatch_tracker.track("prefix_insert", fence)
            if self._draft_pool is not None:
                # mirror the same rows into the draft pool (the draft
                # suffix prefill dispatched just before this, so the
                # draft cache holds the data) — one trie node, two
                # pools, one refcount
                self._draft_pool, dfence = _insert_prefix_blocks(
                    self._draft_pool, self._draft_cache,
                    *self._prefix_rows(rows, oob="block"), shardings=None)
                self.dispatch_tracker.track("draft_prefix_insert", dfence)
        if created:     # insert-refs protected the blocks until dispatch
            self._prefix_cache.release(created)

    def _prefix_rows(self, rows, *, oob: str):
        """(slot, block, chunk_idx, offset) rows -> padded device arrays
        for the copy/insert programs. Pad rows divert the WRITE index out
        of bounds (the destination axis named by ``oob``) so their writes
        drop, and leave the other (gather) index at 0 — `jnp.minimum`
        clamping in the programs keeps gathers in range anyway."""
        k_rows = _pow2_rows(len(rows))
        slots = np.zeros(k_rows, np.int32)
        blocks = np.zeros(k_rows, np.int32)
        chunk_idx = np.zeros(k_rows, np.int32)
        offsets = np.zeros(k_rows, np.int32)
        if oob == "slot":
            slots[:] = self.slots + np.arange(k_rows, dtype=np.int32)
        else:
            blocks[:] = (self._prefix_cache.n_blocks
                         + np.arange(k_rows, dtype=np.int32))
        for r, (s, b, ci, off) in enumerate(rows):
            slots[r], blocks[r], chunk_idx[r], offsets[r] = s, b, ci, off
        return (jnp.asarray(slots), jnp.asarray(blocks),
                jnp.asarray(chunk_idx), jnp.asarray(offsets))

    def _pack_rows(self, admissions, r: int, *, commit: bool = True):
        """Chunk round ``r`` of a burst as `_prefill_batch`'s row
        arguments (tokens, slots, starts, offsets, n_valids, last_tokens,
        targets, temps, topks, fin) and the prompt tokens they hold (some
        admission has a chunk in every round a caller asks for). Rows are
        padded to the next power of two (O(log slots) compiled widths);
        padding rows and rows whose prompt has already finished keep an
        out-of-bounds slot id, so all their writes drop. ``commit=False``
        (a draft model's cache; a prefill-role replica) leaves the commit
        columns zero and ``fin`` all False: the rows write KV and lengths,
        and the slots' committed decode state rides through the
        program's donation untouched."""
        C = self.prefill_chunk
        k_rows = _pow2_rows(len(admissions))
        tokens = np.zeros((k_rows, C), np.int32)
        slots = self.slots + np.arange(k_rows, dtype=np.int32)  # OOB default
        starts, offsets, n_valids, lasts, targets, topks = (
            np.zeros(k_rows, np.int32) for _ in range(6))
        temps = np.zeros(k_rows, np.float32)
        fin = np.zeros(k_rows, bool)
        n_tokens = 0
        for row, adm in enumerate(admissions):
            if r >= len(adm.chunk_starts):
                continue                # this prompt has no chunk round r
            c0 = adm.chunk_starts[r]
            nv = max(0, min(C, adm.body.size - c0))
            tokens[row, :nv] = adm.body[c0:c0 + nv]
            slots[row], starts[row] = adm.slot, c0
            offsets[row], n_valids[row] = adm.offset, nv
            n_tokens += nv
            if commit:
                lasts[row], targets[row] = adm.last, adm.target
                temps[row], topks[row] = adm.temp, adm.topk
                fin[row] = r == len(adm.chunk_starts) - 1
        return tuple(jnp.asarray(a) for a in (
            tokens, slots, starts, offsets, n_valids, lasts, targets,
            temps, topks, fin)), n_tokens

    def _dispatch_prefill(self, cache, rows, *, draft: bool = False):
        """One `_prefill_batch` dispatch of packed ``rows`` into ``cache``
        (the ring cache or a paged view; with ``draft`` the draft model's)
        -> the written cache. The slots' state vectors ride through every
        dispatch (donated), the target's and the draft's alike."""
        args = (self._draft_params if draft else self._params, cache,
                self._d_tokens, self._d_active, self._d_target,
                self._d_offsets, self._d_temps, self._d_topks, *rows)
        statics = dict(cfg=self._draft_cfg if draft else self.cfg,
                       shardings=None if draft else self._shardings)
        self._register(_prefill_batch, ("prefill", draft, rows[0].shape[0]),
                       args, statics)
        (cache, self._d_tokens, self._d_active, self._d_target,
         self._d_offsets, self._d_temps, self._d_topks,
         fence, *picks) = _prefill_batch(*args, **statics)
        self.admission_dispatches += 1
        self.dispatch_tracker.track(
            "draft_prefill" if draft else "prefill", fence)
        # the experts the chunk's positions chose (routed layers only):
        # left on the device for the requests that asked (_prefill_burst)
        self._prefill_picks = picks[0] if picks else None
        return cache

    def _prefill_burst(self, admissions) -> None:
        """Chunk round r of EVERY admitted request in one `_prefill_batch`
        dispatch — max-chunks rounds total, not sum-of-chunks."""
        for r in range(max(len(a.chunk_starts) for a in admissions)):
            rows, n_tokens = self._pack_rows(admissions, r)
            self._cache = self._dispatch_prefill(self._cache, rows)
            self.prefill_tokens_computed += n_tokens
            asked = [(row, adm) for row, adm in enumerate(admissions)
                     if adm.req.routes and r < len(adm.chunk_starts)]
            if asked:
                # on its way to the host now, read at the completion: a
                # transfer asked for then would wait for the blocks in flight
                self._prefill_picks.copy_to_host_async()
            for row, adm in asked:
                nv = min(self.prefill_chunk,
                         adm.body.size - adm.chunk_starts[r])
                self._route_prefill.setdefault(adm.req.id, []).append(
                    (self._prefill_picks, row, max(0, nv)))

    def _prefill_draft(self, admissions) -> None:
        """Speculative serving: the draft model needs the same context
        in its OWN slot cache. A prefix-cache hit covers the draft too
        — the trie's blocks are mirrored into a draft-shaped pool by
        the same insert rows (``_dispatch_prefix_copy`` seeded the
        draft slot cache before this ran) — so only the uncached
        suffix prefills, same ``chunk_starts`` as the target, one
        dispatch per chunk round (the draft config compiles its own
        variant) with no commit: the DRAFT cache's lengths land at each
        row's body size. Every admission appears in round 0 even with an
        empty suffix (fully-cached or 1-token prompt): the zero-valid row
        still RESETS the draft slot's stale length from its previous
        occupant, exactly as the target's degenerate final chunk does."""
        for adm in admissions:
            self.draft_prefill_tokens_reused += adm.prefix_len
        for r in range(max(len(a.chunk_starts) for a in admissions)):
            rows, _ = self._pack_rows(admissions, r, commit=False)
            self._draft_cache = self._dispatch_prefill(
                self._draft_cache, rows, draft=True)

    # ------------------------------------------------- paged-KV engine
    # Every dispatch is gather -> (unchanged ring program) -> scatter:
    # the gather materializes a transient RING-ORDERED view of the
    # busy slots' blocks (same indices, same masked-garbage semantics as
    # the ring cache, so greedy outputs are byte-identical by
    # construction), the program runs exactly as in ring mode, and the
    # scatter commits only the rows the program wrote back into the
    # pool. Blocks are allocated UP FRONT at admission (ceil(target /
    # kv_block) per request), so an admitted request can never run out
    # of KV mid-decode — "zero failed requests" is structural, and
    # overload surfaces as admission deferral instead of preemption.

    def _free_slot_blocks(self, slot: int) -> None:
        """Return a slot's table to the all-pad state: unref every held
        block (exclusively-owned ones free unless the trie adopted them;
        trie-shared ones just drop this slot's ref), credit the class
        budget for the exclusive holdings, and floor the slot so no
        in-flight decode garbage row can land in a freed block."""
        own, shared = self._slot_blocks[slot], self._slot_shared[slot]
        if own or shared:
            self._allocator.credit(self._slot_class[slot], len(own))
            for block in own:
                self._allocator.unref(block)
            for block in shared:
                self._allocator.unref(block)
            self._slot_blocks[slot] = []
            self._slot_shared[slot] = []
            self._np_tables[slot, :] = self._allocator.n_blocks   # pad
            self._tables_dirty = True
        self._np_floor[slot] = self.max_len

    def _gather_view(self, pool=None, lens=None):
        """Dispatch the pool -> ring-view gather for the next program.
        Host tables/offsets are the authority (the device copies lag by
        design: _d_offsets commits at each finalize, fine for programs,
        stale for layout). ``pool``/``lens`` select the draft mirror
        pool in speculative mode (same tables, same offsets)."""
        if self._tables_dirty:
            self._d_tables = jnp.asarray(self._np_tables)
            self._tables_dirty = False
        self.paged_gather_dispatches += 1
        return _gather_paged_view(
            self._kv_pool if pool is None else pool, self._d_tables,
            self._d_lens if lens is None else lens,
            jnp.asarray(self._np_offs), shardings=self._shardings)

    def _scatter_view(self, view, ring_ids, n_valids, floors,
                      draft: bool = False) -> None:
        """Commit the program's written rows back into the pool (the
        gather/program/scatter triple always shares one table+offset
        snapshot — nothing mutates them in between). ``draft=True``
        commits into the draft mirror pool instead (same tables)."""
        pool = self._draft_kv_pool if draft else self._kv_pool
        pool, fence = _scatter_paged_rows(
            pool, view, self._d_tables,
            jnp.asarray(self._np_offs), jnp.asarray(ring_ids),
            jnp.asarray(n_valids), jnp.asarray(floors),
            shardings=self._shardings)
        if draft:
            self._draft_kv_pool = pool
        else:
            self._kv_pool = pool
        self.paged_scatter_dispatches += 1
        self.dispatch_tracker.track("paged_scatter", fence)

    def _admit_paged(self) -> None:
        """Paged admission: gate on free POOL blocks (and the class
        budget), not just free slots. Allocation is all-or-nothing per
        request and FIFO by default; the one reordering allowed is
        skipping past a head-of-line request whose CLASS is over budget
        to the first request of the other tier — per-class budgets would
        otherwise head-of-line-block the tier they exist to protect.
        Admitted requests join _pending_prefill; _pump_prefill drains
        their chunks (fully here when interleaving is off, or capped
        per decode block when on)."""
        self._sweep_expired()
        for slot in range(self.slots):
            if not self._queue:
                break
            if not self._free_for_admission(slot):
                continue
            status = self._try_admit_paged(slot, 0)
            if status == "ok":
                continue
            self.admission_defers += 1
            if status == "budget":
                head_cls = self._queue[0].priority
                alt = next(
                    (i for i in range(1, len(self._queue))
                     if self._queue[i].priority != head_cls), None)
                if alt is not None and \
                        self._try_admit_paged(slot, alt) == "ok":
                    continue
            break       # pool exhausted: FIFO holds, retry next tick
        self._pump_prefill(self.prefill_interleave or None)

    def _try_admit_paged(self, slot: int, qidx: int) -> str:
        """Attempt one (slot, queued-request) admission. Returns "ok"
        (queue entry consumed, admission pending), "budget" (the
        request's class is over its block budget), or "pool" (free
        blocks short even after reclaiming trie leaves)."""
        B = self.kv_block
        req = self._queue[qidx]
        prompt = req.prompt
        resume = req.resume_tokens
        full = (np.concatenate([prompt, np.asarray(resume, np.int32)])
                if resume else prompt)
        body = full[:-1]
        target = body.size + req.max_new_tokens - len(resume or ())
        # every logical position the request can ever write, allocated
        # up front: no admitted request ever stalls or fails on KV
        cap_blocks = max(1, -(-target // B))
        prefix_len, path = 0, []
        if self._prefix_cache is not None:
            path = self._prefix_cache.lookup(body)
            prefix_len = len(path) * B
        n_new = cap_blocks - len(path)
        cls = req.priority
        blocks = self._allocator.alloc_for(cls, n_new)
        if blocks is None:
            budget = self._allocator.class_budgets.get(cls)
            if budget is not None and \
                    self._allocator.class_used.get(cls, 0) + n_new > budget:
                return "budget"
            short = n_new - self._allocator.free_blocks
            if self._prefix_cache is not None and short > 0:
                # cached prefixes yield to live admissions; reclaiming
                # may evict nodes on the matched path, so re-resolve it
                self._prefix_cache.reclaim(short)
                path = self._prefix_cache.lookup(body) if path else []
                prefix_len = len(path) * B
                n_new = cap_blocks - len(path)
                blocks = self._allocator.alloc_for(cls, n_new)
            if blocks is None:
                return "pool"
        del self._queue[qidx]
        if resume is not None:
            self.replays += 1
            self.replayed_tokens += len(resume)
        for stale in [r for r, s in self._slot_of.items() if s == slot]:
            del self._slot_of[stale]
        # predictive re-admission: the predecessor's completion is
        # unprocessed but its decode is device-done — free its blocks
        # now (its _slot_of mapping is gone, so _release_request cannot
        # double-free)
        self._free_slot_blocks(slot)
        self._slot_of[req.id] = slot
        self._inflight.add(req.id)
        # speculative mode has no shared cursor (per-slot lengths
        # advance by variable accepted counts); the ring degenerates to
        # offset 0, as in the ring engine's spec admission
        offset = (0 if self._spec
                  else (self._cursor - body.size) % self.max_len)
        temp = (self.temperature if req.temperature is None
                else float(req.temperature))
        topk = (self.top_k if req.top_k is None else int(req.top_k))
        if path:
            self._prefix_cache.acquire(path)
            self.prefill_tokens_reused += prefix_len
            self._prefix_refs[req.id] = path
        chunk_starts = (list(range(prefix_len, body.size,
                                   self.prefill_chunk)) or [prefix_len])
        tr = self._traces.get(req.id)
        if tr is not None:
            tr.attrs["prompt_tokens"] = int(prompt.size)
            tr.attrs["prefix_hit_blocks"] = len(path)
            tr.mark("admitted")
        # table row: trie-hit blocks first (shared — one allocator ref
        # each, zero copies: the hit IS the block), then the fresh
        # exclusively-owned blocks the prefill/decode will fill
        row = self._np_tables[slot]
        row[:] = self._allocator.n_blocks                   # pad
        shared = []
        for i, node in enumerate(path):
            row[i] = node.block
            self._allocator.ref(node.block)
            shared.append(node.block)
        for j, block in enumerate(blocks):
            row[len(path) + j] = block
        self._tables_dirty = True
        self._slot_blocks[slot] = list(blocks)
        self._slot_shared[slot] = shared
        self._slot_class[slot] = cls
        self._np_offs[slot] = offset
        self._np_floor[slot] = self.max_len     # no decode writes until
        #                                         the finalize activates
        self._host_busy[slot] = True
        self._np_temps[slot] = temp
        self._np_topks[slot] = topk
        self._np_lp[slot] = req.logprobs
        self._pending_prefill.append(
            [_Admission(slot=slot, req=req, body=body, offset=offset,
                        target=target, temp=temp, topk=topk,
                        chunk_starts=chunk_starts, last=int(full[-1]),
                        prefix_len=prefix_len, hit_path=path), 0])
        return "ok"

    def _pump_prefill(self, budget: int | None) -> None:
        """Dispatch pending admissions' prefill chunks, oldest first, up
        to ``budget`` prompt tokens (None = drain everything now, the
        uncapped ring-engine behavior). The cap is the chunked-prefill
        interleave: a decode block dispatches between pumps, so an
        admission burst stretches across decode blocks instead of
        stalling every in-flight stream for the whole burst's prefill."""
        spent = 0
        while self._pending_prefill:
            if budget is not None and spent >= budget:
                self.prefill_chunks_interleaved += 1
                break
            pend = self._pending_prefill[0]
            adm, idx = pend
            final = idx == len(adm.chunk_starts) - 1
            if final and not self._spec and self.role != "prefill":
                # the admission-time offset aligned the slot's first
                # decode write with the cursor AS OF ADMISSION; decode
                # blocks interleaved since then moved the cursor. The
                # pool is logical (tables map positions to blocks), so
                # the offset is free to change between dispatches —
                # re-derive it so the finalize commits an offset whose
                # first decode write lands at the CURRENT cursor. A
                # no-op when nothing interleaved. (Spec mode pins
                # offset 0 — no shared cursor; a prefill-role slot
                # never decodes, so its offset is moot.)
                adm.offset = (self._cursor - adm.body.size) % self.max_len
                self._np_offs[adm.slot] = adm.offset
            spent += max(1, self._dispatch_paged_prefill(adm, idx))
            if final:
                self._pending_prefill.popleft()
                self._finalize_admit_paged(adm)
            else:
                pend[1] = idx + 1

    def _dispatch_paged_prefill(self, adm: _Admission, r: int) -> int:
        """One one-row `_prefill_batch` dispatch of the admission's chunk
        ``r`` on the gathered view, then scatter the chunk's span back
        into the slot's blocks. A prefill-role replica dispatches even
        the final chunk without the commit: the KV write is
        unconditional, only the device-side slot ACTIVATION rides on
        ``fin`` — so the blocks finish fully written while the slot never
        decodes (the export snapshot is taken at `_finalize_admit_paged`). In speculative mode the draft mirror
        pool prefills the same span right after (same tables, same ring
        ids, its own length vector), never committing: the target's
        commit owns the slot state. Returns the chunk's prompt tokens."""
        C = self.prefill_chunk
        slot, c0 = adm.slot, adm.chunk_starts[r]
        rows, n_valid = self._pack_rows(
            [adm], r, commit=self.role != "prefill")
        view = self._dispatch_prefill(self._gather_view(), rows)
        self._d_lens = view.length
        ring_ids = np.zeros((self.slots, C), np.int32)
        ring_ids[slot] = (adm.offset + c0
                          + np.arange(C, dtype=np.int32)) % self.max_len
        n_valids = np.zeros((self.slots,), np.int32)
        n_valids[slot] = n_valid
        # floors stay zero here: this IS the prefill writing the span
        # the floor will later protect
        floors = np.zeros((self.slots,), np.int32)
        self._scatter_view(view, ring_ids, n_valids, floors)
        self.prefill_tokens_computed += n_valid
        if self._spec:
            draft_rows, _ = self._pack_rows([adm], r, commit=False)
            dview = self._dispatch_prefill(
                self._gather_view(pool=self._draft_kv_pool,
                                  lens=self._d_draft_lens),
                draft_rows, draft=True)
            self._d_draft_lens = dview.length
            self._scatter_view(dview, ring_ids, n_valids, floors,
                               draft=True)
        return n_valid

    def _finalize_admit_paged(self, adm: _Admission) -> None:
        """The finalize chunk is dispatched: activate the slot for
        decode (floor + exact host model), adopt its freshly-filled full
        chunks into the trie (zero-copy — the trie just refs the
        blocks), and log the admit event at this position in the
        dispatch order. A prefill-role replica terminates here instead:
        snapshot the finished blocks into an export payload, complete
        the request with finish_reason="prefilled", and free the slot —
        the request decodes on whichever replica imports the payload."""
        slot, req, body = adm.slot, adm.req, adm.body
        tr = self._traces.get(req.id)
        if tr is not None:
            tr.mark("prefill_done")
        if self._spec:
            self.draft_prefill_tokens_reused += adm.prefix_len
        want = (self.cache_prompts if req.cache_prompt is None
                else req.cache_prompt)
        if self._prefix_cache is not None and want:
            B = self.kv_block
            row = self._np_tables[slot]
            offer = {i: int(row[i])
                     for i in range(adm.prefix_len // B, body.size // B)}
            if offer:
                self._prefix_cache.adopt(body, offer)
        if self.role == "prefill":
            self._stash_export(adm)
            self._done[req.id] = Completion(
                req.id, [], "prefilled",
                trace=self._finish_trace(
                    req.id, "finished", n_tokens=0, reason="prefilled"))
            self._finish_stream(req.id)
            self._host_busy[slot] = False
            self._release_request(req.id)   # frees blocks (snapshot is
            return                          # host bytes), seals journal
        self._np_floor[slot] = body.size
        self._model_len[slot] = body.size
        self._model_active[slot] = True
        self._model_target[slot] = adm.target
        admit = (slot, body.size, req)
        if self._pipeline:
            self._pipeline[-1]["events"].append(("admit", admit))
        else:                           # nothing in flight: applies now
            self._apply_admit(admit)

    # ---------------------------- KV block transfer (disaggregation)

    def _stash_export(self, adm: _Admission) -> None:
        """Serialize a finished prefill's blocks + replay state into
        the bounded export stash. The snapshot is host bytes (the
        device sync happens here), so the slot and its blocks recycle
        immediately after."""
        req, slot, body = adm.req, adm.slot, adm.body
        B = self.kv_block
        n_blocks = max(1, -(-int(body.size) // B))
        ids = [int(b) for b in self._np_tables[slot][:n_blocks]]
        entry = {
            "id": int(req.id),
            "prompt": [int(t) for t in req.prompt],
            "max_new_tokens": int(req.max_new_tokens),
            "temperature": req.temperature,
            "top_k": req.top_k,
            "cache_prompt": req.cache_prompt,
            "seed": self._seed,
            "emitted": [int(t) for t in (req.resume_tokens or ())],
            "model": req.model,
            "stop": ([list(map(int, s)) for s in req.stop]
                     if req.stop else None),
            "logprobs": int(req.logprobs or 0),
            "priority": req.priority,
            # the prefill leg's trace identity rides the durable
            # payload: a decode replica importing this lands in the
            # originating distributed trace even header-less
            "trace": (self._traces[req.id].ctx.as_dict()
                      if req.id in self._traces
                      and self._traces[req.id].ctx is not None else None),
        }
        self._exports[int(req.id)] = serialize_kv_blocks(
            self._kv_pool, ids, model=self.model, kv_block=B,
            kv_dtype=self.kv_dtype, body_len=int(body.size),
            entry=entry)
        self.kv_exports += 1
        tr = self._traces.get(req.id)
        if tr is not None:
            tr.attrs["exported_blocks"] = n_blocks
        while len(self._exports) > self._exports_cap:
            self._exports.popitem(last=False)

    def export_blocks(self, request_id: int) -> dict:
        """Pop a prefilled request's transfer payload. KeyError when
        the request never finished prefilling here (or the bounded
        stash aged it out) — the caller falls back to journal replay
        on a decode replica, which re-prefills from the prompt."""
        payload = self._exports.pop(int(request_id), None)
        if payload is None:
            raise KeyError(
                f"no KV export payload for request {int(request_id)}")
        return payload

    def import_blocks(self, payload: dict, trace=None) -> int:
        """Install a prefill replica's exported blocks and resume the
        request HERE, decode-only: allocate fresh blocks from our own
        pool, write the payload in (one donated dispatch), install the
        table row at our cursor's offset, and activate the slot exactly
        as a local finalize would — the gather view cannot tell an
        imported block from a locally-prefilled one, so decode is
        byte-identical. Raises ValueError on any payload damage
        (version/model/geometry/checksum — the torn-transfer contract:
        loud rejection, the caller re-prefills via journal replay) and
        QueueFullError when no slot or pool blocks are free right now.
        ``trace`` (a TraceContext or its dict form, usually parsed from
        the transport's X-Tony-Trace header) puts the decode leg in the
        caller's distributed trace; absent that, the payload entry's
        own "trace" field is used (the prefill leg becomes the parent).
        Returns the new engine-local request id."""
        try:
            return self._import_blocks(payload, trace)
        except ValueError:
            self.kv_import_rejects += 1
            raise

    def _import_blocks(self, payload: dict, trace=None) -> int:
        if not self._paged:
            raise ValueError(
                "import_blocks requires paged=True (the transfer unit "
                "is the paged KV block)")
        if self.role == "prefill":
            raise ValueError(
                "a prefill-role replica cannot import KV blocks "
                "(nothing here decodes them)")
        if self._spec:
            raise ValueError(
                "KV import into a speculative server is unsupported "
                "(the transfer carries no draft-pool payload)")
        B = self.kv_block
        if not isinstance(payload, dict):
            raise ValueError("KV transfer payload must be an object")
        if payload.get("model") != self.model:
            raise ValueError(
                f"KV transfer is for model {payload.get('model')!r} "
                f"but this engine serves {self.model!r}")
        if int(payload.get("kv_block", 0)) != B:
            raise ValueError(
                f"KV transfer kv_block={payload.get('kv_block')} != "
                f"this engine's {B}")
        if str(payload.get("kv_dtype")) != str(self.kv_dtype):
            raise ValueError(
                f"KV transfer kv_dtype={payload.get('kv_dtype')!r} != "
                f"this engine's {self.kv_dtype!r}")
        k, v, ks, vs = deserialize_kv_blocks(payload)   # checksum etc.
        pk = self._kv_pool.k
        if k.shape[0] != pk.shape[0] or k.shape[2:] != pk.shape[2:] \
                or str(k.dtype) != str(pk.dtype):
            raise ValueError(
                f"KV transfer block shape {k.shape[0:1] + k.shape[2:]}"
                f"/{k.dtype} does not match this pool's "
                f"{pk.shape[0:1] + pk.shape[2:]}/{pk.dtype}")
        entry = payload.get("entry")
        if not isinstance(entry, dict):
            raise ValueError("KV transfer payload has no journal entry")
        try:
            prompt = [int(t) for t in entry["prompt"]]
            max_new = int(entry["max_new_tokens"])
            emitted = [int(t) for t in (entry.get("emitted") or ())]
        except (KeyError, TypeError, ValueError) as e:
            raise ValueError(
                f"malformed KV transfer entry: {e}") from None
        body_len = int(payload["body_len"])
        if body_len != len(prompt) + len(emitted) - 1:
            raise ValueError(
                f"KV transfer body_len={body_len} does not match the "
                f"entry's {len(prompt)} prompt + {len(emitted)} emitted "
                "tokens")
        n_payload = int(payload["n_blocks"])
        if n_payload != max(1, -(-body_len // B)):
            raise ValueError("KV transfer n_blocks/body_len mismatch")
        if len(prompt) < 1 or max_new < 1:
            raise ValueError("KV transfer entry has an empty request")
        if len(emitted) >= max_new:
            raise ValueError(
                "KV transfer entry is already satisfied (nothing left "
                "to decode); deliver it from the journal instead")
        if len(prompt) + max_new > self.max_len:
            raise ValueError(
                f"KV transfer request needs {len(prompt)} prompt + "
                f"{max_new} new tokens but slots hold "
                f"max_len={self.max_len}")
        stop = entry.get("stop")
        req = Request(
            prompt=np.asarray(prompt, np.int32),
            max_new_tokens=max_new,
            temperature=entry.get("temperature"),
            top_k=entry.get("top_k"),
            cache_prompt=entry.get("cache_prompt"),
            resume_tokens=emitted or None,
            stop=_normalize_stop(stop) if stop else None,
            logprobs=int(entry.get("logprobs") or 0),
            priority=(entry.get("priority")
                      if entry.get("priority") in PRIORITY_CLASSES
                      else "interactive"))
        if req.logprobs and not 0 <= req.logprobs <= LOGPROBS_MAX:
            raise ValueError(f"logprobs must be in [0, {LOGPROBS_MAX}]")
        # -- strict admission: a handoff needs a seat NOW or the router
        #    falls back; queueing it would hide the backpressure
        slot = next((s for s in range(self.slots)
                     if self._free_for_admission(s)), None)
        if slot is None:
            err = QueueFullError("no free slot for KV import")
            err.retry_after_s = self.estimate_retry_after()
            err.priority = req.priority
            raise err
        full = np.concatenate(
            [req.prompt, np.asarray(emitted, np.int32)]
        ) if emitted else req.prompt
        body = full[:-1]
        target = body.size + max_new - len(emitted)
        cap_blocks = max(1, -(-target // B))
        cls = req.priority
        blocks = self._allocator.alloc_for(cls, cap_blocks)
        if blocks is None:
            short = cap_blocks - self._allocator.free_blocks
            if self._prefix_cache is not None and short > 0:
                self._prefix_cache.reclaim(short)
                blocks = self._allocator.alloc_for(cls, cap_blocks)
            if blocks is None:
                self.admission_defers += 1
                err = QueueFullError(
                    f"pool blocks short for KV import ({cap_blocks} "
                    "needed)")
                err.retry_after_s = self.estimate_retry_after()
                err.priority = cls
                raise err
        # -- validated and funded: install
        tr = RequestTrace(req.id)
        tr.mark("submitted")
        ctx = trace if isinstance(trace, TraceContext) \
            else TraceContext.from_dict(trace)
        if ctx is None:
            # header-less import (e.g. a payload replayed from disk):
            # the prefill leg's identity persisted in the entry is the
            # parent — same trace, new span for the decode leg
            stashed = TraceContext.from_dict(entry.get("trace"))
            if stashed is not None:
                ctx = stashed.child()
        if ctx is not None:
            tr.bind(ctx)
            tr.attrs["service"] = "serve"
        tr.attrs["imported_blocks"] = n_payload
        if emitted:
            tr.attrs["resume_tokens"] = len(emitted)
        self._traces[req.id] = tr
        ids = np.asarray(blocks[:n_payload], np.int32)
        self._kv_pool = _write_pool_blocks(
            self._kv_pool, jnp.asarray(ids), jnp.asarray(k),
            jnp.asarray(v),
            None if ks is None else jnp.asarray(ks),
            None if vs is None else jnp.asarray(vs),
            shardings=self._shardings)
        for stale in [r for r, s in self._slot_of.items() if s == slot]:
            del self._slot_of[stale]
        self._free_slot_blocks(slot)
        self._slot_of[req.id] = slot
        self._inflight.add(req.id)
        offset = (self._cursor - body.size) % self.max_len
        temp = (self.temperature if req.temperature is None
                else float(req.temperature))
        topk = (self.top_k if req.top_k is None else int(req.top_k))
        row = self._np_tables[slot]
        row[:] = self._allocator.n_blocks                   # pad
        for j, block in enumerate(blocks):
            row[j] = block
        self._tables_dirty = True
        self._slot_blocks[slot] = list(blocks)
        self._slot_shared[slot] = []
        self._slot_class[slot] = cls
        self._np_offs[slot] = offset
        self._np_floor[slot] = body.size
        self._host_busy[slot] = True
        self._np_temps[slot] = temp
        self._np_topks[slot] = topk
        self._np_lp[slot] = req.logprobs
        # device-side activation: exactly what the finalize chunk's
        # commit lane would have written
        self._d_tokens = self._d_tokens.at[slot].set(int(full[-1]))
        self._d_active = self._d_active.at[slot].set(True)
        self._d_target = self._d_target.at[slot].set(int(target))
        self._d_offsets = self._d_offsets.at[slot].set(int(offset))
        self._d_temps = self._d_temps.at[slot].set(float(temp))
        self._d_topks = self._d_topks.at[slot].set(int(topk))
        self._d_lens = self._d_lens.at[slot].set(int(body.size))
        self._model_len[slot] = body.size
        self._model_active[slot] = True
        self._model_target[slot] = target
        tr.mark("admitted")
        tr.mark("prefill_done")
        # the imported prefix seeds the trie zero-copy, same as a local
        # finalize: repeated prompts to this decode replica skip the
        # transfer entirely next time
        want = (self.cache_prompts if req.cache_prompt is None
                else req.cache_prompt)
        if self._prefix_cache is not None and want:
            offer = {i: int(row[i]) for i in range(body.size // B)}
            if offer:
                self._prefix_cache.adopt(body, offer)
        if self._journal is not None:
            self._journal.submit(
                req.id, prompt, max_new,
                temperature=req.temperature, top_k=req.top_k,
                cache_prompt=req.cache_prompt, seed=self._seed,
                emitted=emitted, model=self.model,
                stop=[list(s) for s in req.stop] if req.stop else None,
                logprobs=req.logprobs, priority=req.priority,
                trace=ctx.as_dict() if ctx is not None else None)
        admit = (slot, int(body.size), req)
        if self._pipeline:
            self._pipeline[-1]["events"].append(("admit", admit))
        else:
            self._apply_admit(admit)
        self.kv_imports += 1
        return req.id

    def _register(self, fn, variant, args, statics) -> None:
        """Note ``fn(*args, **statics)`` in observability.PROGRAMS the
        first time this server dispatches ``variant`` of it: one set
        look-up a dispatch, and nothing lowered."""
        if variant not in self._programs_seen:
            self._programs_seen.add(variant)
            register_program(fn, args, statics)

    def _dispatch_decode(self, cache, key, lp_k: int) -> tuple:
        """One `_decode_block` over ``cache`` (the ring, or the paged
        engine's gathered view) -> (cache, tokens, active, packed)."""
        args = (self._params, self._fused, cache,
                self._d_tokens, self._d_active, self._d_target,
                self._d_offsets, jnp.int32(self._cursor), self._d_temps,
                self._d_topks, key)
        statics = dict(
            cfg=self.cfg, block=self.block_size,
            stop_tokens=self.stop_tokens, pad_id=self.pad_id,
            top_k=self.top_k,
            # _host_busy never goes False while a row is still active on
            # device, so these are safe whenever they say all-greedy /
            # nobody-overrides-k
            per_row_topk=bool(
                (self._np_topks[self._host_busy] != self.top_k).any()),
            weight_dtype=self.weight_dtype, build_fused=self._build_fused,
            all_greedy=not bool(
                (self._np_temps[self._host_busy] > 0).any()),
            lp_k=lp_k,
            shardings=self._shardings)
        self._register(_decode_block,
                       ("decode", lp_k, statics["all_greedy"],
                        statics["per_row_topk"]), args, statics)
        return _decode_block(*args, **statics)

    def _dispatch_block_paged(self) -> None:
        """Paged decode block: pump at most ``prefill_interleave``
        pending prefill tokens, then gather -> `_decode_block` (the
        unchanged ring program) -> scatter the cursor window. Pipeline
        record, counters, predictive model advance, and chaos hooks are
        exactly the ring path's — processing cannot tell the engines
        apart."""
        if self._pending_prefill and self.prefill_interleave:
            self._pump_prefill(self.prefill_interleave)
        t0 = time.monotonic()
        self._key, sub = jax.random.split(self._key)
        lp_k = (LOGPROBS_MAX
                if bool((self._np_lp[self._host_busy] > 0).any()) else 0)
        view = self._gather_view()
        (view, self._d_tokens, self._d_active,
         packed) = self._dispatch_decode(view, sub, lp_k)
        self._d_lens = view.length
        # every row writes the shared cursor window; floors divert the
        # rows that must not commit (pending/idle/finished-and-lapped)
        ring_ids = np.tile(
            (self._cursor + np.arange(self.block_size, dtype=np.int32))
            % self.max_len, (self.slots, 1))
        self._scatter_view(
            view, ring_ids,
            np.full((self.slots,), self.block_size, np.int32),
            self._np_floor.copy())
        self._cursor = (self._cursor + self.block_size) % self.max_len
        self.blocks_dispatched += 1
        self.telemetry.observe("decode_block_s", time.monotonic() - t0)
        seq = self.dispatch_tracker.track("decode_block", packed)
        self._pipeline.append({"packed": packed, "events": [], "seq": seq,
                               "offsets": self._np_offs.copy(),
                               "w": self.block_size + 2
                               + (self.block_size * (2 * lp_k + 1)
                                  if lp_k else 0) + self._recurrent,
                               "lp_k": lp_k,
                               "spec_gamma": None})
        if self._predictive:            # exact: no EOS can surprise us
            adv = np.minimum(self.block_size,
                             self._model_target - self._model_len)
            self._model_len = self._model_len + np.where(
                self._model_active, adv, 0).astype(np.int32)
            self._model_active &= self._model_len < self._model_target
        self._post_dispatch_chaos()

    def _apply_admit(self, admit) -> None:
        slot, body_len, req = admit
        # the slot belongs to a NEW request from this event on: any
        # pending stop-cancel skip for the predecessor ends here (the
        # admission program was dispatched after the cancel program)
        self._stop_cancelled.discard(int(slot))
        self._spec_round_counts[slot] = 0
        self._spec_accepted_counts[slot] = 0
        self._expect_len[slot] = body_len
        self._expect_active[slot] = True
        self._requests[slot] = req
        # a resumed request's completion owes the caller the FULL stream:
        # seed the tally with the teacher-forced prefix (those positions
        # were prefilled, not decoded — only the continuation appends)
        self._emitted[slot] = [int(t) for t in (req.resume_tokens or ())]
        # logprob placeholders for the teacher-forced prefix keep the
        # per-token alignment (those rows were prefilled, not decoded)
        self._lp_acc[slot] = ([{"token": int(t), "logprob": None,
                                "top": None}
                               for t in (req.resume_tokens or ())]
                              if req.logprobs else [])
        self._route_acc[slot] = []
        # re-arm busy at the replay position: when this slot was
        # re-admitted before its PREDECESSOR's completion was processed,
        # that processing (replayed just before this admit) cleared
        # _host_busy — without the re-arm the server can read idle while
        # this request still decodes on device, and a loop that gates
        # stepping on busyness strands it (its waiter hangs)
        self._host_busy[slot] = True

    def _apply_cancel(self, payload) -> None:
        """Processing-side half of cancel(): replayed at the cancel's
        position in the event log (after every block dispatched before
        it, before every one after), so the emitted-token tally is
        exactly what the device produced before the deactivation took
        effect. A request that finished naturally in one of those earlier
        blocks won the race — its completion already fired and the slot
        may even belong to a successor; skip."""
        slot, rid = payload
        req = self._requests[slot]
        if req is None or req.id != rid:
            # the request finished naturally in an earlier-dispatched
            # block (EOS-mode race): the cancel did nothing — reconcile
            # the counter its optimistic True incremented
            self.cancelled_requests -= 1
            return
        out = self._emitted[slot]
        self._done[rid] = Completion(
            rid, out, "cancelled",
            trace=self._finish_trace(rid, "cancelled", n_tokens=len(out)),
            logprobs=(self._lp_acc[slot] if req.logprobs else None))
        self._finish_stream(rid)
        self._requests[slot] = None
        self._emitted[slot] = []
        self._lp_acc[slot] = []
        self._route_acc[slot] = []
        self._host_busy[slot] = False
        self._expect_active[slot] = False
        self._release_request(rid)

    def _dispatch_block(self) -> None:
        if self._paged:
            self._dispatch_block_paged()
            return
        t0 = time.monotonic()
        self._key, sub = jax.random.split(self._key)
        # logprobs: one packed-width variant whenever ANY busy slot
        # asked (static — two compiled programs total); requests slice
        # down to their own k at processing time
        lp_k = (LOGPROBS_MAX
                if bool((self._np_lp[self._host_busy] > 0).any()) else 0)
        (self._cache, self._d_tokens, self._d_active,
         packed) = self._dispatch_decode(self._cache, sub, lp_k)
        self._cursor = (self._cursor + self.block_size) % self.max_len
        self.blocks_dispatched += 1
        # host DISPATCH time (the program runs async): what a decode
        # block costs the scheduling loop, not device execution time
        self.telemetry.observe("decode_block_s", time.monotonic() - t0)
        # device time: the reaper blocks on `packed` (never donated) off
        # the hot path and records when the device actually finished the
        # block; _process subtracts that from its observation instant to
        # measure the pipeline lag this block's tokens were delivered at
        seq = self.dispatch_tracker.track("decode_block", packed)
        self._pipeline.append({"packed": packed, "events": [], "seq": seq,
                               "offsets": self._np_offs.copy(),
                               "w": self.block_size + 2
                               + (self.block_size * (2 * lp_k + 1)
                                  if lp_k else 0)
                               + routed_columns(self.cfg, self.block_size)
                               + self._recurrent,
                               "lp_k": lp_k,
                               "spec_gamma": None})
        if self._predictive:            # exact: no EOS can surprise us
            adv = np.minimum(self.block_size,
                             self._model_target - self._model_len)
            self._model_len = self._model_len + np.where(
                self._model_active, adv, 0).astype(np.int32)
            self._model_active &= self._model_len < self._model_target
        self._post_dispatch_chaos()

    def _post_dispatch_chaos(self) -> None:
        """Deterministic chaos (constants.py TEST_SERVING_*): crash the
        loop — or the whole process — at exact decode-block ordinals
        (spec rounds count as blocks), i.e. mid-decode by construction.
        The block was really dispatched: recovery has genuine in-flight
        work to replay."""
        if (self._chaos_sigkill_block
                and self.blocks_dispatched >= self._chaos_sigkill_block):
            log.error("chaos: SIGKILLing this process at decode block %d",
                      self.blocks_dispatched)
            os.kill(os.getpid(), signal.SIGKILL)
        if self.blocks_dispatched in self._chaos_crash_blocks:
            self._chaos_crash_blocks.discard(self.blocks_dispatched)
            self.chaos_faults_injected += 1
            raise RuntimeError(
                "chaos: injected mid-decode loop crash at block "
                f"{self.blocks_dispatched}")

    def _current_gamma(self) -> int:
        """The NEXT spec round's draft window. Pinned via spec_gamma, or
        autotuned: the busy slots' mean acceptance EWMA mapped through
        the expected-accepted-run-length rule a/(1-a) — the window a
        geometric acceptance process actually fills — clamped to
        [1, spec_gamma_max] and snapped to a power of two so the
        compiled spec-program set stays O(log gamma_max)."""
        if self._spec_gamma_pin:
            return self._spec_gamma_pin
        busy = self._host_busy
        a = float(self._accept_ewma[busy].mean() if busy.any()
                  else self._accept_ewma.mean())
        a = min(max(a, 0.0), 0.99)
        raw = max(1.0, min(a / max(1e-6, 1.0 - a),
                           float(self.spec_gamma_max)))
        g = 1 << int(round(math.log2(raw)))
        # ceiling = the largest power of two <= spec_gamma_max: a plain
        # min() against a non-power-of-two max would return the max
        # itself and compile an off-ladder program variant
        cap = 1 << (self.spec_gamma_max.bit_length() - 1)
        return max(1, min(g, cap))

    def _dispatch_spec_round(self) -> None:
        """Speculative-mode decode dispatch: one propose/verify round
        for all slots (`_spec_block`), logged in the SAME pipeline the
        plain decode blocks use — admissions and cancels recorded
        against it replay at exactly their dispatch positions, and the
        packed result is sliced by length delta, so the whole event-log
        discipline (journal appends included) is untouched by
        speculation."""
        if self._paged:
            self._dispatch_spec_round_paged()
            return
        t0 = time.monotonic()
        gamma = self._current_gamma()
        (self._cache, self._draft_cache, self._d_tokens, self._d_active,
         packed) = _spec_block(
            self._params, self._draft_params, self._cache,
            self._draft_cache, self._d_tokens, self._d_active,
            self._d_target, self._d_offsets,
            cfg=self.cfg, draft_cfg=self._draft_cfg, gamma=gamma,
            stop_tokens=self.stop_tokens, pad_id=self.pad_id)
        self.blocks_dispatched += 1
        self.spec_rounds += 1
        self.telemetry.observe("decode_block_s", time.monotonic() - t0)
        seq = self.dispatch_tracker.track("spec_round", packed)
        self._pipeline.append({"packed": packed, "events": [], "seq": seq,
                               "w": gamma + 4, "spec_gamma": gamma})
        self._post_dispatch_chaos()

    def _dispatch_spec_round_paged(self) -> None:
        """Paged speculative round: gather BOTH pools into ring views
        (same tables, per-pool length vectors), run the unchanged
        `_spec_block`, scatter each slot's round window — the gamma+1
        positions starting at its pre-round length — back into both
        pools; ``_dispatch`` then processes the round IMMEDIATELY (forced
        sync, like spec's sync mode generally: the scatter window is
        computed from host lengths, which only stay exact with an empty
        pipeline).
        Committing all gamma+1 rows is safe even when the verify
        rolled tokens back: rolled-back rows sit ABOVE the slot's new
        length in exclusively-owned tail blocks — the mask never reads
        past length, and the next round overwrites them. View rows the
        program didn't write round-trip their gathered bytes
        unchanged."""
        t0 = time.monotonic()
        gamma = self._current_gamma()
        # pre-round lengths: exact under forced sync (pipeline empty,
        # every admit/import/process already applied)
        lens_before = self._expect_len.copy()
        view = self._gather_view()
        dview = self._gather_view(pool=self._draft_kv_pool,
                                  lens=self._d_draft_lens)
        (view, dview, self._d_tokens, self._d_active,
         packed) = _spec_block(
            self._params, self._draft_params, view, dview,
            self._d_tokens, self._d_active,
            self._d_target, self._d_offsets,
            cfg=self.cfg, draft_cfg=self._draft_cfg, gamma=gamma,
            stop_tokens=self.stop_tokens, pad_id=self.pad_id)
        self._d_lens = view.length
        self._d_draft_lens = dview.length
        w = gamma + 1
        ring_ids = (self._np_offs[:, None] + lens_before[:, None]
                    + np.arange(w, dtype=np.int32)[None, :]) \
            % self.max_len
        n_valids = np.full((self.slots,), w, np.int32)
        floors = self._np_floor.copy()
        self._scatter_view(view, ring_ids, n_valids, floors)
        self._scatter_view(dview, ring_ids, n_valids, floors,
                           draft=True)
        self.blocks_dispatched += 1
        self.spec_rounds += 1
        self.telemetry.observe("decode_block_s", time.monotonic() - t0)
        seq = self.dispatch_tracker.track("spec_round", packed)
        self._pipeline.append({"packed": packed, "events": [], "seq": seq,
                               "w": gamma + 4, "spec_gamma": gamma})
        self._post_dispatch_chaos()

    def _dispatch(self) -> None:
        """One decode dispatch (a block, or a speculative round) as the
        turn's ``serve.step.dispatch`` phase; ``live`` counts the slots
        it decodes for."""
        with phase(PHASE_DISPATCH, live=self.n_active, slots=self.slots):
            if self._spec:
                self._dispatch_spec_round()
            else:
                self._dispatch_block()
        # the paged spec round's forced sync (see its docstring), once
        # its phase has closed: sync and bookkeep stay leaves. The chaos
        # hook may have emptied the pipeline.
        if self._spec and self._paged and self._pipeline:
            self._process(1)

    def _process(self, count: int) -> None:
        """Sync + bookkeep the oldest ``count`` in-flight blocks with ONE
        device->host transfer: their packed results are concatenated
        on-device first (each transfer is a host sync of its own, no
        matter the size). Emitted token count per slot is the length delta
        vs the expectation; completions fire where a slot went inactive;
        each block's admissions AND cancellations replay after it, in
        dispatch order (the order the device applied them). The two
        halves are the turn's ``serve.step.sync`` and
        ``serve.step.bookkeep`` phases."""
        recs = [self._pipeline.popleft() for _ in range(count)]
        with phase(PHASE_SYNC, blocks=count):
            flat, lags = self._sync(recs)
        with phase(PHASE_BOOKKEEP) as span:
            done = len(self._done)
            read, ring = self.kv_blocks_read, self.kv_blocks_ring
            in_place = self.latent_rows_in_place
            hyper = self.hyper_rows
            window = self._window_counts()
            rows = self.state_rows
            s_read, s_held = self.state_rows_read, self.state_rows_held
            e_touched, e_read, e_held, e_asg = (
                self.experts_touched, self.experts_read, self.experts_held,
                self.expert_assignments)
            self._span_tokens_max = 0
            tokens = self._bookkeep(recs, flat, lags)
            span.set_metadata(tokens=tokens,
                              completions=len(self._done) - done,
                              kv_blocks_read=self.kv_blocks_read - read,
                              kv_blocks_ring=self.kv_blocks_ring - ring,
                              latent_rows_in_place=(
                                  self.latent_rows_in_place - in_place),
                              hyper_rows=self.hyper_rows - hyper,
                              state_rows=self.state_rows - rows,
                              state_rows_read=self.state_rows_read - s_read,
                              state_rows_held=self.state_rows_held - s_held)
            if self.cfg.n_window_layers:
                span.set_metadata(**{k: v - window[k] for k, v in
                                     self._window_counts().items()})
            if self._routed:
                held = self.experts_held - e_held
                span.set_metadata(
                    experts_touched=self.experts_touched - e_touched,
                    experts_read=self.experts_read - e_read,
                    experts_held=held,
                    expert_tokens_max=self._span_tokens_max,
                    expert_tokens_mean=self._expert_tokens_mean(
                        self.expert_assignments - e_asg, held))

    def _expert_tokens_mean(self, assignments: int, held_steps: int) -> float:
        """An expert's mean load: ``assignments`` over the (expert, layer,
        step) places of ALL the experts, held here or not, that
        ``held_steps`` (held x routed layers x steps) stands for."""
        places = held_steps * self.cfg.moe_experts // self.cfg.experts_held[1]
        return assignments / places if places else 0.0

    def _sync(self, recs) -> tuple:
        """-> (the blocks' packed results on the host, each block's
        measured device lag or None)."""
        if len(recs) == 1:
            flat = np.asarray(recs[0]["packed"])
        else:
            flat = np.asarray(
                jnp.concatenate([r["packed"] for r in recs], axis=1))
        # measured device lag: the transfer above forced every block in
        # this batch ready, so the reaper's serial walk completes in
        # microseconds — resolving the NEWEST seq first lets every older
        # one be read without waiting. lag = host observation instant
        # minus the block's device-ready instant: the real number behind
        # the documented "lags by up to pipeline_depth blocks" bound.
        t_obs = time.monotonic()
        tracker = self.dispatch_tracker
        tracker.ready_time(recs[-1].get("seq", -1), timeout=0.25)
        lags: list[float | None] = []
        for rec in recs:
            rt = tracker.ready_time(rec.get("seq", -1))
            lag = max(0.0, t_obs - rt) if rt is not None else None
            lags.append(lag)
            if lag is not None:
                self.telemetry.observe("device_lag_s", lag)
        return flat, lags

    def _bookkeep(self, recs, flat, lags) -> int:
        """Replay the synced blocks into the host's state; -> the tokens
        newly emitted (each fed to its request's stream, if it has one)."""
        fed = 0
        col = 0
        for i, rec in enumerate(recs):
            # records carry their own packed width: plain decode blocks
            # are [S, block+2] (+ the logprob columns when lp_k was on),
            # spec rounds [S, gamma+4] (emissions, raw acceptance count,
            # length, active) — and gammas vary across rounds when the
            # autotuner moves
            w = rec.get("w", self.block_size + 2)
            packed = flat[:, col:col + w]
            col += w
            if self._recurrent:     # the decode block's last column
                self.state_rows += int(packed[:, -1].sum())
                packed = packed[:, :-1]
            picks = None
            if self._routed:
                # the routed layers' columns (`_decode_block`): the experts
                # chosen, then the block's three counts in their first row
                n = routed_columns(self.cfg, self.block_size)
                touched, streamed, busiest = (int(v) for v in packed[0, -3:])
                picks = packed[:, -n:-3].reshape(
                    self.slots, self.block_size, self.cfg.n_routed_layers,
                    self.cfg.moe_top_k)
                packed = packed[:, :-n]
                self.experts_touched += touched
                self.experts_read += streamed
                self.experts_held += (self.cfg.experts_held[1]
                                      * self.cfg.n_routed_layers
                                      * self.block_size)
                self.expert_tokens_max = max(self.expert_tokens_max, busiest)
                self._span_tokens_max = max(self._span_tokens_max, busiest)
            lag = lags[i]
            gamma = rec.get("spec_gamma")
            lp_k = rec.get("lp_k", 0) or 0
            lp_chosen = lp_ids = lp_vals = None
            if gamma is not None:
                toks, n_accs, lengths, active = (
                    packed[:, :gamma + 1], packed[:, gamma + 1],
                    packed[:, gamma + 2], packed[:, gamma + 3].astype(bool))
            elif lp_k:
                B = self.block_size
                toks = packed[:, :B]
                n_accs = None
                lengths, active = packed[:, B], packed[:, B + 1].astype(bool)
                # the logprob columns ride the same int32 transfer:
                # f32 values bitcast at pack time, viewed back here
                base = B + 2
                lp_chosen = np.ascontiguousarray(
                    packed[:, base:base + B]).view(np.float32)
                lp_ids = np.ascontiguousarray(
                    packed[:, base + B:base + B + B * lp_k]
                ).reshape(-1, B, lp_k)
                lp_vals = np.ascontiguousarray(
                    packed[:, base + B + B * lp_k:
                           base + B + 2 * B * lp_k]
                ).view(np.float32).reshape(-1, B, lp_k)
            else:
                toks, n_accs, lengths, active = (
                    packed[:, :-2], None, packed[:, -2],
                    packed[:, -1].astype(bool))
            self._count_kv_blocks(rec, lengths, active)
            if self._recurrent:
                self._count_state_rows(lengths)
            if self.cfg.hc_mult and "offsets" in rec:   # a decode block
                self.hyper_rows += (self.slots * 2 * self.cfg.n_layers
                                    * self.block_size)
            for slot in np.nonzero(self._expect_active)[0]:
                if slot in self._stop_cancelled:
                    continue
                if n_accs is not None:
                    # speculative bookkeeping: the RAW acceptance count
                    # (true draft-target agreement, pre-clamp — the solo
                    # stats convention) feeds the per-slot EWMA the
                    # autotuner steers gamma from, the acceptance-rate
                    # histogram, and the proposed/accepted counters
                    acc = int(n_accs[slot])
                    rate = acc / gamma if gamma else 0.0
                    self.spec_proposed_tokens += gamma
                    self.spec_accepted_tokens += acc
                    self._accept_ewma[slot] += self._spec_ewma_alpha * (
                        rate - self._accept_ewma[slot])
                    self.spec_accept_hist.observe(rate)
                    self._spec_round_counts[slot] += 1
                    self._spec_accepted_counts[slot] += acc
                n = int(lengths[slot] - self._expect_len[slot])
                had_tokens = bool(self._emitted[slot])
                req = self._requests[slot]
                new = [int(t) for t in toks[slot, :n]]
                stop_hit = False
                if new and req is not None and req.stop:
                    # per-request stop sequences, checked at the
                    # durability point so journal/stream/replay all see
                    # the truncated stream; a match may START inside
                    # already-delivered tokens but must END in this
                    # batch (delivered tokens are never retracted)
                    prev_len = len(self._emitted[slot])
                    cand = self._emitted[slot] + new
                    end = _stop_match_end(cand, req.stop, start=prev_len)
                    if end is not None:
                        new = cand[prev_len:end]
                        stop_hit = True
                n_new = len(new)
                fed += n_new
                self._emitted[slot].extend(new)
                if picks is not None:
                    # a row that emitted n tokens took the block's first n
                    # steps (a stop match may have cut ``new`` shorter)
                    self.expert_assignments += (
                        n * self.cfg.n_routed_layers * self.cfg.moe_top_k)
                    if req is not None and req.routes:
                        self._route_acc[slot].append(
                            picks[slot, :n].copy())
                if (n_new and lp_chosen is not None and req is not None
                        and req.logprobs):
                    k = req.logprobs
                    for j in range(n_new):
                        self._lp_acc[slot].append({
                            "token": new[j],
                            "logprob": round(
                                float(lp_chosen[slot, j]), 6),
                            "top": [
                                [int(t) for t in lp_ids[slot, j, :k]],
                                [round(float(v), 6)
                                 for v in lp_vals[slot, j, :k]]]})
                if n_new > 0 and req is not None and \
                        self._journal is not None:
                    # durability point: the journaled prefix advances at
                    # processing time (host-known tokens only — replay
                    # from any true prefix is exact, the pipeline lag
                    # just re-decodes)
                    self._journal.emit(req.id, new)
                if n_new > 0 and req is not None:
                    # streaming delivery at the SAME instant: the
                    # absolute-position feed appends only the unseen
                    # suffix (resume prefixes flow on the first
                    # processed block, replays never double-deliver)
                    self._stream_feed(req.id, self._emitted[slot])
                if not had_tokens and n_new > 0 and req is not None:
                    # first emitted token OBSERVED by the host — the TTFT
                    # span (lags the device by the processing pipeline;
                    # trace timestamps are host-monotonic by contract).
                    # The lag is no longer just documented: the dispatch
                    # tracker measured when this block went ready on
                    # device, and the difference rides the trace.
                    tr = self._traces.get(req.id)
                    if tr is not None and tr.t("first_token") is None:
                        tr.mark("first_token")
                        if lag is not None:
                            tr.attrs["device_lag_first_token_s"] = round(
                                lag, 6)
                if stop_hit:
                    # complete NOW with reason "stop" and free the
                    # device slot like a cancel (dispatch order is
                    # device order: blocks already dispatched decode
                    # dead tokens the bookkeeping skips; later blocks
                    # see an idle row). _stop_cancelled keeps the slot
                    # skipped until the deactivation is OBSERVED in a
                    # later block's packed state (or an admit event
                    # re-occupies the slot for a new request).
                    self._complete_slot(slot, req, "stop", lag)
                    if active[slot]:
                        self._d_active = _cancel_slot(
                            self._d_active, jnp.int32(slot),
                            shardings=self._shardings)
                        self._stop_cancelled.add(int(slot))
                    self._model_active[slot] = False
                    continue
                if not active[slot]:
                    out = self._emitted[slot]
                    reason = ("stop" if out and out[-1] in self.stop_tokens
                              else "length")
                    self._complete_slot(slot, req, reason, lag)
            self._expect_len = np.array(lengths)
            self._expect_active = np.array(active)
            for slot in list(self._stop_cancelled):
                if not active[slot]:
                    # the cancel program's effect reached this block:
                    # the ledger entry has done its job
                    self._stop_cancelled.discard(slot)
                else:
                    self._expect_active[slot] = False
            for kind, payload in rec["events"]:
                if kind == "admit":
                    self._apply_admit(payload)
                else:
                    self._apply_cancel(payload)
        return fed

    def _count_kv_blocks(self, rec, lengths, active) -> None:
        """Add one processed block to ``kv_blocks_read`` / ``_ring``: the
        KV blocks its last decode step streams, per layer, by the
        kernel's own rule (``live_kv_blocks`` on the block's final
        lengths and the offsets it was dispatched with; a row that
        emitted in the block counts as live for it) against the blocks
        its slots' rings hold. Where the step runs the einsum (a mesh,
        the CPU, a short ring, a speculative round) it reads them all.

        With window layers the two counts are over one period of the
        pattern's attention layers (its full and window layers in their
        ratio: 1 and 3 for three window layers to a full one), a window
        ring's blocks by the same rule through `window_view`; and
        ``window_blocks_read`` / ``_context`` add a window layer's blocks
        read and those its live rows' whole contexts span (the blocks of
        the full ring holding each row's positions 0 .. length), and
        ``live_positions`` / ``window_positions`` the positions those rows
        hold and the ones a window layer's query sees, min(length,
        win_size) a row.

        Where the decode kernel engages a decode block also adds its
        latent rows written in place to ``latent_rows_in_place``."""
        ring = self.slots * -(-self.max_len // self._kv_block_k)
        read = ring
        block = "offsets" in rec        # a decode block, no speculative round
        live = active | (lengths > self._expect_len)
        if self._decode_kernel and block:
            # every row's latent row, an idle row's dead one included,
            # each step and latent layer (`latent_kernel_engages`)
            self.latent_rows_in_place += (
                self.slots * self.cfg.n_latent_layers * self.block_size)
            read = int(live_kv_blocks(
                rec["offsets"], lengths, live, block_k=self._kv_block_k,
                m_cap=self.max_len, window=self.cfg.attn_window or 0,
            )[1].sum())
        if self.cfg.n_window_layers:
            r, bk = self._window_ring, self._win_block_k
            w_ring = w_read = self.slots * -(-r // bk)
            if self._win_kernel and block:
                w_len, w_off = window_view(lengths, rec["offsets"], r, 1,
                                           xp=np)
                w_read = int(live_kv_blocks(
                    w_off, w_len, live, block_k=bk, m_cap=r,
                    window=self.cfg.win_size)[1].sum())
            self.window_blocks_read += w_read
            self.window_blocks_context += int(live_kv_blocks(
                rec.get("offsets", np.zeros_like(lengths)), lengths, live,
                block_k=bk, m_cap=self.max_len)[1].sum())
            held = np.where(live, lengths, 0)
            self.live_positions += int(held.sum())
            self.window_positions += int(
                np.minimum(held, self.cfg.win_size).sum())
            n_full, n_win = self.cfg.n_attn_layers, self.cfg.n_window_layers
            g = math.gcd(n_full, n_win)
            read = (n_full * read + n_win * w_read) // g
            ring = (n_full * ring + n_win * w_ring) // g
        self.kv_blocks_read += read
        self.kv_blocks_ring += ring

    def _window_counts(self) -> dict:
        return {"window_blocks_read": self.window_blocks_read,
                "window_blocks_context": self.window_blocks_context,
                "live_positions": self.live_positions,
                "window_positions": self.window_positions}

    def _count_state_rows(self, lengths) -> None:
        """Add one processed block to ``state_rows_read`` / ``_held``:
        the rows whose state tiles its last decode step streams, a linear
        layer, by the kernel's own rule (``live_state_rows`` on the rows
        live at that step: those that took every one of the block's
        steps) against the slots. Where the step runs ``gated_delta_step``
        on the layer's slice (a mesh, the CPU) it reads them all."""
        read = self.slots
        if self._state_kernel:
            at_last = (lengths - self._expect_len) == self.block_size
            read = int(live_state_rows(at_last)[1])
        self.state_rows_read += read
        self.state_rows_held += self.slots

    def _complete_slot(self, slot: int, req: Request, reason: str,
                       lag: float | None) -> None:
        """Deliver one slot's finished request (natural end or a
        per-request stop match) and free the host-side slot state —
        the single completion point both paths in ``_process`` share."""
        out = self._emitted[slot]
        if lag is not None:
            tr = self._traces.get(req.id)
            if tr is not None:
                tr.attrs["device_lag_s"] = round(lag, 6)
        if self._spec:
            tr = self._traces.get(req.id)
            if tr is not None:
                tr.attrs["spec_rounds"] = int(
                    self._spec_round_counts[slot])
                tr.attrs["spec_accepted_tokens"] = int(
                    self._spec_accepted_counts[slot])
            if self._spec_round_counts[slot]:
                self.spec_rounds_hist.observe(
                    float(self._spec_round_counts[slot]))
            self._spec_round_counts[slot] = 0
            self._spec_accepted_counts[slot] = 0
        lps = self._lp_acc[slot] if req.logprobs else None
        if lps is not None and len(lps) > len(out):
            lps = lps[:len(out)]
        routes = None
        if req.routes:
            # the prefill's pieces come off the device only now (each an
            # array some earlier dispatch left there), then the decode
            # steps' in order: one row a position the request consumed
            routes = np.concatenate(
                [np.asarray(dev)[row, :nv] for dev, row, nv
                 in self._route_prefill.get(req.id, ())]
                + self._route_acc[slot]
                + [np.zeros((0, self.cfg.n_routed_layers,
                             self.cfg.moe_top_k), np.int32)])
            # a per-request stop match ends the answer short of what the
            # device had consumed
            routes = routes[:len(req.prompt) + len(out) - 1]
        self._done[req.id] = Completion(
            req.id, out, reason,
            trace=self._finish_trace(
                req.id, "finished", n_tokens=len(out), reason=reason),
            logprobs=lps, routes=routes)
        self._finish_stream(req.id)
        self._requests[slot] = None
        self._emitted[slot] = []
        self._lp_acc[slot] = []
        self._route_acc[slot] = []
        self._host_busy[slot] = False
        self._release_request(req.id)

    def _device_may_be_active(self) -> bool:
        if self._predictive:
            return bool(self._model_active.any())
        return bool(self._expect_active.any()) or any(
            kind == "admit"
            for r in self._pipeline for kind, _ in r["events"])

    def _inject_chaos(self) -> None:
        """Serving-side fault injection (constants.py TEST_SERVING_*):
        seeded, so a chaos run's fault sequence is reproducible — the
        n-th scheduling turn fails iff the n-th RNG draw does, regardless
        of wall-clock timing. Raises the same way a real dispatch failure
        (device loss, OOM) surfaces: out of step(), into the serving
        loop's recovery path."""
        if self._chaos_delay_ms:
            time.sleep(self._chaos_delay_ms / 1000)
        if (self._chaos_fail_rate
                and self._chaos_rng.random() < self._chaos_fail_rate):
            self.chaos_faults_injected += 1
            raise RuntimeError(
                "chaos: injected serving dispatch failure "
                f"#{self.chaos_faults_injected}")

    def step(self) -> None:
        """One scheduling turn.

        Predictive mode (no stop tokens): admission comes straight off the
        exact host model, blocks dispatch open-loop, and nothing is synced
        until the results are wanted (drain) or the backlog hits the cap —
        the device never waits on the host.

        EOS mode: admit when the host's view is current, dispatch a block
        if any slot may be running, and burst-process blocks beyond the
        pipeline depth (all of them on the drain tail)."""
        self._inject_chaos()
        if self._predictive:
            self._admit()
            if self._device_may_be_active():
                self._dispatch()
            elif self._pipeline:
                self._process(len(self._pipeline))
            if len(self._pipeline) >= 64:      # bound host-side backlog
                self._process(len(self._pipeline) - self.pipeline_depth)
            return
        if not self._pipeline:
            self._admit()
        dispatched = False
        if self._device_may_be_active():
            self._dispatch()
            dispatched = True
        depth = self.pipeline_depth if dispatched else 0
        if len(self._pipeline) > depth:
            self._process(len(self._pipeline) - depth)
            self._admit()

    def checkpoint_progress(self) -> None:
        """Durability checkpoint: process every in-flight block EXCEPT
        the newest ``pipeline_depth``, advancing the journal's emitted
        prefixes (and delivering any finished-but-unprocessed
        completions) without draining the dispatch runway — on an
        open-loop backlog the processed blocks went device-ready long
        ago, so the cost is one packed device->host transfer, never a
        stall. Without this, sparse predictive traffic only processes
        at completion, leaving a solo request's journal/ /progress
        prefix empty for its whole decode — a failover would restart
        it from scratch. ``ServeApp`` calls this on a
        ``journal_checkpoint_s`` cadence (serve
        ``--journal-checkpoint-s``; each checkpoint costs one packed
        device->host transfer — tune or disable accordingly)."""
        n = len(self._pipeline) - self.pipeline_depth
        if n > 0:
            self._process(n)

    def drain_completed(self) -> dict[int, Completion]:
        if self._predictive and self._pipeline and not self._done:
            self._process(len(self._pipeline))
        done, self._done = self._done, {}
        return done

    def run_until_drained(self) -> dict[int, Completion]:
        """Serve until the queue, every slot, and the pipeline are empty."""
        out: dict[int, Completion] = {}
        while not self.idle:
            self.step()
            if self._done:
                out.update(self.drain_completed())
        out.update(self.drain_completed())
        return out


__all__ = ["Request", "Completion", "SlotServer", "PrefixCache",
           "BlockAllocator", "QueueFullError", "RequestJournal",
           "ModelEntry", "ModelRegistry",
           "COMPLETION_FINISH_REASONS", "FINISH_REASONS",
           "PRIORITY_CLASSES",
           "KV_TRANSFER_VERSION", "KV_IMPORT_KEYS", "KV_ENTRY_KEYS",
           "serialize_kv_blocks", "deserialize_kv_blocks"]
