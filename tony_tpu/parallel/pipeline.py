"""Pipeline parallelism over the ``pipe`` mesh axis.

GPipe-style microbatch schedule expressed as a single shard_map program:
layer parameters are stacked [n_stages, ...] and sharded over ``pipe``; each
device applies its stage and passes activations to the next stage with
``lax.ppermute`` each tick. The whole schedule is one `lax.scan`, so XLA sees
static control flow (no data-dependent Python) and can overlap the ppermute
with stage compute. Bubble fraction is (S-1)/(M+S-1) for S stages and M
microbatches, as usual for GPipe.

The reference cannot express any of this (SURVEY.md §2.3) — pipelining here
is a first-class library feature, not an orchestration concern.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

StageFn = Callable[[Any, jax.Array], jax.Array]  # (stage_params, x) -> y


def _pipeline_local(
    stage_fn: StageFn,
    stage_params: Any,
    microbatches: jax.Array,  # [M, mb, ...] identical on every device
    axis_name: str,
    squeeze_stage_dim: bool = True,
    has_aux: bool = False,
) -> jax.Array:
    """Runs on one device inside shard_map; stage_params is this device's
    stage slice (leading dim squeezed when it is a single stage; kept when
    the stage holds a stack of layers — see make_pipeline_stacked).

    With has_aux, stage_fn returns (y, aux_scalar) and the pipeline also
    returns the aux sum over all (stage, real-microbatch) applications —
    how MoE load-balancing losses survive pipelining."""
    n = lax.psum(1, axis_name)
    me = lax.axis_index(axis_name)
    m = microbatches.shape[0]
    total = m + n - 1
    mb_shape = microbatches.shape[1:]

    if squeeze_stage_dim:
        params = jax.tree.map(lambda p: jnp.squeeze(p, axis=0), stage_params)
    else:
        params = stage_params

    def tick(carry, t):
        inbox, outputs, aux_acc = carry
        # stage 0 feeds itself from the microbatch stream; other stages read
        # their inbox (written by the previous stage last tick)
        feed = microbatches[jnp.minimum(t, m - 1)]
        x = jnp.where(me == 0, feed, inbox)
        if has_aux:
            y, aux = stage_fn(params, x)
            # this device processes a REAL microbatch only during its
            # active window t in [me, me + m); outside it the tick carries
            # wrap-around garbage whose aux must not count
            real = (t >= me) & (t < me + m)
            aux_acc = aux_acc + jnp.where(real, aux.astype(jnp.float32), 0.0)
        else:
            y = stage_fn(params, x)
        # last stage records its result at slot t - (n - 1)
        slot = t - (n - 1)
        valid = (slot >= 0) & (me == n - 1)
        outputs = lax.cond(
            valid,
            lambda o: lax.dynamic_update_index_in_dim(
                o, y, jnp.maximum(slot, 0), axis=0
            ),
            lambda o: o,
            outputs,
        )
        # pass activations forward around the ring (stage i -> i+1; the wrap
        # edge n-1 -> 0 carries garbage that stage 0 ignores)
        inbox_next = lax.ppermute(
            y, axis_name, [(i, (i + 1) % n) for i in range(n)]
        )
        return (inbox_next, outputs, aux_acc), None

    inbox0 = jnp.zeros(mb_shape, microbatches.dtype)
    outputs0 = jnp.zeros((m,) + mb_shape, microbatches.dtype)
    (_, outputs, aux_acc), _ = lax.scan(
        tick, (inbox0, outputs0, jnp.float32(0)), jnp.arange(total)
    )
    # only stage n-1 holds real outputs; broadcast via masked psum so the
    # shard_map output is replicated across the pipe axis
    outputs = lax.psum(
        jnp.where(me == n - 1, outputs, jnp.zeros_like(outputs)), axis_name
    )
    if has_aux:
        return outputs, lax.psum(aux_acc, axis_name)
    return outputs


def make_pipeline(
    mesh: Mesh,
    stage_fn: StageFn,
    num_microbatches: int,
    axis_name: str = "pipe",
) -> Callable[[Any, jax.Array], jax.Array]:
    """Returns pipeline_apply(stacked_params, batch) -> batch.

    stacked_params: pytree with leading dim n_stages on every leaf, sharded
    over `axis_name`. batch: [B, ...] replicated w.r.t. `axis_name`; B must
    divide into num_microbatches.
    """
    n_stages = mesh.shape[axis_name]

    def apply(stacked_params: Any, batch: jax.Array) -> jax.Array:
        b = batch.shape[0]
        if b % num_microbatches:
            raise ValueError(f"batch {b} not divisible by {num_microbatches} microbatches")
        mb = b // num_microbatches
        micro = batch.reshape((num_microbatches, mb) + batch.shape[1:])

        param_specs = jax.tree.map(lambda _: P(axis_name), stacked_params)
        fn = shard_map(
            functools.partial(_pipeline_local, stage_fn, axis_name=axis_name),
            mesh=mesh,
            in_specs=(param_specs, P()),
            out_specs=P(),
            check_vma=False,
        )
        out = fn(stacked_params, micro)
        return out.reshape((b,) + out.shape[2:])

    return apply


def stack_stage_params(per_stage_params: list[Any]) -> Any:
    """[stage0_tree, stage1_tree, ...] -> one tree with leading stage dim."""
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *per_stage_params)


# --------------------------------------------------------------- circular

def _pipeline_circular_local(
    stage_fn, stage_params, microbatches, axis_name, num_chunks,
    has_aux=False,
):
    """Circular (interleaved) schedule on one device inside shard_map.

    The device holds `num_chunks` NON-adjacent layer chunks (stage_params
    leading dim V); an item traverses stages 0..S-1 with chunk 0, wraps the
    ring back to stage 0 for chunk 1, and so on. Stage 0 prioritises
    wrapped items over fresh microbatch injection (at most one wrapped
    item can arrive per tick, so no deeper buffer is needed). Each tick
    runs one chunk (1/V of a GPipe stage), and with M a multiple of S
    (enforced by the caller) the wrap arrivals tile stage 0's timeline
    densely — every item flows delay-free and the last completes at tick
    V*M + S - 2 (Megatron's interleaved/virtual-pipeline schedule). The
    fill/drain bubble is therefore S-1 chunk-ticks against GPipe's
    V*(S-1): V× cheaper. Without the M % S == 0 constraint the injection
    pattern de-phases from the wraps and the static tick count would have
    to cover a far worse worst case, erasing the win.

    Items carry (x, chunk, mb, live) through the ring; outputs are items
    leaving the last stage with the last chunk."""
    S = lax.psum(1, axis_name)
    me = lax.axis_index(axis_name)
    M = microbatches.shape[0]
    V = num_chunks
    mb_shape = microbatches.shape[1:]
    T = V * M + S  # completion at V*M + S - 2; one slack tick
    # local param view is [V, 1, per_chunk, ...] (stage axis sharded away)
    stage_params = jax.tree.map(lambda p: jnp.squeeze(p, 1), stage_params)

    def tick(carry, t):
        (in_x, in_chunk, in_mb, in_live, next_mb, outputs, aux_acc) = carry
        # stage 0: a wrapped item (live arrival) wins; otherwise inject the
        # next fresh microbatch if any remain
        inject = (me == 0) & (~in_live) & (next_mb < M)
        feed = microbatches[jnp.clip(next_mb, 0, M - 1)]
        x = jnp.where(inject, feed, in_x)
        chunk = jnp.where(inject, 0, in_chunk)
        mb = jnp.where(inject, next_mb, in_mb)
        live = in_live | inject
        next_mb = next_mb + inject.astype(next_mb.dtype)

        lp = jax.tree.map(
            lambda p: lax.dynamic_index_in_dim(
                p, jnp.clip(chunk, 0, V - 1), 0, keepdims=False
            ),
            stage_params,
        )
        if has_aux:
            y, aux = stage_fn(lp, x)
            # idle ticks run on garbage — their aux must not count
            aux_acc = aux_acc + jnp.where(live, aux.astype(jnp.float32), 0.0)
        else:
            y = stage_fn(lp, x)

        done = live & (me == S - 1) & (chunk == V - 1)
        slot = jnp.clip(mb, 0, M - 1)
        old = lax.dynamic_index_in_dim(outputs, slot, 0, keepdims=False)
        outputs = lax.dynamic_update_index_in_dim(
            outputs, jnp.where(done, y, old), slot, 0
        )

        # forward around the ring; the wrap edge S-1 -> 0 carries the item
        # into its next chunk
        out_chunk = chunk + (me == S - 1).astype(chunk.dtype)
        out_live = live & ~done
        nxt_x = lax.ppermute(y, axis_name, [(i, (i + 1) % S) for i in range(S)])
        nxt_chunk = lax.ppermute(
            out_chunk, axis_name, [(i, (i + 1) % S) for i in range(S)]
        )
        nxt_mb = lax.ppermute(mb, axis_name, [(i, (i + 1) % S) for i in range(S)])
        nxt_live = lax.ppermute(
            out_live, axis_name, [(i, (i + 1) % S) for i in range(S)]
        )
        return (nxt_x, nxt_chunk, nxt_mb, nxt_live, next_mb, outputs,
                aux_acc), None

    carry0 = (
        jnp.zeros(mb_shape, microbatches.dtype),
        jnp.int32(0),                       # chunk of inbox item
        jnp.int32(0),                       # mb of inbox item
        jnp.bool_(False),                   # inbox holds a live item
        jnp.int32(0),                       # next fresh microbatch
        jnp.zeros((M,) + mb_shape, microbatches.dtype),
        jnp.float32(0),                     # aux sum over live applications
    )
    (_, _, _, _, _, outputs, aux_acc), _ = lax.scan(
        tick, carry0, jnp.arange(T)
    )
    # completed outputs live on the last stage; replicate
    outputs = lax.psum(
        jnp.where(me == S - 1, outputs, jnp.zeros_like(outputs)), axis_name
    )
    if has_aux:
        return outputs, lax.psum(aux_acc, axis_name)
    return outputs


def make_pipeline_circular(
    mesh: Mesh,
    stage_fn,
    num_microbatches: int,
    num_chunks: int,
    axis_name: str = "pipe",
    has_aux: bool = False,
    expect_chunked: bool = False,
):
    """Circular/interleaved pipeline: stacked_params' leading layer dim is
    reshaped to [V, S, layers_per_chunk] so device i holds V non-adjacent
    chunks {i, S+i, 2S+i, ...}; `stage_fn(chunk_stack, x)` applies one
    chunk. Bubble wall-time shrinks ~V× vs GPipe at the cost of V× more
    ring hops. Autodiff provides the backward (like make_pipeline_stacked).

    apply(stacked_params, batch) -> batch_out (or (batch_out, aux_sum)
    with has_aux); stacked_params as for make_pipeline_stacked
    ([n_layers, ...] leaves, n_layers divisible by S * V) — or already
    chunked to [V, S, per_chunk, ...] with expect_chunked=True (how a
    train step keeps the params stored in the schedule's native layout,
    avoiding a per-step reshard).
    """
    V = num_chunks
    S = mesh.shape[axis_name]

    def apply(stacked_params: Any, batch: jax.Array):
        b = batch.shape[0]
        if b % num_microbatches:
            raise ValueError(
                f"batch {b} not divisible by {num_microbatches} microbatches"
            )
        if num_microbatches % S:
            # the dense (delay-free) schedule — and therefore the tight
            # tick count — needs injections grouped in multiples of S
            raise ValueError(
                f"circular schedule needs num_microbatches "
                f"({num_microbatches}) divisible by pipeline stages ({S})"
            )
        mb = b // num_microbatches
        micro = batch.reshape((num_microbatches, mb) + batch.shape[1:])
        if expect_chunked:
            chunked = stacked_params
        else:
            n_layers = jax.tree.leaves(stacked_params)[0].shape[0]
            if n_layers % (S * V):
                raise ValueError(
                    f"n_layers {n_layers} not divisible by stages*chunks "
                    f"{S * V}"
                )
            # [n_layers] -> [V, S, per_chunk]: chunk v on stage s holds
            # layers [(v*S + s) * per_chunk, ...) — consecutive layers stay
            # together within a chunk, chunks interleave across the ring
            per_chunk = n_layers // (S * V)
            chunked = jax.tree.map(
                lambda p: p.reshape((V, S, per_chunk) + p.shape[1:]),
                stacked_params,
            )
        param_specs = jax.tree.map(
            lambda _: P(None, axis_name), chunked
        )
        fn = shard_map(
            functools.partial(
                _pipeline_circular_local, stage_fn, axis_name=axis_name,
                num_chunks=V, has_aux=has_aux,
            ),
            mesh=mesh,
            in_specs=(param_specs, P()),
            out_specs=(P(), P()) if has_aux else P(),
            check_vma=False,
        )
        if has_aux:
            out, aux = fn(chunked, micro)
            return out.reshape((b,) + out.shape[2:]), aux
        out = fn(chunked, micro)
        return out.reshape((b,) + out.shape[2:])

    return apply


# ------------------------------------------------------------------- 1F1B

def _tree_scale_add(acc, delta, mask):
    return jax.tree.map(lambda a, d: a + d.astype(a.dtype) * mask, acc, delta)


def _pipeline_1f1b_local(
    stage_fn, head_fn, aux_cot,
    stage_params, head_params, microbatches, targets, head_cot,
    axis_name: str,
):
    """One device's 1F1B schedule inside shard_map.

    Round r (r = 0..M+2S-3), stage i:
      forward  of microbatch mf = r - i            (if 0 <= mf < M)
      backward of microbatch mb = r - (2S-2-i)     (if 0 <= mb < M)
    The last stage runs the head (loss) on each forward output and starts
    that microbatch's backward the same round; gradients flow stage i ->
    i-1 one round apart, so each stage holds at most 2(S-1-i)+1 <= 2S-1
    forward activations — an O(S) residual ring buffer instead of GPipe's
    O(M) live set (the schedule of Narayanan et al.'s PipeDream-flush /
    Megatron 1F1B). Backward recomputes the stage forward from the saved
    input (activation recomputation), so residuals are stage INPUTS only.

    stage_fn(params, x) -> (y, aux_scalar); head_fn(head_params, y, target)
    -> scalar loss contribution. Gradients are pre-scaled through the
    cotangents: head calls get `head_cot` (a traced scalar), aux outputs get
    `aux_cot` — so the returned grads need no further normalisation.
    Returns (loss_sum [unscaled], aux_sum, dstage_params, dhead_params,
    dx_per_microbatch)."""
    S = lax.psum(1, axis_name)
    me = lax.axis_index(axis_name)
    M = microbatches.shape[0]
    mb_shape = microbatches.shape[1:]
    R = 2 * S - 1  # residual ring slots (max in-flight per stage)
    T = M + 2 * (S - 1)

    f32 = jnp.float32

    def fwd_only(p, x):
        return stage_fn(p, x)[0]

    def round_(carry, r):
        (fwd_inbox, bwd_inbox, resid, dparams, dhead, dx_out,
         loss_acc, aux_acc) = carry

        # ---------------- forward half ----------------
        mf = r - me
        f_valid = (mf >= 0) & (mf < M)
        feed = microbatches[jnp.clip(mf, 0, M - 1)]
        x_in = jnp.where(me == 0, feed, fwd_inbox)
        y, aux = stage_fn(stage_params, x_in)
        # jnp.where, not aux * f_mask: warmup/drain rounds run the stage on
        # garbage activations whose aux may be non-finite, and NaN*0=NaN
        aux_acc = aux_acc + jnp.where(f_valid, aux.astype(f32), 0.0)
        # save the stage input for backward recompute; masked read-modify-
        # write so invalid rounds leave the buffer untouched
        slot_f = jnp.clip(mf, 0, M - 1) % R
        old = lax.dynamic_index_in_dim(resid, slot_f, 0, keepdims=False)
        resid = lax.dynamic_update_index_in_dim(
            resid, jnp.where(f_valid, x_in, old), slot_f, 0
        )

        # head at the last stage: loss + dy for this microbatch's backward,
        # which starts this same round. lax.cond so the (potentially
        # vocab-sized) head fwd+vjp only executes on the last stage's real
        # rounds, not S*(M+2S-2) times
        tgt = targets[jnp.clip(mf, 0, M - 1)]
        head_on = (me == S - 1) & f_valid

        def do_head(ops):
            hp, yy = ops
            loss_mb, vjp_head = jax.vjp(
                lambda hp_, yy_: head_fn(hp_, yy_, tgt), hp, yy
            )
            dhead_mb, dy = vjp_head(head_cot.astype(loss_mb.dtype))
            return loss_mb.astype(f32), dhead_mb, dy

        def skip_head(ops):
            hp, yy = ops
            return (f32(0), jax.tree.map(jnp.zeros_like, hp),
                    jnp.zeros_like(yy))

        loss_mb, dhead_mb, dy_own = lax.cond(
            head_on, do_head, skip_head, (head_params, y)
        )
        loss_acc = loss_acc + loss_mb  # already zero when head_on is false
        dhead = _tree_scale_add(dhead, dhead_mb, f32(1))

        # ---------------- backward half ----------------
        mb_ = r - (2 * S - 2 - me)
        b_valid = (mb_ >= 0) & (mb_ < M)
        dy_in = jnp.where(me == S - 1, dy_own, bwd_inbox)
        slot_b = jnp.clip(mb_, 0, M - 1) % R
        x_saved = lax.dynamic_index_in_dim(resid, slot_b, 0, keepdims=False)

        def do_bwd(ops):
            dy, xs = ops
            (_, _), vjp_stage = jax.vjp(stage_fn, stage_params, xs)
            return vjp_stage((dy, f32(aux_cot)))

        def skip_bwd(ops):
            dy, xs = ops
            return jax.tree.map(jnp.zeros_like, stage_params), jnp.zeros_like(xs)

        # cond: the recompute+vjp (the schedule's dominant cost) is skipped
        # on warmup/drain rounds instead of being computed and masked
        dp_mb, dx = lax.cond(b_valid, do_bwd, skip_bwd, (dy_in, x_saved))
        dparams = _tree_scale_add(dparams, dp_mb, f32(1))  # cond zeroed invalid
        # stage 0's dx is d(embedded input) — recorded for the caller's
        # embedding gradient
        is_first = ((me == 0) & b_valid)
        old_dx = lax.dynamic_index_in_dim(
            dx_out, jnp.clip(mb_, 0, M - 1), 0, keepdims=False
        )
        dx_out = lax.dynamic_update_index_in_dim(
            dx_out, jnp.where(is_first, dx, old_dx), jnp.clip(mb_, 0, M - 1), 0
        )

        # ---------------- ring exchanges ----------------
        fwd_next = lax.ppermute(
            y, axis_name, [(i, (i + 1) % S) for i in range(S)]
        )
        bwd_next = lax.ppermute(
            dx, axis_name, [(i, (i - 1) % S) for i in range(S)]
        )
        return (fwd_next, bwd_next, resid, dparams, dhead, dx_out,
                loss_acc, aux_acc), None

    carry0 = (
        jnp.zeros(mb_shape, microbatches.dtype),          # fwd inbox
        jnp.zeros(mb_shape, microbatches.dtype),          # bwd inbox (dy)
        jnp.zeros((R,) + mb_shape, microbatches.dtype),   # residual ring
        jax.tree.map(jnp.zeros_like, stage_params),       # dparams
        jax.tree.map(jnp.zeros_like, head_params),        # dhead
        jnp.zeros((M,) + mb_shape, microbatches.dtype),   # dx per microbatch
        f32(0),                                           # loss sum
        f32(0),                                           # aux sum
    )
    (_, _, _, dparams, dhead, dx_out, loss_acc, aux_acc), _ = lax.scan(
        round_, carry0, jnp.arange(T)
    )
    # losses/head grads live on the last stage, dx on the first — make all
    # outputs replicated across the pipe axis
    loss = lax.psum(loss_acc, axis_name)
    aux = lax.psum(aux_acc, axis_name)
    dhead = jax.tree.map(lambda g: lax.psum(g, axis_name), dhead)
    me_f = (me == 0).astype(dx_out.dtype)
    dx_out = lax.psum(dx_out * me_f, axis_name)
    return loss, aux, dparams, dhead, dx_out


def make_pipeline_1f1b(
    mesh: Mesh,
    stage_fn,
    head_fn,
    num_microbatches: int,
    aux_weight: float = 0.0,
    axis_name: str = "pipe",
    loss_denom_fn=None,
):
    """1F1B pipelined loss + gradients (forward AND backward inside one
    schedule). Unlike make_pipeline_stacked — whose backward falls out of
    autodiff and therefore keeps every microbatch's residuals live — this
    runs the PipeDream-flush schedule with an O(stages) residual buffer and
    activation recomputation, which is what makes deep-pipeline training
    fit in HBM at large microbatch counts.

    stage_fn(local_stack, x) -> (y, aux_scalar)
    head_fn(head_params, y_mb, target_mb) -> per-microbatch loss contribution

    loss_denom_fn(targets) -> scalar D: the head contributions are summed
    and divided by D. Default D = num_microbatches (right when head_fn
    returns per-microbatch MEANS). Pass e.g. the global valid-token count
    (with head_fn returning token SUMS) to weight every token equally
    regardless of how padding distributes across microbatches.

    apply(stacked_params, head_params, batch, targets) ->
        (loss, dstacked, dhead, dx[batch])
    where loss = sum_mb(head) / D + aux_weight * aux_sum / M and the
    gradients are exactly d loss / d (params, inputs) — scaled through the
    vjp cotangents, not by post-hoc division (the aux and head terms carry
    different normalisations).
    """
    M = num_microbatches

    def apply(stacked_params: Any, head_params: Any, batch: jax.Array,
              targets: jax.Array):
        b = batch.shape[0]
        if b % M:
            raise ValueError(f"batch {b} not divisible by {M} microbatches")
        mb = b // M
        micro = batch.reshape((M, mb) + batch.shape[1:])
        micro_t = targets.reshape((M, mb) + targets.shape[1:])
        denom = (
            jnp.float32(M) if loss_denom_fn is None
            else loss_denom_fn(targets).astype(jnp.float32)
        )
        head_cot = 1.0 / denom

        param_specs = jax.tree.map(lambda _: P(axis_name), stacked_params)
        head_specs = jax.tree.map(lambda _: P(), head_params)
        fn = shard_map(
            functools.partial(
                _pipeline_1f1b_local, stage_fn, head_fn, aux_weight / M,
                axis_name=axis_name,
            ),
            mesh=mesh,
            in_specs=(param_specs, head_specs, P(), P(), P()),
            out_specs=(P(), P(), param_specs, head_specs, P()),
            check_vma=False,
        )
        loss_sum, aux_sum, dparams, dhead, dx = fn(
            stacked_params, head_params, micro, micro_t, head_cot
        )
        loss = loss_sum * head_cot + aux_weight * aux_sum / M
        dx = dx.reshape((b,) + dx.shape[2:])
        return loss, dparams, dhead, dx

    return apply


def make_pipeline_stacked(
    mesh: Mesh,
    stage_fn: StageFn,
    num_microbatches: int,
    axis_name: str = "pipe",
    has_aux: bool = False,
) -> Callable[[Any, jax.Array], jax.Array]:
    """Pipeline over params whose leading dim is a LAYER stack (n_layers,
    divisible by the pipe-axis size): sharding that dim over `axis_name`
    hands each stage its contiguous run of layers, and `stage_fn(local_stack,
    x)` applies them (typically with lax.scan). This is how the flagship
    transformer pipelines without re-packing its [n_layers, ...] params.

    With has_aux, stage_fn returns (y, aux_scalar) per application and
    apply returns (batch_out, aux_sum)."""

    def apply(stacked_params: Any, batch: jax.Array):
        b = batch.shape[0]
        if b % num_microbatches:
            raise ValueError(
                f"batch {b} not divisible by {num_microbatches} microbatches"
            )
        mb = b // num_microbatches
        micro = batch.reshape((num_microbatches, mb) + batch.shape[1:])

        param_specs = jax.tree.map(lambda _: P(axis_name), stacked_params)
        fn = shard_map(
            functools.partial(
                _pipeline_local, stage_fn, axis_name=axis_name,
                squeeze_stage_dim=False, has_aux=has_aux,
            ),
            mesh=mesh,
            in_specs=(param_specs, P()),
            out_specs=(P(), P()) if has_aux else P(),
            check_vma=False,
        )
        if has_aux:
            out, aux = fn(stacked_params, micro)
            return out.reshape((b,) + out.shape[2:]), aux
        out = fn(stacked_params, micro)
        return out.reshape((b,) + out.shape[2:])

    return apply
