"""Blockwise (logits-free) cross entropy for large vocabularies.

The last matmul of an LM — ``hidden @ unembed`` — produces a [B*L, V] f32
logits tensor that usually dwarfs every activation in the model: at
B*L=32k, V=256k that is 32GB, and XLA autodiff keeps it (plus the softmax)
alive for the backward. This op fuses the unembed matmul with the softmax
cross entropy and with the reduction its callers make at once: it takes the
row weights (``valid / count`` for a mean, ``valid`` for a sum) and returns
the weighted sum of the rows' losses. Its cotangent is then one scalar, so
the gradients can be formed while a block of logits exists.

The blocks are chunks of rows under ``lax.scan``; a chunk's f32 logits
[chunk, V] are the largest value ever live, and the chunk's rows come from
the shapes (``chunk_rows``: the most rows whose logits fit
LOGITS_BUFFER_BYTES).

- loss only (the primal: evaluation, ``jit(loss_fn)`` without grad): one
  sweep of the unembedding — per chunk the logits, their logsumexp and each
  row's target logit.
- loss and gradients (the custom VJP's forward rule): **three** sweeps,
  forward + backward of one matrix product and no more. While a chunk's
  logits are live, ``ds = row_weight * (softmax - onehot)`` feeds dx[chunk]
  and dW (one [D, V] f32 accumulator carried over the chunks). The backward
  rule only scales dx and dW by the scalar cotangent: it has no sweep of
  its own, and nothing is recomputed.

Every product is a large dense matmul -> MXU-friendly. This is an XLA-level
fusion (scan + matmuls), not a Pallas kernel: the matmuls already saturate
the MXU and XLA fuses the elementwise tail into them, so a hand kernel
would only re-derive the same schedule.

Sharding note: on a mesh whose batch or sequence axes shard the rows, the
model-side dispatch (models/transformer.py token_nll) names those axes in
``shard``, and both rules run their loop under a ``shard_map`` over them:
each device chunks its own rows by its own row count, and dW is summed
across devices once, after the loop, in f32 (a scan carry under GSPMD could
not hold a partial sum, so GSPMD alone would reduce it every chunk). The
custom VJP sits outside the shard_map, so nothing is differentiated through
it. With the vocab dim mesh-sharded (tensor parallelism) token_nll keeps the
dense path, whose logits stay vocab-sharded.

No reference counterpart: TonY has no compute layer (SURVEY.md §2.3); this
is part of the TPU-native capability layer.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

# the most a chunk's f32 logits may take; with the row granule below, the
# whole rule for the chunk's rows
LOGITS_BUFFER_BYTES = 512 * 2**20
_ROW_GRANULE = 128


def chunk_rows(n: int, v: int) -> int:
    """Rows of one chunk for ``n`` rows against ``v`` columns: the fewest
    chunks whose [rows, V] f32 logits fit LOGITS_BUFFER_BYTES, the rows
    spread evenly over them in multiples of the row granule. ``n`` itself
    where one chunk holds every row."""
    most = max(LOGITS_BUFFER_BYTES // (4 * v) // _ROW_GRANULE, 1) * _ROW_GRANULE
    chunks = -(-n // most)
    if chunks == 1:
        return n
    return -(-n // (chunks * _ROW_GRANULE)) * _ROW_GRANULE


def _in_chunks(rows_per_chunk, v, *per_row):
    """[N, ...] arrays -> [chunks, rows, ...], the last chunk filled with
    zeros (rows of weight 0: they add exact zeros to loss, dx and dW)."""
    n = per_row[0].shape[0]
    rows = min(rows_per_chunk or chunk_rows(n, v), n)
    chunks = -(-n // rows)
    if chunks * rows != n:
        per_row = [jnp.pad(a, [(0, chunks * rows - n)] + [(0, 0)] * (a.ndim - 1))
                   for a in per_row]
    return [a.reshape(chunks, rows, *a.shape[1:]) for a in per_row]


def _chunk_nll(xc, w, tc):
    """A chunk's f32 logits [C, V], their logsumexp and the rows' losses."""
    logits = jnp.dot(xc, w, preferred_element_type=jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    target_logit = jnp.take_along_axis(logits, tc[:, None], axis=1)[:, 0]
    return logits, lse, lse - target_logit


def _local_loss(rows_per_chunk, x, w, targets, row_weights):
    """-> ((loss,), ()): what is summed over devices, what is per row."""
    def chunk(loss, rows_in):
        xc, tc, rwc = rows_in
        _, _, nll = _chunk_nll(xc, w, tc)
        return loss + jnp.sum(rwc * nll), None

    loss, _ = lax.scan(chunk, jnp.zeros((), jnp.float32), _in_chunks(
        rows_per_chunk, w.shape[1], x, targets, row_weights))
    return (loss,), ()


def _local_loss_and_grads(rows_per_chunk, x, w, targets, row_weights):
    """For a unit cotangent, chunk of rows by chunk -> ((loss, dW still
    f32), (dx, the rows' losses))."""
    n = x.shape[0]

    def chunk(carry, rows_in):
        loss, dw = carry
        xc, tc, rwc = rows_in
        logits, lse, nll = _chunk_nll(xc, w, tc)
        onehot = lax.broadcasted_iota(jnp.int32, logits.shape, 1) == tc[:, None]
        ds = rwc[:, None] * (jnp.exp(logits - lse[:, None]) - onehot)  # [C, V] f32
        dxc = jnp.dot(ds, w.T.astype(jnp.float32),
                      preferred_element_type=jnp.float32)
        dw = dw + jnp.dot(xc.astype(jnp.float32).T, ds,
                          preferred_element_type=jnp.float32)
        return (loss + jnp.sum(rwc * nll), dw), (dxc.astype(x.dtype), nll)

    carry0 = (jnp.zeros((), jnp.float32), jnp.zeros(w.shape, jnp.float32))
    (loss, dw), (dx, nll) = lax.scan(chunk, carry0, _in_chunks(
        rows_per_chunk, w.shape[1], x, targets, row_weights))
    return (loss, dw), (dx.reshape(-1, x.shape[1])[:n], nll.reshape(-1)[:n])


def _on_own_rows(local, shard, x, w, targets, row_weights):
    """``local`` on the rows flattened: all of them, or, with ``shard`` =
    (mesh, for each leading dim of the rows the mesh axes that shard it),
    each device's own under a shard_map over those axes, its sums (the
    loss; dW, still f32) then summed over the devices, once. What it
    returns per row comes back in the rows' shape."""
    def flat(x, w, targets, row_weights):
        sums, per_row = local(
            x.reshape(-1, x.shape[-1]), w, targets.reshape(-1),
            row_weights.reshape(-1).astype(jnp.float32))
        return sums, tuple(a.reshape(targets.shape + a.shape[1:])
                           for a in per_row)

    if shard is None:
        return flat(x, w, targets, row_weights)
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh, row_axes = shard
    axes = tuple(a for dim in row_axes for a in dim or ())

    def per_device(*args):
        sums, per_row = flat(*args)
        return lax.psum(sums, axes), per_row

    rows = P(*row_axes)
    return shard_map(
        per_device, mesh=mesh, in_specs=(rows, P(), rows, rows),
        out_specs=(P(), rows), axis_names=frozenset(axes), check_vma=False,
    )(x, w, targets, row_weights)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def blockwise_cross_entropy(x, w, targets, row_weights, rows_per_chunk=None,
                            shard=None):
    """Weighted sum over rows of the softmax cross entropy of ``x @ w``
    against ``targets``, without materializing the [N, V] logits.

    x: [..., D] hidden states (any float dtype; accumulation in f32)
    w: [D, V] unembedding matrix
    targets: [...] int
    row_weights: [...] float — 0 for padding rows, ``valid / count`` for a
        masked mean, ``valid`` for a sum
    rows_per_chunk (static): the rows whose logits are live at a time;
        None takes ``chunk_rows`` of the (device's) rows
    shard (static): None, or (mesh, for each leading dim of the rows the
        mesh axes that shard it, or None) — see ``_on_own_rows``
    -> scalar f32
    """
    (loss,), _ = _on_own_rows(
        functools.partial(_local_loss, rows_per_chunk), shard,
        x, w, targets, row_weights)
    return loss


def _ce_vjp_fwd(x, w, targets, row_weights, rows_per_chunk, shard):
    (loss, dw), (dx, nll) = _on_own_rows(
        functools.partial(_local_loss_and_grads, rows_per_chunk), shard,
        x, w, targets, row_weights)
    return loss, (dx, dw.astype(w.dtype), nll.astype(row_weights.dtype))


def _ce_vjp_bwd(rows_per_chunk, shard, res, g):
    def scaled(a):
        return (g * a.astype(jnp.float32)).astype(a.dtype)

    dx, dw, nll = res
    # targets take no cotangent; the row weights' is each row's loss
    return scaled(dx), scaled(dw), None, scaled(nll)


blockwise_cross_entropy.defvjp(_ce_vjp_fwd, _ce_vjp_bwd)


def dense_cross_entropy(x, w, targets):
    """Reference path: materialize logits, log_softmax, gather."""
    logits = jnp.dot(x, w, preferred_element_type=jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[:, None], axis=1)[:, 0]


__all__ = ["blockwise_cross_entropy", "chunk_rows", "dense_cross_entropy"]
