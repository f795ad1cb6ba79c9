"""The gated delta rule: the recurrence of a linear-attention layer.

Per head a state ``S`` [d_k, d_v] (float32, zero at a sequence's start)
is decayed, corrected towards the new value along the new key, and read
with the query:

    S <- alpha_t S;  u = beta_t (v_t - S^T k_t);  S <- S + k_t u^T;  o_t = S^T q_t

``gated_delta_step`` is that statement for one token a row (the decode
step; ``gated_delta_decode`` is its kernel, below). ``gated_delta_chunk``
is the same result for a block of positions in the chunkwise form (the
prompt chunks): within a chunk of ``c`` positions, with ``G_t`` the
running sum of ``log alpha``,

    (I + tril(diag(beta) D * K K^T, -1)) U = diag(beta) (V - diag(e^G) K S0)
    O = diag(e^G) Q S0 + tril(D * Q K^T) U,   D[t, i] = e^(G_t - G_i)
    S1 = e^(G_c) S0 + (diag(e^(G_c - G)) K)^T U

so the sequential part is one triangular solve a chunk (independent of
``S0``, all chunks at once) and one pass over the chunks that carries the
state. Every decay is formed from a difference of running sums with
``i <= t``, so it never exceeds 1.

Those two are plain ``jax.numpy``: float32 throughout (q, k, v are
upcast), the small products at precision "highest" (on a TPU a float32
product otherwise rounds its operands to bfloat16, the state among them,
which is the fault the benchmark's bfloat16-state control plants); the
step multiplies and sums on the vector unit for the same reason. Positions
that are not ``valid`` leave the state as it was: alpha 1, beta 0.

``gated_delta_decode`` is ``gated_delta_step`` as one Pallas call, for the
decode step of a cache that holds every linear layer's state in one stack
[n_linear, B, H, d_k, d_v]. As ``jax.numpy`` the step makes three passes
over the layer's slice (decay and S^T k; the update under the mask; S^T
q) for all B rows, live or frozen, and a fourth to set the slice back
into the stack: at the hybrid serving cell's 16 x 30 x 96 x 192 that was
a fifth of the decode step with a quarter of the rows live (PERF.md, PR
35). The kernel's grid is the list of live rows (``live_state_rows``; its
length is a runtime value, as ``flash_decode``'s): a step brings a few
heads' [d_k, d_v] tiles of one live row into VMEM, runs the statement on
them there, in ``gated_delta_step``'s own float32 expressions on the
vector unit (no ``dot``: the MXU would round the state to bfloat16), and
writes them back to where they came from. The stack is the operand,
aliased to the output, with the layer indexed in the BlockSpec, so no
slice of it is made and nothing of it is copied; a frozen row has no grid
step and its tiles are not touched. Who runs it: the decode step (one new
token a row) on the TPU outside a mesh (``generate.state_kernel_engages``);
the CPU, a mesh and every block of positions keep the two above, and
``gated_delta_step`` is the statement the kernel is tested against.

``log_alpha`` and not ``alpha`` is the argument: the layer computes the
logarithm (``-exp(A_log) * softplus(.)``) and a round trip through
``exp`` would only lose bits.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
_HI = lax.Precision.HIGHEST


def gated_delta_step(q, k, v, log_alpha, beta, state, active=None):
    """One token a row. q, k [B, H, d_k]; v [B, H, d_v]; log_alpha, beta
    [B, H]; state [B, H, d_k, d_v] float32; ``active`` [B] bool or None
    (all): a row that is not active keeps its state.
    -> (o [B, H, d_v] float32, new state)."""
    q, k, v = (t.astype(F32) for t in (q, k, v))
    decayed = state * jnp.exp(log_alpha.astype(F32))[..., None, None]
    u = beta.astype(F32)[..., None] * (
        v - jnp.sum(decayed * k[..., :, None], axis=-2))
    new = decayed + k[..., :, None] * u[..., None, :]
    o = jnp.sum(new * q[..., :, None], axis=-2)
    if active is not None:
        new = jnp.where(active[:, None, None, None], new, state)
    return o, new


# Heads of one row a grid step of ``gated_delta_decode`` holds in VMEM: 10
# tiles of [96, 192] float32 are 1 MB a step. Chosen on the v5e (PERF.md
# §6, PR 35) at the hybrid serving cell's shapes: a call with 4 live rows
# took 80 / 63 / 56 / 51 / 49 / 49 / 48 us at 1 / 2 / 3 / 5 / 10 / 15 / 30
# heads a step, with 16 live 279 / 211 / 181 / 163 / 162 / 159 / 160 (the
# heads' loop unrolled; as the loop it is now 54 / 51 / 51 and 172 / 161 /
# 163 at 5 / 10 / 30); all 30 would hold 12 of the kernel's 16 MB of VMEM.
HEAD_BLOCK = 10


def live_state_rows(active, xp=np):
    """The rows whose state a decode step streams -> (rows [B] int32, the
    live rows first and in order, their count). ``xp`` is numpy on the
    host (the serving engine's ``state_rows_read`` count) and jax.numpy
    inside ``gated_delta_decode``, whose grid walks exactly these rows."""
    order = xp.argsort(~active, stable=True)
    return order.astype(xp.int32), active.sum().astype(xp.int32)


def _decode_kernel(rows_ref, n_ref, layer_ref, alpha_ref, beta_ref, q_ref,
                   k_ref, v_ref, s_ref, zero_ref, o_ref, s_out_ref, *, heads,
                   head_block):
    """One grid step = ``head_block`` heads of one live row: each head's
    [d_k, d_v] tile of the state is in VMEM for the whole of
    ``gated_delta_step``'s statement, in its expressions and order."""
    del layer_ref, zero_ref         # the index maps'; o's initial value
    groups = heads // head_block
    row = rows_ref[pl.program_id(0) // groups]
    head0 = row * heads + (pl.program_id(0) % groups) * head_block

    def head(h, _):
        k_col = k_ref[h][:, None]                       # [d_k, 1]
        q_col = q_ref[h][:, None]
        decayed = s_ref[h] * alpha_ref[head0 + h]       # [d_k, d_v]
        u = beta_ref[head0 + h] * (
            v_ref[pl.ds(h, 1), :]
            - jnp.sum(decayed * k_col, axis=0, keepdims=True))
        new = decayed + k_col * u
        o_ref[pl.ds(h, 1), :] = jnp.sum(new * q_col, axis=0, keepdims=True)
        s_out_ref[h] = new

    # a loop and not ``head_block`` copies of the body: a program that
    # holds the kernel traces it again in every process, whatever the
    # compile cache holds, and ten copies a layer cost the serving cell
    # 4.7 s of set-up (PERF.md §6, PR 35)
    @pl.when(n_ref[0] > 0)
    def _step():
        lax.fori_loop(0, head_block, head, None)

    @pl.when(n_ref[0] == 0)         # the one step of an empty work list
    def _nothing():
        o_ref[...] = jnp.zeros_like(o_ref)
        s_out_ref[...] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("head_block", "interpret"))
def gated_delta_decode(q, k, v, log_alpha, beta, state, active=None, *,
                       layer, head_block: int | None = None,
                       interpret: bool = False):
    """``gated_delta_step`` as one Pallas call on the WHOLE stacked state
    [n_linear, B, H, d_k, d_v] float32, updated in place at ``layer``: q,
    k [B, H, d_k]; v [B, H, d_v]; log_alpha, beta [B, H]; ``active`` [B]
    bool or None (all). -> (o [B, H, d_v] float32, the state stack).

    The grid is the work list: one step per live row and group of
    ``head_block`` heads (``live_state_rows``; its length is a runtime
    value), handed to the index maps as scalar-prefetch operands. A step
    brings its heads' [d_k, d_v] tiles into VMEM once, runs the statement
    on them there in float32 on the vector unit, and writes them back
    once to where they came from (the stack is aliased to the output, and
    the layer is indexed in the BlockSpec: a slice handed to a Pallas
    operand would be a copy). ``layer`` rides with the work list as one
    more scalar, so a model's linear layers share one traced kernel. A
    row that is not active has no step: its tiles, and every other
    layer's, are not touched, and its ``o`` is zeros."""
    _, b, h, dk, dv = state.shape
    hb = min(head_block or HEAD_BLOCK, h)
    while h % hb:
        hb -= 1
    groups = h // hb
    live = jnp.ones((b,), bool) if active is None else active
    rows, count = live_state_rows(live, xp=jnp)

    def by_heads(x):                # [B, H, d] -> [B, groups, hb, d]
        return x.astype(F32).reshape(b, groups, hb, x.shape[-1])

    def vec_index(s, rows_ref, *_):
        return rows_ref[s // groups], s % groups, 0, 0

    def state_index(s, rows_ref, n_ref, layer_ref, *_):
        return layer_ref[0], rows_ref[s // groups], s % groups, 0, 0

    def vec_spec(d):
        return pl.BlockSpec((None, None, hb, d), vec_index)

    state_spec = pl.BlockSpec((None, None, hb, dk, dv), state_index)
    n_prefetch = 5
    o, state = pl.pallas_call(
        functools.partial(_decode_kernel, heads=h, head_block=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_prefetch,
            grid=(jnp.maximum(count, 1) * groups,),
            in_specs=[vec_spec(dk), vec_spec(dk), vec_spec(dv), state_spec,
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[vec_spec(dv), state_spec],
        ),
        out_shape=[jax.ShapeDtypeStruct((b, groups, hb, dv), F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # rows with no step keep the zeros o starts as, and their tiles
        input_output_aliases={n_prefetch + 3: 1, n_prefetch + 4: 0},
        interpret=interpret,
    )(rows, count[None], jnp.asarray(layer, jnp.int32)[None], jnp.exp(log_alpha.astype(F32)).reshape(-1),
      beta.astype(F32).reshape(-1), by_heads(q), by_heads(k), by_heads(v),
      state, jnp.zeros((b, groups, hb, dv), F32))
    return o.reshape(b, h, dv), state


def gated_delta_chunk(q, k, v, log_alpha, beta, state, valid=None, *,
                      chunk: int = 64):
    """A block of L positions a row, ``chunk`` at a time. q, k [B, L, H,
    d_k]; v [B, L, H, d_v]; log_alpha, beta [B, L, H]; state [B, H, d_k,
    d_v] float32; ``valid`` [B, L] bool or None (all).
    -> (o [B, L, H, d_v] float32, new state). ``o`` at a position that is
    not valid is unspecified."""
    b, l, h, dk = q.shape
    dv = v.shape[-1]
    c = min(chunk, l)
    n = -(-l // c)
    g, bt = log_alpha.astype(F32), beta.astype(F32)
    if valid is not None:
        g = jnp.where(valid[..., None], g, 0.0)
        bt = jnp.where(valid[..., None], bt, 0.0)

    def chunks(x):      # [B, L, H, ...] -> [B, H, n, c, ...], pad: zeros
        x = jnp.pad(x.astype(F32),
                    ((0, 0), (0, n * c - l)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((b, n, c) + x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    q, k, v, g, bt = map(chunks, (q, k, v, g, bt))
    run = jnp.cumsum(g, axis=-1)                        # G  [B, H, n, c]
    t_idx = jnp.arange(c)
    lower = t_idx[:, None] >= t_idx[None, :]            # i <= t
    diff = run[..., :, None] - run[..., None, :]
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
    kk = jnp.einsum("bhnte,bhnie->bhnti", k, k, precision=_HI)
    a = jnp.where(t_idx[:, None] > t_idx[None, :],
                  bt[..., :, None] * decay * kk, 0.0)
    rhs = jnp.concatenate(
        [bt[..., None] * v, (bt * jnp.exp(run))[..., None] * k], axis=-1)
    sol = jax.scipy.linalg.solve_triangular(
        a + jnp.eye(c, dtype=F32), rhs, lower=True, unit_diagonal=True)
    u0, w = sol[..., :dv], sol[..., dv:]                # [.., c, dv], [.., c, dk]
    qk = decay * jnp.einsum("bhnte,bhnie->bhnti", q, k, precision=_HI)
    q_in = q * jnp.exp(run)[..., None]
    k_out = k * jnp.exp(run[..., -1:] - run)[..., None]
    whole = jnp.exp(run[..., -1])                       # e^(G_c)  [B, H, n]

    def one(s, xs):
        u0_c, w_c, qk_c, q_c, k_c, whole_c = xs
        u = u0_c - jnp.einsum("bhte,bhev->bhtv", w_c, s, precision=_HI)
        o = (jnp.einsum("bhte,bhev->bhtv", q_c, s, precision=_HI)
             + jnp.einsum("bhti,bhiv->bhtv", qk_c, u, precision=_HI))
        s = (whole_c[..., None, None] * s
             + jnp.einsum("bhte,bhtv->bhev", k_c, u, precision=_HI))
        return s, o

    xs = tuple(jnp.moveaxis(x, 2, 0) for x in (u0, w, qk, q_in, k_out, whole))
    state, o = lax.scan(one, state.astype(F32), xs)     # o [n, B, H, c, dv]
    o = jnp.moveaxis(o, 0, 2).reshape(b, h, n * c, dv)[:, :, :l]
    return jnp.moveaxis(o, 1, 2), state


__all__ = ["gated_delta_step", "gated_delta_chunk", "gated_delta_decode",
           "live_state_rows"]
