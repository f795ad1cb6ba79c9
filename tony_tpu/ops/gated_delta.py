"""The gated delta rule: the recurrence of a linear-attention layer.

Per head a state ``S`` [d_k, d_v] (float32, zero at a sequence's start)
is decayed, corrected towards the new value along the new key, and read
with the query:

    S <- alpha_t S;  u = beta_t (v_t - S^T k_t);  S <- S + k_t u^T;  o_t = S^T q_t

``gated_delta_step`` is that statement for one token a row (the decode
step). ``gated_delta_chunk`` is the same result for a block of positions
in the chunkwise form (the prompt chunks): within a chunk of ``c``
positions, with ``G_t`` the running sum of ``log alpha``,

    (I + tril(diag(beta) D * K K^T, -1)) U = diag(beta) (V - diag(e^G) K S0)
    O = diag(e^G) Q S0 + tril(D * Q K^T) U,   D[t, i] = e^(G_t - G_i)
    S1 = e^(G_c) S0 + (diag(e^(G_c - G)) K)^T U

so the sequential part is one triangular solve a chunk (independent of
``S0``, all chunks at once) and one pass over the chunks that carries the
state. Every decay is formed from a difference of running sums with
``i <= t``, so it never exceeds 1.

Plain ``jax.numpy``: float32 throughout (q, k, v are upcast), the small
products at precision "highest" (on a TPU a float32 product otherwise
rounds its operands to bfloat16, the state among them, which is the
fault the benchmark's bfloat16-state control plants); the step multiplies
and sums on the vector unit for the same reason. Positions that are not
``valid`` leave the state as it was: alpha 1, beta 0.

``log_alpha`` and not ``alpha`` is the argument: the layer computes the
logarithm (``-exp(A_log) * softplus(.)``) and a round trip through
``exp`` would only lose bits.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

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


__all__ = ["gated_delta_step", "gated_delta_chunk"]
