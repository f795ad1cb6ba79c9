"""Pallas op tests (interpret mode on CPU): flash attention vs reference.

Every flash test runs THREE times via the autouse `attn_path` fixture:
on the VMEM-resident kernels (the default at CI-sized L), with
RESIDENT_MAX_L forced to 0 (the fused-streaming mid tier, 2048 < L <=
8192 in production), and with FUSED_STREAM_MAX_L also 0 (the split
dq/dkv O(block)-memory kernels that serve the longest sequences)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.ops import flash_attention, attention_blhd
from tony_tpu.parallel import reference_attention


@pytest.fixture(params=["resident", "stream_fused", "stream_split"],
                autouse=True)
def attn_path(request, monkeypatch):
    if request.param == "resident":
        yield request.param
        return
    import tony_tpu.ops.attention as A

    monkeypatch.setattr(A, "RESIDENT_MAX_L", 0)
    if request.param == "stream_split":
        monkeypatch.setattr(A, "FUSED_STREAM_MAX_L", 0)
    # _flash_fwd/_flash_bwd are jitted and the dispatch reads the module
    # globals at TRACE time — stale cache entries would silently run the
    # other path, so retrace everything on entry and exit
    jax.clear_caches()
    yield request.param
    jax.clear_caches()


def _ref_bhld(q, k, v, causal):
    # reference is [B, L, H, D]; ours is [B, H, L, D]
    o = reference_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=causal,
    )
    return o.transpose(0, 2, 1, 3)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("l", [128, 256])
def test_flash_matches_reference(causal, l):
    key = jax.random.PRNGKey(0)
    b, h, d = 2, 2, 32
    q, k, v = (
        jax.random.normal(kk, (b, h, l, d), jnp.float32)
        for kk in jax.random.split(key, 3)
    )
    out = flash_attention(q, k, v, causal=causal)
    expected = _ref_bhld(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=2e-5)


def test_flash_ragged_length_causal():
    """L not divisible by the block size exercises padding."""
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 200, 16))
    out = flash_attention(q, q, q, causal=True)
    expected = _ref_bhld(q, q, q, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_ragged_padded_blocks(causal):
    """L=300 pads to a 384-row block: padded KV columns must be masked
    in-kernel and padded Q rows zeroed via the lse residual (regression: the
    old lse=-inf padding made p=exp(s+1e30)=inf -> NaN dK/dV). Multi-block
    grids are covered by test_flash_multi_qblock_paths_small_blocks."""
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    q, k, v = (jax.random.normal(kk, (1, 2, 300, 16)) for kk in keys)
    out = flash_attention(q, k, v, causal=causal)
    expected = _ref_bhld(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=2e-5)

    def loss_flash(args):
        return jnp.sum(flash_attention(*args, causal=causal) ** 2)

    def loss_ref(args):
        return jnp.sum(_ref_bhld(*args, causal) ** 2)

    g1 = jax.grad(loss_flash)((q, k, v))
    g2 = jax.grad(loss_ref)((q, k, v))
    for a, b in zip(g1, g2):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_flash_cross_attention_ragged_kv():
    """L_q != L_k with ragged L_k (non-causal cross attention)."""
    q = jax.random.normal(jax.random.PRNGKey(8), (1, 2, 300, 16))
    k = jax.random.normal(jax.random.PRNGKey(9), (1, 2, 520, 16))
    out = flash_attention(q, k, k, causal=False)
    expected = _ref_bhld(q, k, k, False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=2e-5)


def test_flash_gradients_match_reference():
    q = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 128, 16))

    def loss_flash(q):
        return jnp.sum(flash_attention(q, q, q, causal=True) ** 2)

    def loss_ref(q):
        return jnp.sum(_ref_bhld(q, q, q, True) ** 2)

    g1 = jax.grad(loss_flash)(q)
    g2 = jax.grad(loss_ref)(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-4)


def test_attention_blhd_layout():
    q = jax.random.normal(jax.random.PRNGKey(2), (2, 64, 4, 16))  # [B,L,H,D]
    out = attention_blhd(q, q, q, causal=True)
    expected = reference_attention(q, q, q, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=2e-5)


def test_flash_bfloat16():
    q = jax.random.normal(jax.random.PRNGKey(3), (1, 2, 128, 32), jnp.bfloat16)
    out = flash_attention(q, q, q, causal=True)
    assert out.dtype == jnp.bfloat16
    expected = _ref_bhld(
        q.astype(jnp.float32), q.astype(jnp.float32), q.astype(jnp.float32), True
    )
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32), np.asarray(expected), atol=3e-2
    )


# -------------------------------------------------------- blockwise CE

def _ce_inputs(n, d, v, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(ks[0], (n, d), dtype)
    w = jax.random.normal(ks[1], (d, v), dtype)
    t = jax.random.randint(ks[2], (n,), 0, v)
    return x, w, t


def _subjaxprs(jaxpr):
    """The jaxpr and every jaxpr nested in its equations' parameters."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else (param,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _subjaxprs(sub)


def _dots(closed):
    return [e for j in _subjaxprs(closed.jaxpr) for e in j.eqns
            if e.primitive.name == "dot_general"]


@pytest.mark.parametrize("v,rows", [(64, 8), (50, 16), (40, None)])
def test_blockwise_ce_matches_dense(v, rows):
    """Chunk-by-chunk logsumexp + target gather == dense log_softmax, over
    several chunks of rows and over one: the weighted sum under each row's
    one-hot weights (the row's own loss) and under a linspace weight
    vector."""
    from tony_tpu.ops import blockwise_cross_entropy, dense_cross_entropy

    n, d = 32, 16
    x, w, t = _ce_inputs(n, d, v)
    expected = dense_cross_entropy(x, w, t)
    per_row = jax.vmap(
        lambda rw: blockwise_cross_entropy(x, w, t, rw, rows))(jnp.eye(n))
    np.testing.assert_allclose(
        np.asarray(per_row), np.asarray(expected), atol=1e-5)
    weights = jnp.linspace(0.1, 1.0, n)
    np.testing.assert_allclose(
        float(blockwise_cross_entropy(x, w, t, weights, rows)),
        float(jnp.sum(expected * weights)), rtol=1e-6)


def test_blockwise_ce_gradients_match_dense():
    """Custom VJP (dx and dW formed chunk by chunk in the forward rule, never
    [N,V]) == XLA autodiff of the dense path, for non-uniform row weights
    and a cotangent that is not 1; the row weights' own gradient is each
    row's loss."""
    from tony_tpu.ops import blockwise_cross_entropy, dense_cross_entropy

    n, d, v, rows = 24, 8, 50, 8
    x, w, t = _ce_inputs(n, d, v, seed=3)
    weights = jnp.linspace(0.1, 1.0, n)

    def loss_blk(x, w, rw):
        return 0.7 * blockwise_cross_entropy(x, w, t, rw, rows)

    def loss_dense(x, w, rw):
        return 0.7 * jnp.sum(dense_cross_entropy(x, w, t) * rw)

    got = jax.grad(loss_blk, argnums=(0, 1, 2))(x, w, weights)
    want = jax.grad(loss_dense, argnums=(0, 1, 2))(x, w, weights)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_blockwise_ce_bfloat16_inputs():
    from tony_tpu.ops import blockwise_cross_entropy, dense_cross_entropy

    n, d, v = 16, 8, 64
    x, w, t = _ce_inputs(n, d, v, jnp.bfloat16, seed=6)
    weights = jnp.linspace(0.1, 1.0, n)
    loss, (dx, dw) = jax.value_and_grad(
        lambda x, w: blockwise_cross_entropy(x, w, t, weights, 8), (0, 1))(x, w)
    assert loss.dtype == jnp.float32
    assert dx.dtype == jnp.bfloat16 and dw.dtype == jnp.bfloat16
    expected = dense_cross_entropy(x.astype(jnp.float32), w.astype(jnp.float32), t)
    np.testing.assert_allclose(
        float(loss), float(jnp.sum(expected * weights)), rtol=5e-3)


def test_blockwise_ce_three_products_none_in_the_backward_rule():
    """value_and_grad of the op holds three products with a V-sized side,
    each on a chunk of rows inside the one loop; the pullback (the backward
    rule alone) holds none; the loss alone holds one."""
    from tony_tpu.ops import blockwise_cross_entropy

    n, d, v, rows = 64, 8, 64, 16
    x, w, t = _ce_inputs(n, d, v)
    rw = jnp.full((n,), 1.0 / n)

    def op(x, w):
        return blockwise_cross_entropy(x, w, t, rw, rows)

    dots = _dots(jax.make_jaxpr(jax.value_and_grad(op, (0, 1)))(x, w))
    shapes = sorted(tuple(v.aval.shape for v in e.invars) for e in dots)
    assert shapes == sorted([
        ((rows, d), (d, v)),        # a chunk's logits
        ((rows, v), (v, d)),        # dx[chunk] = ds @ w^T
        ((d, rows), (rows, v)),     # dW += x[chunk]^T @ ds
    ])
    _, pullback = jax.vjp(op, x, w)
    assert _dots(jax.make_jaxpr(pullback)(jnp.float32(1.0))) == []
    assert len(_dots(jax.make_jaxpr(op)(x, w))) == 1


@pytest.mark.parametrize("grad", [True, False])
def test_blockwise_ce_keeps_nothing_larger_than_a_chunk_of_logits(grad):
    """With N of several chunks no value of the jaxpr (value_and_grad's, or
    the loss's alone) is larger than [chunk, V] (here D*V and N*D are
    smaller still), and the chunk's logits are there."""
    from tony_tpu.ops import blockwise_cross_entropy

    n, d, v, rows = 64, 8, 64, 16
    x, w, t = _ce_inputs(n, d, v)
    rw = jnp.full((n,), 1.0 / n)

    def op(x, w):
        return blockwise_cross_entropy(x, w, t, rw, rows)

    closed = jax.make_jaxpr(jax.value_and_grad(op, (0, 1)) if grad else op)(x, w)
    sizes = [v.aval.size for j in _subjaxprs(closed.jaxpr) for e in j.eqns
             for v in e.outvars]
    assert max(sizes) == rows * v < n * v


@pytest.mark.parametrize("n,rows", [(30, 8), (30, None), (32, 8)])
def test_blockwise_ce_ragged_rows_vocab_and_zero_weights(n, rows):
    """N not a multiple of the chunk (the last chunk filled with rows of
    weight 0), a vocabulary of 50 columns, and padding rows: loss and
    gradients match dense autodiff to 1e-5, and a row of weight 0 gets an
    exactly zero row of dx."""
    from tony_tpu.ops import blockwise_cross_entropy, dense_cross_entropy

    d, v = 8, 50
    x, w, t = _ce_inputs(n, d, v, seed=9)
    rw = jnp.linspace(0.1, 1.0, n).at[jnp.array([0, 7, n - 1])].set(0.0)
    got_l, got = jax.value_and_grad(
        lambda x, w: blockwise_cross_entropy(x, w, t, rw, rows), (0, 1))(x, w)
    want_l, want = jax.value_and_grad(
        lambda x, w: jnp.sum(dense_cross_entropy(x, w, t) * rw), (0, 1))(x, w)
    np.testing.assert_allclose(float(got_l), float(want_l), rtol=1e-6)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    assert not np.asarray(got[0])[[0, 7, n - 1]].any()


@pytest.mark.parametrize("n,v,want", [
    (8192, 32768, 4096),        # the benchmark's train cell: two chunks
    (4096, 32768, 4096),        # a device's rows of the fsdp=4 cell: one
    (32768, 262144, 512),       # the docstring's 32 GB of logits: 64 chunks
    (8192, 128256, 1024),       # Llama-3 vocabulary
    (8193, 32768, 2816),        # ragged: three even chunks, not 4096+4096+1
    (5000, 32000, 2560),
    (24, 50, 24),               # everything in one chunk: the rows themselves
])
def test_blockwise_ce_chunk_rows_follow_the_shapes(n, v, want):
    from tony_tpu.ops.cross_entropy import LOGITS_BUFFER_BYTES, chunk_rows

    rows = chunk_rows(n, v)
    assert rows == want
    chunks = -(-n // rows)
    if chunks > 1:
        assert rows * v * 4 <= LOGITS_BUFFER_BYTES
        # one chunk fewer would not fit
        assert -(-n // (chunks - 1)) * v * 4 > LOGITS_BUFFER_BYTES


def test_flash_multi_qblock_paths_small_blocks():
    """Force nq>1 and nk>1 with explicit 128-row blocks (the default
    BLOCK_Q=512 makes every CI-sized sequence a single block, which would
    leave the qi>0 causal pruning untested). Under attn_path='streaming'
    this also exercises the _dkv diagonal-down lo start and the
    double-buffer slot rotation; under 'resident' the static tile
    classification."""
    from tony_tpu.ops.attention import _flash_bwd, _flash_fwd

    keys = jax.random.split(jax.random.PRNGKey(11), 4)
    q, k, v, g = (jax.random.normal(kk, (1, 2, 300, 16)) for kk in keys)
    out, lse = _flash_fwd(q, k, v, True, None, block_q=128, block_k=128,
                          interpret=True)
    expected = _ref_bhld(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=2e-5)

    dq, dk, dv = _flash_bwd(q, k, v, out, lse, g, True, None,
                            block_q=128, block_k=128, interpret=True)
    eq, ek, ev = jax.grad(
        lambda q, k, v: jnp.sum(_ref_bhld(q, k, v, True) * g),
        argnums=(0, 1, 2),
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(eq), atol=1e-4)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(ek), atol=1e-4)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(ev), atol=1e-4)


# ------------------------------------------------------- sliding window

@pytest.mark.parametrize("window", [1, 7, 64, 500])
def test_flash_sliding_window_matches_reference(window):
    from tony_tpu.ops.attention import _flash_fwd

    keys = jax.random.split(jax.random.PRNGKey(13), 3)
    q, k, v = (jax.random.normal(kk, (1, 2, 300, 16)) for kk in keys)
    # small blocks force multi-block band pruning (lo > 0 for late q blocks)
    out, _ = _flash_fwd(q, k, v, True, None, block_q=128, block_k=128,
                        interpret=True, window=window)
    expected = reference_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=True, window=window,
    ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=2e-5)


def test_flash_sliding_window_gradients():
    from tony_tpu.ops.attention import _flash_bwd, _flash_fwd

    keys = jax.random.split(jax.random.PRNGKey(17), 4)
    q, k, v, g = (jax.random.normal(kk, (1, 1, 300, 16)) for kk in keys)
    w = 40
    out, lse = _flash_fwd(q, k, v, True, None, block_q=128, block_k=128,
                          interpret=True, window=w)
    dq, dk, dv = _flash_bwd(q, k, v, out, lse, g, True, None,
                            block_q=128, block_k=128, interpret=True, window=w)

    def ref(q, k, v):
        o = reference_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=True, window=w,
        ).transpose(0, 2, 1, 3)
        return jnp.sum(o * g)

    eq, ek, ev = jax.grad(ref, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(eq), atol=1e-4)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(ek), atol=1e-4)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(ev), atol=1e-4)


def test_flash_window_public_api_and_validation():
    q = jax.random.normal(jax.random.PRNGKey(19), (1, 2, 128, 16))
    out = flash_attention(q, q, q, causal=True, window=16)
    expected = reference_attention(
        q.transpose(0, 2, 1, 3), q.transpose(0, 2, 1, 3),
        q.transpose(0, 2, 1, 3), causal=True, window=16,
    ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=2e-5)
    g = jax.grad(lambda x: jnp.sum(flash_attention(x, x, x, True, None, 16) ** 2))(q)
    assert np.isfinite(np.asarray(g)).all()
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, q, q, causal=False, window=4)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, q, q, causal=True, window=0)


def test_chunked_reference_attention_matches_reference():
    """The bench's long-context XLA baseline (chunked+remat, the strongest
    thing plain XLA can compile at 16k) must match the materializing
    reference exactly where both compile — otherwise the recorded flash
    speedup is against a broken baseline."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tony_tpu.ops.attention import (
        chunked_reference_attention, reference_attention,
    )

    B, H, L, D = 2, 4, 512, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (B, H, L, D), jnp.float32) for kk in ks)
    o1 = chunked_reference_attention(q, k, v, causal=True, q_block=128)
    o2 = reference_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=True,
    ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-3, atol=2e-3)
    g1 = jax.grad(
        lambda a, b, c_: chunked_reference_attention(a, b, c_).sum(),
        argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(
        lambda a, b, c_: reference_attention(
            a.transpose(0, 2, 1, 3), b.transpose(0, 2, 1, 3),
            c_.transpose(0, 2, 1, 3), causal=True).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-3)


# ------------------------------------------------------- flash decode kernel

def _decode_ref(q, ck, cv, length, window=0):
    """Dense reference: [B, kvH, rep, D] query vs [B, kvH, M, D] cache."""
    M = ck.shape[2]
    s = jnp.einsum("bhrd,bhmd->bhrm", q.astype(jnp.float32),
                   ck.astype(jnp.float32)) * ck.shape[-1] ** -0.5
    mask = jnp.arange(M) <= length
    if window:
        mask &= jnp.arange(M) > length - window
    s = jnp.where(mask[None, None, None], s, -1e30)
    return jnp.einsum("bhrm,bhmd->bhrd", jax.nn.softmax(s, -1),
                      cv.astype(jnp.float32))


def test_flash_decode_matches_reference():
    """Split-KV decode kernel vs dense reference: GQA grouping, ragged
    final block (M not a multiple of block_k), length masking."""
    from tony_tpu.ops.decode_attention import flash_decode

    B, kvH, rep, D, M = 2, 4, 2, 128, 700
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, kvH, rep, D), jnp.float32)
    ck = jax.random.normal(ks[1], (B, kvH, M, D), jnp.float32)
    cv = jax.random.normal(ks[2], (B, kvH, M, D), jnp.float32)
    for length in (0, 437, M - 1):
        out = flash_decode(q, ck, cv, jnp.int32(length), block_k=256,
                           interpret=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(_decode_ref(q, ck, cv, length)),
            rtol=2e-5, atol=2e-5)


def test_flash_decode_window_and_int8():
    """Sliding-window band + int8 cache with folded dequant scales: the
    softmax denominator must sum RAW probabilities (V scales apply only
    to the value accumulation)."""
    from tony_tpu.ops.decode_attention import flash_decode

    B, kvH, rep, D, M = 1, 2, 4, 128, 384
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (B, kvH, rep, D), jnp.float32)
    ck = jax.random.normal(ks[1], (B, kvH, M, D), jnp.float32)
    cv = jax.random.normal(ks[2], (B, kvH, M, D), jnp.float32)
    out = flash_decode(q, ck, cv, jnp.int32(300), window=64, block_k=128,
                       interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_decode_ref(q, ck, cv, 300, window=64)),
        rtol=2e-5, atol=2e-5)

    def quant(x):
        amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
        sc = jnp.maximum(amax / 127.0, 1e-8)
        qv = jnp.clip(jnp.round(x / sc), -127, 127).astype(jnp.int8)
        return qv, sc[..., 0].astype(jnp.bfloat16)

    ck8, cks = quant(ck)
    cv8, cvs = quant(cv)
    out8 = flash_decode(q, ck8, cv8, jnp.int32(300), cks, cvs,
                        block_k=128, interpret=True)
    ref8 = _decode_ref(
        q,
        ck8.astype(jnp.float32) * cks[..., None].astype(jnp.float32),
        cv8.astype(jnp.float32) * cvs[..., None].astype(jnp.float32), 300)
    np.testing.assert_allclose(np.asarray(out8), np.asarray(ref8),
                               rtol=2e-3, atol=2e-3)


def test_flash_decode_layer_indexed_stack():
    """`layer=` reads one layer of the full [Ly, B, kvH, M, D] stack via
    the BlockSpecs (the caller never slices — a sliced pallas operand is
    a real copy)."""
    from tony_tpu.ops.decode_attention import flash_decode

    Ly, B, kvH, rep, D, M = 3, 1, 2, 1, 128, 256
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, kvH, rep, D), jnp.float32)
    ck = jax.random.normal(ks[1], (Ly, B, kvH, M, D), jnp.float32)
    cv = jax.random.normal(ks[2], (Ly, B, kvH, M, D), jnp.float32)
    for i in range(Ly):
        out = flash_decode(q, ck, cv, jnp.int32(100), layer=i,
                           block_k=128, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(_decode_ref(q, ck[i], cv[i], 100)),
            rtol=2e-5, atol=2e-5)


# (lengths, offsets, active, window, int8, layered) for a 4-row ring of
# M = 256 read in blocks of 64; each case against the einsum on the same
# inputs
_RING_CASES = {
    "random_offsets_and_lengths":
        ([17, 100, 201, 63], [5, 200, 130, 250], None, 0, False, False),
    "range_wraps_across_m":
        ([90, 130, 40, 255], [250, 192, 255, 1], None, 0, False, False),
    "length_zero":
        ([0, 0, 0, 5], [0, 63, 255, 64], None, 0, False, False),
    "inactive_row":
        ([37, 100, 0, 200], [9, 250, 3, 77], [True, False, False, True],
         0, False, False),
    "row_fills_the_ring":
        ([255, 255, 254, 255], [0, 100, 64, 255], None, 0, False, False),
    "window_band_binds":
        ([200, 255, 30, 129], [100, 7, 250, 192], None, 48, False, False),
    "int8_with_scales":
        ([90, 255, 0, 180], [250, 31, 64, 200], [True, True, True, False],
         0, True, False),
    "layer_of_the_stack":
        ([17, 130, 201, 63], [5, 192, 130, 250], [True, True, False, True],
         96, False, True),
}


@pytest.mark.parametrize("case", _RING_CASES)
def test_flash_decode_ring_matches_cached_attention_einsum(case):
    """The kernel's (length, offset, active) contract against the einsum
    path of _cached_attention — what the serving decode block ran before
    the kernel took rings, and still runs on the CPU and on a mesh."""
    from tony_tpu.models.generate import _cached_attention, _quantize_kv
    from tony_tpu.models.transformer import TransformerConfig
    from tony_tpu.ops.decode_attention import flash_decode

    lengths, offsets, active, window, int8, layered = _RING_CASES[case]
    Ly, B, kvH, rep, D, M = 3, 4, 2, 2, 128, 256
    cfg = TransformerConfig(
        vocab_size=64, d_model=kvH * rep * D, n_layers=Ly,
        n_heads=kvH * rep, n_kv_heads=kvH, d_ff=64, max_seq_len=M,
        dtype=jnp.float32, attn_window=window or None)
    ks = jax.random.split(jax.random.PRNGKey(len(case)), 3)
    q = jax.random.normal(ks[0], (B, 1, kvH * rep, D), jnp.float32)
    ck = jax.random.normal(ks[1], (Ly, B, kvH, M, D), jnp.float32)
    cv = jax.random.normal(ks[2], (Ly, B, kvH, M, D), jnp.float32)
    k_scale = v_scale = None
    if int8:
        ck, k_scale = _quantize_kv(ck)
        cv, v_scale = _quantize_kv(cv)
    layer = 1 if layered else None
    if not layered:
        ck, cv = ck[2], cv[2]
        if int8:
            k_scale, v_scale = k_scale[2], v_scale[2]
    lengths = jnp.asarray(lengths, jnp.int32)
    offsets = jnp.asarray(offsets, jnp.int32)
    ref = _cached_attention(cfg, q, ck, cv, lengths, 1, k_scale, v_scale,
                            ring_offsets=offsets, layer_idx=layer)
    out = flash_decode(
        q.reshape(B, kvH, rep, D), ck, cv, lengths, k_scale, v_scale,
        ring_offsets=offsets,
        active=None if active is None else jnp.asarray(active),
        window=window, layer=layer, block_k=64, interpret=True,
    ).reshape(ref.shape)
    live = np.ones(B, bool) if active is None else np.asarray(active)
    tol = 2e-3 if int8 else 2e-5
    np.testing.assert_allclose(np.asarray(out)[live], np.asarray(ref)[live],
                               rtol=tol, atol=tol)
    # a row that is not active reads nothing: zeros, whatever its ring holds
    assert not np.asarray(out)[~live].any()


# (lengths, offsets, active, M) for a 4-row ring of latent rows read in
# blocks of 64; each case against `_latent_attention`'s einsum
_LATENT_CASES = {
    "random_offsets_and_lengths":
        ([17, 100, 201, 63], [5, 200, 130, 250], None, 256),
    "rows_wrap_across_m":
        ([90, 130, 40, 255], [250, 192, 255, 1], None, 256),
    "some_rows_not_active":
        ([37, 100, 0, 200], [9, 250, 3, 77], [True, False, False, True],
         256),
    "no_row_active":
        ([37, 100, 5, 200], [9, 250, 3, 77], [False] * 4, 256),
    "ragged_tail":
        ([199, 150, 30, 120], [0, 100, 190, 185], [True, True, True, False],
         200),
    "one_live_position":
        ([0, 0, 0, 3], [0, 63, 199, 64], None, 200),
}


@pytest.mark.parametrize("case", _LATENT_CASES)
def test_flash_decode_latent_matches_the_absorbed_einsum(case):
    """The absorbed form in the decode kernel: one latent row R wide the
    keys of every head and its first kv_rank features their values, on
    the flash_decode contract (lengths, ring offsets, active rows), read
    out of layer 1 of a 3-layer stack whose other layers are NaN: the
    kernel reads only the layer it is given."""
    from tony_tpu.models.generate import _latent_attention
    from tony_tpu.models.transformer import TransformerConfig, latent_scale
    from tony_tpu.ops.decode_attention import flash_decode_latent

    lengths, offsets, active, M = _LATENT_CASES[case]
    Ly, B, H, kv_rank, rope = 3, 4, 4, 32, 8
    cfg = TransformerConfig(
        vocab_size=64, d_model=64, n_layers=Ly, n_heads=H, n_kv_heads=H,
        d_ff=64, max_seq_len=M, dtype=jnp.float32,
        layer_kinds=("latent",) * Ly, lat_q_rank=16, lat_kv_rank=kv_rank,
        lat_nope_dim=16, lat_rope_dim=rope, lat_v_dim=16)
    ks = jax.random.split(jax.random.PRNGKey(len(case)), 2)
    q = jax.random.normal(ks[0], (B, 1, H, kv_rank + rope), jnp.float32)
    rows = jax.random.normal(ks[1], (B, M, kv_rank + rope), jnp.float32)
    stack = jnp.full((Ly, B, M, kv_rank + rope), jnp.nan).at[1].set(rows)
    lengths = jnp.asarray(lengths, jnp.int32)
    offsets = jnp.asarray(offsets, jnp.int32)
    ref = _latent_attention(cfg, q, rows, lengths, 1, offsets)[:, 0]
    out = flash_decode_latent(
        q[:, 0], stack, lengths, 1, v_dim=kv_rank, scale=latent_scale(cfg),
        ring_offsets=offsets,
        active=None if active is None else jnp.asarray(active),
        block_k=64, interpret=True)
    assert out.shape == (B, H, kv_rank)
    live = np.ones(B, bool) if active is None else np.asarray(active)
    np.testing.assert_allclose(np.asarray(out)[live],
                               np.asarray(ref)[live, :, :kv_rank],
                               rtol=2e-5, atol=2e-5)
    # a row that is not active reads nothing: zeros, whatever its ring holds
    assert not np.asarray(out)[~live].any()


@pytest.mark.parametrize("cursor", ["first", "127", "128", "129", "last"])
@pytest.mark.parametrize("b", [32, 12])
@pytest.mark.parametrize("r, dtype", [(576, jnp.bfloat16), (40, jnp.float32)],
                         ids=["r576_bf16", "r40_f32"])
def test_write_latent_rows_is_the_update_bit_for_bit(cursor, b, r, dtype):
    """``write_latent_rows`` against ``lax.dynamic_update_slice`` of the
    same rows at the same cursor, bit for bit, interpreted: 576 is the
    latent cell's row, 40 the tiny config's; 12 rows are not a whole
    number of the kernel's steps. Layer 1 of a 3-layer stack is written,
    and the other layers hold NaN and keep it, as do the other positions
    of the lane tile the kernel rewrites."""
    from tony_tpu.ops.decode_attention import write_latent_rows

    m = 256
    at = {"first": 0, "last": m - 1}.get(cursor)
    at = int(cursor) if at is None else at
    ks = jax.random.split(jax.random.PRNGKey(at + b + r), 2)
    stack = jnp.full((3, b, m, r), jnp.nan, dtype).at[1].set(
        jax.random.normal(ks[0], (b, m, r)).astype(dtype))
    new = jax.random.normal(ks[1], (b, r), jnp.float32)
    want = jax.lax.dynamic_update_slice(
        stack, new.astype(dtype)[None, :, None], (1, 0, at, 0))
    got = write_latent_rows(stack, new, 1, at, interpret=True)
    bits = np.uint16 if dtype == jnp.bfloat16 else np.uint32
    assert got.shape == stack.shape and got.dtype == stack.dtype
    np.testing.assert_array_equal(np.asarray(got).view(bits),
                                  np.asarray(want).view(bits))
    assert np.isnan(np.asarray(got, np.float32)[[0, 2]]).all()


def test_flash_under_a_mesh_runs_in_a_shard_map_and_matches_reference():
    """GSPMD cannot partition a Mosaic kernel, so on a mesh of several
    devices the model wraps the flash call in a shard_map over batch and
    heads (models/transformer._attention). Same numbers as the reference,
    and the error for a batch that does not divide is explicit."""
    from tony_tpu.models import transformer
    from tony_tpu.parallel import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(fsdp=2, tensor=2), devices=jax.devices()[:4])
    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=64, n_layers=1, n_heads=4, n_kv_heads=4,
        d_ff=64, dtype=jnp.float32, attn_impl="flash")
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q, k, v = (jax.random.normal(kk, (4, 128, 4, 16), jnp.float32)
               for kk in ks)
    got = jax.jit(lambda q, k, v: transformer._attention(q, k, v, cfg, mesh)
                  )(q, k, v)
    want = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    with pytest.raises(ValueError, match="must divide"):
        transformer._attention(q[:3], k[:3], v[:3], cfg, mesh)

