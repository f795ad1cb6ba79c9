"""``gated_delta_decode`` (ops/gated_delta.py), the decode step's recurrence
as one Pallas call on the cache's whole state stack, interpreted on the
CPU and held to ``gated_delta_step``, the statement it implements; and the
gate that says where it runs (``generate.state_kernel_engages``).

What interpret mode cannot see (a shape Mosaic refuses, a copy of the
stack in the compiled program) is ``tests/test_chip_compile.py``'s.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.models import transformer
from tony_tpu.ops.gated_delta import (
    gated_delta_decode,
    gated_delta_step,
    live_state_rows,
)

LAYERS = 3
# (rows, heads, d_k, d_v): the hybrid cell's tile, and a small one whose
# heads the default block does not divide
SHAPES = {"cell": (3, 30, 96, 192), "small": (5, 4, 8, 16)}
LIVE = {
    "all": lambda b: np.ones(b, bool),
    "some": lambda b: np.arange(b) % 2 == 0,
    "none": lambda b: np.zeros(b, bool),
    "unmasked": lambda b: None,
}


def _inputs(shape, seed=0):
    b, h, dk, dv = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, h, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, h, dk)))
    v = jax.random.normal(ks[2], (b, h, dv))
    log_alpha = -jax.random.uniform(ks[3], (b, h))
    beta = 2.0 * jax.random.uniform(ks[4], (b, h))
    state = jax.random.normal(ks[5], (LAYERS, b, h, dk, dv))
    return (q, k, v, log_alpha, beta), state


@pytest.mark.parametrize("live", sorted(LIVE))
@pytest.mark.parametrize("layer", range(LAYERS))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_kernel_is_the_step_on_the_live_rows_of_one_layer(shape, layer, live):
    x, state = _inputs(SHAPES[shape], seed=layer)
    b = SHAPES[shape][0]
    active = LIVE[live](b)
    o_ref, new_ref = gated_delta_step(
        *x, state[layer], None if active is None else jnp.asarray(active))
    o, new = gated_delta_decode(
        *x, state, None if active is None else jnp.asarray(active),
        layer=layer, interpret=True)
    rows = np.ones(b, bool) if active is None else active
    o, new, before = np.asarray(o), np.asarray(new), np.asarray(state)
    # a frozen row's tiles and every other layer's slice: bit for bit
    assert (new[layer][~rows] == before[layer][~rows]).all()
    others = [i for i in range(LAYERS) if i != layer]
    assert (new[others] == before[others]).all()
    assert (o[~rows] == 0).all()
    # the live rows: the statement's own expressions, float32 throughout;
    # only the order of a d_k-long sum may differ
    np.testing.assert_allclose(new[layer][rows], np.asarray(new_ref)[rows],
                               rtol=1e-5, atol=2e-6)
    np.testing.assert_allclose(o[rows], np.asarray(o_ref)[rows],
                               rtol=1e-5, atol=2e-5)
    if rows.any():
        assert not (new[layer][rows] == before[layer][rows]).all()


@pytest.mark.parametrize("head_block", (1, 2, 3, 4, 30))
def test_every_head_block_gives_the_same_state(head_block):
    """A block that does not divide the heads falls to the next that does;
    one larger than the heads is all of them."""
    x, state = _inputs((2, 6, 8, 16))
    active = jnp.asarray([True, False])
    want = gated_delta_decode(*x, state, active, layer=1, interpret=True)
    got = gated_delta_decode(*x, state, active, layer=1, interpret=True,
                             head_block=head_block)
    for a, b in zip(want, got):
        assert bool(jnp.array_equal(a, b))


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_state_keeps_float32_through_the_kernel(shape):
    """The precision guard: a state whose float32 bits a bfloat16 operand
    would drop (1 + k 2^-14) comes out within 1e-6 of the float64 result,
    where the same step on bfloat16-rounded operands lies 1e-4 and more
    away."""
    (q, k, v, log_alpha, beta), state = _inputs(SHAPES[shape], seed=7)
    state = 1.0 + jnp.round(state * 64.0) * 2.0 ** -14

    def f64(state, k):
        s, kk, vv, qq = (np.asarray(t, np.float64)
                         for t in (state[1], k, v, q))
        dec = s * np.exp(np.asarray(log_alpha, np.float64))[..., None, None]
        u = np.asarray(beta, np.float64)[..., None] * (
            vv - (dec * kk[..., None]).sum(-2))
        new = dec + kk[..., None] * u[..., None, :]
        return (new * qq[..., None]).sum(-2), new

    def gap(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        return np.linalg.norm(a - b) / np.linalg.norm(b)

    o_want, new_want = f64(state, k)
    o, new = gated_delta_decode(q, k, v, log_alpha, beta, state, layer=1,
                                interpret=True)
    assert gap(new[1], new_want) < 1e-6 and gap(o, o_want) < 1e-6
    bf16 = lambda t: t.astype(jnp.bfloat16).astype(jnp.float32)
    _, new_low = f64(bf16(state), bf16(k))
    assert gap(new_low, new_want) > 1e-4


def test_live_rows_first_and_in_order_on_host_and_device():
    active = np.array([False, True, True, False, True, False])
    for xp, mask in ((np, active), (jnp, jnp.asarray(active))):
        rows, count = live_state_rows(mask, xp=xp)
        assert int(count) == 3 and rows.dtype == xp.int32
        assert list(np.asarray(rows)) == [1, 2, 4, 0, 3, 5]
    rows, count = live_state_rows(np.zeros(4, bool))
    assert int(count) == 0 and list(rows) == [0, 1, 2, 3]


# ------------------------------------------------------------------ the gate

HYBRID = transformer.TransformerConfig(
    vocab_size=64, d_model=32, n_layers=4, n_heads=2, n_kv_heads=2,
    d_ff=64, max_seq_len=32, dtype=jnp.float32, rope_theta=None,
    qk_norm=True, norm_order="post",
    layer_kinds=("linear", "linear", "linear", "full"),
    lin_heads=2, lin_key_dim=8, lin_value_dim=16,
)


@pytest.mark.parametrize("backend, l_new, sharded, engages", [
    ("cpu", 1, False, False),       # the CPU keeps the jax.numpy statement
    ("tpu", 1, False, True),
    ("tpu", 2, False, False),       # a block of positions: the chunkwise form
    ("tpu", 1, True, False),        # under a mesh: a shard_map away
])
def test_who_runs_the_kernel(monkeypatch, backend, l_new, sharded, engages):
    """The gate by itself, and what `_forward_with_cache` traces by it:
    one call of the kernel a linear layer, all of one traced pallas_call
    (the layer is an operand), or none."""
    import importlib

    generate = importlib.import_module("tony_tpu.models.generate")
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert generate.state_kernel_engages(l_new, sharded) is engages
    if sharded:
        return      # SlotServer and generate() refuse a state over a mesh
    params = transformer.init(jax.random.PRNGKey(0), HYBRID)
    cache = generate.init_cache(HYBRID, 2, 32)
    jaxpr = str(jax.make_jaxpr(
        lambda p, t, c: generate._forward_with_cache(p, HYBRID, t, c))(
            params, jnp.zeros((2, l_new), jnp.int32), cache))
    assert jaxpr.count("name=gated_delta_decode") == (3 if engages else 0)
    assert jaxpr.count("pallas_call") == (1 if engages else 0)
    assert ("cumsum" in jaxpr or "triangular_solve" in jaxpr) == (l_new > 1)
