"""The routed layer's decode-step grouped matmul (parallel/routed_experts.py
``grouped_matmul``: megablox's Pallas kernel at a 16-row tile and whole-K
weight blocks) in interpret mode against ``jax.lax.ragged_dot``, the path
it replaces in a decode step and the one the CPU and a prefill keep; and
the gate that says where it runs (``generate.expert_kernel_engages``).

bfloat16 operands as the benchmark serves them: both sides accumulate in
float32 and round once to bfloat16, so they agree to that rounding.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.models import transformer
from tony_tpu.parallel import routed_experts as rx

E, D, F, K = 256, 128, 128, 8


def _decode_sizes(rows, seed=0, experts=E):
    """The groups a decode step of ``rows`` tokens makes: each picks K
    distinct experts, the popular ones more often (about 150 of 256
    touched at 32 rows, 1-3 rows each)."""
    rng = np.random.default_rng(seed)
    p = rng.gamma(2.0, size=experts)
    chosen = np.stack([rng.choice(experts, K, replace=False, p=p / p.sum())
                       for _ in range(rows)])
    return np.bincount(chosen.reshape(-1), minlength=experts).astype(np.int32)


def _patterns():
    one = np.zeros(E, np.int32)
    one[37] = 32 * K
    last = np.zeros(E, np.int32)
    last[-1] = 32 * K
    return {"one expert": one, "32 rows": _decode_sizes(32),
            "only the last": last, "1 row": _decode_sizes(1, seed=1)}


@pytest.fixture(scope="module")
def operands():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    w_gu = (jax.random.normal(ks[0], (E, D, 2 * F)) * D ** -0.5
            ).astype(jnp.bfloat16)
    w_down = (jax.random.normal(ks[1], (E, F, D)) * F ** -0.5
              ).astype(jnp.bfloat16)
    return w_gu, w_down, ks[2]


def _bf16_close(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    # one rounding to bfloat16 each side: at most an ulp of the larger
    assert np.all(np.abs(got - want) <= 2 ** -7 * np.maximum(
        np.abs(got), np.abs(want)) + 1e-6)


@pytest.mark.parametrize("pattern", sorted(_patterns()))
def test_grouped_matmul_is_ragged_dots_statement(operands, pattern):
    """Both matmuls of the layer on each pattern of groups: the rows of
    every group equal ``ragged_dot``'s to bfloat16 rounding."""
    w_gu, w_down, key = operands
    sizes = jnp.asarray(_patterns()[pattern])
    m = int(sizes.sum())
    for w in (w_gu, w_down):
        x = jax.random.normal(key, (m, w.shape[1])).astype(jnp.bfloat16)
        got = rx.grouped_matmul(x, w, sizes)
        assert got.shape == (m, w.shape[2]) and got.dtype == jnp.bfloat16
        _bf16_close(got, jax.lax.ragged_dot(x, w, sizes))


@pytest.mark.parametrize("rows, held", [
    (32, (0, E)),
    (1, (0, E)),
    (32, (64, 96)),     # a share: rows of experts held elsewhere sort past
    (1, (200, 56)),     # the last group, and the kernel never visits them
])
def test_routed_ffn_with_the_kernel_is_the_ragged_dot_layer(operands, rows,
                                                            held):
    """The whole layer, sort and combine included, on a decode step's
    routing of 1 and 32 rows, with all experts held and with a share."""
    w_gu, w_down, key = operands
    first, count = held
    sizes = _decode_sizes(rows, seed=rows + first)
    chosen = jnp.asarray(np.repeat(np.arange(E), sizes).reshape(K, rows).T)
    ks = jax.random.split(key)
    x = jax.random.normal(ks[0], (rows, D)).astype(jnp.bfloat16)
    w = jax.random.uniform(ks[1], (rows, K))
    mine = w_gu[first:first + count], w_down[first:first + count]
    got = rx.routed_ffn(x, chosen, w, *mine, held=held, kernel=True)
    want = rx.routed_ffn(x, chosen, w, *mine, held=held)
    # the combine sums 8 bfloat16 products in float32: a few roundings
    scale = float(jnp.abs(want.astype(jnp.float32)).max())
    assert float(jnp.abs(got.astype(jnp.float32)
                         - want.astype(jnp.float32)).max()) < 2e-2 * scale
    assert scale > 0 and np.isfinite(np.asarray(got, np.float32)).all()


@pytest.mark.parametrize("backend, l_new, sharded, engages", [
    ("cpu", 1, False, False),       # the CPU keeps ragged_dot
    ("tpu", 1, False, True),        # a decode step: 0-3 rows an expert
    ("tpu", 8, False, False),       # a block of positions: ragged_dot
    ("tpu", 1, True, False),        # under a mesh: a shard_map away
])
def test_who_runs_the_kernel(monkeypatch, backend, l_new, sharded, engages):
    """The gate by itself, and what `_forward_with_cache` traces by it: two
    kernels a routed layer (gate-and-up, down) where it engages, else two
    ``ragged_dot``s."""
    import importlib

    from tony_tpu.models.generate import _forward_with_cache, init_cache

    generate = importlib.import_module("tony_tpu.models.generate")
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert generate.expert_kernel_engages(l_new, sharded) is engages
    if sharded:
        return      # SlotServer refuses a routed layer over a mesh
    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=2, n_kv_heads=2,
        d_ff=64, max_seq_len=32, dtype=jnp.float32,
        layer_kinds=("full", "full"), mlp_kinds=("dense", "routed"),
        moe_experts=8, moe_top_k=2, moe_ff=16, moe_scale=1.0)
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    cache = init_cache(cfg, 2, 32)
    jaxpr = str(jax.make_jaxpr(
        lambda p, t, c: _forward_with_cache(p, cfg, t, c))(
            params, jnp.zeros((2, l_new), jnp.int32), cache))
    assert jaxpr.count("pallas_call") == (2 if engages else 0)
    assert jaxpr.count("ragged_dot_general[") == (0 if engages else 2)
