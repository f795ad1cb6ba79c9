"""A decoder of sliding-window and global attention layers in a parallel
block with routed and shared experts (``layer_kinds`` "window" and "full",
``norm_order`` "parallel", ``norm_type`` "layer", ``mlp_kinds`` "routed":
the ``cohere2_moe`` block) through the normal serving path, on the CPU at
a tiny size: three window layers and a global one; hidden 64, 8 query
heads of 16 over 2 KV heads (a head width that is not hidden / heads), a
window of 8 kept in a ring of 16 beside the global layer's 64, 8 of 16
experts held and 4 a token, two shared experts averaged.

The program is held to the benchmark's plain reference
(``benchmark/reference/window_moe_decoder.py``: float32, attention over
the whole sequence with a mask, a loop over the experts, no cache, nothing
of ``tony_tpu``) on seeded weights from
``benchmark/weights/window_moe_decoder.py``, through the mapping the
benchmark's driver makes: prefill and decode past the window and across
the window ring's wrap. Also here: the parallel block and LayerNorm
against their equations, a chip's shares of the experts against the
whole layer, the ring's view, the counters and the refusals.
"""

import contextlib
import dataclasses
import functools
import sys
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.models import serving, transformer
from tony_tpu.models.generate import (init_cache, prepare_decode,
                                      window_ring, window_view)
from tony_tpu.models.serving import Request, SlotServer
from tony_tpu.ops import decode_attention

REPO = Path(__file__).resolve().parent.parent
BENCH = REPO / "benchmark"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import lib  # noqa: E402  (benchmark/lib.py)

driver = lib.load("drivers/serve_window_moe.py")
reference = lib.load("reference/window_moe_decoder.py")
weights = lib.load("weights/window_moe_decoder.py")

CFG = lib.read_json(
    BENCH / "tests/fixture_window_moe/benchmark/configs/tiny-window-moe.json")
SEED = 2 ** 31 + 7
CHUNK, MAX_LEN = 8, 64
ENGINE = dict(slots=4, max_len=MAX_LEN, block_size=4, prefill_chunk=CHUNK)
# float32 on both sides, the same mathematics in another order of
# operations (a ring read through its view against a masked whole
# sequence, sorted assignments against a loop over the experts) through 4
# layers: the logprobs agree to 1e-5. A window off by one position, the
# shared experts summed, a norm without its mean read 0.01 and up
LOGPROB_TOL = 1e-3


@pytest.fixture(scope="module")
def tcfg():
    return driver.transformer_config(CFG, MAX_LEN)


@pytest.fixture(scope="module")
def prepared(tcfg):
    return prepare_decode(
        driver.program_params(CFG, SEED, jnp.float32), tcfg)


def _reference_logprobs(prompt, served, cfg=CFG):
    """log-softmax of the reference's logits at every served position."""
    toks = np.zeros((1, MAX_LEN), np.int32)
    full = np.concatenate([prompt, np.asarray(served, np.int32)])
    toks[0, :full.size] = full
    positions = (len(prompt) - 1 + np.arange(len(served)))[None]
    logits = reference.served_logits(cfg, SEED, "float32", toks, positions)
    return np.asarray(jax.nn.log_softmax(logits[0], axis=-1))


def _check(req, comp, cfg=CFG):
    want = _reference_logprobs(req.prompt, comp.tokens, cfg)
    assert len(comp.tokens) == req.max_new_tokens
    assert (want.argmax(-1) == np.asarray(comp.tokens)).all()
    for i, entry in enumerate(comp.logprobs):
        ids, values = entry["top"]
        assert abs(entry["logprob"] - want[i, entry["token"]]) < LOGPROB_TOL
        assert np.abs(np.asarray(values) - want[i, ids]).max() < LOGPROB_TOL


def _request(rng, n_prompt, max_new=9, **kw):
    return Request(prompt=rng.integers(3, CFG["vocab_size"], n_prompt,
                                       dtype=np.int32),
                   max_new_tokens=max_new, logprobs=8, **kw)


# ------------------------------------------------------ the serving path

def test_the_config_is_the_files(tcfg):
    assert tcfg.layer_kinds == ("window",) * 3 + ("full",)
    assert tcfg.head_dim == 16 != tcfg.d_model // tcfg.n_heads
    assert (tcfg.win_size, tcfg.rope_theta, tcfg.norm_order,
            tcfg.norm_type) == (8, None, "parallel", "layer")
    assert tcfg.experts_held == (4, 8) and tcfg.moe_experts == 16
    assert window_ring(tcfg, MAX_LEN, CHUNK) == CFG["engine"]["window_ring"]


@pytest.mark.parametrize("n_prompt, max_new", [
    (1, 20),                # decode alone, past the window and the ring
    (5, 14),                # a part of a chunk
    (CHUNK + 1, 12),        # two chunks
    (3 * CHUNK + 2, 20),    # four chunks: the prefill wraps the ring
])
def test_slot_server_agrees_with_the_reference(prepared, tcfg, n_prompt,
                                               max_new):
    """Prefill then decode through the two rings against the reference's
    full forward, at logits level; every case runs past the window (8)
    and the window ring (16)."""
    assert n_prompt + max_new > 16
    server = SlotServer(prepared, tcfg, **ENGINE)
    req = _request(np.random.default_rng(n_prompt), n_prompt, max_new)
    server.submit(req)
    _check(req, server.run_until_drained()[req.id])


@pytest.mark.parametrize("predictive", [True, False])
def test_a_burst_of_lengths_and_a_slot_reused(prepared, tcfg, predictive):
    """Six requests over four slots, prompts of one to four chunks in one
    burst, a slot taken again after a completion; with and without a stop
    token (the two scheduling modes)."""
    server = SlotServer(prepared, tcfg, **ENGINE,
                        stop_tokens=() if predictive else (2,))
    rng = np.random.default_rng(7)
    reqs = [_request(rng, n, m) for n, m in
            ((3, 17), (19, 15), (CHUNK, 11), (30, 4), (2, 19), (12, 6))]
    for r in reqs:
        server.submit(r)
    done = server.run_until_drained()
    for r in reqs:
        if predictive or 2 not in done[r.id].tokens:
            _check(r, done[r.id])


def test_rounds_over_the_live_key_blocks(prepared, tcfg, monkeypatch):
    """With the scores' budget at nothing, every chunk round takes its
    attention a row at a time over the live blocks of 4 positions (online
    softmax), to the same logits."""
    monkeypatch.setattr(serving, "ROW_SCORES_BYTES", 0)
    monkeypatch.setattr(serving, "kv_block_k", lambda *a: 4)
    # a block size no other test compiles: the budget is read when
    # `_prefill_batch` is traced
    server = SlotServer(prepared, tcfg, **dict(ENGINE, block_size=3))
    rng = np.random.default_rng(11)
    reqs = [_request(rng, n, 9) for n in (9, 30, 2, 17)]
    for r in reqs:
        server.submit(r)
    done = server.run_until_drained()
    for r in reqs:
        _check(r, done[r.id])


@pytest.mark.parametrize("window", [0, 6])
def test_the_blockwise_round_is_the_einsum(monkeypatch, window):
    """`_rows_attention` over the live blocks against the einsum over the
    whole ring, on rings of 32 whose rows start anywhere and wrap."""
    from tony_tpu.models.generate import _cached_attention

    k_rows, l, kvh, rep, d, m = 4, 8, 2, 3, 16, 32
    cfg = transformer.TransformerConfig(
        d_model=kvh * rep * d, n_heads=kvh * rep, n_kv_heads=kvh,
        dtype=jnp.float32)
    ks = jax.random.split(jax.random.PRNGKey(window), 3)
    q = jax.random.normal(ks[0], (k_rows, l, kvh * rep, d))
    ck, cv = (jax.random.normal(k, (k_rows, kvh, m, d)) for k in ks[1:])
    starts = jnp.array([0, 5, 17, 24])
    offsets = jnp.array([3, 30, 20, 9])
    want = _cached_attention(cfg, q, ck, cv, starts, l, ring_offsets=offsets,
                             allow_kernel=False, window=window)
    monkeypatch.setattr(serving, "ROW_SCORES_BYTES", 0)
    monkeypatch.setattr(serving, "kv_block_k", lambda *a: 4)
    got = serving._rows_attention(cfg, q, ck, cv, starts, l, None, None,
                                  offsets, window=window)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_the_full_forward_is_the_reference(prepared, tcfg):
    """`transformer.apply` (the training forward: no cache, the model's own
    attention with each kind's window) over 40 positions, five windows
    long, against the reference's logits."""
    toks = np.random.default_rng(4).integers(3, 256, (1, 40), np.int32)
    cfg = dataclasses.replace(tcfg, attn_impl="ref")
    with jax.default_matmul_precision("highest"):
        got, _ = transformer.apply(prepared.params, jnp.asarray(toks), cfg)
    pad = np.zeros((1, MAX_LEN), np.int32)
    pad[0, :40] = toks[0]
    want = reference.served_logits(CFG, SEED, "float32", pad,
                                   np.arange(40)[None])
    np.testing.assert_allclose(got, want, atol=2e-4)


def test_the_reference_in_bfloat16_activations_moves_only_by_rounding():
    """The reference's "a16" (every activation rounded to bfloat16, the
    matrices kept: the witness of what the served dtype alone moves), on
    the experts the float32 pass chose, moves the logits by a few
    bfloat16 steps through 4 layers (1.8% of their size on the mean), and
    does move them; a precision it does not know is refused."""
    toks = np.random.default_rng(5).integers(3, 256, (1, MAX_LEN), np.int32)
    at = np.arange(MAX_LEN)[None]
    _, chosen, _ = reference.hidden(CFG, SEED, "float32", toks)
    exact, rounded = (np.asarray(reference.served_logits(
        CFG, SEED, "float32", toks, at, lowp=lowp, routes=chosen))
        for lowp in (None, "a16"))
    gap = np.abs(rounded - exact).mean() / np.abs(exact).mean()
    assert 2 ** -9 < gap < 2 ** -4
    with pytest.raises(ValueError, match="lowp"):
        reference.served_logits(CFG, SEED, "float32", toks, at, lowp="b16")


def test_lockstep_generate_serves_the_same_tokens(prepared, tcfg):
    """`generate()` (rings of max_len that never wrap, the whole prompt at
    once) gives the tokens the slot server gives through its window ring."""
    from tony_tpu.models.generate import generate

    req = _request(np.random.default_rng(5), 21, 20)
    server = SlotServer(prepared, tcfg, **ENGINE)
    server.submit(req)
    served = server.run_until_drained()[req.id].tokens
    got = generate(prepared, tcfg, jnp.asarray(req.prompt)[None], 20,
                   max_len=MAX_LEN)
    assert np.asarray(got[0]).tolist() == list(served)


def test_the_window_ring_holds_the_last_positions(prepared, tcfg):
    """After the admission of a prompt of 30 (29 positions prefilled in
    four chunks, the last fed), the first window layer's ring holds the
    last 16 of its K, rotated, at (p + offset) mod 16: the global ring's
    offset, taken mod the window ring's length. (Once a request is done,
    its slot's rings take the idle row's writes at the cursor.)"""
    server = SlotServer(prepared, tcfg, **ENGINE)
    req = _request(np.random.default_rng(3), 30, 10)
    server.submit(req)
    server._admit()
    cache = server._cache
    slot = 0
    n = int(cache.length[slot])
    assert n == 29
    off = int(server._np_offs[slot])
    toks = req.prompt[:n]
    # the reference's K of layer 0, whose input is the embedding
    lw = weights.layer(weights.seed_key(SEED), CFG, 0, jnp.float32)
    x = weights.embed(weights.seed_key(SEED), CFG, jnp.float32)[toks]
    h = reference.layer_norm(x, lw["attn_norm"], CFG["layer_norm_eps"])
    with jax.default_matmul_precision("highest"):
        k = reference.rope((h @ lw["wk"]).reshape(n, 2, 16), jnp.arange(n),
                           10000.0)
    ring = np.asarray(cache.win_k[0, slot])             # [kvH, 16, hd]
    for p in range(n - 16, n):
        np.testing.assert_allclose(ring[:, (p + off) % 16], k[p], atol=1e-5)
    assert cache.k.shape[3] == MAX_LEN and cache.win_k.shape[3] == 16


def test_window_view_counts_from_the_oldest_position_held():
    """A ring of 16 with a row at 37 positions (its query the 38th): the
    view starts at position 22 (= 37 + 1 - 16), which lies at index (22 +
    off) mod 16; before the ring fills nothing moves."""
    lengths, offsets = np.array([37, 5, 15]), np.array([3, 9, 0])
    got = window_view(lengths, offsets, 16, 1, xp=np)
    np.testing.assert_array_equal(got[0], [15, 5, 15])
    np.testing.assert_array_equal(got[1], [(3 + 22) % 16, 9, 0])
    # a chunk of 8 starting at 20: the ring then holds 12 .. 27
    got = window_view(np.array([20]), np.array([1]), 16, 8, xp=np)
    assert got[0].tolist() == [8] and got[1].tolist() == [13]


# ------------------------------------------------ the block's equations

def _pieces(seed=0, t=6, d=32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (1, t, d)) * 3 + 1.5,
            1.0 + 0.1 * jax.random.normal(ks[1], (d,)))


def test_layer_norm_is_mean_centred_with_a_weight_and_no_bias():
    x, w = _pieces()
    got = transformer.layer_norm(x, w, 1e-5)
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    np.testing.assert_allclose(got, (x - mean) / np.sqrt(var + 1e-5) * w,
                               rtol=1e-5, atol=1e-5)
    cfg = transformer.TransformerConfig(norm_type="layer", norm_eps=1e-5)
    np.testing.assert_array_equal(transformer.norm(cfg, x, w), got)
    rms = transformer.TransformerConfig(norm_eps=1e-5)
    np.testing.assert_array_equal(transformer.norm(rms, x, w),
                                  transformer.rms_norm(x, w, 1e-5))


def test_the_parallel_block_is_x_plus_both_branches_of_one_norm():
    """x + Attn(LN(x)) + MLP(LN(x)): the MLP reads the mixer's normed
    input, and there is no second norm."""
    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=1, n_heads=4, n_kv_heads=2,
        attn_head_dim=12, d_ff=24, norm_order="parallel", norm_type="layer",
        norm_eps=1e-5, rope_theta=None, dtype=jnp.float32, attn_impl="ref")
    params = transformer.init(jax.random.PRNGKey(1), cfg)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    assert "mlp_norm" not in lp and lp["wq"].shape == (32, 4, 12)
    x, _ = _pieces(2)
    pos = jnp.arange(6)[None]
    got, _ = transformer._layer(cfg, None, x, pos, lp)
    h = transformer.layer_norm(x, lp["attn_norm"], 1e-5)
    q = jnp.einsum("bld,dhk->blhk", h, lp["wq"])
    k = jnp.repeat(jnp.einsum("bld,dhk->blhk", h, lp["wk"]), 2, axis=2)
    v = jnp.repeat(jnp.einsum("bld,dhk->blhk", h, lp["wv"]), 2, axis=2)
    s = jnp.einsum("bqhk,bphk->bhqp", q, k) / np.sqrt(12)
    s = jnp.where(jnp.tril(jnp.ones((6, 6), bool)), s, -jnp.inf)
    attn = jnp.einsum("bhqp,bphk->bqhk", jax.nn.softmax(s, -1), v)
    attn = jnp.einsum("blhk,hkd->bld", attn, lp["wo"])
    mlp = (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) @ lp["w_down"]
    np.testing.assert_allclose(got, x + attn + mlp, rtol=1e-5, atol=1e-5)


def test_a_window_layer_rotates_and_a_full_one_does_not():
    cfg = transformer.TransformerConfig(
        d_model=32, n_heads=2, n_kv_heads=2, rope_theta=None,
        win_rope_theta=100.0, rope_interleave=True, dtype=jnp.float32)
    lp = jax.tree.map(lambda a: a[0], transformer._attention_stack(
        iter(jax.random.split(jax.random.PRNGKey(0), 8)), cfg, 1))
    h, _ = _pieces(3)
    pos = jnp.arange(6)[None]
    q_full, k_full, _ = transformer._qkv(cfg, h, pos, lp, "full")
    q_win, k_win, _ = transformer._qkv(cfg, h, pos, lp, "window")
    np.testing.assert_array_equal(
        q_full, jnp.einsum("bld,dhk->blhk", h, lp["wq"]))
    np.testing.assert_allclose(
        q_win, transformer.rope(q_full, pos, 100.0, interleave=True),
        rtol=1e-6)
    np.testing.assert_allclose(
        k_win, transformer.rope(k_full, pos, 100.0, interleave=True),
        rtol=1e-6)


@pytest.mark.parametrize("shares", [1, 2])
def test_the_shares_of_the_experts_add_up_to_the_whole_layer(shares):
    """With the 16 experts held whole and as two halves, the parts of the
    result that the shares give, the averaged shared experts counted once,
    add up to the uncut reference's MLP; and the program's shared experts
    are their mean."""
    whole = dict(CFG, num_experts=16, experts_held=[0, 16])
    key = weights.seed_key(SEED)
    lw = weights.layer(key, whole, 1, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(2), (20, CFG["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        chosen, w, _ = reference.route(whole, x, lw)
        shared = np.asarray(reference.shared_experts(whole, x, lw))
        want = reference.routed_experts(whole, x, lw, chosen, w) + shared
    count = 16 // shares
    total = 0.0
    for i in range(shares):
        held = [i * count, count]
        part = dict(CFG, num_experts=count, experts_held=held)
        _, mlp = driver.program_layer(part, weights.layer(key, part, 1,
                                                          jnp.float32))
        cfg = driver.transformer_config(part, MAX_LEN)
        assert mlp["we_gu"].shape[0] == count == cfg.experts_held[1]
        out, picks = transformer._mlp(cfg, x[None], mlp)
        total = total + np.asarray(out[0]) - (shared if i else 0.0)
        assert (np.sort(picks[0], -1) == np.sort(chosen, -1)).all()
    np.testing.assert_allclose(total, want, atol=1e-5)
    # the two shared experts averaged: half their sum
    _, mlp = driver.program_layer(whole, lw)
    cfg = driver.transformer_config(whole, MAX_LEN)
    summed = dataclasses.replace(cfg, moe_shared_mean=False)
    with jax.default_matmul_precision("highest"):
        routed = np.asarray(transformer._mlp(
            dataclasses.replace(cfg, moe_shared=0), x[None], mlp)[0][0])
        mean = np.asarray(transformer._mlp(cfg, x[None], mlp)[0][0]) - routed
        both = np.asarray(transformer._mlp(summed, x[None], mlp)[0][0]) \
            - routed
    np.testing.assert_allclose(mean, shared, atol=1e-5)
    np.testing.assert_allclose(both, 2 * shared, atol=1e-5)


# --------------------------------------------------- counters and kernel

def _recording_phase(seen):
    """``serving.phase`` stood in for: each span's name and counts."""
    @contextlib.contextmanager
    def phase(name, **counts):
        span = types.SimpleNamespace(counts=dict(counts))
        span.set_metadata = span.counts.update
        seen.append((name, span.counts))
        yield span
    return phase


def test_the_window_decode_kernel_serves_the_reference(prepared, tcfg,
                                                       monkeypatch):
    """With the gate forced open (``flash_decode`` interpreted, blocks of 4
    positions at 4 query heads a KV head), the served tokens are the
    reference's; the bookkeep spans carry the window layers' blocks read
    by the kernel's rule through the ring's view and those the rows'
    contexts span, the positions held and seen, and ``kv_blocks_read`` /
    ``_ring`` over one full layer and three window ones."""
    import importlib

    generate = importlib.import_module("tony_tpu.models.generate")
    for module in (generate, serving):
        monkeypatch.setattr(module, "decode_kernel_engages",
                            lambda *a: True)
    for module in (decode_attention, serving):
        monkeypatch.setattr(module, "kv_block_k", lambda *a: 4)
    monkeypatch.setattr(decode_attention, "flash_decode", functools.partial(
        decode_attention.flash_decode, interpret=True))
    seen = []
    monkeypatch.setattr(serving, "phase", _recording_phase(seen))
    # the gate is read when `_decode_block` is traced: a block size no
    # other test compiles
    server = SlotServer(prepared, tcfg, **dict(ENGINE, block_size=5))
    rng = np.random.default_rng(12)
    reqs = [_request(rng, n, m) for n, m in ((4, 20), (17, 14), (25, 3))]
    for r in reqs:
        server.submit(r)
    done = server.run_until_drained()
    for r in reqs:
        _check(r, done[r.id])
    spans = [c for name, c in seen if name == "serve.step.bookkeep"]
    total = lambda k: sum(c[k] for c in spans)
    assert total("window_blocks_read") == server.window_blocks_read > 0
    # a window of 8 in blocks of 4 is 2-3 blocks a row; a context past 16
    # spans 5 and more
    assert server.window_blocks_read < server.window_blocks_context
    assert 0 < server.window_positions < server.live_positions
    assert total("window_positions") == server.window_positions
    blocks = server.kv_blocks_ring // (4 * (64 // 4) + 3 * 4 * (16 // 4))
    assert server.kv_blocks_ring == blocks * (64 + 3 * 16)
    assert 0 < server.kv_blocks_read < server.kv_blocks_ring


def test_the_expert_counts_are_of_the_experts_held(prepared, tcfg):
    """A chip holding 8 of 16 experts: the experts touched and read a
    step are among its 8, never more, whatever the rows chose elsewhere."""
    server = SlotServer(prepared, tcfg, **ENGINE)
    rng = np.random.default_rng(9)
    for n, m in ((4, 12), (11, 8), (7, 10)):
        server.submit(_request(rng, n, m))
    server.run_until_drained()
    e = server.stats()["experts"]
    assert e["held_steps"] % (8 * 4) == 0
    assert 0 < e["touched"] <= e["read"] <= e["held_steps"]


def test_the_benchmark_reads_the_window_counts_off_the_trace(prepared, tcfg,
                                                              tmp_path,
                                                              monkeypatch):
    """What the program writes on the bookkeep spans of a profiler session
    and what ``window_kv_read_pct`` reads back through the benchmark's
    ``trace/host_spans.py`` are the same counts."""
    host_spans = lib.load("trace/host_spans.py")
    server = SlotServer(prepared, tcfg, **ENGINE)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        rng = np.random.default_rng(13)
        for n, m in ((6, 18), (20, 9)):
            server.submit(_request(rng, n, m))
        server.run_until_drained()
    finally:
        jax.profiler.stop_trace()
    monkeypatch.setattr(host_spans, "TRACE_ROOT", tmp_path)
    got = lib.load("layer_metrics/window_kv_read_pct.py").read({}, "")
    # the CPU's einsum reads the whole ring: 4 slots x one block of 16
    assert got == pytest.approx(
        100.0 * server.window_blocks_read / server.window_blocks_context)
    assert server.window_blocks_read % 4 == 0


def test_flash_decode_at_sixteen_query_heads_a_kv_head():
    """The window layers' decode attention at the served cell's grouping
    (128 query heads over 8 KV heads: 16 a KV head) on a wrapped ring,
    interpreted, against the einsum through the ring's view."""
    from tony_tpu.models.generate import _cached_attention

    b, kvh, rep, d, ring = 3, 2, 16, 128, 32
    cfg = transformer.TransformerConfig(d_model=kvh * rep * d,
                                        n_heads=kvh * rep, n_kv_heads=kvh,
                                        dtype=jnp.float32)
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (b, 1, kvh * rep, d))
    ck, cv = (jax.random.normal(k, (b, kvh, ring, d)) for k in ks[1:])
    lengths = jnp.array([5, 40, 71])           # one inside, two wrapped
    offsets = jnp.array([7, 30, 2])
    w_len, w_off = window_view(lengths, offsets, ring, 1)
    want = _cached_attention(cfg, q, ck, cv, w_len, 1, ring_offsets=w_off,
                             allow_kernel=False, window=12)
    got = decode_attention.flash_decode(
        q.reshape(b, kvh, rep, d), ck, cv, w_len, ring_offsets=w_off,
        window=12, block_k=8, interpret=True)
    np.testing.assert_allclose(got.reshape(want.shape), want, atol=2e-5)


# ------------------------------------------------------------- refusals

@pytest.mark.parametrize("kwargs, message", [
    (dict(prefix_cache_blocks=4), "cannot use prefix_cache_blocks"),
    (dict(paged=True), "cannot use paged=True"),
    (dict(draft="draft"), "cannot use a draft / spec_gamma"),
    (dict(spec_gamma=2), "cannot use a draft / spec_gamma"),
    (dict(kv_dtype="int8"), "cannot use kv_dtype='int8'"),
    (dict(weight_dtype="int8"), "cannot use weight_dtype='int8'"),
    (dict(mesh="a mesh"), "cannot use a mesh"),
    (dict(role="prefill"), "cannot use role='prefill'"),
], ids=lambda v: v if isinstance(v, str) else next(iter(v)))
def test_what_is_not_built_for_a_window_ring_is_refused(kwargs, message):
    """Window layers over a dense MLP: the refusal is the window ring's
    own, by name."""
    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=16, n_layers=2, n_heads=2, n_kv_heads=2,
        d_ff=32, layer_kinds=("window", "full"), win_size=4,
        dtype=jnp.float32)
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="window ring beside.*" + message):
        SlotServer(params, cfg, **ENGINE, **kwargs)
    with pytest.raises(ValueError, match="not implemented for latent or "
                                         "window"):
        init_cache(cfg, 2, 16, kv_dtype="int8")


def test_a_window_config_needs_its_window():
    with pytest.raises(ValueError, match="window layers need win_size"):
        transformer.TransformerConfig(n_layers=2,
                                      layer_kinds=("window", "full"))
    with pytest.raises(ValueError, match="norm_type must be"):
        transformer.TransformerConfig(norm_type="batch")
