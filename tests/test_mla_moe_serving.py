"""A latent-attention decoder with routed experts (``layer_kinds``
"latent", ``mlp_kinds`` "routed": the DeepSeek-V3-shaped block) through the
normal serving path, on the CPU at a tiny size: a dense layer and two
routed ones; hidden 64, 4 heads of nope 16 / rope 8 / v 16, a latent of 32,
16 experts of which 4 a token.

The program is held to the benchmark's plain reference
(``benchmark/reference/mla_moe_decoder.py``: float32, the expanded
attention, a loop over the experts, no cache, nothing of ``tony_tpu``) on
seeded weights from ``benchmark/weights/mla_moe_decoder.py``, through the
mapping the benchmark's driver makes. Also here: the expert layer alone
(the bias, the load, a chip's share of the experts), the refusals of what
is not built for a latent cache or an expert layer, and the uniform and
hybrid programs' StableHLO against the parent's.
"""

import contextlib
import dataclasses
import hashlib
import json
import sys
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.models import serving, transformer
from tony_tpu.models.generate import (_forward_with_cache, init_cache,
                                      prepare_decode)
from tony_tpu.models.serving import Request, SlotServer
from tony_tpu.parallel import routed_experts

REPO = Path(__file__).resolve().parent.parent
BENCH = REPO / "benchmark"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import lib  # noqa: E402  (benchmark/lib.py)

driver = lib.load("drivers/serve_mla_moe.py")
reference = lib.load("reference/mla_moe_decoder.py")
weights = lib.load("weights/mla_moe_decoder.py")

CFG = lib.read_json(
    BENCH / "tests/fixture_mla_moe/benchmark/configs/tiny-mla-moe.json")
SEED = 2 ** 31 + 5
CHUNK, MAX_LEN = 8, 64
ENGINE = dict(slots=4, max_len=MAX_LEN, block_size=4, prefill_chunk=CHUNK)
# float32 on both sides, the same mathematics in another order of
# operations (absorbed against expanded attention, cached against full,
# sorted assignments against a loop over the experts) through 3 layers:
# the logprobs agree to 1e-5. A bias that weighs, a row cached before its
# norm, a rotation of the wrong pairs read 0.1 and up
LOGPROB_TOL = 1e-3


@pytest.fixture(scope="module")
def tcfg():
    return driver.transformer_config(CFG, MAX_LEN)


@pytest.fixture(scope="module")
def prepared(tcfg):
    return prepare_decode(
        driver.program_params(CFG, SEED, jnp.float32), tcfg)


def _reference_logprobs(prompt, served, routes=None):
    """log-softmax of the reference's logits at every served position."""
    toks = np.zeros((1, MAX_LEN), np.int32)
    full = np.concatenate([prompt, np.asarray(served, np.int32)])
    toks[0, :full.size] = full
    positions = (len(prompt) - 1 + np.arange(len(served)))[None]
    if routes is not None:
        pad = np.tile(np.arange(routes.shape[-1], dtype=np.int32),
                      (1, MAX_LEN, routes.shape[1], 1))
        pad[0, :len(routes)] = routes
        routes = pad
    logits = reference.served_logits(CFG, SEED, "float32", toks, positions,
                                     routes=routes)
    return np.asarray(jax.nn.log_softmax(logits[0], axis=-1))


def _check(req, comp):
    want = _reference_logprobs(req.prompt, comp.tokens)
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

@pytest.mark.parametrize("n_prompt", [1, 5, CHUNK + 1, 3 * CHUNK + 2])
def test_slot_server_agrees_with_the_reference(prepared, tcfg, n_prompt):
    """Prefill (no chunk, a part of one, two, four) then decode through the
    latent cache against the reference's full forward, at logits level."""
    server = SlotServer(prepared, tcfg, **ENGINE)
    req = _request(np.random.default_rng(n_prompt), n_prompt)
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
            ((3, 7), (19, 5), (CHUNK, 11), (30, 4), (2, 9), (12, 6))]
    for r in reqs:
        server.submit(r)
    done = server.run_until_drained()
    for r in reqs:
        if predictive or 2 not in done[r.id].tokens:
            _check(r, done[r.id])


def test_a_burst_wider_than_the_rows_taken_at_once(prepared, tcfg):
    """Sixteen admissions in one dispatch: their latent attention goes
    ``LATENT_ROWS_AT_ONCE`` rows at a time, to the same logits."""
    assert serving.LATENT_ROWS_AT_ONCE < 16
    server = SlotServer(prepared, tcfg, **dict(ENGINE, slots=16))
    rng = np.random.default_rng(11)
    reqs = [_request(rng, 3 + i, 5) for i in range(16)]
    for r in reqs:
        server.submit(r)
    done = server.run_until_drained()
    assert server.admission_dispatches == 3     # three chunk rounds, 16 rows
    for r in reqs[::5]:
        _check(r, done[r.id])


def test_the_routes_a_request_asked_for_are_the_references_own(prepared,
                                                               tcfg):
    """``Completion.routes``: a row a consumed position, from the prefill
    and the decode steps alike; in float32 they are the experts the
    reference itself chooses, and the reference given them computes what
    it computes alone."""
    server = SlotServer(prepared, tcfg, **ENGINE)
    req = _request(np.random.default_rng(3), 2 * CHUNK + 3, 10, routes=True)
    plain = _request(np.random.default_rng(4), 5, 6)
    server.submit(req)
    server.submit(plain)
    done = server.run_until_drained()
    comp = done[req.id]
    assert done[plain.id].routes is None
    n = len(req.prompt) + len(comp.tokens) - 1
    assert comp.routes.shape == (n, 2, CFG["num_experts_per_tok"])
    toks = np.concatenate([req.prompt, comp.tokens])[None, :n]
    _, chosen, margin = reference.hidden(CFG, SEED, "float32", toks)
    assert (np.sort(np.asarray(chosen)[0], -1)
            == np.sort(comp.routes, -1)).all()
    _, _, margin = reference.hidden(CFG, SEED, "float32", toks,
                                    routes=comp.routes[None])
    assert float(np.asarray(margin).max()) < 1e-6
    want = _reference_logprobs(req.prompt, comp.tokens, comp.routes)
    assert np.abs(want - _reference_logprobs(req.prompt, comp.tokens)).max() \
        < 1e-5
    # and a choice that is not the reference's shows in the margin
    wrong = comp.routes.copy()
    wrong[5, 0, 0] = (set(range(16)) - set(wrong[5, 0].tolist())).pop()
    _, _, margin = reference.hidden(CFG, SEED, "float32", toks,
                                    routes=wrong[None])
    assert float(np.asarray(margin)[0, 5, 0]) > 0
    dense = transformer.TransformerConfig(
        vocab_size=64, d_model=16, n_layers=1, n_heads=2, n_kv_heads=2,
        d_ff=32)
    with pytest.raises(ValueError, match="without routed expert layers"):
        SlotServer(transformer.init(jax.random.PRNGKey(0), dense), dense,
                   **ENGINE).submit(
            Request(prompt=[1, 2], max_new_tokens=2, routes=True))


def test_the_cached_row_is_the_latent_after_norm_and_rotation(prepared, tcfg):
    """What a slot holds of a position is [c_kv | k_r] after the norm and
    the rotation: the reference's rows of the first layer (which reads the
    embedding, the same numbers on both sides), prefilled and decoded."""
    server = SlotServer(prepared, tcfg, **ENGINE)
    req = _request(np.random.default_rng(5), CHUNK + 5, 7)
    server.submit(req)
    comp = server.run_until_drained()[req.id]
    slot = comp.trace["attrs"]["slot"]
    held = server.slot_latent_rows(0)
    n = len(req.prompt) + len(comp.tokens) - 1
    assert int(held["length"][slot]) == n
    toks = np.concatenate([req.prompt, comp.tokens])[:n]
    key = weights.seed_key(SEED)
    lw = weights.layer(key, CFG, 0, "dense", jnp.float32)
    x = weights.embed(key, CFG, jnp.float32)[toks]
    with jax.default_matmul_precision("highest"):
        want = reference.latent_rows(
            CFG, reference.rms_norm(x, lw["attn_norm"], CFG["rms_norm_eps"]),
            lw)
    assert want.shape == (n, 32 + 8)
    assert np.abs(held["rows"][slot, :n] - np.asarray(want)).max() < 1e-5


def test_a_frozen_row_keeps_its_latent_rows_while_others_decode(prepared,
                                                                tcfg):
    """A row that finished keeps taking the block's shared-cursor write,
    which lands at its frozen length: its positions' rows stay as they
    were, bit for bit, in every layer."""
    server = SlotServer(prepared, tcfg, **ENGINE)
    rng = np.random.default_rng(6)
    short, long = _request(rng, 6, 3), _request(rng, 9, 24)
    server.submit(short)
    server.submit(long)
    while short.id not in server._done:
        server.step()
    slot = server._done[short.id].trace["attrs"]["slot"]
    n = len(short.prompt) + 3 - 1
    before = [server.slot_latent_rows(i)["rows"][slot, :n] for i in range(3)]
    done = server.run_until_drained()
    for i in range(3):
        after = server.slot_latent_rows(i)
        assert int(after["length"][slot]) == n
        assert np.array_equal(after["rows"][slot, :n], before[i])
    _check(long, done[long.id])


def test_absorbed_and_expanded_forms_agree(prepared, tcfg):
    """``transformer.apply`` (the expanded form: k_nope and v made from the
    latent, attention as any) against a block through the cache (the
    absorbed form: q through W_kvb^K^T, scores against the rows
    themselves), prefill and then a further block on the filled cache."""
    rng = np.random.default_rng(8)
    toks = jnp.asarray(rng.integers(3, CFG["vocab_size"], (2, 21),
                                    dtype=np.int32))
    want, _ = transformer.apply(prepared.params, toks, tcfg)
    cache = init_cache(tcfg, 2, 32)
    assert cache.k.shape[0] == 0 and cache.latent.shape == (3, 2, 32, 40)
    first, cache = _forward_with_cache(
        prepared.params, tcfg, toks[:, :13], cache, prefill=True,
        all_logits=True)
    rest, cache, picks = _forward_with_cache(
        prepared.params, tcfg, toks[:, 13:], cache, all_logits=True,
        routes=True)
    got = jnp.concatenate([first, rest], axis=1)
    assert picks.shape == (2, 2, 8, 4) and int(cache.length) == 21
    assert float(jnp.abs(got - want).max()) < 1e-4
    full = np.asarray(reference.served_logits(
        CFG, SEED, "float32", np.asarray(toks), np.tile(np.arange(21), (2, 1))))
    assert np.abs(np.asarray(want) - full).max() < 1e-4


def test_init_builds_a_stack_of_mixers_and_a_list_of_mlps(tcfg):
    params = transformer.init(jax.random.PRNGKey(0), tcfg)
    axes = transformer.param_logical_axes(tcfg)
    assert set(params["layers"]) == {"latent", "dense", "routed"} \
        == set(axes["layers"])
    for name, leaf in params["layers"]["latent"].items():
        assert leaf.shape[0] == 3
        assert len(axes["layers"]["latent"][name]) == leaf.ndim, name
    assert [len(params["layers"][f]) for f in ("dense", "routed")] == [1, 2]
    for form in ("dense", "routed"):
        for lp, ax in zip(params["layers"][form], axes["layers"][form]):
            assert set(lp) == set(ax)
            assert all(len(ax[k]) == lp[k].ndim for k in lp)
    routed = params["layers"]["routed"][0]
    assert routed["router"].dtype == routed["router_bias"].dtype == jnp.float32
    assert routed["we_gu"].shape == (16, 64, 48)
    # a cast to bfloat16 activations leaves the router what it is, and
    # nothing of an expert layer is held twice
    low = dataclasses.replace(tcfg, dtype=jnp.bfloat16)
    prep = prepare_decode(params, low)
    assert prep.fused == {}
    assert prep.params["layers"]["routed"][1]["router"].dtype == jnp.float32
    assert prep.params["layers"]["routed"][1]["we_gu"].dtype == jnp.bfloat16
    with pytest.raises(ValueError, match="mlp_kinds must name"):
        transformer.TransformerConfig(n_layers=2, mlp_kinds=("dense",) * 2)
    with pytest.raises(ValueError, match="latent layers need"):
        transformer.TransformerConfig(n_layers=1, layer_kinds=("latent",))


# ------------------------------------------------------- the expert layer

def _layer_inputs(seed, t=24, d=64, e=16, f=24):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return dict(
        x=jax.random.normal(ks[0], (t, d)),
        router=jax.random.normal(ks[1], (d, e)) * d ** -0.5,
        bias=0.3 * jax.random.normal(ks[2], (e,)),
        w_gu=jax.random.normal(ks[3], (e, d, 2 * f)) * d ** -0.5,
        w_down=jax.random.normal(ks[4], (e, f, d)) * f ** -0.5)


def _experts_one_by_one(x, chosen, w, w_gu, w_down):
    """sum_sel w_e SwiGLU_e(x): every expert over every token."""
    f = w_down.shape[1]
    y = jnp.zeros_like(x)
    for e in range(w_gu.shape[0]):
        gu = x @ w_gu[e]
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)
        y = y + w_e[:, None] * ((jax.nn.silu(gu[:, :f]) * gu[:, f:])
                                @ w_down[e])
    return y


def test_the_bias_selects_and_does_not_weigh():
    a = _layer_inputs(0)
    chosen, w = routed_experts.route(a["x"], a["router"], a["bias"], top_k=4,
                                     scale=2.5)
    s = jax.nn.sigmoid(a["x"] @ a["router"])
    _, by_bias = jax.lax.top_k(s + a["bias"], 4)
    _, by_score = jax.lax.top_k(s, 4)
    assert (np.sort(chosen, -1) == np.sort(by_bias, -1)).all()
    # the bias is large enough to move the selection somewhere
    assert (np.sort(by_bias, -1) != np.sort(by_score, -1)).any()
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    want = picked / picked.sum(-1, keepdims=True) * 2.5
    assert float(jnp.abs(w - want).max()) < 1e-6
    assert float(jnp.abs(w.sum(-1) - 2.5).max()) < 1e-5


def test_no_token_is_dropped_when_every_token_picks_the_same_experts():
    a = _layer_inputs(1)
    # a bias that decides everything: every token picks experts 3, 7, 9, 12
    bias = jnp.zeros((16,)).at[jnp.array([3, 7, 9, 12])].set(10.0)
    chosen, w = routed_experts.route(a["x"], a["router"], bias, top_k=4,
                                     scale=2.5)
    assert (np.sort(chosen, -1) == np.array([3, 7, 9, 12])).all()
    load = routed_experts.expert_load(chosen, n_experts=16)
    assert load.tolist() == [24 if e in (3, 7, 9, 12) else 0
                             for e in range(16)]
    got = routed_experts.routed_ffn(a["x"], chosen, w, a["w_gu"], a["w_down"],
                                    held=(0, 16))
    want = _experts_one_by_one(a["x"], chosen, w, a["w_gu"], a["w_down"])
    assert float(jnp.abs(got - want).max()) < 1e-5
    assert float(jnp.abs(want).min(axis=-1).max()) > 0    # every token served


@pytest.mark.parametrize("shares", [1, 2, 8])
def test_the_shares_of_the_experts_add_up_to_the_whole_layer(shares):
    """With the experts held as two halves and as eight eighths, the parts
    of the result that the shares give, the shared expert counted once, add
    up to the uncut reference's layer."""
    key = weights.seed_key(SEED)
    lw = weights.layer(key, CFG, 1, "routed", jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(2), (20, CFG["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        chosen, w, _ = reference.route(CFG, x, lw)
        want = reference.routed_experts(CFG, x, lw, chosen, w) \
            + reference.shared_expert(CFG, x, lw)
    count = CFG["n_routed_experts"] // shares
    total = 0.0
    for i in range(shares):
        held = (i * count, count)
        part = weights.layer(key, CFG, 1, "routed", jnp.float32, held)
        _, mlp = driver.program_layer(CFG, part, "routed")
        cfg = driver.transformer_config(
            dict(CFG, experts_held=list(held)), MAX_LEN)
        assert mlp["we_gu"].shape[0] == count == cfg.experts_held[1]
        # the layer's own share: its experts' part, and (once) the shared
        # expert every chip computes alike
        out, picks = transformer._mlp(cfg, x[None], mlp)
        shared = np.asarray(reference.shared_expert(CFG, x, lw))
        total = total + np.asarray(out[0]) - (shared if i else 0.0)
        assert (np.sort(picks[0], -1) == np.sort(chosen, -1)).all()
    assert np.abs(total - np.asarray(want)).max() < 1e-5


def test_the_counters_of_the_experts(prepared, tcfg):
    """The device's counts a block: the experts the live rows routed to
    are among those any row routed to are among those held; the busiest
    expert's load and the mean; on the bookkeep span and in stats()."""
    server = SlotServer(prepared, tcfg, **ENGINE)
    rng = np.random.default_rng(9)
    for n, m in ((4, 12), (11, 8)):
        server.submit(_request(rng, n, m))
    server.run_until_drained()
    e = server.stats()["experts"]
    steps = e["held_steps"] // (16 * 2)
    assert steps % 4 == 0 and steps >= 12
    assert 0 < e["touched"] <= e["read"] <= e["held_steps"]
    # two live rows of four: each step and layer touches 4 to 8 experts
    assert 4 * 2 * 8 <= e["touched"] <= 8 * 2 * steps
    assert 1 <= e["tokens_max"] <= 2
    assert e["tokens_mean"] == pytest.approx(20 * 2 * 4 / (16 * 2 * steps))


def _recording_phase(seen):
    """``serving.phase`` stood in for: each span's name and counts."""
    @contextlib.contextmanager
    def phase(name, **counts):
        span = types.SimpleNamespace(counts=dict(counts))
        span.set_metadata = span.counts.update
        seen.append((name, span.counts))
        yield span
    return phase


@pytest.mark.parametrize("engages", [False, True])
def test_the_decode_kernel_serves_the_reference(prepared, tcfg, monkeypatch,
                                                engages):
    """With the gate forced open (the kernel interpreted) and shut (the
    CPU's ``ragged_dot``), the served tokens are the reference's, and the
    decode block's counts of the experts read reach the bookkeep spans and
    ``stats()["experts"]`` alike."""
    import importlib

    generate = importlib.import_module("tony_tpu.models.generate")
    monkeypatch.setattr(generate, "expert_kernel_engages",
                        lambda *a: engages)
    seen = []
    monkeypatch.setattr(serving, "phase", _recording_phase(seen))
    # the gate is read when `_decode_block` is traced: the forced one takes
    # a block size no other test compiles
    server = SlotServer(prepared, tcfg,
                        **dict(ENGINE, block_size=3 if engages else 4))
    rng = np.random.default_rng(9)
    reqs = [_request(rng, n, m) for n, m in ((4, 12), (11, 8))]
    for r in reqs:
        server.submit(r)
    done = server.run_until_drained()
    for r in reqs:
        _check(r, done[r.id])
    e = server.stats()["experts"]
    spans = [c for name, c in seen
             if name == "serve.step.bookkeep" and "experts_read" in c]
    assert spans and sum(c["experts_read"] for c in spans) == e["read"] > 0
    assert "tiles" not in e
    # the CPU's decode gate is shut: the latent rows' write is the
    # update, and no row is counted as written in place
    assert server.stats()["latent_rows_in_place"] == 0
    assert all(c["latent_rows_in_place"] == 0 for c in spans)


@pytest.mark.parametrize("backend, l_new, sharded, m_cap, engages", [
    ("cpu", 1, False, 4096, False),     # the CPU keeps the einsum
    ("tpu", 1, False, 4096, True),      # a decode step over a long ring
    ("tpu", 8, False, 4096, False),     # a block of positions: the einsum
    ("tpu", 1, True, 4096, False),      # under a mesh: a shard_map away
    ("tpu", 1, False, 2048, False),     # a short ring: the einsum wins
])
def test_who_runs_the_latent_decode_kernel(prepared, tcfg, monkeypatch,
                                           backend, l_new, sharded, m_cap,
                                           engages):
    """The one gate on what the caller observes (`latent_kernel_engages`:
    ``decode_kernel_engages`` on the backend and the ring's length, one
    new token a row, no mesh), and what it traces: `_latent_attention`
    runs ``flash_decode_latent`` on the whole stack or the einsum on the
    layer's rows, and `_forward_with_cache` calls ``write_latent_rows``
    and ``flash_decode_latent`` once a latent layer where it opens, each
    one traced pallas_call (the layer is an operand), and neither where
    it does not: the write and the read cannot disagree."""
    import importlib

    generate = importlib.import_module("tony_tpu.models.generate")
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert generate.decode_kernel_engages(tcfg, m_cap) is (
        backend == "tpu" and m_cap >= 4096)
    assert generate.latent_kernel_engages(tcfg, l_new, m_cap,
                                          sharded) is engages
    cache = init_cache(tcfg, 2, m_cap)
    q = jnp.zeros((2, l_new, 4, 40), jnp.float32)
    lengths = jnp.zeros((2,), jnp.int32) + (m_cap - l_new)
    jaxpr = str(jax.make_jaxpr(
        lambda q, lat: generate._latent_attention(
            tcfg, q, lat, lengths, l_new, jnp.zeros((2,), jnp.int32),
            layer_idx=1, kernel=engages))(q, cache.latent))
    assert jaxpr.count("pallas_call") == (1 if engages else 0)
    if sharded:
        return      # SlotServer refuses a latent cache over a mesh
    # (the routed layers' kernels, which their own gate opens, shut here)
    monkeypatch.setattr(generate, "expert_kernel_engages", lambda *a: False)
    jaxpr = str(jax.make_jaxpr(
        lambda p, t, c: _forward_with_cache(p, tcfg, t, c))(
            prepared.params, jnp.zeros((2, l_new), jnp.int32), cache))
    assert jaxpr.count("name=flash_decode_latent") == (3 if engages else 0)
    assert jaxpr.count("name=write_latent_rows") == (3 if engages else 0)
    assert jaxpr.count("pallas_call") == (2 if engages else 0)
    assert ("dynamic_update_slice" in jaxpr) is not engages


def test_the_latent_decode_kernel_serves_the_reference(prepared, tcfg,
                                                       monkeypatch):
    """With the gate forced open (the kernels interpreted, the read in
    blocks of 16 positions of the 64-position rings), the served tokens
    are the reference's; ``kv_blocks_read`` on the bookkeep spans and on
    ``SlotServer`` is ``live_kv_blocks`` of each processed block in that
    unit (its final lengths, the offsets it was dispatched with, the rows
    that emitted in it), below ``kv_blocks_ring``, for blocks in which a
    row stops mid-block among them; and ``latent_rows_in_place`` is every
    row of every latent layer at every step of those blocks."""
    import importlib

    from tony_tpu.ops import decode_attention

    generate = importlib.import_module("tony_tpu.models.generate")
    for module in (generate, serving):
        monkeypatch.setattr(module, "decode_kernel_engages",
                            lambda *a: True)
    for module in (decode_attention, serving):
        monkeypatch.setattr(module, "kv_block_k", lambda *a: 16)
    seen = []
    monkeypatch.setattr(serving, "phase", _recording_phase(seen))
    # the gate is read when `_decode_block` is traced: a block size no
    # other test compiles
    block = 5
    server = SlotServer(prepared, tcfg, **dict(ENGINE, block_size=block))
    blocks = []
    count = server._count_kv_blocks

    def counted(rec, lengths, active):
        before = server.kv_blocks_read
        expect = np.array(server._expect_len)
        count(rec, lengths, active)
        blocks.append((np.array(rec["offsets"]), np.array(lengths),
                       np.array(active), expect,
                       server.kv_blocks_read - before))

    monkeypatch.setattr(server, "_count_kv_blocks", counted)
    rng = np.random.default_rng(12)
    reqs = [_request(rng, n, m) for n, m in ((4, 12), (11, 8), (20, 3))]
    for r in reqs:
        server.submit(r)
    done = server.run_until_drained()
    for r in reqs:
        _check(r, done[r.id])
    mid_block = 0
    for offsets, lengths, active, expect, read in blocks:
        emitted = lengths > expect
        want = decode_attention.live_kv_blocks(
            offsets, lengths, active | emitted, block_k=16, m_cap=MAX_LEN)[1]
        assert read == int(want.sum())
        mid_block += int((emitted & ~active
                          & (lengths - expect < block)).any())
    assert mid_block >= 1
    spans = [c for name, c in seen if name == "serve.step.bookkeep"]
    assert sum(c["kv_blocks_read"] for c in spans) == server.kv_blocks_read
    assert 0 < server.kv_blocks_read < server.kv_blocks_ring
    assert server.kv_blocks_ring == len(blocks) * ENGINE["slots"] * 4
    in_place = len(blocks) * ENGINE["slots"] * 3 * block
    assert server.latent_rows_in_place == in_place \
        == sum(c["latent_rows_in_place"] for c in spans) \
        == server.stats()["latent_rows_in_place"]


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
def test_what_is_not_built_for_a_latent_cache_is_refused(prepared, tcfg,
                                                         kwargs, message):
    with pytest.raises(ValueError, match="latent slot cache.*" + message):
        SlotServer(prepared, tcfg, **ENGINE, **kwargs)


@pytest.mark.parametrize("kwargs, message", [
    (dict(prefix_cache_blocks=4), "cannot use prefix_cache_blocks"),
    (dict(paged=True), "cannot use paged=True"),
    (dict(spec_gamma=2), "cannot use a draft / spec_gamma"),
    (dict(weight_dtype="int8"), "cannot use weight_dtype='int8'"),
    (dict(mesh="a mesh"), "cannot use a mesh"),
], ids=lambda v: v if isinstance(v, str) else next(iter(v)))
def test_what_is_not_built_for_an_expert_layer_is_refused(kwargs, message):
    """Routed layers over plain full attention: the refusal is the expert
    layer's own."""
    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=16, n_layers=2, n_heads=2, n_kv_heads=2,
        d_ff=32, layer_kinds=("full", "full"), mlp_kinds=("dense", "routed"),
        moe_experts=4, moe_top_k=2, moe_ff=8, dtype=jnp.float32)
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="routed expert layers.*" + message):
        SlotServer(params, cfg, **ENGINE, **kwargs)
    server = SlotServer(params, cfg, **ENGINE)      # and serves without
    server.submit(Request(prompt=[1, 2, 3], max_new_tokens=5, routes=True))
    (comp,) = server.run_until_drained().values()
    assert len(comp.tokens) == 5 and comp.routes.shape == (7, 1, 2)


def test_the_other_paths_refuse_the_kind_by_name(prepared, tcfg):
    from tony_tpu.parallel.mesh import single_device_mesh
    from tony_tpu.train.step import create_train_step

    with pytest.raises(ValueError, match="'latent' layers cannot be trained"):
        create_train_step(tcfg, single_device_mesh())
    with pytest.raises(ValueError, match="weight_dtype='int8' is not"):
        prepare_decode(prepared.params, tcfg, weight_dtype="int8")
    with pytest.raises(ValueError, match="not implemented for latent"):
        init_cache(tcfg, 2, 16, kv_dtype="int8")


# ------------------ the other configurations' programs are the parent's

def _program_texts(cfg):
    """`_decode_block` and `_prefill_batch` (1 and 2 rows) of ``cfg``,
    lowered at 4 slots of 64 positions: {program: StableHLO text}."""
    prep = prepare_decode(transformer.init(jax.random.PRNGKey(0), cfg), cfg)
    s = 4
    cache = init_cache(cfg, s, 64)._replace(length=jnp.zeros((s,), jnp.int32))
    vec = lambda dt, n=s: jnp.zeros((n,), dt)
    out = {"_decode_block": serving._decode_block.lower(
        prep.params, prep.fused, cache, vec(jnp.int32), vec(bool),
        vec(jnp.int32), vec(jnp.int32), jnp.int32(0), vec(jnp.float32),
        vec(jnp.int32), jax.random.PRNGKey(0), cfg=cfg, block=4,
        stop_tokens=(2,), pad_id=0, top_k=0, per_row_topk=False,
        weight_dtype="native", build_fused=False, all_greedy=True, lp_k=0,
        shardings=None).as_text()}
    for k in (1, 2):
        out[f"_prefill_batch[{k}]"] = serving._prefill_batch.lower(
            prep.params, cache, vec(jnp.int32), vec(bool), vec(jnp.int32),
            vec(jnp.int32), vec(jnp.float32), vec(jnp.int32),
            jnp.zeros((k, 8), jnp.int32), *(vec(jnp.int32, k),) * 6,
            vec(jnp.float32, k), vec(jnp.int32, k), vec(bool, k), cfg=cfg,
            shardings=None).as_text()
    return out


def _bf16(cfg):
    return dataclasses.replace(cfg, dtype=jnp.bfloat16,
                               param_dtype=jnp.bfloat16)


def _hybrid_program_texts():
    hybrid = lib.load("drivers/serve_hybrid.py")
    cfg_file = lib.read_json(
        BENCH / "tests/fixture_hybrid/benchmark/configs/tiny-hybrid.json")
    return _program_texts(_bf16(hybrid.transformer_config(cfg_file, 64)))


def _latent_routed_program_texts():
    return _program_texts(_bf16(driver.transformer_config(CFG, 64)))


GOLDEN = {name: json.loads((REPO / f"tests/fixtures/{name}_stablehlo.json")
                           .read_text())
          for name in ("uniform", "hybrid", "latent_routed")}


@pytest.mark.parametrize("config, program", [
    (name, program) for name in GOLDEN
    for program in sorted(GOLDEN[name]["sha256"])])
def test_the_other_configs_lower_to_the_parents_stablehlo(config, program):
    """A uniform (Mistral-shaped), a hybrid (gated-delta-rule) and a
    latent-routed config lower `_decode_block` and `_prefill_batch` to the
    text the trees before them lowered them to: the measured cells load
    the programs they loaded (the fixtures say how the digests were taken;
    another jax prints another text)."""
    if jax.__version__ != GOLDEN[config]["jax"]:
        pytest.skip(f"digests are of jax {GOLDEN[config]['jax']}")
    if config == "uniform":
        from test_hybrid_serving import _uniform_program_texts as texts
    else:
        texts = {"hybrid": _hybrid_program_texts,
                 "latent_routed": _latent_routed_program_texts}[config]
    assert hashlib.sha256(texts()[program].encode()).hexdigest() \
        == GOLDEN[config]["sha256"][program]
