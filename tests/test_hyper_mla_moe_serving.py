"""A latent-attention decoder with routed experts, manifold-constrained
hyper-connections and YaRN rope (``hc_mult`` 4 streams, ``rope_scaling``
"yarn", ``layer_kinds`` "latent", ``mlp_kinds`` two "dense" then two
"routed": the ``xing4_0`` block) through the normal serving path, on the
CPU at a tiny size: hidden 64, 4 heads of q/k 24 (nope 16 + rope 8) and v
16, 16 experts of 24 wide, 4 a token, one shared, YaRN with the published
factor 64 over 4096 positions, 20 Sinkhorn alternations.

The program is held to the benchmark's plain reference
(``benchmark/reference/hyper_mla_moe_decoder.py``: float32, the expanded
attention, a loop over the experts, the streams a sequence at a time, no
cache, nothing of ``tony_tpu``) on seeded weights from
``benchmark/weights/hyper_mla_moe_decoder.py``, through the mapping the
benchmark's driver makes: prefill and decode at logits level, and the
planted hyper-connection faults fail that comparison. Also here: the
Sinkhorn projection's sums, YaRN's table by hand, the coefficients against
the reference's, the admission's walk over a long latent ring, the
``hyper_rows`` counter and the refusals.
"""

import contextlib
import dataclasses
import math
import sys
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.models import serving, transformer
from tony_tpu.models.generate import prepare_decode
from tony_tpu.models.serving import Request, SlotServer

REPO = Path(__file__).resolve().parent.parent
BENCH = REPO / "benchmark"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import lib  # noqa: E402  (benchmark/lib.py)

driver = lib.load("drivers/serve_hyper_mla_moe.py")
reference = lib.load("reference/hyper_mla_moe_decoder.py")
weights = lib.load("weights/hyper_mla_moe_decoder.py")

CFG = lib.read_json(BENCH / "tests/fixture_hyper_mla_moe/benchmark/configs/"
                    "tiny-hyper-mla-moe.json")
PUBLISHED = lib.read_json(BENCH / "configs/xing4.0-29b-a4b-serve.json")
SEED = 2 ** 31 + 7
CHUNK, MAX_LEN = 8, 64
ENGINE = dict(slots=4, max_len=MAX_LEN, block_size=4, prefill_chunk=CHUNK)
# float32 on both sides, the same mathematics in another order (the
# absorbed attention over a ring against the expanded one over the whole
# sequence, sorted assignments against a loop over the experts) through 4
# layers and 8 hyper-connection sites: the logprobs agree to 5e-6. Every
# stream mix the identity, or one Sinkhorn alternation, reads 0.1 and up
LOGPROB_TOL = 1e-4
CASES = ((1, 20), (5, 14), (CHUNK + 1, 12), (3 * CHUNK + 2, 20))


@pytest.fixture(scope="module")
def tcfg():
    return driver.transformer_config(CFG, MAX_LEN)


@pytest.fixture(scope="module")
def prepared(tcfg):
    return prepare_decode(
        driver.program_params(CFG, SEED, jnp.float32), tcfg)


def _request(rng, n_prompt, max_new=9, **kw):
    return Request(prompt=rng.integers(3, CFG["vocab_size"], n_prompt,
                                       dtype=np.int32),
                   max_new_tokens=max_new, logprobs=8, **kw)


def _recording_phase(seen):
    """``serving.phase`` stood in for: each span's name and counts."""
    @contextlib.contextmanager
    def phase(name, **counts):
        span = types.SimpleNamespace(counts=dict(counts))
        span.set_metadata = span.counts.update
        seen.append((name, span.counts))
        yield span
    return phase


@pytest.fixture(scope="module")
def served(prepared, tcfg):
    """Four requests of one to four chunks through one server, its
    bookkeep spans recorded: (server, requests, completions, spans)."""
    seen = []
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(serving, "phase", _recording_phase(seen))
        server = SlotServer(prepared, tcfg, **ENGINE)
        rng = np.random.default_rng(3)
        reqs = [_request(rng, n, m) for n, m in CASES]
        for r in reqs:
            server.submit(r)
        done = server.run_until_drained()
    spans = [c for name, c in seen if name == "serve.step.bookkeep"]
    return server, reqs, [done[r.id] for r in reqs], spans


def _batch(reqs, comps):
    """The requests as the reference's arguments: tokens [n, MAX_LEN]
    (prompt, served tokens, padding) and positions [n, P] (where each
    served token was produced; padded with the last)."""
    p = max(len(c.tokens) for c in comps)
    toks = np.zeros((len(reqs), MAX_LEN), np.int32)
    pos = np.zeros((len(reqs), p), np.int32)
    for i, (r, c) in enumerate(zip(reqs, comps)):
        full = np.concatenate([r.prompt, np.asarray(c.tokens, np.int32)])
        toks[i, :full.size] = full
        at = len(r.prompt) - 1 + np.arange(len(c.tokens))
        pos[i] = np.concatenate([at, np.full(p - at.size, at[-1])])
    return toks, pos


@pytest.fixture(scope="module")
def reference_logprobs(served):
    """The reference's log-softmax at every position of the served
    requests' sequences, sound and with each planted hyper-connection
    fault: {None or the fault: [n, MAX_LEN, vocab]}."""
    _, reqs, comps, _ = served
    toks, _ = _batch(reqs, comps)
    at = np.tile(np.arange(MAX_LEN), (len(reqs), 1))
    return {fault: np.asarray(jax.nn.log_softmax(reference.served_logits(
        CFG, SEED, "float32", toks, at, fault=fault), axis=-1))
        for fault in (None, "h_res_identity", "sinkhorn_one")}


def _gaps(reqs, comps, want):
    """Each request's largest distance between a served logprob (of the
    token and of the top 8) and the log-softmax ``want`` [n, MAX_LEN,
    vocab] at the position where it was produced, and whether the served
    tokens are that log-softmax's argmax."""
    _, pos = _batch(reqs, comps)
    out = []
    for i, c in enumerate(comps):
        w = want[i, pos[i, :len(c.tokens)]]
        gap = 0.0
        for j, entry in enumerate(c.logprobs):
            ids, values = entry["top"]
            gap = max(gap, abs(entry["logprob"] - w[j, entry["token"]]),
                      float(np.abs(np.asarray(values) - w[j, ids]).max()))
        out.append((gap, bool((w.argmax(-1) == np.asarray(c.tokens)).all())))
    return out


# ------------------------------------------------------ the serving path

def test_the_config_is_the_files(tcfg):
    assert tcfg.hc_mult == 4 and tcfg.hc_sinkhorn_iters == 20
    assert tcfg.rope_scaling == ("yarn", 64.0, 32.0, 1.0, 4096, 1.0, 1.0)
    assert tcfg.mlp_kinds == ("dense", "dense", "routed", "routed")
    assert tcfg.layer_kinds == ("latent",) * 4
    pub = driver.transformer_config(PUBLISHED, 12288)
    assert (pub.d_model, pub.n_heads, pub.lat_q_rank, pub.lat_kv_rank,
            pub.lat_nope_dim, pub.lat_rope_dim, pub.lat_v_dim, pub.moe_experts,
            pub.moe_ff, pub.moe_top_k, pub.d_ff, pub.vocab_size,
            pub.hc_mult, pub.hc_sinkhorn_iters) == (
        3584, 32, 768, 512, 128, 64, 128, 64, 1024, 4, 9216, 131072, 4, 20)
    assert pub.mlp_kinds == ("dense",) * 2 + ("routed",) * 4


def test_slot_server_agrees_with_the_reference(served, reference_logprobs):
    """Prefill (one to four chunks) then decode through the latent ring,
    four requests in one server, against the reference's full forward at
    logits level."""
    _, reqs, comps, _ = served
    for r, c in zip(reqs, comps):
        assert len(c.tokens) == r.max_new_tokens
    for gap, same in _gaps(reqs, comps, reference_logprobs[None]):
        assert same and gap < LOGPROB_TOL


@pytest.mark.parametrize("fault", ["h_res_identity", "sinkhorn_one"])
def test_the_planted_hyper_faults_fail(served, reference_logprobs, fault):
    """The reference with every stream mix the identity (the plain
    residual repeated n times), or with one Sinkhorn alternation, is far
    from what the program served: the comparison sees both."""
    _, reqs, comps, _ = served
    gaps = _gaps(reqs, comps, reference_logprobs[fault])
    assert min(gap for gap, _ in gaps) > 100 * LOGPROB_TOL


@pytest.fixture(scope="module")
def unserved(served, prepared, tcfg):
    """The two forwards beside the slot server's: `transformer.apply`'s
    log-softmax over the last request's sequence (the training forward:
    the expanded latent attention, the embedding into the streams and
    their sum before the final norm in `_hidden`), and `generate()`'s
    tokens for the third request (the whole prompt at once, then lockstep
    decode through `_forward_with_cache`'s streams)."""
    from tony_tpu.models.generate import generate

    _, reqs, comps, _ = served
    toks, _ = _batch(reqs, comps)
    with jax.default_matmul_precision("highest"):
        logits, _ = transformer.apply(prepared.params,
                                      jnp.asarray(toks[-1:]), tcfg)
    tokens = generate(prepared, tcfg, jnp.asarray(reqs[2].prompt)[None],
                      len(comps[2].tokens), max_len=MAX_LEN)
    return np.asarray(jax.nn.log_softmax(logits[0], axis=-1)), tokens


def test_the_full_forward_is_the_reference(reference_logprobs, unserved):
    np.testing.assert_allclose(unserved[0], reference_logprobs[None][-1],
                               atol=2e-4)


def test_lockstep_generate_serves_the_same_tokens(served, unserved):
    _, _, comps, _ = served
    assert np.asarray(unserved[1][0]).tolist() == list(comps[2].tokens)


@pytest.fixture(scope="module")
def walked(served, prepared, tcfg):
    """The served requests again, with the latent scores' budget at
    nothing: every chunk round takes the latent attention a row at a time
    over the live blocks of 4 positions (`serving._rows_walk`)."""
    _, reqs, _, _ = served
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(serving, "LATENT_SCORES_BYTES", 0)
        mp.setattr(serving, "kv_block_k", lambda *a: 4)
        # another static config: the budget is read when `_prefill_batch`
        # is traced
        cfg = dataclasses.replace(tcfg, max_seq_len=MAX_LEN + 1)
        server = SlotServer(prepared, cfg, **ENGINE)
        again = [Request(prompt=r.prompt, max_new_tokens=r.max_new_tokens,
                         logprobs=8) for r in reqs]
        for r in again:
            server.submit(r)
        done = server.run_until_drained()
    return [done[r.id] for r in again]


def test_the_admission_walks_a_long_latent_ring(served, walked):
    """The walk over the live blocks (online softmax) serves the same
    tokens and logprobs as the einsum over the whole ring."""
    _, _, comps, _ = served
    for w, c in zip(walked, comps):
        assert w.tokens == c.tokens
        np.testing.assert_allclose([e["logprob"] for e in w.logprobs],
                                   [e["logprob"] for e in c.logprobs],
                                   atol=1e-5)


# ---------------------------------------------- the block's new equations

def _streams(seed=0, t=6, d=64):
    return jax.random.normal(jax.random.PRNGKey(seed), (1, t, 4, d))


def _site(layer, cfg=CFG):
    hw = weights.hyper(weights.seed_key(SEED), cfg, layer)
    return {"phi": hw["hc_phi"], "bias": hw["hc_bias"],
            "alpha": hw["hc_alpha"]}


@pytest.mark.parametrize("site", [0, 1])
def test_the_coefficients_and_mixes_are_the_references(tcfg, site):
    """`hyper_coeffs`, `hyper_in` and `hyper_out` against the reference's
    coefficients and its X <- H_res X + H_post^T y, in float32."""
    x = _streams(site)
    y = jax.random.normal(jax.random.PRNGKey(9), x.shape[:2] + (64,))
    hc = _site(2)
    lw = {"hc_phi": hc["phi"], "hc_bias": hc["bias"], "hc_alpha": hc["alpha"]}
    with jax.default_matmul_precision("highest"):
        pre, post, res = transformer.hyper_coeffs(tcfg, x, hc, site)
        want = reference.coefficients(CFG, x[0], lw, site)
        for got, w in zip((pre, post, res), want):
            np.testing.assert_allclose(got[0], w, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(
            transformer.hyper_in(pre, x)[0],
            jnp.einsum("ln,lnc->lc", want[0], x[0]), rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(
            transformer.hyper_out(res, post, x, y)[0],
            jnp.einsum("lij,ljc->lic", want[2], x[0])
            + want[1][..., None] * y[0][:, None], rtol=1e-5, atol=1e-5)


def test_the_sinkhorn_projection_is_doubly_stochastic(tcfg):
    """Twenty alternations of positive 4 x 4 mixes whose logits spread by
    a unit or so leave rows and columns summing to 1 within a few hc_eps
    (1e-6). The drawn weights' mixes spread by several units (neither the
    identity nor uniform): their rows sum to 1 within that, the median
    token's columns within 1e-4, and one alternation leaves those columns
    a tenth and more off."""
    m = jnp.exp(0.5 * jax.random.normal(jax.random.PRNGKey(1), (4096, 4, 4)))
    res = transformer.sinkhorn(m, 20, CFG["hc_eps"])
    assert np.abs(res.sum(-1) - 1).max() < 5 * CFG["hc_eps"]
    assert np.abs(res.sum(-2) - 1).max() < 5 * CFG["hc_eps"]
    for layer in range(CFG["num_hidden_layers"]):
        for site in (0, 1):
            x = _streams(10 * layer + site, t=256) * (1 + layer)
            _, _, res = transformer.hyper_coeffs(tcfg, x, _site(layer), site)
            assert np.abs(res.sum(-1) - 1).max() < 5 * CFG["hc_eps"]
            assert np.median(np.abs(res.sum(-2) - 1)) < 1e-4
            diag = np.diagonal(res, axis1=-2, axis2=-1).mean()
            assert 0.05 < diag < 0.8 and res.max() < 1.0
            one = transformer.hyper_coeffs(
                dataclasses.replace(tcfg, hc_sinkhorn_iters=1), x,
                _site(layer), site)[2]
            assert np.median(np.abs(one.sum(-2) - 1)) > 0.1


def test_the_yarn_frequencies_by_hand():
    """The published rope (64 dims, theta 1e4, factor 64 over 4096,
    beta 32 and 1): low = 10 and high = 23, so pair i turns at
    theta^(-i/32) up to pair 10, 1/64 of it from pair 23, and between
    them the ramp (i - 10) / 13; read off `transformer.rope`'s rotation
    of (1, 0) at position 1. The scores' factor is (0.1 ln 64 + 1)^2 =
    2.0047 in the program and in the reference."""
    sc = driver.transformer_config(PUBLISHED, 12288).rope_scaling
    x = jnp.tile(jnp.array([1.0, 0.0]), 32)[None, None, None]
    out = transformer.rope(x, jnp.ones((1, 1), jnp.int32), 10000.0, sc,
                           interleave=True)[0, 0, 0]
    freq = np.arctan2(out[1::2], out[0::2])
    hand = {0: 1.0, 10: 10000.0 ** (-10 / 32),
            16: 0.01 * (1 - 6 / 13 + 6 / 13 / 64),
            23: 10000.0 ** (-23 / 32) / 64, 31: 10000.0 ** (-31 / 32) / 64}
    for i, want in hand.items():
        assert freq[i] == pytest.approx(want, rel=1e-5), i
    np.testing.assert_allclose(freq, reference.yarn_inv_freq(PUBLISHED),
                               rtol=1e-5)
    factor = (0.1 * math.log(64) + 1) ** 2
    assert factor == pytest.approx(2.0047, abs=1e-4)
    pub = driver.transformer_config(PUBLISHED, 12288)
    assert transformer.latent_scale(pub) == pytest.approx(
        factor / math.sqrt(192), rel=1e-7)
    assert reference.softmax_scale(PUBLISHED) == pytest.approx(
        factor / math.sqrt(192), rel=1e-7)
    # without a rope scaling the latent scale is the plain one
    assert transformer.latent_scale(dataclasses.replace(
        pub, rope_scaling=None)) == 192 ** -0.5


# ------------------------------------------------------ counters, refusals

def test_hyper_rows_are_rows_sites_and_steps(served):
    """Every processed decode block adds slots x 2 sites x 4 layers x its
    4 steps, on its bookkeep span and in ``stats()``."""
    server, _, _, spans = served
    assert server.blocks_dispatched > 0
    want = 4 * 2 * 4 * 4 * server.blocks_dispatched
    assert server.stats()["hyper_rows"] == server.hyper_rows == want
    assert sum(c["hyper_rows"] for c in spans) == want


def test_a_server_without_streams_counts_no_hyper_rows():
    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=16, n_layers=1, n_heads=2, n_kv_heads=2,
        d_ff=32, dtype=jnp.float32)
    server = SlotServer(transformer.init(jax.random.PRNGKey(0), cfg), cfg,
                        **ENGINE)
    server.submit(Request(prompt=np.array([3, 4, 5], np.int32),
                          max_new_tokens=6))
    server.run_until_drained()
    assert server.blocks_dispatched > 0 and server.hyper_rows == 0


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
def test_what_is_not_built_for_the_streams_is_refused(tcfg, prepared, kwargs,
                                                      message):
    """The refusal is the streams' own, by name, before any other's."""
    with pytest.raises(ValueError, match="hyper-connections.*" + message):
        SlotServer(prepared, tcfg, **ENGINE, **kwargs)


def test_training_refuses_the_streams(tcfg):
    from tony_tpu.parallel.mesh import single_device_mesh
    from tony_tpu.train.step import create_train_step

    with pytest.raises(ValueError, match="hyper-connections"):
        create_train_step(tcfg, single_device_mesh())


def test_a_hyper_config_needs_its_layer_pattern():
    with pytest.raises(ValueError, match="hyper-connections need"):
        transformer.TransformerConfig(hc_mult=4)
    with pytest.raises(ValueError, match="hyper-connections need"):
        transformer.TransformerConfig(n_layers=1, layer_kinds=("full",),
                                      hc_mult=4, norm_order="post")
    with pytest.raises(ValueError, match="unsupported rope scaling"):
        transformer.rope(jnp.ones((1, 1, 1, 4)), jnp.ones((1, 1)), 1e4,
                         ("ntk", 2.0, 1.0, 1.0, 16))
