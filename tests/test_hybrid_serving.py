"""A hybrid decoder (gated-delta-rule layers beside full-attention layers,
``TransformerConfig.layer_kinds``) through the normal serving path, on the
CPU at a tiny size: two periods of linear, linear, linear, full; hidden 64,
d_k 8, d_v 16.

The program is held to the benchmark's plain reference
(``benchmark/reference/hybrid_decoder.py``: float32, one scan over the
positions, no cache, nothing of ``tony_tpu``) on seeded weights from
``benchmark/weights/hybrid_decoder.py``, through the mapping the
benchmark's driver makes. Also here: the chunkwise form against the
sequential one, the refusals of what cannot hold a recurrent state yet,
and the uniform (Mistral-shaped) programs' StableHLO against the parent's.
"""

import hashlib
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.models import serving, transformer
from tony_tpu.models.generate import init_cache, prepare_decode
from tony_tpu.models.serving import Request, SlotServer
from tony_tpu.ops.gated_delta import gated_delta_chunk, gated_delta_step

REPO = Path(__file__).resolve().parent.parent
BENCH = REPO / "benchmark"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import lib  # noqa: E402  (benchmark/lib.py)

driver = lib.load("drivers/serve_hybrid.py")
reference = lib.load("reference/hybrid_decoder.py")

CFG = lib.read_json(
    BENCH / "tests/fixture_hybrid/benchmark/configs/tiny-hybrid.json")
SEED = 2 ** 31 + 5
CHUNK, MAX_LEN = 8, 64
ENGINE = dict(slots=4, max_len=MAX_LEN, block_size=4, prefill_chunk=CHUNK)
# float32 on both sides, the same mathematics in another order of
# operations (chunkwise against sequential recurrence, cached against full
# attention, one projection against three) through 8 layers: the logprobs
# agree to 1e-5..1e-4, and to 2.3e-3 where a head's 8-wide q' or k' comes
# out small and its L2 normalisation multiplies an earlier layer's rounding
# by the inverse of its norm (a toy's fault: 96 wide, the norm does not get
# small). A state not carried between chunks, a tail dropped or a mask left
# out reads 3 and up (each planted once: 3.3 to 4.5)
LOGPROB_TOL = 5e-3


@pytest.fixture(scope="module")
def tcfg():
    return driver.transformer_config(CFG, MAX_LEN)


@pytest.fixture(scope="module")
def prepared(tcfg):
    make, key = driver.program_params(CFG, SEED, jnp.float32)
    return prepare_decode(jax.jit(make)(key), tcfg)


def _reference_logprobs(prompt, served):
    """log-softmax of the reference's logits at every served position."""
    toks = np.zeros((1, MAX_LEN), np.int32)
    full = np.concatenate([prompt, np.asarray(served, np.int32)])
    toks[0, :full.size] = full
    positions = (len(prompt) - 1 + np.arange(len(served)))[None]
    logits = reference.served_logits(CFG, SEED, "float32", toks, positions)
    return np.asarray(jax.nn.log_softmax(logits[0], axis=-1))


def _check(req, comp):
    want = _reference_logprobs(req.prompt, comp.tokens)
    assert len(comp.tokens) == req.max_new_tokens
    assert (want.argmax(-1) == np.asarray(comp.tokens)).all()
    for i, entry in enumerate(comp.logprobs):
        ids, values = entry["top"]
        assert abs(entry["logprob"] - want[i, entry["token"]]) < LOGPROB_TOL
        assert np.abs(np.asarray(values) - want[i, ids]).max() < LOGPROB_TOL


def _request(rng, n_prompt, max_new=9):
    return Request(prompt=rng.integers(3, CFG["vocab_size"], n_prompt,
                                       dtype=np.int32),
                   max_new_tokens=max_new, logprobs=8)


# ------------------------------------------------- the recurrence's two forms

def _delta_inputs(seed, b=2, l=37, h=3, dk=8, dv=16):
    rng = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    f = lambda *s: rng.normal(size=s).astype(np.float32)
    return dict(q=unit(f(b, l, h, dk)), k=unit(f(b, l, h, dk)),
                v=f(b, l, h, dv),
                log_alpha=-rng.uniform(1e-3, 2, (b, l, h)).astype(np.float32),
                beta=rng.uniform(0, 2, (b, l, h)).astype(np.float32))


def _sequential(x, state, valid):
    outs = []
    for t in range(x["q"].shape[1]):
        o, state = gated_delta_step(
            *(x[n][:, t] for n in ("q", "k", "v", "log_alpha", "beta")),
            state, active=jnp.asarray(valid[:, t]))
        outs.append(o)
    return jnp.stack(outs, axis=1), state


@pytest.mark.parametrize("chunk", (1, 3, 16))
@pytest.mark.parametrize("case", ("zero_state", "masked_tail", "state0"))
def test_chunked_form_equals_the_sequential_scan(chunk, case):
    x = _delta_inputs(seed=chunk)
    b, l, h, dk = x["q"].shape
    dv = x["v"].shape[-1]
    state = (np.random.default_rng(9).normal(size=(b, h, dk, dv))
             .astype(np.float32) if case == "state0"
             else np.zeros((b, h, dk, dv), np.float32))
    n_valid = np.array([l, 20] if case == "masked_tail" else [l, l])
    valid = np.arange(l)[None, :] < n_valid[:, None]
    want_o, want_s = _sequential(x, jnp.asarray(state), valid)
    got_o, got_s = gated_delta_chunk(
        x["q"], x["k"], x["v"], x["log_alpha"], x["beta"], jnp.asarray(state),
        None if case == "zero_state" else jnp.asarray(valid), chunk=chunk)
    # float32 sums in another order; values are O(1)
    where = valid[..., None, None]
    assert float(jnp.max(jnp.abs(jnp.where(where, got_o - want_o, 0)))) < 2e-5
    assert float(jnp.max(jnp.abs(got_s - want_s))) < 2e-5
    if case == "masked_tail":       # the pad tail left the state alone
        short = {n: a[1:, :20] for n, a in x.items()}
        _, s20 = gated_delta_chunk(
            *(short[n] for n in ("q", "k", "v", "log_alpha", "beta")),
            jnp.asarray(state[1:]), chunk=chunk)
        assert float(jnp.max(jnp.abs(got_s[1:] - s20))) < 2e-5


# ------------------------------------------------- through the slot pool

@pytest.mark.parametrize("n_prompt", (5, CHUNK + 1, 3 * CHUNK + 1, 1),
                         ids=("shorter", "equal", "three_chunks", "one_token"))
def test_slot_server_agrees_with_the_reference(prepared, tcfg, n_prompt):
    """Prefill (n_prompt - 1 tokens: shorter than, equal to and three times
    the chunk) and then decoding through the cache, against the
    reference's full forward."""
    server = SlotServer(prepared, tcfg, stop_tokens=(2,), **ENGINE)
    req = _request(np.random.default_rng(n_prompt), n_prompt)
    server.submit(req)
    comp = server.run_until_drained()[req.id]
    _check(req, comp)
    # what the slot is left holding: the prompt and all that was served but
    # the last token, consumed; the first layer's state is the reference's
    # (float32 on both sides, chunkwise then stepwise against one scan)
    held = server.slot_states()
    slot, n = comp.trace["attrs"]["slot"], n_prompt + len(comp.tokens) - 1
    assert held["length"][slot] == n
    toks = np.zeros((1, MAX_LEN), np.int32)
    toks[0, :n] = np.concatenate([req.prompt, comp.tokens])[:n]
    want = np.asarray(reference.served_states(CFG, SEED, "float32", toks, [n]))
    assert np.abs(held["state"][slot] - want[0]).max() \
        < 1e-4 * np.abs(want).max()
    assert 0 < server.state_rows <= len(comp.tokens)


@pytest.mark.parametrize("predictive", (False, True), ids=("eos", "open_loop"))
def test_a_burst_of_two_lengths_and_a_slot_reused(prepared, tcfg, predictive):
    """Two requests of different lengths admitted in one burst (one
    `_prefill_batch` a chunk round, the shorter row idle in the later
    rounds), then more requests than slots: a slot is reused after a
    completion and its new occupant starts from a zero state."""
    server = SlotServer(prepared, tcfg,
                        stop_tokens=() if predictive else (2,),
                        **{**ENGINE, "slots": 2})
    rng = np.random.default_rng(3)
    reqs = [_request(rng, n, max_new) for n, max_new in
            ((4, 6), (2 * CHUNK + 3, 11), (7, 5), (CHUNK + 2, 8), (3, 7))]
    for req in reqs[:2]:
        server.submit(req)
    server.step()
    assert server.admission_dispatches == 3       # rounds of the longer one
    for req in reqs[2:]:
        server.submit(req)
    done = server.run_until_drained()
    for req in reqs:
        _check(req, done[req.id])
    assert server.stats()["recurrent_state"]["rows_advanced"] \
        == server.state_rows > 0


def test_a_frozen_row_keeps_its_state_while_others_decode(prepared, tcfg):
    """`_decode_block` with row 1 inactive for a whole turn of the ring
    while rows 0 and 2 decode: row 1's state and convolution tail come back
    bit for bit, and, its K/V ring put back as it was (an idle row's K/V
    takes the shared-cursor garbage write by design), its later logprobs
    are those of a row that never waited."""
    slots, block = ENGINE["slots"], ENGINE["block_size"]
    server = SlotServer(prepared, tcfg, stop_tokens=(2,), **ENGINE)
    rng = np.random.default_rng(11)
    for n in (6, 11, 9):
        server.submit(_request(rng, n, max_new=40))
    server._admit()             # prefill only: no decode block yet
    cache, cursor = server._cache, server._cursor
    assert float(jnp.abs(cache.state[:, :3]).max()) > 0

    def run(cache, active, cursor, n_blocks):
        tokens, out = server._d_tokens, []
        for _ in range(n_blocks):
            cache, tokens, active, packed = serving._decode_block(
                server._params, server._fused, jax.tree.map(jnp.copy, cache),
                tokens, active, server._d_target, server._d_offsets,
                jnp.int32(cursor), server._d_temps, server._d_topks,
                jax.random.PRNGKey(0), cfg=server.cfg, block=block,
                stop_tokens=(), pad_id=0, top_k=0, per_row_topk=False,
                weight_dtype="native", build_fused=False, all_greedy=True,
                lp_k=2)
            cursor = (cursor + block) % MAX_LEN
            out.append(np.asarray(packed))
        return cache, cursor, out

    live = server._d_active
    assert list(np.asarray(live)) == [True, True, True, False]
    _, _, straight = run(cache, live, cursor, 2)
    waited, cursor_w, frozen_blocks = run(
        cache, live.at[1].set(False), cursor, MAX_LEN // block)
    assert cursor_w == cursor                   # one whole turn of the ring
    # the device's own account of whose state a block changed: the last
    # column of its result, from the state before and after
    assert all(list(p[:, -1]) == [1, 1, 1, 0] for p in straight)
    assert list(frozen_blocks[0][:, -1]) == [1, 0, 1, 0]
    assert not any(p[1, -1] or p[3, -1] for p in frozen_blocks)
    for name in ("state", "conv"):
        before, after = getattr(cache, name), getattr(waited, name)
        assert bool(jnp.array_equal(before[:, 1], after[:, 1])), name
        assert not bool(jnp.array_equal(before[:, 0], after[:, 0])), name
    assert int(waited.length[1]) == int(cache.length[1])
    resumed = cache._replace(
        state=cache.state.at[:, 1].set(waited.state[:, 1]),
        conv=cache.conv.at[:, 1].set(waited.conv[:, 1]))
    _, _, later = run(resumed, live, cursor, 2)
    for a, b in zip(straight, later):           # tokens and their logprobs
        assert (a == b).all()


def test_host_count_of_streamed_states_is_the_devices_live_list(
        prepared, tcfg, monkeypatch):
    """``state_rows_read`` on a block in which row 1 stops at its budget
    two steps in: the host's rule (the rows that took every step of the
    block, off the packed lengths) names the rows that the device's
    ``active`` holds going into the block's last step, which is the list
    ``gated_delta_decode`` walks there."""
    from tony_tpu.ops.gated_delta import live_state_rows

    monkeypatch.setattr(serving, "state_kernel_engages", lambda *a: True)
    block = ENGINE["block_size"]
    server = SlotServer(prepared, tcfg, stop_tokens=(), **ENGINE)
    rng = np.random.default_rng(3)
    for n, new in ((6, 40), (11, 3), (9, 40)):
        server.submit(_request(rng, n, max_new=new))
    server._admit()
    assert list(np.asarray(server._d_active)) == [True, True, True, False]
    before = np.asarray(server._cache.length)

    def run(steps):
        return serving._decode_block(
            server._params, server._fused,
            jax.tree.map(jnp.copy, server._cache), server._d_tokens,
            server._d_active, server._d_target, server._d_offsets,
            jnp.int32(server._cursor), server._d_temps, server._d_topks,
            jax.random.PRNGKey(0), cfg=server.cfg, block=steps,
            stop_tokens=(), pad_id=255, top_k=0, per_row_topk=False,
            weight_dtype="native", build_fused=False, all_greedy=True)

    # (pad_id 255: programs of this test's own, so that the next test's
    # block of 3 is still traced under its planted fault)
    _, _, at_last, _ = run(block - 1)       # ``active`` into the last step
    rows, count = live_state_rows(at_last, xp=jnp)
    assert int(count) == 2 and list(np.asarray(rows)[:2]) == [0, 2]
    *_, packed = run(block)
    packed = np.asarray(packed)
    assert list(packed[:, -1]) == [1, 1, 1, 0]      # row 1's state did move
    server._expect_len = before
    server._count_state_rows(packed[:, block])
    assert (server.state_rows_read, server.state_rows_held) == (
        int(count), ENGINE["slots"])


def test_a_mask_left_out_shows_in_the_devices_count(prepared, tcfg,
                                                    monkeypatch):
    """``state_rows`` is read off the state itself, so a decode step that
    advanced a frozen row's state (the fault the mask is there against)
    counts that row: planted here, in a program traced apart (block 3)."""
    import tony_tpu.ops.gated_delta as gd

    server = SlotServer(prepared, tcfg, stop_tokens=(2,), **ENGINE)
    server.submit(_request(np.random.default_rng(4), 6, max_new=20))
    server._admit()
    real = gd.gated_delta_step
    monkeypatch.setattr(gd, "gated_delta_step",
                        lambda *a: real(*a[:6]))    # ``active`` dropped
    *_, packed = serving._decode_block(
        server._params, server._fused, jax.tree.map(jnp.copy, server._cache),
        server._d_tokens, server._d_active, server._d_target,
        server._d_offsets, jnp.int32(server._cursor), server._d_temps,
        server._d_topks, jax.random.PRNGKey(0), cfg=server.cfg, block=3,
        stop_tokens=(), pad_id=0, top_k=0, per_row_topk=False,
        weight_dtype="native", build_fused=False, all_greedy=True)
    assert list(np.asarray(server._d_active)) == [True, False, False, False]
    assert list(np.asarray(packed)[:, -1]) == [1, 1, 1, 1]


# ------------------------------------------------------------- refusals

@pytest.mark.parametrize("kwargs, message", [
    (dict(prefix_cache_blocks=4), "cannot use prefix_cache_blocks"),
    (dict(paged=True), "cannot use paged=True"),
    (dict(draft="draft"), "cannot use a draft / spec_gamma"),
    (dict(kv_dtype="int8"), "cannot use kv_dtype='int8'"),
    (dict(mesh="a mesh"), "cannot use a mesh"),
    (dict(role="decode"), "cannot use role='decode'"),
], ids=lambda v: v if isinstance(v, str) else next(iter(v)))
def test_what_cannot_hold_a_state_yet_is_refused(prepared, tcfg, kwargs,
                                                 message):
    with pytest.raises(ValueError, match="recurrent slot state.*" + message):
        SlotServer(prepared, tcfg, **ENGINE, **kwargs)


def test_spec_gamma_alone_and_int8_weights_are_refused_too(prepared, tcfg):
    with pytest.raises(ValueError, match="cannot use a draft / spec_gamma"):
        SlotServer(prepared, tcfg, spec_gamma=2, **ENGINE)
    with pytest.raises(ValueError, match="weight_dtype='int8' is not"):
        prepare_decode(prepared.params, tcfg, weight_dtype="int8")
    with pytest.raises(ValueError, match="layer_kinds must name"):
        transformer.TransformerConfig(n_layers=2, layer_kinds=("full",))


def test_journal_replay_re_prefills_the_state(prepared, tcfg):
    """A loop crash mid-decode: ``reset()`` replays the journaled request
    from its tokens (prompt + emitted so far through the chunked form), and
    the whole answer still agrees with the reference."""
    server = SlotServer(prepared, tcfg, stop_tokens=(2,), **ENGINE)
    req = _request(np.random.default_rng(5), 13, max_new=14)
    server.submit(req)
    server._chaos_crash_blocks = {3}
    with pytest.raises(RuntimeError, match="chaos"):
        server.run_until_drained()
    server.reset()
    done = server.run_until_drained()
    assert server.replays == 1
    comp = done[req.id]
    want = _reference_logprobs(req.prompt, comp.tokens)
    assert len(comp.tokens) == 14
    assert (want.argmax(-1) == np.asarray(comp.tokens)).all()


# ------------------------------------------------- the whole forward, solo

def test_apply_and_generate_walk_the_pattern(prepared, tcfg):
    """``transformer.apply`` (the training forward: zero state, the
    chunkwise form over the whole sequence) and ``generate()`` (lockstep
    prefill then decode through the cache) against the reference."""
    from tony_tpu.models.generate import generate

    rng = np.random.default_rng(2)
    prompt = rng.integers(3, CFG["vocab_size"], (1, 19), dtype=np.int32)
    out = np.asarray(generate(prepared, tcfg, jnp.asarray(prompt), 8))
    want = _reference_logprobs(prompt[0], out[0])
    assert (want.argmax(-1) == out[0]).all()
    logits, _ = transformer.apply(prepared.params, jnp.asarray(prompt), tcfg)
    full = _reference_logprobs(prompt[0][:1], prompt[0][1:])  # positions 0..17
    got = np.asarray(jax.nn.log_softmax(logits[0, :18], axis=-1))
    assert np.abs(got - full).max() < LOGPROB_TOL


def test_init_builds_one_stack_per_kind(tcfg):
    params = transformer.init(jax.random.PRNGKey(0), tcfg)
    axes = transformer.param_logical_axes(tcfg)
    assert set(params["layers"]) == {"full", "linear"} == set(axes["layers"])
    for kind, n in (("full", 2), ("linear", 6)):
        assert set(params["layers"][kind]) == set(axes["layers"][kind])
        for name, leaf in params["layers"][kind].items():
            assert leaf.shape[0] == n
            assert len(axes["layers"][kind][name]) == leaf.ndim, name
    cache = init_cache(tcfg, 3, 16)
    assert cache.k.shape[0] == 2 and cache.state.shape == (6, 3, 4, 8, 16)
    assert cache.conv.shape == (6, 3, 3, 4 * (2 * 8 + 16))
    assert cache.state.dtype == jnp.float32
    uniform = init_cache(transformer.TransformerConfig(n_layers=2), 1, 8)
    assert uniform.state is None and uniform.conv is None


# -------------------------- the uniform programs are the parent's programs

def _uniform_program_texts():
    cfg = transformer.TransformerConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=64, attn_window=48, norm_eps=1e-5,
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
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


GOLDEN = json.loads((REPO / "tests/fixtures/uniform_stablehlo.json").read_text())


@pytest.mark.parametrize("program", sorted(GOLDEN["sha256"]))
def test_uniform_config_lowers_to_the_parents_stablehlo(program):
    """A config without ``layer_kinds`` (Mistral-shaped: GQA, a window,
    bf16, fused decode weights) lowers `_decode_block` and `_prefill_batch`
    to the text the tree before the layer pattern lowered them to
    (``as_text()`` carries no locations): the measured cells load the
    programs they loaded. The digests were taken on that tree
    (tests/fixtures/uniform_stablehlo.json says how); another jax prints
    another text, and then there is nothing to hold them to."""
    if jax.__version__ != GOLDEN["jax"]:
        pytest.skip(f"digests are of jax {GOLDEN['jax']}")
    text = _uniform_program_texts()[program]
    assert hashlib.sha256(text.encode()).hexdigest() \
        == GOLDEN["sha256"][program]
