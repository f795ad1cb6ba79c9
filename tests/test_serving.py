"""Continuous-batching slot-pool server (models/serving.py).

The contract under test: a request served through the slot pool — admitted
into whatever slot frees up, decoded alongside unrelated requests, its
prompt chunk-prefilled at arbitrary offsets — emits EXACTLY the tokens a
solo generate() call emits. That exactness is what makes continuous
batching safe to deploy: batching policy must never change results.
Reference analogue: TonY keeps long-lived services alive and routes to
them (NotebookSubmitter.java:71-133, ProxyServer.java:27-39); the model
serving layer itself is this framework's TPU-native capability extension
(SURVEY.md §2.3).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.models import transformer
from tony_tpu.models.generate import generate, prepare_decode
from tony_tpu.models.serving import (
    Completion, PrefixCache, Request, SlotServer,
)

TINY = transformer.TransformerConfig(
    vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
    d_ff=128, max_seq_len=128, dtype=jnp.float32,
)


@pytest.fixture(scope="module")
def params():
    return transformer.init(jax.random.PRNGKey(0), TINY)


def _prompts(n, key=3, lo=2, hi=14):
    """n random prompts of varied lengths."""
    k = jax.random.PRNGKey(key)
    out = []
    for i in range(n):
        k, a, b = jax.random.split(k, 3)
        lp = int(jax.random.randint(a, (), lo, hi))
        out.append(np.asarray(
            jax.random.randint(b, (lp,), 0, TINY.vocab_size), np.int32))
    return out


def _solo(params, prompt, max_new, **kw):
    out = generate(params, TINY, jnp.asarray(prompt)[None], max_new, **kw)
    return [int(t) for t in np.asarray(out)[0]]


def test_slot_server_parity_with_solo_generate(params):
    """12 mixed-length requests through 3 slots (forcing admission into
    freed slots mid-flight) — every completion token-exact vs a solo
    generate() run of the same prompt."""
    prompts = _prompts(12)
    srv = SlotServer(params, TINY, slots=3, max_len=64, block_size=4,
                     prefill_chunk=8)
    reqs = [Request(prompt=p, max_new_tokens=6 + (i % 5))
            for i, p in enumerate(prompts)]
    for r in reqs:
        srv.submit(r)
    done = srv.run_until_drained()
    assert len(done) == len(reqs)
    for r, p in zip(reqs, prompts):
        comp = done[r.id]
        assert comp.finish_reason == "length"
        assert comp.tokens == _solo(params, p, r.max_new_tokens), (
            f"request {r.id} (prompt len {p.size}) diverged")


def test_slot_server_eos_frees_slot_and_matches_generate(params):
    """Stop tokens end a request mid-block; the emitted stream (stop token
    included) matches generate(stop_tokens=...), and the freed slot admits
    a queued request."""
    prompts = _prompts(6, key=11)
    # discover each prompt's greedy stream to pick a stop token that
    # actually fires for some requests
    solo = [_solo(params, p, 10) for p in prompts]
    stop = solo[0][3]
    srv = SlotServer(params, TINY, slots=2, max_len=64, block_size=4,
                     prefill_chunk=8, stop_tokens=(stop,), pad_id=255)
    reqs = [Request(prompt=p, max_new_tokens=10) for p in prompts]
    for r in reqs:
        srv.submit(r)
    done = srv.run_until_drained()
    assert len(done) == len(reqs)
    saw_stop = False
    for r, p in zip(reqs, prompts):
        ref = _solo(params, p, 10, stop_tokens=(stop,), pad_id=255)
        got = done[r.id].tokens
        # generate pads past the stop; the server emits only up to it
        if stop in ref:
            ref = ref[:ref.index(stop) + 1]
            assert done[r.id].finish_reason == "stop"
            saw_stop = True
        assert got == ref, f"request {r.id} diverged"
    assert saw_stop, "test needs at least one request hitting the stop"


def test_slot_server_int8_kv_and_weights(params):
    """kv_dtype/weight_dtype wire through the slot pool: quantized cache +
    scale buffers + int8 decode weights serve mixed bursts. vs solo
    generate() the int8 paths agree within QUANTIZATION TOLERANCE, not
    bit-exactly:
    serving chunk-prefills the prompt body through the quantized cache
    (and raw, unfused prefill weights) where generate's true prefill
    attends raw K/V (and the w8-fused weights) — a near-tie at int8
    resolution can flip a greedy token, and does under some jax versions.
    Exactness claims belong to the native-dtype paths (tested above);
    here we assert majority agreement with solo (a plumbing regression
    produces garbage everywhere, not one flipped near-tie)."""
    prompts = _prompts(4, key=7)
    srv = SlotServer(params, TINY, slots=2, max_len=64, block_size=4,
                     prefill_chunk=8, kv_dtype="int8", weight_dtype="int8")
    reqs = [Request(prompt=p, max_new_tokens=5) for p in prompts]
    for r in reqs:
        srv.submit(r)
    done = srv.run_until_drained()
    outs = [done[r.id].tokens for r in reqs]
    refs = [_solo(params, p, 5, kv_dtype="int8", weight_dtype="int8")
            for p in prompts]
    for toks in outs:
        assert len(toks) == 5
        assert all(0 <= t < TINY.vocab_size for t in toks)
    agree = sum(t == r for t, r in zip(outs, refs))
    assert agree * 2 >= len(refs), (outs, refs)


def test_slot_server_prepared_weights_and_incremental_api(params):
    """prepare_decode weights serve without per-call fusion; submit/step/
    drain_completed works incrementally (the live-service loop shape) with
    requests arriving WHILE others decode."""
    prompts = _prompts(5, key=23)
    prep = prepare_decode(params, TINY)
    srv = SlotServer(prep, TINY, slots=2, max_len=64, block_size=2,
                     prefill_chunk=8)
    reqs = [Request(prompt=p, max_new_tokens=6) for p in prompts]
    srv.submit(reqs[0])
    srv.submit(reqs[1])
    done: dict[int, Completion] = {}
    late = list(reqs[2:])
    for i in range(200):
        srv.step()
        done.update(srv.drain_completed())
        if late:                      # arrivals mid-decode
            srv.submit(late.pop(0))
        if len(done) == len(reqs) and not late:
            break
    assert len(done) == len(reqs)
    for r, p in zip(reqs, prompts):
        assert done[r.id].tokens == _solo(params, p, 6)


def test_slot_server_rejections(params):
    srv = SlotServer(params, TINY, slots=1, max_len=16)
    with pytest.raises(ValueError, match="max_len"):
        srv.submit(Request(prompt=list(range(10)), max_new_tokens=10))
    with pytest.raises(ValueError, match="empty"):
        srv.submit(Request(prompt=[], max_new_tokens=4))
    with pytest.raises(ValueError, match="max_new_tokens"):
        srv.submit(Request(prompt=[1], max_new_tokens=0))


def test_slot_server_single_token_prompt(params):
    """A 1-token prompt has no prefill body at all — the token is fed
    directly as the first decode input."""
    srv = SlotServer(params, TINY, slots=2, max_len=32, block_size=4)
    r = Request(prompt=[7], max_new_tokens=6)
    srv.submit(r)
    done = srv.run_until_drained()
    assert done[r.id].tokens == _solo(params, np.asarray([7], np.int32), 6)


def test_serve_http_end_to_end(params):
    """`tony-tpu serve`'s HTTP surface: concurrent POST /generate requests
    through the ServeApp loop return token-exact completions; /stats
    reports the pool. In-process (ThreadingHTTPServer on an ephemeral
    port) — the same app object the CLI main wires up."""
    import json
    import threading
    import urllib.request
    from http.server import ThreadingHTTPServer

    from tony_tpu.cli.serve import ServeApp, make_handler
    from tony_tpu.models.serving import SlotServer

    slot_server = SlotServer(params, TINY, slots=2, max_len=64,
                             block_size=4, prefill_chunk=8)
    app = ServeApp(slot_server)
    app.start()
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(app))
    port = httpd.server_address[1]
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        prompts = _prompts(4, key=31)
        results = {}

        def post(i, p):
            body = json.dumps({"prompt": [int(x) for x in p],
                               "max_new_tokens": 5}).encode()
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/generate", data=body, timeout=120
            ) as r:
                results[i] = json.loads(r.read())

        threads = [threading.Thread(target=post, args=(i, p))
                   for i, p in enumerate(prompts)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
        assert len(results) == 4
        for i, p in enumerate(prompts):
            assert results[i]["tokens"] == _solo(params, p, 5)
            assert results[i]["finish_reason"] == "length"

        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/stats", timeout=10
        ) as r:
            stats = json.loads(r.read())
        assert stats["slots"] == 2 and stats["active"] == 0

        # malformed request -> 400, service stays up
        import urllib.error
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/generate",
                data=b'{"max_new_tokens": 5}', timeout=10)
        assert ei.value.code == 400
    finally:
        httpd.shutdown()
        httpd.server_close()
        app.shutdown()


def test_serve_loop_failure_fails_pending_and_healthz(params):
    """If the serving loop raises, waiters must get an immediate error
    (not hang to their timeouts), /healthz must flip to 503 with the
    cause, and new submissions must be rejected fast."""
    import json
    import threading
    import urllib.error
    import urllib.request
    from http.server import ThreadingHTTPServer

    from tony_tpu.cli.serve import ServeApp, ServingLoopError, make_handler

    class ExplodingServer:
        """SlotServer stand-in whose step() dies once a request is in."""
        slots, max_len, block_size = 1, 32, 4
        n_active, pending = 0, 0

        def __init__(self):
            self.idle = True

        def submit(self, req):
            self.idle = False
            return req.id

        def step(self):
            raise RuntimeError("XlaRuntimeError: device lost")

    app = ServeApp(ExplodingServer())
    app.start()
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(app))
    port = httpd.server_address[1]
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10) as r:
            assert json.loads(r.read())["healthy"] is True
        # the request must FAIL (503), well before the 600s default timeout
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/generate",
                data=b'{"prompt": [1], "max_new_tokens": 4}', timeout=30)
        assert ei.value.code == 503
        assert "device lost" in json.loads(ei.value.read())["error"]
        # unhealthy is observable and sticky
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10)
        assert ei.value.code == 503
        assert "device lost" in json.loads(ei.value.read())["error"]
        # new submissions are rejected immediately, not queued into a
        # dead loop
        with pytest.raises(ServingLoopError):
            app.generate([1], 4, timeout=5)
    finally:
        httpd.shutdown()
        httpd.server_close()
        app.shutdown()


def test_slot_server_prefill_tail_past_ring_capacity(params):
    """The final prefill chunk's padded tail can span past the ring
    capacity (prefill_chunk not dividing max_len): those writes must be
    DROPPED, not wrapped onto the slot's own earliest prompt K/V — a wrap
    silently corrupts positions the attention mask legitimately reads."""
    import numpy as np

    prompt = np.asarray(
        jax.random.randint(jax.random.PRNGKey(41), (36,), 0,
                           TINY.vocab_size), np.int32)
    # body=35 -> chunks at 0,16,32; last chunk spans logical 32..47 > 40
    srv = SlotServer(params, TINY, slots=2, max_len=40, block_size=4,
                     prefill_chunk=16)
    r = Request(prompt=prompt, max_new_tokens=4)
    srv.submit(r)
    done = srv.run_until_drained()
    assert done[r.id].tokens == _solo(params, prompt, 4)


@pytest.mark.parametrize("burst", [1, 3, 9])
def test_slot_server_admission_bursts_match_solo(params, burst):
    """One admission program whatever the burst: 9 requests admitted in
    bursts of 1, 3 or 9 (one `_prefill_batch` dispatch per chunk ROUND, at
    1, 4 or 16 rows) emit exactly solo generate()'s tokens — the size of
    a burst can never change results — and a burst costs its LONGEST
    prompt's chunk rounds in dispatches, not the sum over its prompts."""
    prompts = _prompts(9, key=61, lo=2, hi=22)   # multi-chunk at chunk=8
    # a 1-token prompt in a burst: its row is commit-only (n_valid=0,
    # every KV write dropped) — the degenerate case must ride along
    # exactly
    prompts[4] = prompts[4][:1]
    budgets = [5 + (i % 3) for i in range(len(prompts))]
    srv = SlotServer(params, TINY, slots=burst, max_len=64, block_size=4,
                     prefill_chunk=8)
    reqs = [Request(prompt=p, max_new_tokens=b)
            for p, b in zip(prompts, budgets)]
    done, rounds = {}, 0
    for g in range(0, len(reqs), burst):    # every slot is free: one burst
        for r in reqs[g:g + burst]:
            srv.submit(r)
        done.update(srv.run_until_drained())
        rounds += max(max(1, -(-(len(p) - 1) // 8))
                      for p in prompts[g:g + burst])
    assert srv.admission_dispatches == rounds
    for r, p, b in zip(reqs, prompts, budgets):
        assert done[r.id].tokens == _solo(params, p, b)


@pytest.mark.parametrize("kv_dtype", ["native", "int8"])
def test_prefill_batch_rows_equal_successive_one_row_calls(params, kv_dtype):
    """The guarantee the one-slot admission program used to be compared
    for: a `_prefill_batch` of K rows leaves every slot's cache rows,
    lengths and committed decode state BYTE-identical to K successive
    one-row calls. Two chunk rounds over three slots at bf16 (and an int8
    cache): a row that commits in round 0, a commit-only row of a 1-token
    prompt (n_valid 0), a row whose second chunk wraps its ring, and the
    out-of-bounds padding row of the burst's power-of-two width."""
    import dataclasses

    from tony_tpu.models.generate import init_cache
    from tony_tpu.models.serving import _prefill_batch

    cfg = dataclasses.replace(TINY, dtype=jnp.bfloat16)
    S, M, C = 4, 32, 8
    rng = np.random.default_rng(5)
    # (slot, start, ring offset, n_valid, final) per row, per round
    rounds = [[(2, 0, 5, 8, False), (0, 0, 0, 0, True), (3, 0, 20, 8, False)],
              [(2, 8, 5, 3, True), (3, 8, 20, 8, True)]]
    toks = [rng.integers(0, cfg.vocab_size, (len(r), C), dtype=np.int32)
            for r in rounds]

    def pack(rows, tokens):
        k = 1 << (len(rows) - 1).bit_length()
        a = {n: np.zeros(k, np.int32) for n in
             ("slots", "starts", "offsets", "n_valids", "lasts", "targets",
              "topks")}
        a["slots"][:] = S + np.arange(k)            # padding rows: OOB
        temps, fin = np.zeros(k, np.float32), np.zeros(k, bool)
        tk = np.zeros((k, C), np.int32)
        for i, (slot, start, off, nv, final) in enumerate(rows):
            tk[i, :nv] = tokens[i, :nv]
            a["slots"][i], a["starts"][i] = slot, start
            a["offsets"][i], a["n_valids"][i] = off, nv
            a["lasts"][i], a["targets"][i] = 7 + slot, 40 + slot
            temps[i], a["topks"][i], fin[i] = 0.5 * slot, slot, final
        return [jnp.asarray(x) for x in (
            tk, a["slots"], a["starts"], a["offsets"], a["n_valids"],
            a["lasts"], a["targets"], temps, a["topks"], fin)]

    def run(groups):
        st = [init_cache(cfg, S, M, kv_dtype)._replace(
                  length=jnp.zeros((S,), jnp.int32)),
              jnp.zeros((S,), jnp.int32), jnp.zeros((S,), bool),
              jnp.zeros((S,), jnp.int32), jnp.zeros((S,), jnp.int32),
              jnp.zeros((S,), jnp.float32), jnp.zeros((S,), jnp.int32)]
        for rows, tokens in groups:
            *st, _ = _prefill_batch(params, *st, *pack(rows, tokens),
                                    cfg=cfg, shardings=None)
        return jax.tree.leaves(st)

    batched = run(zip(rounds, toks))
    serial = run(([row], t[i:i + 1]) for r, t in zip(rounds, toks)
                 for i, row in enumerate(r))
    assert len(batched) == len(serial) == (11 if kv_dtype == "int8" else 9)
    for a, b in zip(batched, serial):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    cache_len, d_active, d_target = (np.asarray(batched[i]) for i in (2, -5, -4))
    assert cache_len.tolist() == [0, 0, 11, 16]
    assert d_active.tolist() == [True, False, True, True]
    assert d_target.tolist() == [40, 0, 42, 43]


@pytest.mark.slow
def test_slot_server_admission_bursts_with_eos(params):
    """Mid-flight re-admission bursts (slots freed by EOS at different
    times) go through the batched program too; completions still match
    generate(stop_tokens=...)."""
    prompts = _prompts(8, key=67)
    solo = [_solo(params, p, 8) for p in prompts]
    stop = solo[0][2]
    srv = SlotServer(params, TINY, slots=3, max_len=64, block_size=4,
                     prefill_chunk=8, stop_tokens=(stop,), pad_id=255)
    reqs = [Request(prompt=p, max_new_tokens=8) for p in prompts]
    for r in reqs:
        srv.submit(r)
    done = srv.run_until_drained()
    assert len(done) == len(reqs)
    for r, p in zip(reqs, prompts):
        ref = _solo(params, p, 8, stop_tokens=(stop,), pad_id=255)
        if stop in ref:
            ref = ref[:ref.index(stop) + 1]
        assert done[r.id].tokens == ref, f"request {r.id} diverged"


def _tp_mesh(data=2, tensor=2):
    from tony_tpu.parallel import MeshSpec, build_mesh

    return build_mesh(MeshSpec(data=data, fsdp=1, tensor=tensor),
                      devices=jax.devices()[:data * tensor])


def test_slot_server_tp_mesh_parity(params):
    """THE tensor-parallel serving contract: a mesh-sharded SlotServer
    (KV pool over ("batch", "kv"), per-slot state over the batch axes, 4
    forced host-platform devices) produces greedy completions
    token-identical to the single-device SlotServer AND to solo
    generate() — sharding, like batching, must never change results."""
    mesh = _tp_mesh()
    prompts = _prompts(10, key=71)
    budgets = [5 + (i % 4) for i in range(len(prompts))]

    def run(server_params, **kw):
        srv = SlotServer(server_params, TINY, slots=4, max_len=64,
                         block_size=4, prefill_chunk=8, **kw)
        reqs = [Request(prompt=p, max_new_tokens=b)
                for p, b in zip(prompts, budgets)]
        for r in reqs:
            srv.submit(r)
        done = srv.run_until_drained()
        return [done[r.id].tokens for r in reqs]

    single = run(params)
    prep = prepare_decode(params, TINY, mesh=mesh)
    assert prep.fused is None           # fusion is single-device-only
    sharded = run(prep)
    assert sharded == single
    # raw params + mesh kwarg prepares internally; same tokens
    assert run(params, mesh=mesh) == single
    # and the per-request solo-generate contract carries over the mesh
    for toks, p, b in zip(sharded, prompts, budgets):
        assert toks == _solo(params, p, b)


@pytest.mark.slow
def test_slot_server_tp_mesh_eos(params):
    """EOS mode composes with the mesh (the sync/burst bookkeeping is
    sharding-agnostic)."""
    mesh = _tp_mesh()
    prompts = _prompts(6, key=73)
    stop = _solo(params, prompts[0], 8)[2]
    prep = prepare_decode(params, TINY, mesh=mesh)
    srv = SlotServer(prep, TINY, slots=2, max_len=64, block_size=4,
                     prefill_chunk=8, stop_tokens=(stop,), pad_id=255)
    reqs = [Request(prompt=p, max_new_tokens=8) for p in prompts]
    for r in reqs:
        srv.submit(r)
    done = srv.run_until_drained()
    for r, p in zip(reqs, prompts):
        ref = _solo(params, p, 8, stop_tokens=(stop,), pad_id=255)
        if stop in ref:
            ref = ref[:ref.index(stop) + 1]
        assert done[r.id].tokens == ref


def test_slot_server_mesh_rejections(params):
    """slots not divisible by the batch axes, and a mesh passed alongside
    meshless prepared weights, fail loudly instead of mis-sharding."""
    mesh = _tp_mesh()
    prep = prepare_decode(params, TINY, mesh=mesh)
    with pytest.raises(ValueError, match="slots=3"):
        SlotServer(prep, TINY, slots=3, max_len=64)
    with pytest.raises(ValueError, match="without a mesh"):
        SlotServer(prepare_decode(params, TINY), TINY, slots=4,
                   max_len=64, mesh=mesh)


# --------------------------------------------------------------------------
# chunk-aligned prefix KV cache
# --------------------------------------------------------------------------

_TEMPLATE = np.asarray(
    jax.random.randint(jax.random.PRNGKey(97), (16,), 0, TINY.vocab_size),
    np.int32)                    # 2 full chunks at prefill_chunk=8


def _templated(n, lo=2, hi=9, key=101):
    """n prompts sharing the 16-token template + short unique suffixes."""
    return [np.concatenate([_TEMPLATE, s]) for s in _prompts(n, key, lo, hi)]


def _serve_all(params, prompts, budgets, **kw):
    srv = SlotServer(params, TINY, slots=2, max_len=64, block_size=4,
                     prefill_chunk=8, **kw)
    reqs = [Request(prompt=p, max_new_tokens=b)
            for p, b in zip(prompts, budgets)]
    for r in reqs:
        srv.submit(r)
    done = srv.run_until_drained()
    return [done[r.id].tokens for r in reqs], srv


def test_prefix_cache_hit_path_token_identical(params):
    """THE prefix-cache contract: completions with the cache enabled are
    token-identical to the cold path AND to solo generate() — reuse is
    pure data movement, never a numerics change. Includes the degenerate
    full-hit prompt (body == cached prefix: no suffix to prefill at
    all)."""
    prompts = _templated(6)
    # body exactly the 2 template chunks -> full hit, finalize-only chunk
    prompts.append(np.concatenate([_TEMPLATE, _TEMPLATE[:1]]))
    budgets = [5 + (i % 3) for i in range(len(prompts))]
    cold, _ = _serve_all(params, prompts, budgets)
    warm, srv = _serve_all(params, prompts, budgets, prefix_cache_blocks=8)
    assert warm == cold
    for toks, p, b in zip(warm, prompts, budgets):
        assert toks == _solo(params, p, b), "hit path diverged from solo"
    st = srv.stats()["prefix_cache"]
    # slots=2 -> the first burst of 2 misses and populates; the rest hit
    assert st["hits"] >= 4 and st["misses"] >= 1
    assert srv.prefill_tokens_reused >= 4 * _TEMPLATE.size
    assert st["copy_dispatches"] >= 1 and st["insert_dispatches"] >= 1
    assert srv.admission_dispatches < (
        _serve_all(params, prompts, budgets)[1].admission_dispatches)


def test_prefix_cache_int8_kv_hit_identical(params):
    """int8 kv: the pool stores the QUANTIZED blocks + scales, so hit and
    cold paths read the same bytes — completions exactly identical (a
    stronger claim than the int8 serving-vs-solo tolerance, which is
    about chunked prefill vs true prefill, not about reuse)."""
    prompts = _templated(5, key=103)
    budgets = [5] * len(prompts)
    cold, _ = _serve_all(params, prompts, budgets, kv_dtype="int8")
    warm, srv = _serve_all(params, prompts, budgets, kv_dtype="int8",
                           prefix_cache_blocks=8)
    assert warm == cold
    assert srv.prefill_tokens_reused > 0


@pytest.mark.slow
def test_prefix_cache_tp_mesh_hit_identical(params):
    """The prefix pool composes with tensor-parallel serving: pool blocks
    shard over ("batch", "kv") like the slot cache, and the hit path
    stays token-identical to the cold path and to the single-device
    server on 4 forced host devices."""
    mesh = _tp_mesh()
    prompts = _templated(6, key=107)
    budgets = [5 + (i % 3) for i in range(len(prompts))]

    def run(server_params, **kw):
        srv = SlotServer(server_params, TINY, slots=4, max_len=64,
                         block_size=4, prefill_chunk=8, **kw)
        reqs = [Request(prompt=p, max_new_tokens=b)
                for p, b in zip(prompts, budgets)]
        for r in reqs:
            srv.submit(r)
        done = srv.run_until_drained()
        return [done[r.id].tokens for r in reqs], srv

    prep = prepare_decode(params, TINY, mesh=mesh)
    cold_tp, _ = run(prep)
    warm_tp, srv = run(prep, prefix_cache_blocks=8)
    warm_single, _ = run(params, prefix_cache_blocks=8)
    assert warm_tp == cold_tp
    assert warm_tp == warm_single
    assert srv.prefill_tokens_reused > 0


def test_prefix_cache_ring_wrap_reuse(params):
    """A copied prefix that spans the max_len ring boundary must land at
    the wrapped indices exactly as prefill's own writes would. Filler
    requests (cache_prompt=False, so they leave the trie alone) advance
    the global cursor until the next admission's ring offset forces the
    template copy to wrap, then the templated request must still match
    solo generate()."""
    max_len = 48
    template = np.asarray(
        jax.random.randint(jax.random.PRNGKey(113), (32,), 0,
                           TINY.vocab_size), np.int32)    # 4 chunks
    sfx = _prompts(2, key=127, lo=2, hi=4)
    srv = SlotServer(params, TINY, slots=2, max_len=max_len, block_size=4,
                     prefill_chunk=8, prefix_cache_blocks=8)

    def run_one(prompt, **kw):
        r = Request(prompt=prompt, max_new_tokens=4, **kw)
        srv.submit(r)
        return srv.run_until_drained()[r.id].tokens

    first = np.concatenate([template, sfx[0]])
    assert run_one(first) == _solo(params, first, 4)      # populates trie
    wrapped = False
    second = np.concatenate([template, sfx[1]])
    body = second.size - 1
    for _ in range(40):          # advance the cursor into the wrap zone
        offset = (srv._cursor - body) % max_len
        if offset + template.size > max_len:    # prefix copy will wrap
            wrapped = True
            break
        filler = np.asarray(
            jax.random.randint(jax.random.PRNGKey(srv._cursor + 1), (5,),
                               0, TINY.vocab_size), np.int32)
        run_one(filler, cache_prompt=False)
    assert wrapped, "test never reached a wrapping offset"
    reused_before = srv.prefill_tokens_reused
    assert run_one(second) == _solo(params, second, 4), (
        "wrapped prefix copy corrupted the ring")
    assert srv.prefill_tokens_reused == reused_before + template.size


def test_prefix_cache_refcount_and_eviction_unit():
    """The host trie/allocator contract, no model needed: the budget is
    respected, eviction is LRU over unreferenced LEAVES only, evicting a
    referenced (or interior) node is impossible, and insertion degrades
    to a shorter cached prefix when nothing is evictable."""
    pc = PrefixCache(2, 4)
    a = np.arange(8, dtype=np.int32)            # 2 chunks
    created = pc.insert(a)
    assert [ci for ci, _ in created] == [0, 1] and pc.blocks_used == 2
    pc.release([n for _, n in created])         # drop the insert-refs

    path = pc.lookup(a)
    assert [n.block for n in path] == [n.block for _, n in created]
    pc.acquire(path)
    # both blocks are on a referenced path: nothing evictable
    assert pc.alloc() is None
    b = np.arange(100, 108, dtype=np.int32)
    assert pc.insert(b) == []                   # degrades, doesn't fail
    pc.release(path)

    # unreferenced now: eviction peels the LEAF (deepest chunk) first
    blk = pc.alloc()
    assert blk == path[1].block and pc.evictions == 1
    assert pc.lookup(a) == path[:1]             # 1-chunk prefix still hits
    # the surviving root child became a leaf -> evictable next
    assert pc.alloc() == path[0].block and pc.evictions == 2
    assert pc.lookup(a) == []

    # LRU: two sibling templates, refresh the older one, evict -> the
    # stale one goes
    pc2 = PrefixCache(2, 4)
    na = pc2.insert(np.arange(4, dtype=np.int32))
    nb = pc2.insert(np.arange(50, 54, dtype=np.int32))
    pc2.release([n for _, n in na] + [n for _, n in nb])
    pc2.lookup(np.arange(4, dtype=np.int32))    # touch A -> B is LRU
    assert pc2.alloc() == nb[0][1].block


def test_prefix_cache_eviction_stress_server(params):
    """A 2-block pool cycling through 3 distinct 2-chunk templates: every
    admission evicts, the budget holds, and every completion stays exact
    vs solo generate()."""
    keys = (131, 137, 139)
    templates = [np.asarray(
        jax.random.randint(jax.random.PRNGKey(k), (16,), 0,
                           TINY.vocab_size), np.int32) for k in keys]
    srv = SlotServer(params, TINY, slots=2, max_len=64, block_size=4,
                     prefill_chunk=8, prefix_cache_blocks=2)
    for rnd in range(3):
        for t in templates:
            prompt = np.concatenate([t, t[:3]])
            r = Request(prompt=prompt, max_new_tokens=4)
            srv.submit(r)
            got = srv.run_until_drained()[r.id].tokens
            assert got == _solo(params, prompt, 4)
            pc = srv._prefix_cache
            assert pc.blocks_used <= pc.n_blocks == 2
    assert srv.stats()["prefix_cache"]["evictions"] > 0


def test_serve_app_stats_exposes_serving_counters(params):
    """ServeApp.stats (the /stats payload) carries the SlotServer's
    prefix-cache/prefill counters plus the MetricsAccumulator snapshot of
    the serving-load gauges."""
    from tony_tpu.cli.serve import ServeApp

    slot_server = SlotServer(params, TINY, slots=2, max_len=64,
                             block_size=4, prefill_chunk=8,
                             prefix_cache_blocks=4)
    app = ServeApp(slot_server)
    app.start()
    try:
        prompt = [int(t) for t in _TEMPLATE] + [3]
        app.generate(prompt, 4, timeout=120)
        app.generate(prompt[:-1] + [7], 4, timeout=120)
        st = app.stats()
    finally:
        app.shutdown()
    assert st["prefix_cache"]["hits"] >= 1
    assert st["prefill_tokens_reused"] >= _TEMPLATE.size
    assert st["admission_dispatches"] >= 1
    assert st["active"] == 0 and st["slots"] == 2
    names = {m["name"] for m in st["metrics"]}
    assert {"max_serving_active_slots", "avg_serving_queue_depth"} <= names


def test_slot_server_per_request_top_k(params):
    """Per-request top_k shares the pool like per-request temperature: a
    top_k=1 request at a hot temperature is argmax by construction, so it
    must reproduce solo greedy generate() even while its neighbors sample
    from the server-global (unfiltered) distribution."""
    prompts = _prompts(6, key=149)
    srv = SlotServer(params, TINY, slots=3, max_len=64, block_size=4,
                     prefill_chunk=8, temperature=0.8, top_k=0, seed=11)
    reqs = [Request(prompt=p, max_new_tokens=6,
                    temperature=4.0 if i % 2 == 0 else None,
                    top_k=1 if i % 2 == 0 else None)
            for i, p in enumerate(prompts)]
    for r in reqs:
        srv.submit(r)
    done = srv.run_until_drained()
    assert len(done) == len(reqs)
    for i, (r, p) in enumerate(zip(reqs, prompts)):
        toks = done[r.id].tokens
        assert len(toks) == 6
        assert all(0 <= t < TINY.vocab_size for t in toks)
        if i % 2 == 0:      # top_k=1 == greedy, neighbors sampling freely
            assert toks == _solo(params, p, 6), f"top_k=1 request {i} diverged"


def test_slot_server_per_request_temperature(params):
    """Greedy and sampled requests share one pool: per-row temperatures
    mean a temperature-0 request stays token-exact vs solo greedy
    generate() even while its neighbors sample."""
    prompts = _prompts(6, key=53)
    srv = SlotServer(params, TINY, slots=3, max_len=64, block_size=4,
                     prefill_chunk=8, temperature=0.9, seed=3)
    reqs = [Request(prompt=p, max_new_tokens=6,
                    temperature=0.0 if i % 2 == 0 else None)
            for i, p in enumerate(prompts)]
    for r in reqs:
        srv.submit(r)
    done = srv.run_until_drained()
    assert len(done) == len(reqs)
    for i, (r, p) in enumerate(zip(reqs, prompts)):
        toks = done[r.id].tokens
        assert len(toks) == 6
        assert all(0 <= t < TINY.vocab_size for t in toks)
        if i % 2 == 0:   # greedy rows: exact despite sampled neighbors
            assert toks == _solo(params, p, 6), f"greedy request {i} diverged"
