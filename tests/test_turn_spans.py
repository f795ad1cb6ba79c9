"""The serving turn's phases in the profiler's own trace
(``observability.phase``), and the benchmark's readers of them.

One profiler session for the whole module, on the CPU: a tiny
``SlotServer`` drained on the test's own thread (EOS mode, then the paged
engine), then a predictive one behind ``ServeApp`` with a thread a
submitter. The tests read the one trace back through the benchmark's
``trace/host_spans.py``: what the program writes and what the benchmark
reads are held to each other here. Also pinned here: the program names that
the device-trace readers match.
"""

import json
import shutil
import sys
import threading
import time
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu import observability as obs
from tony_tpu.models import transformer
from tony_tpu.models.serving import Request, SlotServer

REPO = Path(__file__).resolve().parent.parent
BENCH = REPO / "benchmark"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import lib  # noqa: E402  (benchmark/lib.py: the readers import it by this name)

host_spans = lib.load("trace/host_spans.py")

TINY = transformer.TransformerConfig(
    vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
    d_ff=128, max_seq_len=128, dtype=jnp.float32,
)
# one period of a hybrid decoder: its linear layers keep a state a slot;
# the rows whose state a block changed ride the bookkeep span
HYBRID = transformer.TransformerConfig(
    vocab_size=256, d_model=64, n_layers=4, n_heads=4, n_kv_heads=4,
    d_ff=128, max_seq_len=128, dtype=jnp.float32, rope_theta=None,
    qk_norm=True, norm_order="post",
    layer_kinds=("linear", "linear", "linear", "full"),
    lin_heads=4, lin_key_dim=8, lin_value_dim=16,
)
# a latent-attention layer under a dense MLP and one under routed experts:
# the experts a block's rows touched ride the bookkeep span
ROUTED = transformer.TransformerConfig(
    vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=4,
    d_ff=128, max_seq_len=128, dtype=jnp.float32,
    layer_kinds=("latent", "latent"), lat_q_rank=48, lat_kv_rank=32,
    lat_nope_dim=16, lat_rope_dim=8, lat_v_dim=16, rope_interleave=True,
    mlp_kinds=("dense", "routed"), moe_experts=16, moe_top_k=4, moe_ff=24,
    moe_shared=1, moe_scale=2.5,
)
CHAT, REASON = "mistral7b-v01.chat-open", "olmo-hybrid-7b.reason-open"
ROLLOUT = "joyai-llm-flash.rollout-closed"
LONGCTX, XING = "command-a-plus.longctx-closed", "xing4.longctx-closed"
STEP_PHASES = (obs.PHASE_ADMIT, obs.PHASE_DISPATCH, obs.PHASE_SYNC,
               obs.PHASE_BOOKKEEP)
NEW_ENTRIES = [m for m in json.loads((REPO / "BENCHMARK.json").read_text())
               ["per_layer"] if m["source"] == "program_span"]


def _requests(n, seed, max_new=9):
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(0, TINY.vocab_size,
                                        int(rng.integers(2, 14)),
                                        dtype=np.int32),
                    max_new_tokens=max_new + i % 4) for i in range(n)]


def _drain(server, reqs):
    for r in reqs:
        server.submit(r)
    return server.run_until_drained()


@pytest.fixture(scope="module")
def params():
    return transformer.init(jax.random.PRNGKey(0), TINY)


@pytest.fixture(scope="module")
def run(params, tmp_path_factory):
    """The one session: -> what ran in it and where its trace lies."""
    from tony_tpu.cli.serve import ServeApp

    trace_dir = tmp_path_factory.mktemp("turn_spans")
    kw = dict(slots=3, max_len=64, block_size=4, prefill_chunk=8)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        # (a) on this thread: more requests than slots, so turns admit
        # into freed slots while blocks are in flight
        eos = _drain(SlotServer(params, TINY, stop_tokens=(65,), pad_id=255,
                                **kw), _requests(7, seed=1))
        paged = _drain(SlotServer(params, TINY, paged=True, kv_block=8,
                                  **kw), _requests(4, seed=2))
        hybrid_server = SlotServer(
            transformer.init(jax.random.PRNGKey(1), HYBRID), HYBRID,
            stop_tokens=(65,), pad_id=255, **kw)
        hybrid = _drain(hybrid_server, _requests(3, seed=6))
        routed_server = SlotServer(
            transformer.init(jax.random.PRNGKey(2), ROUTED), ROUTED,
            stop_tokens=(65,), pad_id=255, **kw)
        routed = _drain(routed_server, _requests(3, seed=7))
        # (c) a predictive engine behind ServeApp: its drain syncs inside
        # the engine, the case that would nest under serve.loop.drain
        app = ServeApp(SlotServer(params, TINY, **kw))
        app.start()
        sent = {}

        def send(i, req):
            sent[i] = app.submit_async(req.prompt, req.max_new_tokens)

        senders = [threading.Thread(target=send, args=(i, r))
                   for i, r in enumerate(_requests(5, seed=3))]
        for t in senders:
            t.start()
        for t in senders:
            t.join(timeout=60)
        served = {rid: ev.wait(60) and app.take_result(rid)
                  for rid, ev in sent.values()}
        time.sleep(0.1)                 # a few idle turns of the loop
        app.shutdown()
    finally:
        jax.profiler.stop_trace()
    return types.SimpleNamespace(
        dir=trace_dir, engine=[eos, paged, hybrid, routed], served=served,
        hybrid_server=hybrid_server, routed_server=routed_server,
        spans=host_spans.spans("serve.", trace_dir))


def _by_thread(spans):
    out = {}
    for span in spans:
        out.setdefault(span[1], []).append(span)
    return out


def _loop_thread(run):
    """The thread line of ServeApp's loop: the one with the idle spans."""
    return next(s[1] for s in run.spans if s[0] == obs.PHASE_IDLE)


def _test_thread(run):
    """The thread line of the session's own thread: step phases, and no
    phase of ServeApp's loop."""
    loop = _loop_thread(run)
    threads = {s[1] for s in run.spans
               if s[0] == obs.PHASE_DISPATCH and s[1] != loop}
    assert len(threads) == 1
    return threads.pop()


def test_every_span_is_a_phase_and_every_phase_was_entered(run):
    assert {s[0] for s in run.spans} == set(obs.PHASES)


def test_step_phases_carry_their_counts(run):
    mine = [s for s in run.spans if s[1] == _test_thread(run)]
    assert {s[0] for s in mine} == set(STEP_PHASES)
    by_name = {name: [s[4] for s in mine if s[0] == name]
               for name in STEP_PHASES}
    for counts in by_name[obs.PHASE_ADMIT]:
        assert set(counts) == {"queued", "admitted", "prefill_tokens"}
        assert 0 <= counts["admitted"] <= counts["queued"]
    for counts in by_name[obs.PHASE_DISPATCH]:
        assert set(counts) == {"live", "slots"}
        assert 1 <= counts["live"] <= counts["slots"] == 3
    for counts in by_name[obs.PHASE_SYNC]:
        assert set(counts) == {"blocks"} and counts["blocks"] >= 1
    for counts in by_name[obs.PHASE_BOOKKEEP]:
        experts = {"experts_touched", "experts_read", "experts_held",
                   "expert_tokens_max", "expert_tokens_mean"}
        assert set(counts) - experts == {
            "tokens", "completions", "kv_blocks_read", "kv_blocks_ring",
            "latent_rows_in_place", "state_rows", "state_rows_read",
            "state_rows_held", "hyper_rows"}
        # (an engine with routed expert layers adds all five or none)
        assert set(counts) & experts in (set(), experts)
        assert 0 <= counts["state_rows"] <= 3
        # the CPU's recurrence is ``gated_delta_step`` over every row
        # (``state_kernel_engages`` is false here); an engine without
        # linear layers holds no state
        assert counts["state_rows_read"] == counts["state_rows_held"]
        assert counts["state_rows_held"] % 3 == 0
        # no latent layers here, and the CPU's decode gate is shut
        assert counts["latent_rows_in_place"] == 0
        # no engine here has hyper-connections
        assert counts["hyper_rows"] == 0
    done = {**run.engine[0], **run.engine[1], **run.engine[2],
            **run.engine[3]}
    assert len(done) == 17
    assert sum(c["admitted"] for c in by_name[obs.PHASE_ADMIT]) == 17
    # the recurrent state: the rows whose state the device says a block
    # changed, at most one a token and at least one a request; none in an
    # engine without linear layers
    hybrid = run.engine[2]
    steps = sum(len(comp.tokens) for comp in hybrid.values())
    assert steps >= sum(c["state_rows"] for c in by_name[obs.PHASE_BOOKKEEP]) \
        == run.hybrid_server.state_rows >= len(hybrid)
    held = sum(c["state_rows_held"] for c in by_name[obs.PHASE_BOOKKEEP])
    assert held == run.hybrid_server.state_rows_held \
        == run.hybrid_server.state_rows_read > 0
    assert run.hybrid_server.stats()["recurrent_state"]["rows_held"] == held
    assert sum(c["prefill_tokens"] for c in by_name[obs.PHASE_ADMIT]) > 0
    assert sum(c["tokens"] for c in by_name[obs.PHASE_BOOKKEEP]) == sum(
        len(comp.tokens) for comp in done.values())
    assert sum(c["completions"] for c in by_name[obs.PHASE_BOOKKEEP]) == 17
    # the routed experts: the device's counts of a block, on the spans of
    # the one engine that has such layers and on none of the others'
    spans = [c for c in by_name[obs.PHASE_BOOKKEEP] if "experts_held" in c]
    server = run.routed_server
    assert 0 < len(spans) < len(by_name[obs.PHASE_BOOKKEEP])
    for name in ("experts_touched", "experts_read", "experts_held"):
        assert sum(c[name] for c in spans) == getattr(server, name) > 0
    assert all(c["experts_touched"] <= c["experts_read"] <= c["experts_held"]
               and c["experts_held"] % (16 * 4) == 0 for c in spans)
    assert max(c["expert_tokens_max"] for c in spans) \
        == server.expert_tokens_max <= 3
    assert server.stats()["experts"]["touched"] == server.experts_touched
    assert sum(c["blocks"] for c in by_name[obs.PHASE_SYNC]) == len(
        by_name[obs.PHASE_DISPATCH])
    assert any(comp.finish_reason == "stop" for comp in done.values())


def test_phases_are_leaves(run):
    """On one thread no phase begins before the last one has ended."""
    threads = _by_thread(run.spans)
    assert len(threads) >= 3        # the test's, the loop's, a submitter's
    for thread, spans in threads.items():
        end = 0.0
        for name, _, start, dur, _ in sorted(spans, key=lambda s: s[2]):
            assert start >= end, f"{name} starts inside a phase on {thread}"
            end = start + dur


def test_serve_app_phases_and_the_submitters_rid(run):
    assert len(run.served) == 5 and all(run.served.values())
    loop = [s for s in run.spans if s[1] == _loop_thread(run)]
    assert {s[0] for s in loop} == set(obs.PHASES) - {
        obs.PHASE_SUBMIT_LOCK_WAIT}
    waits = [s for s in run.spans if s[0] == obs.PHASE_SUBMIT_LOCK_WAIT]
    assert sorted(s[4]["rid"] for s in waits) == sorted(run.served)
    assert all(s[1] != _loop_thread(run) for s in waits)
    delivered = sum(s[4]["completions"] for s in loop
                    if s[0] == obs.PHASE_DELIVER)
    drained = sum(s[4]["completions"] for s in loop
                  if s[0] == obs.PHASE_DRAIN)
    assert delivered == drained == 5
    tokens = sum(s[4]["tokens"] for s in loop if s[0] == obs.PHASE_BOOKKEEP)
    assert tokens == sum(len(c.tokens) for c in run.served.values())


def test_no_session_no_difference(params, run):
    """With no session open the same requests give the same tokens, and a
    submit is taken as before."""
    from tony_tpu.cli.serve import ServeApp

    assert not jax.profiler.TraceAnnotation.is_enabled()
    kw = dict(slots=3, max_len=64, block_size=4, prefill_chunk=8)
    again = _drain(SlotServer(params, TINY, stop_tokens=(65,), pad_id=255,
                              **kw), _requests(7, seed=1))
    assert ([c.tokens for c in again.values()]
            == [c.tokens for c in run.engine[0].values()])
    app = ServeApp(SlotServer(params, TINY, **kw))
    app.start()
    try:
        req = _requests(1, seed=3)[0]
        rid, ev = app.submit_async(req.prompt, req.max_new_tokens)
        assert ev.wait(60)
        assert (app.take_result(rid).tokens
                == next(iter(run.served.values())).tokens)
    finally:
        app.shutdown()


def test_phase_without_jax_is_the_shared_null_context(monkeypatch):
    monkeypatch.setitem(sys.modules, "jax.profiler", None)  # import fails
    obs._trace_annotation.cache_clear()
    try:
        span = obs.phase(obs.PHASE_IDLE, completions=1)
        assert span is obs.phase(obs.PHASE_ADMIT)
        with span as entered:
            assert entered is None
    finally:
        obs._trace_annotation.cache_clear()
    monkeypatch.undo()
    assert isinstance(obs.phase(obs.PHASE_IDLE), jax.profiler.TraceAnnotation)


def test_step_raises_what_it_raised_before(params):
    """A failing dispatch leaves step() as itself, through the phase."""
    server = SlotServer(params, TINY, slots=2, max_len=64, block_size=4)
    server.submit(_requests(1, seed=4)[0])
    server._chaos_crash_blocks = {1}
    with pytest.raises(RuntimeError, match="chaos: injected mid-decode"):
        server.run_until_drained()


def test_kv_blocks_read_never_pass_the_ring_and_equal_it_on_the_cpu(run):
    """The CPU engine's decode step is the einsum over the whole ring
    (``decode_kernel_engages`` is false here), so every processed block
    reads as many blocks as its slots' rings hold: one of 64 a slot."""
    spans = [s[4] for s in run.spans if s[0] == obs.PHASE_BOOKKEEP]
    assert spans
    for counts in spans:
        assert 0 <= counts["kv_blocks_read"] <= counts["kv_blocks_ring"]
        assert counts["kv_blocks_read"] == counts["kv_blocks_ring"]
        assert counts["kv_blocks_ring"] % 3 == 0
    assert sum(c["kv_blocks_ring"] for c in spans) > 0


def test_kv_block_count_where_the_kernel_engages(params, monkeypatch):
    """With the gate forced open the engine counts by the kernel's rule:
    a few blocks of short rows, not the ring."""
    from tony_tpu.models import serving

    monkeypatch.setattr(serving, "decode_kernel_engages",
                        lambda *a: True)
    monkeypatch.setattr(serving, "kv_block_k", lambda *a: 16)
    server = SlotServer(params, TINY, slots=3, max_len=64, block_size=4,
                        prefill_chunk=8)
    _drain(server, _requests(5, seed=5))
    ring_a_block = 3 * (64 // 16)
    blocks = server.kv_blocks_ring // ring_a_block
    assert blocks >= 3 and server.kv_blocks_ring == blocks * ring_a_block
    # prompts of 2-13 and up to 12 new tokens: a live row spans at most
    # ceil(26 / 16) + 1 = 3 of its 4 blocks, and some rows are idle
    assert blocks <= server.kv_blocks_read < server.kv_blocks_ring * 3 // 4


def test_state_row_count_where_the_kernel_engages(monkeypatch):
    """With the gate forced open the engine counts by the kernel's rule:
    the rows live at a block's last step, not the slots."""
    from tony_tpu.models import serving

    monkeypatch.setattr(serving, "state_kernel_engages", lambda *a: True)
    server = SlotServer(transformer.init(jax.random.PRNGKey(1), HYBRID),
                        HYBRID, slots=3, max_len=64, block_size=4,
                        prefill_chunk=8, stop_tokens=(65,), pad_id=255)
    _drain(server, _requests(5, seed=6, max_new=11))
    blocks = server.state_rows_held // 3
    assert blocks >= 3 and server.state_rows_held == blocks * 3
    # a row that stops inside a block is not live at its last step, so the
    # count stays under the rows whose state the blocks changed
    assert 0 < server.state_rows_read <= server.state_rows
    assert server.state_rows_read < server.state_rows_held


@pytest.mark.parametrize("window", (0, 5, 40))
@pytest.mark.parametrize("block_k, m_cap", [(16, 64), (16, 56), (64, 64)])
def test_live_kv_blocks_against_a_brute_force_mask(block_k, m_cap, window):
    """The block walk that ``flash_decode``'s index maps make, against the
    set of blocks in which the einsum's mask has a visible position."""
    from tony_tpu.ops.decode_attention import live_kv_blocks

    rng = np.random.default_rng(block_k + m_cap + window)
    n = 200
    offsets = rng.integers(0, m_cap, n).astype(np.int32)
    lengths = rng.integers(0, m_cap, n).astype(np.int32)
    lengths[:4] = (0, m_cap - 1, m_cap - 1, 0)
    offsets[:4] = (0, 0, m_cap - 1, m_cap - 1)
    active = rng.random(n) < 0.8
    first, count = live_kv_blocks(offsets, lengths, active, block_k=block_k,
                                  m_cap=m_cap, window=window)
    n_blocks = -(-m_cap // block_k)
    idx = np.arange(m_cap)
    for i in range(n):
        logical = (idx - offsets[i]) % m_cap
        seen = logical <= lengths[i]
        if window:
            seen &= logical > lengths[i] - window
        want = set(idx[seen] // block_k) if active[i] else set()
        walk = [(first[i] + j) % n_blocks for j in range(count[i])]
        assert len(walk) == len(set(walk)) and set(walk) == want, i


# ------------------------------------------------ the benchmark's readers

def test_host_spans_reads_the_recorded_chip_trace(tmp_path):
    shutil.copy(BENCH / "tests" / "small.xplane.pb", tmp_path)
    found = host_spans.spans("bench_", tmp_path)
    assert [s[0] for s in found] == ["bench_host_span"] * 3
    assert all(0.015 < s[3] * 1e-9 < 0.03 and s[4] == {} for s in found)
    assert len({s[1] for s in found}) == 1
    assert host_spans.spans("serve.", tmp_path) == []
    assert host_spans.spans("serve.", tmp_path / "nothing_here") == []


def _read(entry, trace_root, monkeypatch):
    monkeypatch.setattr(host_spans, "TRACE_ROOT", trace_root)
    stem, _, suffix = entry["name"].partition(".")
    reader = lib.load(f"layer_metrics/{stem}.py")
    return reader.read({"trace_window_s": 5.0}, suffix)


@pytest.mark.parametrize("entry", NEW_ENTRIES, ids=lambda m: m["name"])
def test_reader_of_each_new_entry(entry, run, tmp_path, monkeypatch):
    stem = entry["name"].partition(".")[0]
    assert (BENCH / "layer_metrics" / f"{stem}.py").is_file()
    assert entry["source"] == "program_span"
    # reason-open's traced 5 s hold no arrival in one run of fourteen
    # (0.53 requests/s), and rollout-closed's lie 13-18 s after its 32
    # clients began answers of 768 tokens and more, so in some runs none
    # has finished and sent again: a metric of the arrivals is listed in
    # neither
    assert entry["workloads"] == {
        "state_rows_advanced_pct": [REASON], "state_read_pct": [REASON],
        "expert_weights_read_pct": [ROLLOUT, LONGCTX, XING],
        "experts_touched_pct": [ROLLOUT, LONGCTX, XING],
        "window_kv_read_pct": [LONGCTX],
        "submit_lock_wait_ms": [CHAT]}.get(
            stem, [CHAT, REASON, ROLLOUT, LONGCTX, XING])
    # a program without the spans (the chip fixture; the parent commit)
    shutil.copy(BENCH / "tests" / "small.xplane.pb", tmp_path)
    assert _read(entry, tmp_path, monkeypatch) is None
    assert _read(entry, tmp_path / "no_trace", monkeypatch) is None
    value = _read(entry, run.dir, monkeypatch)
    if stem == "window_kv_read_pct":
        # no engine of this session has window layers: a window engine's
        # spans are read in tests/test_window_moe_serving.py
        assert value is None
        return
    assert isinstance(value, float) and value >= 0.0
    if entry["unit"] == "%" and "occupancy" in entry["name"]:
        assert value <= 100.0
    if entry["name"] in ("decode_kv_read_pct", "state_read_pct"):
        assert value == 100.0       # the CPU engine reads the whole ring,
        #                             and every slot's state
    if stem == "state_rows_advanced_pct":
        # of every engine's blocks x 3 slots, the hybrid engine's rows
        assert 0.0 < value < 100.0
    if stem in ("expert_weights_read_pct", "experts_touched_pct"):
        # of the routed engine's 16 experts a step: up to 3 rows x 4
        assert 0.0 < value <= 75.0


PART_ENTRIES = [m for m in json.loads((REPO / "BENCHMARK.json").read_text())
                ["per_layer"] if m["name"].startswith(
                    ("decode_step_part_ms.", "train_step_part_ms."))]


@pytest.mark.parametrize("entry", PART_ENTRIES, ids=lambda m: m["name"])
def test_part_readers_find_no_device_run_in_the_cpu_session(entry, run,
                                                            monkeypatch):
    """The session's programs are registered, but its trace holds no TPU
    plane: no whole run of a device program to sum by part."""
    monkeypatch.setattr(host_spans, "TRACE_ROOT", run.dir)
    stem, _, suffix = entry["name"].partition(".")
    reader = lib.load(f"layer_metrics/{stem}.py")
    facts = {"programs": {"decode": ["_decode_block"],
                          "train_step": ["jit_step"]},
             "chips": 1, "engine": {"block_size": 4}, "trace_window_s": 5.0}
    assert any(p.module == "jit__decode_block"
               for p in obs.PROGRAMS._programs.values())
    assert reader.read(facts, suffix) is None


def test_new_entries_are_the_thirteen_and_the_shares_are_disjoint(
        run, monkeypatch):
    assert len(NEW_ENTRIES) == 14      # with the window layers' reader
    monkeypatch.setattr(host_spans, "TRACE_ROOT", run.dir)
    pct = lib.load("layer_metrics/serve_loop_phase_pct.py")
    named = [name for names in pct.PHASES.values() for name in names]
    assert len(named) == len(set(named)) and set(named) <= set(obs.PHASES)
    assert {m["name"].partition(".")[2] for m in NEW_ENTRIES
            if m["name"].startswith("serve_loop_phase_pct.")} == set(pct.PHASES)


# ------------------------------- names that the device-trace readers match

def _train_step_name():
    from jax.sharding import Mesh

    from tony_tpu.train.step import create_train_step

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1, 1),
                ("data", "fsdp", "tensor", "seq"))
    bundle = create_train_step(TINY, mesh)
    tokens = jnp.zeros((2, 16), jnp.int32)
    lowered = bundle.step_fn.lower(bundle.params, bundle.opt_state,
                                   tokens, tokens)
    return lowered.as_text().split("module @", 1)[1].split()[0]


def _jit_name(module, attr):
    import importlib

    fn = getattr(importlib.import_module(module), attr)
    assert hasattr(fn, "lower"), f"{module}.{attr} is not jitted any more"
    return f"jit_{fn.__name__}"


@pytest.mark.parametrize("needle, reader, name_of", [
    ("_decode_block", "drivers/serve.py",
     lambda: _jit_name("tony_tpu.models.serving", "_decode_block")),
    ("jit_step", "drivers/train.py", _train_step_name),
    ("_flash_fwd", "layer_metrics/flash_fwd_roofline.py",
     lambda: _jit_name("tony_tpu.ops.attention", "_flash_fwd")),
    ("_flash_bwd", "layer_metrics/flash_bwd_roofline.py",
     lambda: _jit_name("tony_tpu.ops.attention", "_flash_bwd")),
], ids=["decode_block", "train_step", "flash_fwd", "flash_bwd"])
def test_program_names_the_trace_readers_match(needle, reader, name_of):
    """A rename on either side fails here, and does not silence
    ``decode_block_device_ms``, ``train_step_device_ms`` or a roofline."""
    assert f'"{needle}"' in (BENCH / reader).read_text()
    assert needle in name_of()
