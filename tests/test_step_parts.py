"""The device step by part (``observability.STEP_PARTS``): the scopes the
programs carry, the map a compiled program gives (``step_parts``), the
registry of the programs a process dispatched (``PROGRAMS``), and the
benchmark's two readers that sum a trace by part
(``layer_metrics/decode_step_part_ms.py``, ``train_step_part_ms.py`` over
``trace/step_parts.py``).

The CPU compiles every program here at toy widths; what a part costs on the
chip is the benchmark's ``--trace 1`` line.
"""

import contextlib
import dataclasses
import gc
import json
import re
import sys
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

step_parts_mod = lib.load("trace/step_parts.py")
host_spans = lib.load("trace/host_spans.py")

UNIFORM = transformer.TransformerConfig(
    vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
    d_ff=128, max_seq_len=128, dtype=jnp.float32,
)
HYBRID = transformer.TransformerConfig(
    vocab_size=256, d_model=64, n_layers=4, n_heads=4, n_kv_heads=4,
    d_ff=128, max_seq_len=128, dtype=jnp.float32, rope_theta=None,
    qk_norm=True, norm_order="post",
    layer_kinds=("linear", "linear", "linear", "full"),
    lin_heads=4, lin_key_dim=8, lin_value_dim=16,
)
ROUTED = transformer.TransformerConfig(
    vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=4,
    d_ff=128, max_seq_len=128, dtype=jnp.float32,
    layer_kinds=("latent", "latent"), lat_q_rank=48, lat_kv_rank=32,
    lat_nope_dim=16, lat_rope_dim=8, lat_v_dim=16, rope_interleave=True,
    mlp_kinds=("dense", "routed"), moe_experts=16, moe_top_k=4, moe_ff=24,
    moe_shared=1, moe_scale=2.5,
)
# the routed block with four streams of hyper-connections around it
HYPER = dataclasses.replace(ROUTED, hc_mult=4)
KINDS = {"uniform": UNIFORM, "hybrid": HYBRID, "routed": ROUTED,
         "hyper": HYPER}
# the parts each kind's decode block has: the count columns are the
# recurrent state's mark and the routed layers' expert counts
DECODE_PARTS = {
    "uniform": {"mixer", "mlp", "unembed"},
    "hybrid": {"mixer", "mlp", "unembed", "counts"},
    "routed": {"mixer", "mlp", "router", "experts", "unembed", "counts"},
    "hyper": {"mixer", "mlp", "router", "experts", "unembed", "counts",
              "hyper"},
}
TRAIN_PARTS = {"mixer", "mlp", "loss", "optimizer"}
CHAT, REASON = "mistral7b-v01.chat-open", "olmo-hybrid-7b.reason-open"
ROLLOUT, TRAIN = "joyai-llm-flash.rollout-closed", "mistral7b-v03.train-1chip"
LONGCTX, XING = "command-a-plus.longctx-closed", "xing4.longctx-closed"
ENGINE = dict(slots=3, max_len=64, block_size=4, prefill_chunk=8)


def _submit(server, seed):
    rng = np.random.default_rng(seed)
    for n in (3, 9, 12):
        server.submit(Request(prompt=rng.integers(0, 256, n, dtype=np.int32),
                              max_new_tokens=6))
    server.run_until_drained()


def _serve(cfg, seed=0):
    server = SlotServer(transformer.init(jax.random.PRNGKey(seed), cfg), cfg,
                        **ENGINE)
    _submit(server, seed)
    return server


def _mesh():
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1, 1),
                ("data", "fsdp", "tensor", "seq"))


def _train_once(cfg=UNIFORM):
    from tony_tpu.train.step import create_train_step

    bundle = create_train_step(cfg, _mesh())
    tokens = jnp.zeros((2, 16), jnp.int32)
    out = bundle.step_fn(bundle.params, bundle.opt_state, tokens, tokens)
    jax.block_until_ready(out)
    return bundle


@pytest.fixture(scope="module")
def served():
    """Each kind served, and a train step run, on a registry of their own:
    -> {kind: the registry}."""
    out = {}
    for kind, cfg in {**KINDS, "train": None}.items():
        with pytest.MonkeyPatch.context() as mp:
            registry = obs.ProgramRegistry()
            mp.setattr(obs, "PROGRAMS", registry)
            if cfg is None:
                _train_once()
            else:
                _serve(cfg)
            out[kind] = registry
    return out


# ------------------------------------------------------------- the map

SNIPPET = """HloModule jit_step, is_scheduled=true

%fused_computation (param_0: f32[8,16]) -> f32[8,16] {
  %param_0 = f32[8,16]{1,0} parameter(0)
  ROOT %add.2 = f32[8,16]{1,0} add(%param_0, %param_0), metadata={op_name="jit(step)/while/body/closed_call/mlp/add" stack_frame_id=6}
}

ENTRY %main.9 (x.1: f32[8,16]) -> f32[8,16] {
  %x.1 = f32[8,16]{1,0} parameter(0), metadata={op_name="x"}
  %dot.1 = f32[8,16]{1,0} dot(%x.1, %x.1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/jvp(mlp)/experts/dot_general"}
  %dot.2 = f32[8,16]{1,0} dot(%dot.1, %x.1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/transpose(jvp(mixer))/dot_general"}
  %multiply_add_fusion = f32[8,16]{1,0} fusion(%dot.2), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/while/body/closed_call/mlp/add"}
  %copy.3 = f32[8,16]{1,0} copy(%multiply_add_fusion)
  %reduce.4 = f32[8,16]{1,0} add(%copy.3, %x.1), metadata={op_name="jit(step)/loss/jit(blockwise_cross_entropy)/while/body/add"}
  %fusion.9 = f32[8,16]{1,0} fusion(%reduce.4), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/jit(linear_mixer)/mlp_tail/add"}
  ROOT %add.5 = f32[8,16]{1,0} add(%fusion.9, %x.1), metadata={op_name="jit(step)/mixer/optimizer/add"}
}
"""


def test_step_parts_are_pinned_and_each_is_read():
    """The parts, and each read by the benchmark reader whose program has
    it (``hyper`` by ``hyper_decode_ms``, in the one cell with streams);
    the twelve entries name a reader's part each."""
    assert obs.STEP_PARTS == ("mixer", "mlp", "router", "experts", "unembed",
                              "loss", "optimizer", "counts", "hyper")
    decode = lib.load("layer_metrics/decode_step_part_ms.py").PARTS
    train = lib.load("layer_metrics/train_step_part_ms.py").PARTS
    assert set(decode) | set(train) | {"hyper"} \
        == set(obs.STEP_PARTS) | {obs.STEP_OTHER}
    (hyper,) = [m for m in json.loads((REPO / "BENCHMARK.json").read_text())
                ["per_layer"] if m["name"] == "hyper_decode_ms"]
    assert (hyper["source"], hyper["layer"], hyper["workloads"]) == (
        "device_trace", "model step", [XING])
    entries = [m for m in json.loads((REPO / "BENCHMARK.json").read_text())
               ["per_layer"] if m["name"].startswith(("decode_step_part_ms.",
                                                     "train_step_part_ms."))]
    assert len(entries) == 12
    serve = [CHAT, REASON, ROLLOUT, LONGCTX, XING]
    for m in entries:
        stem, _, part = m["name"].partition(".")
        assert part in (decode if stem == "decode_step_part_ms" else train)
        assert (m["source"], m["layer"], m["unit"], m["better"]) == (
            "device_trace", "model step", "ms", "lower")
        if stem == "train_step_part_ms":
            assert (m["moves"], m["workloads"]) == ("train_tokens_per_s",
                                                    [TRAIN])
        else:
            assert m["moves"] == "token_gap_p50_ms"
            assert m["workloads"] == {
                "router": [ROLLOUT, LONGCTX, XING],
                "experts": [ROLLOUT, LONGCTX, XING],
                "counts": [REASON, ROLLOUT, LONGCTX, XING]}.get(part, serve)


def test_step_parts_of_a_fixed_module():
    """The innermost part on the path wins, a backward counts in its
    forward's part, a fusion in its root's, and a word that only contains
    a part's name is no part."""
    assert obs.step_parts(SNIPPET) == {
        "param_0": "other", "add.2": "mlp", "x.1": "other",
        "dot.1": "experts", "dot.2": "mixer", "multiply_add_fusion": "mlp",
        "copy.3": "other", "reduce.4": "loss", "fusion.9": "other",
        "add.5": "optimizer"}


# ------------------------------------------------- the programs' scopes

def _top_level_share_other(text):
    """The share of the instructions outside fused computations, plumbing
    left out, whose part is "other"."""
    parts = obs.step_parts(text)
    loops = set(re.findall(r"(?:body|condition)=%([\w.\-]+)", text))
    names, current = [], None
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if head:
            current = "ENTRY" if head.group(1) else head.group(2)
            continue
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = .*? ([a-z][a-z0-9\-]*)\(",
                     line)
        if m and (current == "ENTRY" or current in loops) and m.group(2) not in (
                "parameter", "constant", "get-tuple-element", "tuple",
                "bitcast", "copy", "while"):
            names.append(m.group(1))
    return sum(parts[n] == obs.STEP_OTHER for n in names) / max(1, len(names))


@pytest.mark.parametrize("kind", sorted(DECODE_PARTS) + ["train"])
def test_every_part_is_in_the_compiled_program(served, kind):
    module = "jit_step" if kind == "train" else "jit__decode_block"
    (program,) = [p for p in served[kind]._programs.values()
                  if p.module == module]
    parts = program.parsed()[0]
    want = TRAIN_PARTS if kind == "train" else DECODE_PARTS[kind]
    assert want <= set(parts.values()) <= set(obs.STEP_PARTS) | {"other"}
    if kind == "uniform":
        assert "counts" not in set(parts.values())
    text = program.fn.lower(*program.args, **program.statics).compile().as_text()
    share = _top_level_share_other(text)
    print(f"{kind}: {share:.1%} of the top-level instructions are 'other'")
    assert share < 0.6


@pytest.mark.parametrize("kind", ["routed", "train"])
def test_the_scopes_change_no_instruction(kind, monkeypatch):
    """The same program lowers to the same text without the scopes: they
    are metadata, and the StableHLO the chip compiles is the one it was."""
    def lowered():
        if kind == "train":
            from tony_tpu.train.step import create_train_step

            bundle = create_train_step(UNIFORM, _mesh())
            tokens = jnp.zeros((2, 16), jnp.int32)
            return bundle.step_fn.lower(bundle.params, bundle.opt_state,
                                        tokens, tokens).as_text()
        registry = obs.ProgramRegistry()
        with monkeypatch.context() as mp:
            mp.setattr(obs, "PROGRAMS", registry)
            _serve(ROUTED)
        (p,) = [p for p in registry._programs.values()
                if p.module == "jit__decode_block"]
        return p.fn.lower(*p.args, **p.statics).as_text()

    with_scopes = lowered()
    jax.clear_caches()
    try:
        monkeypatch.setattr(jax, "named_scope",
                            lambda name: contextlib.nullcontext())
        without = lowered()
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert with_scopes == without


# ------------------------------------------------------------ the registry

def test_a_server_registers_its_programs_and_lowers_none(monkeypatch):
    registry = obs.ProgramRegistry()
    monkeypatch.setattr(obs, "PROGRAMS", registry)
    server = _serve(ROUTED, seed=3)
    modules = sorted(p.module for p in registry._programs.values())
    # one decode variant; the admission widths the three prompts took
    assert modules.count("jit__decode_block") == 1
    assert "jit__prefill_batch" in modules
    assert all(p._parsed is None for p in registry._programs.values())
    # the same variants again are a set look-up each, not more entries
    _submit(server, seed=5)
    assert sorted(p.module for p in registry._programs.values()) == modules
    parts = obs.program_parts("_decode_block")
    assert parts and set(parts.values()) >= DECODE_PARTS["routed"]
    assert all(p._parsed is None for p in registry._programs.values()
               if p.module != "jit__decode_block")


def test_the_registry_keeps_no_array(monkeypatch):
    registry = obs.ProgramRegistry()
    monkeypatch.setattr(obs, "PROGRAMS", registry)
    gc.collect()
    before = {id(a) for a in jax.live_arrays()}
    server = _serve(HYBRID, seed=4)
    assert registry._programs
    del server
    gc.collect()
    held = [x for p in registry._programs.values()
            for x in jax.tree.leaves((p.args, p.statics))]
    assert not any(isinstance(x, jax.Array) for x in held)
    # what outlives the server is the dispatch tracker's last reaped block
    # (its reaper thread's own reference), never a program's argument
    left = [a for a in jax.live_arrays() if id(a) not in before]
    args = {(x.shape, x.dtype) for x in held if hasattr(x, "shape")}
    assert not [a for a in left if (a.shape, a.dtype) in args]
    assert sum(a.nbytes for a in left) <= 1024
    # and its map is still there to be had after the server is gone
    assert obs.program_parts("jit__decode_block") is not None


def _variant(x, *, part):
    with jax.named_scope(part):
        return jnp.sin(x) * 2.0


def test_program_parts_tells_variants_apart_or_says_none(monkeypatch):
    registry = obs.ProgramRegistry()
    monkeypatch.setattr(obs, "PROGRAMS", registry)
    fn = jax.jit(_variant, static_argnames="part")
    a, b = jnp.ones((8, 16)), jnp.ones((4, 16))
    registry.register(fn, (a,), {"part": "mixer"})
    registry.register(fn, (b,), {"part": "mlp"})
    registry.register(fn, (a,), {"part": "mixer"})       # the same: once
    assert len(registry._programs) == 2

    def ops(x, part):
        text = fn.lower(x, part=part).compile().as_text()
        entry = text[text.index("ENTRY"):]
        return {line.strip().removeprefix("ROOT ") for line in
                entry.splitlines()[1:] if line.strip().startswith(("%", "ROOT"))}

    mixer = obs.program_parts("jit__variant(123)", ops(a, "mixer"))
    mlp = obs.program_parts("jit__variant(456)", ops(b, "mlp"))
    assert "mixer" in mixer.values() and "mlp" not in mixer.values()
    assert "mlp" in mlp.values() and "mixer" not in mlp.values()
    # no runs to go by, and two maps that differ: no guess
    assert obs.program_parts("_variant") is None
    # a third variant of the first one's shapes: its runs look the same
    registry.register(fn, (a,), {"part": "loss"})
    assert obs.program_parts("jit__variant(123)", ops(a, "mixer")) is None
    assert obs.program_parts("jit__nothing_like_it") is None


def test_a_capture_carries_the_programs_map(tmp_path, monkeypatch):
    from tony_tpu.cli.serve import ServeApp

    registry = obs.ProgramRegistry()
    monkeypatch.setattr(obs, "PROGRAMS", registry)
    fn = jax.jit(_variant, static_argnames="part")
    registry.register(fn, (jnp.ones((8, 16)),), {"part": "mlp"})
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, *a, **k: Path(d).mkdir(parents=True,
                                                         exist_ok=True))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    engine = types.SimpleNamespace(shutdown=lambda: None)
    out = ServeApp(engine, trace_dir=str(tmp_path)).capture_profile(0.01)
    assert "step_parts.json" in out["files"]
    maps = json.loads((Path(out["dir"]) / "step_parts.json").read_text())
    assert list(maps) == ["jit__variant"]
    assert "mlp" in maps["jit__variant"].values()


# ------------------------------------------------------------- the readers

def _event(name, start, dur):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def _trace(modules, ops):
    line = lambda name, evs: types.SimpleNamespace(  # noqa: E731
        name=name, events=[_event(*e) for e in evs])
    return types.SimpleNamespace(planes=[
        types.SimpleNamespace(name="/host:CPU", lines=[]),
        types.SimpleNamespace(name="/device:TPU:0", lines=[
            line("XLA Modules", modules), line("XLA Ops", ops)])])


DECODE = "jit__decode_block(7)"
MAP = {"fusion.1": "mixer", "fusion.2": "mlp", "gmm.3": "experts",
       "sort.4": "router", "reduce.5": "unembed", "add.6": "counts",
       "copy.7": "other"}


def _ops(t0):
    """One run's operations, from ``t0`` (ns): 10 + 20 + 30 + 5 + 15 + 2
    named, 6 under no part, a loop wrapper around them; the run is 100."""
    return [("%while.9 = (s32[]) while(s32[] %c)", t0 + 1, 95),
            ("%fusion.1 = bf16[2]{0} fusion(bf16[2]{0} %a)", t0 + 1, 10),
            ("%fusion.2 = bf16[2]{0} fusion(bf16[2]{0} %a)", t0 + 11, 20),
            ("%gmm.3 = bf16[2]{0} custom-call(bf16[2]{0} %a)", t0 + 31, 30),
            ("%sort.4 = s32[2]{0} sort(s32[2]{0} %a)", t0 + 61, 5),
            ("%reduce.5 = f32[2]{0} reduce(f32[2]{0} %a)", t0 + 66, 15),
            ("%add.6 = s32[] add(s32[] %a, s32[] %b)", t0 + 81, 2),
            ("%copy.7 = s32[2]{0} copy(s32[2]{0} %a)", t0 + 83, 6)]


@pytest.fixture
def synthetic(monkeypatch, tmp_path):
    """A trace of five programs on one chip: a decode run cut by the
    trace's start, two whole decode runs around an admission, and one cut
    by its end; the decode block's map as ``program_parts`` would give it."""
    modules = [(DECODE, 0, 80), (DECODE, 100, 100),
               ("jit__prefill_batch(8)", 200, 100), (DECODE, 300, 100),
               (DECODE, 400, 40)]
    ops = []
    for _, t0, _ in modules:
        ops += _ops(t0)
    asked = []
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(b"")
    monkeypatch.setattr(host_spans, "TRACE_ROOT", tmp_path)
    monkeypatch.setattr(host_spans, "parse",
                        lambda p: (_trace(modules, ops), []))
    monkeypatch.setattr(obs, "program_parts", lambda name, seen: asked.append(
        (name, seen)) or (MAP if name.startswith("jit__decode_block") else None))
    step_parts_mod._tally.cache_clear()
    yield asked
    step_parts_mod._tally.cache_clear()


def test_part_sums_over_the_whole_runs(synthetic):
    facts = {"programs": {"decode": ["_decode_block"]}, "chips": 1,
             "engine": {"block_size": 4}}
    runs, seconds, parts = step_parts_mod.tally(facts, "decode")
    # the first and the last program are left out: two whole runs of 100
    assert runs == 2 and seconds == pytest.approx(200e-9)
    assert parts == pytest.approx({
        "mixer": 20e-9, "mlp": 40e-9, "experts": 60e-9, "router": 10e-9,
        "unembed": 30e-9, "counts": 4e-9, "other": 12e-9})
    # the operations cover 88 of a run's 100 ns, the wrapper not counted
    assert sum(parts.values()) == pytest.approx(176e-9)
    ((name, seen),) = synthetic
    assert name == DECODE and len(seen) == 7
    reader = lib.load("layer_metrics/decode_step_part_ms.py")
    got = {p: reader.read(facts, p) for p in reader.PARTS}
    steps = 2 * 4
    assert got["mixer"] == pytest.approx(20e-9 / steps * 1e3)
    assert got["experts"] == pytest.approx(60e-9 / steps * 1e3)
    # "other": the runs' time less the named parts, so the parts add up
    assert got["other"] == pytest.approx((200 - 164) * 1e-9 / steps * 1e3)
    assert sum(got.values()) == pytest.approx(200e-9 / steps * 1e3)
    assert reader.read(facts, "loss") is None


def test_part_readers_say_none_where_they_cannot_tell(synthetic, monkeypatch):
    train = lib.load("layer_metrics/train_step_part_ms.py")
    decode = lib.load("layer_metrics/decode_step_part_ms.py")
    # no such program in the trace
    assert train.read({"programs": {"train_step": ["jit_step"]},
                       "chips": 1}, "mixer") is None
    # no map of the program: another process's, or the parent's program
    step_parts_mod._tally.cache_clear()
    monkeypatch.setattr(obs, "program_parts", lambda name, seen: None)
    facts = {"programs": {"decode": ["_decode_block"]}, "chips": 1,
             "engine": {"block_size": 4}}
    assert decode.read(facts, "mixer") is None
    step_parts_mod._tally.cache_clear()
    monkeypatch.delattr(obs, "program_parts")
    assert decode.read(facts, "other") is None
    assert decode.read({"chips": 1}, "mixer") is None


@pytest.mark.parametrize("reader", ["decode_step_part_ms",
                                    "train_step_part_ms"])
def test_part_readers_read_nothing_in_the_recorded_chip_trace(
        reader, tmp_path, monkeypatch):
    """The chip fixture holds other programs than the step's."""
    import shutil

    shutil.copy(BENCH / "tests" / "small.xplane.pb", tmp_path)
    monkeypatch.setattr(host_spans, "TRACE_ROOT", tmp_path)
    read = lib.load(f"layer_metrics/{reader}.py")
    facts = {"programs": {"decode": ["_decode_block"],
                          "train_step": ["jit_step"]},
             "chips": 1, "engine": {"block_size": 16}}
    assert all(read.read(facts, p) is None for p in read.PARTS)
    # the recorded programs themselves are not this process's: no map
    step_parts_mod._tally.cache_clear()
    facts["programs"] = {"decode": ["jit_bench_scan"],
                         "train_step": ["jit_bench_scan"]}
    assert all(read.read(facts, p) is None for p in read.PARTS)
