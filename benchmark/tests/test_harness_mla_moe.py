"""CPU rehearsal of the latent-attention, routed-expert decoder's driver
(run by hand with the other rehearsals: ``python -m pytest benchmark/tests
-q``; not part of tier-1).

``fixture_mla_moe/`` is laid out as ``fixture/`` is, with a
``BENCHMARK.json`` of its own: a toy of a dense layer and two routed ones
through ``drivers/serve_mla_moe.py`` end to end in a closed loop, the schema
of the last line, the routed comparison failing on each planted router
fault, on int8 weights and on an altered token, the three readers on
recorded facts, and the cost arithmetic against a hand count at the
published widths.
"""

from __future__ import annotations

import json
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parent.parent))

import lib  # noqa: E402
import run  # noqa: E402

FIXTURE = HERE / "fixture_mla_moe"
CELL = "tiny-mla-moe.closed"
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
SEED = 2 ** 31 + 13         # the driver's seeds pass 32 signed bits


@pytest.fixture()
def bench():
    return lib.read_json(FIXTURE / "BENCHMARK.json")


def test_rehearsal_is_correct_and_well_formed(bench):
    line = run.execute(bench, CELL, SEED, 1.5, False, dict(CPU), root=FIXTURE,
                       control="w8")
    json.dumps(line)
    assert list(line)[-1] == "compared"
    assert line["correct"], line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {"token_gap_p50_ms", "setup_s"} <= set(line["metrics"])
    notes = line["notes"]
    counters = notes["counters"]
    assert counters["compiles"] == 0
    assert 0 < counters["experts_touched"] <= counters["experts_read"] \
        <= counters["experts_held"]
    assert notes["positions_routed"] > notes["served_tokens_compared"] > 0
    # the saturated drain: the clients outlast the window (how many they
    # sent after its end is a matter of timing at this size)
    assert notes["sent_after_the_window_and_abandoned"] >= 0
    # every control with a verdict of its own against the toy's limits: a
    # router that selects without the bias fails by the margin (its logits
    # are its own model's, self-consistent), one that weighs with it and
    # int8 weights by the logits
    assert not notes["fault_control_w8"]["correct"], notes["fault_control_w8"]
    select = notes["fault_router_select_without_bias"]
    margin = select["compared"]["routing_margin_gap"]
    assert not select["correct"] and margin["value"] > margin["limit"], select
    weigh = notes["fault_router_weights_with_bias"]
    assert not weigh["correct"], weigh
    assert not notes["fault_token_altered"]["correct"]
    # float32 on the CPU: the server's choices are the reference's own
    assert notes["own_routing"]["choices_differing_pct"] == 0.0


def test_fault_token_altered_where_it_is_produced(bench, monkeypatch):
    import jax
    import tony_tpu.models.serving as serving

    real = serving.sample_token
    monkeypatch.setattr(
        serving, "sample_token",
        lambda logits, *a, **k: (real(logits, *a, **k) + 1)
        % logits.shape[-1])
    jax.clear_caches()
    try:
        line = run.execute(bench, CELL, SEED, 1.5, False, dict(CPU),
                           root=FIXTURE)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert not line["correct"]
    assert line["compared"]["logit_gap_max"]["value"] > \
        line["compared"]["logit_gap_max"]["limit"]


def test_fault_router_planted_in_the_program(bench, monkeypatch):
    """The program's own router weighing with the bias: the served tokens
    are another model's, and the comparison says so."""
    import jax
    import jax.numpy as jnp
    import tony_tpu.parallel.routed_experts as routed

    def route(x, router, router_bias, *, top_k, scale):
        s = jax.nn.sigmoid(jnp.matmul(x.astype(jnp.float32), router))
        vals, chosen = jax.lax.top_k(s + router_bias, top_k)
        w = vals / (jnp.sum(vals, -1, keepdims=True) + 1e-20) * scale
        return chosen.astype(jnp.int32), w

    monkeypatch.setattr(routed, "route", route)
    jax.clear_caches()
    try:
        line = run.execute(bench, CELL, SEED, 1.5, False, dict(CPU),
                           root=FIXTURE)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert not line["correct"], line["compared"]


def test_a_layer_alone_equals_the_layer_among_the_others():
    """To a rounding of the last float32 bit: two programs that scale the
    same draw may differ by it."""
    import jax.numpy as jnp
    import numpy as np

    drv = lib.load("drivers/serve_mla_moe.py")
    w = lib.load("weights/mla_moe_decoder.py")
    cfg = lib.read_json(FIXTURE / "benchmark/configs/tiny-mla-moe.json")
    tree = drv.program_params(cfg, SEED, jnp.float32)
    one = w.layer(w.seed_key(SEED), cfg, 2, "routed", jnp.float32)
    mixer, mlp = drv.program_layer(cfg, one, "routed")
    same = lambda a, b: np.testing.assert_allclose(a, b, rtol=3e-7, atol=0)
    for k, v in mixer.items():
        same(tree["layers"]["latent"][k][2], v)
    for k, v in mlp.items():
        same(tree["layers"]["routed"][1][k], v)
    # a chip's share of the experts holds the whole layer's numbers for them
    part = w.layer(w.seed_key(SEED), cfg, 2, "routed", jnp.float32, (4, 8))
    same(part["experts_up"], one["experts_up"][4:12])


def _facts(cfg, counters):
    return {"cfg": cfg, "engine": cfg["engine"], "counters": counters,
            "programs": {"decode": ["_decode_block"]},
            "decode_tokens": 32 * 160, "decode_context_sum": 32 * 160 * 1000.0,
            "peaks": {"hbm_bytes_per_s": 819e9},
            "trace": {"programs": {"_decode_block": {
                "runs": 10, "seconds": 10 * 16 * 0.011}}}}


def test_readers_on_recorded_facts(monkeypatch):
    cfg = lib.read_json(HERE.parent / "configs/joyai-llm-flash-serve.json")
    costs = lib.load("costs/mla_moe_decoder.py")
    reduce = lib.load("trace/reduce.py")
    monkeypatch.setattr(
        reduce, "program_time",
        lambda trace, names: (trace["programs"][names[0]]["runs"],
                              trace["programs"][names[0]]["seconds"]))
    counters = {"blocks_dispatched": 10, "experts_held": 10 * 16 * 4 * 256,
                "experts_touched": 10 * 16 * 4 * 163}
    roof = lib.load("layer_metrics/moe_decode_hbm_roofline.py")
    got = roof.read(_facts(cfg, counters), "")
    least = costs.decode_least_bytes(cfg, 32 * 1000.0, 32.0, 4 * 163.0)
    assert got == pytest.approx(100.0 * least / 819e9 / 0.011)
    assert 75.0 < got < 85.0
    # a program without the counters (the parent's) gives nothing to read
    assert roof.read(_facts(cfg, {"blocks_dispatched": 10}), "") is None
    spans = lib.load("trace/host_spans.py")
    rows = [("serve.step.bookkeep", "t", 0.0, 1.0,
             {"experts_held": 16384, "experts_read": 11000,
              "experts_touched": 10400}),
            ("serve.step.bookkeep", "t", 2.0, 1.0,
             {"experts_held": 16384, "experts_read": 12000,
              "experts_touched": 10500})]
    monkeypatch.setattr(spans, "spans", lambda prefix, trace_dir=None: rows)
    read = lib.load("layer_metrics/expert_weights_read_pct.py").read({}, "")
    touched = lib.load("layer_metrics/experts_touched_pct.py").read({}, "")
    assert read == pytest.approx(100.0 * 23000 / 32768)
    assert touched == pytest.approx(100.0 * 20900 / 32768) and touched <= read
    monkeypatch.setattr(spans, "spans", lambda prefix, trace_dir=None: [
        ("serve.step.bookkeep", "t", 0.0, 1.0, {"tokens": 3})])
    assert lib.load("layer_metrics/experts_touched_pct.py").read({}, "") \
        is None


def test_costs_match_a_hand_count():
    costs = lib.load("costs/mla_moe_decoder.py")
    cfg = lib.read_json(HERE.parent / "configs/joyai-llm-flash-serve.json")
    attn = (2048 * 1536 + 1536 * 32 * 192 + 2048 * 576 + 512 * 32 * 256
            + 32 * 128 * 2048)
    expert = 3 * 2048 * 768
    assert costs.attention_params(cfg) == attn == 26_345_472
    assert costs.expert_params(cfg) == expert == 4_718_592
    assert costs.row_width(cfg) == 576
    norms = 2 * 2048 + 1536 + 512
    routed = 2048 * 256 + 256 + 257 * expert
    assert costs.n_params(cfg) == 5 * (attn + norms) + 3 * 2048 * 7168 \
        + 4 * routed + 2048 + 2 * 2048 * 129280
    other = 2 * (5 * attn + 3 * 2048 * 7168 + 4 * expert + 2048 * 129280) \
        + 4 * 4 * 2048 * 256
    assert costs.other_weight_bytes_step(cfg) == other
    assert costs.latent_bytes_position(cfg) == 576 * 5 * 2
    assert costs.decode_least_bytes(cfg, 32000, 32, 652) == \
        other + 652 * 2 * expert + 32000 * 5760 + 32 * 2048 * 2
    mlp = 2 * (3 * 2048 * 7168 + 4 * (2048 * 256 + 9 * expert))
    layer = 2 * (2048 * 1536 + 1536 * 32 * 192 + 2048 * 576 + 32 * 128 * 2048
                 + 32 * 128 * 512 + 32 * 512 * 128 + 900 * 32 * (576 + 512))
    assert costs.decode_flops(cfg, 900) == 5 * layer + mlp \
        + 2 * 2048 * 129280
    prompt = 2 * attn + 2 * 50.5 * 32 * (192 + 128)
    assert costs.prefill_flops(cfg, 100) == 100 * (5 * prompt + mlp)
