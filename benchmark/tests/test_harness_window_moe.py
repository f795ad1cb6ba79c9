"""CPU rehearsal of the window-and-global, routed-expert decoder's driver
(run by hand with the other rehearsals: ``python -m pytest benchmark/tests
-q``; not part of tier-1).

``fixture_window_moe/`` is laid out as ``fixture/`` is, with a
``BENCHMARK.json`` of its own: a toy of one period (three window layers
and a global one) through ``drivers/serve_window_moe.py`` end to end in a
closed loop, the schema of the last line, the routed comparison failing
on each control, a token altered where it is produced, the two readers on recorded facts, and the cost
arithmetic against a hand count at the published widths.
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

FIXTURE = HERE / "fixture_window_moe"
CELL = "tiny-window-moe.closed"
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
SEED = 2 ** 31 + 17         # the driver's seeds pass 32 signed bits


@pytest.fixture()
def bench():
    return lib.read_json(FIXTURE / "BENCHMARK.json")


def test_rehearsal_is_correct_and_well_formed(bench):
    line = run.execute(bench, CELL, SEED, 1.5, False, dict(CPU), root=FIXTURE,
                       control="w8,f8")
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
    for control in ("fault_control_w8", "fault_control_f8",
                    "fault_router_select_without_bias",
                    "fault_router_weights_with_bias", "fault_token_altered"):
        assert not notes[control]["correct"], (control, notes[control])
    # float32 on the CPU: the server's choices are the reference's own
    assert notes["own_routing"]["choices_differing_pct"] == 0.0
    assert line["compared"]["tokens_below_own_first"]["value"] == 0


def test_fault_token_altered_where_it_is_produced(bench, monkeypatch):
    """Every token one past the greedy choice: below the program's own
    first at every position, whatever the logits read."""
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
    below = line["compared"]["tokens_below_own_first"]["value"]
    assert below == line["notes"]["served_tokens_compared"] > 0


def _facts(cfg, counters):
    return {"cfg": cfg, "engine": cfg["engine"], "counters": counters,
            "programs": {"decode": ["_decode_block"]},
            "decode_tokens": 16 * 160, "decode_context_sum": 16 * 160 * 5000.0,
            "peaks": {"hbm_bytes_per_s": 819e9},
            "trace": {"programs": {"_decode_block": {
                "runs": 10, "seconds": 10 * 16 * 0.0125}}}}


def test_readers_on_recorded_facts(monkeypatch):
    cfg = lib.read_json(HERE.parent / "configs/command-a-plus-05-2026-serve.json")
    costs = lib.load("costs/window_moe_decoder.py")
    reduce = lib.load("trace/reduce.py")
    spans = lib.load("trace/host_spans.py")
    monkeypatch.setattr(
        reduce, "program_time",
        lambda trace, names: (trace["programs"][names[0]]["runs"],
                              trace["programs"][names[0]]["seconds"]))
    rows = [("serve.step.sync", "t", 0.0, 1.0, {"blocks": 2}),
            ("serve.step.bookkeep", "t", 1.0, 1.0,
             {"live_positions": 2 * 16 * 5000, "window_positions": 2 * 16 * 3600,
              "window_blocks_read": 2 * 16 * 15,
              "window_blocks_context": 2 * 16 * 20})]
    monkeypatch.setattr(spans, "spans", lambda prefix, trace_dir=None: [
        r for r in rows if r[0].startswith(prefix)])
    counters = {"blocks_dispatched": 10, "experts_held": 10 * 16 * 4 * 16,
                "experts_touched": 10 * 16 * 4 * 10}
    roof = lib.load("layer_metrics/window_moe_decode_hbm_roofline.py")
    got = roof.read(_facts(cfg, counters), "")
    least = costs.decode_least_bytes(cfg, 16 * 5000.0, 16 * 3600.0, 16.0,
                                     4 * 10.0)
    assert got == pytest.approx(100.0 * least / 819e9 / 0.0125)
    assert 70.0 < got < 100.0
    share = lib.load("layer_metrics/window_kv_read_pct.py").read({}, "")
    assert share == pytest.approx(75.0)
    # a program without the counts (the parent's) gives nothing to read
    monkeypatch.setattr(spans, "spans", lambda prefix, trace_dir=None: [
        ("serve.step.bookkeep", "t", 0.0, 1.0, {"tokens": 3})])
    assert roof.read(_facts(cfg, counters), "") is None
    assert lib.load("layer_metrics/window_kv_read_pct.py").read({}, "") is None


def test_costs_match_a_hand_count():
    costs = lib.load("costs/window_moe_decoder.py")
    cfg = lib.read_json(HERE.parent / "configs/command-a-plus-05-2026-serve.json")
    attn = 4096 * 16384 * 2 + 4096 * 1024 * 2
    expert = 3 * 4096 * 4096
    assert costs.attention_params(cfg) == attn == 142_606_336
    assert costs.expert_params(cfg) == expert == 50_331_648
    assert costs.router_params(cfg) == 4096 * 128
    layer = attn + 4096 + 4096 * 128 + 20 * expert
    assert costs.n_params(cfg) == 4 * layer + 4096 + 4096 * 32768
    assert 4 * layer * 2 / 1e9 == pytest.approx(9.20, abs=0.01)
    other = 2 * (4 * (attn + 4096 + 4 * expert) + 4096 + 4096 * 32768) \
        + 4 * 4 * 4096 * 128
    assert costs.other_weight_bytes_step(cfg) == other
    assert costs.kv_bytes_position(cfg) == 4096
    assert costs.decode_least_bytes(cfg, 50_000, 40_000, 16, 40) == \
        other + 40 * 2 * expert + 4096 * (50_000 + 3 * 40_000) + 16 * 4096 * 2
    token = 2 * 4 * (attn + 4096 * 128 + 12 * expert)
    assert costs.decode_flops(cfg, 6000) == token + 4 * 128 * 128 * (
        6000 + 3 * 4096) + 2 * 4096 * 32768
    assert costs.prefill_flops(cfg, 3) == 3 * token + 4 * 128 * 128 * 4 * (
        1 + 2 + 3)
