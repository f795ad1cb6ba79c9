"""CPU rehearsal of the hyper-connected latent decoder's driver (run by hand
with the other rehearsals: ``python -m pytest benchmark/tests -q``; not
part of tier-1).

``fixture_hyper_mla_moe/`` is laid out as ``fixture/`` is, with a
``BENCHMARK.json`` of its own: a toy of two leading dense layers and two
routed ones, four streams and YaRN, through
``drivers/serve_hyper_mla_moe.py`` end to end in a closed loop, the schema
of the last line, the routed comparison failing on each control and on
both planted hyper-connection faults, the two readers on recorded facts,
and the cost arithmetic against a hand count at the published widths.
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

FIXTURE = HERE / "fixture_hyper_mla_moe"
CELL = "tiny-hyper-mla-moe.closed"
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
SEED = 2 ** 31 + 17         # the driver's seeds pass 32 signed bits
PUBLISHED = HERE.parent / "configs/xing4.0-29b-a4b-serve.json"


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
    assert notes["counters"]["compiles"] == 0
    assert notes["positions_routed"] > notes["served_tokens_compared"] > 0
    for control in ("fault_control_w8", "fault_router_select_without_bias",
                    "fault_router_weights_with_bias", "fault_token_altered",
                    "fault_hyper_h_res_identity", "fault_hyper_sinkhorn_one"):
        assert not notes[control]["correct"], (control, notes[control])
    assert notes["own_routing"]["choices_differing_pct"] == 0.0


def _facts(cfg, counters):
    return {"cfg": cfg, "engine": cfg["engine"], "counters": counters,
            "chips": 1, "programs": {"decode": ["_decode_block"]},
            "decode_tokens": 16 * 160, "decode_context_sum": 16 * 160 * 4200.0,
            "peaks": {"hbm_bytes_per_s": 819e9},
            "trace": {"programs": {"_decode_block": {
                "runs": 10, "seconds": 10 * 16 * 0.009}}}}


def test_readers_on_recorded_facts(monkeypatch):
    cfg = lib.read_json(PUBLISHED)
    costs = lib.load("costs/hyper_mla_moe_decoder.py")
    reduce = lib.load("trace/reduce.py")
    parts = lib.load("trace/step_parts.py")
    monkeypatch.setattr(
        reduce, "program_time",
        lambda trace, names: (trace["programs"][names[0]]["runs"],
                              trace["programs"][names[0]]["seconds"]))
    counters = {"blocks_dispatched": 10, "experts_held": 10 * 16 * 4 * 64,
                "experts_touched": 10 * 16 * 4 * 41}
    roof = lib.load("layer_metrics/hyper_moe_decode_hbm_roofline.py")
    got = roof.read(_facts(cfg, counters), "")
    # the live positions of a step, summed over its 16 rows
    least = costs.decode_least_bytes(cfg, 16 * 4200.0, 16.0, 4 * 41.0)
    assert got == pytest.approx(100.0 * least / 819e9 / 0.009)
    assert 50.0 < got < 100.0
    # a configuration without streams has nothing to read
    plain = {k: v for k, v in cfg.items() if k != "hc_mult"}
    assert roof.read(_facts(plain, counters), "") is None
    # the part "hyper" of a tally: 10 runs of 16 steps
    hyper = lib.load("layer_metrics/hyper_decode_ms.py")
    found = (10, 1.44, {"hyper": 0.08, "mixer": 0.3})
    monkeypatch.setattr(parts, "tally", lambda facts, key: found)
    assert hyper.read(_facts(cfg, counters), "") == pytest.approx(0.5)
    monkeypatch.setattr(parts, "tally", lambda facts, key: (10, 1.44, {}))
    assert hyper.read(_facts(cfg, counters), "") is None
    monkeypatch.setattr(parts, "tally", lambda facts, key: None)
    assert hyper.read(_facts(cfg, counters), "") is None


def test_costs_match_a_hand_count():
    costs = lib.load("costs/hyper_mla_moe_decoder.py")
    cfg = lib.read_json(PUBLISHED)
    d = 3584
    attn = d * 768 + 768 * 32 * 192 + d * 576 + 512 * 32 * 256 + 4096 * d
    assert costs.attention_params(cfg) == attn == 28_409_856
    expert = 3 * d * 1024
    assert costs.expert_params(cfg) == expert == 11_010_048
    hyper = 2 * (4 * d * 24 + 24 + 3)
    assert costs.hyper_params(cfg) == hyper == 688_182
    norms = 2 * d + 768 + 512
    dense = attn + norms + 3 * d * 9216 + hyper
    routed = attn + norms + d * 64 + 64 + 65 * expert + hyper
    assert costs.n_params(cfg) == 2 * dense + 4 * routed + d + 2 * d * 131072
    # the issue's reckoning: 8.35 GB of bf16 weights at this cut
    assert costs.n_params(cfg) * 2 / 1e9 == pytest.approx(8.35, abs=0.02)
    other = 2 * (6 * attn + 2 * 3 * d * 9216 + 4 * expert + d * 131072) \
        + 4 * d * 64 * 4
    assert costs.decode_least_bytes(cfg, 1000.0, 16.0, 40.0) == (
        other + 40 * 2 * expert + 1000.0 * 576 * 6 * 2 + 16 * d * 2
        + 6 * hyper * 4)
    site = 2 * (4 * d * 24 + 4 * d + 16 * d + 4 * d)
    mla = lib.load("costs/mla_moe_decoder.py")
    assert costs.decode_flops(cfg, 5000) == mla.decode_flops(cfg, 5000) \
        + 12 * site
    assert costs.prefill_flops(cfg, 3) == mla.prefill_flops(cfg, 3) \
        + 3 * 12 * site
