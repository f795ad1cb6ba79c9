"""CPU rehearsal of the hybrid decoder's driver (run by hand with the other
rehearsals: ``python -m pytest benchmark/tests -q``; not part of tier-1).

``fixture_hybrid/`` is laid out as ``fixture/`` is, with a ``BENCHMARK.json``
of its own: a toy of two periods of linear, linear, linear, full through
``drivers/serve_hybrid.py`` end to end, the schema of the last line, both
controls (the reference with int8 weights, and with its recurrent state
kept in bfloat16) and a token altered where it is produced, each of which
has to come out as not correct; and the cost arithmetic against a hand
count at the published widths.
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

FIXTURE = HERE / "fixture_hybrid"
CELL = "tiny-hybrid.open"
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
SEED = 2 ** 31 + 13         # the driver's seeds pass 32 signed bits


@pytest.fixture()
def bench():
    return lib.read_json(FIXTURE / "BENCHMARK.json")


def test_rehearsal_is_correct_and_well_formed(bench):
    line = run.execute(bench, CELL, SEED, 1.5, False, dict(CPU), root=FIXTURE,
                       control="w8,s16")
    json.dumps(line)
    assert list(line)[-1] == "compared"
    assert line["correct"], line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {"token_gap_p50_ms", "setup_s"} <= set(line["metrics"])
    counters = line["notes"]["counters"]
    assert counters["state_rows"] > 0 and counters["compiles"] == 0
    assert line["notes"]["states_compared"] > 0
    # both controls and the altered token, each with a verdict of its own
    # against the toy's limits; the bfloat16 state fails by ``state_gap``,
    # the one number its precision moves
    notes = line["notes"]
    assert not notes["fault_control_w8"]["correct"], notes["fault_control_w8"]
    s16 = notes["fault_control_s16"]["compared"]["state_gap"]
    assert not notes["fault_control_s16"]["correct"] \
        and s16["value"] > s16["limit"], notes["fault_control_s16"]
    assert not notes["fault_token_altered"]["correct"]


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


def test_stacked_weights_equal_layerwise_weights():
    import jax.numpy as jnp

    w = lib.load("weights/hybrid_decoder.py")
    cfg = lib.read_json(FIXTURE / "benchmark/configs/tiny-hybrid.json")
    key = w.seed_key(SEED)
    for kind in w.KINDS:
        stack = w.stack(key, cfg, kind, jnp.float32)
        index = w.layer_indices(cfg, kind)[1]
        one = w.layer(key, cfg, index, kind, jnp.float32)
        assert all(bool(jnp.array_equal(stack[k][1], one[k])) for k in one)


def test_costs_match_a_hand_count():
    costs = lib.load("costs/hybrid_decoder.py")
    cfg = lib.read_json(HERE.parent / "configs/olmo-hybrid-7b-serve.json")
    mlp = 3 * 3840 * 11008
    full = 4 * 3840 * 3840 + mlp
    # q, k [3840, 2880]; v, g [3840, 5760]; a, b [3840, 30]; o [5760, 3840]
    linear = 3840 * (2 * 2880 + 2 * 5760 + 60) + 5760 * 3840 + mlp
    assert costs.layer_matmul_params(cfg, "full_attention") == full == 185_794_560
    assert costs.layer_matmul_params(cfg, "linear_attention") == linear \
        == 215_516_160
    assert costs.conv_channels(cfg) == 11520
    assert costs.weight_bytes_step(cfg, 2) == 2 * (
        2 * full + 6 * linear + 3840 * 100352)
    assert costs.kv_bytes_position(cfg, 2) == 2 * 3840 * 2 * 2
    slot = 6 * (30 * 96 * 192 * 4 + 3 * 11520 * 2)
    assert costs.state_bytes_slot(cfg) == slot
    assert costs.decode_least_bytes(cfg, 1000, 4) == \
        costs.weight_bytes_step(cfg, 2) + 1000 * 30720 + 2 * 4 * slot
    per_token = (2 * (2 * full + 6 * linear) + 2 * 4 * 900 * 3840
                 + 6 * (2 * 4 * 11520 + 8 * 30 * 96 * 192)
                 + 2 * 3840 * 100352)
    assert costs.decode_flops(cfg, 900) == per_token
    assert costs.n_params(cfg) == 2 * (full + 2 * 3840 + 2 * 3840) + 6 * (
        linear + 2 * 3840 + 4 * 11520 + 60 + 192) + 3840 \
        + 2 * 3840 * 100352
