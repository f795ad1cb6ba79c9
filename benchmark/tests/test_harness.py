"""CPU rehearsals of the benchmark (run by hand: ``python -m pytest
benchmark/tests -q``; not part of tier-1).

They skip the harness's look for a chip and drive the rest of a run at toy
widths: every driver and traffic kind end to end, the schema of the last
line, the control (the reference in a lower precision has to fail
``correct``), and the timed path broken underneath (a token altered where
it is produced, a step that leaves its state unchanged, half of the batch
left out): each has to come out as not correct.
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

FIXTURE = HERE / "fixture"
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
SEED = 2 ** 31 + 11          # the driver's seeds pass 32 signed bits


@pytest.fixture()
def bench():
    return lib.read_json(FIXTURE / "BENCHMARK.json")


def _run(bench, cell, seconds=1.5, seed=SEED):
    return run.execute(bench, cell, seed, seconds, False, dict(CPU),
                       root=FIXTURE)


def _check_line(line, metrics):
    json.dumps(line)                                   # one JSON object
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in line
    assert list(line)[-1] == "compared"                # limits come last
    assert set(metrics) <= set(line["metrics"])
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] == m["value"]
    assert line["metrics"]["setup_s"]["value"] > 0
    for row in line["compared"].values():
        assert set(row) == {"value", "limit"}


@pytest.mark.parametrize("cell,metrics", [
    ("tiny.open", ["ttft_p95_ms", "tpot_p95_ms"]),
    ("tiny.closed", ["serve_tokens_per_s"]),
    ("tiny.train", ["train_tokens_per_s"]),
])
def test_rehearsal_is_correct_and_well_formed(bench, cell, metrics):
    line = _run(bench, cell)
    _check_line(line, metrics)
    assert line["correct"], line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0


def test_no_chip_no_result():
    with pytest.raises(SystemExit):
        run.require_chips(1)


def test_same_seed_same_inputs():
    gen = lib.load("traffic/generate.py")
    mix = lib.read_json(FIXTURE / "benchmark/traffic/tiny-open.json")
    a = gen.requests(mix, 256, SEED, 2.0)
    b = gen.requests(mix, 256, SEED, 2.0)
    c = gen.requests(mix, 256, SEED + 1, 2.0)
    assert all((x["prompt"] == y["prompt"]).all() and x["due_s"] == y["due_s"]
               for x, y in zip(a["requests"], b["requests"]))
    # another seed: the same sizes and gaps in another order
    assert sorted(len(r["prompt"]) for r in a["requests"]) == \
        sorted(len(r["prompt"]) for r in c["requests"])
    assert [len(r["prompt"]) for r in a["requests"]] != \
        [len(r["prompt"]) for r in c["requests"]]


def test_open_loop_sends_on_time_whatever_submit_does():
    """A send that waits inside ``submit_async`` holds up no later one:
    every request leaves at its due instant, from a thread of its own."""
    import threading
    import time

    serve = lib.load("drivers/serve.py")
    gen = lib.load("traffic/generate.py")
    mix = dict(lib.read_json(FIXTURE / "benchmark/traffic/tiny-open.json"),
               rate_per_s=20.0, lead_in_s=0.0)
    plan = gen.requests(mix, 256, SEED, 1.0)

    class SlowApp:
        def submit_async(self, prompt, max_new, **kw):
            time.sleep(0.4)                 # far longer than an arrival gap
            return 1, threading.Event()

    class NoStream:
        pass

    sent, t_origin = [], time.monotonic() + 0.05
    serve._load_loop(SlowApp(), NoStream, plan, t_origin, t_origin + 1.0,
                     sent, threading.Event())
    assert len(sent) == len(plan["requests"]) == 20
    assert all(r.ev is not None for r in sent)
    assert max(r.sent - r.due for r in sent) < 0.1
    assert min(r.taken - r.sent for r in sent) >= 0.4


def test_costs_match_a_hand_count():
    costs = lib.load("costs/dense_decoder.py")
    v01 = lib.read_json(HERE.parent / "configs/mistral-7b-v01-serve.json")
    v03 = lib.read_json(HERE.parent / "configs/mistral-7b-v03-train1.json")
    # a Mistral-7B layer: q 4096x4096, k and v 4096x1024, o 4096x4096,
    # gate, up, down 4096x14336 each
    layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert layer == 218_103_808 == costs.layer_matmul_params(v01)
    n01 = v01["num_hidden_layers"]
    assert costs.n_params(v01) == n01 * (layer + 8192) + 4096 \
        + 2 * 4096 * 32000
    assert costs.weight_bytes_step(v01, 2) == 2 * (n01 * layer + 4096 * 32000)
    assert costs.kv_bytes_position(v01, 2) == 2 * 1024 * n01 * 2
    n = v03["num_hidden_layers"]
    fwd = n * (2 * layer + 4 * 2048 * 4096) + 2 * 4096 * 32768
    assert costs.train_flops_per_token(v03, 4096) == 3.0 * fwd
    # causal flash forward of one [1, 32, 4096, 128] call
    assert costs.flash_flops(1, 32, 4096, 128, False) == \
        4 * 32 * 4096 * 4096 * 128 / 2


def test_stacked_weights_equal_layerwise_weights():
    import jax.numpy as jnp

    w = lib.load("weights/dense_decoder.py")
    cfg = lib.read_json(FIXTURE / "benchmark/configs/tiny-serve.json")
    key = w.seed_key(SEED)
    stack = w.stack(key, cfg, jnp.float32)
    one = w.layer(key, cfg, 1, jnp.float32)
    assert all(bool(jnp.array_equal(stack[k][1], one[k])) for k in one)


# ------------------------------------------------------------------ control

def test_control_lower_precision_serving_fails(bench):
    """The reference with int8 weights, put in the program's place: the
    token it puts first lies further below the float32 reference's best
    than the cell's limit allows."""
    import numpy as np

    serve = lib.load("drivers/serve.py")
    gen = lib.load("traffic/generate.py")
    found = run.find_cell(bench, "tiny.open", FIXTURE)
    plan = gen.requests(found.mix, found.cfg["vocab_size"], SEED, 2.0)

    class Done:
        def __init__(self, req):
            self.req = req
            self.comp = type("C", (), {"tokens": [5] * req["max_new"]})()

    finished = [Done(r) for r in plan["requests"][:6]]
    gaps = serve.served_gaps(found.cfg, found.mix, SEED, finished, lowp="w8")
    verdict = lib.Checks(found.cell["limits"]).judge({
        "logit_gap_max": float(np.max(gaps)),
        "logit_gap_mean": float(np.mean(gaps))})
    assert not verdict["correct"]
    # at the cells' own size it is the mean that the control fails (the
    # widest gap swings by its nature, PERF.md 6): hold it to that here too
    mean = verdict["compared"]["logit_gap_mean"]
    assert mean["value"] > mean["limit"]


def test_control_lower_precision_training_fails(bench):
    train = lib.load("drivers/train.py")
    gen = lib.load("traffic/generate.py")
    ref = lib.load("reference/dense_decoder.py")
    found = run.find_cell(bench, "tiny.train", FIXTURE)
    cfg, mix = found.cfg, found.mix
    batches = [gen.lm_batch(mix, cfg["vocab_size"], SEED, i) for i in range(3)]
    want = ref.train_steps(cfg, SEED, cfg["optimizer"], batches)
    low = ref.train_steps(cfg, SEED, cfg["optimizer"], batches, lowp="w8a8")
    verdict = lib.Checks(found.cell["limits"]).judge(train.compare(low, want))
    assert not verdict["correct"], verdict


# ------------------------------------------------------------------- faults

def _fresh_programs():
    import jax

    jax.clear_caches()


def test_fault_token_altered_where_it_is_produced(bench, monkeypatch):
    import tony_tpu.models.serving as serving

    real = serving.sample_token
    monkeypatch.setattr(
        serving, "sample_token",
        lambda logits, *a, **k: (real(logits, *a, **k) + 1)
        % logits.shape[-1])
    _fresh_programs()
    try:
        line = _run(bench, "tiny.open")
    finally:
        monkeypatch.undo()
        _fresh_programs()
    assert not line["correct"]
    assert line["compared"]["logit_gap_max"]["value"] > \
        line["compared"]["logit_gap_max"]["limit"]


def test_fault_step_returns_its_state_unchanged(bench, monkeypatch):
    import optax

    monkeypatch.setattr(optax, "apply_updates", lambda params, updates: params)
    _fresh_programs()
    try:
        line = _run(bench, "tiny.train")
    finally:
        monkeypatch.undo()
        _fresh_programs()
    assert not line["correct"]
    assert line["compared"]["param_change_gap"]["value"] == pytest.approx(1.0, abs=1e-3)


def test_fault_half_of_the_batch_left_out(bench, monkeypatch):
    import tony_tpu.models.transformer as transformer

    real = transformer.loss_fn

    def half(params, tokens, targets, *a, **k):
        n = tokens.shape[0] // 2
        return real(params, tokens[:n], targets[:n], *a, **k)

    monkeypatch.setattr(transformer, "loss_fn", half)
    _fresh_programs()
    try:
        line = _run(bench, "tiny.train")
    finally:
        monkeypatch.undo()
        _fresh_programs()
    assert not line["correct"]
