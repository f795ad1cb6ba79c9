"""The one traffic generator. A traffic mix is a data file of parameters
(``traffic/<mix>.json``; a cell may override keys under ``traffic_params``
in its own file); this module turns it and ``--seed`` into the inputs of a
run. Nothing here imports the program or JAX.

Every seed gets the same multiset of sizes and of arrival gaps, drawn once
from the mix's ``population_seed``, in another order: the seed changes
which request comes when and what its tokens are, not how much work the
run holds.

Kinds:

``requests``  prompts for a server. ``arrival`` is ``open_poisson``
    (``rate_per_s``: exponential gaps, scaled so that they fill the run
    exactly) or ``closed`` (``clients`` callers, each sending its next
    request when its last one completes; ``requests_per_s_cap`` sizes the
    list). Lengths: ``{"dist": "lognormal", "median", "sigma", "min",
    "max"}`` or ``{"dist": "uniform", "min", "max"}``. Traffic starts
    ``lead_in_s`` before the window so that the window opens on a system
    in its steady state.
``lm_batches``  training batches of ``batch`` rows of ``seq`` + 1 tokens:
    affine sequences modulo the vocabulary, each row its own start and
    stride (the arithmetic of ``train/step.py``'s ``synthetic_lm_batch``).
"""

from __future__ import annotations

import numpy as np


def _rng(*words: int) -> np.random.Generator:
    return np.random.default_rng([int(w) % (2 ** 63) for w in words])


def _lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    if spec["dist"] == "lognormal":
        x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    elif spec["dist"] == "uniform":
        x = rng.uniform(spec["min"], spec["max"] + 1, n)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.floor(x), spec["min"], spec["max"]).astype(np.int64)


def requests(mix: dict, vocab: int, seed: int, seconds: float) -> dict:
    """-> {"mode", "clients", "lead_in_s", "requests": [{"due_s", "prompt",
    "max_new"}]}; ``due_s`` counts from the start of the lead-in (open
    loop) and is None in a closed loop."""
    lead = float(mix.get("lead_in_s", 0.0))
    span = seconds + lead
    pop = _rng(mix.get("population_seed", 0), 17)
    run = _rng(seed, 29)
    if mix["arrival"] == "open_poisson":
        n = max(1, int(round(mix["rate_per_s"] * span)))
        gaps = pop.exponential(1.0, n)
        gaps *= span / gaps.sum()
        due = np.cumsum(run.permutation(gaps)) - gaps.min() / 2
        due = np.clip(due, 0.0, None)
    elif mix["arrival"] == "closed":
        n = max(1, int(round(mix["requests_per_s_cap"] * span)))
        due = [None] * n
    else:
        raise ValueError(f"unknown arrival {mix['arrival']!r}")
    p_len = _lengths(mix["prompt_tokens"], n, pop)
    o_len = _lengths(mix["output_tokens"], n, pop)
    order = run.permutation(n)
    out = []
    for i in range(n):
        j = order[i]
        out.append({
            "due_s": None if due[i] is None else float(due[i]),
            "prompt": run.integers(0, vocab, int(p_len[j]), dtype=np.int32),
            "max_new": int(o_len[j])})
    return {"mode": mix["arrival"], "clients": int(mix.get("clients", 0)),
            "lead_in_s": lead, "requests": out}


def lm_batch(mix: dict, vocab: int, seed: int, step: int):
    """Batch ``step`` of the run: (tokens, targets) int32 [batch, seq]."""
    rng = _rng(seed, 31, step)
    b, l = int(mix["batch"]), int(mix["seq"])
    start = rng.integers(0, vocab, (b, 1))
    stride = rng.integers(1, 7, (b, 1))
    toks = (start + stride * np.arange(l + 1)[None, :]) % vocab
    return toks[:, :-1].astype(np.int32), toks[:, 1:].astype(np.int32)
