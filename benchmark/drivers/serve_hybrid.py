"""Driver: a hybrid decoder (gated-delta-rule layers beside full-attention
layers) served in-process through ``ServeApp.submit_async``, as
``drivers/serve.py`` serves a dense one and through the same entry points:
``prepare_decode`` -> ``SlotServer`` -> ``ServeApp``.

Its own: the configuration's mapping onto the program's config, the seed's
weights in the program's tree (``weights/hybrid_decoder.py``), the cost
arithmetic (``costs/hybrid_decoder.py``), the counter of the recurrent
slot state, and ``state_gap``: the recurrent state a slot is left holding
against the reference's, the one number of ``correct`` that the state's
precision moves (the logits cannot tell a bfloat16 state from what
bfloat16 activations do anyway). The timed stream, the load loop, the warm-up and the
comparison with the reference named by ``cfg["reference"]`` are
``drivers/serve.py``'s own helpers, taken through ``lib.load``; the
window's numbers are computed here as that driver computes them, so that
a metric of one name is one metric.
"""

from __future__ import annotations

import lib

KIND = {"full_attention": "full", "linear_attention": "linear"}


def transformer_config(cfg: dict, max_len: int):
    import jax.numpy as jnp
    from tony_tpu.models.transformer import LIN_L2_EPS, TransformerConfig

    weights = lib.load("weights/hybrid_decoder.py")
    if (cfg["linear_conv_bias"] or cfg["linear_gate"] != "A_log_dt_bias"
            or cfg["linear_output_norm"] != "gated_rms"
            or cfg["linear_l2_eps"] != LIN_L2_EPS
            or not cfg["linear_allow_neg_eigval"]
            or cfg["head_dim"] * cfg["num_attention_heads"]
            != cfg["hidden_size"]):
        raise ValueError("the program has only the stated form of the layers")
    heads, dk, dv, kernel = weights.linear_sizes(cfg)
    theta = cfg["rope_parameters"]["rope_theta"]
    dt = cfg["dtype"]
    return TransformerConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], max_seq_len=max_len,
        rope_theta=None if theta is None else float(theta),
        norm_eps=float(cfg["rms_norm_eps"]),
        layer_kinds=tuple(KIND[k] for k in cfg["layer_types"]),
        lin_heads=heads, lin_key_dim=dk, lin_value_dim=dv, lin_conv=kernel,
        qk_norm=bool(cfg["qk_norm"]), norm_order=cfg["norm_order"],
        dtype=jnp.dtype(dt["activations"]),
        param_dtype=jnp.dtype(dt["weights"]))


def program_params(cfg: dict, seed: int, dtype):
    """The seed's weights in the program's tree: one stack per kind; a
    linear layer's q, k, v projections are the columns of one matrix and
    its two gates of another, as the program keeps them."""
    import jax.numpy as jnp

    weights = lib.load("weights/hybrid_decoder.py")
    h, kvh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    d = cfg["hidden_size"]

    def make(key):
        w = weights.whole(key, cfg, dtype)
        layers = {}
        for kind, lw in w["layers"].items():
            lw = dict(lw)
            n = lw["wo"].shape[0]
            if kind == "full_attention":
                lw["wq"] = lw["wq"].reshape(n, d, h, hd)
                lw["wk"] = lw["wk"].reshape(n, d, kvh, hd)
                lw["wv"] = lw["wv"].reshape(n, d, kvh, hd)
                lw["wo"] = lw["wo"].reshape(n, h, hd, d)
            else:
                lw["w_qkv"] = jnp.concatenate(
                    [lw.pop("wq"), lw.pop("wk"), lw.pop("wv")], axis=-1)
                lw["w_ab"] = jnp.concatenate(
                    [lw.pop("wa"), lw.pop("wb")], axis=-1)
                lw["w_g"], lw["conv_w"] = lw.pop("wg"), lw.pop("conv")
            layers[KIND[kind]] = lw
        return {**w, "layers": layers}

    return make, weights.seed_key(seed)


def held_states(held: dict, finished: list, most: int) -> list:
    """The finished requests whose state a slot still holds when the
    engine has gone idle: of each slot its last occupant (the trace names
    the slot), where the slot's length is that request's own, ``most`` of
    them at most -> [(request, tokens consumed, state [H, d_k, d_v])]. A
    request has consumed its prompt and all it served but the last token."""
    last = {}
    for r in finished:
        trace = r.comp.trace or {}
        slot = trace.get("attrs", {}).get("slot")
        when = dict(trace.get("spans", [])).get("admitted")
        if slot is not None and when is not None and (
                slot not in last or when > last[slot][0]):
            last[slot] = (when, r)
    out = []
    for slot, (_, r) in sorted(last.items()):
        n = len(r.req["prompt"]) + len(r.comp.tokens) - 1
        if int(held["length"][slot]) == n:
            out.append((r, n, held["state"][slot]))
    return out[:most]


def state_gaps(cfg: dict, mix: dict, seed: int, sample: list, lowp=None):
    """How far the first linear layer's state lies from the reference's,
    a head at a time: |S - S_ref| / |S_ref| (Frobenius) -> [requests,
    heads]. ``S`` is what the slot held, or with ``lowp`` what the control
    (the reference in that precision) computes in the program's place."""
    import numpy as np

    ref = lib.load("reference/" + cfg["reference"] + ".py")
    width = -(-(mix["prompt_tokens"]["max"]
                + mix["output_tokens"]["max"]) // 128) * 128
    tokens = np.zeros((len(sample), width), np.int32)
    for i, (r, n, _) in enumerate(sample):
        tokens[i, :n] = (list(r.req["prompt"]) + list(r.comp.tokens))[:n]
    lengths = [n for _, n, _ in sample]
    wdtype = cfg["dtype"]["weights"]
    want = np.asarray(ref.served_states(cfg, seed, wdtype, tokens, lengths))
    got = (np.stack([s for *_, s in sample]) if lowp is None else np.asarray(
        ref.served_states(cfg, seed, wdtype, tokens, lengths, lowp=lowp)))
    norm = lambda x: np.sqrt((x.astype(np.float64) ** 2).sum((-2, -1)))
    return norm(got - want) / norm(want)


def run(ctx) -> dict:
    import threading
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    from tony_tpu.cli.serve import ServeApp
    from tony_tpu.models.generate import prepare_decode
    from tony_tpu.models.serving import Request, SlotServer

    serve = lib.load("drivers/serve.py")
    costs = lib.load("costs/hybrid_decoder.py")
    gen = lib.load("traffic/generate.py")
    cfg, mix, engine = ctx.cfg, ctx.mix, ctx.cfg["engine"]
    tcfg = transformer_config(cfg, engine["max_len"])

    make, key = program_params(cfg, ctx.seed,
                               jnp.dtype(cfg["dtype"]["weights"]))
    params = jax.jit(make)(key)
    prepared = prepare_decode(params, tcfg,
                              weight_dtype=engine["weight_dtype"])
    del params
    server = SlotServer(
        prepared, tcfg, slots=engine["slots"], max_len=engine["max_len"],
        block_size=engine["block_size"],
        prefill_chunk=engine["prefill_chunk"], kv_dtype=engine["kv_dtype"],
        stop_tokens=tuple(engine["stop_tokens"]),
        pipeline_depth=engine["pipeline_depth"],
        seed=ctx.seed % (2 ** 31))
    serve._warm_up(server, Request, engine, cfg["vocab_size"])
    app = ServeApp(server)
    app.start()
    stream_cls = serve._timed_stream_class()
    plan = gen.requests(mix, cfg["vocab_size"], ctx.seed, ctx.seconds)
    watch = lib.CompileWatch.install()

    def read_counters():
        return {"admission_dispatches": server.admission_dispatches,
                "blocks_dispatched": server.blocks_dispatched,
                "prefill_tokens_computed": server.prefill_tokens_computed,
                "compiles": watch.count, "state_rows": server.state_rows}

    sent: list = []
    stop = threading.Event()
    t_origin = time.monotonic() + 0.05
    t0 = t_origin + plan["lead_in_s"]
    t1 = t0 + ctx.seconds
    loader = threading.Thread(
        target=serve._load_loop, name="bench-load",
        args=(app, stream_cls, plan, t_origin, t1, sent, stop))
    loader.start()
    time.sleep(max(0.0, t0 - time.monotonic()))
    setup_s = time.monotonic() - ctx.t_start
    c0 = read_counters()
    traced = ctx.trace_window(t0, t1)       # blocks while the profiler runs
    time.sleep(max(0.0, t1 - time.monotonic()))
    c1 = read_counters()
    # the window has closed: every answer that is due gets its grace, and
    # is late, not wrong, if it comes within it
    if plan["mode"] != "open_poisson":
        stop.set()
    loader.join(timeout=mix["grace_s"])
    deadline = time.monotonic() + mix["grace_s"]
    for rec in sent:
        if rec.ev is not None:
            rec.ev.wait(max(0.0, deadline - time.monotonic()))
    stop.set()
    loader.join(timeout=10)
    for rec in sent:
        if rec.error is None and rec.ev is None:
            rec.error = "never taken by submit_async"
        elif rec.error is None and rec.ev.is_set():
            try:
                rec.comp = app.take_result(rec.rid)
            except Exception as e:
                rec.error = repr(e)
        elif rec.error is None:
            rec.error = "unfinished after the grace"
    app.shutdown()
    memory_peak = lib.peak_memory_bytes()
    finished = [r for r in sent if r.comp is not None]
    held = held_states(server.slot_states(), finished, mix["check_requests"])
    del app, server, prepared

    # ------------------------------------------------- the window's numbers
    # (each computed as drivers/serve.py computes it: the same metric)
    in_window = [r for r in sent if t0 <= r.due < t1]
    ok = [r for r in in_window if r.comp is not None and r.stream.feeds]
    ttft = [(r.stream.feeds[0][0] - r.due) * 1e3 for r in ok]
    tpot, gaps, weights = [], [], []
    for r in ok:
        feeds = r.stream.feeds
        n = sum(k for _, k in feeds)
        if len(feeds) > 1 and n > 1:
            tpot.append((feeds[-1][0] - feeds[0][0]) * 1e3 / (n - feeds[0][1]))
        # a feed brings a block of tokens at once: each feed after a
        # request's first counts once per token it brings, at (time since
        # the last feed) / tokens
        for (ta, _), (tb, k) in zip(feeds, feeds[1:]):
            gaps.append((tb - ta) * 1e3 / k)
            weights.append(k)
    token_gaps = np.repeat(gaps, weights)
    done_in = [r for r in sent if r.comp is not None
               and r.stream.t_done is not None and t0 <= r.stream.t_done < t1]
    e2e = {"serve_tokens_per_s": sum(
        len(r.req["prompt"]) + len(r.comp.tokens) for r in done_in)
        / ctx.seconds}
    if ttft:
        e2e["ttft_p95_ms"] = lib.percentile(ttft, 95)
    if tpot:
        e2e["tpot_p95_ms"] = lib.percentile(tpot, 95)
    if gaps:
        e2e["token_gap_p50_ms"] = lib.percentile(token_gaps, 50)

    # work of the window, for the per-layer readers: a prompt counts where
    # its admission fell, a generated token where its feed fell
    flops = context_sum = 0.0
    decode_tokens = 0
    queue_wait_ms = []
    for r in sent:
        if r.comp is None:
            continue
        sp = serve._spans(r.comp)
        p = len(r.req["prompt"])
        adm = sp.get("admitted")
        if adm is not None and t0 <= adm < t1:
            flops += costs.prefill_flops(cfg, p - 1)
        if adm is not None and "submitted" in sp and t0 <= r.due < t1:
            queue_wait_ms.append((adm - sp["submitted"]) * 1e3)
        seen = 0
        for t, k in r.stream.feeds:
            if t0 <= t < t1:
                ctxs = p + seen + (k + 1) / 2.0
                flops += k * costs.decode_flops(cfg, ctxs)
                decode_tokens += k
                context_sum += k * ctxs
            seen += k
    facts = {
        "cfg": cfg, "engine": engine, "window_s": ctx.seconds,
        "chips": ctx.chips,
        "counters": {k: c1[k] - c0[k] for k in c0},
        "flops": flops,
        "decode_tokens": decode_tokens, "decode_context_sum": context_sum,
        "programs": {"decode": ["_decode_block"],
                     "prefill": ["_prefill_batch"]},
    }

    def pct(values, q):
        return lib.percentile(values, q) if len(values) else None

    submit_wait_ms = [(r.taken - r.sent) * 1e3 for r in in_window
                      if r.taken is not None]
    notes = {
        "requests_in_window": len(in_window), "with_first_token": len(ttft),
        "done_in_window": len(done_in),
        "unfinished_at_close": sum(
            1 for r in sent if r.due < t1 and (
                r.stream is None or r.stream.t_done is None
                or r.stream.t_done >= t1)),
        "ttft_p50_ms": pct(ttft, 50), "ttft_p95_ms": pct(ttft, 95),
        "tpot_p50_ms": pct(tpot, 50), "tpot_p95_ms": pct(tpot, 95),
        "token_gap_mean_ms": float(np.mean(token_gaps)) if gaps else None,
        "token_gap_p95_ms": pct(token_gaps, 95) if gaps else None,
        "tokens_fed_per_s": decode_tokens / ctx.seconds,
        "requests_done_per_s": len(done_in) / ctx.seconds,
        "send_late_p95_ms": pct(
            [(r.sent - r.due) * 1e3 for r in in_window
             if r.sent is not None], 95),
        "submit_wait_p50_ms": pct(submit_wait_ms, 50),
        "queue_wait_p50_ms": pct(queue_wait_ms, 50),
        "queue_wait_p95_ms": pct(queue_wait_ms, 95),
        "compiled_in_window": watch.names[c0["compiles"]:c1["compiles"]],
        "counters": facts["counters"],
    }

    # ------------------------------------------------------------- correct
    checks = lib.Checks(ctx.cell.get("limits"))
    checks.add("compiles_in_window", c1["compiles"] - c0["compiles"], 0)
    eos = set(engine["stop_tokens"])
    bad = sum(1 for r in finished
              if len(r.comp.tokens) != r.req["max_new"]
              and not (r.comp.tokens and r.comp.tokens[-1] in eos))
    bad += sum(1 for r in finished if sum(k for _, k in r.stream.feeds)
               != len(r.comp.tokens))
    checks.add("answers_of_wrong_length", bad, 0)
    if finished:
        found = serve.served_gaps(cfg, mix, ctx.seed, finished)
        checks.add("logit_gap_max", float(np.max(found)))
        checks.add("logit_gap_mean", float(np.mean(found)))
        # a slot left holding no finished request's state has nothing to
        # compare: not a number, which fails the run
        checks.add("state_gap", float(np.max(
            state_gaps(cfg, mix, ctx.seed, held))) if held else float("nan"))
        notes["served_tokens_compared"] = int(found.size)
        notes["states_compared"] = len(held)
        if ctx.control:
            # the controls, each put in the program's place and each with
            # a verdict of its own: the reference in the precision named,
            # comma-separated ("w8,s16")
            for lowp in ctx.control.split(","):
                low = serve.served_gaps(cfg, mix, ctx.seed, finished,
                                        lowp=lowp)
                notes["fault_control_" + lowp] = checks.judge({
                    "logit_gap_max": float(np.max(low)),
                    "logit_gap_mean": float(np.mean(low)),
                    "state_gap": float(np.max(state_gaps(
                        cfg, mix, ctx.seed, held, lowp=lowp)))})
            # one altered token, at the position where it shows least
            alt = serve.served_gaps(cfg, mix, ctx.seed, finished, alter=1)
            notes["fault_token_altered"] = checks.judge({
                "logit_gap_max": float(np.min(alt))})
            notes["fault_token_altered"]["positions_over_limit_pct"] = \
                100.0 * float(np.mean(alt > checks.limits["logit_gap_max"]))
    failed = len([r for r in in_window if r.comp is None])
    return {"attempted": len(in_window), "failed": failed, "e2e": e2e,
            "setup_s": setup_s, "facts": facts, "notes": notes,
            "checks": checks, "memory_peak_bytes": memory_peak,
            "traced": traced}
