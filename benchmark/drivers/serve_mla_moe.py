"""Driver: a latent-attention decoder with routed experts served in-process
through ``ServeApp.submit_async``, as ``drivers/serve.py`` serves a dense
one and through the same entry points: ``prepare_decode`` -> ``SlotServer``
-> ``ServeApp``.

Its own: the configuration's mapping onto the program's config, the seed's
weights in the program's tree (``weights/mla_moe_decoder.py``, a layer at
a time), the cost arithmetic (``costs/mla_moe_decoder.py``), the experts'
counters, and two things the other serving drivers have no need of.

The routed comparison. With 256 experts the k-th and the next score lie so
close that bfloat16 activations flip a few percent of the choices against a
float32 reference fed the same tokens, and each flip moves a layer's output
by tens of percent: a reference left to its own routing reads gaps no limit
can hold between a sound run and a control. So every request asks the
server for the experts it chose (``Request.routes``), the reference
computes its forward WITH those choices and its own weights for them
(``logit_gap_*`` then read as the dense cells'), and
``routing_margin_gap`` checks the choices themselves: how far below the
reference's own k-th best ``score + bias`` the worst chosen expert lies,
over every compared position and routed layer (0 where the sets agree; a
rounding flip reads thousandths; a router that selects or weighs wrongly
reads far more, here or in the logits).

The saturated drain. ``drivers/serve.py`` stops a closed loop's clients at
the window's end, while the gaps of a request sent in the window count to
its last token: with answers of 15-35 s a third of the counted tokens would
come from a server running empty. Here the clients keep sending until every
request sent before the window's end has finished, and are stopped then:
every counted gap is of a full server. What they sent after the window's
end is abandoned and counted nowhere.

The timed stream, the load loop, the warm-up and the choice of the compared
requests are ``drivers/serve.py``'s own helpers, taken through
``lib.load``; the window's numbers are computed here as that driver
computes them, so that a metric of one name is one metric.
"""

from __future__ import annotations

import lib


def transformer_config(cfg: dict, max_len: int):
    import jax.numpy as jnp
    from tony_tpu.models.transformer import TransformerConfig

    weights = lib.load("weights/mla_moe_decoder.py")
    lib.load("reference/" + cfg["reference"] + ".py")._check(cfg)
    dt = cfg["dtype"]
    n = cfg["num_hidden_layers"]
    held = cfg.get("experts_held")
    return TransformerConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=n, n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], max_seq_len=max_len,
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        layer_kinds=("latent",) * n,
        lat_q_rank=cfg["q_lora_rank"], lat_kv_rank=cfg["kv_lora_rank"],
        lat_nope_dim=cfg["qk_nope_head_dim"],
        lat_rope_dim=cfg["qk_rope_head_dim"], lat_v_dim=cfg["v_head_dim"],
        rope_interleave=bool(cfg["rope_interleave"]),
        mlp_kinds=tuple(weights.layer_kinds(cfg)),
        moe_experts=cfg["n_routed_experts"],
        moe_top_k=cfg["num_experts_per_tok"],
        moe_ff=cfg["moe_intermediate_size"],
        moe_shared=cfg["n_shared_experts"],
        moe_scale=float(cfg["routed_scaling_factor"]),
        moe_held=None if held is None else tuple(held),
        dtype=jnp.dtype(dt["activations"]),
        param_dtype=jnp.dtype(dt["weights"]))


def program_layer(cfg: dict, lw: dict, kind: str) -> tuple:
    """One layer of ``weights/mla_moe_decoder.py`` in the program's
    format -> (its part of the latent stack, its MLP): the heads split out
    of the projections' widths, gate and up side by side in one matrix
    (the experts' [count, d, 2 f], the shared experts' [d, 2 S f])."""
    import jax.numpy as jnp

    h = cfg["num_attention_heads"]
    d, v = cfg["hidden_size"], cfg["v_head_dim"]
    mixer = {k: lw[k] for k in ("attn_norm", "mlp_norm", "q_a_norm",
                                "kv_a_norm", "wq_a", "wkv_a")}
    mixer["wq_b"] = lw["wq_b"].reshape(cfg["q_lora_rank"], h, -1)
    mixer["wkv_b"] = lw["wkv_b"].reshape(cfg["kv_lora_rank"], h, -1)
    mixer["wo"] = lw["wo"].reshape(h, v, d)
    if kind == "dense":
        return mixer, {k: lw[k] for k in ("w_gate", "w_up", "w_down")}
    return mixer, {
        "router": lw["router"], "router_bias": lw["router_bias"],
        "we_gu": jnp.concatenate([lw["experts_gate"], lw["experts_up"]], -1),
        "we_down": lw["experts_down"],
        "ws_gu": jnp.concatenate([lw["shared_gate"], lw["shared_up"]], -1),
        "ws_down": lw["shared_down"]}


def program_params(cfg: dict, seed: int, dtype) -> dict:
    """The seed's weights in the program's tree, made a layer at a time
    (one jitted call a layer: the float32 draw of a whole model's experts
    is never live), the mixers then stacked, the MLPs a list a layer."""
    import jax
    import jax.numpy as jnp

    weights = lib.load("weights/mla_moe_decoder.py")
    key = weights.seed_key(seed)
    held = cfg.get("experts_held")
    make = {kind: jax.jit(lambda key, i, kind=kind: program_layer(
        cfg, weights.layer(key, cfg, i, kind, dtype, held), kind))
        for kind in weights.KINDS}
    mixers, mlps = [], {}
    for i, kind in enumerate(weights.layer_kinds(cfg)):
        mixer, mlp = make[kind](key, jnp.int32(i))
        mixers.append(mixer)
        mlps.setdefault(kind, []).append(mlp)
    stack = jax.jit(lambda *ls: jax.tree.map(lambda *a: jnp.stack(a), *ls))
    return {"embed": jax.jit(lambda k: weights.embed(k, cfg, dtype))(key),
            "layers": {"latent": stack(*mixers), **mlps},
            "final_norm": weights.final_norm(cfg, dtype),
            "unembed": jax.jit(lambda k: weights.unembed(k, cfg, dtype))(key)}


class _AskingRoutes:
    """The app, every request asking for the experts it chose; and which
    submits are under way (a client appends its request to ``sent`` only
    when ``submit_async`` has returned, a second or two after the request
    was due: the drain must not take the window's last requests for
    absent)."""

    def __init__(self, app):
        import threading

        self._app = app
        self._lock = threading.Lock()
        self._began: dict = {}      # a sending thread -> when it began

    def submit_async(self, *args, **kw):
        import threading
        import time

        me = threading.get_ident()
        with self._lock:
            self._began[me] = time.monotonic()
        try:
            return self._app.submit_async(*args, routes=True, **kw)
        finally:
            with self._lock:
                del self._began[me]

    def submitting_since_before(self, t: float) -> bool:
        with self._lock:
            return any(began < t for began in self._began.values())


def compared(cfg: dict, mix: dict, sample: list) -> dict:
    """The sample as the reference's arguments: ``tokens`` [n, width] (a
    prompt, what was served, padding), the served tokens' ``positions`` and
    ids ``served`` [n, o_max] with ``valid``, the server's ``routes`` [n,
    width, layers, k] (a pad position "chose" experts 0..k-1: it comes
    after every compared one and is read by none) and ``consumed`` [n,
    width], the positions the routes are the server's."""
    import numpy as np

    k = cfg["num_experts_per_tok"]
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    o_max = mix["output_tokens"]["max"]
    width = -(-(mix["prompt_tokens"]["max"] + o_max) // 128) * 128
    n = len(sample)
    out = {"tokens": np.zeros((n, width), np.int32),
           "positions": np.zeros((n, o_max), np.int32),
           "served": np.zeros((n, o_max), np.int32),
           "valid": np.zeros((n, o_max), bool),
           "routes": np.tile(np.arange(k, dtype=np.int32),
                             (n, width, layers, 1)),
           "consumed": np.zeros((n, width), bool)}
    for i, r in enumerate(sample):
        p, ans = len(r.req["prompt"]), np.asarray(r.comp.tokens, np.int32)
        out["tokens"][i, :p] = r.req["prompt"]
        out["tokens"][i, p:p + ans.size] = ans
        out["positions"][i, :ans.size] = p - 1 + np.arange(ans.size)
        out["served"][i, :ans.size] = ans
        out["valid"][i, :ans.size] = True
        m = p + ans.size - 1
        if r.comp.routes is None or len(r.comp.routes) != m:
            raise ValueError(
                f"a request of {p} + {ans.size} tokens came back with "
                f"{None if r.comp.routes is None else len(r.comp.routes)} "
                f"positions' routes, not {m}")
        out["routes"][i, :m] = r.comp.routes
        out["consumed"][i, :m] = True
    return out


def routed_gaps(cfg: dict, seed: int, c: dict, lowp=None, fault=None) -> dict:
    """The numbers of the routed comparison for the sample ``c``
    (`compared`): ``gaps`` (for every served token, how far its logit lies
    below the reference's best at that position, the reference computing
    with the server's choices of experts), ``altered`` (the same for the
    token id after the served one) and ``margin`` (``routing_margin_gap``).

    With ``lowp`` or ``fault`` a stand-in takes the server's place: the
    reference in that precision or with that router fault, on its own
    routing, serves ITS first token at every position and reports ITS
    choices, and those are what the true reference is then given."""
    import numpy as np

    ref = lib.load("reference/" + cfg["reference"] + ".py")
    wdtype = cfg["dtype"]["weights"]
    served, routes = c["served"], c["routes"]
    if lowp is not None or fault is not None:
        stand_in = ref.served_scores(cfg, seed, wdtype, c["tokens"],
                                     c["positions"], served, lowp=lowp,
                                     fault=fault)
        served = np.asarray(stand_in["first"])
        routes = np.asarray(stand_in["chosen"])
    sc = ref.served_scores(cfg, seed, wdtype, c["tokens"], c["positions"],
                           served, routes=routes)
    best = np.asarray(sc["best"])
    margin = np.asarray(sc["margin"])[c["consumed"]]
    return {"gaps": (best - np.asarray(sc["got"]))[c["valid"]],
            "altered": (best - np.asarray(sc["got_next"]))[c["valid"]],
            "margin": float(margin.max())}


def run(ctx) -> dict:
    import threading
    import time

    import jax.numpy as jnp
    import numpy as np
    from tony_tpu.cli.serve import ServeApp
    from tony_tpu.models.generate import prepare_decode
    from tony_tpu.models.serving import Request, SlotServer

    serve = lib.load("drivers/serve.py")
    costs = lib.load("costs/mla_moe_decoder.py")
    gen = lib.load("traffic/generate.py")
    cfg, mix, engine = ctx.cfg, ctx.mix, ctx.cfg["engine"]
    if mix["arrival"] != "closed":
        raise ValueError("this driver's drain is a closed loop's")
    tcfg = transformer_config(cfg, engine["max_len"])

    params = program_params(cfg, ctx.seed, jnp.dtype(cfg["dtype"]["weights"]))
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
                "compiles": watch.count,
                "experts_touched": server.experts_touched,
                "experts_read": server.experts_read,
                "experts_held": server.experts_held}

    sent: list = []
    stop = threading.Event()
    t_origin = time.monotonic() + 0.05
    t0 = t_origin + plan["lead_in_s"]
    t1 = t0 + ctx.seconds
    # the clients outlast the window: they are stopped below, once what
    # was sent inside it has finished
    asking = _AskingRoutes(app)
    loader = threading.Thread(
        target=serve._load_loop, name="bench-load",
        args=(asking, stream_cls, plan, t_origin, float("inf"), sent, stop))
    loader.start()
    time.sleep(max(0.0, t0 - time.monotonic()))
    setup_s = time.monotonic() - ctx.t_start
    c0 = read_counters()
    traced = ctx.trace_window(t0, t1)       # blocks while the profiler runs
    time.sleep(max(0.0, t1 - time.monotonic()))
    c1 = read_counters()
    # the saturated drain: the server stays full until every request sent
    # before the window's end has its answer (or the grace runs out)
    deadline = time.monotonic() + mix["grace_s"]

    def drained():
        # (a request is due a moment before its submit begins)
        return not asking.submitting_since_before(t1 + 0.5) and all(
            r.error is not None or (r.ev is not None and r.ev.is_set())
            for r in list(sent) if r.due < t1)

    while time.monotonic() < deadline:
        if drained():
            time.sleep(0.2)     # a submit that has returned is appended at once
            if drained():
                break
        time.sleep(0.05)
    stop.set()
    loader.join(timeout=10)
    stats = server.stats()["experts"]
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
    # everything that holds the server goes, so that its weights leave the
    # device before the reference's arrive
    del app, server, prepared, asking, drained, loader
    finished = [r for r in sent if r.comp is not None]

    # ------------------------------------------------- the window's numbers
    # (each computed as drivers/serve.py computes it: the same metric)
    in_window = [r for r in sent if t0 <= r.due < t1]
    ok = [r for r in in_window if r.comp is not None and r.stream.feeds]
    ttft = [(r.stream.feeds[0][0] - r.due) * 1e3 for r in ok]
    tpot, gaps, weights, gap_at = [], [], [], []
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
            gap_at.append(tb)
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
    # its admission fell, a generated token where its feed fell (of every
    # request that streamed, finished or abandoned: the device did it)
    flops = context_sum = 0.0
    decode_tokens = 0
    for r in sent:
        if r.stream is None:
            continue
        p = len(r.req["prompt"])
        adm = serve._spans(r.comp).get("admitted") if r.comp else None
        if adm is not None and t0 <= adm < t1:
            flops += costs.prefill_flops(cfg, p - 1)
        seen = 0
        for t, k in list(r.stream.feeds):
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

    notes = {
        "requests_in_window": len(in_window), "with_first_token": len(ttft),
        "done_in_window": len(done_in),
        "sent_after_the_window_and_abandoned": sum(
            1 for r in sent if r.due >= t1),
        "ttft_p50_ms": pct(ttft, 50), "ttft_p95_ms": pct(ttft, 95),
        "tpot_p50_ms": pct(tpot, 50), "tpot_p95_ms": pct(tpot, 95),
        "token_gap_mean_ms": float(np.mean(token_gaps)) if gaps else None,
        "token_gap_p95_ms": pct(token_gaps, 95) if gaps else None,
        "token_gap_p10_p25_p75_p90_ms": [pct(token_gaps, q) for q in
                                         (10, 25, 75, 90)] if gaps else None,
        # the median gap of the feeds that fell in each eighth of the
        # window: whether the step drifts through a run
        "token_gap_p50_ms_by_eighth": [
            pct(np.repeat([g for g, t in zip(gaps, gap_at) if lo <= t < hi],
                          [w for w, t in zip(weights, gap_at)
                           if lo <= t < hi]), 50)
            for lo, hi in ((t0 + i * ctx.seconds / 8,
                            t0 + (i + 1) * ctx.seconds / 8)
                           for i in range(8))] if gaps else None,
        "tokens_fed_per_s": decode_tokens / ctx.seconds,
        "requests_done_per_s": len(done_in) / ctx.seconds,
        "compiled_in_window": watch.names[c0["compiles"]:c1["compiles"]],
        "counters": facts["counters"],
        "experts": stats,
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
        c = compared(cfg, mix, serve.check_sample(mix, ctx.seed, finished))
        found = routed_gaps(cfg, ctx.seed, c)
        checks.add("logit_gap_max", float(np.max(found["gaps"])))
        checks.add("logit_gap_mean", float(np.mean(found["gaps"])))
        checks.add("routing_margin_gap", found["margin"])
        notes["served_tokens_compared"] = int(found["gaps"].size)
        notes["positions_routed"] = int(c["consumed"].sum())

        def verdict(numbers):
            return checks.judge({
                "logit_gap_max": float(np.max(numbers["gaps"])),
                "logit_gap_mean": float(np.mean(numbers["gaps"])),
                "routing_margin_gap": numbers["margin"]})

        if ctx.control:
            # the controls, each a stand-in put in the program's place and
            # each with a verdict of its own: the reference in the
            # precision named (comma-separated), then the router's faults
            for lowp in ctx.control.split(","):
                notes["fault_control_" + lowp] = verdict(
                    routed_gaps(cfg, ctx.seed, c, lowp=lowp))
            for fault in ("weights_with_bias", "select_without_bias"):
                notes["fault_router_" + fault] = verdict(
                    routed_gaps(cfg, ctx.seed, c, fault=fault))
            # one altered token, at the position where it shows least
            notes["fault_token_altered"] = checks.judge({
                "logit_gap_max": float(np.min(found["altered"]))})
            # what the simpler comparison would read: the reference left
            # to its own routing, the served tokens against it
            ref = lib.load("reference/" + cfg["reference"] + ".py")
            own = ref.served_scores(
                cfg, ctx.seed, cfg["dtype"]["weights"], c["tokens"],
                c["positions"], c["served"])
            own_gaps = (np.asarray(own["best"])
                        - np.asarray(own["got"]))[c["valid"]]
            differ = (np.sort(np.asarray(own["chosen"]), -1)
                      != np.sort(c["routes"], -1)).any(-1)[c["consumed"]]
            notes["own_routing"] = {
                "logit_gap_max": float(own_gaps.max()),
                "logit_gap_mean": float(own_gaps.mean()),
                "choices_differing_pct": 100.0 * float(differ.mean())}
    failed = len([r for r in in_window if r.comp is None])
    return {"attempted": len(in_window), "failed": failed, "e2e": e2e,
            "setup_s": setup_s, "facts": facts, "notes": notes,
            "checks": checks, "memory_peak_bytes": memory_peak,
            "traced": traced}
