"""Driver: a model served in-process through ``ServeApp.submit_async`` with
a ``TokenStream`` per request: the call every HTTP handler of
``tony_tpu/cli/serve.py`` makes. No sockets, no orchestrator.

From the program: ``prepare_decode`` -> ``SlotServer`` -> ``ServeApp``, its
``Completion.trace`` spans and its counters. Everything else (weights from
the seed, traffic, clocks, the arithmetic of every metric, the reference)
is the benchmark's own.
"""

from __future__ import annotations

import collections
import threading
import time

import numpy as np

import lib


def transformer_config(cfg: dict, max_len: int):
    import jax.numpy as jnp
    from tony_tpu.models.transformer import TransformerConfig

    dt = cfg["dtype"]
    return TransformerConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], max_seq_len=max_len,
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        attn_window=int(cfg.get("sliding_window") or 0),
        dtype=jnp.dtype(dt["activations"]),
        param_dtype=jnp.dtype(dt["weights"]))


def program_params(cfg: dict, seed: int, dtype):
    """The seed's weights in the program's tree (one jitted call, on the
    device, in the dtype they are served or trained in)."""
    import jax

    weights = lib.load("weights/dense_decoder.py")
    h, kvh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    d = cfg["hidden_size"]

    def make(key):
        w = weights.whole(key, cfg, dtype)
        lw = dict(w["layers"])
        n = lw["wq"].shape[0]
        lw["wq"] = lw["wq"].reshape(n, d, h, hd)
        lw["wk"] = lw["wk"].reshape(n, d, kvh, hd)
        lw["wv"] = lw["wv"].reshape(n, d, kvh, hd)
        lw["wo"] = lw["wo"].reshape(n, h, hd, d)
        return {**w, "layers": lw}

    return make, weights.seed_key(seed)


def _timed_stream_class():
    from tony_tpu.api.stream import TokenStream

    class TimedStream(TokenStream):
        """A TokenStream that notes when each feed reached it: what a
        streaming client gets, and when."""

        def __init__(self):
            super().__init__(max_chunks=1 << 16)
            self.feeds: list = []        # (monotonic instant, n tokens)
            self.t_done: float | None = None

        def feed(self, emitted):
            n, stalled = super().feed(emitted)
            if n:
                self.feeds.append((time.monotonic(), n))
            return n, stalled

        def finish(self, reason):
            if self.t_done is None:
                self.t_done = time.monotonic()
            super().finish(reason)

        def fail(self, message):
            if self.t_done is None:
                self.t_done = time.monotonic()
            super().fail(message)

    return TimedStream


class _Sent:
    __slots__ = ("req", "due", "sent", "taken", "rid", "ev", "stream",
                 "comp", "error")

    def __init__(self, req, due):
        self.req, self.due = req, due
        self.sent = self.taken = self.rid = self.ev = self.stream = None
        self.comp = self.error = None


def _send(app, stream_cls, rec: _Sent) -> None:
    rec.stream = stream_cls()
    rec.sent = time.monotonic()
    try:
        rec.rid, rec.ev = app.submit_async(
            rec.req["prompt"], rec.req["max_new"], timeout=600.0,
            temperature=0.0, stream=rec.stream)
    except Exception as e:       # shed or refused: counts as failed
        rec.error = repr(e)
    rec.taken = time.monotonic()


def _load_loop(app, stream_cls, plan, t_origin, t_end, sent, stop):
    """Offers the whole load. Open loop: every request is sent at its due
    instant from a thread of its own, as each handler of ``cli/serve.py``
    calls ``submit_async`` on its connection's thread: a send that waits
    inside ``submit_async`` holds up no later one, so the arrivals are the
    mix's whatever the system does with them. Closed loop: ``clients``
    callers, a thread each, every one sending its next request when its
    last has completed."""
    reqs = collections.deque(plan["requests"])
    if plan["mode"] == "open_poisson":
        senders = []
        while reqs and not stop.is_set():
            req = reqs.popleft()
            due = t_origin + req["due_s"]
            while True:
                now = time.monotonic()
                if now >= due or stop.is_set():
                    break
                time.sleep(min(due - now, 0.05))
            if stop.is_set():
                break
            rec = _Sent(req, due)
            sent.append(rec)
            senders.append(threading.Thread(
                target=_send, name="bench-send", daemon=True,
                args=(app, stream_cls, rec)))
            senders[-1].start()
        for t in senders:
            t.join()
        return

    def client():
        while not stop.is_set() and time.monotonic() < t_end:
            try:
                req = reqs.popleft()
            except IndexError:
                return
            rec = _Sent(req, time.monotonic())
            _send(app, stream_cls, rec)
            sent.append(rec)
            if rec.error is not None:
                time.sleep(0.05)
                continue
            while not rec.ev.wait(0.1):
                if stop.is_set():
                    return

    clients = [threading.Thread(target=client, name=f"bench-client-{i}")
               for i in range(plan["clients"])]
    for t in clients:
        t.start()
    for t in clients:
        t.join()


def _warm_up(server, request_cls, engine: dict, vocab: int) -> None:
    """Every program the window can drive, through the engine's own step:
    the one-request chunk programs (both variants: a prompt of two
    chunks), each width of the batched admission (bursts of 2, 4, ... up
    to the slots), the decode block, and the one- and two-block reads of
    the drain."""
    rng = np.random.default_rng(0)
    n_prompt = engine["prefill_chunk"] + 2
    burst = 1
    while burst <= engine["slots"]:
        for _ in range(burst):
            server.submit(request_cls(
                prompt=rng.integers(0, vocab, n_prompt, dtype=np.int32),
                max_new_tokens=engine["block_size"] + 1, temperature=0.0))
        server.run_until_drained()
        burst *= 2


def _spans(comp) -> dict:
    trace = getattr(comp, "trace", None) or {}
    return {name: t for name, t in trace.get("spans", [])}


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp
    from tony_tpu.cli.serve import ServeApp
    from tony_tpu.models.generate import prepare_decode
    from tony_tpu.models.serving import Request, SlotServer

    cfg, mix, engine = ctx.cfg, ctx.mix, ctx.cfg["engine"]
    costs = lib.load("costs/dense_decoder.py")
    gen = lib.load("traffic/generate.py")
    tcfg = transformer_config(cfg, engine["max_len"])
    wdtype = jnp.dtype(cfg["dtype"]["weights"])

    make, key = program_params(cfg, ctx.seed, wdtype)
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
    _warm_up(server, Request, engine, cfg["vocab_size"])
    app = ServeApp(server)
    app.start()
    stream_cls = _timed_stream_class()
    plan = gen.requests(mix, cfg["vocab_size"], ctx.seed, ctx.seconds)

    watch = lib.CompileWatch.install()

    def counters():
        return {"admission_dispatches": server.admission_dispatches,
                "blocks_dispatched": server.blocks_dispatched,
                "prefill_tokens_computed": server.prefill_tokens_computed,
                "compiles": watch.count}

    sent: list = []
    stop = threading.Event()
    t_origin = time.monotonic() + 0.05
    t0 = t_origin + plan["lead_in_s"]
    t1 = t0 + ctx.seconds
    loader = threading.Thread(
        target=_load_loop, name="bench-load",
        args=(app, stream_cls, plan, t_origin, t1, sent, stop))
    loader.start()
    time.sleep(max(0.0, t0 - time.monotonic()))
    setup_s = time.monotonic() - ctx.t_start
    c0 = counters()
    traced = ctx.trace_window(t0, t1)       # blocks while the profiler runs
    time.sleep(max(0.0, t1 - time.monotonic()))
    c1 = counters()
    # the window has closed: no more load; every answer that is due gets
    # its grace, and is late, not wrong, if it comes within it
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
    del app, server, prepared

    # ------------------------------------------------- the window's numbers
    in_window = [r for r in sent if t0 <= r.due < t1]
    ok = [r for r in in_window if r.comp is not None and r.stream.feeds]
    ttft = [(r.stream.feeds[0][0] - r.due) * 1e3 for r in ok]
    tpot = []
    for r in ok:
        n = sum(k for _, k in r.stream.feeds)
        if len(r.stream.feeds) > 1 and n > 1:
            tpot.append((r.stream.feeds[-1][0] - r.stream.feeds[0][0])
                        * 1e3 / (n - r.stream.feeds[0][1]))
    # the gap a reader of the stream sees between tokens: a feed brings a
    # block of tokens at once, so each feed after a request's first counts
    # once per token it brings, at (time since the last feed) / tokens
    gaps, weights = [], []
    for r in ok:
        for (ta, _), (tb, k) in zip(r.stream.feeds, r.stream.feeds[1:]):
            gaps.append((tb - ta) * 1e3 / k)
            weights.append(k)
    token_gaps = np.repeat(gaps, weights)
    done_in = [r for r in sent if r.comp is not None
               and r.stream.t_done is not None and t0 <= r.stream.t_done < t1]
    tokens_done = sum(len(r.req["prompt"]) + len(r.comp.tokens)
                      for r in done_in)
    e2e = {"serve_tokens_per_s": tokens_done / ctx.seconds}
    if ttft:
        e2e["ttft_p95_ms"] = lib.percentile(ttft, 95)
    if tpot:
        e2e["tpot_p95_ms"] = lib.percentile(tpot, 95)
    if gaps:
        e2e["token_gap_p50_ms"] = lib.percentile(token_gaps, 50)

    # work of the window, for the per-layer readers: a prompt counts where
    # its admission fell, a generated token where its feed fell
    flops = 0.0
    decode_tokens = 0
    context_sum = 0.0
    queue_wait_ms = []
    for r in sent:
        if r.comp is None:
            continue
        sp = _spans(r.comp)
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
    late = [(r.sent - r.due) * 1e3 for r in in_window if r.sent is not None]
    submit_wait_ms = [(r.taken - r.sent) * 1e3 for r in in_window
                      if r.taken is not None]
    half = t0 + ctx.seconds / 2
    first = [(r.stream.feeds[0][0] - r.due) * 1e3 for r in ok if r.due < half]
    second = [(r.stream.feeds[0][0] - r.due) * 1e3 for r in ok
              if r.due >= half]
    facts = {
        "cfg": cfg, "engine": engine, "window_s": ctx.seconds,
        "chips": ctx.chips,
        "counters": {k: c1[k] - c0[k] for k in c0},
        "flops": flops,
        "decode_tokens": decode_tokens, "decode_context_sum": context_sum,
        "programs": {"decode": ["_decode_block"]},
    }

    def pct(values, q):
        return lib.percentile(values, q) if len(values) else None

    # what the host layers do to a request: on an earlier line of every
    # run, since no bound of at most 10% holds them (PERF.md 2)
    notes = {
        "requests_in_window": len(in_window), "with_first_token": len(ttft),
        "with_a_token_gap": len(tpot), "done_in_window": len(done_in),
        "unfinished_at_close": sum(
            1 for r in sent if r.due < t1 and (
                r.stream is None or r.stream.t_done is None
                or r.stream.t_done >= t1)),
        "ttft_p50_ms": pct(ttft, 50), "ttft_p95_ms": pct(ttft, 95),
        "ttft_mean_ms": float(np.mean(ttft)) if ttft else None,
        "ttft_p50_ms_by_half": [pct(first, 50), pct(second, 50)],
        "tpot_p50_ms": pct(tpot, 50), "tpot_p95_ms": pct(tpot, 95),
        "token_gap_mean_ms": float(np.mean(token_gaps)) if gaps else None,
        "token_gap_p95_ms": pct(token_gaps, 95) if gaps else None,
        "tokens_fed_per_s": decode_tokens / ctx.seconds,
        "requests_done_per_s": len(done_in) / ctx.seconds,
        "send_late_p95_ms": pct(late, 95),
        "submit_wait_p50_ms": pct(submit_wait_ms, 50),
        "submit_wait_p95_ms": pct(submit_wait_ms, 95),
        "queue_wait_p50_ms": pct(queue_wait_ms, 50),
        "queue_wait_p95_ms": pct(queue_wait_ms, 95),
        "compiled_in_window": watch.names[c0["compiles"]:c1["compiles"]],
        "counters": facts["counters"],
    }

    # ------------------------------------------------------------- correct
    checks = lib.Checks(ctx.cell.get("limits"))
    checks.add("compiles_in_window", c1["compiles"] - c0["compiles"], 0)
    finished = [r for r in sent if r.comp is not None]
    eos = set(engine["stop_tokens"])
    bad = sum(1 for r in finished
              if len(r.comp.tokens) != r.req["max_new"]
              and not (r.comp.tokens and r.comp.tokens[-1] in eos))
    bad += sum(1 for r in finished if sum(k for _, k in r.stream.feeds)
               != len(r.comp.tokens))
    checks.add("answers_of_wrong_length", bad, 0)
    if finished:
        gaps = served_gaps(cfg, mix, ctx.seed, finished)
        checks.add("logit_gap_max", float(np.max(gaps)))
        checks.add("logit_gap_mean", float(np.mean(gaps)))
        notes["served_tokens_compared"] = int(gaps.size)
        if ctx.control:
            low = served_gaps(cfg, mix, ctx.seed, finished, lowp=ctx.control)
            notes["control"] = checks.judge({
                "logit_gap_max": float(np.max(low)),
                "logit_gap_mean": float(np.mean(low))})
            # the fault a served token can have: one token altered where
            # it is produced reads, at the least, the smallest of these
            alt = served_gaps(cfg, mix, ctx.seed, finished, alter=1)
            notes["fault_token_altered"] = checks.judge({
                "logit_gap_max": float(np.min(alt))})
    failed = len([r for r in in_window if r.comp is None])
    return {"attempted": len(in_window), "failed": failed, "e2e": e2e,
            "setup_s": setup_s, "facts": facts, "notes": notes,
            "checks": checks, "memory_peak_bytes": memory_peak,
            "traced": traced}


def check_sample(mix: dict, seed: int, finished: list) -> list:
    """The requests whose served tokens are compared: the longest one
    (prompt + served) and ``check_requests`` - 1 others drawn from the
    seed."""
    order = sorted(range(len(finished)), key=lambda i: -(
        len(finished[i].req["prompt"]) + len(finished[i].comp.tokens)))
    rng = np.random.default_rng([seed % (2 ** 63), 41])
    rest = [int(i) for i in rng.permutation(order[1:])]
    pick = [order[0]] + rest[:max(0, mix["check_requests"] - 1)]
    return [finished[i] for i in pick]


def served_gaps(cfg: dict, mix: dict, seed: int, finished: list,
                lowp=None, alter: int = 0) -> np.ndarray:
    """For every served token of the sample: how far its logit lies below
    the reference's best at that position (0 where the reference would
    have served the same token). With ``lowp`` the control: the gap of the
    token that the lower-precision reference puts first instead. With
    ``alter`` the gap of the served token's id + ``alter``: what one
    altered token would read, position by position."""
    ref = lib.load("reference/" + cfg["reference"] + ".py")
    sample = check_sample(mix, seed, finished)
    p_max = mix["prompt_tokens"]["max"]
    o_max = mix["output_tokens"]["max"]
    width = -(-(p_max + o_max) // 128) * 128
    tokens = np.zeros((len(sample), width), np.int32)
    positions = np.zeros((len(sample), o_max), np.int32)
    served = np.zeros((len(sample), o_max), np.int32)
    valid = np.zeros((len(sample), o_max), bool)
    for i, r in enumerate(sample):
        p, out = len(r.req["prompt"]), np.asarray(r.comp.tokens, np.int32)
        tokens[i, :p] = r.req["prompt"]
        tokens[i, p:p + out.size] = out
        positions[i, :out.size] = p - 1 + np.arange(out.size)
        served[i, :out.size] = out
        valid[i, :out.size] = True
    wdtype = cfg["dtype"]["weights"]
    logits = np.asarray(ref.served_logits(
        cfg, seed, wdtype, tokens, positions))
    if lowp is not None:
        low = np.asarray(ref.served_logits(
            cfg, seed, wdtype, tokens, positions, lowp=lowp))
        served = low.argmax(-1).astype(np.int32)
    served = (served + alter) % logits.shape[-1]
    best = logits.max(-1)
    got = np.take_along_axis(logits, served[..., None], -1)[..., 0]
    return (best - got)[valid]
