"""Request-level serving telemetry (tony_tpu/observability.py).

The contract under test: every request that terminates — completed,
cancelled, expired, shed — leaves a complete, ordered lifecycle trace
(host-monotonic spans); the latency histograms those traces feed are
correct at the bucket level (boundaries, merge, quantiles); GET /metrics
renders everything in parseable Prometheus text format whose numbers
match /stats; and the 429 Retry-After header is a rate-derived estimate
that grows with the backlog instead of a constant. Model-backed tests
reuse the TINY shapes of tests/test_serving*.py so the tier-1 run hits
the already-compiled programs.
"""

import json
import re
import threading
import urllib.request
from http.server import ThreadingHTTPServer

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu import metrics as _metrics
from tony_tpu.cli.serve import ServeApp, make_handler
from tony_tpu.models import transformer
from tony_tpu.models.serving import (
    QueueFullError, Request, SlotServer,
)
from tony_tpu.observability import (
    Histogram,
    PromRenderer,
    RequestTrace,
    ServiceRateEstimator,
    ServingTelemetry,
    parse_prom_text,
)

TINY = transformer.TransformerConfig(
    vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
    d_ff=128, max_seq_len=128, dtype=jnp.float32,
)


@pytest.fixture(scope="module")
def params():
    return transformer.init(jax.random.PRNGKey(0), TINY)


def _srv(params, **kw):
    """Same shapes as tests/test_serving.py — the tier-1 run reuses the
    already-compiled programs."""
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("block_size", 4)
    kw.setdefault("prefill_chunk", 8)
    return SlotServer(params, TINY, **kw)


def _prompt(n, seed=5):
    rng = np.random.default_rng(seed)
    return rng.integers(0, TINY.vocab_size, size=n, dtype=np.int32)


# --------------------------------------------------------------------------
# Histogram: boundaries, merge, quantiles
# --------------------------------------------------------------------------

def test_histogram_bucket_boundaries():
    h = Histogram(lo=1.0, hi=1000.0, per_decade=1)
    assert h.bounds == [1.0, 10.0, 100.0, 1000.0]
    h.observe(0.5)          # <= lo: first bucket
    h.observe(10.0)         # ON a boundary: le semantics, bucket le=10
    h.observe(10.0001)      # just past it: next bucket
    h.observe(5000.0)       # past hi: +Inf overflow
    assert h.counts == [1, 1, 1, 0, 1]
    assert h.count == 4
    assert h.sum == pytest.approx(0.5 + 10.0 + 10.0001 + 5000.0)


def test_histogram_merge():
    a = Histogram(lo=1.0, hi=100.0, per_decade=1)
    b = Histogram(lo=1.0, hi=100.0, per_decade=1)
    for v in (0.5, 5.0):
        a.observe(v)
    for v in (50.0, 5000.0):
        b.observe(v)
    a.merge(b)
    assert a.counts == [1, 1, 1, 1]
    assert a.count == 4 and a.sum == pytest.approx(5055.5)
    with pytest.raises(ValueError, match="different buckets"):
        a.merge(Histogram(lo=1.0, hi=100.0, per_decade=2))


def test_histogram_quantiles_known_distribution():
    h = Histogram(lo=1e-3, hi=100.0, per_decade=5)
    for k in range(1, 1001):                # uniform on (0, 1]
        h.observe(k / 1000.0)
    # bucket-resolution estimates: within the containing log bucket
    assert 0.35 < h.quantile(0.5) < 0.66
    assert 0.80 < h.quantile(0.99) <= 1.01
    qs = [h.quantile(q) for q in (0.0, 0.1, 0.5, 0.9, 0.99, 1.0)]
    assert qs == sorted(qs), "quantiles must be monotone in q"
    assert h.mean == pytest.approx(0.5005, rel=1e-6)
    assert Histogram().quantile(0.5) == 0.0         # empty: defined as 0
    with pytest.raises(ValueError):
        h.quantile(1.5)


def test_histogram_snapshot_shape():
    h = Histogram()
    h.observe(0.02)
    snap = h.snapshot()
    assert snap["count"] == 1
    assert set(snap) == {"count", "mean_s", "p50_s", "p90_s", "p99_s"}


# --------------------------------------------------------------------------
# Prometheus exposition: golden format
# --------------------------------------------------------------------------

# one exposition line: a comment, or name{labels} value
_PROM_LINE = re.compile(
    r"^(# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* .+|"
    r"[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? [^\s]+)$")


def test_prom_renderer_golden():
    h = Histogram(lo=1.0, hi=100.0, per_decade=1)
    for v in (0.5, 5.0, 50.0, 500.0):
        h.observe(v)
    r = PromRenderer()
    r.gauge("g_one", 3, "a gauge")
    r.counter("c_total", 7, "a counter", labels={"kind": "x"})
    r.histogram("h_seconds", h, "a histogram")
    text = r.render()
    assert text == (
        "# HELP g_one a gauge\n"
        "# TYPE g_one gauge\n"
        "g_one 3\n"
        "# HELP c_total a counter\n"
        "# TYPE c_total counter\n"
        'c_total{kind="x"} 7\n'
        "# HELP h_seconds a histogram\n"
        "# TYPE h_seconds histogram\n"
        'h_seconds_bucket{le="1"} 1\n'
        'h_seconds_bucket{le="10"} 2\n'
        'h_seconds_bucket{le="100"} 3\n'
        'h_seconds_bucket{le="+Inf"} 4\n'
        "h_seconds_sum 555.5\n"
        "h_seconds_count 4\n"
    )
    for line in text.strip().splitlines():
        assert _PROM_LINE.match(line), f"unparseable line: {line!r}"


def test_prom_renderer_sanitizes_and_groups():
    r = PromRenderer()
    r.gauge("weird-name.x", 1, "g", labels={"a b": 'q"uote\nnl'})
    r.gauge("weird-name.x", 2, "g", labels={"a b": "two"})
    text = r.render()
    # one TYPE line for the family, two samples, escaped label value
    assert text.count("# TYPE weird_name_x gauge") == 1
    assert 'weird_name_x{a_b="q\\"uote\\nnl"} 1' in text
    assert 'weird_name_x{a_b="two"} 2' in text


# --------------------------------------------------------------------------
# Retry-After estimation
# --------------------------------------------------------------------------

def test_service_rate_estimator_retry_after():
    est = ServiceRateEstimator()
    assert est.retry_after_s(0, 8) == 1         # no observations: floor
    for _ in range(20):
        est.observe(8.0)
    assert est.service_time_s == pytest.approx(8.0)
    assert est.retry_after_s(0, 2) == 4         # 8s * 1 waiter / 2 slots
    assert est.retry_after_s(1000, 2) == 60     # ceiling clamp
    vals = [est.retry_after_s(q, 2) for q in range(0, 40, 4)]
    assert vals == sorted(vals) and vals[-1] > vals[0], (
        "Retry-After must grow with queue depth")
    fast = ServiceRateEstimator()
    fast.observe(0.01)
    assert fast.retry_after_s(0, 8) == 1        # sub-second: 1s floor


def test_retry_after_monotone_under_saturated_queue(params):
    """SlotServer surface: with a fixed observed service rate, every
    added waiter advances (never shrinks) the advertised retry — the
    header a saturated queue sends is ordered by backlog depth. No
    step() calls: submission-only, so no compiled programs run."""
    srv = _srv(params)
    srv._rate.observe(4.0)          # as if requests served in ~4s
    seen = []
    for i in range(12):
        srv.submit(Request(prompt=_prompt(3, seed=i), max_new_tokens=4))
        seen.append(srv.estimate_retry_after())
    assert seen == sorted(seen) and seen[-1] > seen[0]
    assert all(isinstance(v, int) and 1 <= v <= 60 for v in seen)


# --------------------------------------------------------------------------
# trace spans: ordering + completeness for every terminal
# --------------------------------------------------------------------------

def _span_names(comp):
    assert comp.trace is not None, "terminated request lost its trace"
    return [n for n, _ in comp.trace["spans"]]


def _assert_ordered(comp):
    ts = [t for _, t in comp.trace["spans"]]
    assert ts == sorted(ts), f"spans out of order: {comp.trace['spans']}"


def test_trace_lifecycle_every_terminal(params):
    """One server, four fates: a completed request records the full
    submitted->admitted->prefill_done->first_token->finished chain; a
    cancelled-in-queue request ends at cancelled with no admission; an
    expired request ends at expired; a shed request never enters the
    queue but still reaches the sink with a submitted->shed trace."""
    sink = []
    srv = _srv(params, max_queue=2, trace_sink=sink.append)
    a = Request(prompt=_prompt(5), max_new_tokens=6)
    b = Request(prompt=_prompt(4, seed=6), max_new_tokens=4)
    srv.submit(a)
    srv.submit(b)                   # queue now at max_queue=2
    shed_req = Request(prompt=_prompt(3, seed=7), max_new_tokens=4)
    with pytest.raises(QueueFullError) as shed_exc:
        srv.submit(shed_req)
    # the 429 handler reads the estimate off the error — no second
    # lock round trip on the shed fast path
    assert 1 <= shed_exc.value.retry_after_s <= 60
    assert srv.cancel(b.id) is True
    expired = Request(prompt=_prompt(4, seed=8), max_new_tokens=4,
                      deadline=-1.0)        # monotonic instant in the past
    srv.submit(expired)
    done = srv.run_until_drained()

    comp = done[a.id]
    assert comp.finish_reason == "length"
    assert _span_names(comp) == ["submitted", "admitted", "prefill_done",
                                 "first_token", "finished"]
    _assert_ordered(comp)
    assert comp.trace["attrs"]["n_tokens"] == len(comp.tokens) == 6
    assert comp.trace["attrs"]["finish_reason"] == "length"
    assert comp.trace["attrs"]["prefix_hit_blocks"] == 0
    assert comp.trace["attrs"]["prompt_tokens"] == 5

    assert _span_names(done[b.id]) == ["submitted", "cancelled"]
    assert _span_names(done[expired.id]) == ["submitted", "expired"]
    for rid in (b.id, expired.id):
        _assert_ordered(done[rid])

    # the shed request reached the sink even though submit() raised
    by_id = {r["id"]: r for r in sink}
    assert [n for n, _ in by_id[shed_req.id]["spans"]] == [
        "submitted", "shed"]
    assert set(by_id) == {a.id, b.id, expired.id, shed_req.id}, (
        "every terminated request must reach the trace sink")

    # histogram feed: only the served request has ttft/queue_wait/tpot,
    # every terminal contributes an e2e observation
    tel = srv.telemetry
    assert tel.hist["ttft_s"].count == 1
    assert tel.hist["queue_wait_s"].count == 1
    assert tel.hist["tpot_s"].count == 1
    assert tel.hist["e2e_s"].count == 4
    assert tel.hist["decode_block_s"].count == srv.blocks_dispatched > 0
    assert not srv._traces, "trace registry must drain with the requests"


def test_trace_mid_decode_cancel(params):
    """A request cancelled mid-decode still closes its trace in order:
    the spans it earned (admission, prefill, first token) stay, the
    terminal is cancelled, and n_tokens matches the partial output."""
    srv = _srv(params)
    a = Request(prompt=_prompt(4, seed=9), max_new_tokens=24)
    c = Request(prompt=_prompt(4, seed=10), max_new_tokens=24)
    srv.submit(a)
    srv.submit(c)
    for _ in range(3):
        srv.step()
    assert srv.cancel(a.id) is True
    done = srv.run_until_drained()
    comp = done[a.id]
    assert comp.finish_reason == "cancelled"
    names = _span_names(comp)
    assert names[0] == "submitted" and names[-1] == "cancelled"
    assert "admitted" in names and "prefill_done" in names
    _assert_ordered(comp)
    assert comp.trace["attrs"]["n_tokens"] == len(comp.tokens) > 0
    assert _span_names(done[c.id])[-1] == "finished"
    assert not srv._traces


def test_device_lag_measured_on_traces(params):
    """Device-time attribution on the live serving path: every served
    request's trace carries the MEASURED device lag (dispatch-tracker
    ready instant vs host observation) where the old contract only
    documented a pipeline_depth bound, the lag distribution feeds the
    device_lag_s histogram, and the tracker's per-kind dispatch→ready
    histograms cover prefill and decode blocks."""
    srv = _srv(params)
    try:
        a = Request(prompt=_prompt(5, seed=30), max_new_tokens=6)
        srv.submit(a)
        done = srv.run_until_drained()
        comp = done[a.id]
        assert comp.finish_reason == "length"
        lag = comp.trace["attrs"].get("device_lag_s")
        lag_ft = comp.trace["attrs"].get("device_lag_first_token_s")
        assert lag is not None and lag >= 0.0
        assert lag_ft is not None and lag_ft >= 0.0
        assert srv.telemetry.hist["device_lag_s"].count > 0
        assert srv.dispatch_tracker.drain(timeout=10)
        snap = srv.dispatch_tracker.snapshot()
        assert snap["in_flight"] == 0 and snap["dropped"] == 0
        assert snap["dispatch_ready"]["prefill"]["count"] >= 1
        assert snap["dispatch_ready"]["decode_block"]["count"] >= 1
        assert snap["tracked"] == sum(
            h["count"] for h in snap["dispatch_ready"].values())
        # stats() mirrors the tracker under "device"
        assert srv.stats()["device"]["tracked"] == snap["tracked"]
    finally:
        srv.shutdown()


def test_reset_seals_inflight_traces(params):
    """reset() with replay OFF must not leak traces: in-flight
    requests' traces end at the failed terminal, queued ones survive."""
    sink = []
    srv = _srv(params, trace_sink=sink.append, replay=False)
    a = Request(prompt=_prompt(4, seed=11), max_new_tokens=16)
    srv.submit(a)
    srv.step()                          # admit + first block
    queued = Request(prompt=_prompt(4, seed=12), max_new_tokens=4)
    srv.submit(queued)
    lost = srv.reset()
    assert lost == [a.id]
    by_id = {r["id"]: r for r in sink}
    assert [n for n, _ in by_id[a.id]["spans"]][-1] == "failed"
    assert queued.id in srv._traces, "queued request's trace must survive"
    done = srv.run_until_drained()
    assert _span_names(done[queued.id])[-1] == "finished"


def test_reset_replay_trace_continuity(params):
    """reset() with replay ON (default): the in-flight request's trace
    is NOT sealed — it gains a 'replayed' mark, repeats the admission
    chain, terminates once, and feeds the replay-catchup histogram."""
    sink = []
    srv = _srv(params, trace_sink=sink.append)
    a = Request(prompt=_prompt(4, seed=13), max_new_tokens=16)
    srv.submit(a)
    srv.step()                          # admit + first block
    assert srv.reset() == []
    assert not sink, "a replayed request's trace must not be sealed"
    done = srv.run_until_drained()
    names = _span_names(done[a.id])
    assert "replayed" in names and names[-1] == "finished"
    assert names.count("admitted") == 2, "the admission chain repeats"
    assert names.count("finished") == 1
    assert done[a.id].trace["attrs"]["replays"] == 1
    assert len(sink) == 1, "exactly one sealed record per request"
    assert srv.telemetry.hist["replay_catchup_s"].count == 1


# --------------------------------------------------------------------------
# GET /metrics: exposition golden test against a live serve instance
# --------------------------------------------------------------------------

def _parse_samples(text):
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        name_labels, value = line.rsplit(" ", 1)
        out[name_labels] = float(value)
    return out


def test_metrics_endpoint_matches_stats(params):
    """GET /metrics on a running serve instance: Prometheus-parseable,
    contains the TTFT/TPOT/queue-wait histograms and every SERVING_*
    series, histogram buckets are cumulative with _count equal to the
    +Inf bucket, and the gauge values agree with GET /stats."""
    srv = _srv(params)
    app = ServeApp(srv)
    app.start()
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(app))
    port = httpd.server_address[1]
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        comp = app.generate(_prompt(5, seed=13), 5, timeout=120)
        assert len(comp.tokens) == 5
        # let the dispatch reaper catch up so the device-time series are
        # consistent between the two scrapes below
        assert srv.dispatch_tracker.drain(timeout=10)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
            assert r.status == 200
            assert r.headers["Content-Type"].startswith("text/plain")
            text = r.read().decode()
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/stats", timeout=10) as r:
            stats = json.loads(r.read())

        for line in text.strip().splitlines():
            assert _PROM_LINE.match(line), f"unparseable line: {line!r}"
        # exposition conformance: the serve payload round-trips the
        # SHARED strict parser (the one the fleet hub scrapes with) —
        # cumulative buckets, +Inf == _count, no duplicate series
        fams = parse_prom_text(text, strict=True)
        assert "serving_ttft_seconds" in fams
        # every SERVING_* series named in metrics.py is present — except
        # the speculative families, which render only for spec-enabled
        # engines (this server has no draft; their live rendering is
        # asserted in tests/test_spec_serving.py's metrics-labels test),
        # and the paged-pool/KV-transfer families, which render only
        # for paged engines (live rendering asserted in
        # tests/test_streaming.py's disaggregated two-leg e2e)
        for attr in dir(_metrics):
            if attr.startswith("SERVING_") and \
                    not attr.startswith(("SERVING_SPEC_", "SERVING_KV_")):
                assert getattr(_metrics, attr) in text, (
                    f"{attr} series missing from /metrics")
        for fam in ("serving_ttft_seconds", "serving_tpot_seconds",
                    "serving_queue_wait_seconds", "serving_e2e_seconds",
                    "serving_device_lag_seconds",
                    "serving_xla_compile_seconds"):
            assert f"# TYPE {fam} histogram" in text
        # device-time attribution families: dispatch→ready per program
        # kind, the in-flight depth gauge, and the compile counters
        assert ('serving_dispatch_ready_seconds_bucket{kind="decode_block"'
                in text)
        assert 'serving_dispatch_ready_seconds_count{kind="prefill"}' in text
        assert "# TYPE serving_inflight_dispatches gauge" in text
        assert "# TYPE serving_xla_compiles_total counter" in text
        assert "serving_xla_recompiles_post_warm_total" in text

        samples = _parse_samples(text)
        # /stats names the device beside the dispatch attribution
        assert stats["device"]["platform"] == "cpu"
        assert stats["device"]["count"] >= 1 and stats["device"]["kind"]
        assert samples["serving_inflight_dispatches"] == 0
        assert samples["serving_dispatches_tracked_total"] == (
            stats["device"]["tracked"]) > 0
        assert samples["serving_dispatch_track_dropped_total"] == 0
        assert samples["serving_dispatch_reap_errors_total"] == 0
        # a delivered completion drew the warmup line; the compile
        # snapshot on /stats matches the exposition counters
        assert stats["compile"]["warm"] is True
        assert samples["serving_xla_compiles_total"] == (
            stats["compile"]["compiles"])
        assert samples["serving_device_lag_seconds_count"] == (
            stats["latency"]["device_lag_s"]["count"]) > 0
        # histogram buckets are cumulative and consistent with _count —
        # the UNLABELED (process-aggregate) series; the {model=...}
        # partition interleaves its own cumulative series in the same
        # family (asserted in tests/test_spec_serving.py)
        buckets = [(nl, v) for nl, v in samples.items()
                   if nl.startswith('serving_ttft_seconds_bucket{le=')]
        counts = [v for _, v in buckets]
        assert counts and counts == sorted(counts), (
            "buckets must be cumulative")
        assert counts[-1] == samples["serving_ttft_seconds_count"] == 1
        # gauges/counters agree with /stats
        assert samples["serving_queue_depth"] == stats["queued"]
        assert samples["serving_active_slots"] == stats["active"]
        assert samples["serving_shed_total"] == stats["shed"]
        assert samples["serving_retry_after_s"] == stats["retry_after_s"]
        assert samples["serving_blocks_dispatched_total"] == (
            stats["blocks_dispatched"])
        # /stats grew the latency section with the same count
        assert stats["latency"]["ttft_s"]["count"] == 1
    finally:
        httpd.shutdown()
        httpd.server_close()
        app.shutdown()


# --------------------------------------------------------------------------
# satellites: StepTimer clock, telemetry plumbing units
# --------------------------------------------------------------------------

def test_step_timer_uses_monotonic_clock(monkeypatch):
    """Durations must come from time.monotonic() — a wall-clock jump
    (NTP) used to corrupt step stats with negative durations."""
    from tony_tpu.train import profiling

    fake = {"t": 100.0}
    monkeypatch.setattr(profiling.time, "monotonic", lambda: fake["t"])
    timer = profiling.StepTimer(window=4)
    timer.tick()
    fake["t"] += 2.5
    assert timer.tick() == pytest.approx(2.5)
    assert timer.steps_per_sec == pytest.approx(1 / 2.5)


def test_histogram_state_roundtrip():
    """state()/restore(): the persistence pair resumes cumulative
    buckets exactly (JSON round trip included — the serve CLI persists
    through json) and refuses mismatched bucket layouts."""
    h = Histogram(lo=1.0, hi=100.0, per_decade=1)
    for v in (0.5, 5.0, 5000.0):
        h.observe(v)
    dumped = json.loads(json.dumps(h.state()))
    h2 = Histogram(lo=1.0, hi=100.0, per_decade=1)
    h2.restore(dumped)
    assert h2.counts == h.counts
    assert h2.count == 3 and h2.sum == pytest.approx(h.sum)
    h2.observe(5.0)                     # restored histograms keep counting
    assert h2.count == 4
    with pytest.raises(ValueError, match="different buckets"):
        Histogram(lo=1.0, hi=100.0, per_decade=2).restore(dumped)


def test_telemetry_persists_across_reset_and_restart(params):
    """Histogram persistence (ROADMAP follow-up): SlotServer.reset()
    must NOT zero the latency histograms, and a fresh server (process
    restart) resumes the cumulative buckets via ServingTelemetry
    state()/restore() — /metrics rate() windows survive a re-arm."""
    srv = _srv(params)
    srv.submit(Request(prompt=_prompt(4, seed=20), max_new_tokens=4))
    srv.run_until_drained()
    assert srv.telemetry.hist["e2e_s"].count == 1
    ttft_sum = srv.telemetry.hist["ttft_s"].sum

    lost = srv.reset()                  # loop recovery: nothing in flight
    assert lost == []
    assert srv.telemetry.hist["e2e_s"].count == 1, (
        "reset() must preserve cumulative histogram buckets")

    state = json.loads(json.dumps(srv.telemetry.state()))
    srv2 = _srv(params)                 # fresh process: restore the dump
    srv2.telemetry.restore(state)
    assert srv2.telemetry.hist["ttft_s"].sum == pytest.approx(ttft_sum)
    srv2.submit(Request(prompt=_prompt(4, seed=21), max_new_tokens=4))
    srv2.run_until_drained()
    assert srv2.telemetry.hist["e2e_s"].count == 2, (
        "restored buckets must keep accumulating")
    # unknown histogram names in an old dump are skipped, not fatal
    srv2.telemetry.restore({"no_such_hist_s": {"bounds": [], "counts": [],
                                               "count": 0, "sum": 0.0}})


# --------------------------------------------------------------------------
# metrics-name lint: constants <-> renderers <-> docs must agree
# --------------------------------------------------------------------------

def test_metrics_names_rendered_and_documented():
    """Drift lint over the metric-name vocabulary: (a) every name
    constant in tony_tpu/metrics.py is documented in
    docs/observability.md; (b) every Prometheus-family constant
    (serving_*/driver_*/router_*) is referenced by a renderer
    (cli/serve.py, driver.py, portal/server.py, router.py); (c) every
    serving_/driver_/portal_/router_ family the doc names maps back to
    something the code actually renders. A new constant nobody renders,
    a renderer series nobody documents, or a doc entry for a deleted
    series all fail here."""
    import inspect
    from pathlib import Path

    import tony_tpu.cli.serve as serve_mod
    import tony_tpu.driver as driver_mod
    import tony_tpu.observability as obs
    import tony_tpu.portal.server as portal_mod
    import tony_tpu.router as router_mod
    import tony_tpu.slo as slo_mod

    consts = {name: val for name, val in vars(_metrics).items()
              if name.isupper() and isinstance(val, str)}
    assert consts, "metrics.py lost its name constants?"
    doc = (Path(__file__).resolve().parent.parent
           / "docs" / "observability.md").read_text()

    undocumented = sorted(v for v in consts.values() if f"`{v}`" not in doc)
    assert not undocumented, (
        f"metrics.py names missing from docs/observability.md "
        f"(backticked): {undocumented}")

    # slo.py renders INTO the driver's exposition (SLOEngine.render_into
    # appends the driver_slo_* families to the driver's renderer), so it
    # counts as a renderer source for the sweep
    sources = "".join(inspect.getsource(mod) for mod in
                      (serve_mod, driver_mod, portal_mod, router_mod,
                       slo_mod))
    unrendered = sorted(
        f"{name} ({val})" for name, val in consts.items()
        if val.startswith(("serving_", "driver_", "router_"))
        and name not in sources and f'"{val}"' not in sources)
    assert not unrendered, f"constants no renderer references: {unrendered}"

    rendered = set(consts.values())
    rendered |= set(re.findall(
        r'"((?:serving|driver|portal|router)_[a-z0-9_]+)"', sources))
    rendered |= {"serving_" + n[:-2] + "_seconds"
                 for n in obs.TELEMETRY_HISTOGRAMS}

    def base(name):
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix) and name[:-len(suffix)] in rendered:
                return name[:-len(suffix)]
        return name

    # PERF.json section names share the serving_ prefix but are bench
    # artifacts, not exposition families
    rendered |= {"serving_latency", "serving_robustness", "serving_fleet"}
    doc_names = set(re.findall(
        r"`((?:serving|driver|portal|router)_[a-z0-9_]+)`", doc))
    phantom = sorted(n for n in doc_names if base(n) not in rendered)
    assert not phantom, (
        f"docs/observability.md names no endpoint renders: {phantom}")

    # the device-time/compile families are pinned EXPLICITLY (not just
    # via the generic sweep): each must be rendered by an endpoint and
    # documented — renaming either side without the other fails here
    for fam in ("serving_dispatch_ready_seconds",
                "serving_inflight_dispatches",
                "serving_dispatches_tracked_total",
                "serving_dispatch_track_dropped_total",
                "serving_dispatch_reap_errors_total",
                "serving_device_lag_seconds",
                "serving_xla_compile_seconds",
                "serving_xla_compiles_total",
                "serving_xla_recompiles_post_warm_total",
                "driver_xla_compile_seconds",
                "driver_xla_compiles_total"):
        assert fam in rendered, f"device/compile family unrendered: {fam}"
        assert fam in doc_names, f"device/compile family undocumented: {fam}"

    # the fleet-router + fleet-replica families are pinned EXPLICITLY
    # the same way (ISSUE 7 lint discipline): each must be rendered by
    # an endpoint (router /metrics, driver /metrics) and documented —
    # renaming either side without the other fails here
    for fam in (_metrics.ROUTER_REPLICA_UP,
                _metrics.ROUTER_REPLICAS_LIVE,
                _metrics.ROUTER_REQUESTS_TOTAL,
                _metrics.ROUTER_RETRIES_TOTAL,
                _metrics.ROUTER_SHED_TOTAL,
                _metrics.ROUTER_FAILED_TOTAL,
                _metrics.ROUTER_EJECTIONS_TOTAL,
                _metrics.ROUTER_ROUTING_SECONDS,
                _metrics.ROUTER_E2E_SECONDS,
                _metrics.ROUTER_AFFINITY_HITS_TOTAL,
                _metrics.ROUTER_AFFINITY_REQUESTS_TOTAL,
                _metrics.ROUTER_AFFINITY_HIT_RATIO,
                _metrics.DRIVER_TASK_SERVICE_PORT,
                _metrics.DRIVER_TASK_ROLLS_TOTAL):
        assert fam in rendered, f"fleet family unrendered: {fam}"
        assert fam in doc_names, f"fleet family undocumented: {fam}"

    # the elastic-training families are pinned EXPLICITLY the same way
    # (ISSUE 9 lint discipline): each must be rendered by the driver
    # /metrics endpoint and documented — renaming either side without
    # the other fails here
    for fam in (_metrics.DRIVER_PREEMPTIONS_TOTAL,
                _metrics.DRIVER_GANG_RESIZES_TOTAL,
                _metrics.DRIVER_CHECKPOINT_AGE_S):
        assert fam in rendered, f"elastic family unrendered: {fam}"
        assert fam in doc_names, f"elastic family undocumented: {fam}"

    # the warm-pool families are pinned EXPLICITLY the same way
    # (ISSUE 10 lint discipline): each must be rendered by the driver
    # /metrics endpoint and documented — renaming either side without
    # the other fails here
    for fam in (_metrics.DRIVER_WARM_POOL_SIZE,
                _metrics.DRIVER_WARM_POOL_ADOPTIONS_TOTAL,
                _metrics.DRIVER_WARM_POOL_MISSES_TOTAL):
        assert fam in rendered, f"warm-pool family unrendered: {fam}"
        assert fam in doc_names, f"warm-pool family undocumented: {fam}"

    # the request-durability/replay families are pinned EXPLICITLY the
    # same way (ISSUE 11 lint discipline): each must be rendered by an
    # endpoint (serve /metrics, router /metrics) and documented —
    # renaming either side without the other fails here
    for fam in (_metrics.SERVING_REPLAYS_TOTAL,
                _metrics.SERVING_REPLAYED_TOKENS_TOTAL,
                _metrics.ROUTER_FAILOVERS_TOTAL,
                "serving_replay_catchup_seconds"):
        assert fam in rendered, f"replay family unrendered: {fam}"
        assert fam in doc_names, f"replay family undocumented: {fam}"

    # the control-plane-recovery families are pinned EXPLICITLY the
    # same way (ISSUE 12 lint discipline): each must be rendered by an
    # endpoint (driver /metrics, router /metrics) and documented —
    # renaming either side without the other fails here
    for fam in (_metrics.DRIVER_RECOVERIES_TOTAL,
                _metrics.DRIVER_TASKS_READOPTED_TOTAL,
                _metrics.ROUTER_DISCOVERY_STALE):
        assert fam in rendered, f"recovery family unrendered: {fam}"
        assert fam in doc_names, f"recovery family undocumented: {fam}"

    # the speculative-decoding + multi-model families are pinned
    # EXPLICITLY the same way (ISSUE 13 lint discipline): each must be
    # rendered by serve /metrics and documented — renaming either side
    # without the other fails here
    for fam in (_metrics.SERVING_MODELS,
                _metrics.SERVING_SPEC_ROUNDS_TOTAL,
                _metrics.SERVING_SPEC_PROPOSED_TOKENS_TOTAL,
                _metrics.SERVING_SPEC_ACCEPTED_TOKENS_TOTAL,
                _metrics.SERVING_SPEC_GAMMA,
                _metrics.SERVING_SPEC_ACCEPTANCE_RATE,
                _metrics.SERVING_SPEC_VERIFY_ROUNDS):
        assert fam in rendered, f"spec/model family unrendered: {fam}"
        assert fam in doc_names, f"spec/model family undocumented: {fam}"
    # the streaming-delivery families are pinned EXPLICITLY the same
    # way (ISSUE 14 lint discipline): each must be rendered by an
    # endpoint (serve /metrics, router /metrics) and documented —
    # renaming either side without the other fails here
    for fam in (_metrics.SERVING_STREAMS_ACTIVE,
                _metrics.SERVING_STREAMS_OPENED_TOTAL,
                _metrics.SERVING_STREAM_STALLS_TOTAL,
                _metrics.SERVING_STREAM_DISCONNECTS_TOTAL,
                _metrics.ROUTER_STREAMS_ACTIVE,
                _metrics.ROUTER_STREAMED_TOKENS_TOTAL,
                _metrics.ROUTER_STREAM_FAILOVERS_TOTAL,
                _metrics.ROUTER_STREAM_DISCONNECTS_TOTAL,
                "serving_stream_itl_seconds"):
        assert fam in rendered, f"streaming family unrendered: {fam}"
        assert fam in doc_names, f"streaming family undocumented: {fam}"

    # the autoscaler + quota families are pinned EXPLICITLY the same
    # way (ISSUE 15 lint discipline): each must be rendered by the
    # driver /metrics endpoint and documented — renaming either side
    # without the other fails here
    for fam in (_metrics.DRIVER_AUTOSCALE_SCALE_UPS_TOTAL,
                _metrics.DRIVER_AUTOSCALE_SCALE_DOWNS_TOTAL,
                _metrics.DRIVER_AUTOSCALE_REPLICAS,
                _metrics.DRIVER_AUTOSCALE_TTFT_P99_S,
                _metrics.DRIVER_AUTOSCALE_QUEUE_DEPTH,
                _metrics.DRIVER_QUOTA_POOL_SLOTS,
                _metrics.DRIVER_QUOTA_POOL_FREE,
                _metrics.DRIVER_QUOTA_SLOTS,
                _metrics.DRIVER_QUOTA_DONATIONS_TOTAL,
                _metrics.DRIVER_QUOTA_RECLAIMS_TOTAL):
        assert fam in rendered, f"autoscale/quota family unrendered: {fam}"
        assert fam in doc_names, (
            f"autoscale/quota family undocumented: {fam}")

    # the disaggregated-serving families are pinned EXPLICITLY the same
    # way (ISSUE 17 lint discipline): pool occupancy by owner plus the
    # KV-transfer counters on serve /metrics, and the split-request
    # accounting on router /metrics — each must be rendered and
    # documented; renaming either side without the other fails here
    for fam in (_metrics.SERVING_KV_POOL_BLOCKS,
                _metrics.SERVING_KV_EXPORTS_TOTAL,
                _metrics.SERVING_KV_IMPORTS_TOTAL,
                _metrics.SERVING_KV_IMPORT_REJECTS_TOTAL,
                _metrics.ROUTER_DISAGG_REQUESTS_TOTAL,
                _metrics.ROUTER_DISAGG_HANDOFFS_TOTAL,
                _metrics.ROUTER_DISAGG_FALLBACKS_TOTAL):
        assert fam in rendered, f"disagg family unrendered: {fam}"
        assert fam in doc_names, f"disagg family undocumented: {fam}"

    # the router-tier HA families are pinned EXPLICITLY the same way
    # (ISSUE 18 lint discipline): each front door's self-telemetry on
    # router /metrics, and the driver's {tier="router"} partition of
    # the autoscale families — each must be rendered and documented;
    # renaming either side without the other fails here
    for fam in (_metrics.ROUTER_FLEET_SIZE,
                _metrics.ROUTER_REPLICAS,
                _metrics.ROUTER_RELAY_INFLIGHT):
        assert fam in rendered, f"router-tier family unrendered: {fam}"
        assert fam in doc_names, f"router-tier family undocumented: {fam}"
    # the tier="router" label partition of the autoscale counters and
    # gauges is a rendered contract too, both directions: the driver
    # renderer must attach it and the doc must describe it
    driver_src = inspect.getsource(driver_mod)
    assert '{"tier": "router"}' in driver_src, (
        "driver /metrics lost its tier=router autoscale partition")
    assert 'tier="router"' in doc, (
        "docs/observability.md lost the tier=router label description")

    # the distributed-tracing families are pinned EXPLICITLY the same
    # way (ISSUE 19 lint discipline): the per-leg router histograms on
    # router /metrics — each must be rendered and documented; renaming
    # either side without the other fails here. The leg label
    # vocabulary is contract too, both directions: the router must
    # build a histogram per leg and the doc must name every leg.
    for fam in (_metrics.ROUTER_LEG_SECONDS,):
        assert fam in rendered, f"tracing family unrendered: {fam}"
        assert fam in doc_names, f"tracing family undocumented: {fam}"
    router_src = inspect.getsource(router_mod)
    for leg in ("prefill", "transfer", "decode", "relay"):
        assert f'"{leg}"' in router_src, (
            f"router lost the {leg} leg histogram")
        assert f"`{leg}`" in doc, (
            f"docs/observability.md lost the {leg} leg description")

    # the model-labeled partition is a rendered contract too: the serve
    # renderer must attach {model=...} labels somewhere (the per-model
    # block) and the doc must describe the label
    serve_src = inspect.getsource(serve_mod)
    assert '{"model": name}' in serve_src, (
        "serve /metrics lost its per-model label partition")
    assert "Per-model labels" in doc, (
        "docs/observability.md lost the per-model-labels section")

    # the metrics-pipeline + SLO families are pinned EXPLICITLY the
    # same way (ISSUE 20 lint discipline): the hub's self-telemetry,
    # the unified scrape-failure counter, and the burn-rate/budget/
    # alert families on driver /metrics — each must be rendered and
    # documented; renaming either side without the other fails here
    for fam in (_metrics.DRIVER_AUTOSCALE_SCRAPE_FAILURES_TOTAL,
                _metrics.DRIVER_METRICSHUB_SCRAPES_TOTAL,
                _metrics.DRIVER_METRICSHUB_SERIES,
                _metrics.DRIVER_METRICSHUB_TARGETS,
                _metrics.DRIVER_SLO_BURN_RATE,
                _metrics.DRIVER_SLO_ERROR_BUDGET_REMAINING,
                _metrics.DRIVER_SLO_ALERTS_FIRING):
        assert fam in rendered, f"slo/hub family unrendered: {fam}"
        assert fam in doc_names, f"slo/hub family undocumented: {fam}"


def test_finish_reason_vocabulary_pinned():
    """Lint over the finish_reason vocabulary, both directions: the
    constants in models/serving.py are the single source of truth, the
    code actually produces every value, docs/serving.md documents every
    value, the trace terminal set stays consistent with it, and the
    HTTP error mapping (shed -> 429, failed -> 503, router fleet-
    saturation -> 429) is still wired. A new terminal added to code
    without the enum/docs — or documented without being produced —
    fails here."""
    import inspect

    import tony_tpu.cli.serve as serve_mod
    import tony_tpu.models.serving as serving_mod
    import tony_tpu.router as router_mod
    from tony_tpu.models.serving import (
        COMPLETION_FINISH_REASONS, FINISH_REASONS,
    )

    # the pinned sets themselves (a rename/removal is a doc+router
    # migration, not a drive-by)
    assert COMPLETION_FINISH_REASONS == ("stop", "length", "cancelled",
                                         "expired", "shed", "prefilled")
    assert FINISH_REASONS == COMPLETION_FINISH_REASONS + ("failed",)
    # trace terminals <-> finish reasons: "finished" carries the
    # stop/length/prefilled reason in attrs; every other terminal IS
    # its reason
    from tony_tpu.observability import TERMINAL_SPANS

    assert set(TERMINAL_SPANS) - {"finished"} == \
        set(FINISH_REASONS) - set(("stop", "length", "prefilled"))
    assert "replayed" not in TERMINAL_SPANS, (
        "replay is a mid-life mark, never a terminal")

    serving_src = inspect.getsource(serving_mod)
    serve_src = inspect.getsource(serve_mod)
    router_src = inspect.getsource(router_mod)
    from pathlib import Path

    doc = (Path(__file__).resolve().parent.parent
           / "docs" / "serving.md").read_text()
    for reason in FINISH_REASONS:
        assert f'"{reason}"' in serving_src, (
            f"finish reason {reason!r} is in the enum but the engine "
            "source never names it")
        assert f'"{reason}"' in doc or f"`{reason}`" in doc, (
            f"finish reason {reason!r} undocumented in docs/serving.md")
    # the engine source names no finish_reason outside the enum: every
    # Completion(...) literal reason and _finish_trace terminal must be
    # in FINISH_REASONS (+ the trace-only "finished" wrapper)
    produced = set(re.findall(
        r'Completion\(\s*[\w.\[\]]+,\s*[\w.\[\]() ]+,\s*"(\w+)"',
        serving_src))
    produced |= set(re.findall(r'_finish_trace\([^)]*"(\w+)"', serving_src))
    produced |= set(re.findall(r'_seal_trace\([^)]*"(\w+)"', serving_src))
    unknown = produced - set(FINISH_REASONS) - {"finished"}
    assert not unknown, f"finish reasons outside the enum: {unknown}"
    assert {"cancelled", "expired", "failed", "shed"} <= produced, (
        f"enum reasons the engine no longer produces: {produced}")
    # HTTP mapping, both layers: shed -> 429 (serve QueueFullError, the
    # router's fleet saturation), failed/down -> 503
    assert "QueueFullError" in serve_src and "429" in serve_src
    assert "ServingLoopError" in serve_src and "503" in serve_src
    assert "FleetSaturatedError" in router_src and "429" in router_src


def test_telemetry_trace_feed_units():
    """observe_trace maps spans to the right histograms, including the
    per-token TPOT division, without a model in sight."""
    tel = ServingTelemetry()
    tr = RequestTrace(7)
    tr.mark("submitted", t=10.0)
    tr.mark("admitted", t=10.5)
    tr.mark("prefill_done", t=10.6)
    tr.mark("first_token", t=11.0)
    tr.attrs["n_tokens"] = 5
    tr.mark("finished", t=11.8)
    tel.observe_trace(tr)
    assert tel.hist["queue_wait_s"].sum == pytest.approx(0.5)
    assert tel.hist["prefill_s"].sum == pytest.approx(0.1)
    assert tel.hist["ttft_s"].sum == pytest.approx(1.0)
    assert tel.hist["e2e_s"].sum == pytest.approx(1.8)
    assert tel.hist["tpot_s"].sum == pytest.approx(0.8 / 4)  # (n-1) steps
    # a shed trace only feeds e2e
    tel2 = ServingTelemetry()
    shed = RequestTrace(8)
    shed.mark("submitted", t=1.0)
    shed.mark("shed", t=1.25)
    tel2.observe_trace(shed)
    assert tel2.hist["e2e_s"].count == 1
    assert tel2.hist["ttft_s"].count == 0
