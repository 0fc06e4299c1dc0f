"""The r18 telemetry layer (onix/utils/telemetry.py): spans + trace-id
propagation, log-bucketed histogram error bounds, Prometheus exposition
(rendered AND strictly parsed), the flight recorder's chaos triggers,
and THE hard constraint — telemetry off leaves winners bit-identical
with per-program dispatch counts unchanged."""

import http.client
import json
import math
import time

import numpy as np
import pytest

from onix.config import OnixConfig, TelemetryConfig
from onix.serving.model_bank import BankService, ModelBank, ScoreRequest
from onix.utils import faults, telemetry
from onix.utils.obs import counters

TOL = 1.0
M = 50


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    faults.reset()
    counters.reset()
    telemetry.reset_for_tests()
    yield
    faults.reset()
    counters.reset()
    telemetry.reset_for_tests()


def _model(rng, d, v, k=8):
    th = rng.dirichlet(np.full(k, 0.5), size=d).astype(np.float32)
    ph = rng.dirichlet(np.full(k, 0.5), size=v).astype(np.float32)
    return th, ph


def _service(n_tenants=2, d=96, v=64, **kw):
    rng = np.random.default_rng(7)
    bank = ModelBank(capacity=8)
    models = {}
    for t in range(n_tenants):
        th, ph = _model(rng, d, v)
        bank.add(f"t{t}", th, ph)
        models[f"t{t}"] = (th, ph)
    return BankService(bank, **kw), models


def _requests(n=4, d=96, v=64, events=128, seed=3):
    rng = np.random.default_rng(seed)
    return [ScoreRequest(tenant=f"t{i % 2}",
                         doc_ids=rng.integers(0, d, events).astype(np.int32),
                         word_ids=rng.integers(0, v, events).astype(np.int32),
                         window=f"w{i}")
            for i in range(n)]


# -- histograms -------------------------------------------------------------

def _nearest_rank(vals, q):
    sv = np.sort(np.asarray(vals))
    return float(sv[max(1, math.ceil(q * len(sv))) - 1])


def test_histogram_quantile_error_bounds_deterministic():
    vals = np.random.default_rng(0).lognormal(0.0, 2.0, 5000)
    h = telemetry.Histogram()
    for v in vals:
        h.observe(float(v))
    assert h.n == 5000
    for q in (0.5, 0.9, 0.99, 0.999):
        lo, hi = h.quantile_bounds(q)
        ref = _nearest_rank(vals, q)
        assert lo <= ref <= hi, (q, lo, ref, hi)
        # The midpoint answer is within the declared relative error of
        # SOME value in its bucket, hence of the true quantile.
        mid = h.quantile(q)
        assert lo / (1 + h.rel_error) <= mid <= hi * (1 + h.rel_error)


def test_histogram_edge_cases():
    h = telemetry.Histogram()
    assert h.quantile(0.99) == 0.0          # empty
    h.observe(0.0)                          # underflow bucket
    h.observe(-1.0)
    assert h.quantile(0.5) == 0.0
    h2 = telemetry.Histogram()
    h2.observe(5.0)
    lo, hi = h2.quantile_bounds(0.99)
    assert lo < 5.0 <= hi
    # Single-value histograms clamp the midpoint into [min, max].
    assert h2.quantile(0.99) == 5.0
    snap = h2.snapshot()
    assert snap["n"] == 1 and snap["min"] == 5.0 and snap["buckets"]


def test_histogram_quantile_bounds_hypothesis():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=60, deadline=None)
    @given(st.lists(st.floats(min_value=1e-9, max_value=1e9,
                              allow_nan=False, allow_infinity=False),
                    min_size=1, max_size=200),
           st.floats(min_value=0.01, max_value=0.999))
    def check(vals, q):
        h = telemetry.Histogram()
        for v in vals:
            h.observe(v)
        lo, hi = h.quantile_bounds(q)
        ref = _nearest_rank(vals, q)
        assert lo <= ref * (1 + 1e-9) and ref <= hi * (1 + 1e-9)

    check()


def test_replay_quantiles_parity_with_numpy():
    """The satellite fix: load_harness.replay quantiles now come from
    the histogram — parity-checked here against numpy nearest-rank
    percentile on the SAME raw latencies (the old path's data), within
    the histogram's declared bucket bounds."""
    from onix.serving.load_harness import (HarnessSpec, build_service,
                                           make_stream, make_tenants, replay)
    spec = HarnessSpec(n_tenants=3, n_docs=64, n_vocab=48, n_topics=5,
                       n_requests=24, events_per_request=64, n_windows=0,
                       batch_requests=4, max_results=10)
    svc = build_service(spec, make_tenants(spec))
    out = replay(svc, make_stream(spec), tol=spec.tol,
                 max_results=spec.max_results, keep_raw=True)
    raw = out["raw_latencies"]["served"]
    assert len(raw) == out["slo"]["served"]["n"] > 0
    h = telemetry.Histogram()
    for q, key in ((0.5, "p50_ms"), (0.99, "p99_ms")):
        ref_ms = _nearest_rank(raw, q) * 1e3
        reported = out["slo"]["served"][key]
        # Reported midpoint and the numpy nearest-rank value share a
        # bucket: within one growth factor of each other.
        assert reported / h.growth <= ref_ms <= reported * h.growth, \
            (key, reported, ref_ms)
    assert out["slo"]["served"]["q_rel_error"] == round(h.rel_error, 4)


# -- prometheus exposition --------------------------------------------------

def test_render_parse_roundtrip():
    telemetry.histograms.observe("span.serve.submit", 0.004)
    telemetry.histograms.observe("span.serve.submit", 0.1)
    counters.inc("serve.served", 3)
    text = telemetry.render_prometheus(
        counters.snapshot(), telemetry.histograms,
        gauges={"serve.queue_depth": 2},
        info={"config_hash": 'ab"c\\d'})
    fams = telemetry.parse_prometheus_text(text)
    assert fams["onix_serve_served"]["samples"][0][2] == 3
    hist = fams["onix_span_serve_submit_seconds"]
    assert hist["type"] == "histogram"
    count = [v for n, _, v in hist["samples"]
             if n == "onix_span_serve_submit_seconds_count"]
    assert count == [2]
    info = fams["onix_build_info"]["samples"][0]
    assert info[1]["config_hash"] == 'ab"c\\d'


@pytest.mark.parametrize("bad", [
    "not a metric line\n",
    "onix_x 1\n",                                   # sample before TYPE
    "# TYPE onix_x counter\nonix_x notanumber\n",
    "# TYPE onix_x wat\n",
    # histogram with non-cumulative buckets
    "# TYPE h histogram\n"
    'h_bucket{le="1"} 5\nh_bucket{le="+Inf"} 3\nh_sum 1\nh_count 3\n',
    # histogram _count disagreeing with +Inf
    "# TYPE h histogram\n"
    'h_bucket{le="+Inf"} 3\nh_sum 1\nh_count 4\n',
])
def test_parser_rejects_malformed(bad):
    with pytest.raises(ValueError):
        telemetry.parse_prometheus_text(bad)


# -- spans + trace propagation ---------------------------------------------

def test_span_tree_nesting_and_trace_ids():
    with telemetry.TRACER.trace("trace-x"):
        with telemetry.TRACER.span("serve.submit"):
            with telemetry.TRACER.span("serve.score"):
                pass
        telemetry.TRACER.observe("serve.queue_wait", 0.002)
    spans = {s.name: s for s in telemetry.TRACER.spans("trace-x")}
    assert set(spans) == {"serve.submit", "serve.score",
                          "serve.queue_wait"}
    assert spans["serve.score"].parent_id == spans["serve.submit"].span_id
    assert spans["serve.submit"].parent_id is None
    assert telemetry.histograms.get("span.serve.queue_wait").n == 1


def test_submit_emits_spans_and_wall_histogram():
    svc, _ = _service()
    svc.submit(_requests(), tol=TOL, max_results=M)
    names = [s.name for s in telemetry.TRACER.spans()]
    for want in ("serve.submit", "serve.queue_wait", "serve.score",
                 "bank.admit", "bank.score_wave"):
        assert want in names, names
    assert telemetry.histograms.get("span.serve.submit").n == 1
    # The service-local Retry-After histogram saw the same wall.
    assert svc._wall_hist.n == 1


def test_sampling_zero_records_nothing_but_clock_still_feeds():
    from onix.utils.obs import OccupancyClock
    telemetry.configure(sample=0.0)
    clock = OccupancyClock()
    with telemetry.TRACER.span("campaign.prepare", clock=clock,
                               clock_name="flow.prepare"):
        pass
    assert counters.get("telemetry.spans_recorded") == 0
    # The occupancy clock was fed regardless — accounting never
    # depends on telemetry being on.
    assert "flow.prepare" in clock.busy_s


def test_score_endpoint_propagates_x_request_id(tmp_path):
    """Acceptance: /score request -> span tree -> /metrics histogram.
    The client's X-Request-Id is the trace id on every span from the
    HTTP handler down to the bank wave dispatch, is echoed back, and
    the submit-latency histogram lands on /metrics as parseable
    Prometheus text with serve/bank counters alongside."""
    from onix.checkpoint import save_model
    from onix.oa.serve import serve_background

    cfg = OnixConfig()
    cfg.store.root = str(tmp_path / "store")
    cfg.validate()
    rng = np.random.default_rng(9)
    th, ph = _model(rng, 120, 90)
    save_model(cfg.serving.models_dir, "flow/20160708", th, ph)
    server, port = serve_background(cfg)
    try:
        d = rng.integers(0, 120, 200).astype(np.int32)
        w = rng.integers(0, 90, 200).astype(np.int32)
        body = {"requests": [{"tenant": "flow/20160708", "window": "d0",
                              "doc_ids": d.tolist(),
                              "word_ids": w.tolist()}],
                "tol": TOL, "max_results": M}
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        conn.request("POST", "/score", body=json.dumps(body),
                     headers={"Content-Type": "application/json",
                              "X-Request-Id": "req-abc-123"})
        r = conn.getresponse()
        out = json.loads(r.read())
        assert r.status == 200 and out["ok"]
        assert out["trace_id"] == "req-abc-123"
        assert r.headers["X-Request-Id"] == "req-abc-123"
        # `serve.request` closes when the handler returns, which is
        # after the client has its response: give the thread a moment.
        for _ in range(200):
            spans = {s.name for s in telemetry.TRACER.spans("req-abc-123")}
            if "serve.request" in spans:
                break
            time.sleep(0.01)
        # End-to-end: HTTP handler -> admission -> scoring -> wave.
        assert {"serve.request", "serve.submit", "serve.queue_wait",
                "serve.score", "bank.score_wave"} <= spans
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        conn.request("GET", "/metrics")
        r = conn.getresponse()
        text = r.read().decode()
        assert r.status == 200
        fams = telemetry.parse_prometheus_text(text)
        hist = fams["onix_span_serve_submit_seconds"]
        count = [v for n, _, v in hist["samples"]
                 if n.endswith("_count")]
        assert count == [1.0]
        assert fams["onix_bank_dispatch"]["samples"][0][2] >= 1
        assert fams["onix_serve_served"]["samples"][0][2] >= 1
        assert fams["onix_bank_tenants_registered"]["samples"][0][2] == 1
        assert fams["onix_build_info"]["samples"][0][1]["config_hash"] \
            == cfg.config_hash
    finally:
        server.server_close()


def test_metrics_on_dashboards_only_server(tmp_path):
    """/metrics must not instantiate jax or the bank — a fresh server
    with no /score traffic still exposes counters + build identity."""
    from onix.oa.serve import serve_background
    cfg = OnixConfig()
    cfg.store.root = str(tmp_path / "store")
    cfg.validate()
    server, port = serve_background(cfg)
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        conn.request("GET", "/metrics")
        r = conn.getresponse()
        assert r.status == 200
        fams = telemetry.parse_prometheus_text(r.read().decode())
        assert "onix_build_info" in fams
        assert server.peek_bank_service() is None   # never constructed
    finally:
        server.server_close()


def test_metrics_histogram_quantiles_match_replayed_harness(tmp_path):
    """The acceptance cell: a replayed load-harness run feeds the
    process histograms through the REAL submit path, and /metrics
    exposes a latency histogram whose p50/p99 (recovered from the
    scraped cumulative buckets) bracket numpy's nearest-rank
    percentiles of the replay's raw walls — within one log bucket of
    slack for the sliver of submit-exit overhead the outer replay
    clock sees and the span does not."""
    from onix.oa.serve import serve_background
    from onix.serving.load_harness import (HarnessSpec, build_service,
                                           make_stream, make_tenants,
                                           replay)
    spec = HarnessSpec(n_tenants=4, n_docs=64, n_vocab=48, n_topics=5,
                       n_requests=120, events_per_request=64, n_windows=0,
                       batch_requests=4, max_results=10)
    svc = build_service(spec, make_tenants(spec))
    out = replay(svc, make_stream(spec), tol=spec.tol,
                 max_results=spec.max_results, keep_raw=True)
    raw = out["raw_latencies"]["served"]
    assert len(raw) == 30
    cfg = OnixConfig()
    cfg.store.root = str(tmp_path / "store")
    cfg.validate()
    # apply_config must not disturb the already-recorded histograms.
    server, port = serve_background(cfg)
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        conn.request("GET", "/metrics")
        r = conn.getresponse()
        fams = telemetry.parse_prometheus_text(r.read().decode())
    finally:
        server.server_close()
    hist = fams["onix_span_serve_submit_seconds"]
    buckets = [(float(lab["le"].replace("Inf", "inf")), v)
               for n, lab, v in hist["samples"] if n.endswith("_bucket")]
    count = buckets[-1][1]
    assert count == len(raw)

    def scraped_bounds(q):
        rank = max(1, math.ceil(q * count))
        prev_edge = 0.0
        for edge, cum in buckets:
            if cum >= rank:
                return prev_edge, edge
            prev_edge = edge
        return prev_edge, buckets[-1][0]

    g = telemetry.Histogram().growth
    for q in (0.5, 0.99):
        lo, hi = scraped_bounds(q)
        ref = _nearest_rank(raw, q)
        assert lo / g <= ref <= hi * g, (q, lo, ref, hi)


# -- flight recorder --------------------------------------------------------

def test_flight_recorder_dump_on_fault_plan(tmp_path):
    """A chaos run under an active ONIX_FAULT_PLAN produces a
    flight-recorder artifact containing the injected fault event (the
    acceptance trigger), plus the counter deltas and span closes that
    led up to it."""
    telemetry.configure(recorder_dir=tmp_path / "flight")
    faults.install_plan("serve:score@1=raise")
    svc, _ = _service()
    reqs = _requests()
    out = svc.submit(reqs, tol=TOL, max_results=M)   # absorbed by retry
    assert len(out) == len(reqs)
    assert counters.get("faults.serve.score") == 1
    dumps = sorted((tmp_path / "flight").glob("flight-*.json"))
    assert len(dumps) == 1
    doc = json.loads(dumps[0].read_text())
    assert doc["reason"] == "fault-serve-score"
    kinds = {}
    for ev in doc["events"]:
        kinds.setdefault(ev["kind"], []).append(ev)
    assert any(ev["site"] == "serve:score" and ev["action"] == "raise"
               for ev in kinds["fault"])
    assert any(ev["name"] == "faults.serve.score"
               for ev in kinds["counter"])
    assert doc["counters"]["faults.serve.score"] == 1


def test_recorder_unwritable_dir_degrades_to_counted_skip(tmp_path):
    """Review fix (r18): a dump into an unwritable dir must degrade to
    a counted failure, never leak OSError into the triggering path (a
    shed would 500 instead of 503, an injected fault would escape its
    bounded retry as the wrong class)."""
    blocked = tmp_path / "file-not-dir"
    blocked.write_text("")      # mkdir under a FILE raises OSError
    telemetry.configure(recorder_dir=blocked / "sub")
    assert telemetry.RECORDER.dump("anything") is None
    assert counters.get("telemetry.recorder_dump_failed") == 1


def test_recorder_unrouted_dump_is_counted_not_written(tmp_path, monkeypatch):
    monkeypatch.delenv("ONIX_TELEMETRY_DIR", raising=False)
    assert telemetry.RECORDER.dump("nowhere") is None
    assert counters.get("telemetry.recorder_dump_unrouted") == 1


def test_shed_triggers_recorder_dump(tmp_path):
    telemetry.configure(recorder_dir=tmp_path / "flight")
    svc, _ = _service(max_queue_depth=1)
    # Fill the depth-1 queue artificially, then submit -> shed + dump.
    svc._pending = 1
    from onix.utils.resilience import Overloaded
    with pytest.raises(Overloaded):
        svc.submit(_requests(1), tol=TOL, max_results=M)
    assert counters.get("serve.shed") == 1
    dumps = list((tmp_path / "flight").glob("flight-*-serve-shed.json"))
    assert len(dumps) == 1


# -- the hard constraint ----------------------------------------------------

def test_disabled_bit_identity_and_dispatch_counts():
    """telemetry.enabled=false / sample=0 ⇒ winners BIT-identical and
    per-program dispatch counts unchanged — asserted, not assumed (the
    tentpole's hard constraint, also run by scripts/lint.sh)."""
    reqs = _requests()

    def run(**tcfg):
        telemetry.reset_for_tests()
        telemetry.configure(**tcfg)
        counters.reset()
        svc, _ = _service()
        res = svc.submit(reqs, tol=TOL, max_results=M)
        return ([(np.asarray(r.topk.scores), np.asarray(r.topk.indices))
                 for r in res],
                svc.bank.dispatches,
                counters.get("bank.dispatch"),
                counters.get("telemetry.spans_recorded"))

    on_res, on_disp, on_cdisp, on_spans = run(enabled=True, sample=1.0)
    for tcfg in ({"enabled": False}, {"enabled": True, "sample": 0.0}):
        off_res, off_disp, off_cdisp, off_spans = run(**tcfg)
        assert off_spans == 0, tcfg
        assert off_disp == on_disp and off_cdisp == on_cdisp, tcfg
        for (s_on, i_on), (s_off, i_off) in zip(on_res, off_res):
            np.testing.assert_array_equal(s_on, s_off)
            np.testing.assert_array_equal(i_on, i_off)
    assert on_spans > 0     # the enabled arm really recorded


# -- config + snapshot ------------------------------------------------------

def test_telemetry_config_validation():
    cfg = OnixConfig()
    cfg.validate()
    assert cfg.telemetry.recorder_dir.endswith("telemetry")
    with pytest.raises(ValueError):
        TelemetryConfig(sample=1.5).validate()
    with pytest.raises(ValueError):
        TelemetryConfig(recorder_events=4).validate()
    from onix.config import from_dict
    c2 = from_dict({"telemetry": {"enabled": False, "sample": 0.25}})
    assert c2.telemetry.enabled is False
    assert c2.telemetry.sample == 0.25


def test_snapshot_shape_and_zeros_included():
    snap = telemetry.snapshot()
    assert snap["enabled"] is True
    assert snap["spans_recorded"] == 0
    assert snap["recorder_dumps"] == 0
    assert snap["histograms"] == {}
    with telemetry.TRACER.span("serve.submit"):
        pass
    full = telemetry.snapshot(full=True)
    assert full["spans_recorded"] == 1
    assert "span.serve.submit" in full["histograms"]
    assert "buckets" in full["histograms"]["span.serve.submit"]
    assert full["counters"]["telemetry.spans_recorded"] == 1


# -- a span's place in time, and the span store ----------------------------

def test_spans_keep_their_place_in_time():
    """`t0` is the open time on `time.monotonic()`: inside the test's
    own bracket, children inside their parents, an observed wall
    opened `dur_s` before it was reported."""
    before = time.monotonic()
    with telemetry.TRACER.span("serve.submit"):
        with telemetry.TRACER.span("serve.score"):
            time.sleep(0.002)
        reported = time.monotonic()
        telemetry.TRACER.observe("serve.queue_wait", 0.001)
    after = time.monotonic()
    spans = {s.name: s for s in telemetry.TRACER.spans()}
    for s in spans.values():
        assert before <= s.t0 <= s.t0 + s.dur_s <= after + 1e-6, s
    outer, inner, wait = (spans[n] for n in (
        "serve.submit", "serve.score", "serve.queue_wait"))
    for child in (inner, wait):
        assert child.parent_id == outer.span_id
        assert outer.t0 <= child.t0
        assert child.t0 + child.dur_s <= outer.t0 + outer.dur_s + 1e-6
    assert inner.dur_s >= 0.002
    assert wait.t0 == pytest.approx(reported - 0.001, abs=5e-4)
    # The ring's span event carries the open time too (the dumps).
    events = [e for e in telemetry.RECORDER.events() if e["kind"] == "span"]
    assert [e["t0"] for e in events] == [
        round(s.t0, 6) for s in telemetry.TRACER.spans()]


def test_span_store_outlives_counter_deltas_and_says_what_it_dropped(
        monkeypatch):
    with telemetry.TRACER.span("fit.prepare", tokens=7):
        pass
    for _ in range(2000):               # the ring holds 1024 events
        counters.inc("stream.batches")
    assert not [e for e in telemetry.RECORDER.events()
                if e["kind"] == "span"]
    (kept,) = telemetry.TRACER.spans()
    assert kept.name == "fit.prepare" and kept.attrs == {"tokens": 7}
    assert telemetry.RECORDER._spans.maxlen == telemetry.SPAN_STORE
    # Past its own bound the store drops its oldest, and the counter
    # beside it says so (what the benchmark's span readers check).
    import collections
    monkeypatch.setattr(telemetry.RECORDER, "_spans",
                        collections.deque(maxlen=3))
    for i in range(5):
        telemetry.TRACER.observe("fit.notify", 0.1, sweep=i)
    assert [s.attrs["sweep"] for s in telemetry.TRACER.spans()] == [2, 3, 4]
    assert counters.get("telemetry.spans_recorded") == 6
    # The ring's size is the ring's alone.
    telemetry.RECORDER.reconfigure(capacity=16)
    assert telemetry.RECORDER._spans.maxlen == 3
    telemetry.RECORDER.reconfigure(capacity=1024)


# -- every compile a span ---------------------------------------------------

@pytest.fixture
def own_compile_cache(tmp_path):
    """JAX's persistent compile cache in a directory of the test's own,
    every program cached; put back as it was afterwards."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    kept = {k: getattr(jax.config, k) for k in keys}
    for k, v in zip(keys, (str(tmp_path / "jax_cache"), 0.0, 0)):
        jax.config.update(k, v)
    cc.reset_cache()
    yield
    for k, v in kept.items():
        jax.config.update(k, v)
    cc.reset_cache()


def _fresh_program():
    """A jitted function no cache in memory knows: a new function
    object every call, the same program (and source lines) every
    time."""
    import jax
    import jax.numpy as jnp
    telemetry.watch_compiles()          # whatever ran before this test

    @jax.jit
    def probe_prog(x):
        return jnp.sort(x * 3.0)[::2].sum()
    return probe_prog, jnp.arange(24.0)


def _compiles(program="probe_prog"):
    return [s for s in telemetry.TRACER.spans()
            if s.name == "jit.compile" and s.attrs["program"] == program]


def test_compile_is_a_child_span_with_its_program_and_the_caches_verdict(
        own_compile_cache):
    """First to an empty directory, then - the same program from the
    same lines, to a process that has forgotten it - answered by the
    directory. (One loop, because the source lines of the callers are
    part of the cache's key once `obs.device_scope` has run.)"""
    import jax
    for want in ("miss", "hit"):
        jax.clear_caches()
        fn, x = _fresh_program()
        with telemetry.TRACER.span("fit.init_state") as asked:
            fn(x).block_until_ready()
        span = _compiles()[-1]
        assert span.parent_id == asked.span_id
        assert span.trace_id == asked.trace_id
        a = span.attrs
        assert a["cache"] == want and a["backend_s"] > 0
        assert a["trace_s"] > 0 and a["lower_s"] > 0
        assert span.dur_s == pytest.approx(
            a["trace_s"] + a["lower_s"] + a["backend_s"])
        assert asked.t0 <= span.t0 + span.dur_s <= asked.t0 + asked.dur_s
        fn(x).block_until_ready()           # compiled: nothing new
    assert len(_compiles()) == 2
    n = counters.get("jit.compiles")
    assert n == len([s for s in telemetry.TRACER.spans()
                     if s.name == "jit.compile"])
    assert 1 <= counters.get("jit.cache_misses") < n
    assert counters.get("jit.compile_us") > 0
    assert telemetry.histograms.get("span.jit.compile").n == n
    fams = telemetry.parse_prometheus_text(
        telemetry.render_prometheus(counters.snapshot()))
    assert fams["onix_jit_compiles"]["samples"][0][2] == n
    assert "onix_span_jit_compile_seconds" in fams


def test_compile_outside_any_trace_is_kept():
    fn, x = _fresh_program()
    fn(x).block_until_ready()
    (span,) = _compiles()
    assert span.parent_id is None and span.trace_id
    assert span.attrs["cache"] in ("off", "hit", "miss")


@pytest.mark.parametrize("how", ["env", "enabled", "sample"])
def test_compiles_are_not_recorded_with_telemetry_off(how, monkeypatch):
    fn, x = _fresh_program()
    fn(x).block_until_ready()           # the listeners are on
    assert _compiles()
    if how == "env":
        monkeypatch.setenv("ONIX_TELEMETRY", "0")
        telemetry.configure(enabled=True)
        assert not telemetry.TRACER.enabled
    elif how == "enabled":
        telemetry.configure(enabled=False)
    else:
        telemetry.configure(sample=0.0)
    spans = counters.get("telemetry.spans_recorded")
    jit = counters.snapshot("jit.")
    ring = len(telemetry.RECORDER.events())
    fn, x = _fresh_program()
    fn(x).block_until_ready()
    assert counters.get("telemetry.spans_recorded") == spans
    assert len(_compiles()) == 1
    if how != "sample":                 # sampled out, still counted
        assert counters.snapshot("jit.") == jit
        assert len(telemetry.RECORDER.events()) == ring


def test_program_name_is_spelled_one_way():
    assert telemetry.program_name("jit(superstep)") == "superstep"
    assert telemetry.program_name("jit(<lambda>)") == "<lambda>"
    assert telemetry.program_name("pmap(step)") == "pmap(step)"
    assert telemetry.program_name("") == "unknown"
