"""The readers ISSUE 37 adds: `span_window` on a synthetic run (spans
before, inside and after a window), `idle_under_span` on hand-made
planes, and the metric files that name them."""

import json
import pathlib
import time

import pytest

from benchmark import harness
from onix.utils import telemetry
from onix.utils.obs import counters

ROOT = pathlib.Path(__file__).resolve().parents[2]
MANIFEST = harness.Manifest(ROOT / "BENCHMARK.json")


@pytest.fixture(autouse=True)
def fresh():
    telemetry.reset_for_tests()
    yield
    telemetry.reset_for_tests()


def _reader(name):
    return MANIFEST.load("readers", name)


def _spec(metric):
    return json.loads(MANIFEST.find("metrics", metric).read_text())


def _span(name, t0, dur_s, **attrs):
    """A closed span at a stated place in time."""
    telemetry.TRACER._close(telemetry.SpanRecord(
        name=name, trace_id="t", span_id=next(telemetry._span_seq),
        parent_id=None, t0=t0, dur_s=dur_s, attrs=attrs))


def _run_with_compiles():
    """Set-up from 100 to 150, a window of 30 s behind it: three
    compiles in set-up (one from the cache), one that straddles the
    window's opening, one inside the window, one after it."""
    for t0, dur, backend, cache in (
            (101.0, 4.0, 3.0, "miss"), (110.0, 0.5, 0.25, "hit"),
            (120.0, 20.0, 18.0, "miss"),
            (149.0, 2.0, 1.0, "miss"),          # closes inside the window
            (160.0, 1.0, 0.5, "miss"), (181.0, 1.0, 0.5, "miss")):
        _span("jit.compile", t0, dur, backend_s=backend, cache=cache,
              program="p")
    _span("scan.stage", 105.0, 2.0)
    return {"t_start": 100.0, "end_to_end": {"setup_s": 50.0},
            "window": {"elapsed_s": 30.0}}


@pytest.mark.parametrize("spec, want", [
    ({}, 24.5),
    ({"attr": "backend_s"}, 21.25),
    ({"attr": "backend_s", "where": {"cache": "miss"}}, 21.0),
    ({"where": {"cache": "off"}}, 0.0),
    ({"span": "scan.stage"}, 2.0),
    ({"span": "fit.prepare"}, None),
])
def test_span_window_keeps_the_spans_that_closed_in_set_up(spec, want):
    run = _run_with_compiles()
    got = _reader("span_window").read(run, {"span": "jit.compile", **spec})
    assert got == (pytest.approx(want) if want is not None else None)


def test_span_window_reads_the_compile_metrics_files():
    run = _run_with_compiles()
    read = _reader("span_window").read
    assert read(run, _spec("compile_s")) == pytest.approx(24.5)
    assert read(run, _spec("compile_cold_s")) == pytest.approx(21.0)
    # A set-up that compiled nothing cold reads 0, not nothing.
    telemetry.reset_for_tests()
    _span("jit.compile", 101.0, 0.5, backend_s=0.25, cache="hit")
    assert read(run, _spec("compile_cold_s")) == 0.0
    assert read(run, _spec("compile_s")) == pytest.approx(0.5)


def test_span_window_gives_nothing_where_it_cannot_tell():
    read = _reader("span_window").read
    run = _run_with_compiles()
    spec = _spec("compile_s")
    assert read(dict(run, end_to_end={}), spec) is None    # no window
    counters.inc("telemetry.spans_recorded")        # one span was dropped
    assert read(run, spec) is None
    telemetry.reset_for_tests()
    _span("jit.compile", 0.0, 4.0)          # a span from before t0 was kept
    assert read(run, spec) is None


def test_fit_init_compile_reads_the_compiles_under_init_state():
    """`program_span` as it is, with the new span: the compile's part
    of `fit.init_state`, and not the compiles under other spans."""
    with telemetry.TRACER.span("fit.init_state"):
        telemetry.TRACER.observe("jit.compile", 4.0, program="init_fn")
        telemetry.TRACER.observe("jit.compile", 0.5, program="iota")
    with telemetry.TRACER.span("fit.superstep"):
        telemetry.TRACER.observe("jit.compile", 30.0, program="superstep")
    telemetry.TRACER.observe("jit.compile", 9.0, program="alone")
    got = _reader("program_span").read({}, _spec("fit_init_compile_s"))
    assert got == pytest.approx(4.5)


def test_real_spans_are_on_the_harness_clock():
    """A span the tracer itself opened falls on the right side of a
    window stamped with `time.monotonic()`, as the drivers stamp it."""
    t_start = time.monotonic()
    with telemetry.TRACER.span("fit.prepare"):
        time.sleep(0.002)
    t_open = time.monotonic()
    with telemetry.TRACER.span("fit.prepare"):
        time.sleep(0.004)
    run = {"t_start": t_start, "end_to_end": {"setup_s": t_open - t_start},
           "window": {"elapsed_s": time.monotonic() - t_open}}
    before = _reader("span_window").read(run, {"span": "fit.prepare"})
    assert 0.002 <= before < 0.004


# -- idle_under_span: hand-made planes, times in picoseconds ---------------

MS = 10 ** 9


def _planes(host_events, *device_ops):
    """Host spans and each device plane's ops as (start, length) in
    ms; the window's marks at 0 and 100 ms."""
    events = [(n, s * MS, d * MS, "") for n, s, d in host_events]
    events += [("bench.trace_open", 0, 1, ""),
               ("bench.trace_close", 100 * MS - 1, 1, "")]
    planes = [{"name": "/host:CPU",
               "lines": [{"name": "main", "events": events}]}]
    for i, ops in enumerate(device_ops):
        planes.append({"name": f"/device:TPU:{i}", "lines": [
            {"name": "XLA Ops", "events": [
                ("op", s * MS, d * MS, "") for s, d in ops]}]})
    return planes


def test_idle_gaps_go_to_the_shortest_span_that_covers_half():
    """Busy 0-20, 30-50, 52-90 of a 100 ms window: the gap 20-30 lies
    under two nested spans and goes to the shorter; 50-52 under a
    dispatch that covers it whole; 90-100 under no span at all. A span
    that covers less than half a gap does not get it."""
    ius = _reader("idle_under_span")
    planes = _planes(
        [("onix.stream.superstep", 5, 60), ("onix.stream.fetch", 18, 10),
         ("onix.scan.dispatch", 49, 4), ("onix.scan.dispatch", 70, 1),
         ("onix.fit.wait", 89, 3), ("other.span", 90, 10)],
        [(0, 20), (30, 20), (52, 38)])
    booked = ius.book(planes)
    assert booked["idle"] == 22 * MS
    assert booked["by_span"] == {"stream.fetch": 10 * MS,
                                 "scan.dispatch": 2 * MS,
                                 "unspanned": 10 * MS}
    assert booked["whole"]["scan.dispatch"] == [2, 2 * MS]
    run = {"scope_planes": planes}
    # 2 ms of idle under two dispatches: 1 ms a chunk.
    assert ius.read(run, _spec("idle_dispatch_s_per_chunk")) == \
        pytest.approx(1e-3)
    for cell in ("fit", "scan"):
        assert ius.read(run, _spec("idle_unspanned_pct." + cell)) == \
            pytest.approx(100 * 10 / 22, rel=1e-6)
    assert ius.read(run, {"span": "fit.notify"}) is None


def test_idle_gaps_are_averaged_over_the_device_planes():
    ius = _reader("idle_under_span")
    planes = _planes([("onix.fit.wait", 40, 40)],
                     [(0, 50), (70, 30)],       # idle 50-70, under the wait
                     [(0, 100)])                # never idle
    booked = ius.book(planes)
    assert booked["by_span"] == {"fit.wait": pytest.approx(10 * MS)}
    assert ius.read({"scope_planes": planes},
                    {"as": "unspanned_pct"}) == 0.0


def test_a_dispatch_that_straddles_a_mark_enters_neither_side():
    """Idle 0-4 under a dispatch that opened before `trace_open`, idle
    50-52 under one whole inside the window: 2 ms a chunk, not 6."""
    ius = _reader("idle_under_span")
    planes = _planes(
        [("onix.scan.dispatch", -3, 7), ("onix.scan.dispatch", 49, 4)],
        [(4, 46), (52, 48)])
    booked = ius.book(planes)
    assert booked["by_span"] == {"scan.dispatch": 6 * MS}
    assert booked["whole"] == {"scan.dispatch": [1, 2 * MS]}
    assert ius.read({"scope_planes": planes},
                    _spec("idle_dispatch_s_per_chunk")) == pytest.approx(2e-3)


def test_the_booking_starts_where_the_first_span_of_the_trace_opens():
    """`flow-fit`: the trace starts inside a `fit.notify` that is in no
    trace, the device idles 12 ms under it, the first span the trace
    holds opens at 12 ms. Those 12 ms are not booked; the 6 ms under
    the wait are."""
    ius = _reader("idle_under_span")
    planes = _planes(
        [("onix.fit.superstep", 12, 50), ("onix.fit.wait", 60, 10)],
        [(12, 50), (68, 32)])
    booked = ius.book(planes)
    assert booked["idle"] == 6 * MS
    assert booked["by_span"] == {"fit.wait": 6 * MS}
    assert ius.read({"scope_planes": planes},
                    _spec("idle_unspanned_pct.fit")) == 0.0


def test_a_share_of_a_few_milliseconds_of_idle_is_not_given():
    """The stream: 4 ms of idle in all, a quarter of it under no span.
    The seconds under a span are still read."""
    ius = _reader("idle_under_span")
    planes = _planes(
        [("onix.stream.superstep", 0, 60), ("onix.stream.fetch", 10, 5)],
        [(0, 10), (13, 83)])            # idle 10-13 and 96-100
    booked = ius.book(planes)
    assert booked["by_span"] == {"stream.fetch": 3 * MS, "unspanned": 4 * MS}
    run = {"scope_planes": planes}
    assert booked["idle"] * 1e-12 >= ius.MIN_IDLE_S
    assert ius.read(run, {"as": "unspanned_pct"}) == \
        pytest.approx(100 * 4 / 7)
    planes = _planes(
        [("onix.stream.superstep", 0, 60), ("onix.stream.fetch", 10, 5)],
        [(0, 10), (13, 86)])            # idle 10-13 and 99-100
    run = {"scope_planes": planes}
    assert ius.read(run, {"as": "unspanned_pct"}) is None
    assert ius.read(run, {"span": "stream.fetch"}) == pytest.approx(3e-3)


def _without_marks(planes):
    events = planes[0]["lines"][0]["events"]
    events[:] = [e for e in events if not e[0].startswith("bench.trace_")]
    return planes


@pytest.mark.parametrize("planes", [
    [],
    _planes([("onix.fit.wait", 1, 2)]),
    _planes([("bench.callback", 1, 2)], [(0, 50)]),
    _without_marks(_planes([("onix.fit.wait", 1, 2)], [(0, 50)])),
], ids=["no trace", "no device plane", "no program span", "no marks"])
def test_idle_under_span_gives_nothing_where_there_is_nothing(planes):
    ius = _reader("idle_under_span")
    run = {"scope_planes": planes}
    assert ius.read(run, {"as": "unspanned_pct"}) is None
    assert ius.read(run, {"span": "scan.dispatch"}) is None


def test_a_device_that_never_idles_gives_nothing():
    ius = _reader("idle_under_span")
    planes = _planes([("onix.fit.wait", 1, 2)], [(-1, 102)])
    assert ius.read({"scope_planes": planes}, {"as": "unspanned_pct"}) is None


def test_idle_under_span_reads_a_trace_made_here(tmp_path):
    """The whole path on a real xplane: the tracer's own `onix.*` events
    are found on the host plane, whatever the CPU's device plane holds
    (on the CPU there is none, so the booking has nothing to read)."""
    import jax.numpy as jnp
    import jax.profiler

    from benchmark import tracered
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.trace_open"):
        pass
    with telemetry.TRACER.span("scan.dispatch"):
        jnp.arange(64.0).sum().block_until_ready()
    with jax.profiler.TraceAnnotation("bench.trace_close"):
        pass
    jax.profiler.stop_trace()
    ss = _reader("scope_seconds")
    planes = ss.read_planes(tracered.find_xplane(str(tmp_path)))
    names = {e[0] for p in planes if p["name"].startswith(tracered.HOST_PLANE)
             for ln in p["lines"] for e in ln["events"]}
    assert {"onix.scan.dispatch", "bench.trace_open"} <= names

    class Dir:
        dir = str(tmp_path)
    run = {"manifest": MANIFEST, "tracer": Dir}
    ius = _reader("idle_under_span")
    assert ius.read(run, _spec("idle_unspanned_pct.scan")) is None
    assert "scope_planes" in run and run["idle_under_span"] is None


def test_every_new_metric_is_in_the_manifest_with_its_cells():
    per_layer = {m["name"]: m for m in MANIFEST.data["per_layer"]}
    six = [w["name"] for w in MANIFEST.data["workloads"]]
    assert per_layer["compile_s"]["workloads"] == six
    assert per_layer["compile_cold_s"]["workloads"] == six
    for name in ("compile_s", "compile_cold_s", "fit_init_compile_s"):
        assert per_layer[name]["moves"] == "setup_s"
        assert per_layer[name]["source"] == "program_span"
    assert per_layer["fit_init_compile_s"]["layer"] == \
        per_layer["fit_init_state_s"]["layer"]
    for name, like in (("idle_unspanned_pct.fit", "device_idle_pct.fit"),
                       ("idle_unspanned_pct.scan", "device_idle_pct.scan"),
                       ("idle_dispatch_s_per_chunk", "device_idle_pct.scan")):
        assert per_layer[name]["workloads"] == per_layer[like]["workloads"]
        assert per_layer[name]["moves"] == per_layer[like]["moves"]
        assert per_layer[name]["source"] == "device_trace"
    # The stream has no share of its own: its device idles 4 ms of a
    # traced window, under the reader's floor.
    assert not [m for m in per_layer.values()
                if m["name"].startswith("idle_")
                and "flow-stream-catchup" in m["workloads"]]
