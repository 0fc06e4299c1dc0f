"""The readers ISSUE 26 adds: `program_span` on a synthetic flight ring,
`scope_seconds` on a small clip of a chip trace."""

import json
import pathlib

import pytest

from benchmark import harness
from onix.utils import telemetry

ROOT = pathlib.Path(__file__).resolve().parents[2]
FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures"


@pytest.fixture
def ring():
    telemetry.reset_for_tests()
    capacity = telemetry.RECORDER._ring.maxlen
    yield telemetry.RECORDER
    telemetry.RECORDER.reconfigure(capacity=capacity)
    telemetry.reset_for_tests()


def _reader(name):
    return harness.Manifest(ROOT / "BENCHMARK.json").load("readers", name)


def _staged_chunk(put_seconds):
    """One `scan.stage` with a `scan.h2d_put` child of each length."""
    with telemetry.TRACER.span("scan.stage"):
        for dur in put_seconds:
            telemetry.TRACER.observe("scan.h2d_put", dur)


def test_program_span_sums_medians_and_groups_by_parent(ring):
    read = _reader("program_span").read
    for dur in (0.5, 0.25, 2.0):
        with telemetry.TRACER.trace():
            telemetry.TRACER.observe("fit.prepare", dur)
    _staged_chunk([0.1, 0.2])
    _staged_chunk([0.3, 0.3, 0.3])
    _staged_chunk([1.0])
    with telemetry.TRACER.trace():      # a put outside any stage
        telemetry.TRACER.observe("scan.h2d_put", 50.0)
    spec = {"span": "fit.prepare"}
    assert read({}, dict(spec, stat="sum")) == pytest.approx(2.75)
    assert read({}, dict(spec, stat="median")) == pytest.approx(0.5)
    # Per parent 0.3, 0.9 and 1.0: the median chunk, not the mean.
    assert read({}, {"span": "scan.h2d_put", "children_of": "scan.stage"}
                ) == pytest.approx(0.9)
    # Nothing to read gives nothing, never 0.
    assert read({}, {"span": "fit.init_state", "stat": "sum"}) is None
    assert read({}, {"span": "scan.h2d_put",
                     "children_of": "fit.prepare"}) is None


def test_program_span_gives_nothing_once_the_ring_has_wrapped(ring):
    read = _reader("program_span").read
    ring.reconfigure(capacity=4)
    spec = {"span": "fit.prepare", "stat": "sum"}
    with telemetry.TRACER.trace():
        for _ in range(4):
            telemetry.TRACER.observe("fit.prepare", 1.0)
        assert read({}, spec) == pytest.approx(4.0)
        telemetry.TRACER.observe("fit.prepare", 1.0)    # drops the first
    assert read({}, spec) is None


def test_scope_seconds_reproduces_hand_sums_on_a_chip_clip():
    ss = _reader("scope_seconds")
    rec = json.loads((FIXTURES / "trace_v5e_scopes.json").read_text())
    by_scope, whole = ss.book(rec["planes"], "stream_scan")
    want = rec["expect"]
    # The second execution runs past bench.trace_close: left out whole.
    assert whole == want["whole_executions"] == 1
    assert {k: round(v * 1e12) for k, v in by_scope.items()} == \
        want["scope_ps"]
    run = {"scope_planes": rec["planes"]}
    lookup = ss.read(run, {"scope": "onix.words.lookup",
                           "module_match": "stream_scan"})
    assert lookup == pytest.approx(
        (want["scope_ps"]["onix.words.lookup_word"]
         + want["scope_ps"]["onix.words.lookup_doc"]) * 1e-12)
    pct = ss.read(run, {"as": "unscoped_pct", "module_match": "stream_scan"})
    assert pct == pytest.approx(100 * want["scope_ps"]["unscoped"]
                                / sum(want["scope_ps"].values()))
    # Nothing to read gives nothing: no such program, no such scope, a
    # program from before the scopes, a trace without the marks.
    assert ss.read(run, {"scope": "onix.words.lookup",
                         "module_match": "superstep"}) is None
    assert ss.read(run, {"scope": "onix.sweep.scatter",
                         "module_match": "stream_scan"}) is None
    bare = json.loads(json.dumps(rec["planes"]))
    for ev in bare[0]["lines"][1]["events"]:
        ev[3] = ev[3].replace("onix.", "")
    assert ss.book(bare, "stream_scan") is None
    bare[1]["lines"][0]["events"].pop()
    assert ss.book(bare, "stream_scan") is None


def test_scope_of_takes_the_innermost_scope():
    scope_of = _reader("scope_seconds").scope_of
    assert scope_of("jit(f)/while/body/onix.select/onix.score.gather/"
                    "gather:") == "onix.score.gather"
    assert scope_of("jit(f)/onix.sweep.scatter/scatter-add:") == \
        "onix.sweep.scatter"
    assert scope_of("jit(f)/while/body/dynamic_update_slice") == "unscoped"
    assert scope_of("") == "unscoped"


def test_the_wire_reader_agrees_with_profile_data(tmp_path):
    """The few fields `scope_seconds` takes from the protobuf itself,
    against jax's own reader, on a trace made here."""
    import jax.numpy as jnp
    import jax.profiler
    from jax.profiler import ProfileData

    from benchmark import tracered
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.trace_open"):
        pass
    with telemetry.TRACER.span("run.score"):
        jnp.arange(64.0).sum().block_until_ready()
    with jax.profiler.TraceAnnotation("bench.trace_close"):
        pass
    jax.profiler.stop_trace()
    path = tracered.find_xplane(str(tmp_path))
    want = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(tracered.HOST_PLANE):
            for line in plane.lines:
                want[line.name] = [(e.name, e.start_ns, e.duration_ns)
                                   for e in line.events]
    ss = _reader("scope_seconds")
    host, = [p for p in ss.read_planes(path)
             if p["name"].startswith(tracered.HOST_PLANE)]
    got = {ln["name"]: ln["events"] for ln in host["lines"]}
    assert set(got) == set(want) and any(want.values())
    for name, events in want.items():
        assert [e[0] for e in got[name]] == [e[0] for e in events]
        assert [e[1] * 1e-3 for e in got[name]] == pytest.approx(
            [e[1] for e in events])
        assert [e[2] * 1e-3 for e in got[name]] == pytest.approx(
            [e[2] for e in events])
    names = [e[0] for events in got.values() for e in events]
    assert {"bench.trace_open", "onix.run.score",
            "bench.trace_close"} <= set(names)
    # A trace with no device plane and no program: nothing to read.
    assert ss.read({"scope_planes": [host]},
                   {"as": "unscoped_pct", "module_match": "scan"}) is None
    assert ss.read({"tracer": harness.Tracer(False, "no-such-cell")},
                   {"as": "unscoped_pct", "module_match": "scan"}) is None


@pytest.mark.parametrize("name", [
    m["name"] for m in json.loads((ROOT / "BENCHMARK.json").read_text())
    ["per_layer"]])
def test_every_metric_file_names_a_reader_and_its_parameters(name):
    mf = harness.Manifest(ROOT / "BENCHMARK.json")
    spec = json.loads(mf.find("metrics", name).read_text())
    assert hasattr(mf.load("readers", spec["reader"]), "read")
    if spec["reader"] == "program_span":
        assert spec["span"] in telemetry.SPAN_REGISTRY
        assert ("children_of" in spec) != ("stat" in spec)
        assert spec.get("children_of", "scan.stage") in telemetry.SPAN_REGISTRY
        assert spec.get("stat", "sum") in ("sum", "median")
    if spec["reader"] == "scope_seconds":
        assert spec["module_match"]
        assert ("scope" in spec) != (spec.get("as") == "unscoped_pct")
