"""Observability + fault-injection tests (SURVEY.md §5.1, §5.3, §5.5)."""

import json

import numpy as np
import pytest

from onix.checkpoint import SimulatedPreemption
from onix.config import LDAConfig
from onix.corpus import synthetic_lda_corpus
from onix.models.lda_gibbs import GibbsLDA
from onix.utils.obs import Meter, RunLog


def test_runlog_appends_jsonl(tmp_path):
    log = RunLog(tmp_path / "r.jsonl")
    log.emit("run_start", datatype="flow")
    with log.stage("fit", n_tokens=10):
        pass
    with pytest.raises(ValueError):
        with log.stage("explode"):
            raise ValueError("boom")
    lines = [json.loads(l) for l in
             (tmp_path / "r.jsonl").read_text().splitlines()]
    events = [l["event"] for l in lines]
    assert events == ["run_start", "stage_start", "stage_end",
                      "stage_start", "stage_error"]
    assert lines[2]["wall_s"] >= 0
    assert "boom" in lines[4]["error"]


def test_runlog_none_path_is_noop():
    log = RunLog(None)
    log.emit("x")
    with log.stage("y"):
        pass


def test_meter():
    m = Meter()
    m.add(100)
    m.add(50)
    assert m.items == 150
    assert m.rate > 0


def test_fault_injection_then_resume_bit_identical(tmp_path):
    """The §5.3 drill: preempt mid-run, retry, and the resumed run must
    produce exactly the uninterrupted result."""
    corpus, _, _ = synthetic_lda_corpus(30, 40, 3, mean_doc_len=20, seed=1)
    cfg = LDAConfig(n_topics=3, n_sweeps=10, burn_in=4, block_size=256,
                    seed=7, checkpoint_every=2)

    ref = GibbsLDA(cfg, corpus.n_docs, corpus.n_vocab).fit(corpus)

    ck = tmp_path / "ck"
    model = GibbsLDA(cfg, corpus.n_docs, corpus.n_vocab)
    with pytest.raises(SimulatedPreemption):
        model.fit(corpus, checkpoint_dir=ck, fault_inject_sweep=5)
    resumed = GibbsLDA(cfg, corpus.n_docs, corpus.n_vocab).fit(
        corpus, checkpoint_dir=ck)

    np.testing.assert_array_equal(np.asarray(ref["state"].z),
                                  np.asarray(resumed["state"].z))
    np.testing.assert_allclose(ref["phi_wk"], resumed["phi_wk"], rtol=1e-6)


def test_fault_env_hook(tmp_path, monkeypatch):
    corpus, _, _ = synthetic_lda_corpus(20, 30, 3, mean_doc_len=10, seed=1)
    cfg = LDAConfig(n_topics=3, n_sweeps=6, burn_in=2, block_size=128,
                    seed=7, checkpoint_every=2)
    monkeypatch.setenv("ONIX_FAULT_SWEEP", "3")
    with pytest.raises(SimulatedPreemption):
        GibbsLDA(cfg, corpus.n_docs, corpus.n_vocab).fit(
            corpus, checkpoint_dir=tmp_path / "ck")


def test_manifest_reports_throughput_and_runlog(tmp_path):
    from onix.config import OnixConfig
    from onix.pipelines import synth
    from onix.pipelines.run import run_scoring
    from onix.store import Store, results_path

    cfg = OnixConfig()
    cfg.store.root = str(tmp_path / "store")
    cfg.store.results_dir = str(tmp_path / "results")
    cfg.store.feedback_dir = str(tmp_path / "feedback")
    cfg.store.checkpoint_dir = str(tmp_path / "ck")
    cfg.pipeline.datatype = "flow"
    cfg.pipeline.date = synth.DEMO_DATE
    cfg.lda.n_topics = 4
    cfg.lda.n_sweeps = 4
    cfg.lda.burn_in = 2
    cfg.lda.block_size = 2048
    table, _ = synth.synth_flow_day(n_events=600, seed=2)
    Store(cfg.store.root).write("flow", cfg.pipeline.date, table)

    assert run_scoring(cfg) == 0
    out = results_path(cfg.store.results_dir, "flow", cfg.pipeline.date)
    manifest = json.loads(out.with_suffix(".manifest.json").read_text())
    assert manifest["events_per_sec"] > 0
    assert manifest["scoring_seconds"] > 0

    lines = [json.loads(l) for l in
             out.with_suffix(".runlog.jsonl").read_text().splitlines()]
    events = [l["event"] for l in lines]
    assert events[0] == "run_start"
    assert events[-1] == "run_end"
    for stage in ("read", "word_creation", "corpus_build", "lda_fit",
                  "scoring"):
        assert f"stage_start" in events and stage in [
            l.get("stage") for l in lines if "stage" in l]
    assert any(e == "likelihood" for e in events)


def test_maybe_trace_collects_profile(tmp_path):
    import jax.numpy as jnp

    from jax.profiler import ProfileData

    from onix.utils import telemetry
    from onix.utils.obs import maybe_trace
    with maybe_trace(str(tmp_path / "prof")) as target:
        assert target is not None
        with telemetry.TRACER.span("run.score"):
            jnp.ones((8, 8)).sum().block_until_ready()
    # a trace dump appeared, and the span lies in its host plane under
    # the profiler's clock as `onix.<name>`
    xplane, = (tmp_path / "prof").rglob("*.xplane.pb")
    host = [p for p in ProfileData.from_file(str(xplane)).planes
            if p.name.startswith("/host:CPU")]
    assert any(e.name == "onix.run.score" and e.duration_ns > 0
               for p in host for line in p.lines for e in line.events)


def test_roofline_math_and_cpu_peak():
    """Roofline helper: achieved bytes/s from the modeled traffic, the
    CPU peak anchored in a live copy probe (no spec-sheet fiction), and
    a None peak yielding a None fraction rather than a made-up one."""
    from onix.utils.obs import (device_peak_bytes_per_s,
                                measured_host_bandwidth, roofline)

    r = roofline(1_000_000, 2.0, 100.0, 1e9)
    assert r["achieved_bytes_per_s"] == 50_000_000.0
    assert r["fraction_of_peak"] == 0.05
    assert roofline(10, 1.0, 4.0, None)["fraction_of_peak"] is None

    bw = measured_host_bandwidth(1 << 24)
    assert bw > 1e8                      # any real machine beats 100 MB/s
    peak, src = device_peak_bytes_per_s()
    assert peak and peak > 1e8           # tests force the CPU backend
    assert "probe" in src
