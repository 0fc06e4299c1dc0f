"""Chaos harness tests: the declarative fault plan (ONIX_FAULT_PLAN),
the end-to-end drill with faults at all four wired stages, and the
no-silent-swallows lint.

The acceptance contract (ISSUE 4): with faults injected at ingest
decode, streaming batch, fit sweep, and checkpoint save, the pipeline
COMPLETES and the final scored artifacts are identical to a fault-free
run — bit-identical where the path is deterministic. Every rule is
one-shot, so the retry/resume machinery (not luck) is what carries the
run to the same answer.
"""

import json
import pathlib

import numpy as np
import pandas as pd
import pytest

from onix import checkpoint as ckpt
from onix.config import LDAConfig, OnixConfig
from onix.utils import faults
from onix.utils.obs import counters


@pytest.fixture(autouse=True)
def _clean_plan(monkeypatch):
    monkeypatch.delenv("ONIX_FAULT_PLAN", raising=False)
    faults.reset()
    counters.reset()
    yield
    faults.reset()
    counters.reset()


# ---------------------------------------------------------------------------
# Plan grammar + firing semantics
# ---------------------------------------------------------------------------


def test_plan_parse_grammar():
    p = faults.FaultPlan.parse(
        "ingest:decode@2=raise, stream:batch@5=raise,"
        "fit:sweep@30=preempt,ckpt:save@1=torn")
    assert [(r.stage, r.point, r.n, r.action) for r in p.rules] == [
        ("ingest", "decode", 2, "raise"), ("stream", "batch", 5, "raise"),
        ("fit", "sweep", 30, "preempt"), ("ckpt", "save", 1, "torn")]
    for bad in ("nonsense", "a:b@x=raise", "a:b@0=raise", "a:b@1=explode",
                "a@1=raise"):
        with pytest.raises(ValueError, match="bad fault rule"):
            faults.FaultPlan.parse(bad)
    assert faults.FaultPlan.parse("").rules == []


def test_counted_rule_fires_once_on_nth_call():
    faults.install_plan("ingest:decode@3=raise")
    assert faults.fire("ingest", "decode") is None
    assert faults.fire("ingest", "decode") is None
    with pytest.raises(faults.InjectedFault):
        faults.fire("ingest", "decode")
    # one-shot: the retry that follows succeeds
    assert faults.fire("ingest", "decode") is None
    assert counters.get("faults.ingest.decode") == 1


def test_indexed_rule_fires_at_first_boundary_at_or_after_n():
    faults.install_plan("fit:sweep@10=preempt")
    assert faults.fire("fit", "sweep", index=4) is None
    with pytest.raises(ckpt.SimulatedPreemption):
        faults.fire("fit", "sweep", index=13)
    assert faults.fire("fit", "sweep", index=20) is None    # one-shot


def test_torn_action_is_returned_not_raised():
    faults.install_plan("ckpt:save@1=torn")
    assert faults.fire("ckpt", "save") == "torn"
    assert faults.fire("ckpt", "save") is None


def test_env_plan_activates_and_counts(monkeypatch):
    monkeypatch.setenv("ONIX_FAULT_PLAN", "stream:batch@1=raise")
    with pytest.raises(faults.InjectedFault):
        faults.fire("stream", "batch")
    assert faults.active_plan().pending() == []


def test_unmatched_points_never_fire():
    faults.install_plan("ingest:decode@1=raise")
    assert faults.fire("stream", "batch") is None
    assert faults.fire("ckpt", "save") is None
    assert faults.active_plan().pending() == ["ingest:decode@1=raise"]


# ---------------------------------------------------------------------------
# Per-stage integration: fit preempt via plan, torn checkpoint save
# ---------------------------------------------------------------------------


def _corpus(seed=0):
    from onix.corpus import synthetic_lda_corpus
    return synthetic_lda_corpus(40, 50, 4, mean_doc_len=25, seed=seed)[0]


def test_plan_preempts_fit_and_resume_is_bit_identical(tmp_path):
    """fit:sweep preempt + ckpt:save torn through the REAL fit loop:
    the first checkpoint save is torn (json never lands), the fit is
    preempted at a later boundary, and the retried fit resumes to a
    bit-identical final state."""
    from onix.models.lda_gibbs import GibbsLDA

    corpus = _corpus(seed=3)
    cfg = LDAConfig(n_topics=4, n_sweeps=8, burn_in=4, block_size=256,
                    seed=5, checkpoint_every=2)
    ref = GibbsLDA(cfg, corpus.n_docs, corpus.n_vocab).fit(corpus)

    faults.install_plan("fit:sweep@4=preempt,ckpt:save@1=torn")
    with pytest.raises(ckpt.SimulatedPreemption):
        GibbsLDA(cfg, corpus.n_docs, corpus.n_vocab).fit(
            corpus, checkpoint_dir=tmp_path)
    # the torn first save left an npz with no adopted json
    fp_dir = next(p for p in tmp_path.iterdir() if p.is_dir())
    npzs = {p.stem for p in fp_dir.glob("*.npz")}
    jsons = {p.stem for p in fp_dir.glob("*.json")}
    assert npzs - jsons          # at least one torn pair
    resumed = GibbsLDA(cfg, corpus.n_docs, corpus.n_vocab).fit(
        corpus, checkpoint_dir=tmp_path)
    for name in ref["state"]._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(ref["state"], name)),
            np.asarray(getattr(resumed["state"], name)), err_msg=name)
    assert faults.active_plan().pending() == []


# ---------------------------------------------------------------------------
# The chaos end-to-end drill (tier-1 smoke): faults at ALL FOUR wired
# stages through a full tiny synth run; artifacts identical to the
# fault-free run.
# ---------------------------------------------------------------------------


GOOD_LINES = [
    ("2016-07-08 09:%02d:00 120 10.0.0.%d 200 TCP_HIT GET http "
     "host%d.example.com 80 /p%d - - - text/html \"UA %d\" - %d %d\n")
    % (i % 60, i % 7 + 1, i % 3, i, i % 4, 200 + i, 300 + 2 * i)
    for i in range(120)
]


def _write_landing(landing: pathlib.Path):
    landing.mkdir(parents=True)
    for b in range(3):
        (landing / f"batch{b}.log").write_text(
            "".join(GOOD_LINES[b * 40:(b + 1) * 40]))


def _run_pipeline(root: pathlib.Path, faulted: bool):
    """One full tiny run: watcher ingest -> streaming scoring over the
    raw files -> Gibbs fit with checkpoints. Under `faulted`, the
    active plan injects at every wired stage and this driver recovers
    exactly the way production callers do (watcher poll retry,
    run_stream's bounded batch retry, fit retry-after-preemption)."""
    from onix.ingest.watcher import IngestWatcher
    from onix.models.lda_gibbs import GibbsLDA
    from onix.pipelines.streaming import run_stream
    from onix.store import Store
    from onix.utils.resilience import RetryPolicy

    cfg = OnixConfig()
    cfg.store.root = str(root / "store")
    cfg.store.results_dir = str(root / "results")
    cfg.store.checkpoint_dir = str(root / "ck")
    cfg.lda = LDAConfig(n_topics=3, n_sweeps=6, burn_in=3, block_size=256,
                        seed=7, checkpoint_every=2,
                        svi_batch_size=64, svi_max_epochs=2)
    landing = root / "landing"
    _write_landing(landing)

    w = IngestWatcher(cfg, "proxy", landing, n_workers=1,
                      retry=RetryPolicy(max_attempts=3, base_backoff_s=0,
                                        jitter=0))
    w.poll_once()                   # quiescence
    for _ in range(6):
        w.poll_once()
        if w.stats["files"] == 3:
            break
    w._pool.shutdown()
    assert w.stats["files"] == 3, w.stats

    paths = sorted(str(p) for p in landing.glob("batch*.log"))
    assert run_stream(cfg, "proxy", paths, n_buckets=256) == 0

    corpus = _corpus(seed=11)
    model = GibbsLDA(cfg.lda, corpus.n_docs, corpus.n_vocab)
    try:
        fit = model.fit(corpus, checkpoint_dir=root / "fitck")
    except ckpt.SimulatedPreemption:
        assert faulted, "preempted without a fault plan"
        fit = GibbsLDA(cfg.lda, corpus.n_docs, corpus.n_vocab).fit(
            corpus, checkpoint_dir=root / "fitck")

    store = Store(cfg.store.root)
    rows = pd.concat([store.read("proxy", d) for d in store.dates("proxy")],
                     ignore_index=True)
    rows = rows.sort_values(list(rows.columns)).reset_index(drop=True)
    stream_csvs = {p.name: p.read_text()
                   for p in pathlib.Path(cfg.store.results_dir).rglob(
                       "*_streaming.csv")}
    return {"rows": rows, "stream_csvs": stream_csvs,
            "state": {k: np.asarray(getattr(fit["state"], k))
                      for k in fit["state"]._fields},
            "theta": np.asarray(fit["theta"]),
            "watcher_stats": dict(w.stats)}


@pytest.mark.faults
def test_chaos_plan_end_to_end_artifacts_identical(tmp_path):
    """THE acceptance drill: one-shot faults at ingest:decode,
    stream:batch, fit:sweep, and ckpt:save; the run completes and every
    artifact — stored rows, streaming alert CSVs, final sampler state —
    is identical to the fault-free run."""
    clean = _run_pipeline(tmp_path / "clean", faulted=False)
    assert clean["watcher_stats"]["errors"] == 0

    faults.install_plan("ingest:decode@2=raise,stream:batch@2=raise,"
                        "fit:sweep@3=preempt,ckpt:save@1=torn")
    chaos = _run_pipeline(tmp_path / "chaos", faulted=True)

    # every planned fault actually fired...
    assert faults.active_plan().pending() == []
    assert counters.get("faults.ingest.decode") == 1
    assert counters.get("faults.stream.batch") == 1
    assert counters.get("faults.fit.sweep") == 1
    assert counters.get("faults.ckpt.save") == 1
    # ...the recovery machinery absorbed them...
    assert chaos["watcher_stats"]["errors"] == 1
    assert chaos["watcher_stats"]["retries"] == 1
    assert chaos["watcher_stats"]["quarantined"] == 0
    assert counters.get("stream.batch.retries") == 1
    # ...and the artifacts are identical to the fault-free run.
    pd.testing.assert_frame_equal(clean["rows"], chaos["rows"])
    assert clean["stream_csvs"] == chaos["stream_csvs"]
    for name, arr in clean["state"].items():
        np.testing.assert_array_equal(arr, chaos["state"][name],
                                      err_msg=f"state.{name}")
    np.testing.assert_allclose(clean["theta"], chaos["theta"])


# ---------------------------------------------------------------------------
# Lint: no silent except-Exception swallows in onix/ — the r9 rule,
# RELOCATED into the contract-linter subsystem (onix/analysis/, pass
# `excepts`; r17). This thin wrapper keeps the guarantee in tier-1
# under its historical name so coverage never lapses across the move:
# the same handler set (Exception/BaseException/bare), the same
# visibility calls, over the same file scope (all of onix/ plus
# chip_smoke.py and scripts/*.py — scope preservation itself is asserted in
# tests/test_analysis.py::test_repo_scope_still_covers_the_r9_file_set).
# ---------------------------------------------------------------------------


def test_no_silent_except_exception_in_onix():
    """Every `except Exception` / `except BaseException` / BARE
    `except:` handler in onix/ (serving and feedback included), in
    chip_smoke.py, and in scripts/ must log, increment an obs counter,
    re-raise, or otherwise answer visibly — a swallowed exception in a
    resilience-hardened pipeline is indistinguishable from silent data
    loss."""
    from onix.analysis import core as analysis_core

    root = pathlib.Path(__file__).parent.parent
    ctx = analysis_core.AnalysisContext.from_root(root)
    offenders = analysis_core.run_passes(ctx, only=["excepts"])
    assert not offenders, (
        "silent except-Exception handlers (log, counters.inc, or raise "
        f"required): {[f.render() for f in offenders]}")


def test_chaos_counters_surface_in_scale_manifest(tmp_path):
    """Injected-fault and salvage tallies ride the scale manifest's
    `resilience` key (bench embeds the same snapshot), so a chaos run's
    evidence is in the artifact, not just stdout."""
    from onix.pipelines.scale import run_scale

    faults.install_plan("fit:sweep@1=preempt")
    try:
        run_scale(n_events=2000, n_hosts=40, n_sweeps=2, n_topics=3,
                  max_results=50, seed=1,
                  out_path=tmp_path / "manifest.json")
    except ckpt.SimulatedPreemption:
        pass
    faults.install_plan(None)
    manifest = run_scale(n_events=2000, n_hosts=40, n_sweeps=2, n_topics=3,
                         max_results=50, seed=1,
                         out_path=tmp_path / "manifest.json")
    assert manifest["resilience"]["faults.fit.sweep"] == 1
