"""Tier-1 smoke for the r10 streaming fast path (ISSUE 5): the fused
minibatch superstep and the warm/cold compacted E-step must stay
WINNER-SET-IDENTICAL to the per-batch path at a tiny shape, so the
fused arm cannot rot between chip runs (same contract as
test_fit_gap_smoke for the Gibbs superstep harness)."""

import dataclasses as dc

import numpy as np

from onix.config import OnixConfig
from onix.pipelines.streaming import StreamingScorer
from onix.pipelines.synth import synth_flow_day


def _cfg(superstep: int = 0) -> OnixConfig:
    cfg = OnixConfig()
    cfg.lda.n_topics = 6
    cfg.lda.svi_tau0 = 1.0
    cfg.pipeline.tol = 0.25        # a real cut: alert sets are proper
    #                                subsets, so parity is non-trivial
    cfg = dc.replace(cfg, pipeline=dc.replace(
        cfg.pipeline, stream_superstep=superstep, tol=0.25))
    return cfg.validate()


def test_stream_superstep_smoke():
    """Per-batch vs S=3 superstep over the same 6-batch feed: same
    alert (winner) sets per batch, close scores, and the dispatch
    collapse the superstep exists for (one fused program per S batches
    instead of svi+score per batch)."""
    table, _ = synth_flow_day(n_events=3000, n_hosts=60, n_anomalies=9,
                              seed=33)
    chunks = [table.iloc[i * 500:(i + 1) * 500].reset_index(drop=True)
              for i in range(6)]

    per_batch = StreamingScorer(_cfg(0), "flow", n_buckets=1 << 11)
    res_a = [per_batch.process(c) for c in chunks]

    fused = StreamingScorer(_cfg(3), "flow", n_buckets=1 << 11)
    res_b = fused.process_many([(c, None) for c in chunks])

    assert len(res_b) == 6
    any_alerts = False
    for a, b in zip(res_a, res_b):
        sa = set(a.alerts["event_idx"].tolist())
        sb = set(b.alerts["event_idx"].tolist())
        assert sa == sb, "superstep winner set diverged from per-batch"
        any_alerts = any_alerts or bool(sa)
        np.testing.assert_allclose(b.scores, a.scores, rtol=1e-4,
                                   atol=1e-6)
    assert any_alerts, "feed produced no alerts — parity was vacuous"

    # The whole point: dispatch syncs collapse. Per-batch pays one
    # svi_update + one score dispatch per batch; the resident arm pays
    # them once, for the first batch (its edges fit on the host path),
    # and then one superstep dispatch per run of eligible batches: the
    # first group's other two, and the second group's three.
    assert per_batch.dispatches["svi_update"] == 6
    assert per_batch.dispatches["score"] == 6
    assert fused.dispatches["superstep"] == 2
    assert fused.dispatches["svi_update"] == 1
    assert fused.dispatches["score"] == 1
    assert fused.words_mode_batches == {"device": 5, "host": 1}
    # One host-path shape; one resident program per group length.
    assert len(fused.pad_shapes) == 1
    assert {s[0] for s in fused.superstep_shapes} == {2, 3}


def test_stream_superstep_resume_cadence(tmp_path):
    """Checkpoints land on superstep boundaries and a resumed scorer
    skips exactly the consumed batches (the run_stream contract)."""
    table, _ = synth_flow_day(n_events=2000, n_hosts=50, n_anomalies=5,
                              seed=34)
    chunks = [table.iloc[i * 400:(i + 1) * 400].reset_index(drop=True)
              for i in range(5)]
    cfg = _cfg(2)
    cfg.lda.checkpoint_every = 2
    sc = StreamingScorer(cfg, "flow", n_buckets=1 << 11,
                         checkpoint_dir=tmp_path / "ck")
    sc.process_many([(c, None) for c in chunks])
    resumed = StreamingScorer(cfg, "flow", n_buckets=1 << 11,
                              checkpoint_dir=tmp_path / "ck")
    # 5 batches at cadence 2 → last boundary save at batch 4.
    assert resumed._batch_no == 4
