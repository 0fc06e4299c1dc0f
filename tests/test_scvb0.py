"""The r11 SCVB0 streaming arm (ISSUE 6 tentpole, streaming half).

lda.stream_estep="scvb0" swaps the local update for the SCVB0
collapsed zeroth-order estimator (arxiv 1305.2452) while riding the
SAME resident superstep and gamma store machinery as the SVI arm. It is a
different estimator, so the discipline is the one
test_stream_superstep_smoke established: exact winner-set parity
WITHIN the arm (per-batch vs fused superstep), winner-parity across
the arms on the same feed, and model-quality bands.
"""

import dataclasses as dc

import numpy as np
import pytest

from onix.config import LDAConfig, OnixConfig
from onix.corpus import synthetic_lda_corpus
from onix.models.lda_svi import SVILda, make_minibatch, phi_estimate
from tests.test_gibbs import _topic_alignment_similarity


def test_scvb0_recovers_topics_from_minibatches():
    """Same quality bar as the SVI arm's recovery test: the collapsed
    estimator must recover the planted topics from streamed
    minibatches."""
    corpus, _, phi_true = synthetic_lda_corpus(
        n_docs=300, n_vocab=100, n_topics=4, mean_doc_len=60,
        alpha=0.2, eta=0.05, seed=0)
    cfg = LDAConfig(n_topics=4, alpha=0.3, eta=0.05, svi_tau0=16.0,
                    svi_kappa=0.7, svi_local_iters=25, seed=0,
                    stream_estep="scvb0")
    model = SVILda(cfg, corpus.n_vocab, corpus_docs=corpus.n_docs)
    state = model.init()
    order = np.argsort(corpus.doc_ids, kind="stable")
    d, w = corpus.doc_ids[order], corpus.word_ids[order]
    for _ in range(3):
        for lo in range(0, corpus.n_docs, 30):
            sel = (d >= lo) & (d < lo + 30)
            batch = make_minibatch(d[sel], w[sel], pad_to=4096)
            state, _ = model.update(state, batch)
    phi_est = np.asarray(phi_estimate(state)).T
    sim = _topic_alignment_similarity(phi_true, phi_est)
    assert sim > 0.8, f"SCVB0 topic recovery too weak: {sim:.3f}"


def test_scvb0_gamma_positive_and_finite():
    """The collapsed responsibilities run log(gamma) directly — gamma
    must stay strictly positive (alpha floor) so the log never sees
    zero, including on padding rows and warm starts."""
    cfg = LDAConfig(n_topics=3, stream_estep="scvb0",
                    svi_meanchange_tol=1e-4, svi_warm_iters=2)
    model = SVILda(cfg, n_vocab=50, corpus_docs=100)
    state = model.init()
    b = make_minibatch(np.array([0, 1, 1]), np.array([4, 5, 6]),
                       pad_to=16, pad_docs=4)
    state2, gamma = model.update(state, b)
    g = np.asarray(gamma)
    assert np.isfinite(g).all() and (g > 0).all()
    assert np.isfinite(np.asarray(state2.lam)).all()


def _cfg(estep: str, superstep: int = 0) -> OnixConfig:
    cfg = OnixConfig()
    cfg.lda.n_topics = 6
    cfg.lda.svi_tau0 = 1.0
    cfg = dc.replace(cfg, lda=dc.replace(cfg.lda, stream_estep=estep),
                     pipeline=dc.replace(cfg.pipeline,
                                         stream_superstep=superstep,
                                         tol=0.25))
    return cfg.validate()


@pytest.fixture(scope="module")
def flow_chunks():
    from onix.pipelines.synth import synth_flow_day
    table, _ = synth_flow_day(n_events=3000, n_hosts=60, n_anomalies=9,
                              seed=33)
    return [table.iloc[i * 500:(i + 1) * 500].reset_index(drop=True)
            for i in range(6)]


def test_scvb0_superstep_winner_parity_within_arm(flow_chunks):
    """WITHIN the scvb0 arm the superstep contract is exact: per-batch
    vs S=3 fused over the same feed — identical winner sets, close
    scores, dispatch collapse (the test_stream_superstep_smoke
    contract on the new arm)."""
    from onix.pipelines.streaming import StreamingScorer

    per_batch = StreamingScorer(_cfg("scvb0", 0), "flow",
                                n_buckets=1 << 11)
    res_a = [per_batch.process(c) for c in flow_chunks]
    fused = StreamingScorer(_cfg("scvb0", 3), "flow", n_buckets=1 << 11)
    res_b = fused.process_many([(c, None) for c in flow_chunks])
    assert len(res_b) == 6
    any_alerts = False
    for a, b in zip(res_a, res_b):
        sa = set(a.alerts["event_idx"].tolist())
        sb = set(b.alerts["event_idx"].tolist())
        assert sa == sb, "scvb0 superstep winner set diverged"
        any_alerts = any_alerts or bool(sa)
        np.testing.assert_allclose(b.scores, a.scores, rtol=1e-4,
                                   atol=1e-6)
    assert any_alerts
    # The first batch fits the edges on the host path; the other five
    # ride two resident supersteps.
    assert fused.dispatches["superstep"] == 2
    assert fused.dispatches["svi_update"] == 1


def test_scvb0_vs_svi_winner_parity_on_stream(flow_chunks):
    """ACROSS the arms the discipline is winner-parity: both
    estimators score the same feed and must agree on (nearly) all
    winners — the alert overlap stays above 90% with both arms
    actually alerting."""
    from onix.pipelines.streaming import StreamingScorer

    sc_svi = StreamingScorer(_cfg("svi"), "flow", n_buckets=1 << 11)
    res_svi = [sc_svi.process(c) for c in flow_chunks]
    sc_scvb = StreamingScorer(_cfg("scvb0"), "flow", n_buckets=1 << 11)
    res_scvb = [sc_scvb.process(c) for c in flow_chunks]
    inter = union = 0
    for a, b in zip(res_svi, res_scvb):
        sa = set(a.alerts["event_idx"].tolist())
        sb = set(b.alerts["event_idx"].tolist())
        inter += len(sa & sb)
        union += len(sa | sb)
    assert union > 0
    jaccard = inter / union
    assert jaccard > 0.9, f"winner sets diverged: jaccard={jaccard:.3f}"


def test_scvb0_fingerprint_differs_from_svi(tmp_path):
    """A lambda trained under one estimator must not be adopted by the
    other: stream_estep is part of the streaming checkpoint
    fingerprint."""
    from onix.pipelines.streaming import StreamingScorer

    a = StreamingScorer(_cfg("svi"), "flow", n_buckets=1 << 11)
    b = StreamingScorer(_cfg("scvb0"), "flow", n_buckets=1 << 11)
    assert a._fingerprint() != b._fingerprint()


def test_scvb0_store_step_matches_sequential_updates():
    """svi_store_step with the scvb0 form must reproduce the sequential
    svi_step chain exactly - the resident store is form-agnostic."""
    from tests.test_svi import _store_chain

    new_state, store, _, seq_state, store_ref, _ = _store_chain(
        LDAConfig(n_topics=4, svi_meanchange_tol=1e-4, svi_local_iters=30,
                  svi_warm_iters=2, seed=3, stream_estep="scvb0"))
    np.testing.assert_allclose(np.asarray(new_state.lam),
                               np.asarray(seq_state.lam), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(store, store_ref, rtol=1e-4, atol=1e-5)
