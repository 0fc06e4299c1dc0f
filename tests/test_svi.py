import numpy as np
import pytest

from onix.config import LDAConfig
from onix.corpus import synthetic_lda_corpus
from onix.models.lda_svi import SVILda, make_minibatch, phi_estimate
from tests.test_gibbs import _topic_alignment_similarity


def test_svi_recovers_topics_from_minibatches():
    corpus, _, phi_true = synthetic_lda_corpus(
        n_docs=300, n_vocab=100, n_topics=4, mean_doc_len=60,
        alpha=0.2, eta=0.05, seed=0)
    cfg = LDAConfig(n_topics=4, alpha=0.3, eta=0.05, svi_tau0=16.0,
                    svi_kappa=0.7, svi_local_iters=25, seed=0)
    model = SVILda(cfg, corpus.n_vocab, corpus_docs=corpus.n_docs)
    state = model.init()
    # Stream documents in batches of 30; 3 epochs.
    order = np.argsort(corpus.doc_ids, kind="stable")
    d, w = corpus.doc_ids[order], corpus.word_ids[order]
    for _ in range(3):
        for lo in range(0, corpus.n_docs, 30):
            sel = (d >= lo) & (d < lo + 30)
            batch = make_minibatch(d[sel], w[sel], pad_to=4096)
            state, _ = model.update(state, batch)
    phi_est = np.asarray(phi_estimate(state)).T
    sim = _topic_alignment_similarity(phi_true, phi_est)
    assert sim > 0.8, f"SVI topic recovery too weak: {sim:.3f}"


def test_minibatch_padding_and_densify():
    b = make_minibatch(np.array([7, 7, 9]), np.array([1, 2, 3]), pad_to=8)
    assert b.n_docs == 2
    assert b.doc_ids.shape == (8,)
    assert float(b.mask.sum()) == 3.0
    assert int(b.doc_ids[0]) == 0 and int(b.doc_ids[2]) == 1


def test_gamma_shapes():
    cfg = LDAConfig(n_topics=3)
    model = SVILda(cfg, n_vocab=50, corpus_docs=100)
    state = model.init()
    b = make_minibatch(np.array([0, 1, 1]), np.array([4, 5, 6]), pad_to=16)
    state2, gamma = model.update(state, b)
    assert gamma.shape == (2, 3)
    assert int(state2.step) == 1
    assert np.all(np.isfinite(np.asarray(state2.lam)))


def test_weighted_dedup_batch_matches_repeated_tokens():
    """The deduped streaming minibatch (unique (doc, word) pairs with
    counts as mask weights) must drive the SAME update as the repeated
    tokens it stands for — same lambda, same gamma (up to scatter-order
    float noise)."""
    rng = np.random.default_rng(0)
    d = rng.integers(0, 12, 400).astype(np.int32)
    w = rng.integers(0, 50, 400).astype(np.int32)
    cfg = LDAConfig(n_topics=4, svi_meanchange_tol=0.0, seed=1)
    model = SVILda(cfg, n_vocab=50, corpus_docs=100)
    s0 = model.init()

    rep = make_minibatch(d, w, pad_to=512)
    s_rep, g_rep = model.update(s0, rep)

    key = d.astype(np.int64) * 50 + w
    uniq, cnt = np.unique(key, return_counts=True)
    du = (uniq // 50).astype(np.int32)
    wu = (uniq % 50).astype(np.int32)
    ded = make_minibatch(du, wu, pad_to=512,
                         weights=cnt.astype(np.float32))
    s_ded, g_ded = model.update(s0, ded)

    assert len(uniq) < 400            # the dedup actually deduped
    np.testing.assert_allclose(np.asarray(s_ded.lam),
                               np.asarray(s_rep.lam), rtol=2e-4)
    np.testing.assert_allclose(np.asarray(g_ded), np.asarray(g_rep),
                               rtol=2e-4)


def test_meanchange_stop_matches_converged_fixed_count():
    """The convergence stop may only end the E-step EARLY on a batch
    that has already converged — its gamma must match the full
    fixed-count iteration within the stopping tolerance."""
    rng = np.random.default_rng(3)
    d = rng.integers(0, 8, 300).astype(np.int32)
    w = rng.integers(0, 40, 300).astype(np.int32)
    batch = make_minibatch(d, w, pad_to=512)
    full = SVILda(LDAConfig(n_topics=4, svi_meanchange_tol=0.0,
                            svi_local_iters=60, seed=1), 40, 100)
    stop = SVILda(LDAConfig(n_topics=4, svi_meanchange_tol=1e-4,
                            svi_local_iters=60, seed=1), 40, 100)
    _, g_full = full.update(full.init(), batch)
    _, g_stop = stop.update(stop.init(), batch)
    np.testing.assert_allclose(np.asarray(g_stop), np.asarray(g_full),
                               atol=5e-3, rtol=1e-3)


def test_active_ladder_buckets():
    from onix.models.lda_svi import _active_ladder
    assert _active_ladder(2048) == [2048, 1024, 512, 256]
    assert _active_ladder(256) == [256, 128, 64]
    assert _active_ladder(64) == [64]


def test_warm_compacted_estep_matches_legacy_loop():
    """The warm/cold compacted E-step (svi_warm_iters > 0) must land on
    the same converged gamma and lambda as the r6 full-block
    while_loop, within the stopping tolerance — the compaction is a
    cost lever, not a model change."""
    rng = np.random.default_rng(11)
    d = rng.integers(0, 16, 600).astype(np.int32)
    w = rng.integers(0, 40, 600).astype(np.int32)
    batch = make_minibatch(d, w, pad_to=1024, pad_docs=32)
    legacy = SVILda(LDAConfig(n_topics=4, svi_meanchange_tol=1e-4,
                              svi_local_iters=100, svi_warm_iters=0,
                              seed=1), 40, 100)
    compact = SVILda(LDAConfig(n_topics=4, svi_meanchange_tol=1e-4,
                               svi_local_iters=100, svi_warm_iters=3,
                               seed=1), 40, 100)
    s_l, g_l = legacy.update(legacy.init(), batch)
    s_c, g_c = compact.update(compact.init(), batch)
    np.testing.assert_allclose(np.asarray(g_c), np.asarray(g_l),
                               atol=5e-3, rtol=1e-2)
    np.testing.assert_allclose(np.asarray(s_c.lam), np.asarray(s_l.lam),
                               rtol=1e-3)


def test_warm_compacted_estep_warm_docs_frozen_cold_docs_converge():
    """A batch mixing pre-converged (warm-started) docs with cold ones
    must still converge the cold docs fully: the compacted extension
    may freeze only docs whose warm-pass delta is already under tol."""
    rng = np.random.default_rng(13)
    d = rng.integers(0, 8, 400).astype(np.int32)
    w = rng.integers(0, 40, 400).astype(np.int32)
    batch = make_minibatch(d, w, pad_to=512, pad_docs=16)
    model = SVILda(LDAConfig(n_topics=4, svi_meanchange_tol=1e-5,
                             svi_local_iters=200, svi_warm_iters=2,
                             seed=1), 40, 100)
    s0 = model.init()
    _, g_ref = model.update(s0, batch)          # all-cold reference
    # Warm start HALF the docs at the converged point, leave the rest
    # at a far-off state: the far-off docs must still converge.
    g0 = np.asarray(g_ref).copy()
    g0[4:] = 50.0
    _, g_mix = model.update(s0, batch, gamma0=g0)
    np.testing.assert_allclose(np.asarray(g_mix)[:8],
                               np.asarray(g_ref)[:8],
                               atol=5e-3, rtol=2e-2)


def _store_chain(cfg):
    """Three batches over overlapping documents 0..11 of a 32-row store:
    `svi_store_step` chained on the device, and `svi_step` per batch
    with the store carried on the host. Returns both ends."""
    import jax.numpy as jnp

    from onix.models.lda_svi import minibatch_arrays, svi_store_step
    from onix.models.scoring import score_events

    rng = np.random.default_rng(17)
    k = cfg.n_topics
    model = SVILda(cfg, n_vocab=50, corpus_docs=100)
    state = model.init()
    gds = [rng.integers(0, 12, 200).astype(np.int32) for _ in range(3)]
    gws = [rng.integers(0, 50, 200).astype(np.int32) for _ in range(3)]
    pad_to, pad_docs, cap = 256, 16, 32
    store0 = np.full((cap, k), cfg.alpha + 1.0, np.float32)

    # Sequential reference: svi_step per batch, host-carried store.
    seq_state, store_ref, seq_scores = state, store0.copy(), []
    for d, w in zip(gds, gws):
        dm = minibatch_arrays(d, w, pad_to=pad_to, pad_docs=pad_docs)[3]
        batch = make_minibatch(d, w, pad_to=pad_to, pad_docs=pad_docs)
        r = dm >= 0
        g0 = np.full((pad_docs, k), cfg.alpha + 1.0, np.float32)
        g0[r] = store_ref[dm[r]]
        seq_state, gamma = model.update(seq_state, batch,
                                        corpus_docs=12.0, gamma0=g0)
        gm = np.asarray(gamma)
        store_ref[dm[r]] = gm[r]
        theta = np.where(r[:, None], gm / gm.sum(1, keepdims=True),
                         1.0 / k).astype(np.float32)
        phi = seq_state.lam / seq_state.lam.sum(0, keepdims=True)
        seq_scores.append(np.asarray(score_events(
            jnp.asarray(theta), phi, batch.doc_ids, batch.word_ids))[:200])

    # The whole store on the device, tokens padded with weight 0 and
    # pointed at the last row, which is no document's.
    new_state, store, scores = state, jnp.asarray(store0), []
    pad = pad_to - 200
    for d, w in zip(gds, gws):
        new_state, store, touched, sc, stats, _ = svi_store_step(
            new_state, store,
            jnp.asarray(np.concatenate([d, np.full(pad, cap - 1, np.int32)])),
            jnp.asarray(np.concatenate([w, np.zeros(pad, np.int32)])),
            jnp.asarray(np.concatenate([np.ones(200, np.float32),
                                        np.zeros(pad, np.float32)])),
            jnp.float32(12.0), alpha=cfg.alpha, eta=cfg.eta,
            tau0=cfg.svi_tau0, kappa=cfg.svi_kappa,
            local_iters=cfg.svi_local_iters,
            meanchange_tol=cfg.svi_meanchange_tol,
            warm_iters=cfg.svi_warm_iters, estep_form=cfg.stream_estep)
        assert set(np.flatnonzero(np.asarray(touched))) == set(np.unique(d))
        assert int(stats[0]) == cfg.svi_warm_iters
        scores.append(np.asarray(sc)[:200])
    return new_state, np.asarray(store), scores, seq_state, store_ref, \
        seq_scores


def test_store_step_matches_sequential_updates():
    """svi_store_step (one batch against the whole gamma store, what the
    streaming scorer chains on the device) must reproduce the sequential
    svi_step chain: same final lambda, same gamma rows, same per-token
    scores; rows no batch touched keep what they held."""
    new_state, store, scores, seq_state, store_ref, seq_scores = \
        _store_chain(LDAConfig(n_topics=4, svi_meanchange_tol=1e-4,
                               svi_local_iters=30, svi_warm_iters=2,
                               seed=3))
    assert int(new_state.step) == int(seq_state.step)
    np.testing.assert_allclose(np.asarray(new_state.lam),
                               np.asarray(seq_state.lam), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(store, store_ref, rtol=1e-4, atol=1e-5)
    for got, want in zip(scores, seq_scores):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)


def test_store_step_in_token_runs(monkeypatch):
    """Past `_TOKEN_RUN` tokens every pass of svi_store_step (E-step,
    lambda step, scores) works a run of tokens at a time, and the
    compaction carries the columns through its sort: the same sums in
    another order."""
    from onix.models import lda_svi

    cfg = LDAConfig(n_topics=4, svi_meanchange_tol=1e-4, svi_local_iters=30,
                    svi_warm_iters=2, seed=3)
    whole = _store_chain(cfg)
    monkeypatch.setattr(lda_svi, "_TOKEN_RUN", 64)     # 256 tokens: 4 runs
    runs = _store_chain(cfg)
    np.testing.assert_allclose(np.asarray(runs[0].lam),
                               np.asarray(whole[0].lam), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(runs[1], whole[1], rtol=1e-4, atol=1e-5)
    for got, want in zip(runs[2], whole[2]):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)


def test_warm_start_gamma_converges_to_same_fixed_point():
    """A warm-started E-step (returning docs' prior gamma) lands on the
    same converged gamma as the cold start — the warm start is a speed
    lever, not a model change."""
    rng = np.random.default_rng(7)
    d = rng.integers(0, 8, 300).astype(np.int32)
    w = rng.integers(0, 40, 300).astype(np.int32)
    batch = make_minibatch(d, w, pad_to=512)
    model = SVILda(LDAConfig(n_topics=4, svi_meanchange_tol=1e-5,
                             svi_local_iters=200, seed=1), 40, 100)
    s0 = model.init()
    _, g_cold = model.update(s0, batch)
    g0 = np.asarray(g_cold) * 0.9 + 0.2      # a perturbed prior state
    _, g_warm = model.update(s0, batch, gamma0=g0)
    np.testing.assert_allclose(np.asarray(g_warm), np.asarray(g_cold),
                               atol=5e-3, rtol=1e-2)


# -- svi_store_step over the batch's unique (document, word) pairs --------

_PAIR_V, _PAIR_CAP, _PAIR_T = 50, 32, 256


def _pair_batch(kind: str):
    """(doc_ids, word_ids, mask) of 256 token slots over documents 0..11
    of a 32-row store and 50 words."""
    rng = np.random.default_rng(29)
    d = np.full(_PAIR_T, _PAIR_CAP - 1, np.int32)
    w = np.zeros(_PAIR_T, np.int32)
    m = np.zeros(_PAIR_T, np.float32)
    if kind == "no_duplicate":
        pairs = rng.choice(12 * _PAIR_V, 200, replace=False)
        d[:200], w[:200], m[:200] = pairs // _PAIR_V, pairs % _PAIR_V, 1.0
    elif kind == "one_document_half":
        # Document 3 holds 100 of 200 tokens in five pairs.
        d[:200] = np.where(np.arange(200) % 2 == 0, 3,
                           rng.integers(0, 12, 200))
        w[:200] = np.where(np.arange(200) % 2 == 0,
                           rng.integers(0, 5, 200),
                           rng.integers(0, _PAIR_V, 200))
        m[:200] = 1.0
    elif kind == "weightless_scattered":
        # Tokens of weight 0 all through the batch, pointing at any row:
        # at documents with tokens of weight, and at rows with none.
        d[:] = rng.integers(0, 12, _PAIR_T)
        w[:] = rng.integers(0, 20, _PAIR_T)
        m[:] = rng.random(_PAIR_T) < 0.7
        d[m == 0] = rng.integers(0, _PAIR_CAP, int((m == 0).sum()))
    else:
        raise ValueError(kind)
    return d, w, m


def _store_step(cfg, state, store, d, w, m):
    import jax.numpy as jnp

    from onix.models.lda_svi import svi_store_step

    return svi_store_step(
        state, jnp.asarray(store), jnp.asarray(d), jnp.asarray(w),
        jnp.asarray(m), jnp.float32(12.0), alpha=cfg.alpha, eta=cfg.eta,
        tau0=cfg.svi_tau0, kappa=cfg.svi_kappa,
        local_iters=cfg.svi_local_iters,
        meanchange_tol=cfg.svi_meanchange_tol,
        warm_iters=cfg.svi_warm_iters, estep_form=cfg.stream_estep)


@pytest.mark.parametrize("kind", ["no_duplicate", "one_document_half",
                                  "weightless_scattered"])
@pytest.mark.parametrize("estep", ["svi", "scvb0"])
def test_store_step_dedupes_as_the_host_path_does(estep, kind):
    """svi_store_step, handed one row a token, leaves the lambda, the
    store, the touched mask and the per-token scores that `svi_step`
    leaves when fed the batch's unique (document, word) pairs with their
    counts as weights, as `StreamingScorer._prep_batch` builds them."""
    import jax.numpy as jnp

    from onix.models.lda_svi import minibatch_arrays
    from onix.models.scoring import score_events

    cfg = LDAConfig(n_topics=4, svi_meanchange_tol=1e-4, svi_local_iters=30,
                    svi_warm_iters=2, seed=3, stream_estep=estep)
    k = cfg.n_topics
    model = SVILda(cfg, n_vocab=_PAIR_V, corpus_docs=100)
    state = model.init()
    store0 = (cfg.alpha + np.random.default_rng(5).gamma(
        2.0, 2.0, (_PAIR_CAP, k))).astype(np.float32)
    d, w, m = _pair_batch(kind)

    real = m > 0
    uniq, inv, cnt = np.unique(d[real].astype(np.int64) * _PAIR_V + w[real],
                               return_inverse=True, return_counts=True)
    if kind == "no_duplicate":
        assert len(uniq) == real.sum()
    else:
        assert len(uniq) < real.sum()
    did_b, wid_b = (uniq // _PAIR_V).astype(np.int32), \
        (uniq % _PAIR_V).astype(np.int32)
    pad_docs = 16
    dm = minibatch_arrays(did_b, wid_b, pad_to=_PAIR_T, pad_docs=pad_docs)[3]
    batch = make_minibatch(did_b, wid_b, pad_to=_PAIR_T, pad_docs=pad_docs,
                           weights=cnt.astype(np.float32))
    r = dm >= 0
    g0 = np.full((pad_docs, k), cfg.alpha + 1.0, np.float32)
    g0[r] = store0[dm[r]]
    want_state, gamma = model.update(state, batch, corpus_docs=12.0,
                                     gamma0=g0)
    gm = np.asarray(gamma)
    want_store = store0.copy()
    want_store[dm[r]] = gm[r]
    theta = np.where(r[:, None], gm / gm.sum(1, keepdims=True),
                     1.0 / k).astype(np.float32)
    phi = want_state.lam / want_state.lam.sum(0, keepdims=True)
    want_scores = np.asarray(score_events(
        jnp.asarray(theta), phi, batch.doc_ids,
        batch.word_ids))[:len(uniq)][inv]

    got_state, store, touched, scores, stats, _ = _store_step(
        cfg, state, store0, d, w, m)
    assert set(np.flatnonzero(np.asarray(touched))) == set(np.unique(did_b))
    np.testing.assert_allclose(np.asarray(got_state.lam),
                               np.asarray(want_state.lam), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(store), want_store, rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(scores)[real], want_scores,
                               rtol=1e-5, atol=1e-7)
    assert int(stats[3]) == len(uniq)


def test_unique_pairs_equal_numpy_unique(monkeypatch):
    """The pairs the device makes are `np.unique`'s of the weighted
    tokens: ids, counts, their number; in front, the rows behind them
    weightless; at ids whose packed key passes 32 bits, and past one
    run of the passes that follow."""
    import jax.numpy as jnp

    from onix.models import lda_svi

    n_words, cap = 1 << 15, 1 << 18
    rng = np.random.default_rng(41)
    t = 512
    d = rng.choice([0, 1, 7, 1000, cap - 2, cap - 1], t).astype(np.int32)
    w = rng.choice(np.concatenate([rng.integers(0, n_words, 40),
                                   [0, n_words - 1]]), t).astype(np.int32)
    m = (rng.random(t) < 0.8).astype(np.float32)
    real = m > 0
    uniq, cnt = np.unique(d[real].astype(np.int64) * n_words + w[real],
                          return_counts=True)
    monkeypatch.setattr(lda_svi, "_TOKEN_RUN", 64)
    assert len(uniq) > 64 and len(uniq) < real.sum()

    p_doc, p_word, p_weight, n_pairs = lda_svi._unique_pairs(
        jnp.asarray(d), jnp.asarray(w), jnp.asarray(m))
    n = int(n_pairs)
    assert n == len(uniq)
    key = np.asarray(p_doc).astype(np.int64) * n_words + np.asarray(p_word)
    order = np.argsort(key[:n])
    np.testing.assert_array_equal(key[:n][order], uniq)
    np.testing.assert_array_equal(np.asarray(p_weight)[:n][order], cnt)
    assert (np.asarray(p_weight)[n:] == 0).all()
    assert p_doc.shape == p_word.shape == p_weight.shape == (t,)
    assert (np.asarray(p_doc) >= 0).all() and (np.asarray(p_doc) < cap).all()
    assert (np.asarray(p_word) >= 0).all() and \
        (np.asarray(p_word) < n_words).all()
    assert int(lda_svi._runs_holding(n_pairs, t)) == -(-n // 64)


def test_store_step_stats_count_tokens_and_pairs():
    """`stats[2]` is the active set's TOKENS (the sum of its pairs'
    weights), `stats[4]` its pairs, `stats[3]` the batch's pairs; the
    batch's pairs handed over as weighted rows read the same."""
    cfg = LDAConfig(n_topics=4, svi_meanchange_tol=1e-6, svi_local_iters=30,
                    svi_warm_iters=1, seed=3)
    model = SVILda(cfg, n_vocab=_PAIR_V, corpus_docs=100)
    state = model.init()
    store0 = np.full((_PAIR_CAP, cfg.n_topics), cfg.alpha + 1.0, np.float32)
    d, w, m = _pair_batch("one_document_half")
    real = m > 0
    uniq, cnt = np.unique(d[real].astype(np.int64) * _PAIR_V + w[real],
                          return_counts=True)

    stats = np.asarray(_store_step(cfg, state, store0, d, w, m)[4])
    assert stats.shape == (5,)
    # One cold pass leaves every touched document moving.
    assert stats[0] == 1 and stats[1] > 0
    assert stats[2] == real.sum() == 200
    assert stats[3] == stats[4] == len(uniq) < 200

    pad = _PAIR_T - len(uniq)
    weighted = np.asarray(_store_step(
        cfg, state, store0,
        np.concatenate([(uniq // _PAIR_V).astype(np.int32),
                        np.full(pad, _PAIR_CAP - 1, np.int32)]),
        np.concatenate([(uniq % _PAIR_V).astype(np.int32),
                        np.zeros(pad, np.int32)]),
        np.concatenate([cnt.astype(np.float32),
                        np.zeros(pad, np.float32)]))[4])
    np.testing.assert_array_equal(weighted, stats)

    # Some documents at rest: the active set's tokens and pairs are
    # theirs alone, and still tokens and pairs.
    cfg2 = LDAConfig(n_topics=4, svi_meanchange_tol=5e-2, svi_local_iters=30,
                     svi_warm_iters=3, seed=3)
    some = np.asarray(_store_step(cfg2, state, store0, d, w, m)[4])
    assert 0 < some[4] < some[3] == len(uniq)
    assert some[4] < some[2] < 200


def test_store_step_dedupe_has_its_own_scope_inside_the_estep():
    """The dedupe's ops are named `onix.svi.estep.pairs` inside
    `onix.svi.estep`: the benchmark's readers match a scope by prefix,
    so the E-step's metrics go on charging it, and a trace shows it
    alone (an op is booked to its innermost scope)."""
    import functools

    import jax

    cfg = LDAConfig(n_topics=4, svi_meanchange_tol=1e-4, svi_local_iters=30,
                    svi_warm_iters=2, seed=3)
    state = SVILda(cfg, n_vocab=_PAIR_V, corpus_docs=100).init()
    text = jax.jit(functools.partial(_store_step, cfg)).lower(
        state, np.ones((_PAIR_CAP, 4), np.float32),
        *_pair_batch("one_document_half")).compile().as_text()
    sorts = [ln for ln in text.splitlines() if " sort(" in ln]
    inside = [ln for ln in sorts
              if "onix.svi.estep/onix.svi.estep.pairs/" in ln]
    assert len(inside) >= 2                 # the dedupe's two
    assert len(sorts) > len(inside)         # the compaction's is not in it
    assert all("onix.svi.estep" in ln for ln in sorts)


# -- the ids mode's gamma packed G documents a 128-lane row -----------------

_PK_K, _PK_CAP, _PK_V, _PK_T, _PK_RUN, _PK_ROWS = 20, 30, 50, 512, 128, 300


def _packed_case(estep: str, active: str):
    """A store of 30 rows at K=20 (G=4: documents in every lane slot, the
    last packed row half full), 300 weighted (document, word) pairs word
    by word in front of 512 slots, in runs of 128 (the last run holds
    rows of weight that a pass must not reach: `n_rows` stops it), and
    the tolerance that leaves no document, some, or every one moving
    after the warm passes."""
    rng = np.random.default_rng(53)
    key = np.sort(rng.choice(_PK_CAP * _PK_V, _PK_ROWS, replace=False))
    d = np.zeros(_PK_T, np.int32)
    w = np.zeros(_PK_T, np.int32)
    m = np.zeros(_PK_T, np.float32)
    order = np.lexsort((key // _PK_V, key % _PK_V))
    d[:_PK_ROWS], w[:_PK_ROWS] = (key // _PK_V)[order], (key % _PK_V)[order]
    m[:_PK_ROWS] = rng.integers(1, 6, _PK_ROWS)
    # Past the runs that hold the pairs: weight, at every document.
    d[3 * _PK_RUN:] = rng.integers(0, _PK_CAP, _PK_T - 3 * _PK_RUN)
    w[3 * _PK_RUN:] = rng.integers(0, _PK_V, _PK_T - 3 * _PK_RUN)
    m[3 * _PK_RUN:] = 7.0
    assert set(d[:_PK_ROWS]) == set(range(_PK_CAP))
    lam = 0.01 + rng.gamma(0.5, 20.0, (_PK_V, _PK_K))
    if estep == "scvb0":
        elog_beta = np.log(lam / lam.sum(0, keepdims=True))
    else:
        from scipy.special import digamma
        elog_beta = digamma(lam) - digamma(lam.sum(0, keepdims=True))
    store = 1.2 + rng.gamma(2.0, 2.0, (_PK_CAP, _PK_K))
    tol, warm, iters = {"none": (1e9, 2, 30), "some": (0.1, 4, 30),
                        "all": (1e-30, 1, 6)}[active]
    return ((store.astype(np.float32), elog_beta.astype(np.float32), d, w,
             m), dict(alpha=1.2, local_iters=iters, meanchange_tol=tol,
                      warm_iters=warm, estep_form=estep))


def _chip_group(k: int) -> int:
    """What `lda_svi._gamma_group` reads on the chip (the CPU's is 1)."""
    from onix.models import lda_gibbs
    return lda_gibbs._ndk_group(k)


def _ids_e_step(case, monkeypatch, packed: bool):
    import jax.numpy as jnp

    from onix.models import lda_svi

    (store, elog_beta, d, w, m), kw = case
    with monkeypatch.context() as mp:
        mp.setattr(lda_svi, "_TOKEN_RUN", _PK_RUN)
        mp.setattr(lda_svi, "_gamma_group",
                   _chip_group if packed else (lambda k: 1))
        gamma, stats = lda_svi._run_e_step(
            jnp.asarray(store), jnp.asarray(w), jnp.asarray(d),
            jnp.asarray(m), with_stats=True,
            elog_beta=jnp.asarray(elog_beta), n_rows=jnp.int32(_PK_ROWS),
            **kw)
    return np.asarray(gamma), [int(s) for s in stats]


@pytest.mark.parametrize("active", ["none", "some", "all"])
@pytest.mark.parametrize("estep", ["svi", "scvb0"])
def test_packed_e_step_matches_rows_form(monkeypatch, estep, active):
    """The ids mode's E-step with gamma packed four documents a 128-lane
    row gives the rows form's gamma, to float32 rounding, and the same
    passes and active set; the rows the pass must not reach are left out
    of both; the gamma handed back is [cap, K]."""
    assert _chip_group(_PK_K) == 4
    case = _packed_case(estep, active)
    got, got_stats = _ids_e_step(case, monkeypatch, packed=True)
    want, want_stats = _ids_e_step(case, monkeypatch, packed=False)
    assert got.shape == want.shape == (_PK_CAP, _PK_K)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert got_stats == want_stats
    full, ext, _, n_rows, act_rows = got_stats
    assert n_rows == _PK_ROWS
    if active == "none":
        assert ext == act_rows == 0
    elif active == "some":
        assert 0 < act_rows < _PK_ROWS and ext > 0
    else:
        assert act_rows == _PK_ROWS and full + ext == 6

    # The rows past `n_rows` weigh nothing: the same E-step without them.
    (store, elog_beta, d, w, m), kw = case
    m = m.copy()
    m[_PK_ROWS:] = 0.0
    alone, alone_stats = _ids_e_step(((store, elog_beta, d, w, m), kw),
                                     monkeypatch, packed=True)
    np.testing.assert_array_equal(alone, got)
    assert alone_stats == got_stats


def _shared_rows_batch():
    """(doc_ids, word_ids, mask) of 256 token slots over a store of 30
    rows at K=20 (G=4: 30 is no multiple of it, so the last packed row,
    documents 28 and 29, has two empty slots): the touched documents
    share packed rows (0-3 one, 8-10 another, 13, 26); weightless tokens
    point at untouched rows sharing those packed rows (11, 24, 25), at
    a packed row no touched document is in (5, 6) and at the last packed
    row (28, 29)."""
    rng = np.random.default_rng(61)
    touched = np.array([0, 1, 2, 3, 8, 9, 10, 13, 26], np.int32)
    d = rng.choice(touched, _PAIR_T).astype(np.int32)
    w = rng.integers(0, _PAIR_V, _PAIR_T).astype(np.int32)
    m = rng.integers(1, 4, _PAIR_T).astype(np.float32)
    pad = np.arange(_PAIR_T) >= 180
    d[pad] = rng.choice([5, 6, 11, 24, 25, 28, 29], int(pad.sum()))
    m[pad] = 0.0
    return d, w, m


@pytest.mark.parametrize("case", ["one_document_half", "shared_rows"])
@pytest.mark.parametrize("estep", ["svi", "scvb0"])
def test_store_step_packed_matches_rows_form(monkeypatch, estep, case):
    """`svi_store_step` with gamma packed (the E-step, the lambda step on
    the E-step's packed rows, the scores on the updated store packed)
    and in the rows form: the same lambda, store and scores to float32
    rounding, every token slot's score among them, the same passes, and
    the store handed back [cap, K]. `shared_rows` packs four documents a
    row into a store whose last packed row is half empty, in runs of 64
    token slots."""
    from onix.models import lda_svi

    k, cap, batch = {
        "one_document_half": (4, _PAIR_CAP, _pair_batch("one_document_half")),
        "shared_rows": (20, 30, _shared_rows_batch())}[case]
    cfg = LDAConfig(n_topics=k, svi_meanchange_tol=1e-4, svi_local_iters=30,
                    svi_warm_iters=2, seed=3, stream_estep=estep)
    state = SVILda(cfg, n_vocab=_PAIR_V, corpus_docs=100).init()
    store0 = (cfg.alpha + np.random.default_rng(5).gamma(
        2.0, 2.0, (cap, cfg.n_topics))).astype(np.float32)
    if case == "shared_rows":
        assert _chip_group(k) == 4 and cap % 4
        monkeypatch.setattr(lda_svi, "_TOKEN_RUN", 64)
    monkeypatch.setattr(lda_svi, "_gamma_group", _chip_group)
    packed = _store_step(cfg, state, store0, *batch)
    monkeypatch.setattr(lda_svi, "_gamma_group", lambda k: 1)
    rows = _store_step(cfg, state, store0, *batch)
    assert packed[1].shape == rows[1].shape == (cap, cfg.n_topics)
    assert np.isfinite(np.asarray(packed[3])).all()
    np.testing.assert_allclose(np.asarray(packed[0].lam),
                               np.asarray(rows[0].lam), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(packed[1]), np.asarray(rows[1]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(packed[2]), np.asarray(rows[2]))
    np.testing.assert_allclose(np.asarray(packed[3]), np.asarray(rows[3]),
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_array_equal(np.asarray(packed[4]), np.asarray(rows[4]))


@pytest.mark.parametrize("backend,k,group", [
    ("tpu", 20, 4), ("tpu", 6, 16), ("tpu", 4, 32), ("tpu", 64, 2),
    ("tpu", 80, 1), ("tpu", 256, 1), ("cpu", 20, 1), ("gpu", 20, 1)])
def test_estep_packs_where_the_fit_packs(backend, k, group):
    """The ids mode's E-step takes the fit's form of n_dk, from the
    backend and K (`lda_gibbs.select_ndk_form`): the chip packs the
    largest power of two of K-lane documents a 128-lane row; K over 64,
    and a backend without a reading, keep one document a row."""
    from unittest import mock

    from onix.models import lda_svi

    with mock.patch.object(lda_svi.jax, "default_backend",
                           return_value=backend):
        assert lda_svi._gamma_group(k) == group


def test_store_step_pack_has_its_own_scope_inside_the_estep(monkeypatch):
    """The E-step's pack on entry and unpack at the end are named
    `onix.svi.estep.pack` inside `onix.svi.estep`, so that a trace shows
    them alone and `estep_s_per_batch` (by prefix) goes on charging the
    E-step for them."""
    import functools

    import jax

    from onix.models import lda_svi

    monkeypatch.setattr(lda_svi, "_gamma_group", _chip_group)
    cfg = LDAConfig(n_topics=20, svi_meanchange_tol=1e-4, svi_local_iters=30,
                    svi_warm_iters=2, seed=3)
    state = SVILda(cfg, n_vocab=_PAIR_V, corpus_docs=100).init()
    text = jax.jit(functools.partial(_store_step, cfg)).lower(
        state, np.ones((_PAIR_CAP, 20), np.float32),
        *_pair_batch("one_document_half")).compile().as_text()
    assert "onix.svi.estep/onix.svi.estep.pack/" in text


def test_store_step_packed_lambda_and_scores_keep_their_scopes(monkeypatch):
    """Packed, the lambda step's ops (its 128-lane row gathers, its
    scatter-add into the words' `[V, 128]` rows) are named
    `onix.svi.lambda`, and the scores' (the 128-lane row gathers of theta
    and phi) `onix.svi.score`, so that `lambda_s_per_batch` and a reader
    of the scores' scope go on booking them; no K-lane row of theta is
    gathered any more."""
    import functools

    import jax

    from onix.models import lda_svi

    monkeypatch.setattr(lda_svi, "_gamma_group", _chip_group)
    cfg = LDAConfig(n_topics=20, svi_meanchange_tol=1e-4, svi_local_iters=30,
                    svi_warm_iters=2, seed=3)
    state = SVILda(cfg, n_vocab=_PAIR_V, corpus_docs=100).init()
    text = jax.jit(functools.partial(_store_step, cfg)).lower(
        state, np.ones((_PAIR_CAP, 20), np.float32),
        *_pair_batch("one_document_half")).compile().as_text()
    lines = text.splitlines()

    def ops(op, scope, shape):
        return [ln for ln in lines if f" {op}(" in ln and shape in ln
                and f"/{scope}/" in ln]

    assert ops("scatter", "onix.svi.lambda", f"f32[{_PAIR_V},128]")
    assert ops("gather", "onix.svi.lambda", "slice_sizes={1,128}")
    assert len(ops("gather", "onix.svi.score", "slice_sizes={1,128}")) >= 1
    assert not ops("gather", "onix.svi.score", "slice_sizes={1,20}")


# -- the E-step on a working table of the batch's documents ---------------


def _working_case(form: str, estep: str):
    """(cfg, state, store0, batch, rows a document) of `one_document_half`
    over the 32-row store: K=4 one document a row, K=20 four (the chip's
    packed form)."""
    k = {"rows": 4, "packed": 20}[form]
    cfg = LDAConfig(n_topics=k, svi_meanchange_tol=1e-4, svi_local_iters=30,
                    svi_warm_iters=2, seed=3, stream_estep=estep)
    state = SVILda(cfg, n_vocab=_PAIR_V, corpus_docs=100).init()
    store0 = (cfg.alpha + np.random.default_rng(5).gamma(
        2.0, 2.0, (_PAIR_CAP, k))).astype(np.float32)
    group = 1 if form == "rows" else _chip_group(k)
    return cfg, state, store0, _pair_batch("one_document_half"), group


@pytest.mark.parametrize("rounds", ["one", "several"])
@pytest.mark.parametrize("form", ["rows", "packed"])
@pytest.mark.parametrize("estep", ["svi", "scvb0"])
def test_store_step_on_a_working_table(monkeypatch, estep, form, rounds):
    """A store of more rows than `_WORKING_ROWS` (patched small) is
    worked through a table of the batch's documents. In one round the
    step leaves the store-sized step's lambda, store, scores and stats,
    bit for bit one document a row and to float32 rounding packed; in
    several, each round's rows are the store-sized step's run on that
    round's documents alone. Rows the batch does not touch keep their
    bits; `work` reads the rounds and the batch's documents."""
    from onix.models import lda_svi

    cfg, state, store0, (d, w, m), group = _working_case(form, estep)
    monkeypatch.setattr(lda_svi, "_gamma_group",
                        lambda k: group)
    docs = np.unique(d[m > 0])
    assert len(docs) == 12
    whole = _store_step(cfg, state, store0, d, w, m)
    assert [int(x) for x in whole[5]] == [0, 12]
    slots = {"one": 16, "several": 4}[rounds]
    monkeypatch.setattr(lda_svi, "_WORKING_ROWS", slots // group)
    got = _store_step(cfg, state, store0, d, w, m)
    assert [int(x) for x in got[5]] == [-(-len(docs) // slots), 12]
    store = np.asarray(got[1])
    untouched = np.setdiff1d(np.arange(_PAIR_CAP), docs)
    np.testing.assert_array_equal(store[untouched], store0[untouched])
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(whole[2]))
    same = (np.testing.assert_array_equal if form == "rows" else
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5,
                                                    atol=1e-6))
    if rounds == "one":
        same(np.asarray(got[0].lam), np.asarray(whole[0].lam))
        same(store, np.asarray(whole[1]))
        same(np.asarray(got[3]), np.asarray(whole[3]))
        np.testing.assert_array_equal(np.asarray(got[4]),
                                      np.asarray(whole[4]))
        return
    # Round j holds the documents of rank [4 j, 4 j + 4).
    monkeypatch.setattr(lda_svi, "_WORKING_ROWS", 1 << 16)
    for j in range(3):
        mine = docs[4 * j:4 * j + 4]
        alone = _store_step(cfg, state, store0, d, w,
                            np.where(np.isin(d, mine), m, 0.0))
        assert [int(x) for x in alone[5]] == [0, 4]
        same(store[mine], np.asarray(alone[1])[mine])


@pytest.mark.parametrize("form", ["rows", "packed"])
def test_store_within_the_working_table_takes_the_store_path(monkeypatch,
                                                             form):
    """A store of at most `_WORKING_ROWS` rows (of its documents) is
    worked whole, as before the working table: no round is read, and
    the step leaves what the same batch forced through the working table
    leaves, to float32 rounding; the store-sized program holds no
    `while` of rounds."""
    import functools

    import jax

    from onix.models import lda_svi

    cfg, state, store0, batch, group = _working_case(form, "svi")
    monkeypatch.setattr(lda_svi, "_gamma_group", lambda k: group)
    monkeypatch.setattr(lda_svi, "_WORKING_ROWS", _PAIR_CAP // group)
    whole = _store_step(cfg, state, store0, *batch)
    assert [int(x) for x in whole[5]] == [0, 12]
    text = jax.jit(functools.partial(_store_step, cfg)).lower(
        state, store0, *batch).as_text()
    monkeypatch.setattr(lda_svi, "_WORKING_ROWS", _PAIR_CAP // group - 1)
    forced = _store_step(cfg, state, store0, *batch)
    assert [int(x) for x in forced[5]] == [1, 12]
    forced_text = jax.jit(functools.partial(_store_step, cfg)).lower(
        state, store0, *batch).as_text()
    assert forced_text.count("while") > text.count("while")
    for a, b in zip(jax.tree.leaves(whole[:5]), jax.tree.leaves(forced[:5])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)
