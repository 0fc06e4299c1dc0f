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
        new_state, store, touched, sc, stats = svi_store_step(
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

    got_state, store, touched, scores, stats = _store_step(
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
