"""Numerical tests for the batched collapsed-Gibbs engine (SURVEY.md §4.2)."""

import numpy as np
import pytest
from scipy.optimize import linear_sum_assignment

from onix.config import LDAConfig
from onix.corpus import synthetic_lda_corpus
from onix.models.lda_gibbs import GibbsLDA


def _topic_alignment_similarity(phi_true, phi_est):
    """Mean cosine similarity after Hungarian topic matching."""
    k = phi_true.shape[0]
    a = phi_true / np.linalg.norm(phi_true, axis=1, keepdims=True)
    b = phi_est / np.linalg.norm(phi_est, axis=1, keepdims=True)
    sim = a @ b.T
    r, c = linear_sum_assignment(-sim)
    return sim[r, c].mean()


@pytest.fixture(scope="module")
def small_fit():
    corpus, theta, phi = synthetic_lda_corpus(
        n_docs=150, n_vocab=120, n_topics=5, mean_doc_len=80,
        alpha=0.2, eta=0.05, seed=0)
    cfg = LDAConfig(n_topics=5, alpha=0.5, eta=0.05, n_sweeps=50,
                    burn_in=25, block_size=2048, seed=0)
    model = GibbsLDA(cfg, corpus.n_docs, corpus.n_vocab)
    result = model.fit(corpus)
    return corpus, theta, phi, cfg, result


def test_count_invariants(small_fit):
    corpus, _, _, _, result = small_fit
    st = result["state"]
    n = corpus.n_tokens
    assert int(np.asarray(st.n_k).sum()) == n
    assert int(np.asarray(st.n_dk).sum()) == n
    assert int(np.asarray(st.n_wk).sum()) == n
    assert np.asarray(st.n_dk).min() >= 0
    assert np.asarray(st.n_wk).min() >= 0
    # Per-doc counts must equal doc lengths exactly.
    np.testing.assert_array_equal(
        np.asarray(st.n_dk).sum(axis=1),
        corpus.doc_lengths())


def test_topic_recovery(small_fit):
    _, _, phi_true, _, result = small_fit
    phi_est = result["phi_wk"].T  # [K,V]
    sim = _topic_alignment_similarity(phi_true, phi_est)
    assert sim > 0.85, f"topic recovery too weak: {sim:.3f}"


def test_likelihood_improves(small_fit):
    _, _, _, _, result = small_fit
    lls = [ll for _, ll in result["ll_history"]]
    assert lls[-1] > lls[0] + 0.1, f"log-likelihood did not improve: {lls}"


def test_estimates_are_distributions(small_fit):
    _, _, _, _, result = small_fit
    theta, phi_wk = result["theta"], result["phi_wk"]
    np.testing.assert_allclose(theta.sum(1), 1.0, atol=1e-4)
    np.testing.assert_allclose(phi_wk.sum(0), 1.0, atol=1e-4)


def test_determinism():
    corpus, _, _ = synthetic_lda_corpus(30, 40, 3, mean_doc_len=20, seed=1)
    cfg = LDAConfig(n_topics=3, n_sweeps=5, burn_in=2, block_size=256, seed=9)
    r1 = GibbsLDA(cfg, corpus.n_docs, corpus.n_vocab).fit(corpus)
    r2 = GibbsLDA(cfg, corpus.n_docs, corpus.n_vocab).fit(corpus)
    np.testing.assert_array_equal(np.asarray(r1["state"].z),
                                  np.asarray(r2["state"].z))
    np.testing.assert_allclose(r1["phi_wk"], r2["phi_wk"], rtol=1e-6)


def test_multi_chain_shapes_and_scoring():
    """n_chains>1 stacks a chain axis on theta/phi; score_events averages
    probabilities over chains (rank stability, SURVEY.md §7.3.2 — chains
    lift the judged oracle overlap above the oracle's own seed-to-seed
    noise floor, measured in tests/test_oracle.py)."""
    import jax.numpy as jnp

    from onix.models.scoring import score_events

    corpus, _, _ = synthetic_lda_corpus(30, 40, 3, mean_doc_len=20, seed=1)
    cfg = LDAConfig(n_topics=3, n_sweeps=6, burn_in=3, block_size=256,
                    seed=0, n_chains=3)
    fit = GibbsLDA(cfg, corpus.n_docs, corpus.n_vocab).fit(corpus)
    theta, phi_wk = fit["theta"], fit["phi_wk"]
    assert theta.shape == (3, corpus.n_docs, 3)
    assert phi_wk.shape == (3, corpus.n_vocab, 3)
    np.testing.assert_allclose(theta.sum(-1), 1.0, atol=1e-4)
    np.testing.assert_allclose(phi_wk.sum(-2), 1.0, atol=1e-4)
    # chains are genuinely independent streams
    assert not np.allclose(theta[0], theta[1])

    d = jnp.asarray(corpus.doc_ids[:50])
    w = jnp.asarray(corpus.word_ids[:50])
    avg = np.asarray(score_events(jnp.asarray(theta), jnp.asarray(phi_wk),
                                  d, w))
    per_chain = np.stack([
        np.asarray(score_events(jnp.asarray(theta[c]),
                                jnp.asarray(phi_wk[c]), d, w))
        for c in range(3)])
    # Geometric mean over chains (rank-stable for the suspicious tail;
    # see score_events docstring + docs/OVERLAP.md).
    geo = np.exp(np.log(np.maximum(per_chain, 1e-38)).mean(0))
    np.testing.assert_allclose(avg, geo, rtol=1e-5)


def test_multi_chain_deterministic():
    corpus, _, _ = synthetic_lda_corpus(30, 40, 3, mean_doc_len=20, seed=1)
    cfg = LDAConfig(n_topics=3, n_sweeps=4, burn_in=2, block_size=256,
                    seed=9, n_chains=2)
    r1 = GibbsLDA(cfg, corpus.n_docs, corpus.n_vocab).fit(corpus)
    r2 = GibbsLDA(cfg, corpus.n_docs, corpus.n_vocab).fit(corpus)
    np.testing.assert_allclose(r1["phi_wk"], r2["phi_wk"], rtol=1e-6)


@pytest.mark.parametrize("n_chains", [1, 2])
def test_superstep_bit_identical_to_sequential_sweeps(n_chains):
    """The S-sweep fused superstep (one program, accumulate fold and ll
    on device) vs S sequential single-sweep dispatches: same key stream
    → same z sequence, same counts, same posterior-mean accumulators —
    including across the burn-in boundary, which the superstep decides
    from the traced sweep counter instead of a static flag."""
    from onix.models.lda_gibbs import init_chains, init_state

    corpus, _, _ = synthetic_lda_corpus(40, 50, 3, mean_doc_len=25, seed=3)
    cfg = LDAConfig(n_topics=3, n_sweeps=6, burn_in=3, block_size=256,
                    seed=5, n_chains=n_chains)
    model = GibbsLDA(cfg, corpus.n_docs, corpus.n_vocab)
    docs, words, mask = model.prepare(corpus)

    def fresh():
        if n_chains == 1:
            return init_state(docs, words, mask, corpus.n_docs,
                              corpus.n_vocab, cfg.n_topics, cfg.seed)
        return init_chains(docs, words, mask, corpus.n_docs,
                           corpus.n_vocab, cfg.n_topics, cfg.seed,
                           n_chains)

    seq = fresh()
    for s in range(cfg.n_sweeps):
        seq = model._sweep(seq, docs, words, mask,
                           accumulate=s >= cfg.burn_in)

    fused, ll = model._superstep(fresh(), docs, words, mask, 0,
                                 n_steps=cfg.n_sweeps)
    for name in seq._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(seq, name)),
            np.asarray(getattr(fused, name)),
            err_msg=f"{name} diverged between fused and sequential")
    assert np.isfinite(float(ll))

    # Segmentation independence: two supersteps of 3 land on the same
    # state as one of 6 (resume boundaries can fall anywhere).
    half, _ = model._superstep(fresh(), docs, words, mask, 0, n_steps=3)
    half, _ = model._superstep(half, docs, words, mask, 3, n_steps=3)
    for name in seq._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(seq, name)),
            np.asarray(getattr(half, name)),
            err_msg=f"{name} diverged across superstep segmentation")


def test_fit_ll_history_lands_on_superstep_boundaries():
    """ll_history semantics survive the fused loop: the pre-sweep point,
    then one entry per superstep boundary, final sweep always last —
    the auto size (10) reproduces the old every-10-sweeps cadence."""
    corpus, _, _ = synthetic_lda_corpus(30, 40, 3, mean_doc_len=20, seed=1)
    cfg = LDAConfig(n_topics=3, n_sweeps=12, burn_in=6, block_size=256,
                    seed=2, superstep=4)
    fit = GibbsLDA(cfg, corpus.n_docs, corpus.n_vocab).fit(corpus)
    sweeps = [s for s, _ in fit["ll_history"]]
    assert sweeps == [-1, 3, 7, 11]
    assert all(np.isfinite(ll) for _, ll in fit["ll_history"])


# The product vocabulary's width, a tiny vocabulary over several
# blocks, and a block that is no multiple of 8; both draw forms: the
# race is what CPU runs, the Gumbel-argmax what the chip runs.
@pytest.mark.parametrize(
    "n_docs,n_vocab,k,block",
    [(150, 512, 20, 640), (60, 40, 4, 256), (50, 64, 5, 1000)])
@pytest.mark.parametrize("sampler", ["race", "gumbel"])
def test_nwk_matmul_form_bit_identical(n_docs, n_vocab, k, block, sampler):
    """The MXU one-hot-matmul n_wk delta (the form the chip runs) must
    equal the scatter form (the form tier-1 runs) bit for bit over full
    sweeps: it is exact integer math in f32 (lda_gibbs module comment
    at _NWK_MATMUL_MAX_V)."""
    import jax

    from onix.models.lda_gibbs import init_state, make_block_step

    corpus, _, _ = synthetic_lda_corpus(n_docs, n_vocab, min(k, 5),
                                        mean_doc_len=30, seed=2)
    cfg = LDAConfig(n_topics=k, n_sweeps=3, block_size=block, seed=1)
    model = GibbsLDA(cfg, corpus.n_docs, corpus.n_vocab)
    docs, words, mask = model.prepare(corpus)
    states = {}
    for form in ("scatter", "matmul"):
        step = make_block_step(alpha=cfg.alpha, eta=cfg.eta,
                               n_vocab=corpus.n_vocab, k_topics=k,
                               nwk_form=form, sampler=sampler)
        st = init_state(docs, words, mask, corpus.n_docs, corpus.n_vocab,
                        k, cfg.seed)
        carry = (st.n_dk, st.n_wk, st.n_k, st.key)
        z = st.z
        for _ in range(cfg.n_sweeps):
            carry, z = jax.lax.scan(step, carry, (docs, words, mask, z))
        states[form] = (np.asarray(carry[0]), np.asarray(carry[1]),
                        np.asarray(carry[2]), np.asarray(z))
    for name, a, b in zip(("n_dk", "n_wk", "n_k", "z"),
                          states["scatter"], states["matmul"]):
        np.testing.assert_array_equal(a, b, err_msg=name)
    # Count-table invariants hold for the matmul form.
    n_dk, n_wk, n_k, _ = states["matmul"]
    assert n_wk.sum() == int(np.asarray(mask).sum())
    np.testing.assert_array_equal(n_wk.sum(axis=0), n_k)
