"""The r11 sparse O(K_active) Gibbs arm (ISSUE 6 tentpole).

Contract (the r8 gate-arm discipline): the sparse arm is a DIFFERENT
chain with the SAME stationary distribution as the dense block sampler
— MH acceptance against the fresh blocked target makes it exact — so
the tests assert winner-parity / perplexity-band / count invariants
across shapes and engines, plus bit-reproducibility properties WITHIN
the arm (determinism, superstep S-invariance, resume refusal across an
arm change). The F+-tree-style CDF bisection and the MH correction get
their own property tests at the bottom.
"""

import numpy as np
import pytest

from onix.config import LDAConfig
from onix.corpus import synthetic_lda_corpus
from onix.models.lda_gibbs import (GibbsLDA, build_sparse_tables,
                                   cdf_lower_bound, init_state,
                                   make_sparse_block_step,
                                   resolve_sparse_active,
                                   sampler_fingerprint,
                                   select_sampler_form)
from tests.test_gibbs import _topic_alignment_similarity


# -- the gate ---------------------------------------------------------------

def test_select_sampler_form_priorities(monkeypatch):
    # Explicit form outranks everything.
    assert select_sampler_form(backend="cpu", k_topics=4,
                               sampler_form="sparse") == "sparse"
    assert select_sampler_form(backend="tpu", k_topics=4096,
                               sampler_form="dense") == "dense"
    with pytest.raises(ValueError):
        select_sampler_form(backend="cpu", k_topics=4, sampler_form="alias")
    # Measured-platforms-only: unmeasured backends stay dense at any K.
    assert select_sampler_form(backend="tpu", k_topics=4096) == "dense"
    assert select_sampler_form(backend="gpu", k_topics=4096) == "dense"
    # The measured cpu crossover engages above its K, not below.
    from onix.models.lda_gibbs import _SAMPLER_SPARSE_MIN_K
    min_k = _SAMPLER_SPARSE_MIN_K["cpu"]
    assert select_sampler_form(backend="cpu", k_topics=int(min_k)) == "sparse"
    assert select_sampler_form(backend="cpu",
                               k_topics=int(min_k) - 1) == "dense"
    # The judged K=20 pipelines sit under the crossover: defaults hold.
    assert select_sampler_form(backend="cpu", k_topics=20) == "dense"


def test_sampler_resolution_order(monkeypatch):
    """The sampler resolution's order: the config field, then
    ONIX_SAMPLER_FORM, then the measured table - and nothing else (an
    n_wk experiment used to hold the auto gate on dense; there is no
    such pin any more)."""
    from onix.models.lda_gibbs import resolve_sampler
    monkeypatch.delenv("ONIX_SAMPLER_FORM", raising=False)
    cfg = LDAConfig(n_topics=64)
    # The table alone: sparse from the cpu crossover up, dense below.
    assert resolve_sampler(cfg, k_topics=64)[0] == "sparse"
    assert resolve_sampler(LDAConfig(n_topics=20), k_topics=20)[0] == "dense"
    # The environment outranks the table, in both directions.
    monkeypatch.setenv("ONIX_SAMPLER_FORM", "dense")
    assert resolve_sampler(cfg, k_topics=64)[0] == "dense"
    monkeypatch.setenv("ONIX_SAMPLER_FORM", "sparse")
    assert resolve_sampler(LDAConfig(n_topics=20), k_topics=20)[0] == "sparse"
    # The config field outranks the environment.
    cfg_d = LDAConfig(n_topics=64, sampler_form="dense")
    assert resolve_sampler(cfg_d, k_topics=64)[0] == "dense"
    monkeypatch.setenv("ONIX_SAMPLER_FORM", "dense")
    cfg_s = LDAConfig(n_topics=20, sampler_form="sparse")
    assert resolve_sampler(cfg_s, k_topics=20)[0] == "sparse"
    # Both engines ride the same resolver.
    monkeypatch.delenv("ONIX_SAMPLER_FORM")
    assert GibbsLDA(cfg, 50, 40).sampler_form == "sparse"
    assert GibbsLDA(cfg_d, 50, 40).sampler_form == "dense"


def test_env_sampler_form_override(monkeypatch):
    from onix.models.lda_gibbs import env_sampler_form
    monkeypatch.delenv("ONIX_SAMPLER_FORM", raising=False)
    assert env_sampler_form() is None
    monkeypatch.setenv("ONIX_SAMPLER_FORM", "auto")
    assert env_sampler_form() is None
    monkeypatch.setenv("ONIX_SAMPLER_FORM", "sparse")
    assert env_sampler_form() == "sparse"
    # The engine consumes the env at construction and pins the
    # resolved form (fingerprint and program must agree).
    cfg = LDAConfig(n_topics=4, n_sweeps=2, block_size=128)
    assert GibbsLDA(cfg, 10, 20).sampler_form == "sparse"


def test_sweep_kernel_resolves_like_the_engines(monkeypatch):
    """make_sweep_kernel is reachable by standalone callers that never
    go through resolve_sampler, so it must apply the SAME order:
    its argument, then ONIX_SAMPLER_FORM, then the measured gate
    untouched - the tests' n_wk pin does not hold it on dense."""
    from onix.models import lda_gibbs

    seen = {}
    real = lda_gibbs.select_sampler_form

    def spy(**kw):
        seen["sampler_form"] = kw.get("sampler_form")
        return real(**kw)

    monkeypatch.setattr(lda_gibbs, "select_sampler_form", spy)
    monkeypatch.setenv("ONIX_SAMPLER_FORM", "dense")
    lda_gibbs.make_sweep_kernel(alpha=0.5, eta=0.01, n_vocab=16,
                                k_topics=64)
    assert seen["sampler_form"] == "dense"
    lda_gibbs.make_sweep_kernel(alpha=0.5, eta=0.01, n_vocab=16,
                                k_topics=64, sampler_form="sparse")
    assert seen["sampler_form"] == "sparse"
    monkeypatch.delenv("ONIX_SAMPLER_FORM")
    lda_gibbs.make_sweep_kernel(alpha=0.5, eta=0.01, n_vocab=16,
                                k_topics=64, nwk_form="scatter")
    assert seen["sampler_form"] is None


def test_resolve_sparse_active_auto_tracks_k():
    assert resolve_sparse_active(16) == 8       # floor
    assert resolve_sparse_active(256) == 16     # K/16
    assert resolve_sparse_active(1024) == 64
    assert resolve_sparse_active(4) == 4        # capped at K
    assert resolve_sparse_active(256, 32) == 32  # explicit
    assert resolve_sparse_active(8, 32) == 8     # explicit, capped


def test_config_validates_sampler_fields():
    with pytest.raises(ValueError):
        LDAConfig(sampler_form="alias").validate()
    with pytest.raises(ValueError):
        LDAConfig(sparse_mh=0).validate()
    with pytest.raises(ValueError):
        LDAConfig(sparse_active=-1).validate()
    LDAConfig(sampler_form="sparse", sparse_active=8,
              sparse_mh=4).validate()


# -- K-sweep parity / perplexity band --------------------------------------

@pytest.fixture(scope="module")
def ksweep_corpus():
    return synthetic_lda_corpus(n_docs=120, n_vocab=100, n_topics=8,
                                mean_doc_len=60, alpha=0.2, eta=0.05,
                                seed=0)


@pytest.mark.parametrize("k,active", [(4, 2), (8, 4), (16, 4)])
def test_ksweep_perplexity_band_and_invariants(ksweep_corpus, k, active):
    """Across K (with A truncated BELOW the true occupancy at the
    larger shapes, so the dense-phi MH branch is genuinely load-
    bearing): the sparse arm's converged ll must land in the dense
    arm's band, counts must stay exact, and both must improve from
    init — the perplexity-band half of the gate-arm contract."""
    corpus, _, _ = ksweep_corpus
    results = {}
    for form in ("dense", "sparse"):
        cfg = LDAConfig(n_topics=k, alpha=0.3, eta=0.05, n_sweeps=30,
                        burn_in=15, block_size=1024, seed=0,
                        sampler_form=form, sparse_active=active)
        r = GibbsLDA(cfg, corpus.n_docs, corpus.n_vocab).fit(corpus)
        st = r["state"]
        assert int(np.asarray(st.n_k).sum()) == corpus.n_tokens
        assert np.asarray(st.n_dk).min() >= 0
        assert np.asarray(st.n_wk).min() >= 0
        np.testing.assert_array_equal(np.asarray(st.n_dk).sum(axis=1),
                                      corpus.doc_lengths())
        np.testing.assert_array_equal(np.asarray(st.n_wk).sum(axis=0),
                                      np.asarray(st.n_k))
        lls = [ll for _, ll in r["ll_history"]]
        assert lls[-1] > lls[0] + 0.1
        results[form] = lls[-1]
    band = 0.05 * abs(results["dense"])
    assert abs(results["sparse"] - results["dense"]) < band, results


def test_sparse_topic_recovery_winner_parity(ksweep_corpus):
    """Winner-parity at the model level: the sparse arm must recover
    the planted topics as well as the dense arm does (within a small
    tolerance), under a truncated active set."""
    corpus, _, phi_true = ksweep_corpus
    sims = {}
    for form in ("dense", "sparse"):
        cfg = LDAConfig(n_topics=8, alpha=0.3, eta=0.05, n_sweeps=40,
                        burn_in=20, block_size=1024, seed=0,
                        sampler_form=form, sparse_active=4)
        r = GibbsLDA(cfg, corpus.n_docs, corpus.n_vocab).fit(corpus)
        sims[form] = _topic_alignment_similarity(phi_true,
                                                 r["phi_wk"].T)
    assert sims["sparse"] > 0.85, sims
    assert sims["sparse"] > sims["dense"] - 0.05, sims


def test_sparse_deterministic():
    corpus, _, _ = synthetic_lda_corpus(30, 40, 3, mean_doc_len=20, seed=1)
    cfg = LDAConfig(n_topics=3, n_sweeps=5, burn_in=2, block_size=256,
                    seed=9, sampler_form="sparse", sparse_active=2)
    r1 = GibbsLDA(cfg, corpus.n_docs, corpus.n_vocab).fit(corpus)
    r2 = GibbsLDA(cfg, corpus.n_docs, corpus.n_vocab).fit(corpus)
    np.testing.assert_array_equal(np.asarray(r1["state"].z),
                                  np.asarray(r2["state"].z))
    np.testing.assert_allclose(r1["phi_wk"], r2["phi_wk"], rtol=1e-6)


@pytest.mark.parametrize("n_chains", [1, 2])
def test_sparse_superstep_bit_identical_to_sequential(n_chains):
    """WITHIN the sparse arm the r7 superstep contract holds exactly:
    S fused sweeps == S sequential dispatches, bit for bit, across the
    burn-in boundary and any segmentation — the stale proposal tables
    are rebuilt per SWEEP inside the fused program, so the chain is
    independent of the superstep size."""
    from onix.models.lda_gibbs import init_chains

    corpus, _, _ = synthetic_lda_corpus(40, 50, 3, mean_doc_len=25, seed=3)
    cfg = LDAConfig(n_topics=3, n_sweeps=6, burn_in=3, block_size=256,
                    seed=5, n_chains=n_chains, sampler_form="sparse",
                    sparse_active=2)
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
            np.asarray(getattr(fused, name)), err_msg=name)
    assert np.isfinite(float(ll))
    half, _ = model._superstep(fresh(), docs, words, mask, 0, n_steps=2)
    half, _ = model._superstep(half, docs, words, mask, 2, n_steps=4)
    for name in seq._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(seq, name)),
            np.asarray(getattr(half, name)), err_msg=name)


# -- sharded engine ---------------------------------------------------------

@pytest.mark.parametrize("dp,mp", [(1, 1), (2, 1), (2, 2)])
def test_sparse_sharded_invariants(dp, mp, eight_devices):
    """The sparse arm through ShardedGibbsLDA: dp=1 rides the fast
    path (no shard_map), dp=2 the psum sweep, dp=2 x mp=2 the chunked
    vocabulary — local stale tables per shard. Counts stay exact and
    the fit improves on every mesh."""
    from onix.parallel.mesh import make_mesh
    from onix.parallel.sharded_gibbs import ShardedGibbsLDA

    corpus, _, _ = synthetic_lda_corpus(60, 48, 4, mean_doc_len=30,
                                        seed=2)
    cfg = LDAConfig(n_topics=4, n_sweeps=12, burn_in=6, block_size=256,
                    seed=0, sampler_form="sparse", sparse_active=2)
    model = ShardedGibbsLDA(cfg, corpus.n_vocab,
                            mesh=make_mesh(dp=dp, mp=mp))
    assert model.sampler_form == "sparse"
    r = model.fit(corpus)
    st = r["state"]
    assert int(np.asarray(st.n_k).sum()) == corpus.n_tokens
    assert np.asarray(st.n_dk).min() >= 0
    assert np.asarray(st.n_wk).min() >= 0
    lls = [ll for _, ll in r["ll_history"]]
    assert lls[-1] > lls[0]
    theta, phi_wk = r["theta"], r["phi_wk"]
    np.testing.assert_allclose(theta.sum(-1), 1.0, atol=1e-4)
    np.testing.assert_allclose(phi_wk.sum(-2), 1.0, atol=1e-4)


def test_sparse_dp1_fast_matches_shardmap(eight_devices, monkeypatch):
    """dp=1 fast path vs the pinned shard_map form, sparse arm: the
    same bit-identity the dense arm has (ONIX_DP1_FAST=0 pins the
    wrapped form; both run the same sweep kernel)."""
    from onix.parallel.mesh import make_mesh
    from onix.parallel.sharded_gibbs import ShardedGibbsLDA

    corpus, _, _ = synthetic_lda_corpus(40, 40, 3, mean_doc_len=20,
                                        seed=4)
    cfg = LDAConfig(n_topics=3, n_sweeps=6, burn_in=3, block_size=256,
                    seed=1, sampler_form="sparse", sparse_active=2)
    monkeypatch.setenv("ONIX_DP1_FAST", "1")
    fast = ShardedGibbsLDA(cfg, corpus.n_vocab, mesh=make_mesh(dp=1))
    assert fast.dp1_fast
    r_fast = fast.fit(corpus)
    monkeypatch.setenv("ONIX_DP1_FAST", "0")
    wrapped = ShardedGibbsLDA(cfg, corpus.n_vocab, mesh=make_mesh(dp=1))
    assert not wrapped.dp1_fast
    r_wrap = wrapped.fit(corpus)
    for name in ("z", "n_dk", "n_wk", "n_k"):
        np.testing.assert_array_equal(
            np.asarray(getattr(r_fast["state"], name)),
            np.asarray(getattr(r_wrap["state"], name)), err_msg=name)


# -- resume-across-arm-change refusal ---------------------------------------

def test_resume_across_arm_change_refused(tmp_path):
    """A checkpointed dense run must NOT be resumed by a sparse-arm
    engine (different chain): the resolved form is part of the
    fingerprint, so the sparse run starts fresh — its ll_history
    restarts at the pre-sweep point instead of adopting the dense
    chain's counts."""
    corpus, _, _ = synthetic_lda_corpus(30, 40, 3, mean_doc_len=20,
                                        seed=1)
    base = dict(n_topics=3, n_sweeps=6, burn_in=3, block_size=256,
                seed=0, checkpoint_every=2, superstep=2)
    dense_cfg = LDAConfig(**base, sampler_form="dense")
    r1 = GibbsLDA(dense_cfg, corpus.n_docs, corpus.n_vocab).fit(
        corpus, checkpoint_dir=tmp_path)
    assert r1["ll_history"][0][0] == -1
    # Same dir, arm changed: fingerprint differs -> no adoption.
    sparse_cfg = LDAConfig(**base, sampler_form="sparse",
                           sparse_active=2)
    r2 = GibbsLDA(sparse_cfg, corpus.n_docs, corpus.n_vocab).fit(
        corpus, checkpoint_dir=tmp_path)
    assert r2["ll_history"][0][0] == -1, (
        "sparse engine adopted a dense-arm checkpoint")
    # Same arm DOES resume (nothing left to sweep -> single ll entry).
    r3 = GibbsLDA(sparse_cfg, corpus.n_docs, corpus.n_vocab).fit(
        corpus, checkpoint_dir=tmp_path)
    assert r3["ll_history"][0][0] == base["n_sweeps"] - 1
    # And the fingerprint extras actually differ.
    assert (sampler_fingerprint("dense", 2, 2)
            != sampler_fingerprint("sparse", 2, 2))


# -- proposal-table properties ----------------------------------------------
#
# The hypothesis-driven versions of these properties live in
# tests/test_sparse_properties.py (skipped where hypothesis is absent,
# like test_properties.py); the seeded sweeps below exercise the same
# invariants unconditionally so the tier-1 suite never runs blind.


def test_cdf_lower_bound_matches_searchsorted_seeded():
    """The F+-tree-style bisection must agree with np.searchsorted
    lower_bound on every CDF and every draw point — the deterministic
    half of 'table draws match exact categorical probabilities'.
    Seeded sweep over widths incl. non-pow2 and k=1 edge cases."""
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    for k in (1, 2, 3, 5, 8, 13, 16, 24, 256):
        for _ in range(8):
            w = rng.random(k).astype(np.float32) + 1e-4
            cdf = np.cumsum(w)
            t = (rng.random(64) * cdf[-1]).astype(np.float32)
            got = np.asarray(cdf_lower_bound(jnp.asarray(cdf),
                                             jnp.zeros(64, jnp.int32),
                                             jnp.asarray(t), k))
            want = np.searchsorted(cdf, t, side="left")
            np.testing.assert_array_equal(got, want, err_msg=f"k={k}")


def test_cdf_draws_match_categorical_probabilities_seeded():
    """Stratified draws through the CDF table reproduce the exact
    categorical distribution: with an evenly-spaced grid of draw
    points, each topic's hit count equals its probability mass to
    within one grid cell."""
    import jax.numpy as jnp
    rng = np.random.default_rng(3)
    n = 4096
    for k in (2, 7, 16):
        w = (rng.random(k) * 100 + 1e-3)
        cdf = np.cumsum(w).astype(np.float32)
        t = ((np.arange(n) + 0.5) / n * cdf[-1]).astype(np.float32)
        idx = np.asarray(cdf_lower_bound(jnp.asarray(cdf),
                                         jnp.zeros(n, jnp.int32),
                                         jnp.asarray(t), k))
        idx = np.minimum(idx, k - 1)
        freq = np.bincount(idx, minlength=k) / n
        p = w / w.sum()
        assert np.abs(freq - p).max() <= 2.0 / n + 1e-3


def test_mh_chain_matches_exact_blocked_conditional():
    """The MH-corrected half: a long proposal chain on one token must
    converge to the EXACT blocked conditional (counts excluding self)
    — the stationary-distribution argument of docs/PERF.md, measured.
    Truncated active set (A=3 < K=8) so the dense-phi branch and the
    acceptance ratio both carry real weight."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    K, V, D = 8, 12, 6
    n_dk = jnp.asarray(rng.integers(0, 10, (D, K)).astype(np.int32))
    n_wk = jnp.asarray(rng.integers(0, 6, (V, K)).astype(np.int32))
    n_k = n_wk.sum(axis=0)
    alpha, eta = 0.4, 0.05
    v_eta = V * eta
    d0, w0, z0 = 2, 5, 1
    nd = np.asarray(n_dk)[d0].astype(np.float64)
    nw = np.asarray(n_wk)[w0].astype(np.float64)
    nk = np.asarray(n_k).astype(np.float64)
    e = np.zeros(K)
    e[z0] = 1
    p = ((nd - e + alpha) * np.maximum(nw - e + eta, 1e-10)
         / (nk - e + v_eta))
    p /= p.sum()
    tables = build_sparse_tables(n_dk, n_wk, n_k, eta=eta, v_eta=v_eta,
                                 n_active=3)
    step = make_sparse_block_step(alpha=alpha, eta=eta, v_eta=v_eta,
                                  k_topics=K, n_mh=64, tables=tables)

    @jax.jit
    def draw(key):
        carry = (n_dk, n_wk, n_k, key)
        xs = (jnp.full((1,), d0, jnp.int32),
              jnp.full((1,), w0, jnp.int32),
              jnp.ones((1,), jnp.float32),
              jnp.full((1,), z0, jnp.int32))
        _, z = step(carry, xs)
        return z[0]

    keys = jax.random.split(jax.random.PRNGKey(7), 12000)
    zs = np.asarray(jax.vmap(draw)(keys))
    freq = np.bincount(zs, minlength=K) / len(zs)
    assert np.abs(freq - p).max() < 0.02, (freq, p)


def test_sparse_padding_blocks_untouched():
    """All-padding blocks (z == K sentinel) must leave every count
    unchanged — the rank-1 scatters drop out-of-bounds updates."""
    import jax
    import jax.numpy as jnp

    K, V, D, B = 4, 10, 5, 16
    rng = np.random.default_rng(1)
    n_dk = jnp.asarray(rng.integers(0, 5, (D, K)).astype(np.int32))
    n_wk = jnp.asarray(rng.integers(0, 5, (V, K)).astype(np.int32))
    n_k = n_wk.sum(axis=0)
    tables = build_sparse_tables(n_dk, n_wk, n_k, eta=0.05,
                                 v_eta=10 * 0.05, n_active=2)
    step = make_sparse_block_step(alpha=0.3, eta=0.05, v_eta=0.5,
                                  k_topics=K, n_mh=2, tables=tables)
    carry = (n_dk, n_wk, n_k, jax.random.PRNGKey(0))
    xs = (jnp.zeros(B, jnp.int32), jnp.zeros(B, jnp.int32),
          jnp.zeros(B, jnp.float32), jnp.full(B, K, jnp.int32))
    (ndk2, nwk2, nk2, _), z = jax.jit(step)(carry, xs)
    np.testing.assert_array_equal(np.asarray(z), K)
    np.testing.assert_array_equal(np.asarray(ndk2), np.asarray(n_dk))
    np.testing.assert_array_equal(np.asarray(nwk2), np.asarray(n_wk))
    np.testing.assert_array_equal(np.asarray(nk2), np.asarray(n_k))
