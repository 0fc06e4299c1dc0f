"""Pallas fused sample+count block step: gate decision table + interpret-
mode bit-identity against the reference scatter block step (ISSUE 3).

The kernel's whole contract is BIT-identity — same z sequence, same
n_wk/n_dk/n_k counts, same posterior-mean accumulators, same key stream
— so every test here is assert_array_equal, never allclose. On CPU the
kernel runs in interpret mode (plain XLA lowering of the same kernel
code); the compiled-Mosaic identity runs are the `tpu`-marked tests at
the bottom (ONIX_TPU_TESTS=1 python -m pytest -m tpu, on the chip).
"""

import numpy as np
import pytest

from onix.config import LDAConfig
from onix.corpus import synthetic_lda_corpus
from onix.models.lda_gibbs import (_NWK_MATMUL_MAX_ELEMS, _NWK_MATMUL_MAX_V,
                                   GibbsLDA, init_state, make_block_step,
                                   select_nwk_form)


# ---------------------------------------------------------------------------
# The decision gate (select_nwk_form): edge cases of the collision-
# density tables. density = block_size / n_rows.
# ---------------------------------------------------------------------------

def test_gate_cpu_always_scatters():
    # CPU has no density entry: the matmul form measured ~2x SLOWER at
    # the densest judged shape (docs/PERF.md r7) — scatter at EVERY
    # density, including absurd ones.
    for block in (0, 1, 512, 1 << 17, 1 << 20):
        assert select_nwk_form(backend="cpu", block_size=block,
                               n_rows=512) == "scatter"
    assert select_nwk_form(backend="cpu", block_size=1 << 17,
                           n_rows=1) == "scatter"


def test_gate_tpu_crossover_is_inclusive():
    # Density exactly AT the measured crossover (32) engages; one token
    # below stays on the scatter.
    v = 512
    assert select_nwk_form(backend="tpu", block_size=32 * v,
                           n_rows=v) == "matmul"
    assert select_nwk_form(backend="tpu", block_size=32 * v - 1,
                           n_rows=v) == "scatter"


def test_gate_v1_degenerate():
    # V=1 (every token the same word — a degenerate product vocabulary)
    # is maximal collision density; the gate must not divide by V or
    # misclassify. 32 tokens reach density 32.
    assert select_nwk_form(backend="tpu", block_size=32,
                           n_rows=1) == "matmul"
    assert select_nwk_form(backend="tpu", block_size=31,
                           n_rows=1) == "scatter"


def test_gate_empty_block():
    # A zero-token block has density 0 on every table: scatter, and no
    # crash.
    assert select_nwk_form(backend="tpu", block_size=0,
                           n_rows=512) == "scatter"


def test_gate_memory_and_exactness_caps():
    # Table wider than the one-hot cap: scatter even when dense.
    assert select_nwk_form(backend="tpu", block_size=1 << 20,
                           n_rows=_NWK_MATMUL_MAX_V * 2) == "scatter"
    # [B, V] one-hot temporary above the elems bound: scatter.
    b, v = 1 << 17, 4096
    assert b * v > _NWK_MATMUL_MAX_ELEMS
    assert select_nwk_form(backend="tpu", block_size=b,
                           n_rows=v) == "scatter"


def test_gate_explicit_forms_win():
    # nwk_form pins the form regardless of backend/density; the legacy
    # nwk_matmul bool keeps working; bad names are rejected.
    assert select_nwk_form(backend="cpu", block_size=4, n_rows=512,
                           nwk_form="pallas") == "pallas"
    assert select_nwk_form(backend="tpu", block_size=1 << 17, n_rows=512,
                           nwk_form="scatter") == "scatter"
    assert select_nwk_form(backend="tpu", block_size=1 << 17, n_rows=512,
                           nwk_matmul=False) == "scatter"
    assert select_nwk_form(backend="cpu", block_size=4, n_rows=512,
                           nwk_matmul=True) == "matmul"
    with pytest.raises(ValueError, match="nwk_form"):
        select_nwk_form(backend="cpu", block_size=4, n_rows=512,
                        nwk_form="mxu")


# ---------------------------------------------------------------------------
# Interpret-mode bit-identity of the kernel vs the reference block step.
# ---------------------------------------------------------------------------

def _run_raw_sweeps(step, st, docs, words, mask, n_sweeps):
    import jax

    carry = (st.n_dk, st.n_wk, st.n_k, st.key)
    z = st.z
    for _ in range(n_sweeps):
        carry, z = jax.jit(lambda c, z: jax.lax.scan(
            step, c, (docs, words, mask, z)))(carry, z)
    return tuple(np.asarray(a) for a in carry[:3]) + (np.asarray(z),)


# >= 3 shapes (ISSUE 3 acceptance): the judged product-vocab width
# V=512, a tiny vocabulary, and a block size that is NOT a multiple of
# the kernel tile (exercises the in-kernel padding path).
@pytest.mark.parametrize(
    "n_docs,n_vocab,k,block",
    [(150, 512, 20, 640),      # product vocabulary, tile 1024 > block
     (60, 40, 4, 256),         # tiny V, multi-block sweep
     (50, 64, 5, 1000)])       # 1000 % 8 != 0: forces tile padding
@pytest.mark.parametrize("sampler", ["race", "gumbel"])
def test_pallas_bit_identical_to_scatter(n_docs, n_vocab, k, block,
                                         sampler):
    """Full sweeps through make_block_step at both sampler forms: the
    race (the CPU default) AND the Gumbel-argmax (the TPU default,
    forced here so CPU tier-1 certifies the exact math the compiled
    kernel will run)."""
    corpus, _, _ = synthetic_lda_corpus(n_docs, n_vocab, min(k, 5),
                                        mean_doc_len=30, seed=2)
    cfg = LDAConfig(n_topics=k, n_sweeps=3, block_size=block, seed=1)
    model = GibbsLDA(cfg, corpus.n_docs, corpus.n_vocab)
    docs, words, mask = model.prepare(corpus)
    results = {}
    for form in ("scatter", "pallas"):
        step = make_block_step(alpha=cfg.alpha, eta=cfg.eta,
                               n_vocab=corpus.n_vocab, k_topics=k,
                               nwk_form=form, sampler=sampler)
        st = init_state(docs, words, mask, corpus.n_docs, corpus.n_vocab,
                        k, cfg.seed)
        results[form] = _run_raw_sweeps(step, st, docs, words, mask,
                                        cfg.n_sweeps)
    for name, a, b in zip(("n_dk", "n_wk", "n_k", "z"),
                          results["scatter"], results["pallas"]):
        np.testing.assert_array_equal(a, b, err_msg=name)
    # Count-table invariants hold for the kernel form.
    n_dk, n_wk, n_k, _ = results["pallas"]
    assert n_wk.sum() == int(np.asarray(mask).sum())
    np.testing.assert_array_equal(n_wk.sum(axis=0), n_k)


def test_pallas_v1_and_all_padding_block():
    """Degenerate shapes through the kernel itself: V=1 (every token
    hits one count row — maximal collision density) and a corpus whose
    final block is ENTIRELY padding (mask 0, sentinel assignments)."""
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    n_docs, k, block = 20, 3, 64
    n_tokens = 70                       # fills block 1 + 6 of block 2
    d = rng.integers(0, n_docs, n_tokens).astype(np.int32)
    w = np.zeros(n_tokens, np.int32)    # V=1
    docs = np.zeros((3, block), np.int32)
    words = np.zeros((3, block), np.int32)
    mask = np.zeros((3, block), np.float32)
    docs.reshape(-1)[:n_tokens] = d
    words.reshape(-1)[:n_tokens] = w
    mask.reshape(-1)[:n_tokens] = 1.0   # block 3 of 3: all padding
    docs, words, mask = (jnp.asarray(docs), jnp.asarray(words),
                         jnp.asarray(mask))
    results = {}
    for form in ("scatter", "pallas"):
        step = make_block_step(alpha=1.2, eta=0.01, n_vocab=1, k_topics=k,
                               nwk_form=form)
        st = init_state(docs, words, mask, n_docs, 1, k, seed=7)
        results[form] = _run_raw_sweeps(step, st, docs, words, mask, 2)
    for name, a, b in zip(("n_dk", "n_wk", "n_k", "z"),
                          results["scatter"], results["pallas"]):
        np.testing.assert_array_equal(a, b, err_msg=name)
    assert results["pallas"][1].sum() == n_tokens    # n_wk total


# ---------------------------------------------------------------------------
# Engine integration: the kernel must compose with the fused superstep
# fit loop, the chain vmap, and both sharded paths (ISSUE 3 tentpole).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_chains", [1, 2])
def test_gibbs_lda_fit_pallas_bit_identical(n_chains):
    corpus, _, _ = synthetic_lda_corpus(40, 50, 3, mean_doc_len=25, seed=3)
    fits = {}
    for form in ("scatter", "pallas"):
        cfg = LDAConfig(n_topics=3, n_sweeps=6, burn_in=3, block_size=256,
                        seed=5, n_chains=n_chains, nwk_form=form)
        fits[form] = GibbsLDA(cfg, corpus.n_docs, corpus.n_vocab).fit(corpus)
    for name in fits["scatter"]["state"]._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(fits["scatter"]["state"], name)),
            np.asarray(getattr(fits["pallas"]["state"], name)),
            err_msg=f"{name} diverged between scatter and pallas fits")
    assert fits["scatter"]["ll_history"] == fits["pallas"]["ll_history"]


@pytest.mark.parametrize("dp,mp", [(1, 1), (2, 1), (2, 2)])
def test_sharded_fit_pallas_bit_identical(eight_devices, dp, mp):
    """dp=1 exercises the fast path (no shard_map); dp=2 and dp=2/mp=2
    run the kernel INSIDE the shard region (replication check dropped —
    sharded_gibbs sweep_smap_kw)."""
    import jax

    from onix.parallel.mesh import make_mesh
    from onix.parallel.sharded_gibbs import ShardedGibbsLDA

    corpus, _, _ = synthetic_lda_corpus(40, 50, 3, mean_doc_len=25, seed=3)
    fits = {}
    for form in ("scatter", "pallas"):
        cfg = LDAConfig(n_topics=3, n_sweeps=4, burn_in=2, block_size=128,
                        seed=5, nwk_form=form)
        model = ShardedGibbsLDA(
            cfg, corpus.n_vocab,
            mesh=make_mesh(dp=dp, mp=mp, devices=jax.devices()[:dp * mp]))
        fits[form] = model.fit(corpus)
    for name in ("z", "n_dk", "n_wk", "n_k", "acc_ndk", "acc_nwk"):
        np.testing.assert_array_equal(
            np.asarray(getattr(fits["scatter"]["state"], name)),
            np.asarray(getattr(fits["pallas"]["state"], name)),
            err_msg=f"{name} diverged at dp={dp} mp={mp}")


@pytest.mark.tpu
def test_pallas_compiled_bit_identical_on_tpu():
    """Compiled-Mosaic identity: the same assertion as the interpret
    tests, on a real TPU where the kernel compiles instead of
    emulating. Auto-skipped off-TPU (conftest `tpu` marker hook)."""
    corpus, _, _ = synthetic_lda_corpus(150, 512, 5, mean_doc_len=40,
                                        seed=2)
    cfg = LDAConfig(n_topics=20, n_sweeps=2, block_size=1 << 13, seed=1)
    model = GibbsLDA(cfg, corpus.n_docs, corpus.n_vocab)
    docs, words, mask = model.prepare(corpus)
    results = {}
    for form in ("scatter", "pallas"):
        step = make_block_step(alpha=cfg.alpha, eta=cfg.eta,
                               n_vocab=corpus.n_vocab,
                               k_topics=cfg.n_topics, nwk_form=form)
        st = init_state(docs, words, mask, corpus.n_docs, corpus.n_vocab,
                        cfg.n_topics, cfg.seed)
        results[form] = _run_raw_sweeps(step, st, docs, words, mask, 2)
    for name, a, b in zip(("n_dk", "n_wk", "n_k", "z"),
                          results["scatter"], results["pallas"]):
        np.testing.assert_array_equal(a, b, err_msg=name)


@pytest.mark.tpu
def test_pallas_compiled_judged_width_on_tpu():
    """The kernel through Mosaic at the JUDGED width — block 2^17,
    V=512, K=20, the shape scale.py's fit runs. Independent block steps
    from one shared state, so a mismatch cannot cascade: on the chip the
    compiled kernel draws the scatter form's z for all but ~5e-7 of the
    tokens (PR 21: 4 of 8.4M — near-tie argmax flips between Mosaic's
    and XLA's float ops; PERF.md), and wherever z agrees the count
    delta is bit-identical. Whole-sweep bit-identity therefore does NOT
    hold at this width (bench.py's gibbs_sweep_pallas asserts it and
    fails); this test pins what does."""
    import jax
    import jax.numpy as jnp

    n_docs, n_vocab, k, block, nb = 20_000, 512, 20, 1 << 17, 4
    rng = np.random.default_rng(4)
    docs = jnp.asarray(rng.integers(0, n_docs, (nb, block))
                       .astype(np.int32))
    words = jnp.asarray(rng.integers(0, n_vocab, (nb, block))
                        .astype(np.int32))
    mask = jnp.ones((nb, block), jnp.float32)
    st = init_state(docs, words, mask, n_docs, n_vocab, k, 0)
    carry = (st.n_dk, st.n_wk, st.n_k, st.key)
    out = {}
    for form in ("scatter", "pallas"):
        step = jax.jit(make_block_step(alpha=1.2, eta=0.01,
                                       n_vocab=n_vocab, k_topics=k,
                                       nwk_form=form))
        out[form] = [step(carry, (docs[b], words[b], mask[b], st.z[b]))
                     for b in range(nb)]
    n_diff = 0
    for (c_s, z_s), (c_p, z_p) in zip(out["scatter"], out["pallas"]):
        diff = int((np.asarray(z_s) != np.asarray(z_p)).sum())
        n_diff += diff
        if diff == 0:
            for name, a, b in zip(("n_dk", "n_wk", "n_k"), c_s, c_p):
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(b), err_msg=name)
        assert int(np.asarray(c_p[2]).sum()) == nb * block   # n_k total
    print(f"pallas vs scatter: {n_diff} of {nb * block} z differ")
    assert n_diff <= 1e-5 * nb * block
