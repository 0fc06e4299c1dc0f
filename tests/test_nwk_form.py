"""The n_wk count-update form: the decision (`select_nwk_form`) and the
bit-identity of its two forms through the engines.

The chip runs the one-hot matmul form and tier-1's CPU the scatter, so
the contract is BIT-identity - same z sequence, same n_wk/n_dk/n_k
counts, same posterior-mean accumulators - and every comparison here is
assert_array_equal, never allclose. The block-step grid is
tests/test_gibbs.py::test_nwk_matmul_form_bit_identical. No engine takes
a form: the engine-level cases give the running backend the chip's
entry in `_NWK_MATMUL_MIN_DENSITY`, the table the chip is read from.
"""

import numpy as np
import pytest

from onix.config import LDAConfig
from onix.corpus import synthetic_lda_corpus
from onix.models import lda_gibbs
from onix.models.lda_gibbs import (_NWK_MATMUL_MAX_ELEMS, _NWK_MATMUL_MAX_V,
                                   GibbsLDA, init_state, make_block_step,
                                   select_nwk_form)


# ---------------------------------------------------------------------------
# The decision: edge cases of the density table and the caps.
# density = block_size / n_rows.
# ---------------------------------------------------------------------------

def test_gate_cpu_always_scatters():
    # CPU has no density entry: scatter at EVERY density, including
    # absurd ones.
    for block in (0, 1, 512, 1 << 17, 1 << 20):
        assert select_nwk_form(backend="cpu", block_size=block,
                               n_rows=512) == "scatter"
    assert select_nwk_form(backend="cpu", block_size=1 << 17,
                           n_rows=1) == "scatter"


def test_gate_tpu_crossover_is_inclusive():
    # Density exactly AT the threshold (32) engages; one token below
    # stays on the scatter.
    v = 512
    assert select_nwk_form(backend="tpu", block_size=32 * v,
                           n_rows=v) == "matmul"
    assert select_nwk_form(backend="tpu", block_size=32 * v - 1,
                           n_rows=v) == "scatter"


def test_gate_v1_degenerate():
    # V=1 (every token the same word) is maximal collision density; the
    # gate must not divide by V or misclassify. 32 tokens reach 32.
    assert select_nwk_form(backend="tpu", block_size=32,
                           n_rows=1) == "matmul"
    assert select_nwk_form(backend="tpu", block_size=31,
                           n_rows=1) == "scatter"


def test_gate_empty_block():
    # A zero-token block has density 0: scatter, and no crash.
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
    # A block of 2^24 tokens leaves the f32 sum's exact range.
    assert select_nwk_form(backend="tpu", block_size=1 << 24,
                           n_rows=1) == "scatter"


def test_gate_pin_wins_and_nothing_else_is_read(monkeypatch):
    # The tests' pin decides regardless of backend and density; a form
    # that is gone, or never was, is refused; and the environment that
    # used to override the decision no longer reaches it.
    assert select_nwk_form(backend="tpu", block_size=1 << 17, n_rows=512,
                           nwk_form="scatter") == "scatter"
    assert select_nwk_form(backend="cpu", block_size=4, n_rows=512,
                           nwk_form="matmul") == "matmul"
    for gone in ("pallas", "mxu", "auto"):
        with pytest.raises(ValueError, match="nwk_form"):
            select_nwk_form(backend="cpu", block_size=4, n_rows=512,
                            nwk_form=gone)
    monkeypatch.setenv("ONIX_NWK_FORM", "matmul")
    monkeypatch.setenv("ONIX_NWK_MATMUL", "1")
    assert select_nwk_form(backend="cpu", block_size=1 << 17,
                           n_rows=512) == "scatter"
    assert not hasattr(LDAConfig(), "nwk_form")


# ---------------------------------------------------------------------------
# Bit-identity of the matmul form against the scatter form.
# ---------------------------------------------------------------------------

def _run_raw_sweeps(step, st, docs, words, mask, n_sweeps):
    import jax

    carry = (st.n_dk, st.n_wk, st.n_k, st.key)
    z = st.z
    for _ in range(n_sweeps):
        carry, z = jax.jit(lambda c, z: jax.lax.scan(
            step, c, (docs, words, mask, z)))(carry, z)
    return tuple(np.asarray(a) for a in carry[:3]) + (np.asarray(z),)


def test_matmul_v1_and_all_padding_block():
    """Degenerate shapes: V=1 (every token hits one count row - maximal
    collision density) and a corpus whose final block is ENTIRELY
    padding (mask 0, sentinel assignments)."""
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    n_docs, k, block = 20, 3, 64
    n_tokens = 70                       # fills block 1 + 6 of block 2
    d = rng.integers(0, n_docs, n_tokens).astype(np.int32)
    docs = np.zeros((3, block), np.int32)
    words = np.zeros((3, block), np.int32)     # V=1
    mask = np.zeros((3, block), np.float32)
    docs.reshape(-1)[:n_tokens] = d
    mask.reshape(-1)[:n_tokens] = 1.0   # block 3 of 3: all padding
    docs, words, mask = (jnp.asarray(docs), jnp.asarray(words),
                         jnp.asarray(mask))
    results = {}
    for form in ("scatter", "matmul"):
        step = make_block_step(alpha=1.2, eta=0.01, n_vocab=1, k_topics=k,
                               nwk_form=form)
        st = init_state(docs, words, mask, n_docs, 1, k, seed=7)
        results[form] = _run_raw_sweeps(step, st, docs, words, mask, 2)
    for name, a, b in zip(("n_dk", "n_wk", "n_k", "z"),
                          results["scatter"], results["matmul"]):
        np.testing.assert_array_equal(a, b, err_msg=name)
    assert results["matmul"][1].sum() == n_tokens    # n_wk total


def _dense_corpus():
    """About 3600 tokens over 12 words: blocks of 512 are 42 tokens a
    count row, past the chip's threshold of 32, and every engine below
    sweeps several of them."""
    return synthetic_lda_corpus(60, 12, 3, mean_doc_len=60, seed=3)


@pytest.fixture
def forms(monkeypatch):
    """`forms(fit)` runs `fit()` once as the backend resolves (scatter
    on CPU) and once with the running backend given the chip's density
    entry, and checks which form each run's block steps resolved to.

    XLA:CPU (jax 0.9.0) has no bf16 dot once vmap leaves one operand
    unbatched, and the engines share the word blocks between chains
    ("Unsupported element type for DotThunk::Execute: BF16 x BF16 =
    F32"). So the matmul run widens the dot's operands to f32: they are
    {-1, 0, 1}, exact in either type, and the f32 accumulation is the
    same sum. The bf16 operands themselves are compared by the
    block-step grid in tests/test_gibbs.py."""
    import jax
    import jax.numpy as jnp

    bf16_dot = jax.lax.dot_general

    def f32_dot(a, b, *args, **kw):
        return bf16_dot(a.astype(jnp.float32), b.astype(jnp.float32),
                        *args, **kw)

    def run(fit):
        out, seen = {}, {}
        real = lda_gibbs.select_nwk_form

        def spy(**kw):
            got = real(**kw)
            seen.setdefault(want, set()).add(got)
            return got

        monkeypatch.setattr(lda_gibbs, "select_nwk_form", spy)
        for want in ("scatter", "matmul"):
            if want == "matmul":
                monkeypatch.setitem(
                    lda_gibbs._NWK_MATMUL_MIN_DENSITY,
                    jax.default_backend(),
                    lda_gibbs._NWK_MATMUL_MIN_DENSITY["tpu"])
                monkeypatch.setattr(jax.lax, "dot_general", f32_dot)
            out[want] = fit()
        assert seen == {"scatter": {"scatter"}, "matmul": {"matmul"}}
        return out
    return run


@pytest.mark.parametrize("n_chains", [1, 2])
def test_gibbs_lda_fit_matmul_bit_identical(forms, n_chains):
    corpus, _, _ = _dense_corpus()
    cfg = LDAConfig(n_topics=3, n_sweeps=6, burn_in=3, block_size=512,
                    seed=5, n_chains=n_chains)
    fits = forms(lambda: GibbsLDA(cfg, corpus.n_docs,
                                  corpus.n_vocab).fit(corpus))
    for name in fits["scatter"]["state"]._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(fits["scatter"]["state"], name)),
            np.asarray(getattr(fits["matmul"]["state"], name)),
            err_msg=f"{name} diverged between scatter and matmul fits")
    assert fits["scatter"]["ll_history"] == fits["matmul"]["ll_history"]


@pytest.mark.parametrize("dp,mp", [(1, 1), (2, 1), (2, 2)])
def test_sharded_fit_matmul_bit_identical(eight_devices, forms, dp, mp):
    """dp=1 takes the fast path (no shard_map); dp=2 and dp=2/mp=2 run
    the block step INSIDE the shard region, where the form is decided
    on the LOCAL vocabulary chunk (6 rows at mp=2)."""
    import jax

    from onix.parallel.mesh import make_mesh
    from onix.parallel.sharded_gibbs import ShardedGibbsLDA

    corpus, _, _ = _dense_corpus()
    cfg = LDAConfig(n_topics=3, n_sweeps=4, burn_in=2, block_size=512,
                    seed=5)

    def fit():
        model = ShardedGibbsLDA(
            cfg, corpus.n_vocab,
            mesh=make_mesh(dp=dp, mp=mp, devices=jax.devices()[:dp * mp]))
        return model.fit(corpus)

    fits = forms(fit)
    for name in ("z", "n_dk", "n_wk", "n_k", "acc_ndk", "acc_nwk"):
        np.testing.assert_array_equal(
            np.asarray(getattr(fits["scatter"]["state"], name)),
            np.asarray(getattr(fits["matmul"]["state"], name)),
            err_msg=f"{name} diverged at dp={dp} mp={mp}")
