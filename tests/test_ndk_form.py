"""The form of n_dk inside a sweep: the decision (`select_ndk_form`),
the pack and its reverse, and the bit-identity of the packed form
(G documents a 128-lane row, a power of two) against the rows form ([D, K])
through the block step and every engine.

The chip carries the packed table and tier-1's CPU the rows, so the
contract is BIT-identity - same z sequence, same n_dk/n_wk/n_k counts,
same accumulators - and every comparison here is assert_array_equal.
No engine takes a form: the engine-level cases name the running
backend in `_NDK_PACKED_BACKENDS`, the table the chip is read from.
"""

import numpy as np
import pytest

from onix.config import LDAConfig
from onix.corpus import synthetic_lda_corpus
from onix.models import lda_gibbs
from onix.models.lda_gibbs import (GibbsLDA, init_state, make_block_step,
                                   make_sweep_kernel, pack_ndk,
                                   select_ndk_form, unpack_ndk)


# ---------------------------------------------------------------------------
# The decision.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [2, 7, 20, 32, 64, 65, 128, 256])
def test_gate_cpu_never_packs(k):
    assert select_ndk_form(backend="cpu", k_topics=k) == ("rows", 1)
    assert select_ndk_form(backend="gpu", k_topics=k) == ("rows", 1)


@pytest.mark.parametrize("k,group", [(2, 64), (7, 16), (16, 8), (20, 4),
                                     (32, 4), (33, 2), (64, 2)])
def test_gate_tpu_packs_while_two_documents_fit_a_row(k, group):
    # The largest power of two of documents that fit a 128-lane row.
    assert select_ndk_form(backend="tpu", k_topics=k) == ("packed", group)


@pytest.mark.parametrize("k", [65, 128, 256])
def test_gate_tpu_keeps_rows_past_64_topics(k):
    assert select_ndk_form(backend="tpu", k_topics=k) == ("rows", 1)


def test_gate_pin_wins_and_nothing_else_is_read(monkeypatch):
    assert select_ndk_form(backend="tpu", k_topics=20,
                           ndk_form="rows") == ("rows", 1)
    assert select_ndk_form(backend="cpu", k_topics=20,
                           ndk_form="packed") == ("packed", 4)
    for bad in ("auto", "pallas", "flat"):
        with pytest.raises(ValueError, match="ndk_form"):
            select_ndk_form(backend="cpu", k_topics=20, ndk_form=bad)
    with pytest.raises(ValueError, match="two documents"):
        select_ndk_form(backend="tpu", k_topics=65, ndk_form="packed")
    monkeypatch.setenv("ONIX_NDK_FORM", "packed")
    monkeypatch.setenv("ONIX_NDK_GROUP", "6")
    assert select_ndk_form(backend="cpu", k_topics=20) == ("rows", 1)
    assert not hasattr(LDAConfig(), "ndk_form")
    assert not hasattr(LDAConfig(), "ndk_group")


def test_bare_block_step_refuses_a_group_that_does_not_fit():
    with pytest.raises(ValueError, match="ndk_group"):
        make_block_step(alpha=1.2, eta=0.01, n_vocab=9, k_topics=20,
                        ndk_group=7)


# ---------------------------------------------------------------------------
# Pack, then unpack.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [7, 20, 32, 64])
@pytest.mark.parametrize("n_docs", [1, 5, 6, 7, 1003])
def test_pack_then_unpack_is_the_identity(n_docs, k):
    import jax.numpy as jnp

    group = lda_gibbs._ndk_group(k)
    rng = np.random.default_rng(n_docs * 1000 + k)
    n_dk = rng.integers(0, 1 << 20, (n_docs, k)).astype(np.int32)
    packed = np.asarray(pack_ndk(jnp.asarray(n_dk), group))
    assert packed.shape == (-(-n_docs // group), 128)
    assert packed.dtype == np.int32
    # Document d sits in lanes (d % G) * K onward of row d // G; the
    # tail documents and the lanes past G * K are zero.
    d = n_docs - 1
    np.testing.assert_array_equal(
        packed[d // group, (d % group) * k:(d % group + 1) * k], n_dk[d])
    assert packed.sum(dtype=np.int64) == n_dk.sum(dtype=np.int64)
    assert not packed[:, group * k:].any()
    back = np.asarray(unpack_ndk(jnp.asarray(packed), n_docs, k, group))
    np.testing.assert_array_equal(back, n_dk)


# ---------------------------------------------------------------------------
# The packed block step against the bare one.
# ---------------------------------------------------------------------------

def _blocks(case: str, n_docs: int, n_vocab: int, block: int, rng):
    """(docs, words, mask) [3, block]: a full block, a part-full block
    (a padded tail) and, by case, what the third holds."""
    docs = np.zeros((3, block), np.int32)
    words = np.zeros((3, block), np.int32)
    mask = np.zeros((3, block), np.float32)
    live = {"all_padding_block": block + block // 3,
            "padded_tail": 2 * block + block // 2,
            "last_partial_row": 3 * block - 5,
            "one_document_block": 3 * block}[case]
    d = rng.integers(0, n_docs, live).astype(np.int32)
    if case == "last_partial_row":
        d[::3] = n_docs - 1         # the last row holds fewer than G
    if case == "one_document_block":
        d[block:2 * block] = n_docs // 2
    docs.reshape(-1)[:live] = d
    words.reshape(-1)[:live] = rng.integers(0, n_vocab, live)
    mask.reshape(-1)[:live] = 1.0
    return docs, words, mask


# The gate's own G for each K, and one that is no power of two: the
# block step takes any G that fits a row.
@pytest.mark.parametrize("sampler", ["race", "gumbel"])
@pytest.mark.parametrize("k,group", [(7, 16), (20, 4), (20, 6), (32, 4),
                                     (64, 2)])
@pytest.mark.parametrize("case", ["all_padding_block", "padded_tail",
                                  "last_partial_row", "one_document_block"])
def test_packed_block_step_bit_identical(case, k, group, sampler):
    import jax
    import jax.numpy as jnp

    n_docs, n_vocab, block = 1003, 23, 128
    assert n_docs % group               # a last row that is part full
    rng = np.random.default_rng(k)
    docs, words, mask = (jnp.asarray(a) for a in
                         _blocks(case, n_docs, n_vocab, block, rng))
    st = init_state(docs, words, mask, n_docs, n_vocab, k, seed=11)
    got = {}
    for g in (1, group):
        step = make_block_step(alpha=1.2, eta=0.01, n_vocab=n_vocab,
                               k_topics=k, sampler=sampler, ndk_group=g)

        def two_sweeps(st):
            n_dk = st.n_dk if g == 1 else pack_ndk(st.n_dk, g)
            carry, z = (n_dk, st.n_wk, st.n_k, st.key), st.z
            for _ in range(2):
                carry, z = jax.lax.scan(step, carry,
                                        (docs, words, mask, z))
            n_dk = (carry[0] if g == 1
                    else unpack_ndk(carry[0], n_docs, k, g))
            return n_dk, carry[1], carry[2], z

        got[g] = [np.asarray(a) for a in jax.jit(two_sweeps)(st)]
    for name, a, b in zip(("n_dk", "n_wk", "n_k", "z"), got[1], got[group]):
        np.testing.assert_array_equal(a, b, err_msg=name)
    # The counts are the assignments': nothing leaked into a neighbour.
    n_dk, _, _, z = got[group]
    live = np.asarray(mask) > 0
    want = np.zeros((n_docs, k), np.int32)
    np.add.at(want, (np.asarray(docs)[live], z[live]), 1)
    np.testing.assert_array_equal(n_dk, want)
    assert (z[~live] == k).all()


@pytest.mark.parametrize("k", [20, 64])
def test_sweep_kernel_pin_packs_inside_and_hands_rows_back(k):
    import jax
    import jax.numpy as jnp

    n_docs, n_vocab, block = 77, 13, 64
    rng = np.random.default_rng(5)
    docs, words, mask = (jnp.asarray(a) for a in
                         _blocks("padded_tail", n_docs, n_vocab, block, rng))
    st = init_state(docs, words, mask, n_docs, n_vocab, k, seed=2)
    outs = {}
    for form in ("rows", "packed"):
        kernel = make_sweep_kernel(alpha=1.2, eta=0.01, n_vocab=n_vocab,
                                   k_topics=k, ndk_form=form,
                                   sampler_form="dense")
        outs[form] = jax.jit(kernel)(st.z, st.n_dk, st.n_wk, st.n_k,
                                     st.key, docs, words, mask)
    assert outs["packed"][1].shape == (n_docs, k)
    for a, b in zip(outs["rows"], outs["packed"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("n_chains", [1, 2])
def test_packed_kernel_under_a_chain_vmap(n_chains):
    """The packed kernel's own vmap rule: a chain axis of one is taken
    off (the scan carries [rows, 128], not [1, rows, 128]) and a longer
    one is vmapped; either way every chain is what a call of its own
    gives, with the blocks shared between them."""
    import jax
    import jax.numpy as jnp

    k, n_docs, n_vocab, block = 20, 77, 13, 64
    n_rows = -(-n_docs // 4)
    rng = np.random.default_rng(8)
    docs, words, mask = (jnp.asarray(a) for a in
                         _blocks("padded_tail", n_docs, n_vocab, block, rng))
    chains = [init_state(docs, words, mask, n_docs, n_vocab, k, seed=s)
              for s in range(n_chains)]
    stacked = [jnp.stack([getattr(c, f) for c in chains])
               for f in ("z", "n_dk", "n_wk", "n_k", "key")]
    kernel = make_sweep_kernel(alpha=1.2, eta=0.01, n_vocab=n_vocab,
                               k_topics=k, ndk_form="packed",
                               sampler_form="dense")

    def over_chains(*state):
        return jax.vmap(lambda *c: kernel(*c, docs, words, mask))(*state)

    text = str(jax.make_jaxpr(over_chains)(*stacked))
    assert (f"i32[{n_rows},128]" in text) == (n_chains == 1)
    assert (f"i32[{n_chains},{n_rows},128]" in text) == (n_chains > 1)
    got = jax.jit(over_chains)(*stacked)
    for i, c in enumerate(chains):
        want = jax.jit(kernel)(c.z, c.n_dk, c.n_wk, c.n_k, c.key,
                               docs, words, mask)
        for a, b in zip(want, got):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b[i]))


# ---------------------------------------------------------------------------
# Through the engines: the running backend read as the chip is.
# ---------------------------------------------------------------------------

@pytest.fixture
def forms(monkeypatch):
    """`forms(fit)` runs `fit()` once as the backend resolves (rows on
    CPU) and once with the running backend named in
    `_NDK_PACKED_BACKENDS`, and checks which form each run's sweep
    kernels resolved to."""
    import jax

    def run(fit):
        out, seen = {}, {}
        real = lda_gibbs.select_ndk_form

        def spy(**kw):
            got = real(**kw)
            seen.setdefault(want, set()).add(got[0])
            return got

        monkeypatch.setattr(lda_gibbs, "select_ndk_form", spy)
        for want in ("rows", "packed"):
            if want == "packed":
                monkeypatch.setattr(
                    lda_gibbs, "_NDK_PACKED_BACKENDS",
                    lda_gibbs._NDK_PACKED_BACKENDS
                    + (jax.default_backend(),))
            out[want] = fit()
        assert seen == {"rows": {"rows"}, "packed": {"packed"}}
        return out
    return run


def _corpus():
    """About 3000 tokens over 61 documents: not a multiple of any G."""
    return synthetic_lda_corpus(61, 12, 3, mean_doc_len=50, seed=3)


@pytest.mark.parametrize("n_chains", [1, 2])
def test_gibbs_lda_fit_packed_bit_identical(forms, n_chains):
    corpus, _, _ = _corpus()
    cfg = LDAConfig(n_topics=5, n_sweeps=6, burn_in=3, block_size=512,
                    seed=5, n_chains=n_chains)
    fits = forms(lambda: GibbsLDA(cfg, corpus.n_docs,
                                  corpus.n_vocab).fit(corpus))
    for name in fits["rows"]["state"]._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(fits["rows"]["state"], name)),
            np.asarray(getattr(fits["packed"]["state"], name)),
            err_msg=f"{name} diverged between rows and packed fits")
    assert fits["rows"]["ll_history"] == fits["packed"]["ll_history"]


@pytest.mark.parametrize("merge_form", ["sync", "async"])
@pytest.mark.parametrize("sync_splits", [1, 2])
@pytest.mark.parametrize("n_chains", [1, 2])
@pytest.mark.parametrize("dp,mp", [(1, 1), (2, 1), (4, 1), (2, 2)])
def test_sharded_fit_packed_bit_identical(eight_devices, forms, dp, mp,
                                          n_chains, sync_splits,
                                          merge_form):
    """dp=1 takes the fast path (no shard_map); the others run the
    sweep kernel INSIDE the shard region, on the chip's own documents
    (61 over dp chips: no shard a multiple of G), under the chain vmap
    (each chain packs its own table)."""
    import jax

    from onix.parallel.mesh import make_mesh
    from onix.parallel.sharded_gibbs import ShardedGibbsLDA

    corpus, _, _ = _corpus()
    cfg = LDAConfig(n_topics=5, n_sweeps=4, burn_in=2, block_size=256,
                    seed=5, n_chains=n_chains, sync_splits=sync_splits,
                    merge_form=merge_form)

    def fit():
        model = ShardedGibbsLDA(
            cfg, corpus.n_vocab,
            mesh=make_mesh(dp=dp, mp=mp, devices=jax.devices()[:dp * mp]))
        return model.fit(corpus)

    fits = forms(fit)
    for name in ("z", "n_dk", "n_wk", "n_k", "acc_ndk", "acc_nwk"):
        np.testing.assert_array_equal(
            np.asarray(getattr(fits["rows"]["state"], name)),
            np.asarray(getattr(fits["packed"]["state"], name)),
            err_msg=f"{name} diverged at dp={dp} mp={mp}")
    assert fits["rows"]["ll_history"] == fits["packed"]["ll_history"]


def test_fleet_refit_packed_bit_identical(forms):
    """One shape class of three tenants (documents padded to a power
    of two), the tenant axis on top of the kernel."""
    from onix.models import fleet_gibbs
    from onix.pipelines.fleet import tenant_name

    rng = np.random.default_rng(3)
    tenants = [fleet_gibbs.TenantDay(
        name=tenant_name(u), uid=u,
        docs=rng.integers(0, 30, 300).astype(np.int32),
        words=rng.integers(0, 90, 300).astype(np.int32),
        n_docs=30, n_vocab=90) for u in range(3)]
    cfg = LDAConfig(n_topics=8, n_sweeps=3, burn_in=1, seed=2)
    sc = fleet_gibbs.stack_tenants(tenants, k_topics=8, seed=2, day=1)[0]
    d_pad, v_pad, _ = sc.key

    def refit():
        prog = fleet_gibbs.make_fleet_refit(cfg, n_docs=d_pad,
                                            n_vocab=v_pad)
        return prog(sc.z0, sc.docs, sc.words, sc.mask, sc.fb_docs,
                    sc.fb_words, sc.fb_weights, sc.keys)

    fits = forms(refit)
    for a, b in zip(fits["rows"], fits["packed"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_packed_fit_names_its_scopes_and_says_its_form(forms):
    """The packed program carries `onix.sweep.pack` beside the rows
    program's scopes (every op still under one of them), and `fit.supersteps` says what the scan carries:
    K=5 packs 16 documents a row, 61 documents make 4 rows."""
    from onix.parallel.mesh import make_mesh
    from onix.parallel.sharded_gibbs import ShardedGibbsLDA
    from onix.utils import telemetry
    from tests.test_trace_scopes import SWEEP_SCOPES, _scopes_in

    corpus, _, _ = _corpus()
    cfg = LDAConfig(n_topics=5, n_sweeps=2, burn_in=1, block_size=256,
                    seed=5)

    def fit():
        telemetry.reset_for_tests()
        model = ShardedGibbsLDA(cfg, corpus.n_vocab,
                                mesh=make_mesh(dp=1, mp=1))
        model.fit(corpus)
        attrs = {s.name: s.attrs for s in telemetry.TRACER.spans()}
        sc = model.prepare(corpus)
        docs, words, mask = model.device_corpus(sc)
        lowered = model._superstep.lower(
            model.init_state(sc), docs, words, mask, 0, n_steps=1,
            with_initial_ll=False)
        # XLA:CPU folds the pack's pad and reshape into their
        # neighbours, whose names the fusions keep: the lowered text
        # still has the scope on them.
        return (attrs["fit.supersteps"],
                _scopes_in(lowered.compile().as_text()),
                "onix.sweep.pack" in lowered.as_text(debug_info=True))

    got = forms(fit)
    attrs, scopes, packs = got["rows"]
    assert (attrs["ndk_form"], attrs["ndk_group"],
            attrs["ndk_rows_packed"]) == ("rows", 1, corpus.n_docs)
    assert scopes == SWEEP_SCOPES and not packs
    attrs, scopes, packs = got["packed"]
    assert (attrs["ndk_form"], attrs["ndk_group"],
            attrs["ndk_rows_packed"]) == ("packed", 16, 4)
    # With the chain axis of one taken off, the block loop's own ops
    # carry `onix.sweep.blocks` (under a vmap the part of the path
    # reads `vmap(onix.sweep.blocks)`, which no reader books).
    assert (scopes - {"onix.sweep.pack"}
            == SWEEP_SCOPES | {"onix.sweep.blocks"}) and packs
