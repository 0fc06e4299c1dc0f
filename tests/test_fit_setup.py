"""The sharded engine's set-up (PR 32): `shard_corpus` deals every token
once and in the order it always had, and `ShardedGibbsLDA.init_state`
draws and counts the chain's first state on the device, in the engine's
shardings, on every mesh the CPU suite builds."""

import hashlib

import numpy as np
import pytest

import jax
from jax.sharding import NamedSharding

from onix.config import LDAConfig
from onix.corpus import synthetic_lda_corpus
from onix.parallel.mesh import make_mesh
from onix.parallel.sharded_gibbs import ShardedGibbsLDA, shard_corpus
from onix.utils import telemetry

K = 4
BLOCK = 256


@pytest.fixture(scope="module")
def corpus():
    """1790 tokens: seven blocks of 256 on one device, the last padded."""
    c, _, _ = synthetic_lda_corpus(n_docs=60, n_vocab=37, n_topics=K,
                                   mean_doc_len=30, alpha=0.2, eta=0.05,
                                   seed=5)
    assert c.n_tokens % BLOCK
    return c


def _digest(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(repr((a.shape, a.dtype.str)).encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


def _model(corpus, dp, mp, **kw):
    cfg = LDAConfig(**{"n_topics": K, "alpha": 0.5, "eta": 0.05,
                       "n_sweeps": 4, "burn_in": 2, "block_size": BLOCK,
                       "seed": 9, **kw})
    mesh = make_mesh(dp=dp, mp=mp, devices=jax.devices()[:dp * mp])
    return ShardedGibbsLDA(cfg, corpus.n_vocab, mesh=mesh)


def _host_counts(sc, z, k):
    """The histograms of `z` by the plainest means: one `np.add.at` a
    (data shard, chunk, chain), summed as the engine's tables are."""
    p, m, c = z.shape[:3]
    n_dk = np.zeros((p, c, sc.n_docs_local, k), np.int32)
    n_wk = np.zeros((m, c, sc.n_vocab_local, k), np.int32)
    for q in range(p):
        for j in range(m):
            live = sc.mask_blocks[q, j] > 0
            for ch in range(c):
                zz = z[q, j, ch][live]
                np.add.at(n_dk[q, ch], (sc.doc_blocks[q, j][live], zz), 1)
                np.add.at(n_wk[j, ch], (sc.word_blocks[q, j][live], zz), 1)
    return n_dk, n_wk, n_wk.sum(axis=(0, 2)).astype(np.int32)


# -- shard_corpus -----------------------------------------------------------

# The layouts of the tree before PR 32 (its `rng.permutation` and two
# gathers) for this corpus, seed 11: the order of tokens is part of the
# resume identity ("layout": 4 in fit's fingerprint), so a digest that
# moves means the fingerprint has to move with it.
LAYOUTS = {
    (1, 1, 1): "e6f770d5e5567c06", (1, 1, 3): "1b56b8661077f8ba",
    (2, 1, 1): "87c1480f5b546de6", (4, 1, 2): "7bc8b4f9ba505a57",
    (3, 1, 1): "8453cd71c2c23304", (4, 1, 1): "7bc8b4f9ba505a57",
    (1, 2, 1): "98a29efa6b5da7a1", (1, 4, 1): "b34adb1f16ca3f22",
    (2, 2, 1): "e6d81f5492d82680", (4, 2, 3): "e00dc1176254e28c",
    (2, 4, 1): "3d2cc1003e175a8b",
}


@pytest.mark.parametrize("n_data,n_mp,n_groups", sorted(LAYOUTS))
def test_shard_corpus_deals_every_token_once(corpus, n_data, n_mp,
                                             n_groups):
    sc = shard_corpus(corpus, n_data, BLOCK, seed=11, n_mp=n_mp,
                      n_groups=n_groups)
    p, m, nb, b = sc.doc_blocks.shape
    assert (p, m) == (n_data, n_mp) and nb % n_groups == 0
    assert sc.word_blocks.shape == sc.mask_blocks.shape == (p, m, nb, b)
    assert (sc.doc_blocks.dtype, sc.word_blocks.dtype,
            sc.mask_blocks.dtype) == (np.int32, np.int32, np.float32)
    assert sc.n_vocab == corpus.n_vocab
    assert sc.n_vocab_local == -(-corpus.n_vocab // n_mp)
    assert sc.doc_map.shape == (n_data, sc.n_docs_local)
    # Every document lives in exactly one shard.
    assert sorted(sc.doc_map[sc.doc_map >= 0].tolist()) == list(
        range(corpus.n_docs))
    live = sc.mask_blocks > 0
    assert int(live.sum()) == corpus.n_tokens
    assert set(np.unique(sc.mask_blocks).tolist()) <= {0.0, 1.0}
    # A bucket's live tokens come first, its padding after them.
    flat = live.reshape(p, m, -1)
    assert (np.diff(flat.astype(np.int8), axis=-1) <= 0).all()
    # The multiset of (global doc, global word) pairs is the corpus's.
    got = []
    for q in range(p):
        for j in range(m):
            sel = live[q, j]
            doc = sc.doc_map[q][sc.doc_blocks[q, j][sel]]
            word = sc.word_blocks[q, j][sel] * n_mp + j
            got.append(doc.astype(np.int64) * corpus.n_vocab + word)
    want = corpus.doc_ids.astype(np.int64) * corpus.n_vocab + corpus.word_ids
    np.testing.assert_array_equal(np.sort(np.concatenate(got)),
                                  np.sort(want))
    # The deal is a shuffle: the corpus lists its tokens document by
    # document, a block does not.
    first = sc.doc_map[0][sc.doc_blocks[0, 0, 0][live[0, 0, 0]]]
    assert (np.diff(first) < 0).sum() > len(first) // 4
    # And it is the deal the engine has always made for this seed.
    assert _digest(sc.doc_blocks, sc.word_blocks, sc.mask_blocks,
                   sc.doc_map) == LAYOUTS[n_data, n_mp, n_groups]


# The same on a corpus of 598252 tokens with documents of very unequal
# lengths, pinned on the tree before PR 33 (a pass over every token per
# bucket, `tok_bucket == q`): since then one stable partition serves
# every bucket, and the one generator still shuffles them in order.
SKEWED = {(4, 1): "4e4fa771010c68b4", (2, 2): "1e6bf4e7c5b6e050",
          (4, 2): "364c99e349d5f0da"}


@pytest.mark.parametrize("n_data,n_mp", sorted(SKEWED))
def test_one_partition_deals_the_buckets_as_a_pass_each_did(n_data, n_mp):
    c, _, _ = synthetic_lda_corpus(n_docs=3000, n_vocab=300, n_topics=K,
                                   mean_doc_len=200, alpha=0.2, eta=0.05,
                                   seed=6)
    sc = shard_corpus(c, n_data, 4096, seed=3, n_mp=n_mp)
    assert _digest(sc.doc_blocks, sc.word_blocks, sc.mask_blocks,
                   sc.doc_map) == SKEWED[n_data, n_mp]
    # The span's balance figures come from a search, not a count.
    from onix.parallel.sharded_gibbs import bucket_tokens
    np.testing.assert_array_equal(
        bucket_tokens(sc), (sc.mask_blocks > 0).sum(axis=(2, 3)))


def test_shard_corpus_other_seed_other_deal(corpus):
    a = shard_corpus(corpus, 1, BLOCK, seed=11)
    b = shard_corpus(corpus, 1, BLOCK, seed=12)
    assert not np.array_equal(a.doc_blocks, b.doc_blocks)
    np.testing.assert_array_equal(a.mask_blocks, b.mask_blocks)


def test_shard_corpus_without_tokens():
    from onix.corpus import Corpus
    empty = Corpus(np.zeros(0, np.int32), np.zeros(0, np.int32), 3, 5)
    sc = shard_corpus(empty, 2, BLOCK, n_mp=2)
    assert sc.doc_blocks.shape == (2, 2, 1, 1)
    assert not sc.mask_blocks.any()


# -- init_state on the device -----------------------------------------------

MESHES = [(1, 1), (2, 1), (4, 1), (1, 2), (1, 4), (2, 2), (4, 2), (2, 4)]


@pytest.mark.parametrize("chains", [1, 2])
@pytest.mark.parametrize("dp,mp", MESHES)
def test_device_init_state_counts_its_own_draw(eight_devices, corpus,
                                               dp, mp, chains):
    # sync_splits rides along on the widest meshes: it pads the blocks.
    model = _model(corpus, dp, mp, n_chains=chains,
                   sync_splits=2 if dp * mp == 8 else 1)
    sc = model.prepare(corpus)
    st = model.init_state(sc)
    z = np.asarray(st.z)
    p, m, nb, b = sc.doc_blocks.shape
    assert z.shape == (p, m, chains, nb, b) and z.dtype == np.int32
    live = np.broadcast_to(sc.mask_blocks[:, :, None] > 0, z.shape)
    assert not live.all()                    # there is padding to hold K
    assert (z[~live] == K).all()
    assert z[live].min() >= 0 and z[live].max() < K
    # Uniform over K: every topic's share within 5 sigma of 1/K.
    n = int(live.sum())
    share = np.bincount(z[live], minlength=K) / n
    assert np.abs(share - 1 / K).max() < 5 * np.sqrt((K - 1) / K ** 2 / n)
    # The tables are the histograms of that very z, exactly.
    n_dk, n_wk, n_k = _host_counts(sc, z, K)
    np.testing.assert_array_equal(np.asarray(st.n_dk), n_dk)
    np.testing.assert_array_equal(np.asarray(st.n_wk), n_wk)
    np.testing.assert_array_equal(np.asarray(st.n_k), n_k)
    assert (n_k.sum(-1) == corpus.n_tokens).all()
    # ... laid out as `_specs` says, ready for the superstep.
    for name, spec in model._specs().items():
        a = getattr(st, name)
        if spec is None:
            assert not a.committed
        else:
            assert a.sharding.is_equivalent_to(
                NamedSharding(model.mesh, spec), a.ndim), name
    assert st.acc_ndk.dtype == st.acc_nwk.dtype == np.float32
    assert st.acc_ndk.shape == st.n_dk.shape
    assert st.acc_nwk.shape == st.n_wk.shape
    assert not np.asarray(st.acc_ndk).any()
    assert not np.asarray(st.acc_nwk).any()
    assert int(st.n_acc) == 0 and st.n_acc.dtype == np.int32
    if chains > 1:
        assert not np.array_equal(z[:, :, 0], z[:, :, 1])
        assert not np.array_equal(np.asarray(st.keys)[:, :, 0],
                                  np.asarray(st.keys)[:, :, 1])
    # The same seed gives the same state twice, another seed another.
    again = model.init_state(sc)
    for x, y in zip(st, again):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    other = _model(corpus, dp, mp, n_chains=chains, seed=10,
                   sync_splits=model.config.sync_splits)
    z_other = np.asarray(other.init_state(other.prepare(corpus)).z)
    assert z_other.shape != z.shape or not np.array_equal(z_other, z)


@pytest.mark.parametrize("rows", [64, 96])
def test_count_runs_do_not_change_the_state(eight_devices, corpus,
                                            monkeypatch, rows):
    """A block is counted in runs of `lda_gibbs._COUNT_ROWS` tokens
    where they divide it (64 into 256), whole where not (96): the same
    state."""
    from onix.models import lda_gibbs
    whole = _model(corpus, 2, 2, n_chains=2)
    sc = whole.prepare(corpus)
    want = whole.init_state(sc)
    monkeypatch.setattr(lda_gibbs, "_COUNT_ROWS", rows)
    runs = _model(corpus, 2, 2, n_chains=2)
    text = runs._init.lower(
        want.keys, *runs.device_corpus(sc), None,
        n_docs_local=sc.n_docs_local,
        n_vocab_local=sc.n_vocab_local).as_text()
    assert (f"tensor<{rows}xi32>" in text) is (BLOCK % rows == 0)
    for x, y in zip(want, runs.init_state(sc)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_device_init_state_is_the_mesh_independent_draw(eight_devices,
                                                        corpus):
    """A shard's draw depends on its key and its blocks alone: the
    same layout on other devices, or through the blocks `fit` has put
    already, is the same state."""
    a = _model(corpus, 2, 2)
    sc = a.prepare(corpus)
    b = ShardedGibbsLDA(a.config, corpus.n_vocab,
                        mesh=make_mesh(dp=2, mp=2,
                                       devices=jax.devices()[4:8]))
    sa = a.init_state(sc)
    sb = b.init_state(sc, device_blocks=b.device_corpus(sc))
    for x, y in zip(sa, sb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# z, keys and tables of the tree before PR 32 (host draw, `np.add.at`
# tables) for seed 9 and the prior below.
WARM = {
    (1, 1, 1): ("68377684605a89b1", "c42354a5a8a91421", "ba08783c917ef388"),
    (2, 2, 2): ("448d10a34c7e3978", "726fc0e13c98df3a", "e766c6de09ece0ef"),
}


@pytest.mark.parametrize("dp,mp,chains", sorted(WARM))
def test_warm_start_keeps_its_host_draw(eight_devices, corpus, dp, mp,
                                        chains):
    phi = np.random.default_rng(2).gamma(0.3, size=(corpus.n_vocab, K))
    model = _model(corpus, dp, mp, n_chains=chains)
    sc = model.prepare(corpus)
    st = model.init_state(sc, init_phi=phi)
    z = np.asarray(st.z)
    n_dk, n_wk, n_k = _host_counts(sc, z, K)
    np.testing.assert_array_equal(np.asarray(st.n_dk), n_dk)
    np.testing.assert_array_equal(np.asarray(st.n_wk), n_wk)
    np.testing.assert_array_equal(np.asarray(st.n_k), n_k)
    assert (z[np.broadcast_to(sc.mask_blocks[:, :, None] == 0,
                              z.shape)] == K).all()
    assert (_digest(z), _digest(np.asarray(st.keys)),
            _digest(n_dk, n_wk, n_k)) == WARM[dp, mp, chains]
    with pytest.raises(ValueError, match="map the prior"):
        model.init_state(sc, init_phi=phi[:-1])


def test_fit_puts_the_corpus_once_and_says_where_the_state_was_made(
        corpus, monkeypatch):
    telemetry.reset_for_tests()
    model = _model(corpus, 1, 1)
    puts = []
    device_corpus = model.device_corpus
    monkeypatch.setattr(model, "device_corpus",
                        lambda sc: puts.append(1) or device_corpus(sc))
    cold = model.fit(corpus, n_sweeps=2)
    assert puts == [1]
    phi = np.random.default_rng(2).gamma(0.3, size=(corpus.n_vocab, K))
    warm = model.fit(corpus, n_sweeps=2, init_phi=phi)
    assert puts == [1, 1]
    first, second = [s.attrs for s in telemetry.TRACER.spans()
                     if s.name == "fit.init_state"]
    nbytes = sum(int(a.nbytes) for a in cold["state"])
    assert first == {"resumed": False, "bytes": nbytes, "draw": "device",
                     "counts": "device", "h2d_bytes": 0}
    assert second == {"resumed": False, "bytes": nbytes, "draw": "host",
                      "counts": "device",
                      "h2d_bytes": int(warm["state"].z.nbytes)}
    # Alone, init_state puts the blocks itself.
    model.init_state(model.prepare(corpus))
    assert puts == [1, 1, 1]


def test_resumed_fit_says_what_crossed(corpus, tmp_path):
    telemetry.reset_for_tests()
    model = _model(corpus, 1, 1, checkpoint_every=2)
    model.fit(corpus, n_sweeps=2, checkpoint_dir=tmp_path)
    fit = _model(corpus, 1, 1, checkpoint_every=2).fit(
        corpus, n_sweeps=4, checkpoint_dir=tmp_path)
    attrs = [s.attrs for s in telemetry.TRACER.spans()
             if s.name == "fit.init_state"][-1]
    nbytes = sum(int(a.nbytes) for a in fit["state"])
    assert attrs == {"resumed": True, "bytes": nbytes, "h2d_bytes": nbytes}


def test_count_block_is_the_single_device_engines(corpus):
    """`lda_gibbs.build_counts` (what `init_state_keyed` counts with)
    and the sharded engine's scan share `count_block`: on one device the
    two engines' tables of one z agree."""
    from onix.models import lda_gibbs
    model = _model(corpus, 1, 1)
    sc = model.prepare(corpus)
    st = model.init_state(sc)
    n_dk, n_wk, n_k = lda_gibbs.build_counts(
        sc.doc_blocks[0, 0], sc.word_blocks[0, 0], st.z[0, 0, 0],
        sc.n_docs_local, sc.n_vocab_local, K)
    np.testing.assert_array_equal(np.asarray(n_dk), np.asarray(st.n_dk[0, 0]))
    np.testing.assert_array_equal(np.asarray(n_wk), np.asarray(st.n_wk[0, 0]))
    np.testing.assert_array_equal(np.asarray(n_k), np.asarray(st.n_k[0]))


# -- the cross-chip merge has a name ----------------------------------------

def _scopes_in(text):
    import re
    found = set()
    for loc in re.findall(r'loc\("([^"]*)"', text):
        found.update(p for p in loc.split("/") if p.startswith("onix."))
    return found


def test_merge_scope_is_in_the_shard_map_superstep_alone(eight_devices,
                                                         corpus):
    """`onix.sweep.merge` names the psum fold of the `shard_map`
    superstep (and `onix.init.merge` the first state's); the dp=1 fast
    path has no psum and no such scope."""
    texts = {}
    for dp in (4, 1):
        model = _model(corpus, dp, 1)
        sc = model.prepare(corpus)
        blocks = model.device_corpus(sc)
        state = model.init_state(sc, device_blocks=blocks)
        assert model.dp1_fast is (dp == 1)
        texts[dp] = model._superstep.lower(
            state, *blocks, 0, n_steps=1,
            with_initial_ll=False).as_text(debug_info=True)
        init = model._init.lower(
            state.keys, *blocks, None, n_docs_local=sc.n_docs_local,
            n_vocab_local=sc.n_vocab_local).as_text(debug_info=True)
        assert "onix.init.merge" in _scopes_in(init)
    assert "onix.sweep.merge" in _scopes_in(texts[4])
    assert "onix.sweep.scatter" in _scopes_in(texts[1])
    assert "onix.sweep.merge" not in _scopes_in(texts[1])


def test_fit_spans_say_how_the_shards_balance(eight_devices, corpus):
    telemetry.reset_for_tests()
    model = _model(corpus, 4, 1, n_chains=2)
    fit = model.fit(corpus, n_sweeps=2)
    spans = {s.name: s.attrs for s in telemetry.TRACER.spans()}
    live = (fit["sharded_corpus"].mask_blocks > 0).sum(axis=(1, 2, 3))
    assert spans["fit.prepare"] == {
        "tokens": corpus.n_tokens, "docs": corpus.n_docs, "shards": 4,
        "tokens_max_shard": int(live.max()),
        "tokens_min_shard": int(live.min()),
        "pad_slots": int(fit["sharded_corpus"].mask_blocks.size
                         - corpus.n_tokens)}
    assert spans["fit.supersteps"] == {
        "sweeps": 2, "merge_form": "sync",
        "merge_bytes_per_sweep": 2 * (corpus.n_vocab * K + K) * 4,
        # n_dk as a chip's block scan carries it: [Dl, K] on the CPU.
        "ndk_form": "rows", "ndk_group": 1,
        "ndk_rows_packed": fit["sharded_corpus"].n_docs_local}
