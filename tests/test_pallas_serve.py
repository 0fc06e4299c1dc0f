"""r15 fused serving kernel: gate precedence + interpret-mode
bit-identity against every XLA scan arm it replaces (ISSUE 11).

The kernel's whole contract is BIT-identity — same winners, same
scores, same tie order as the three-stage XLA path, on every
tier-1 shape including the empty-filter and no-feedback fast-path
cases — so every test here is assert_array_equal, never allclose. On
CPU the kernel runs in interpret mode (plain XLA lowering of the same
kernel code); the compiled-Mosaic identity runs are the `tpu`-marked
tests at the bottom (ONIX_TPU_TESTS=1 python -m pytest -m tpu, on the
chip).
"""

import numpy as np
import pytest

from onix.config import OnixConfig, resolve_form_gate
from onix.feedback.filter import (FilterTables, HostFilter, _pad_sorted,
                                  pack_pair, split_key)
from onix.models import pallas_serve as ps
from onix.models.pallas_serve import (_FILTER_SEARCH_TILE, _SERVE_TILE,
                                      select_serve_form)


# ---------------------------------------------------------------------------
# The gate: select_serve_form + the shared resolve_form_gate chain.
# ---------------------------------------------------------------------------

def test_serve_gate_default_xla_everywhere():
    # The crossover table is DELIBERATELY EMPTY (tpu included) until
    # the queued rows land: auto resolves to xla on every backend at
    # every size (the acceptance criterion "gate default unchanged").
    assert ps._SERVE_FUSED_MIN_EVENTS == {}
    for backend in ("cpu", "tpu", "gpu", "quantum"):
        for n in (0, 1, 1 << 10, 1 << 24):
            assert select_serve_form("auto", n, backend=backend) == "xla"


def test_serve_gate_priority(monkeypatch):
    # env > explicit > measured table > xla (config.resolve_form_gate).
    monkeypatch.setenv("ONIX_SERVE_FORM", "fused")
    assert select_serve_form("xla", 4, backend="cpu") == "fused"
    monkeypatch.setenv("ONIX_SERVE_FORM", "auto")   # reset, not crash
    assert select_serve_form("xla", 4, backend="cpu") == "xla"
    monkeypatch.delenv("ONIX_SERVE_FORM")
    assert select_serve_form("fused", 4, backend="cpu") == "fused"
    monkeypatch.setitem(ps._SERVE_FUSED_MIN_EVENTS, "cpu", 1 << 10)
    assert select_serve_form("auto", 1 << 10, backend="cpu") == "fused"
    assert select_serve_form("auto", (1 << 10) - 1,
                             backend="cpu") == "xla"
    assert select_serve_form("xla", 1 << 20, backend="cpu") == "xla"
    with pytest.raises(ValueError, match="serve_form"):
        select_serve_form("sideways", 4, backend="cpu")
    monkeypatch.setenv("ONIX_SERVE_FORM", "sideways")
    with pytest.raises(ValueError, match="serve_form"):
        select_serve_form("auto", 4, backend="cpu")


def test_resolve_form_gate_one_chain_per_gate(monkeypatch):
    """The satellite contract: all three measured gates resolve
    through ONE precedence chain (env > explicit > measured >
    default), exercised per gate so the tables cannot drift."""
    # sampler (no env layer here — engines resolve ONIX_SAMPLER_FORM
    # themselves): explicit > measured (cpu: K >= 64) > dense.
    from onix.models.lda_gibbs import select_sampler_form
    assert select_sampler_form(backend="cpu", k_topics=64,
                               sampler_form="dense") == "dense"
    assert select_sampler_form(backend="cpu", k_topics=64) == "sparse"
    assert select_sampler_form(backend="tpu", k_topics=64) == "dense"
    # bank: env > explicit > measured (cpu: gather-always) > vmap.
    from onix.serving.model_bank import select_bank_form
    monkeypatch.setenv("ONIX_BANK_FORM", "vmap")
    assert select_bank_form("gather", 64, 4096, backend="cpu") == "vmap"
    monkeypatch.delenv("ONIX_BANK_FORM")
    assert select_bank_form("gather", 1, 1, backend="cpu") == "gather"
    assert select_bank_form("auto", 64, 4096, backend="cpu") == "gather"
    assert select_bank_form("auto", 64, 4096, backend="gpu") == "vmap"
    # serve: env > explicit > measured > xla (test_serve_gate_priority
    # covers the table leg).
    monkeypatch.setenv("ONIX_SERVE_FORM", "fused")
    assert select_serve_form("xla", 1, backend="cpu") == "fused"
    monkeypatch.delenv("ONIX_SERVE_FORM")
    # The helper itself: a typo'd env override fails loudly in every
    # gate, never a silently-mislabeled experiment.
    with pytest.raises(ValueError, match="env override"):
        resolve_form_gate(gate="g", choices=("a", "b"), env="c",
                          default="a")
    assert resolve_form_gate(gate="g", choices=("a", "b"), env="",
                             explicit=None, default="a") == "a"
    assert resolve_form_gate(gate="g", choices=("a", "b"), env="b",
                             explicit="a", default="a") == "b"
    assert resolve_form_gate(gate="g", choices=("a", "b"),
                             explicit="auto", measured=lambda: "b",
                             default="a") == "b"


def test_serving_config_validates_serve_form():
    cfg = OnixConfig()
    cfg.serving.serve_form = "fused"
    cfg.validate()
    cfg.serving.serve_form = "mxu"
    with pytest.raises(ValueError, match="serve_form"):
        cfg.validate()


# ---------------------------------------------------------------------------
# Interpret-mode bit-identity vs the XLA scan arms.
# ---------------------------------------------------------------------------

def _tables(rng, n_docs, n_vocab, k):
    theta = rng.dirichlet(np.ones(k), n_docs).astype(np.float32)
    phi = rng.dirichlet(np.ones(k), n_vocab).astype(np.float32)
    return theta, phi


def _assert_topk_equal(a, b, msg=""):
    np.testing.assert_array_equal(np.asarray(a.scores),
                                  np.asarray(b.scores),
                                  err_msg=f"{msg} scores")
    np.testing.assert_array_equal(np.asarray(a.indices),
                                  np.asarray(b.indices),
                                  err_msg=f"{msg} indices")


def _assert_same_winners(a, b, msg=""):
    """What holds of `dot` mode on both platforms (PR 21): the kernel
    takes the K-term product-sum itself, so its association is the
    kernel compiler's while XLA contracts its fused gather-dot; the
    two agree on the winners and their order, and on the scores within
    2 ulp (the `tpu` test below allows the chip's Mosaic 4). `min2` and
    `scores` move scores without arithmetic and stay on
    `_assert_topk_equal`."""
    np.testing.assert_array_equal(np.asarray(a.indices),
                                  np.asarray(b.indices),
                                  err_msg=f"{msg} indices")
    np.testing.assert_array_max_ulp(np.asarray(a.scores),
                                    np.asarray(b.scores), maxulp=2)


# >= 3 shapes (ISSUE 11 acceptance): a multi-tile stream whose length
# is NOT a tile multiple, the V=1 degenerate vocabulary, and a stream
# shorter than one tile.
@pytest.mark.parametrize("n_docs,n_vocab,k,n", [
    (300, 64, 8, 5000),     # 5000 % 256 != 0: in-wrapper padding path
    (40, 1, 3, 700),        # V=1 degenerate: every event one word
    (25, 16, 4, 13),        # n < tile: single clamped tile
])
def test_fused_top_suspicious_bit_identical(n_docs, n_vocab, k, n):
    import jax.numpy as jnp

    from onix.feedback.rescore import top_suspicious_filtered
    from onix.models.scoring import top_suspicious

    rng = np.random.default_rng(3)
    theta, phi = _tables(rng, n_docs, n_vocab, k)
    d = rng.integers(0, n_docs, n).astype(np.int32)
    w = rng.integers(0, n_vocab, n).astype(np.int32)
    mask = np.ones(n, np.float32)
    mask[-max(n // 10, 1):] = 0.0
    pair = pack_pair(d.astype(np.uint32), w.astype(np.uint32))
    ph, pl = split_key(pair)
    tol, m = 0.2, 50

    ref = top_suspicious(jnp.asarray(theta), jnp.asarray(phi),
                         jnp.asarray(d), jnp.asarray(w),
                         jnp.asarray(mask), tol=tol, max_results=m)
    out = ps.fused_top_suspicious(theta, phi, d, w, mask,
                                  tol=tol, max_results=m)
    _assert_same_winners(ref, out, "unfiltered")

    # Filtered: suppress half the winners' pairs, boost some words.
    win = np.asarray(ref.indices)
    win = win[win >= 0]
    filt = HostFilter.empty(0.25).merged(
        pair_suppress=pair[win[::2]] if win.size else None,
        word_boost=np.unique(w[: n // 3]).astype(np.uint64))
    tabs = filt.tables()
    ref_f = top_suspicious_filtered(
        jnp.asarray(theta), jnp.asarray(phi), jnp.asarray(d),
        jnp.asarray(w), jnp.asarray(mask), jnp.asarray(ph),
        jnp.asarray(pl), tabs, tol=tol, max_results=m)
    out_f = ps.fused_top_suspicious(theta, phi, d, w, mask,
                                    jnp.asarray(ph), jnp.asarray(pl),
                                    tabs, tol=tol, max_results=m)
    _assert_same_winners(ref_f, out_f, "filtered")

    # Empty-filter identity: zero entries == the UNFILTERED scan (the
    # filter.py exactness contract through the kernel).
    out_e = ps.fused_top_suspicious(theta, phi, d, w, mask,
                                    jnp.asarray(ph), jnp.asarray(pl),
                                    HostFilter.empty().tables(),
                                    tol=tol, max_results=m)
    _assert_same_winners(ref, out_e, "empty-filter")


def test_fused_pair_table_filter_straddles_search_tiles():
    """The flow pair-table path under a filter LARGER than one VMEM
    search tile (> _FILTER_SEARCH_TILE entries -> the tiled
    compare-sweep), with live members placed in BOTH halves of the
    sorted table so the hit must come from different search tiles."""
    import jax.numpy as jnp

    from onix.feedback.rescore import table_pair_bottom_k_filtered
    from onix.models.scoring import score_table

    rng = np.random.default_rng(5)
    n_docs, n_vocab, k, n = 2000, 32, 6, 4000
    theta, phi = _tables(rng, n_docs, n_vocab, k)
    table = score_table(jnp.asarray(theta), jnp.asarray(phi)).ravel()
    ds = rng.integers(0, n_docs, n).astype(np.int32)
    dd = rng.integers(0, n_docs, n).astype(np.int32)
    w = rng.integers(0, n_vocab, n).astype(np.int32)
    isrc = jnp.asarray(ds * n_vocab + w)
    idst = jnp.asarray(dd * n_vocab + w)
    pair = pack_pair(ds.astype(np.uint32), dd.astype(np.uint32))
    ph, pl = split_key(pair)

    # Fillers spread over the full uint64 range so real pairs (small
    # hi) sort into the FIRST search tile and large fillers into later
    # ones; boost keys sit above 2^62 to land in the last tile.
    filler = np.unique(
        rng.integers(1 << 40, 1 << 62, 3 * _FILTER_SEARCH_TILE,
                     dtype=np.int64).astype(np.uint64))
    boost_hi = np.unique(
        rng.integers(-(1 << 61), -1, 64, dtype=np.int64)
        .view(np.uint64))
    filt = HostFilter.empty(0.5).merged(
        pair_suppress=np.concatenate([filler, pair[:40]]),
        pair_boost=np.concatenate([boost_hi, pair[100:140]]))
    tabs = filt.tables()
    assert tabs.pair_suppress[0].shape[0] > _FILTER_SEARCH_TILE

    tol, m = 0.5, 64
    ref = table_pair_bottom_k_filtered(
        table, isrc, idst, jnp.asarray(w), jnp.asarray(ph),
        jnp.asarray(pl), tabs, tol=tol, max_results=m)
    out = ps.fused_table_pair_bottom_k(
        table, isrc, idst, jnp.asarray(w), jnp.asarray(ph),
        jnp.asarray(pl), tabs, tol=tol, max_results=m)
    _assert_topk_equal(ref, out, "straddling filter")
    # The filter actually fired (suppressed pairs were live events).
    sup = np.flatnonzero(HostFilter.member(pair, filt.pair_suppress))
    fidx = set(np.asarray(out.indices)[np.asarray(out.indices) >= 0]
               .tolist())
    assert not (fidx & set(sup.tolist()))


def test_fused_all_padding_tile_and_zero_events():
    """A mask that zeroes an ENTIRE kernel tile (the all-padding tile
    case) and the n=0 degenerate (static empty TopK, matching
    _scan_bottom_k's n==0 path)."""
    import jax.numpy as jnp

    from onix.models.scoring import top_suspicious

    rng = np.random.default_rng(7)
    n = 2 * _SERVE_TILE
    theta, phi = _tables(rng, 50, 16, 4)
    d = rng.integers(0, 50, n).astype(np.int32)
    w = rng.integers(0, 16, n).astype(np.int32)
    mask = np.ones(n, np.float32)
    mask[_SERVE_TILE:] = 0.0               # tile 2 of 2: all padding
    ref = top_suspicious(jnp.asarray(theta), jnp.asarray(phi),
                         jnp.asarray(d), jnp.asarray(w),
                         jnp.asarray(mask), tol=1.0, max_results=20)
    out = ps.fused_top_suspicious(theta, phi, d, w, mask,
                                  tol=1.0, max_results=20)
    _assert_topk_equal(ref, out, "all-padding tile")

    empty = ps.fused_bottom_k_scores(np.zeros(0, np.float32),
                                     tol=1.0, max_results=8)
    assert np.all(np.asarray(empty.indices) == -1)
    assert np.all(np.isinf(np.asarray(empty.scores)))


def test_fused_fills_fewer_than_max_results():
    # Fewer qualifying events than M: +inf slots carry the -1 index
    # sentinel, exactly like _finalize_topk.
    scores = np.array([0.5, 0.1, 0.9, 0.1], np.float32)
    out = ps.fused_bottom_k_scores(scores, tol=0.6, max_results=8)
    np.testing.assert_array_equal(np.asarray(out.indices)[:3],
                                  [1, 3, 0])    # tie at 0.1: lower idx
    assert np.all(np.asarray(out.indices)[3:] == -1)
    assert np.all(np.isinf(np.asarray(out.scores)[3:]))


# ---------------------------------------------------------------------------
# The model bank's fused kernels (both forms, filtered + the static
# no-feedback fast path, zero-event tenant row).
# ---------------------------------------------------------------------------

def _bank_fixture(rng, B=4, D=64, V=32, K=6, R=4, N=200):
    import jax.numpy as jnp

    theta_bank = jnp.asarray(
        rng.dirichlet(np.ones(K), (B, D)).astype(np.float32))
    phi_bank = jnp.asarray(
        rng.dirichlet(np.ones(K), (B, V)).astype(np.float32))
    slots = jnp.asarray(np.array([2, 0, 3, 1], np.int32))
    d = rng.integers(0, D, (R, N)).astype(np.int32)
    w = rng.integers(0, V, (R, N)).astype(np.int32)
    m = np.ones((R, N), np.float32)
    m[1, N - 50:] = 0.0
    m[3, :] = 0.0                           # zero-event tenant row
    return theta_bank, phi_bank, slots, d, w, m


def _bank_filter_rows(rng, d, w, R):
    import jax.numpy as jnp

    def rows_for(keys_list, f_pad):
        rows = np.tile(_pad_sorted(np.empty(0, np.uint64), f_pad),
                       (R, 1))
        for r, keys in enumerate(keys_list):
            rows[r, :len(keys)] = keys
        hi, lo = split_key(rows.ravel())
        return (jnp.asarray(hi.reshape(R, -1)),
                jnp.asarray(lo.reshape(R, -1)))

    sup0 = np.unique(pack_pair(d[0, :10].astype(np.uint32),
                               w[0, :10].astype(np.uint32)))
    wb2 = np.unique(w[2, :5]).astype(np.uint64)
    return FilterTables(
        word_suppress=rows_for([[], [], [], []], 8),
        word_boost=rows_for([[], [], wb2, []], 8),
        pair_suppress=rows_for([sup0, [], [], []], 16),
        pair_boost=rows_for([[], [], [], []], 8),
        boost_scale=jnp.asarray(
            np.array([1.0, 1.0, 0.25, 1.0], np.float32)))


@pytest.mark.parametrize("filtered", [False, True])
def test_bank_fused_forms_bit_identical(filtered):
    import jax.numpy as jnp

    from onix.serving.model_bank import (_bank_score_gather,
                                         _bank_score_vmap)

    rng = np.random.default_rng(9)
    theta_bank, phi_bank, slots, d, w, m = _bank_fixture(rng)
    filt_rows = _bank_filter_rows(rng, d, w, 4) if filtered else None
    pairs = ((_bank_score_vmap, ps.bank_score_vmap_fused),
             (_bank_score_gather, ps.bank_score_gather_fused))
    for xla_kern, fused_kern in pairs:
        ref = xla_kern(theta_bank, phi_bank, slots, jnp.asarray(d),
                       jnp.asarray(w), jnp.asarray(m),
                       jnp.float32(0.08), filt_rows, max_results=20)
        out = fused_kern(theta_bank, phi_bank, slots, jnp.asarray(d),
                         jnp.asarray(w), jnp.asarray(m),
                         jnp.float32(0.08), filt_rows, max_results=20,
                         interpret=True)
        _assert_same_winners(ref, out, fused_kern.__name__)
        # Zero-event tenant row: all slots unfilled, sentinel indices.
        assert np.all(np.asarray(out.indices)[3] == -1)


def test_bank_serve_form_fused_end_to_end(monkeypatch):
    """ModelBank(serve_form=...) reaches the fused kernels through
    score_batch, winners identical to the xla bank, and the RESOLVED
    serve form lands in compiled_shapes (the manifest/bench stamp)."""
    from onix.serving.model_bank import ModelBank, ScoreRequest

    rng = np.random.default_rng(13)
    theta = rng.dirichlet(np.ones(5), 300).astype(np.float32)
    phi = rng.dirichlet(np.ones(5), 40).astype(np.float32)
    reqs = [ScoreRequest(tenant="t0",
                         doc_ids=rng.integers(0, 300, 500)
                         .astype(np.int32),
                         word_ids=rng.integers(0, 40, 500)
                         .astype(np.int32))
            for _ in range(3)]
    outs = {}
    for serve in ("xla", "fused"):
        bank = ModelBank(capacity=2, serve_form=serve)
        bank.add("t0", theta, phi)
        outs[serve] = bank.score_batch(reqs, tol=0.2, max_results=25)
        assert {k[1] for k in bank.compiled_shapes} == {serve}
    for a, b in zip(outs["xla"], outs["fused"]):
        _assert_same_winners(a, b, "bank serve_form")


# ---------------------------------------------------------------------------
# The serve-gated dispatchers + the streaming fused tail.
# ---------------------------------------------------------------------------

def test_rescore_fast_dispatchers_route_both_arms():
    import jax.numpy as jnp

    from onix.feedback.rescore import (
        table_bottom_k_filtered_fast, table_pair_bottom_k_filtered_fast,
        top_suspicious_filtered_fast)
    from onix.models.scoring import score_table

    rng = np.random.default_rng(17)
    n_docs, n_vocab, k, n = 200, 16, 4, 900
    theta, phi = _tables(rng, n_docs, n_vocab, k)
    table = score_table(jnp.asarray(theta), jnp.asarray(phi)).ravel()
    d = rng.integers(0, n_docs, n).astype(np.int32)
    d2 = rng.integers(0, n_docs, n).astype(np.int32)
    w = rng.integers(0, n_vocab, n).astype(np.int32)
    pair = pack_pair(d.astype(np.uint32), d2.astype(np.uint32))
    ph, pl = split_key(pair)
    filt = HostFilter.empty().merged(pair_suppress=pair[::7]).tables()
    kw = dict(tol=0.4, max_results=16)

    a = table_pair_bottom_k_filtered_fast(
        table, jnp.asarray(d * n_vocab + w), jnp.asarray(d2 * n_vocab + w),
        jnp.asarray(w), jnp.asarray(ph), jnp.asarray(pl), filt,
        serve_form="xla", **kw)
    b = table_pair_bottom_k_filtered_fast(
        table, jnp.asarray(d * n_vocab + w), jnp.asarray(d2 * n_vocab + w),
        jnp.asarray(w), jnp.asarray(ph), jnp.asarray(pl), filt,
        serve_form="fused", **kw)
    _assert_topk_equal(a, b, "pair dispatcher")

    a = table_bottom_k_filtered_fast(
        table, jnp.asarray(d * n_vocab + w), jnp.asarray(w),
        jnp.asarray(ph), jnp.asarray(pl), filt, serve_form="xla", **kw)
    b = table_bottom_k_filtered_fast(
        table, jnp.asarray(d * n_vocab + w), jnp.asarray(w),
        jnp.asarray(ph), jnp.asarray(pl), filt, serve_form="fused", **kw)
    _assert_topk_equal(a, b, "single dispatcher")

    mask = np.ones(n, np.float32)
    a = top_suspicious_filtered_fast(
        jnp.asarray(theta), jnp.asarray(phi), jnp.asarray(d),
        jnp.asarray(w), jnp.asarray(mask), jnp.asarray(ph),
        jnp.asarray(pl), filt, serve_form="xla", **kw)
    b = top_suspicious_filtered_fast(
        jnp.asarray(theta), jnp.asarray(phi), jnp.asarray(d),
        jnp.asarray(w), jnp.asarray(mask), jnp.asarray(ph),
        jnp.asarray(pl), filt, serve_form="fused", **kw)
    _assert_topk_equal(a, b, "top_suspicious dispatcher")


def _flow_batch(seed, n=1200):
    import pandas as pd

    from onix.pipelines.synth import synth_flow_day
    t, _ = synth_flow_day(n_events=n, n_hosts=80, n_anomalies=0,
                          seed=seed)
    rows = t.iloc[:3].copy()
    rows["sip"] = "10.66.66.66"
    rows["dip"] = "203.0.113.99"
    rows["sport"] = 44123
    rows["dport"] = 51789
    rows["proto"] = "TCP"
    rows["ipkt"] = 2
    rows["ibyt"] = 99
    rows["treceived"] = "2016-07-08 03:33:00"
    return pd.concat([t, rows], ignore_index=True)


def test_streaming_fused_tail_matches_host_tail():
    """The streaming consumer: serve_form='fused' routes winner
    selection through the one-kernel tail; winners, order and scores
    match the host tail batch for batch — no filter, then with a live
    dismissal (the default dyadic boost_scale, where the f32 kernel
    tail is exact against the float64 host tail)."""
    from onix.pipelines.streaming import StreamingScorer
    from onix.utils.obs import counters

    cfg_x = OnixConfig()
    cfg_x.validate()
    cfg_f = OnixConfig()
    cfg_f.serving.serve_form = "fused"
    cfg_f.validate()
    a = StreamingScorer(cfg_x, "flow", n_buckets=1 << 10)
    b = StreamingScorer(cfg_f, "flow", n_buckets=1 << 10)
    base = counters.get("serve.fused_tail")
    for seed in (0, 1):
        ra = a.process(_flow_batch(seed))
        rb = b.process(_flow_batch(seed))
        np.testing.assert_array_equal(ra.scores, rb.scores)
        assert (ra.alerts["event_idx"].tolist()
                == rb.alerts["event_idx"].tolist())
    # Batch 1 rides the host word path (edges not yet frozen, so the
    # device flow layout — the fused tail's gate condition — is not
    # up); every later batch goes through the kernel.
    assert counters.get("serve.fused_tail") - base >= 1

    # Dismiss the beacon on BOTH scorers; the fused tail must suppress
    # it identically (filter + min + pair adjust inside the kernel).
    for sc, res in ((a, ra), (b, rb)):
        m = ((res.alerts["sip"] == "10.66.66.66")
             & (res.alerts["dip"] == "203.0.113.99"))
        rows = res.alerts[m].drop(columns=["score", "event_idx"])
        assert len(rows) > 0
        sc.apply_feedback(rows, np.full(len(rows), 3), immediate=True,
                          online=False)
    rbase = counters.get("feedback.rescored_events")
    ra = a.process(_flow_batch(2))
    host_delta = counters.get("feedback.rescored_events") - rbase
    rb = b.process(_flow_batch(2))
    fused_delta = (counters.get("feedback.rescored_events") - rbase
                   - host_delta)
    np.testing.assert_array_equal(ra.scores, rb.scores)
    assert (ra.alerts["event_idx"].tolist()
            == rb.alerts["event_idx"].tolist())
    assert not ((rb.alerts["sip"] == "10.66.66.66")
                & (rb.alerts["dip"] == "203.0.113.99")).any()
    # Flipping the arm must not zero the r13 monitoring counter: the
    # fused tail counts the SAME newly-pair-suppressed events.
    assert host_delta > 0 and fused_delta == host_delta


@pytest.mark.tpu
def test_fused_serve_compiled_bit_identical_on_tpu():
    """Compiled-Mosaic identity: the same asserts as the interpret
    tests, on a real TPU where the kernel compiles instead of
    emulating — including the compare-sweep membership and the
    rank-merge scatter, whose Mosaic lowerings are exactly what this
    test decides. Auto-skipped off-TPU (conftest `tpu` marker hook)."""
    import jax.numpy as jnp

    from onix.feedback.rescore import table_pair_bottom_k_filtered
    from onix.models.scoring import score_table, table_pair_bottom_k

    rng = np.random.default_rng(21)
    n_docs, n_vocab, k, n = 20_000, 512, 20, 1 << 18
    theta, phi = _tables(rng, n_docs, n_vocab, k)
    table = score_table(jnp.asarray(theta), jnp.asarray(phi)).ravel()
    ds = rng.integers(0, n_docs, n).astype(np.int32)
    dd = rng.integers(0, n_docs, n).astype(np.int32)
    w = rng.integers(0, n_vocab, n).astype(np.int32)
    isrc = jnp.asarray(ds * n_vocab + w)
    idst = jnp.asarray(dd * n_vocab + w)
    pair = pack_pair(ds.astype(np.uint32), dd.astype(np.uint32))
    ph, pl = split_key(pair)
    filt = HostFilter.empty().merged(pair_suppress=pair[::97]).tables()

    ref_u = table_pair_bottom_k(table, isrc, idst, tol=1.0,
                                max_results=200)
    out_u = ps.fused_table_pair_bottom_k(table, isrc, idst, tol=1.0,
                                         max_results=200,
                                         interpret=False)
    _assert_topk_equal(ref_u, out_u, "compiled unfiltered")
    ref_f = table_pair_bottom_k_filtered(
        table, isrc, idst, jnp.asarray(w), jnp.asarray(ph),
        jnp.asarray(pl), filt, tol=1.0, max_results=200)
    out_f = ps.fused_table_pair_bottom_k(
        table, isrc, idst, jnp.asarray(w), jnp.asarray(ph),
        jnp.asarray(pl), filt, tol=1.0, max_results=200,
        interpret=False)
    _assert_topk_equal(ref_f, out_f, "compiled filtered")


def _judged_request(rng):
    """One /score-sized request at the judged width: 4096 events,
    K=20, a product-size vocabulary."""
    n_docs, n_vocab, k, n = 20_000, 512, 20, 4096
    theta, phi = _tables(rng, n_docs, n_vocab, k)
    ds = rng.integers(0, n_docs, n).astype(np.int32)
    dd = rng.integers(0, n_docs, n).astype(np.int32)
    w = rng.integers(0, n_vocab, n).astype(np.int32)
    return theta, phi, ds, dd, w


@pytest.mark.tpu
def test_fused_serve_compiled_judged_width_min2_on_tpu():
    """The fused kernel through Mosaic at the JUDGED serving width —
    4096 events, max_results 1000 — in `min2` mode (the flow pair-table
    path), unfiltered and filtered, bit-identical to the XLA scans on
    the same chip."""
    import jax.numpy as jnp

    from onix.feedback.rescore import table_pair_bottom_k_filtered
    from onix.models.scoring import score_table, table_pair_bottom_k

    theta, phi, ds, dd, w = _judged_request(np.random.default_rng(31))
    n_vocab = phi.shape[0]
    table = score_table(jnp.asarray(theta), jnp.asarray(phi)).ravel()
    isrc = jnp.asarray(ds * n_vocab + w)
    idst = jnp.asarray(dd * n_vocab + w)
    pair = pack_pair(ds.astype(np.uint32), dd.astype(np.uint32))
    ph, pl = split_key(pair)
    filt = HostFilter.empty().merged(pair_suppress=pair[::97]).tables()

    ref_u = table_pair_bottom_k(table, isrc, idst, tol=1.0,
                                max_results=1000)
    out_u = ps.fused_table_pair_bottom_k(table, isrc, idst, tol=1.0,
                                         max_results=1000,
                                         interpret=False)
    _assert_topk_equal(ref_u, out_u, "min2 unfiltered")
    ref_f = table_pair_bottom_k_filtered(
        table, isrc, idst, jnp.asarray(w), jnp.asarray(ph),
        jnp.asarray(pl), filt, tol=1.0, max_results=1000)
    out_f = ps.fused_table_pair_bottom_k(
        table, isrc, idst, jnp.asarray(w), jnp.asarray(ph),
        jnp.asarray(pl), filt, tol=1.0, max_results=1000,
        interpret=False)
    _assert_topk_equal(ref_f, out_f, "min2 filtered")


@pytest.mark.tpu
def test_fused_serve_compiled_judged_width_dot_on_tpu():
    """Same width, `dot` mode (the bank's per-request path): the kernel
    takes the K-term product-sum itself, so its accumulation order is
    Mosaic's while the XLA arm's is XLA's. On the chip the two agree on
    the winners and their order and differ by up to 2 ulp in the scores
    (PR 21: 345 of 1000 winners, PERF.md) — the same kind of drift the
    interpret-mode tests above show on CPU under jax 0.9.0, where the
    XLA arm's fused gather-dot is FMA-contracted. Bit-identity, the r15
    contract, does NOT hold in this mode; this test pins what does
    (same winners, scores within 4 ulp)."""
    import jax.numpy as jnp

    from onix.feedback.rescore import top_suspicious_filtered
    from onix.models.scoring import top_suspicious

    theta, phi, d, _, w = _judged_request(np.random.default_rng(32))
    mask = np.ones(d.shape[0], np.float32)
    pair = pack_pair(d.astype(np.uint32), w.astype(np.uint32))
    ph, pl = split_key(pair)
    filt = HostFilter.empty().merged(pair_suppress=pair[::97]).tables()
    args = [jnp.asarray(a) for a in (theta, phi, d, w, mask)]

    def assert_same_winners(ref, out, msg):
        np.testing.assert_array_equal(np.asarray(ref.indices),
                                      np.asarray(out.indices),
                                      err_msg=f"{msg} indices")
        np.testing.assert_array_max_ulp(np.asarray(ref.scores),
                                        np.asarray(out.scores), maxulp=4)

    ref_u = top_suspicious(*args, tol=1.0, max_results=1000)
    out_u = ps.fused_top_suspicious(*args, tol=1.0, max_results=1000,
                                    interpret=False)
    assert_same_winners(ref_u, out_u, "dot unfiltered")
    ref_f = top_suspicious_filtered(*args, jnp.asarray(ph),
                                    jnp.asarray(pl), filt, tol=1.0,
                                    max_results=1000)
    out_f = ps.fused_top_suspicious(*args, jnp.asarray(ph),
                                    jnp.asarray(pl), filt, tol=1.0,
                                    max_results=1000, interpret=False)
    assert_same_winners(ref_f, out_f, "dot filtered")
    n_diff = int((np.asarray(ref_u.scores) != np.asarray(out_u.scores))
                 .sum())
    print(f"dot mode: {n_diff} of 1000 unfiltered winner scores differ "
          "from the XLA arm")
