"""The resident superstep (`streaming.stream_svi_step`: the gamma store,
the document table and the selection on the device) held to the host
path of the same scorer, `process` per batch, on the same batches at a
tiny shape: same winners, lambda and gamma within the float32 tolerance
of a different summation order (the resident path dedupes the tokens
on the device, pairs word by word; the host path with `np.unique`)."""

import dataclasses as dc
import time

import numpy as np
import pytest

from onix.config import OnixConfig
from onix.pipelines.streaming import (BatchResult, StreamingScorer,
                                      U32DocTable, run_stream)
from onix.pipelines.synth import synth_flow_day

N_BUCKETS = 1 << 11


def _cfg(superstep: int = 0, estep: str = "svi", **pipeline) -> OnixConfig:
    cfg = OnixConfig()
    cfg.lda.n_topics = 6
    cfg.lda.svi_tau0 = 1.0
    return dc.replace(
        cfg, lda=dc.replace(cfg.lda, stream_estep=estep),
        pipeline=dc.replace(cfg.pipeline, stream_superstep=superstep,
                            tol=0.25, **pipeline)).validate()


def _chunks(n_chunks: int, size: int = 500, n_hosts: int = 60,
            seed: int = 33):
    table, _ = synth_flow_day(n_events=n_chunks * size, n_hosts=n_hosts,
                              n_anomalies=3 * n_chunks, seed=seed)
    return [table.iloc[i * size:(i + 1) * size].reset_index(drop=True)
            for i in range(n_chunks)]


def _winners(res: BatchResult) -> list:
    return res.alerts["event_idx"].tolist()


def _assert_same_stream(res_a, res_b):
    any_alerts = False
    for a, b in zip(res_a, res_b):
        # The same events; their order may differ where two scores tie
        # to the last bits (the two paths sum in another order).
        assert set(_winners(a)) == set(_winners(b))
        any_alerts = any_alerts or bool(len(a.alerts))
        np.testing.assert_allclose(b.scores, a.scores, rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(b.alerts["score"].to_numpy(),
                                   a.alerts["score"].to_numpy(),
                                   rtol=1e-4, atol=1e-6)
        assert (a.n_events, a.step) == (b.n_events, b.step)
    assert any_alerts, "feed produced no alerts: parity was vacuous"


def _spans_since(t: float) -> list:
    """Names of the spans opened since `t` (`time.monotonic()`). The
    store is left whole: clearing it would read as dropped spans to the
    benchmark's readers later in the process."""
    from onix.utils import telemetry
    return [s.name for s in telemetry.TRACER.spans() if s.t0 >= t]


def _assert_same_state(host: StreamingScorer, res: StreamingScorer):
    """lambda, and every document's gamma and last-seen stamp, once the
    resident scorer's state is back in its host arrays."""
    host._pull_resident()
    res._pull_resident()
    n = host.docs.n_docs
    assert n == res.docs.n_docs
    np.testing.assert_array_equal(host.docs.keys, res.docs.keys)
    np.testing.assert_allclose(np.asarray(res.state.lam),
                               np.asarray(host.state.lam),
                               rtol=2e-4, atol=1e-6)
    np.testing.assert_allclose(res._gamma[:n], host._gamma[:n],
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_array_equal(res._last_seen[:n], host._last_seen[:n])


@pytest.mark.parametrize("estep", ["svi", "scvb0"])
def test_resident_matches_host_path(estep):
    """Seven batches, S=3: the first on the host path (the edges fit),
    two resident runs, and a last group of one."""
    chunks = _chunks(7)
    host = StreamingScorer(_cfg(0, estep), "flow", n_buckets=N_BUCKETS)
    res_a = [host.process(c) for c in chunks]
    res = StreamingScorer(_cfg(3, estep), "flow", n_buckets=N_BUCKETS)
    res_b = res.process_many([(c, None) for c in chunks])
    _assert_same_stream(res_a, res_b)
    _assert_same_state(host, res)
    assert res.dispatches["superstep"] == 3
    assert [r.n_new_docs for r in res_a] == [r.n_new_docs for r in res_b]
    assert res.events_seen == host.events_seen == 3500


def test_resident_counts_the_pairs_the_host_path_counts():
    """The resident superstep dedupes a batch's tokens on the device:
    `pair_rows` and the counter `stream.pair_rows` read what the host
    path's `np.unique` reads for the same batches, fewer than the
    tokens; `stream.active_pairs` the rows of the extended loops."""
    from onix.utils.obs import counters

    chunks = _chunks(7)
    host = StreamingScorer(_cfg(0), "flow", n_buckets=N_BUCKETS)
    host.process(chunks[0])
    first = host.pair_rows
    for c in chunks[1:]:
        host.process(c)
    assert first < host.pair_rows < 2 * 3500    # duplicates in the feed

    res = StreamingScorer(_cfg(3), "flow", n_buckets=N_BUCKETS)
    at = {c: counters.get(c) for c in ("stream.pair_rows",
                                       "stream.active_pairs",
                                       "stream.active_tokens")}
    res.process_many([(c, None) for c in chunks])
    assert res.dispatches["superstep"] == 3
    assert res.pair_rows == host.pair_rows
    got = {c: counters.get(c) - v for c, v in at.items()}
    # The first batch took the host path: the counters are the resident
    # path's own.
    assert got["stream.pair_rows"] == host.pair_rows - first
    assert res.last_estep_stats.shape == (1, 5)     # the last group of one
    assert 0 < got["stream.active_pairs"] <= got["stream.pair_rows"]
    assert got["stream.active_pairs"] < got["stream.active_tokens"] \
        <= 2 * 3000


def test_resident_in_runs(monkeypatch):
    """The look-up, the token passes and the selection a run at a time
    (what a 2^22-event batch does on the chip) give the answers of one
    run."""
    from onix.models import lda_svi
    from onix.pipelines import streaming

    chunks = _chunks(5)
    whole = StreamingScorer(_cfg(2), "flow", n_buckets=N_BUCKETS)
    res_a = whole.process_many([(c, None) for c in chunks])
    monkeypatch.setattr(lda_svi, "_TOKEN_RUN", 128)
    monkeypatch.setattr(streaming, "_DOCS_RUN", 256)
    monkeypatch.setattr(streaming, "_SELECT_CHUNK", 128)
    runs = StreamingScorer(_cfg(2), "flow", n_buckets=N_BUCKETS)
    # Another tolerance makes another program: the patched sizes are
    # read when it is traced.
    runs._step_kw["tol"] = 0.25 + 1e-9
    res_b = runs.process_many([(c, None) for c in chunks])
    _assert_same_stream(res_a, res_b)
    _assert_same_state(whole, runs)


@pytest.mark.parametrize("estep", ["svi", "scvb0"])
def test_resident_packed_matches_rows_form(monkeypatch, estep):
    """The resident superstep with the E-step's gamma packed G documents
    a 128-lane row (16 at K=6, the chip's form) and with it one document
    a row (the CPU's): the same winners, the same passes, lambda and
    gamma to float32 rounding, and the store it carries and hands back
    [cap, K]."""
    from onix.models import lda_gibbs, lda_svi
    from onix.utils.obs import counters

    def passes(scorer):
        at = counters.get("stream.estep_iters")
        out = scorer.process_many([(c, None) for c in chunks])
        return out, counters.get("stream.estep_iters") - at

    assert lda_gibbs._ndk_group(6) == 16
    chunks = _chunks(7)
    monkeypatch.setattr(lda_svi, "_gamma_group", lda_gibbs._ndk_group)
    packed = StreamingScorer(_cfg(3, estep), "flow", n_buckets=N_BUCKETS)
    # Another tolerance makes another program (and not one that another
    # test traces): the patched form is read when it is traced.
    packed._step_kw["tol"] = 0.25 + 3e-9
    res_a, passes_a = passes(packed)
    stats_a = packed.last_estep_stats.copy()
    cap = packed._res.store.shape[0]
    assert packed._res.store.shape == (cap, 6)
    monkeypatch.setattr(lda_svi, "_gamma_group", lambda k: 1)
    rows = StreamingScorer(_cfg(3, estep), "flow", n_buckets=N_BUCKETS)
    rows._step_kw["tol"] = 0.25 + 2e-9
    res_b, passes_b = passes(rows)
    _assert_same_stream(res_a, res_b)
    assert passes_a == passes_b > 0
    np.testing.assert_array_equal(rows.last_estep_stats, stats_a)
    assert rows._res.store.shape == (cap, 6)
    _assert_same_state(packed, rows)


@pytest.mark.parametrize("table", ["store", "one_round", "rounds"])
def test_resident_counts_the_working_table(monkeypatch, table):
    """The counters `stream.estep_rounds` and `stream.estep_docs` read
    each resident batch's rounds of the E-step's working table and its
    documents (its distinct addresses): no round where the store is
    within `lda_svi._WORKING_ROWS` rows, one where the batch's documents
    fit the table and the store does not - the store path's answers and
    state, to float32 rounding - and one per table of them past that."""
    from onix.models import lda_svi
    from onix.utils.obs import counters

    chunks = _chunks(7)
    docs = [len(set(c.sip) | set(c.dip)) for c in chunks[1:]]
    store = StreamingScorer(_cfg(3), "flow", n_buckets=N_BUCKETS)
    res_store = store.process_many([(c, None) for c in chunks])
    cap = store._res.store.shape[0]
    assert max(docs) < cap <= lda_svi._WORKING_ROWS
    rounds = [0] * len(docs)
    res = StreamingScorer(_cfg(3), "flow", n_buckets=N_BUCKETS)
    if table != "store":
        slots = max(docs) if table == "one_round" else 32
        assert slots < cap
        monkeypatch.setattr(lda_svi, "_WORKING_ROWS", slots)
        rounds = [-(-n // slots) for n in docs]
        # Another tolerance makes another program: the patched size is
        # read when it is traced.
        res._step_kw["tol"] = 0.25 + {"one_round": 4e-9, "rounds": 5e-9}[
            table]
    at = {c: counters.get(c) for c in ("stream.estep_rounds",
                                       "stream.estep_docs")}
    res_b = res.process_many([(c, None) for c in chunks])
    got = {c: counters.get(c) - v for c, v in at.items()}
    assert got["stream.estep_docs"] == sum(docs)
    assert got["stream.estep_rounds"] == sum(rounds)
    assert (table == "rounds") == (max(rounds) > 1)
    if table == "one_round":
        _assert_same_stream(res_store, res_b)
        _assert_same_state(store, res)


def test_resident_scores_are_fetched_when_read():
    chunks = _chunks(4)
    res = StreamingScorer(_cfg(3), "flow", n_buckets=N_BUCKETS)
    out = res.process_many([(c, None) for c in chunks])
    assert callable(out[1]._scores)             # still on the device
    scores = out[1].scores
    assert scores.dtype == np.float64 and scores.shape == (500,)
    assert out[1].scores is scores              # fetched once
    top = out[1].alerts
    np.testing.assert_array_equal(top["score"].to_numpy(),
                                  scores[top["event_idx"].to_numpy()])
    assert (np.diff(top["score"].to_numpy()) >= 0).all()
    assert (top["score"] < 0.25).all()


def test_resident_new_addresses_mid_stream():
    """Hosts that first appear in the middle of a group, in the middle
    of the stream, and enough of them to outgrow the store's rows: the
    table grows on the host, the rows start cold, the answers are the
    host path's."""
    early = _chunks(4, n_hosts=40, seed=5)
    late = _chunks(5, n_hosts=400, seed=6)      # 10.0.x.y: a superset
    chunks = early + late
    host = StreamingScorer(_cfg(0), "flow", n_buckets=N_BUCKETS)
    res_a = [host.process(c) for c in chunks]
    res = StreamingScorer(_cfg(3), "flow", n_buckets=N_BUCKETS)
    res_b = res.process_many([(c, None) for c in chunks])
    _assert_same_stream(res_a, res_b)
    _assert_same_state(host, res)
    new = [r.n_new_docs for r in res_b]
    assert new == [r.n_new_docs for r in res_a]
    assert sum(n > 0 for n in new[4:]) >= 2     # growth inside late groups
    assert res._res.store.shape[0] > 256        # the rows ran out once
    assert res.docs.n_docs > 256


def test_resident_ineligible_batch_falls_back():
    """A batch with an IPv6 address in the middle of a group takes the
    host path in its turn (the table flips to string keys, one way) and
    the stream goes on there; the per-event scores stay the per-batch
    scorer's."""
    chunks = _chunks(6)
    odd = chunks[3].copy()
    odd.loc[7, "sip"] = "2001:db8::7"
    chunks[3] = odd
    host = StreamingScorer(_cfg(0), "flow", n_buckets=N_BUCKETS)
    res_a = [host.process(c) for c in chunks]
    res = StreamingScorer(_cfg(3), "flow", n_buckets=N_BUCKETS)
    res_b = res.process_many([(c, None) for c in chunks])
    _assert_same_stream(res_a, res_b)
    assert res.dispatches["superstep"] == 1     # batches 1 and 2 alone
    assert res._res is None
    assert not isinstance(res.docs, U32DocTable)
    n = host.docs.n_docs
    assert host.docs.keys == res.docs.keys
    np.testing.assert_allclose(res._gamma[:n], host._gamma[:n],
                               rtol=2e-3, atol=2e-3)


def test_resident_host_words_and_filter_keep_the_host_path(monkeypatch):
    chunks = _chunks(4)
    monkeypatch.setenv("ONIX_HOST_WORDS", "1")
    sc = StreamingScorer(_cfg(2), "flow", n_buckets=N_BUCKETS)
    sc.process_many([(c, None) for c in chunks])
    assert sc.dispatches["superstep"] == 0
    assert sc.dispatches["svi_update"] == 4
    monkeypatch.delenv("ONIX_HOST_WORDS")
    sc = StreamingScorer(_cfg(2), "flow", n_buckets=N_BUCKETS)
    out = sc.process_many([(c, None) for c in chunks[:2]])
    assert sc.dispatches["superstep"] == 1
    sc.apply_feedback(out[1].alerts.drop(columns=["score", "event_idx"]),
                      np.full(len(out[1].alerts), 3), immediate=True,
                      online=False)
    sc.process_many([(c, None) for c in chunks[2:]])
    assert sc.dispatches["superstep"] == 1      # the filter's r13 tail
    assert sc.dispatches["svi_update"] == 3


def test_resident_checkpoint_resumes_to_the_same_winners(tmp_path):
    """A checkpoint taken after a resident superstep holds the device's
    store; a scorer resumed from it goes on to the winners the first
    one goes on to."""
    chunks = _chunks(9)
    cfg = _cfg(2)
    cfg.lda.checkpoint_every = 5
    one = StreamingScorer(cfg, "flow", n_buckets=N_BUCKETS,
                          checkpoint_dir=tmp_path / "ck")
    one.process_many([(c, None) for c in chunks[:5]])
    assert one._res is not None                 # still resident
    two = StreamingScorer(cfg, "flow", n_buckets=N_BUCKETS,
                          checkpoint_dir=tmp_path / "ck")
    assert two._batch_no == 5 and two._res is None
    cfg.lda.checkpoint_every = 0
    res_a = one.process_many([(c, None) for c in chunks[5:]])
    res_b = two.process_many([(c, None) for c in chunks[5:]])
    for a, b in zip(res_a, res_b):
        assert _winners(a) == _winners(b)
        np.testing.assert_allclose(b.scores, a.scores, rtol=1e-6, atol=0)
    assert any(len(r.alerts) for r in res_a)


def test_resident_eviction_bounds_the_store():
    """With max_docs set the least recently seen quarter goes at a
    superstep's boundary: the device's stamps decide, the rows are
    compacted on the host and pushed again."""
    chunks = _chunks(4, n_hosts=40, seed=5) + _chunks(4, n_hosts=400, seed=6)
    sc = StreamingScorer(_cfg(2), "flow", n_buckets=N_BUCKETS, max_docs=200)
    out = sc.process_many([(c, None) for c in chunks])
    assert sc.docs.n_docs <= 200 + 1
    assert len(out) == 8 and all(r.n_events == 500 for r in out)
    sc._pull_resident()
    n = sc.docs.n_docs
    assert (sc._last_seen[:n] > 0).all()
    assert np.isfinite(sc._gamma[:n]).all() and (sc._gamma[:n] > 0).all()


def _churn_chunks(n_chunks: int, fresh: int, seed: int = 33):
    """`_chunks`, with `fresh` events of every chunk sent from an address
    no other chunk holds (11.<chunk>.x.y): a table that grows by about
    `fresh` documents a batch while the 60 known hosts stay recent."""
    out = []
    for b, c in enumerate(_chunks(n_chunks, seed=seed)):
        c = c.copy()
        c.loc[:fresh - 1, "sip"] = [f"11.{b}.{i // 256}.{i % 256}"
                                    for i in range(fresh)]
        out.append(c)
    return out


def _evictions(sc: StreamingScorer) -> list:
    """The table's keys, stamps and rows each time an eviction fires,
    read where it compacts the table (the state pulled to the host)."""
    seen, compact = [], sc.docs.compact

    def spy(keep_mask):
        n = sc.docs.n_docs
        seen.append((sc.docs.keys.copy(), sc._last_seen[:n].copy(),
                     sc._gamma[:n].copy(), sc._batch_no))
        return compact(keep_mask)
    sc.docs.compact = spy
    return seen


def test_resident_eviction_keeps_the_latest_ties_to_the_lower_id():
    """The survivors of an eviction are the 0.75 max_docs documents seen
    last, those seen in the batch of the last survivor's sighting by
    ascending id; they keep first-seen order under new ids, their rows
    and stamps bit for bit, and every row past them holds the cold
    start."""
    import jax.numpy as jnp
    sc = StreamingScorer(_cfg(2), "flow", n_buckets=N_BUCKETS)
    sc.process_many([(c, None) for c in _churn_chunks(4, 40)])
    sc._pull_resident()
    n, cap = sc.docs.n_docs, sc._res.store.shape[0]
    stamps = np.random.default_rng(3).integers(1, 5, n)     # many ties
    sc._res.last_seen = jnp.asarray(
        np.pad(stamps, (0, cap - n)).astype(np.int32))
    keys0, rows0 = sc.docs.keys.copy(), np.asarray(sc._res.store)[:n].copy()
    sc.max_docs = n - 1
    target = int(0.75 * (n - 1))
    assert sc._maybe_evict() == n - target
    keep = np.flatnonzero(np.isin(keys0, sc.docs.keys))
    assert len(keep) == target
    np.testing.assert_array_equal(sc.docs.keys, keys0[keep])
    gone = np.setdiff1d(np.arange(n), keep)
    cut = stamps[keep].min()
    assert stamps[gone].max() <= cut
    assert keep[stamps[keep] == cut].max() < gone[stamps[gone] == cut].min()
    snap = sc.snapshot_resident()           # the compacted state pushed
    store, seen = np.asarray(snap["store"]), np.asarray(snap["last_seen"])
    np.testing.assert_array_equal(store[:target], rows0[keep])
    np.testing.assert_array_equal(seen[:target], stamps[keep])
    assert (store[target:] == np.float32(sc.cfg.lda.alpha + 1.0)).all()


def test_resident_evicted_address_returns_under_a_new_id_cold():
    """An address the eviction dropped that comes back is a new
    document: the next id, and the cold start before its batch."""
    chunks = _churn_chunks(6, 40)
    sc = StreamingScorer(_cfg(2), "flow", n_buckets=N_BUCKETS, max_docs=150)
    fired = _evictions(sc)
    sc.process_many([(c, None) for c in chunks[:4]])
    assert fired
    keys0 = fired[-1][0]
    gone = np.setdiff1d(keys0, sc.docs.keys)
    assert len(gone)
    from onix.pipelines.words import u32_to_ips
    back = chunks[5].copy()
    back.loc[len(back) - 1, "sip"] = u32_to_ips(gone[:1])[0]
    n_before = sc.docs.n_docs
    sc.process_many([(chunks[4], None), (back, None)])
    # Its token's store row in the group, whatever an eviction at the
    # group's end did to the ids after it.
    new_id = int(np.asarray(sc.before_last_batch[2])[len(back) - 1])
    assert new_id >= n_before
    store = np.asarray(sc.before_last_batch[1])
    assert (store[new_id] == np.float32(sc.cfg.lda.alpha + 1.0)).all()


def test_resident_regrow_starts_new_documents_cold():
    """A group whose new addresses outgrow the store's rows: the store
    regrows before the group runs, and every document first seen in its
    last batch - those past the old capacity too - starts from alpha + 1."""
    early = _chunks(3, n_hosts=40, seed=5)
    late = _chunks(2, n_hosts=400, seed=6)
    sc = StreamingScorer(_cfg(2), "flow", n_buckets=N_BUCKETS)
    sc.process_many([(c, None) for c in early[:1] + [early[1]]])
    cap = sc._res.store.shape[0]
    out = sc.process_many([(early[2], None), (late[0], None)])
    assert sc._res.store.shape[0] > cap             # the rows ran out
    first_new = sc.docs.n_docs - out[-1].n_new_docs
    assert sc.docs.n_docs > cap and first_new < cap
    store = np.asarray(sc.before_last_batch[1])
    fresh = store[first_new:sc.docs.n_docs]
    assert (fresh == np.float32(sc.cfg.lda.alpha + 1.0)).all()


def test_resident_eviction_matches_host_path_where_boundaries_meet():
    """A table that crosses max_docs at the last batch of a group, each
    time: the per-batch host path evicts after the same batches, and the
    winners, stamps, keys and rows are the same."""
    chunks = _churn_chunks(6, 100, seed=8)
    host = StreamingScorer(_cfg(0), "flow", n_buckets=N_BUCKETS, max_docs=450)
    host_fired = _evictions(host)
    res_a = [host.process(c) for c in chunks]
    res = StreamingScorer(_cfg(2), "flow", n_buckets=N_BUCKETS, max_docs=450)
    res_fired = _evictions(res)
    res_b = res.process_many([(c, None) for c in chunks])
    assert [f[3] for f in host_fired] == [f[3] for f in res_fired] == [4, 6]
    for a, b in zip(host_fired, res_fired):
        np.testing.assert_array_equal(a[0], b[0])
        np.testing.assert_array_equal(a[1], b[1])
    _assert_same_stream(res_a, res_b)
    _assert_same_state(host, res)


def test_restore_resident_puts_the_table_back_with_the_state():
    """A snapshot carries the document table: a group that grew the
    table and evicted, run again from the snapshot taken before it,
    gives the same winners, table, stamps and rows."""
    chunks = _churn_chunks(6, 60, seed=12)
    sc = StreamingScorer(_cfg(2), "flow", n_buckets=N_BUCKETS, max_docs=150)
    sc.process_many([(c, None) for c in chunks[:2]])
    snap = sc.snapshot_resident()
    cap, n = snap["store"].shape[0], len(snap["doc_keys"])
    fired = _evictions(sc)
    first = sc.process_many([(c, None) for c in chunks[2:4]])
    once = sc.snapshot_resident()
    assert len(fired) == 1 and len(fired[0][0]) > n
    sc.restore_resident(snap)
    np.testing.assert_array_equal(sc.docs.keys, snap["doc_keys"])
    assert sc._res.store.shape[0] == cap
    again = sc.process_many([(c, None) for c in chunks[2:4]])
    assert [_winners(r) for r in first] == [_winners(r) for r in again]
    twice = sc.snapshot_resident()
    np.testing.assert_array_equal(twice["doc_keys"], once["doc_keys"])
    for name in ("store", "last_seen"):
        np.testing.assert_array_equal(np.asarray(twice[name]),
                                      np.asarray(once[name]))


def test_rows_no_document_owns_hold_the_cold_start():
    """One fill value, alpha + 1, for every row past the table: at
    construction, after a regrow and after an eviction."""
    cold = np.float32(_cfg().lda.alpha + 1.0)
    sc = StreamingScorer(_cfg(0), "flow", n_buckets=N_BUCKETS, max_docs=150)
    assert (sc._gamma == cold).all()
    for c in _churn_chunks(4, 60, seed=13):
        sc.process(c)
        n = sc.docs.n_docs
        assert (sc._gamma[n:] == cold).all()
    assert sc.docs.n_docs <= 150


def test_resident_stages_the_next_group_ahead():
    """`stage_next` (what run_stream passes) is staged and probed under
    the running superstep and used by the next call; the answers are
    those of a call without it."""
    chunks = _chunks(7)
    a = StreamingScorer(_cfg(3), "flow", n_buckets=N_BUCKETS)
    res_a = a.process_many([(c, None) for c in chunks])
    b = StreamingScorer(_cfg(3), "flow", n_buckets=N_BUCKETS)
    groups = [[(c, None) for c in chunks[i:i + 3]] for i in (0, 3, 6)]
    t0 = time.monotonic()
    res_b = b.process_many(groups[0], stage_next=groups[1])
    staged = b._staged
    assert staged is not None and staged.probe is not None
    res_b += b.process_many(groups[1], stage_next=groups[2])
    assert b._staged is not None and b._staged is not staged
    res_b += b.process_many(groups[2])
    assert b._staged is None
    _assert_same_stream(res_a, res_b)
    names = _spans_since(t0)
    assert names.count("stream.stage") == 3
    assert names.count("stream.fetch") == 3
    assert names.count("stream.h2d_put") == 3 * 8
    assert names.count("stream.superstep") == 3


def test_run_stream_superstep_writes_the_same_alerts(tmp_path):
    """`run_stream` with supersteps on (one group held back so that the
    next can be staged) appends the alert rows the per-batch run
    appends."""
    import pandas as pd

    from onix.ingest.nfdecode import write_v5

    paths = []
    for i, c in enumerate(_chunks(5)):
        epoch = (pd.to_datetime(c["treceived"]).astype(np.int64)
                 / 1e9).to_numpy()
        paths.append(tmp_path / f"chunk{i}.nf5")
        paths[-1].write_bytes(write_v5(
            c.assign(start_ts=epoch, end_ts=epoch + 10.0)))
    outs = []
    for s in (0, 2):
        cfg = _cfg(s)
        cfg.store.results_dir = str(tmp_path / f"res{s}")
        cfg.store.checkpoint_dir = str(tmp_path / f"ck{s}")
        assert run_stream(cfg, "flow", [str(p) for p in paths],
                          n_buckets=N_BUCKETS) == 0
        files = sorted((tmp_path / f"res{s}").rglob("flow_streaming.csv"))
        assert files
        outs.append(pd.concat([pd.read_csv(f) for f in files]))
    assert len(outs[0]) == len(outs[1]) > 0
    assert outs[0]["event_idx"].tolist() == outs[1]["event_idx"].tolist()
    np.testing.assert_allclose(outs[1]["score"], outs[0]["score"],
                               rtol=1e-4, atol=1e-6)


def _churn_groups(n_groups: int = 6, fresh: int = 12, seed: int = 21):
    """Groups of two `_churn_chunks`: with `max_docs` 150 a superstep that
    stays under the bound, then one that crosses it and evicts."""
    chunks = _churn_chunks(2 * n_groups, fresh, seed=seed)
    return [[(c, None) for c in chunks[i:i + 2]]
            for i in range(0, len(chunks), 2)]


def _run_staged(sc: StreamingScorer, groups: list) -> list:
    """`groups` one call each, the next named as the one to stage."""
    out = []
    for g, ahead in zip(groups, groups[1:] + [None]):
        out += sc.process_many(g, stage_next=ahead)
    return out


def _until_evicted():
    """A scorer run with `stage_next` up to the first superstep that
    evicts, and the group it staged ahead."""
    sc = StreamingScorer(_cfg(2), "flow", n_buckets=N_BUCKETS, max_docs=150)
    fired = _evictions(sc)
    groups = _churn_groups()
    for g, ahead in zip(groups, groups[1:]):
        sc.process_many(g, stage_next=ahead)
        if fired:
            return sc, ahead
    raise AssertionError("no eviction fired")


class _Forgets(set):
    """A set of probed shapes that never holds one: every group whose
    turn comes unprobed is probed on the host."""

    def add(self, item):
        pass


def test_device_probe_after_eviction_equals_the_host_probe():
    """The group staged ahead of a superstep that evicts stays staged
    with its probe cleared; probed on the device against the compacted
    table pushed again, it reads the host probe's counts and masks bit
    for bit."""
    sc, ahead = _until_evicted()
    staged = sc._staged
    assert staged is not None and staged.holds(ahead)
    assert staged.probe is None and sc._res is None
    sc._push_resident()                 # the compacted table on the device
    assert sc._probe_shape(staged) in sc.probe_shapes   # probed ahead
    on_dev, on_host = dc.replace(staged), dc.replace(staged)
    sc._probe_again(on_dev)
    sc._probe_on_host(on_host)
    counts_d, masks_d = (np.asarray(x) for x in on_dev.probe)
    counts_h, masks_h = on_host.probe
    np.testing.assert_array_equal(counts_d, counts_h)
    np.testing.assert_array_equal(masks_d, masks_h)
    assert counts_h.sum() > 0


def test_device_probe_gives_the_stream_the_host_probe_gives(monkeypatch):
    """A stream across evictions that probes each kept group on the
    device gives the winners, scores, table, stamps and store rows of
    one made to probe every unprobed group on the host."""
    from onix.utils.obs import counters

    groups = _churn_groups()
    runs = []
    for forced in (False, True):
        sc = StreamingScorer(_cfg(2), "flow", n_buckets=N_BUCKETS,
                             max_docs=150)
        if forced:
            monkeypatch.setattr(sc, "probe_shapes", _Forgets())
        before = counters.snapshot()
        res = _run_staged(sc, groups)
        after = counters.snapshot()
        probes = {k: after.get(k, 0) - before.get(k, 0)
                  for k in ("stream.device_probes", "stream.host_probes")}
        sc._pull_resident()
        runs.append((sc, res, probes))
    (dev, res_d, probes_d), (host, res_h, probes_h) = runs
    assert probes_d["stream.device_probes"] >= 2
    assert probes_h["stream.device_probes"] == 0
    assert probes_h["stream.host_probes"] == (probes_d["stream.host_probes"]
                                              + probes_d["stream.device_probes"])
    _assert_same_stream(res_h, res_d)
    for a, b in zip(res_h, res_d):
        assert _winners(a) == _winners(b)
        np.testing.assert_array_equal(a.scores, b.scores)
    n = host.docs.n_docs
    np.testing.assert_array_equal(dev.docs.keys, host.docs.keys)
    np.testing.assert_array_equal(dev._gamma[:n], host._gamma[:n])
    np.testing.assert_array_equal(dev._last_seen[:n], host._last_seen[:n])


def test_eviction_keeps_the_staged_group_and_probes_it_on_the_device(
        monkeypatch):
    """Across evictions with `stage_next`: one `stream.stage` a group
    (the group an eviction kept is not staged again), the host probes
    the first resident group alone, the device probes one kept group an
    eviction, and a group staged ahead while the table is past its bound
    carries no probe and dispatches no `stream_docs_probe`."""
    from onix.pipelines import streaming
    from onix.utils.obs import counters

    groups = _churn_groups()
    sc = StreamingScorer(_cfg(2), "flow", n_buckets=N_BUCKETS, max_docs=150)
    dispatched = []
    probe = streaming.stream_docs_probe

    def spy(*args):
        dispatched.append(args[0].shape[0])
        return probe(*args)
    monkeypatch.setattr(streaming, "stream_docs_probe", spy)
    stages, stage = [], sc._stage

    def staging(group, probe=False):
        over, n = sc._over_bound(), len(dispatched)
        staged = stage(group, probe)
        stages.append((over, staged.probe is None, len(dispatched) - n))
        return staged
    monkeypatch.setattr(sc, "_stage", staging)
    t0, before = time.monotonic(), counters.snapshot()
    _run_staged(sc, groups)
    after = counters.snapshot()
    got = {k: after.get(k, 0) - before.get(k, 0)
           for k in ("stream.evictions", "stream.device_probes",
                     "stream.host_probes")}
    names = _spans_since(t0)
    assert got["stream.evictions"] >= 2
    assert names.count("stream.stage") == len(stages) == len(groups)
    assert got["stream.host_probes"] == names.count("stream.probe_host") == 1
    assert (got["stream.device_probes"] == names.count("stream.probe_device")
            == got["stream.evictions"])
    over = [s for s in stages if s[0]]
    assert len(over) == got["stream.evictions"]
    assert all(none and n == 0 for _, none, n in over)
    ahead = [s for s in stages[1:] if not s[0]]
    assert all(not none and n == 1 for _, none, n in ahead)
    assert len(dispatched) == len(ahead) + got["stream.device_probes"]


def test_a_host_path_batch_drops_the_group_an_eviction_kept():
    """The kept group's columns leave the device once a batch takes the
    host path: the next group may take it too, and its staging would
    then sit on the chip unused."""
    sc, ahead = _until_evicted()
    assert sc._res is None and sc._staged is not None
    sc.process(ahead[0][0])
    assert sc._staged is None
