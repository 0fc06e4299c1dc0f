"""The resident superstep (`streaming.stream_svi_step`: the gamma store,
the document table and the selection on the device) held to the host
path of the same scorer, `process` per batch, on the same batches at a
tiny shape: same winners, lambda and gamma within the float32 tolerance
of a different summation order (the resident path dedupes the tokens
on the device, pairs word by word; the host path with `np.unique`)."""

import dataclasses as dc

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


def test_resident_stages_the_next_group_ahead():
    """`stage_next` (what run_stream passes) is staged and probed under
    the running superstep and used by the next call; the answers are
    those of a call without it."""
    from onix.utils import telemetry

    chunks = _chunks(7)
    a = StreamingScorer(_cfg(3), "flow", n_buckets=N_BUCKETS)
    res_a = a.process_many([(c, None) for c in chunks])
    b = StreamingScorer(_cfg(3), "flow", n_buckets=N_BUCKETS)
    groups = [[(c, None) for c in chunks[i:i + 3]] for i in (0, 3, 6)]
    telemetry.RECORDER.clear()
    res_b = b.process_many(groups[0], stage_next=groups[1])
    staged = b._staged
    assert staged is not None and staged.probe is not None
    res_b += b.process_many(groups[1], stage_next=groups[2])
    assert b._staged is not None and b._staged is not staged
    res_b += b.process_many(groups[2])
    assert b._staged is None
    _assert_same_stream(res_a, res_b)
    names = [s.name for s in telemetry.TRACER.spans()]
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
