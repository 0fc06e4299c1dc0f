"""Streaming online-VB path tests (SURVEY.md §4.5: "feed the same day as
one batch vs minibatches, assert bounded score divergence") — judged
config 4, BASELINE.json "streaming online-VB LDA over oni-ingest
minibatches (incremental scoring)"."""

import dataclasses

import numpy as np
import pandas as pd

from onix.config import OnixConfig
from onix.ingest.parsers import format_bluecoat
from onix.pipelines.streaming import DocTable, StreamingScorer, run_stream
from onix.pipelines.synth import synth_flow_day, synth_proxy_day


def _cfg(**lda_overrides) -> OnixConfig:
    cfg = OnixConfig()
    cfg.lda.n_topics = 8
    cfg.lda.svi_tau0 = 1.0      # stream-reactive schedule for short tests
    for k, v in lda_overrides.items():
        setattr(cfg.lda, k, v)
    return cfg.validate()


def test_bucket_of_keys_stable_and_uniform():
    """Packed-key bucketing: process-stable, in-range, low collision at
    light fill — the integer twin of the string-hash contract above."""
    from onix.pipelines.streaming import _bucket_of_keys, _datatype_salt
    keys = (np.arange(500, dtype=np.int64) * 131071 + 7)
    salt = _datatype_salt("flow")
    a = _bucket_of_keys(keys, salt, 1 << 13)
    b = _bucket_of_keys(keys, salt, 1 << 13)
    np.testing.assert_array_equal(a, b)
    assert a.min() >= 0 and a.max() < (1 << 13)
    assert len(np.unique(a)) >= 480
    # Different datatypes salt differently (no systematic collisions).
    c = _bucket_of_keys(keys, _datatype_salt("dns"), 1 << 13)
    assert (a != c).any()


def test_streaming_ipv6_batch_switches_to_string_docs():
    """A mid-stream batch carrying IPv6 rides the tagged-u64 columnar
    word path (no uint32 doc keys), flipping the doc table one-way to
    string keys; previously-seen v4 docs keep their identities across
    the conversion."""
    from onix.pipelines.streaming import DocTable, U32DocTable
    table, _ = synth_flow_day(n_events=600, n_hosts=50, n_anomalies=4,
                              seed=3)
    sc = StreamingScorer(_cfg(), "flow", n_buckets=1 << 12)
    sc.process(table)
    assert isinstance(sc.docs, U32DocTable)
    docs_before = sc.docs.n_docs
    keys_before = sc.docs.as_strings()

    v6 = table.iloc[:50].copy().reset_index(drop=True)
    v6.loc[:4, "sip"] = "2001:db8::1"        # forces tagged-u64 keys
    res = sc.process(v6)
    assert res.n_events == 50
    assert isinstance(sc.docs, DocTable)
    # Old v4 docs kept their ids (prefix preserved); v6 doc appended.
    assert sc.docs.keys[:docs_before] == keys_before
    assert "2001:db8::1" in sc.docs.keys

    # Subsequent v4 batches keep scoring consistently in string mode.
    res2 = sc.process(table.iloc[:100].reset_index(drop=True))
    assert np.isfinite(res2.scores).all()
    assert sc.docs.n_docs >= docs_before + 1


def test_doc_table_first_seen_order():
    t = DocTable()
    ids1 = t.ids(np.array(["b", "a", "b"], dtype=object))
    assert t.n_docs == 2
    ids2 = t.ids(np.array(["c", "a"], dtype=object))
    assert t.n_docs == 3
    # Ids are stable: "a"/"b" keep their first-seen ids.
    assert ids1.tolist() == [ids1[0], ids1[1], ids1[0]]
    assert ids2[1] == ids1[1]
    assert t.keys[ids2[0]] == "c"


def test_streaming_matches_batch_and_surfaces_anomalies():
    """One day fed as 8 minibatches vs as a single batch: both must
    surface the planted anomalies, with bounded rank divergence."""
    table, anomalies = synth_flow_day(n_events=4000, n_hosts=80,
                                      n_anomalies=15, seed=11)
    chunks = [table.iloc[i:i + 500].reset_index(drop=True)
              for i in range(0, 4000, 500)]

    stream = StreamingScorer(_cfg(), "flow", n_buckets=1 << 13)
    for epoch in range(2):
        scores = np.full(4000, np.inf)
        for ci, ch in enumerate(chunks):
            res = stream.process(ch)
            assert res.n_events == 500
            scores[ci * 500:(ci + 1) * 500] = res.scores
    # Equal-size minibatches must reuse one compiled shape (static-shape
    # padding contract — a retrace per batch would be a TPU-side bug).
    assert len(stream.pad_shapes) == 1

    batch = StreamingScorer(_cfg(), "flow", n_buckets=1 << 13)
    for epoch in range(2):
        bres = batch.process(table)

    s_rank = np.argsort(np.argsort(scores))
    b_rank = np.argsort(np.argsort(bres.scores))

    s_recall = np.isin(np.argsort(scores)[:300], anomalies).sum() / 15
    b_recall = np.isin(np.argsort(bres.scores)[:300], anomalies).sum() / 15
    assert s_recall >= 0.6, f"streaming surfaced only {s_recall:.0%}"
    assert b_recall >= 0.8, f"batch surfaced only {b_recall:.0%}"
    # Bounded divergence between the two feeding regimes (§4.5).
    rho = np.corrcoef(s_rank, b_rank)[0, 1]
    assert rho >= 0.55, f"rank correlation {rho:.2f} too low"


def test_streaming_alerts_respect_tol_and_order():
    table, _ = synth_flow_day(n_events=2000, n_anomalies=10, seed=5)
    cfg = _cfg()
    cfg.pipeline.tol = 0.05
    sc = StreamingScorer(cfg, "flow", n_buckets=1 << 12)
    res = sc.process(table)
    if len(res.alerts):
        a = res.alerts["score"].to_numpy()
        assert (a < 0.05).all()
        assert (np.diff(a) >= 0).all()
    assert len(res.alerts) <= cfg.pipeline.max_results


def test_run_stream_cli_writes_alert_files(tmp_path):
    """File-per-minibatch driver: proxy logs in, streaming alert CSV out."""
    table, _ = synth_proxy_day(n_events=1200, n_anomalies=12, seed=7)
    paths = []
    for i in range(3):
        p = tmp_path / f"proxy_{i}.log"
        p.write_text(format_bluecoat(
            table.iloc[i * 400:(i + 1) * 400].reset_index(drop=True)))
        paths.append(str(p))

    cfg = OnixConfig()
    cfg.store.root = str(tmp_path / "store")
    cfg.store.results_dir = str(tmp_path / "results")
    cfg.lda.n_topics = 6
    cfg.lda.svi_tau0 = 1.0
    cfg.pipeline.tol = 0.5

    assert run_stream(cfg, "proxy", paths, n_buckets=1 << 12, epochs=2) == 0
    out = list((tmp_path / "results").glob("*/proxy_streaming.csv"))
    assert out, "no streaming alerts written"
    alerts = pd.concat([pd.read_csv(p) for p in out])
    assert "score" in alerts.columns and len(alerts) > 0


def test_streaming_checkpoint_resume_identical_scores(tmp_path):
    """Kill-and-resume: a stream checkpointed every batch, killed after
    batch 4, and resumed in a FRESH process-equivalent scorer must score
    the remaining batches identically to an uninterrupted stream
    (SURVEY.md §5.3-5.4 for the streaming path)."""
    table, _ = synth_flow_day(n_events=4000, n_hosts=80, n_anomalies=15,
                              seed=11)
    chunks = [table.iloc[i:i + 500].reset_index(drop=True)
              for i in range(0, 4000, 500)]
    cfg = _cfg(checkpoint_every=1)
    ck = tmp_path / "ck"

    # Uninterrupted reference (no checkpointing side effects on math).
    ref = StreamingScorer(cfg, "flow", n_buckets=1 << 12)
    ref_scores = [ref.process(ch).scores for ch in chunks]

    # Interrupted: process 4 batches, checkpoint each, then "die".
    first = StreamingScorer(cfg, "flow", n_buckets=1 << 12,
                            checkpoint_dir=ck)
    for ch in chunks[:4]:
        first.process(ch)
    del first

    # Fresh scorer resumes from the checkpoint and continues.
    resumed = StreamingScorer(cfg, "flow", n_buckets=1 << 12,
                              checkpoint_dir=ck)
    assert resumed._batch_no == 4
    assert resumed.docs.n_docs > 0
    assert resumed.edges is not None        # frozen edges survived
    for i, ch in enumerate(chunks[4:], start=4):
        got = resumed.process(ch).scores
        np.testing.assert_allclose(got, ref_scores[i], rtol=1e-5,
                                   err_msg=f"batch {i} diverged")


def test_streaming_checkpoint_rejects_other_config(tmp_path):
    """A checkpoint from different sampling hyperparams must not be
    adopted (fingerprint mismatch -> fresh model)."""
    table, _ = synth_flow_day(n_events=1000, n_hosts=40, n_anomalies=5,
                              seed=3)
    ck = tmp_path / "ck"
    a = StreamingScorer(_cfg(checkpoint_every=1), "flow",
                        n_buckets=1 << 12, checkpoint_dir=ck)
    a.process(table)
    b = StreamingScorer(_cfg(checkpoint_every=1, n_topics=7), "flow",
                        n_buckets=1 << 12, checkpoint_dir=ck)
    assert b._batch_no == 0                 # nothing adopted
    # The SVI schedule is part of the streaming identity too.
    c = StreamingScorer(_cfg(checkpoint_every=1, svi_kappa=0.9), "flow",
                        n_buckets=1 << 12, checkpoint_dir=ck)
    assert c._batch_no == 0


def test_run_stream_resume_skips_processed_files(tmp_path):
    """A restarted run_stream must not double-train on (or re-alert for)
    files its checkpoint already consumed."""
    from onix.ingest.nfdecode import write_v5
    from onix.pipelines.streaming import run_stream

    table, _ = synth_flow_day(n_events=900, n_hosts=40, n_anomalies=5,
                              seed=2)
    epoch = (pd.to_datetime(table["treceived"]).astype(np.int64)
             / 1e9).to_numpy()
    table = table.assign(start_ts=epoch, end_ts=epoch + 10.0)
    paths = []
    for i in range(3):
        p = tmp_path / f"chunk{i}.nf5"
        p.write_bytes(write_v5(
            table.iloc[i * 300:(i + 1) * 300].reset_index(drop=True)))
        paths.append(str(p))
    cfg = _cfg(checkpoint_every=1)
    cfg = dataclasses.replace(
        cfg, store=dataclasses.replace(
            cfg.store, checkpoint_dir=str(tmp_path / "ck"),
            results_dir=str(tmp_path / "res")))
    run_stream(cfg, "flow", paths[:2])      # "crash" after 2 files
    scorer_probe = StreamingScorer(cfg, "flow",
                                   checkpoint_dir=tmp_path / "ck" / "flow"
                                   / "stream")
    assert scorer_probe._batch_no == 2
    run_stream(cfg, "flow", paths)          # restart with the full list
    final = StreamingScorer(cfg, "flow",
                            checkpoint_dir=tmp_path / "ck" / "flow"
                            / "stream")
    # 2 from the first run + only the 1 unseen file from the second.
    assert final._batch_no == 3


def test_doc_table_bulk_load_million_keys():
    """Vectorized restore: a 10⁶-IP doc table loads in one bulk pass
    (round 2 replayed checkpointed IPs one np.unique call at a time)."""
    import time

    keys = [f"10.{i >> 16 & 255}.{i >> 8 & 255}.{i & 255}"
            for i in range(1_000_000)]
    dt = DocTable()
    t0 = time.perf_counter()
    dt.load(keys)
    elapsed = time.perf_counter() - t0
    assert dt.n_docs == 1_000_000
    assert elapsed < 5.0            # bulk, not per-key replay
    # Existing keys resolve to their loaded ids, new keys append.
    out = dt.ids(np.array(["10.0.0.5", "99.9.9.9"], dtype=object))
    assert out[0] == 5 and out[1] == 1_000_000


def test_streaming_eviction_bounds_docs_and_checkpoint(tmp_path):
    """A stream that sees an unbounded IP population keeps per-doc state
    (and checkpoint size) bounded by max_docs, evicting least-recently-
    seen docs; docs hot in the latest batches survive."""
    cfg = _cfg(checkpoint_every=1)
    sc = StreamingScorer(cfg, "flow", n_buckets=1 << 12,
                         checkpoint_dir=tmp_path / "ck", max_docs=600)
    for b in range(6):
        # Every batch brings ~400 fresh client IPs (disjoint /16s) plus
        # a stable set of servers.
        table, _ = synth_flow_day(n_events=800, n_hosts=200, n_anomalies=4,
                                  seed=b)
        table = table.copy()
        table["sip"] = [f"10.{b}.{i % 200}.{i // 200}"
                        for i in range(len(table))]
        sc.process(table)
    assert sc.docs.n_docs <= 600
    assert sc._gamma.shape[0] <= 1024          # pow2 cap over max_docs
    assert sc._last_seen.shape[0] == sc._gamma.shape[0]
    # The latest batch's client IPs survived eviction (membership check
    # — ids() would insert a missing key and mask the failure). The
    # columnar stream keys docs by uint32 IP.
    from onix.ingest.nfdecode import str_to_ip
    assert str_to_ip(np.array(["10.5.0.0"]))[0] in sc.docs.keys
    assert "10.5.0.0" in sc.docs.as_strings()
    # Checkpoint carries columnar doc state trimmed to n_docs, no JSON
    # doc_keys blob.
    import json

    ck_dir = next((tmp_path / "ck").iterdir())
    js = sorted(ck_dir.glob("ckpt-*.json"))[-1]
    meta = json.loads(js.read_text())
    assert "doc_keys" not in meta
    with np.load(js.with_suffix(".npz")) as z:
        assert z["doc_keys"].shape[0] == sc.docs.n_docs == z["gamma"].shape[0]
        assert z["last_seen"].shape[0] == sc.docs.n_docs


def test_streaming_checkpoint_restore_after_eviction(tmp_path):
    """Resume after eviction: restored table, gamma, and last_seen stay
    id-aligned and scoring continues identically to an uninterrupted
    run."""
    cfg = _cfg(checkpoint_every=1)

    def feed(sc, n):
        outs = []
        for b in range(n):
            table, _ = synth_flow_day(n_events=400, n_hosts=150,
                                      n_anomalies=4, seed=10 + b)
            outs.append(sc.process(table).scores)
        return outs

    ref = StreamingScorer(cfg, "flow", n_buckets=1 << 12, max_docs=120)
    r_all = feed(ref, 4)

    a = StreamingScorer(cfg, "flow", n_buckets=1 << 12,
                        checkpoint_dir=tmp_path / "ck", max_docs=120)
    feed(a, 3)
    b = StreamingScorer(cfg, "flow", n_buckets=1 << 12,
                        checkpoint_dir=tmp_path / "ck", max_docs=120)
    assert b._batch_no == 3
    np.testing.assert_array_equal(b.docs.keys, a.docs.keys)
    table, _ = synth_flow_day(n_events=400, n_hosts=150, n_anomalies=4,
                              seed=13)
    np.testing.assert_allclose(b.process(table).scores, r_all[3],
                               rtol=1e-5)


def test_streaming_device_mode_default_and_host_escape(monkeypatch):
    """After the first (edge-fitting) batch, columnar minibatches ride
    the fused device word path by default; ONIX_HOST_WORDS=1 pins every
    batch to the host reference arm. Scores from the two arms agree in
    rank where it matters (same alert tail)."""
    table, _ = synth_flow_day(n_events=3000, n_hosts=80, n_anomalies=10,
                              seed=21)
    chunks = [table.iloc[i:i + 1000].reset_index(drop=True)
              for i in range(0, 3000, 1000)]

    monkeypatch.delenv("ONIX_HOST_WORDS", raising=False)
    dev = StreamingScorer(_cfg(), "flow", n_buckets=1 << 12)
    dev_scores = np.concatenate([dev.process(c).scores for c in chunks])
    assert dev.words_mode_batches == {"device": 2, "host": 1}

    monkeypatch.setenv("ONIX_HOST_WORDS", "1")
    host = StreamingScorer(_cfg(), "flow", n_buckets=1 << 12)
    host_scores = np.concatenate([host.process(c).scores for c in chunks])
    assert host.words_mode_batches == {"device": 0, "host": 3}

    # Same words, same buckets (up to the documented f32 edge caveat),
    # different E-step schedule (dedup + warm start vs the reference
    # fixed count) — the suspicious tails must still agree strongly.
    k = 300
    a = set(np.argsort(dev_scores)[:k].tolist())
    b = set(np.argsort(host_scores)[:k].tolist())
    assert len(a & b) >= 0.8 * k


def test_prefetched_columns_match_serial_processing():
    """The one-deep conversion prefetch (ColumnPrefetcher) must change
    NOTHING but the wall: identical scores/alerts to serial process()
    calls, with the hidden conversion seconds accounted in
    stage_walls["prefetch_overlap"]/["prefetch_wait"]."""
    from onix.pipelines.streaming import ColumnPrefetcher

    table, _ = synth_flow_day(n_events=1500, n_hosts=60, n_anomalies=4,
                              seed=11)
    chunks = [table.iloc[i: i + 300].reset_index(drop=True)
              for i in range(0, 1500, 300)]

    serial = StreamingScorer(_cfg(), "flow", n_buckets=1 << 10)
    ref_scores = [serial.process(c).scores for c in chunks]

    pre = StreamingScorer(_cfg(), "flow", n_buckets=1 << 10)
    got_scores = []
    n_cols = 0
    for tbl, cols in ColumnPrefetcher(pre, chunks):
        n_cols += cols is not None
        got_scores.append(pre.process(tbl, cols=cols).scores)
    assert n_cols == len(chunks)        # flow frames all convert
    for a, b in zip(ref_scores, got_scores):
        np.testing.assert_array_equal(a, b)
    walls = pre.stage_walls
    assert walls["prefetch_overlap"] >= 0.0
    assert walls["prefetch_wait"] >= 0.0
    # The conversion wall went SOMEWHERE: overlap + wait together cover
    # every prefetched conversion (no silently dropped accounting).
    assert walls["prefetch_overlap"] + walls["prefetch_wait"] > 0.0


def test_prefetcher_decodes_callables_on_worker():
    """The callable item form (run_stream's decode thunks) is invoked
    on the worker and yields the decoded frame itself."""
    from onix.pipelines.streaming import ColumnPrefetcher

    table, _ = synth_flow_day(n_events=400, n_hosts=30, n_anomalies=2,
                              seed=3)
    chunks = [table.iloc[:200].reset_index(drop=True),
              table.iloc[200:].reset_index(drop=True)]
    sc = StreamingScorer(_cfg(), "flow", n_buckets=1 << 10)
    seen = []
    items = [lambda c=c: seen.append(id(c)) or c for c in chunks]
    out = [(t, cols) for t, cols in ColumnPrefetcher(sc, items)]
    assert len(out) == 2 and len(seen) == 2
    for (t, cols), c in zip(out, chunks):
        assert t is c and cols is not None


def test_prefetcher_depth_k_preserves_order():
    """Depth>1 with deliberately inverted per-item produce times must
    still hand batches over in submission order (scorer state mutates
    in stream order), with occupancy bounded by the depth."""
    import time as _t

    from onix.pipelines.streaming import ColumnPrefetcher

    table, _ = synth_flow_day(n_events=600, n_hosts=40, n_anomalies=2,
                              seed=5)
    chunks = [table.iloc[i * 150:(i + 1) * 150].reset_index(drop=True)
              for i in range(4)]
    # First item slowest, last fastest: an unordered pipeline would
    # yield them inverted.
    delays = [0.2, 0.1, 0.05, 0.0]

    def make(i):
        def produce():
            _t.sleep(delays[i])
            return chunks[i]
        return produce

    sc = StreamingScorer(_cfg(), "flow", n_buckets=1 << 10)
    got = [t for t, _ in ColumnPrefetcher(sc, [make(i) for i in range(4)],
                                          depth=3, mode="thread")]
    assert len(got) == 4
    for g, c in zip(got, chunks):
        assert g is c
    stats = sc.prefetch_stats
    assert stats["mode"] == "thread" and stats["depth"] == 3
    assert 1 <= stats["occupancy_max"] <= 3


def test_prefetcher_worker_exception_propagates():
    """A worker exception must surface at the consumer's next handoff —
    never hang the pipeline, never be swallowed — and the pool must
    shut down cleanly afterwards."""
    import pytest

    from onix.pipelines.streaming import ColumnPrefetcher

    table, _ = synth_flow_day(n_events=300, n_hosts=30, n_anomalies=2,
                              seed=6)

    def boom():
        raise RuntimeError("poison decode")

    sc = StreamingScorer(_cfg(), "flow", n_buckets=1 << 10)
    items = [table, boom, table]
    it = iter(ColumnPrefetcher(sc, items, depth=2, mode="thread"))
    first, _ = next(it)
    assert first is table
    with pytest.raises(RuntimeError, match="poison decode"):
        for _ in it:
            pass


def test_prefetcher_backpressure_bounds_inflight():
    """When the consumer (device stage) is the bottleneck, the pipeline
    must not run ahead of depth: at any point the source has been
    pulled at most (yielded + depth) items — peak memory stays at
    depth+1 frames no matter how long the stream."""
    from onix.pipelines.streaming import ColumnPrefetcher

    table, _ = synth_flow_day(n_events=400, n_hosts=30, n_anomalies=2,
                              seed=7)
    chunk = table.iloc[:100].reset_index(drop=True)
    pulled = 0

    def source():
        nonlocal pulled
        for _ in range(8):
            pulled += 1
            yield chunk

    sc = StreamingScorer(_cfg(), "flow", n_buckets=1 << 10)
    it = iter(ColumnPrefetcher(sc, source(), depth=2, mode="thread"))
    seen = 0
    for _tbl, _cols in it:
        seen += 1
        assert pulled <= seen + 2, (
            f"prefetcher ran {pulled - seen} items ahead (depth 2)")
    assert seen == 8 and pulled == 8


def test_prefetcher_clean_shutdown_on_early_exit():
    """Breaking out of the consuming loop mid-stream must cancel the
    pipeline promptly: the source is never drained and the test (and
    interpreter) does not hang on pool teardown."""
    from onix.pipelines.streaming import ColumnPrefetcher

    table, _ = synth_flow_day(n_events=300, n_hosts=30, n_anomalies=2,
                              seed=8)
    chunk = table.iloc[:100].reset_index(drop=True)
    pulled = 0

    def source():
        nonlocal pulled
        for _ in range(100):
            pulled += 1
            yield chunk

    sc = StreamingScorer(_cfg(), "flow", n_buckets=1 << 10)
    it = iter(ColumnPrefetcher(sc, source(), depth=3, mode="thread"))
    next(it)
    it.close()          # early exit — GeneratorExit runs the cleanup
    assert pulled <= 1 + 3, "early exit kept draining the source"


def test_prefetcher_process_mode_matches_thread(monkeypatch, tmp_path):
    """The process-pool arm must be a pure transport change: identical
    (table, cols) handoffs and identical downstream scores. Counter
    deltas tallied in a worker process (e.g. salvage) merge back into
    the parent registry."""
    monkeypatch.delenv("ONIX_PREFETCH_MODE", raising=False)
    from onix.pipelines.streaming import ColumnPrefetcher

    # The prefetcher pins threads where `__main__` has no file (stdin,
    # `python -c`), and a pytest-xdist worker is such a process: give
    # it one, so that the pool arm is what runs however the tests are
    # started. Spawned workers run that file as `__mp_main__`.
    import __main__
    (tmp_path / "main.py").write_text("")
    monkeypatch.setattr(__main__, "__file__", str(tmp_path / "main.py"),
                        raising=False)

    table, _ = synth_flow_day(n_events=600, n_hosts=40, n_anomalies=3,
                              seed=9)
    chunks = [table.iloc[i * 300:(i + 1) * 300].reset_index(drop=True)
              for i in range(2)]

    ref = StreamingScorer(_cfg(), "flow", n_buckets=1 << 10)
    ref_scores = [ref.process(c).scores for c in chunks]

    sc = StreamingScorer(_cfg(), "flow", n_buckets=1 << 10)
    got = []
    for tbl, cols in ColumnPrefetcher(sc, chunks, depth=1,
                                      mode="process"):
        assert cols is not None
        got.append(sc.process(tbl, cols=cols).scores)
    assert sc.prefetch_stats["mode"] == "process"
    for a, b in zip(ref_scores, got):
        np.testing.assert_array_equal(a, b)


def test_prefetcher_auto_pins_thread_under_fault_plan(monkeypatch):
    """Chaos drills must never route decode through a process pool —
    fault-plan rule state (one-shot marks) is process-local, so a
    pool worker's injected fault could not be marked consumed."""
    from onix.pipelines.streaming import ColumnPrefetcher
    from onix.utils import faults

    monkeypatch.delenv("ONIX_PREFETCH_MODE", raising=False)
    table, _ = synth_flow_day(n_events=300, n_hosts=30, n_anomalies=2,
                              seed=4)
    faults.install_plan("stream:batch@999=raise")
    try:
        sc = StreamingScorer(_cfg(), "flow", n_buckets=1 << 10)
        out = list(ColumnPrefetcher(sc, [table, table], depth=2,
                                    mode="process"))
        assert len(out) == 2
        assert sc.prefetch_stats["mode"] == "thread"
        assert sc.prefetch_stats.get("mode_forced_by_fault_plan")
    finally:
        faults.reset()


def test_pick_pad_caps_shape_lattice():
    """Adversarial batch-size streams must not grow the compiled-shape
    set unboundedly: past stream_max_shapes, batches re-pad into a
    covering shape; a batch nothing covers escalates ONE ceiling
    shape. Compiles and re-pads are counted."""
    import dataclasses as dc

    cfg = _cfg()
    cfg = dc.replace(cfg, pipeline=dc.replace(cfg.pipeline,
                                              stream_max_shapes=3))
    sc = StreamingScorer(cfg, "flow", n_buckets=1 << 10)
    assert sc._pick_pad(100, 10) == (256, 64)
    assert sc._pick_pad(300, 10) == (512, 64)
    assert sc._pick_pad(1000, 100) == (1024, 128)
    assert sc.shape_stats == {"compiled": 3, "repadded": 0}
    # Lattice full: a coverable new pair re-pads into the smallest
    # covering member instead of compiling a fourth program.
    assert sc._pick_pad(400, 100) == (1024, 128)
    assert sc.shape_stats["repadded"] == 1
    assert len(sc.pad_shapes) == 3
    # Nothing covers 5000 tokens: ONE ceiling shape joins the lattice,
    # and covers every later oddball too.
    big = sc._pick_pad(5000, 20)
    assert big == (8192, 128)
    assert sc._pick_pad(3000, 90) == big
    assert sc.shape_stats["compiled"] == 4
    assert len(sc.pad_shapes) == 4


def test_stage_walls_account_total_wall():
    """Under the depth-k prefetcher, the consumer-side stage walls
    (including prefetch_wait — the only prefetch time that blocks the
    pipeline) must sum to ≈ the measured loop wall: no double-counted
    hidden host time, no silently dropped stage."""
    import time as _t

    from onix.pipelines.streaming import ColumnPrefetcher

    table, _ = synth_flow_day(n_events=8000, n_hosts=80, n_anomalies=4,
                              seed=12)
    chunks = [table.iloc[i * 2000:(i + 1) * 2000].reset_index(drop=True)
              for i in range(4)]
    sc = StreamingScorer(_cfg(), "flow", n_buckets=1 << 11)
    t0 = _t.perf_counter()
    for tbl, cols in ColumnPrefetcher(sc, chunks, depth=2,
                                      mode="thread"):
        sc.process(tbl, cols=cols)
    wall = _t.perf_counter() - t0
    accounted = sum(v for k, v in sc.stage_walls.items()
                    if k != "prefetch_overlap")
    # Accounted stages can never exceed the wall (they are disjoint
    # consumer-side intervals), and must cover most of it (the rest is
    # python glue). Generous bounds — this is a structural identity,
    # not a performance assertion.
    assert accounted <= wall + 0.05, (sc.stage_walls, wall)
    assert accounted >= 0.5 * wall, (sc.stage_walls, wall)
    # The overlap metric is informational and non-additive — it must
    # not have been folded into the accounted sum.
    assert sc.stage_walls["prefetch_overlap"] >= 0.0


def test_streaming_device_mode_non_pow2_buckets_falls_back():
    """A non-power-of-two bucket count cannot use the device low-bits
    mod — every batch stays on the host path, results stay sane."""
    table, _ = synth_flow_day(n_events=1200, n_hosts=50, n_anomalies=5,
                              seed=9)
    sc = StreamingScorer(_cfg(), "flow", n_buckets=3000)
    for _ in range(2):
        res = sc.process(table)
    assert sc.words_mode_batches["device"] == 0
    assert np.isfinite(res.scores).all()


def test_streaming_device_buckets_compile_once_per_size_class():
    """Irregular minibatch sizes must NOT retrace the fused bucket
    program per batch — per-event columns are pow2-padded, so a stream
    of varied batch lengths reuses one compiled program per size class
    (a retrace costs seconds on an accelerator)."""
    from onix.pipelines import device_words as dw

    sc = StreamingScorer(_cfg(), "flow", n_buckets=1 << 12)
    table, _ = synth_flow_day(n_events=700, n_hosts=50, n_anomalies=4,
                              seed=2)
    before = dw.flow_stream_buckets._cache_size()
    # Varied sizes, all within one pow2 size class (<= 256 floor pads
    # n<=256; 130/190/251 all pad to 256).
    for n in (130, 190, 251, 163):
        sc.process(table.iloc[:n].reset_index(drop=True))
    added = dw.flow_stream_buckets._cache_size() - before
    assert sc.words_mode_batches["device"] == 3   # batch 1 fits edges
    assert added <= 1, f"{added} compiles for one size class"
