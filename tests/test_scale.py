"""Scale-runner contract (BASELINE configs[3]): the end-to-end pipeline
manifest, and the train-on-prefix / stream-score-everything mode that
demonstrates the 10^9 configuration on bounded hardware."""

import numpy as np
import pytest

from onix.pipelines.scale import run_scale


@pytest.mark.slow
def test_scale_full_small():
    m = run_scale(40_000, n_hosts=300, n_sweeps=6)
    assert m["n_events"] == m["train_events"] == 40_000
    assert m["planted_in_bottom_k"] >= 0.8 * m["planted_anomalies"]
    ws = m["walls_seconds"]
    assert {"synthesize", "word_creation", "corpus_build", "gibbs_fit",
            "score_select", "total"} <= set(ws)


@pytest.mark.slow
def test_scale_streaming_mode(tmp_path):
    """train_events < n_events: the model fits on the prefix, every
    event streams through the fused scorer, planted anomalies from
    BOTH the training window and the streamed chunks surface, and the
    manifest records the streaming stage walls."""
    m = run_scale(150_000, train_events=60_000, n_hosts=400, n_sweeps=6,
                  out_path=tmp_path / "scale.json")
    assert m["train_events"] == 60_000 and m["n_events"] == 150_000
    # training window plants its own budget; the 2 streamed chunks share
    # ONE day budget so planted stays comparable to max_results
    # (training default(60k)=30, day default(150k)=30 over 3 chunks -> 10
    # per streamed chunk)
    assert m["planted_anomalies"] == 30 + 2 * 10
    assert m["planted_in_bottom_k"] >= 0.85 * m["planted_anomalies"]
    ws = m["walls_seconds"]
    assert ws["stream_words_map"] > 0 and ws["stream_score"] > 0
    # Generation is excluded from the pipeline wall, so the pipeline
    # rate can never fall below the end-to-end rate.
    assert (m["events_per_second_pipeline_only"]
            >= m["events_per_second_end_to_end"])
    assert (tmp_path / "scale.json").exists()


def test_bundle_packed_lookup_matches_string_path():
    """The searchsorted fast maps (packed word key -> vocab id,
    uint32 IP -> doc id) must agree with the render-then-string lookup
    they replace on the streaming path, including unseen entries."""
    import numpy as np

    from onix.pipelines.corpus_build import build_corpus
    from onix.pipelines.synth import synth_flow_day_arrays
    from onix.pipelines.words import flow_words_from_arrays, u32_to_ips

    cols = synth_flow_day_arrays(20_000, n_hosts=300, n_anomalies=10,
                                 seed=4)
    wt = flow_words_from_arrays(
        **{k: cols[k] for k in ("sip_u32", "dip_u32", "sport", "dport",
                                "proto_id", "hour", "ibyt", "ipkt")},
        proto_classes=cols["proto_classes"])
    bundle = build_corpus(wt)

    cols2 = synth_flow_day_arrays(8_000, n_hosts=500, n_anomalies=10,
                                  seed=99)   # other hosts -> unseen docs
    wt2 = flow_words_from_arrays(
        **{k: cols2[k] for k in ("sip_u32", "dip_u32", "sport", "dport",
                                 "proto_id", "hour", "ibyt", "ipkt")},
        proto_classes=cols2["proto_classes"], edges=wt.edges)

    got_w = bundle.word_ids_packed(wt2.word_key)
    want_w = bundle.vocab.ids(wt2.render_keys(wt2.word_key), strict=False)
    np.testing.assert_array_equal(got_w, want_w)
    got_d = bundle.doc_ids_u32(wt2.ip_u32)
    want_d = bundle.doc_index(u32_to_ips(wt2.ip_u32), strict=False)
    np.testing.assert_array_equal(got_d, want_d)
    assert (got_w >= 0).any() and (got_w < 0).any()   # both regimes hit
    assert (got_d >= 0).any() and (got_d < 0).any()


def test_scale_streaming_unseen_score_at_prior_rarity():
    """An event whose word was never seen in training must score MORE
    suspicious than any seen word, through the PRODUCTION extension
    used by the streaming scorer (the novel-behavior failure mode)."""
    import jax.numpy as jnp

    from onix.models import scoring
    from onix.pipelines.scale import extend_model_for_unseen

    rng = np.random.default_rng(0)
    theta = rng.dirichlet(np.full(4, 0.5), 10).astype(np.float32)
    phi = rng.dirichlet(np.full(4, 0.5), 6).astype(np.float32)
    theta_x, phi_x = extend_model_for_unseen(theta, phi)
    assert theta_x.shape == (11, 4) and phi_x.shape == (7, 4)
    np.testing.assert_allclose(theta_x[-1], 0.25)
    table = np.asarray(scoring.score_table(jnp.asarray(theta_x),
                                           jnp.asarray(phi_x)))
    # Unseen word column is the per-row minimum for EVERY document,
    # including the unseen-document row.
    assert (table[:, 6] <= table[:, :6].min(axis=1) + 1e-9).all()


@pytest.mark.slow
@pytest.mark.parametrize("datatype", ["dns", "proxy"])
def test_scale_datatypes(datatype, tmp_path):
    """configs[1]/[2] at scale: the dns/proxy columnar pipeline runs
    end-to-end (incl. the fused single-token device selection) and
    surfaces the planted anomalies."""
    m = run_scale(40_000, n_hosts=300, n_sweeps=6, datatype=datatype,
                  out_path=tmp_path / "scale.json")
    assert m["datatype"] == datatype
    assert m["planted_in_bottom_k"] >= 0.8 * m["planted_anomalies"]
    assert (tmp_path / "scale.json").exists()


@pytest.mark.slow
@pytest.mark.parametrize("datatype", ["dns", "proxy"])
def test_scale_streaming_datatypes(datatype):
    """Streaming mode for dns/proxy: train on a prefix, stream-score the
    full day through table_bottom_k (single-token layout)."""
    m = run_scale(90_000, train_events=45_000, n_hosts=300, n_sweeps=6,
                  datatype=datatype)
    assert m["walls_seconds"]["stream_score"] > 0
    assert m["planted_in_bottom_k"] >= 0.7 * m["planted_anomalies"]


def test_scale_chained_ensemble():
    """n_chains > 1 rides the sharded engine's vmapped restart ensemble
    through BOTH score paths (fused batch; streamed chunks with the
    geometric-merged chain table) — the north-star combination of
    multi-chip training and the judged-overlap estimator."""
    m = run_scale(90_000, train_events=45_000, n_hosts=400, n_sweeps=6,
                  n_chains=2, max_results=800)
    assert m["planted_in_bottom_k"] > 0
    m2 = run_scale(40_000, n_hosts=300, n_sweeps=6, n_chains=2,
                   max_results=800)
    assert m2["planted_in_bottom_k"] > 0


@pytest.mark.slow
def test_scale_resume_matches_uninterrupted(tmp_path):
    """--resume-dir (a killed session must extend a run, not restart
    it). A run resumed mid-stream must
    produce the SAME winners as an uninterrupted run: the fitted model
    is loaded instead of re-fitted and completed chunks' bottom-k
    survive, so the final merge sees identical inputs."""
    base = run_scale(150_000, train_events=60_000, n_hosts=400,
                     n_sweeps=6, out_path=tmp_path / "base.json")

    rdir = tmp_path / "ckpt"
    full = run_scale(150_000, train_events=60_000, n_hosts=400,
                     n_sweeps=6, resume_dir=rdir)
    # Checkpoints exist and the uninterrupted resumable run agrees with
    # the plain run (determinism in seed).
    assert (rdir / "model.npz").exists() and (rdir / "stream.npz").exists()
    assert full["planted_in_bottom_k"] == base["planted_in_bottom_k"]
    assert full["selected_score_range"] == base["selected_score_range"]

    # Sever the run after chunk 1 of 3: rewind the stream checkpoint to
    # what a killed session would have left behind (chunk 0+1 winners),
    # then resume. np.load here replays exactly what _save_progress
    # wrote after chunk 1 — by re-running with the stream checkpoint
    # deleted but the model kept we simulate death-after-fit; by
    # re-running with both kept we simulate death-after-stream.
    (rdir / "stream.npz").unlink()
    resumed = run_scale(150_000, train_events=60_000, n_hosts=400,
                        n_sweeps=6, resume_dir=rdir,
                        out_path=tmp_path / "resumed.json")
    assert resumed["resumed_sessions"] == 2
    assert resumed["planted_in_bottom_k"] == base["planted_in_bottom_k"]
    assert resumed["selected_score_range"] == base["selected_score_range"]
    assert "wall_all_sessions" in resumed["walls_seconds"]
    # gibbs_fit wall carries the PAYING session's cost, not the load.
    assert resumed["walls_seconds"]["gibbs_fit"] == pytest.approx(
        full["walls_seconds"]["gibbs_fit"])

    # Fingerprint mismatch starts clean instead of resuming another
    # run's state.
    other = run_scale(150_000, train_events=60_000, n_hosts=400,
                      n_sweeps=7, resume_dir=rdir)
    assert "resumed_sessions" not in other
