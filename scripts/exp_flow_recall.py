"""Why is flow planted recall 218/1900 at 1B? (VERDICT r03 next #4)

DNS catches 1000/1000 and proxy 924/1000 at 1e8, but the flow plant
lands only ~11% in bottom-3000 — reproducible across rounds and never
explained. This experiment measures WHICH of the three candidate
mechanisms is binding, at the same shapes the scale artifacts use:

  (a) distribution floor — the background's own rare tail outnumbers
      the plants at the depth the contract reads: with 1e9 background
      events and 3000 result slots, background tail mass above ~3e-6
      buries anything.
  (b) pair-min burying — flow events score min(src-doc, dst-doc
      token); if the external-peer doc dominates the min for
      BACKGROUND events too, plants lose their margin.
  (c) unseen-row ties — events whose word/doc fall outside the trained
      tables share one constant score; if background generates unseen
      pairs at even 1e-5, thousands of ties compete for the same slots
      and recall within the tie is ~(plants / tie pool).

Method: fit exactly as onix.pipelines.scale does (same synth, same
sharded engine), stream-score the full day at max_results deep enough
to read recall at several depths, then regenerate the stream chunks to
collect EXACT per-token scores for every planted event plus a uniform
background sample. Everything is scored through the same extended
theta/phi table the pipeline uses.

    python scripts/exp_flow_recall.py --events 1e8 --train-events 2e7 \
        --out docs/FLOW_RECALL_r04.json
Runs on whatever JAX_PLATFORMS gives it and prints the device.
CPU dev shape: --events 2e6 --train-events 5e5
"""
import argparse
import json
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--events", type=float, default=1e8)
    ap.add_argument("--train-events", type=float, default=2e7)
    ap.add_argument("--n-hosts", type=int, default=100_000)
    ap.add_argument("--n-topics", type=int, default=20)
    ap.add_argument("--n-sweeps", type=int, default=40)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--bg-sample", type=int, default=200_000)
    ap.add_argument("--depths", type=int, nargs="+",
                    default=[3000, 10_000, 30_000, 100_000])
    ap.add_argument("--out", default="docs/FLOW_RECALL_r04.json")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from onix.config import LDAConfig
    from onix.models import scoring
    from onix.parallel.mesh import make_mesh
    from onix.parallel.sharded_gibbs import ShardedGibbsLDA
    from onix.pipelines.corpus_build import build_corpus
    from onix.utils.obs import print_device
    print_device()
    from onix.pipelines.scale import (_default_anomalies, _stream_score,
                                      _words_from_cols,
                                      extend_model_for_unseen)
    from onix.pipelines.synth import SYNTH_ARRAYS

    n_events = int(args.events)
    train_events = int(args.train_events)
    seed = args.seed
    t_all = time.monotonic()

    # -- fit: identical recipe to scale.run_scale ------------------------
    cols0 = SYNTH_ARRAYS["flow"](train_events, n_hosts=args.n_hosts,
                                 n_anomalies=_default_anomalies(train_events),
                                 seed=seed)
    wt = _words_from_cols("flow", cols0)
    bundle = build_corpus(wt)
    corpus = bundle.corpus
    cfg = LDAConfig(n_topics=args.n_topics, n_sweeps=args.n_sweeps,
                    burn_in=max(1, args.n_sweeps // 2),
                    block_size=1 << 17, seed=seed)
    model = ShardedGibbsLDA(cfg, corpus.n_vocab,
                            mesh=make_mesh(dp=len(jax.devices()), mp=1))
    fit = model.fit(corpus)
    theta, phi_wk = fit["theta"], fit["phi_wk"]
    print(f"fit done ({time.monotonic() - t_all:.0f}s): "
          f"D={corpus.n_docs} V={corpus.n_vocab}", flush=True)

    # -- deep stream-scored day (recall at several depths) ---------------
    planted: set = set(cols0["anomaly_idx"].tolist())
    walls: dict = {}
    max_depth = max(args.depths)
    top_idx, top_scores = _stream_score(
        bundle, wt.edges, theta, phi_wk, n_events=n_events,
        chunk_events=train_events, n_hosts=args.n_hosts, seed=seed,
        max_results=max_depth, planted=planted, walls=walls,
        datatype="flow")
    valid = top_idx >= 0
    hit_flags = np.isin(top_idx[valid], np.fromiter(planted, np.int64))
    recall_at = {}
    for d in args.depths:
        hits = int(hit_flags[:d].sum())
        recall_at[str(d)] = {
            "hits": hits, "planted": len(planted),
            "recall": round(hits / max(len(planted), 1), 4)}
    thresholds = {str(d): (float(top_scores[d - 1])
                           if valid.sum() >= d else None)
                  for d in args.depths}
    print(f"recall@depths: { {d: v['recall'] for d, v in recall_at.items()} }",
          flush=True)

    # -- exact planted / background-sample token scores -------------------
    theta_x, phi_x = extend_model_for_unseen(theta, phi_wk)
    v_x = phi_x.shape[0]
    unseen_w, unseen_d = v_x - 1, theta_x.shape[0] - 1
    table = np.asarray(scoring.score_table(jnp.asarray(theta_x),
                                           jnp.asarray(phi_x)).ravel())

    rng = np.random.default_rng(seed + 7)
    n_chunks = -(-n_events // train_events)
    anomalies_per_chunk = max(1, _default_anomalies(n_events) // n_chunks)
    pl_min, pl_src, pl_dst = [], [], []
    pl_unseen_w, pl_unseen_d = 0, 0
    bg_min = []
    bg_unseen_w, bg_unseen_d, bg_n = 0, 0, 0
    per_chunk_bg = max(1, args.bg_sample // max(n_chunks - 1, 1))

    def token_scores(cols, rows):
        sub = {k: (v[rows] if isinstance(v, np.ndarray)
                   and v.shape[:1] == (len(cols["sip_u32"]),) else v)
               for k, v in cols.items()}
        sub["anomaly_idx"] = np.zeros(0, np.int64)
        w = _words_from_cols("flow", sub, edges=wt.edges)
        m = len(rows)
        wid = bundle.word_ids_packed(w.word_key, fill=unseen_w)
        did = bundle.doc_ids_u32(w.ip_u32, fill=unseen_d)
        s = table[did.astype(np.int64) * v_x + wid]
        return (s[:m], s[m:], wid.reshape(2, m), did.reshape(2, m))

    for c in range(1, n_chunks):
        m = min(train_events, n_events - c * train_events)
        cols = SYNTH_ARRAYS["flow"](m, n_hosts=args.n_hosts,
                                    n_anomalies=anomalies_per_chunk,
                                    seed=seed + 1000 * c)
        a_rows = cols["anomaly_idx"]
        s_src, s_dst, wids, dids = token_scores(cols, a_rows)
        pl_src.append(s_src)
        pl_dst.append(s_dst)
        pl_min.append(np.minimum(s_src, s_dst))
        pl_unseen_w += int((wids == unseen_w).any(0).sum())
        pl_unseen_d += int((dids == unseen_d).any(0).sum())
        bg_rows = rng.choice(m, size=min(per_chunk_bg, m), replace=False)
        bg_rows = bg_rows[~np.isin(bg_rows, a_rows)]
        b_src, b_dst, bwids, bdids = token_scores(cols, bg_rows)
        bg_min.append(np.minimum(b_src, b_dst))
        bg_unseen_w += int((bwids == unseen_w).any(0).sum())
        bg_unseen_d += int((bdids == unseen_d).any(0).sum())
        bg_n += len(bg_rows)
    pl_min = np.concatenate(pl_min) if pl_min else np.zeros(0)
    pl_src = np.concatenate(pl_src) if pl_src else np.zeros(0)
    pl_dst = np.concatenate(pl_dst) if pl_dst else np.zeros(0)
    bg_min = np.concatenate(bg_min) if bg_min else np.zeros(0)

    q = lambda a: {p: float(np.quantile(a, float(p) / 100))
                   for p in (1, 5, 25, 50, 75, 95, 99)} if len(a) else {}
    # Expected rank of each planted event in a background-only day:
    # fraction of the background sample strictly below it, scaled to
    # n_events. If the median expected rank >> the reading depth, the
    # background tail — not the engine — sets the recall (mechanism a).
    exp_rank = (np.searchsorted(np.sort(bg_min), pl_min, side="left")
                / max(bg_n, 1) * n_events) if len(pl_min) else np.zeros(0)
    # Mechanism (c): unseen-tie pools. The unseen-word score is exactly
    # table[d, unseen_w] — constant per doc row; measure the tie pool as
    # background events scoring EQUAL to each planted event's score.
    ties = (np.mean(np.isin(pl_min, bg_min)) if len(pl_min) else 0.0)

    doc = {
        "experiment": "flow planted-recall diagnosis (VERDICT r03 #4)",
        "n_events": n_events, "train_events": train_events,
        "n_hosts": args.n_hosts, "seed": seed,
        "devices": [str(d) for d in jax.devices()],
        "recall_at_depth": recall_at,
        "depth_score_thresholds": thresholds,
        "planted_scores": {
            "n": int(len(pl_min)), "quantiles_min": q(pl_min),
            "quantiles_src_token": q(pl_src),
            "quantiles_dst_token": q(pl_dst),
            "min_is_dst_fraction": (float(np.mean(pl_dst < pl_src))
                                    if len(pl_min) else None),
            "unseen_word_fraction": round(pl_unseen_w / max(len(pl_min), 1), 4),
            "unseen_doc_fraction": round(pl_unseen_d / max(len(pl_min), 1), 4),
        },
        "background_sample": {
            "n": bg_n, "quantiles_min": q(bg_min),
            "unseen_word_fraction": round(bg_unseen_w / max(bg_n, 1), 6),
            "unseen_doc_fraction": round(bg_unseen_d / max(bg_n, 1), 6),
        },
        "expected_rank_of_planted": {
            "quantiles": q(exp_rank),
            "fraction_expected_within_3000": (
                float(np.mean(exp_rank < 3000)) if len(exp_rank) else None),
            "fraction_expected_within_100k": (
                float(np.mean(exp_rank < 100_000)) if len(exp_rank) else None),
        },
        "planted_score_in_bg_sample_tie_fraction": round(float(ties), 4),
        "walls_seconds": {k: round(v, 2) for k, v in walls.items()},
        "wall_total_seconds": round(time.monotonic() - t_all, 1),
    }
    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(doc, indent=2) + "\n")
    print(json.dumps({k: doc[k] for k in
                      ("recall_at_depth", "expected_rank_of_planted",
                       "planted_score_in_bg_sample_tie_fraction")},
                     indent=2))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
