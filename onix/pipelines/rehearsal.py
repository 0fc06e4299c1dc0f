"""Judged-metric rehearsal: top-1k suspicious-connect overlap vs oracle.

BASELINE.json's fidelity metric is "top-1k suspicious-connect overlap vs
lda-c >= 0.95". The reference binary is absent from the mount, so the
C++ `onix-lda-ref` engine stands in for lda-c (SURVEY.md §2.4 #1). This
module runs the full pairing on a realistic role-structured flow day and
records every number that contextualizes the bar:

  * jax_vs_oracle      — the judged number: JAX multi-chain Gibbs
                         (geometric score-average over chains) vs an
                         oracle restart-ensemble.
  * oracle_vs_oracle   — the achievable ceiling: two disjoint oracle
                         ensembles against each other. Run-to-run
                         posterior noise bounds ANY engine's agreement.
  * single_run_floor   — one oracle run vs another: what the metric
                         looks like without ensemble averaging (the
                         round-1 design measured ~0.85 here).
  * gibbs_vs_vem       — the inter-algorithm gap SURVEY.md §7.3.2 asks
                         to quantify (lda-c lineage is VEM; BASELINE
                         calls it a Gibbs sampler — the truth is the
                         band between them).

Method notes in docs/OVERLAP.md. Reproduce with:
    python -m onix.pipelines.rehearsal --events 100000 --out <path>
"""

from __future__ import annotations

import json
import pathlib
import time

import numpy as np

JUDGED_K = 1000
JUDGED_BAR = 0.95


def run_rehearsal(n_events: int = 100_000, n_sweeps: int = 300,
                  n_chains: int = 8, n_oracle_runs: int = 8,
                  n_topics: int = 20, alpha: float = 0.5, eta: float = 0.05,
                  seed: int = 5, datatype: str = "flow",
                  generator: str = "mixture",
                  bf16_arm: bool = False, engine: str = "gibbs",
                  engine_mesh: tuple[int, int] | None = None,
                  sync_splits: int = 1,
                  out_path=None) -> dict:
    """engine="sharded" runs the SAME judged pairing with the multi-chip
    ShardedGibbsLDA (chained restart ensemble vmapped per device over
    the ambient mesh) instead of the single-device GibbsLDA — closing
    VERDICT r03 weak #5: the 0.95 bar and the multi-chip engine must be
    satisfiable by ONE engine, not one each."""
    from onix import oracle
    from onix.config import LDAConfig
    from onix.models.lda_gibbs import GibbsLDA
    from onix.models.scoring import score_all
    from onix.pipelines.corpus_build import build_corpus
    from onix.pipelines.synth import SYNTH
    from onix.pipelines.words import WORD_FNS

    if generator not in ("mixture", "sessions"):
        raise ValueError(f"unknown generator {generator!r}; "
                         "expected 'mixture' or 'sessions'")
    if generator == "sessions":
        # The independent witness: session/state-machine telemetry the
        # model family did NOT generate (synth2.py; VERDICT r04 next
        # #4). The overlap pairing itself is engine-vs-oracle on the
        # SAME corpus, so the bar is meaningful on any data — running
        # it here shows the agreement doesn't depend on
        # mixture-generated input.
        from onix.pipelines.scale import _words_from_cols
        from onix.pipelines.synth2 import SYNTH2_ARRAYS
        cols = SYNTH2_ARRAYS[datatype](
            n_events, n_hosts=max(120, n_events // 250),
            n_anomalies=max(30, n_events // 650), seed=seed)
        n_day = len(cols["hour"])
        planted = cols["anomaly_idx"]
        bundle = build_corpus(_words_from_cols(datatype, cols))
        del cols
    else:
        day, planted = SYNTH[datatype](
            n_events=n_events, n_hosts=max(120, n_events // 250),
            n_anomalies=max(30, n_events // 650), seed=seed)
        n_day = len(day)
        bundle = build_corpus(WORD_FNS[datatype](day))
    corpus = bundle.corpus
    sc = corpus.to_doc_word_counts()

    walls = {}
    t = time.monotonic()
    ora_a = oracle.gibbs_ensemble_scores(
        sc, corpus.doc_ids, corpus.word_ids, n_topics=n_topics, alpha=alpha,
        eta=eta, n_sweeps=n_sweeps, n_runs=n_oracle_runs, seed=100)
    ora_b = oracle.gibbs_ensemble_scores(
        sc, corpus.doc_ids, corpus.word_ids, n_topics=n_topics, alpha=alpha,
        eta=eta, n_sweeps=n_sweeps, n_runs=n_oracle_runs, seed=500)
    walls["oracle_ensembles"] = round(time.monotonic() - t, 1)

    t = time.monotonic()
    g1 = oracle.gibbs(sc, n_topics=n_topics, alpha=alpha, eta=eta,
                      n_sweeps=n_sweeps, burn_in=n_sweeps // 2, seed=31)
    g2 = oracle.gibbs(sc, n_topics=n_topics, alpha=alpha, eta=eta,
                      n_sweeps=n_sweeps, burn_in=n_sweeps // 2, seed=32)
    s1 = oracle.score_events_np(g1["theta"], g1["phi"],
                                corpus.doc_ids, corpus.word_ids)
    s2 = oracle.score_events_np(g2["theta"], g2["phi"],
                                corpus.doc_ids, corpus.word_ids)
    vem = oracle.vem(sc, n_topics=n_topics, alpha=alpha, eta=eta,
                     em_max_iter=80, seed=31)
    sv = oracle.score_events_np(vem["theta"], vem["phi"],
                                corpus.doc_ids, corpus.word_ids)
    walls["oracle_singles_and_vem"] = round(time.monotonic() - t, 1)

    t = time.monotonic()
    cfg = LDAConfig(n_topics=n_topics, alpha=alpha, eta=eta,
                    n_sweeps=n_sweeps, burn_in=n_sweeps // 2,
                    block_size=8192, seed=0, n_chains=n_chains,
                    sync_splits=sync_splits)
    if engine == "sharded":
        from onix.parallel.mesh import make_mesh
        from onix.parallel.sharded_gibbs import ShardedGibbsLDA
        mesh = (make_mesh(dp=engine_mesh[0], mp=engine_mesh[1])
                if engine_mesh else None)
        fit = ShardedGibbsLDA(cfg, corpus.n_vocab, mesh=mesh).fit(corpus)
    else:
        fit = GibbsLDA(cfg, corpus.n_docs, corpus.n_vocab).fit(corpus)
    jx = np.asarray(score_all(fit["theta"], fit["phi_wk"],
                              corpus.doc_ids, corpus.word_ids))
    walls["jax_fit_and_score"] = round(time.monotonic() - t, 1)
    jx16 = None
    if bf16_arm:
        # The bf16 arm: identical fit, tables rounded to bfloat16 at
        # rest — exactly what `top_suspicious(..., table_dtype=
        # "bfloat16")` does on TPU (gather bf16, upcast, f32 dot).
        # Scoring it against the SAME oracle answers whether bf16
        # tables meet the judged fidelity bar. Opt-in: it costs a full
        # extra score_all pass, and its wall is recorded apart so
        # jax_fit_and_score stays comparable across rounds.
        import jax.numpy as jnp
        t = time.monotonic()
        rb = lambda a: np.asarray(jnp.asarray(a).astype(jnp.bfloat16)
                                  .astype(jnp.float32))
        jx16 = np.asarray(score_all(rb(fit["theta"]), rb(fit["phi_wk"]),
                                    corpus.doc_ids, corpus.word_ids))
        walls["bf16_score"] = round(time.monotonic() - t, 1)

    k = JUDGED_K
    # Detection sanity alongside fidelity: fraction of planted exfil
    # events each engine surfaces in its bottom-k (event score = min
    # over the event's tokens, via the layout-checked shared helper).
    from onix.pipelines.corpus_build import event_scores
    n = n_day
    hits = {}
    for name, sc_tok in (("jax", jx), ("oracle", ora_a)):
        ev = event_scores(bundle, np.asarray(sc_tok), n)
        bottom = set(np.argsort(ev)[:k].tolist())
        hits[name] = round(
            len(bottom & set(planted.tolist())) / len(planted), 4)
    result = {
        "metric": f"top-{k} suspicious-connect overlap vs oracle",
        "bar": JUDGED_BAR,
        "jax_vs_oracle": round(oracle.topk_overlap(jx, ora_a, k), 4),
        "jax_vs_oracle_b": round(oracle.topk_overlap(jx, ora_b, k), 4),
        "oracle_vs_oracle": round(oracle.topk_overlap(ora_a, ora_b, k), 4),
        "single_run_floor": round(oracle.topk_overlap(s1, s2, k), 4),
        "gibbs_vs_vem": round(oracle.topk_overlap(s1, sv, k), 4),
        "jax_vs_vem": round(oracle.topk_overlap(jx, sv, k), 4),
        "overlap_at_k": {
            str(kk): round(oracle.topk_overlap(jx, ora_a, kk), 4)
            for kk in (100, 500, 1000, 2000)},
        "planted_hit_at_k": hits,
        "config": {
            "datatype": datatype, "engine": engine,
            "generator": generator,
            "engine_mesh": list(engine_mesh) if engine_mesh else None,
            "n_events": n_events, "n_docs": int(corpus.n_docs),
            "n_vocab": int(corpus.n_vocab),
            "n_tokens": int(corpus.n_tokens), "n_topics": n_topics,
            "alpha": alpha, "eta": eta, "n_sweeps": n_sweeps,
            "n_chains": n_chains, "n_oracle_runs": n_oracle_runs,
            "sync_splits": sync_splits,
            "seed": seed},
        "walls_seconds": walls,
    }
    if jx16 is not None:
        result["jax_bf16_vs_oracle"] = round(
            oracle.topk_overlap(jx16, ora_a, k), 4)
        result["bf16_vs_f32"] = round(oracle.topk_overlap(jx16, jx, k), 4)
    result["passes_bar"] = bool(result["jax_vs_oracle"] >= JUDGED_BAR)
    if out_path is not None:
        out_path = pathlib.Path(out_path)
        out_path.parent.mkdir(parents=True, exist_ok=True)
        out_path.write_text(json.dumps(result, indent=2) + "\n")
    return result


def summarize_cells(cells: dict) -> dict:
    """Per-datatype min-over-seeds summary of rehearsal cells keyed
    "<datatype>/seed<N>": the ONE judged-bar aggregation for a study
    (recipes in the committed docs/OVERLAP_r0*.json artifacts; single
    cells re-run via `scripts/exp_campaign.py --rehearsal-cell`)."""
    per_dt = {}
    for dt in sorted({k.split("/")[0] for k in cells}):
        mine = [c for k, c in cells.items() if k.startswith(dt + "/")]
        vals = [c["jax_vs_oracle"] for c in mine]
        per_dt[dt] = {
            "jax_vs_oracle_by_seed": vals,
            "min_over_seeds": min(vals),
            "oracle_ceiling_by_seed": [c["oracle_vs_oracle"] for c in mine],
            "n_chains": sorted({c["config"]["n_chains"] for c in mine}),
            "n_oracle_runs": sorted({c["config"]["n_oracle_runs"]
                                     for c in mine}),
            "passes_bar_min": min(vals) >= JUDGED_BAR,
        }
    return per_dt


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="judged overlap rehearsal")
    ap.add_argument("--events", type=int, default=100_000)
    ap.add_argument("--sweeps", type=int, default=300)
    ap.add_argument("--chains", type=int, default=8)
    ap.add_argument("--oracle-runs", type=int, default=8)
    ap.add_argument("--datatype", choices=("flow", "dns", "proxy"),
                    default="flow")
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--generator", choices=("mixture", "sessions"),
                    default="mixture",
                    help="telemetry source: role-mixture synth or the "
                         "independent session/state-machine generator")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    r = run_rehearsal(n_events=args.events, n_sweeps=args.sweeps,
                      n_chains=args.chains, n_oracle_runs=args.oracle_runs,
                      datatype=args.datatype, seed=args.seed,
                      generator=args.generator,
                      out_path=args.out)
    print(json.dumps(r, indent=2))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
