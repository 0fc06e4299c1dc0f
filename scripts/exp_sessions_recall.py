"""Planted-campaign recall on the INDEPENDENT session generator.

VERDICT r04 next #4: every prior accuracy number rode the mixture
generator the model family shares assumptions with. This experiment
runs the full production pipeline on synth2.py's session/state-machine
telemetry and reports per-CAMPAIGN recall (scan / beacon / exfil; DGA /
tunnel; C2 / URI-exfil) at several result depths — honestly, whichever
way it comes out.

Two arms:
  * before — uniform equal-mass quantile bins (the r01-r04 recipe).
    Measured first because the independent data EXPOSED a blindness:
    out-of-support magnitudes (40-80-char exfil URIs, GB-scale
    uploads) saturate the top 20%-mass bin and become word-identical
    to ordinary large values.
  * after  — tail-resolution bins (features.tail_quantile_edges: two
    extra cut points at q99/q99.9), the fix shipped in this round.

The C2/beacon campaigns are DESIGNED to blend (common ports, fixed
legit-looking sizes, top user agent): a word recipe without host
identity cannot see them, and the honest expectation is ~0 recall —
the artifact records that too, with the reason.

    python scripts/exp_sessions_recall.py --out docs/RECALL_r05_sessions.json

Runs on whatever JAX_PLATFORMS gives it and prints the device.
"""
import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import numpy as np  # noqa: E402


def campaign_slices(datatype: str, n_anomalies: int) -> dict:
    """Mirror of synth2's campaign layout inside anomaly_idx."""
    if datatype == "flow":
        n_scan = int(n_anomalies * 0.4)
        n_beacon = int(n_anomalies * 0.3)
        return {"scan": (0, n_scan),
                "beacon": (n_scan, n_scan + n_beacon),
                "exfil_443": (n_scan + n_beacon, n_anomalies)}
    if datatype == "dns":
        n_dga = n_anomalies // 2
        return {"dga": (0, n_dga), "tunnel": (n_dga, n_anomalies)}
    n_c2 = n_anomalies // 2
    return {"c2_blend": (0, n_c2), "uri_exfil": (n_c2, n_anomalies)}


def run_arm(datatype: str, n_events: int, n_anomalies: int, seed: int,
            n_sweeps: int, depths, tail_bins: bool) -> dict:
    from onix.utils import features
    if not tail_bins:
        # The r01-r04 binning, reproduced exactly by fitting edges
        # without the tail cut points (explicit, visible monkeypatch —
        # this arm documents the blindness the fix removes).
        orig = features.tail_quantile_edges
        import onix.pipelines.words as words_mod
        words_mod.tail_quantile_edges = features.quantile_edges
    try:
        from onix.config import LDAConfig
        from onix.models.lda_gibbs import GibbsLDA
        from onix.pipelines.corpus_build import (build_corpus,
                                                 select_suspicious_events)
        from onix.pipelines.scale import _words_from_cols
        from onix.pipelines.synth2 import SYNTH2_ARRAYS

        t0 = time.monotonic()
        cols = SYNTH2_ARRAYS[datatype](n_events, n_hosts=n_events // 100,
                                       n_anomalies=n_anomalies, seed=seed)
        bundle = build_corpus(_words_from_cols(datatype, cols))
        corpus = bundle.corpus
        cfg = LDAConfig(n_topics=20, n_sweeps=n_sweeps,
                        burn_in=max(1, n_sweeps // 2), block_size=1 << 14,
                        seed=seed)
        fit = GibbsLDA(cfg, corpus.n_docs, corpus.n_vocab).fit(corpus)
        top = select_suspicious_events(bundle, fit["theta"], fit["phi_wk"],
                                       n_events, tol=1.0,
                                       max_results=max(depths))
        # Doc-level arm (round 5): the campaign detector. Where does
        # each campaign's client land in the topic-rarity ranking?
        from onix.pipelines.corpus_build import doc_rarity_scores
        dsc, _w = doc_rarity_scores(bundle, fit["theta"])
        drank = np.argsort(np.argsort(dsc))
        ids = np.asarray(bundle.doc_u32_ids)
        u32s = np.asarray(bundle.doc_u32_sorted)
        order = np.asarray(top.indices)
        order = order[order >= 0]
        slices = campaign_slices(datatype, n_anomalies)
        ai = cols["anomaly_idx"]
        out = {"n_vocab": int(corpus.n_vocab),
               "n_docs": int(corpus.n_docs),
               "wall_seconds": round(time.monotonic() - t0, 1),
               "client_doc_ranks": {}, "recall": {}}
        # Campaign actor column: dns/proxy key docs by client ip;
        # flow's campaigns act from the SOURCE ip.
        actor = cols["sip_u32"] if datatype == "flow" \
            else cols["client_u32"]
        for name, (lo, hi) in slices.items():
            ranks = []
            for cu in np.unique(actor[ai[lo:hi]]):
                pos = np.searchsorted(u32s, np.uint32(cu))
                if pos < len(u32s) and u32s[pos] == cu:
                    ranks.append(int(drank[ids[pos]]))
            out["client_doc_ranks"][name] = sorted(ranks)
        for depth in depths:
            sel = set(order[:depth].tolist())
            by_c = {}
            for name, (lo, hi) in slices.items():
                ids = ai[lo:hi]
                by_c[name] = round(
                    len(sel & set(ids.tolist())) / max(len(ids), 1), 4)
            by_c["all"] = round(
                len(sel & set(ai.tolist())) / len(ai), 4)
            out["recall"][str(depth)] = by_c
        return out
    finally:
        if not tail_bins:
            words_mod.tail_quantile_edges = orig


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--events", type=float, default=2e6)
    ap.add_argument("--anomalies", type=int, default=600)
    ap.add_argument("--sweeps", type=int, default=40)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--depths", type=int, nargs="+",
                    default=[1000, 3000, 10000])
    ap.add_argument("--datatypes", nargs="+",
                    default=["flow", "dns", "proxy"])
    ap.add_argument("--out", default="docs/RECALL_r05_sessions.json")
    args = ap.parse_args()
    from onix.utils.obs import print_device
    print_device()

    doc = {
        "metric": "planted-campaign recall on INDEPENDENT session/"
                  "state-machine telemetry (synth2, NOT mixture-"
                  "generated)",
        "n_events": int(args.events),
        "n_anomalies": args.anomalies,
        "n_sweeps": args.sweeps,
        "seed": args.seed,
        "note": ("before = r01-r04 uniform quantile bins; after = "
                 "tail-resolution bins (q99/q99.9). c2_blend/beacon "
                 "campaigns deliberately mimic benign words (common "
                 "port/size/UA, no host identity in the word recipe) — "
                 "near-zero recall there is the expected truthful "
                 "outcome, not a regression."),
        "arms": {},
    }
    outp = pathlib.Path(args.out)
    for arm, tail in (("before_uniform_bins", False),
                      ("after_tail_bins", True)):
        doc["arms"][arm] = {}
        for dt in args.datatypes:
            r = run_arm(dt, int(args.events), args.anomalies, args.seed,
                        args.sweeps, args.depths, tail_bins=tail)
            doc["arms"][arm][dt] = r
            print(f"[{arm}/{dt}] {json.dumps(r['recall'])}", flush=True)
            outp.parent.mkdir(parents=True, exist_ok=True)
            outp.write_text(json.dumps(doc, indent=2) + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
