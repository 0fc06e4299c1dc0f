"""Replay harness for the analyst feedback loop (r13, ISSUE 9).

Acceptance bar (ISSUE 9 / ROADMAP item 4): a flagged-then-dismissed
(src, dst) pair stops appearing in the streaming winner set within
<= N batches — N=1 via the immediate noise filter, N<=5 via the online
λ/γ update ALONE (filter disabled) — while recall on injected true
positives is unchanged vs a no-feedback control, and a filter of zero
entries is bit-identical to no filter at all.

Construction: a synthetic flow stream (synth.synth_flow_day
background) with PERSISTENT planted campaigns — one dismissable beacon
pair plus `--tp-pairs` true-positive pairs, each recurring every batch
with off-profile ports/sizes so they land in the per-batch winner set.
Three arms over the SAME batches:

  control   — no feedback; the beacon and every TP stay detected.
  filter    — at --feedback-batch the beacon's alert rows are labeled
              benign with the online update OFF: detection must stop
              on the NEXT batch (lag <= 1).
  online    — same labels with the immediate filter OFF: the
              feedback-weighted minibatch (feedback.dismiss_weight,
              the ×DUPFACTOR analog) must stop detection within
              --max-online-lag batches without any filtering.

Every arm asserts TP recall == control per batch. The bit-identity arm
re-scores one batch under an explicitly EMPTY filter and asserts
per-event scores identical to the control's.

    python scripts/exp_feedback_loop.py --out docs/FEEDBACK_r13_cpu.json
    python scripts/exp_feedback_loop.py --small     # tier-1 smoke shape

Exit code 0 = every assertion held; the JSON artifact carries the
per-batch detection timelines either way.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys

import numpy as np
import pandas as pd

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from onix.config import OnixConfig                       # noqa: E402
from onix.pipelines.streaming import StreamingScorer     # noqa: E402
from onix.pipelines.synth import synth_flow_day          # noqa: E402


@dataclasses.dataclass
class Spec:
    n_batches: int = 6          # measured batches per arm — kept short
    #                             enough that the recurring plants have
    #                             not yet accumulated word mass and
    #                             FADED from the winner set naturally
    #                             (the campaign-fade effect would then
    #                             confound the feedback lag)
    warm_epochs: int = 6        # burn-in replays of the batch set before
    #                             the measured phase (run_stream's
    #                             epochs>1 mechanism): a cold SVI model
    #                             scores everything near the uniform
    #                             prior and no winner set is meaningful
    events_per_batch: int = 1500

    n_hosts: int = 100
    tp_pairs: int = 3
    beacon_events: int = 2      # beacon rows per batch (more rows per
    #                             batch accumulate word mass and fade
    #                             the campaign out of the winner set)
    feedback_batch: int = 2     # label the beacon after this batch (1-based)
    max_online_lag: int = 5
    n_buckets: int = 1 << 10
    max_results: int = 120      # winner-set size: alerts are the
    #                             bottom-max_results scores per batch,
    #                             so "detected" means "in the top
    #                             suspicious winners", not merely
    #                             "under tol"
    seed: int = 0


def _plant_rows(template: pd.DataFrame, sip: str, dip: str, n: int,
                sport: int, dport: int, hour: str = "03:33",
                ipkt: int = 2, ibyt: int = 99) -> pd.DataFrame:
    """A recurring off-profile campaign: ephemeral<->ephemeral ports,
    odd payloads — signatures the synth backgrounds never emit, so the
    pair's word stays rare and the campaign is detected every batch.

    Each campaign gets its OWN (hour, sizes) signature: the flow word
    is (proto, port class, hour bin, byte bin, packet bin), so two
    campaigns sharing a signature share a word BUCKET — and a model
    update learned from dismissing one would bleed onto the other.
    Distinct campaigns must be distinct words, as they are in real
    traffic. The hour is FIXED per campaign (the word includes the
    hour bin; rows inheriting the template's random hours would hash
    to a different bucket every batch — no model could learn them, and
    no analyst would see one campaign). Real beacons fire on a
    schedule."""
    rows = template.iloc[:n].copy()
    rows["sip"] = sip
    rows["dip"] = dip
    rows["sport"] = sport
    rows["dport"] = dport
    rows["proto"] = "TCP"
    rows["ipkt"] = ipkt
    rows["ibyt"] = ibyt
    rows["treceived"] = f"2016-07-08 {hour}:00"
    return rows


BEACON = ("10.66.66.66", "203.0.113.99")


def _tp_pair(i: int) -> tuple[str, str]:
    return (f"10.77.{i}.7", f"198.51.100.{i + 1}")


def make_batch(spec: Spec, b: int, plants: bool = True) -> pd.DataFrame:
    bg, _ = synth_flow_day(n_events=spec.events_per_batch,
                           n_hosts=spec.n_hosts, n_anomalies=0,
                           seed=spec.seed + b)
    if not plants:
        return bg
    extra = [_plant_rows(bg, *BEACON, spec.beacon_events,
                         44123, 51789)]
    for i in range(spec.tp_pairs):
        extra.append(_plant_rows(
            bg, *_tp_pair(i), spec.beacon_events,
            45000 + 7 * i, 52000 + 11 * i,
            hour=f"{7 + 3 * i:02d}:1{i}", ipkt=400 + 50 * i,
            ibyt=900_000 + 70_000 * i))
    return pd.concat([bg, *extra], ignore_index=True)


def _pair_alerts(alerts: pd.DataFrame, pair: tuple[str, str]) -> int:
    if len(alerts) == 0:
        return 0
    return int(((alerts["sip"] == pair[0])
                & (alerts["dip"] == pair[1])).sum())


def run_arm(spec: Spec, name: str, *, feedback: bool,
            immediate: bool, online: bool) -> dict:
    cfg = OnixConfig()
    cfg.pipeline.max_results = spec.max_results
    cfg.validate()
    sc = StreamingScorer(cfg, "flow", n_buckets=spec.n_buckets)
    # Burn-in: background-only epochs train the model before the
    # campaigns START (scores from a cold SVI model sit near the
    # uniform prior and rank by noise; and a campaign word seen all
    # through training accumulates mass until it stops being rare:
    # the campaign fades). The measured
    # phase then injects the persistent plants into fresh-seed
    # batches: zero-lag detection of a NEW campaign against a warm
    # model, the streaming scorer's actual contract.
    for ep in range(spec.warm_epochs):
        for b in range(spec.n_batches):
            sc.process(make_batch(spec, b, plants=False))
    timeline = []
    results = []
    fed = False
    for b in range(spec.n_batches):
        res = sc.process(make_batch(spec, 1000 + b))
        results.append(res)
        timeline.append({
            "batch": b + 1,
            "beacon_alerts": _pair_alerts(res.alerts, BEACON),
            "tp_alerts": [_pair_alerts(res.alerts, _tp_pair(i))
                          for i in range(spec.tp_pairs)],
            "n_alerts": int(len(res.alerts)),
        })
        if feedback and not fed and b + 1 == spec.feedback_batch:
            mask = ((res.alerts["sip"] == BEACON[0])
                    & (res.alerts["dip"] == BEACON[1]))
            rows = res.alerts[mask].drop(columns=["score", "event_idx"])
            if len(rows) == 0:
                raise AssertionError(
                    f"{name}: beacon not detected by batch "
                    f"{spec.feedback_batch}; cannot label it")
            stats = sc.apply_feedback(rows, np.full(len(rows), 3),
                                      immediate=immediate, online=online)
            timeline[-1]["feedback"] = stats
            fed = True
    # Detection lag: batches AFTER the feedback batch until the beacon
    # first disappears from the winner set (None = never disappears).
    lag = None
    if feedback:
        for t in timeline[spec.feedback_batch:]:
            if t["beacon_alerts"] == 0:
                lag = t["batch"] - spec.feedback_batch
                break
    return {"name": name, "timeline": timeline, "lag_batches": lag,
            "scorer": sc, "results": results}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description="feedback-loop replay: dismissed traffic stops "
                    "resurfacing; recall elsewhere preserved")
    ap.add_argument("--batches", type=int, default=None)
    ap.add_argument("--events-per-batch", type=int, default=None)
    ap.add_argument("--tp-pairs", type=int, default=None)
    ap.add_argument("--max-online-lag", type=int, default=5)
    ap.add_argument("--small", action="store_true",
                    help="tier-1 smoke shape (~6 tiny batches)")
    ap.add_argument("--out", default=None,
                    help="write the JSON artifact here")
    args = ap.parse_args(argv)

    spec = Spec(max_online_lag=args.max_online_lag)
    if args.small:
        spec = Spec(n_batches=5, warm_epochs=4, events_per_batch=800,
                    n_hosts=60, tp_pairs=2, feedback_batch=2,
                    max_results=60, max_online_lag=args.max_online_lag)
    if args.batches:
        spec = dataclasses.replace(spec, n_batches=args.batches)
    if args.events_per_batch:
        spec = dataclasses.replace(spec,
                                   events_per_batch=args.events_per_batch)
    if args.tp_pairs is not None:
        spec = dataclasses.replace(spec, tp_pairs=args.tp_pairs)

    checks: dict[str, bool] = {}

    def check(name: str, ok: bool, detail: str = "") -> None:
        checks[name] = bool(ok)
        print(f"  [{'ok' if ok else 'FAIL'}] {name}"
              + (f" — {detail}" if detail else ""))

    print(f"== control arm ({spec.n_batches} batches x "
          f"{spec.events_per_batch} events)")
    control = run_arm(spec, "control", feedback=False,
                      immediate=False, online=False)
    pre = control["timeline"][spec.feedback_batch - 1]
    check("control_detects_beacon",
          all(t["beacon_alerts"] > 0 for t in control["timeline"]),
          f"beacon alerts/batch: "
          f"{[t['beacon_alerts'] for t in control['timeline']]}")
    check("control_detects_tps",
          all(min(t["tp_alerts"]) > 0 for t in control["timeline"]))

    print("== immediate-filter arm (online update off)")
    filt = run_arm(spec, "filter", feedback=True,
                   immediate=True, online=False)
    check("filter_lag_le_1", filt["lag_batches"] is not None
          and filt["lag_batches"] <= 1,
          f"lag={filt['lag_batches']} batches")
    check("filter_beacon_never_resurfaces",
          all(t["beacon_alerts"] == 0
              for t in filt["timeline"][spec.feedback_batch:]))

    print("== online-update arm (immediate filter off)")
    online = run_arm(spec, "online", feedback=True,
                     immediate=False, online=True)
    check(f"online_lag_le_{spec.max_online_lag}",
          online["lag_batches"] is not None
          and online["lag_batches"] <= spec.max_online_lag,
          f"lag={online['lag_batches']} batches")

    # Recall on true positives: every arm must match the control's
    # per-batch TP detection exactly (zero-lag detection on everything
    # else is preserved).
    for arm in (filt, online):
        same = all(
            (np.asarray(t["tp_alerts"]) > 0).tolist()
            == (np.asarray(c["tp_alerts"]) > 0).tolist()
            for t, c in zip(arm["timeline"], control["timeline"]))
        check(f"{arm['name']}_tp_recall_unchanged", same)

    # Bit-identity: an explicitly EMPTY filter re-scores one batch with
    # per-event scores identical to a no-filter scorer's.
    from onix.feedback.filter import HostFilter
    cfg_id = OnixConfig()
    cfg_id.pipeline.max_results = spec.max_results
    sc_a = StreamingScorer(cfg_id, "flow", n_buckets=spec.n_buckets)
    sc_b = StreamingScorer(cfg_id, "flow", n_buckets=spec.n_buckets)
    sc_b.noise_filter = HostFilter.empty()
    ra = sc_a.process(make_batch(spec, 0))
    rb = sc_b.process(make_batch(spec, 0))
    check("empty_filter_bit_identical",
          np.array_equal(ra.scores, rb.scores)
          and ra.alerts["event_idx"].tolist()
          == rb.alerts["event_idx"].tolist())

    ok = all(checks.values())
    artifact = {
        "spec": dataclasses.asdict(spec),
        "checks": checks,
        "ok": ok,
        "pre_feedback_beacon_alerts": pre["beacon_alerts"],
        "lags": {"filter": filt["lag_batches"],
                 "online": online["lag_batches"]},
        "feedback_stats": {
            "filter": filt["scorer"].feedback_stats,
            "online": online["scorer"].feedback_stats},
        "timelines": {a["name"]: a["timeline"]
                      for a in (control, filt, online)},
    }
    line = json.dumps({"ok": ok, "lag_filter": filt["lag_batches"],
                       "lag_online": online["lag_batches"]})
    print(line)
    if args.out:
        out = pathlib.Path(args.out)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(artifact, indent=2) + "\n")
        print(f"artifact: {out}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
