"""DAILY_r19: the continuous-operation acceptance experiment (ISSUE 15
tentpole; ROADMAP item 4).

Seven simulated days through the daily supervisor
(onix/pipelines/daily.py), stationary background traffic
(day_seed_stride=0 — the same enterprise keeps the same habits all
week) with planted campaigns on days 1 and 7 and a mid-week analyst
dismissal on day 4:

  * **cold** — the control: every day fits from scratch
    (daily.force_cold), no feedback. Establishes the full-budget fit
    walls, the plant detections, and — because the mid-week feeds are
    identical — that the day-4 false-positive winner RECURS on days
    5 and 6 absent feedback.
  * **warm** — the production chain: day d warm-starts from day d−1's
    persisted φ̂ (φ̂-as-prior z-init, arxiv 1601.01142) under half the
    sweep budget, drift-gated (daily.drift_max), with the day-4
    dismissal feeding the corpus build ×dupfactor from day 5 on (the
    reference's DUPFACTOR noise-filter loop).

Asserted every run: warm-start cuts the days-2..7 fit wall vs cold
(the ratio is THE reported number), plant detection parity-or-better
on days 1 AND 7, every warm day inside the drift gate, and the
dismissed event gone from the warm arm's winners on days 5 and 6 —
suppressed through the NEXT day's refit and the one after — while the
cold control still surfaces it.

    python scripts/exp_daily.py --out docs/DAILY_r19_cpu.json

Runs on whatever JAX_PLATFORMS gives it and prints the device.
"""

import argparse
import json
import pathlib
import sys
import tempfile
import time

import jax

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from onix.config import DailyConfig  # noqa: E402
from onix.pipelines.daily import run_daily  # noqa: E402


def _fit_walls(manifest: dict) -> dict:
    out = {}
    for rec in manifest["days"]:
        if rec.get("status") != "ok":
            continue
        walls = rec["timing"]["stage_walls_s"]
        out[rec["day"]] = round(sum(w["fit"] for w in walls.values()), 3)
    return out


def _hits(manifest: dict, day: int) -> dict:
    rec = manifest["days"][day - 1]
    return {dt: w["planted_in_bottom_k"]
            for dt, w in rec["winners"].items()}


def _winner_idx(manifest: dict, day: int, dt: str) -> set:
    return set(manifest["days"][day - 1]["winners"][dt]["indices"])


def main() -> int:
    ap = argparse.ArgumentParser(
        description="r19 continuous-operation acceptance harness")
    ap.add_argument("--days", type=int, default=7)
    ap.add_argument("--events", type=int, default=60_000,
                    help="events per datatype per day")
    ap.add_argument("--datatypes", default="flow,dns")
    ap.add_argument("--sweeps", type=int, default=24,
                    help="cold fit budget; warm runs half (daily auto)")
    ap.add_argument("--topics", type=int, default=20)
    ap.add_argument("--max-results", type=int, default=200)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--plant", type=int, default=60,
                    help="planted anomalies on day 1 and the final day")
    ap.add_argument("--dismiss-day", type=int, default=4)
    ap.add_argument("--drift-max", type=float, default=0.5)
    ap.add_argument("--out", default="docs/DAILY_r19_cpu.json")
    args = ap.parse_args()
    from onix.utils.obs import print_device
    print_device()
    datatypes = tuple(d.strip() for d in args.datatypes.split(",")
                      if d.strip())
    plants = {1: args.plant, args.days: args.plant}
    kw = dict(n_events=args.events, datatypes=datatypes,
              n_sweeps=args.sweeps, n_topics=args.topics,
              max_results=args.max_results, seed=args.seed,
              plants=plants, collect_winner_pairs=True)
    d_day = args.dismiss_day

    t_all = time.monotonic()
    with tempfile.TemporaryDirectory(prefix="onix-daily-") as td:
        td = pathlib.Path(td)
        # ---- arm 1: the cold control ---------------------------------
        print("cold control arm", flush=True)
        cold = run_daily(args.days, td / "cold",
                         daily=DailyConfig(force_cold=True,
                                           day_seed_stride=0), **kw)
        assert cold["aggregate"]["ok_days"] == args.days

        # The analyst's mid-week dismissal: the most suspicious
        # NON-planted day-4 winner that also recurs in the day-5
        # control winners (stationary week ⇒ the same row index is the
        # same event) — a recurring false positive, exactly what the
        # noise-filter loop exists for.
        rec4 = cold["days"][d_day - 1]["winners"]
        dismiss_dt = dismissed = None
        for dt in datatypes:
            nxt = _winner_idx(cold, d_day + 1, dt)
            for wp in rec4[dt]["winner_pairs"]:
                if wp["event"] in nxt:
                    dismiss_dt, dismissed = dt, wp
                    break
            if dismissed:
                break
        assert dismissed is not None, (
            "no recurring day-4 winner to dismiss — raise --max-results")
        import pandas as pd
        fb = pd.DataFrame([{"ip": ip, "word": word}
                           for ip, word in dismissed["pairs"]])
        recurred = [d for d in range(d_day + 1, args.days)
                    if dismissed["event"] in _winner_idx(cold, d,
                                                         dismiss_dt)]
        assert recurred, "control lost the dismissed winner on its own"

        # ---- arm 2: warm + the day-4 dismissal -----------------------
        # Counters are process-global; reset the arm-visible namespaces
        # so the warm arm's resilience block reports ONLY its own
        # events (the cold arm's block was snapshotted inside its own
        # run_daily).
        from onix.utils.obs import counters
        for ns in ("daily", "campaign", "faults", "ckpt"):
            counters.reset(ns)
        print(f"warm arm (dismissing {dismiss_dt} event "
              f"{dismissed['event']} from day {d_day + 1})", flush=True)
        warm = run_daily(args.days, td / "warm",
                         daily=DailyConfig(drift_max=args.drift_max,
                                           day_seed_stride=0),
                         feedback={d_day + 1: fb}, **kw)
        assert warm["aggregate"]["ok_days"] == args.days

    # ---- the judged numbers ------------------------------------------
    cold_walls, warm_walls = _fit_walls(cold), _fit_walls(warm)
    # Day 1 is cold in both arms; the warm-start claim is days 2..N.
    cold_tail = sum(cold_walls[d] for d in range(2, args.days + 1))
    warm_tail = sum(warm_walls[d] for d in range(2, args.days + 1))
    ratio = round(cold_tail / max(warm_tail, 1e-9), 3)
    assert warm_tail < cold_tail, (
        f"warm-start did not cut the fit wall: {warm_tail} vs {cold_tail}")

    refits = {rec["day"]: {dt: rec["refit"][dt] for dt in datatypes}
              for rec in warm["days"]}
    for day in range(2, args.days + 1):
        for dt in datatypes:
            assert refits[day][dt]["form"] == "warm", (
                f"day {day} {dt} fell back to {refits[day][dt]['form']}")

    plant_parity = {}
    for day in (1, args.days):
        hc, hw = _hits(cold, day), _hits(warm, day)
        plant_parity[str(day)] = {"cold": hc, "warm": hw}
        for dt in datatypes:
            tol = max(2, round(0.15 * max(hc[dt], 1)))
            assert hw[dt] >= hc[dt] - tol and hw[dt] > 0, (
                f"day {day} {dt}: warm lost the plant ({hw[dt]} vs "
                f"{hc[dt]})")

    # Dismissal suppression: gone from the warm arm's winners on every
    # comparable post-dismissal day (5, 6 — day 7's plant changes the
    # feed, so row identity ends there), while the control still
    # surfaces it on those days.
    suppressed_days = []
    for d in recurred:
        assert dismissed["event"] not in _winner_idx(warm, d, dismiss_dt), (
            f"dismissed event resurfaced on day {d} after the refit")
        suppressed_days.append(d)

    doc = {
        "harness": "exp_daily r19",
        "platform": jax.default_backend(),
        "n_devices": len(jax.devices()),
        "config": {
            "days": args.days, "events_per_day": args.events,
            "datatypes": list(datatypes), "cold_sweeps": args.sweeps,
            "warm_sweeps": max(2, args.sweeps // 2),
            "topics": args.topics, "max_results": args.max_results,
            "seed": args.seed, "plants": {str(k): v
                                          for k, v in plants.items()},
            "drift_max": args.drift_max, "day_seed_stride": 0,
        },
        "fit_walls_s": {"cold": cold_walls, "warm": warm_walls},
        "fit_wall_days2plus_s": {"cold": round(cold_tail, 3),
                                 "warm": round(warm_tail, 3)},
        "warm_vs_cold_fit_wall_ratio": ratio,
        "plant_detection": plant_parity,
        "warm_refit_forms": {str(d): refits[d] for d in sorted(refits)},
        "drift_by_day": {str(rec["day"]): {dt: rec["refit"][dt]["drift"]
                                           for dt in datatypes}
                         for rec in warm["days"] if rec["day"] > 1},
        "dismissal": {
            "day_dismissed": d_day, "applied_from_day": d_day + 1,
            "datatype": dismiss_dt, "event": dismissed["event"],
            "pairs": dismissed["pairs"],
            "recurred_in_control_days": recurred,
            "suppressed_in_warm_days": suppressed_days,
            "suppressed_through_next_refit": True,
        },
        "resilience": {"cold": cold["resilience"],
                       "warm": warm["resilience"]},
        "wall_seconds_total": round(time.monotonic() - t_all, 1),
        "note": ("CPU rows include per-day re-jit in both arms "
                 "symmetrically; the warm-vs-cold ratio is not "
                 "measured on the chip"),
    }
    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(doc, indent=2) + "\n")
    print(json.dumps({k: doc[k] for k in
                      ("warm_vs_cold_fit_wall_ratio",
                       "fit_wall_days2plus_s", "plant_detection",
                       "dismissal")}, default=str))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
