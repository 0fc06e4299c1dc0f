"""FLEET_r20: the fleet-batched refit acceptance experiment (ISSUE 20
tentpole).

Two measurements over the r20 fleet supervisor
(onix/pipelines/fleet.py):

  * **the week** — seven simulated days over a >=200-tenant roster,
    planted campaigns on days 1 and 7, ONE tenant's feed poisoned
    mid-week. Asserted: the poisoned tenant is quarantined ALONE (its
    chain skips the day and reparents on its last ok model; every
    other tenant-day stays ok), and per-tenant warm/cold plant parity
    — each tenant's day-7 WARM chain (six refits deep) detects its
    plant no worse than its own day-1 cold fit.
  * **the sublinearity curve** — one representative all-cold day at
    N in {25, 50, 100, 200} tenants through BOTH arms: the sequential
    per-tenant supervisor (batched=False, one program dispatch per
    tenant — the r19 shape) and the fused fleet arm (ONE vmapped
    Gibbs program per pow2 shape class). Asserted: the fleet arm's
    fit wall grows SUBLINEARLY in N (the vmapped program amortizes
    dispatch + compile across lanes) and beats the sequential arm at
    the top of the curve.

    python scripts/exp_fleet.py --out docs/FLEET_r20_cpu.json

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

from onix.pipelines.fleet import (run_fleet, tenant_lineage,  # noqa: E402
                                  tenant_name)
from onix.utils.obs import counters  # noqa: E402


def _bodies(manifest: dict, tenant: str) -> list[dict]:
    return [rec["tenants"][tenant] for rec in manifest["days"]]


def _plant_hits(manifest: dict, day: int) -> dict:
    rec = manifest["days"][day - 1]
    return {t: b["winners"]["planted_in_bottom_k"]
            for t, b in rec["tenants"].items()
            if b.get("status") == "ok"}


def main() -> int:
    ap = argparse.ArgumentParser(
        description="r20 fleet-batched refit acceptance harness")
    ap.add_argument("--days", type=int, default=7)
    ap.add_argument("--tenants", type=int, default=200)
    ap.add_argument("--events", type=int, default=600,
                    help="events per tenant per day")
    ap.add_argument("--sweeps", type=int, default=8)
    ap.add_argument("--topics", type=int, default=10)
    ap.add_argument("--max-results", type=int, default=60)
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--plant", type=int, default=8,
                    help="planted anomalies on day 1 and the final day")
    ap.add_argument("--poison-day", type=int, default=4)
    ap.add_argument("--curve", default="25,50,100,200",
                    help="tenant counts for the seq-vs-fleet scaling "
                         "curve ('' skips it)")
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--out", default="docs/FLEET_r20_cpu.json")
    args = ap.parse_args()
    from onix.utils.obs import print_device
    print_device()
    assert 1 < args.poison_day < args.days
    plants = {1: args.plant, args.days: args.plant}
    kw = dict(n_events=args.events, n_sweeps=args.sweeps,
              n_topics=args.topics, max_results=args.max_results,
              seed=args.seed, dp=args.dp)
    victim = tenant_name(args.tenants // 2)

    t_all = time.monotonic()
    with tempfile.TemporaryDirectory(prefix="onix-fleet-") as td:
        td = pathlib.Path(td)

        # ---- the week: N tenants, 7 days, one mid-week poisoning ----
        print(f"week arm ({args.tenants} tenants x {args.days} days, "
              f"{victim} poisoned day {args.poison_day})", flush=True)
        week = run_fleet(args.days, args.tenants, td / "week",
                         plants=plants,
                         poison_feed={(victim, args.poison_day)}, **kw)

        agg = week["aggregate"]
        assert agg["failed_tenant_days"] == 1, (
            f"exactly the poisoned day should fail, got "
            f"{agg['failed_tenant_days']}")
        assert agg["ok_tenant_days"] == args.days * args.tenants - 1

        # Quarantined ALONE: the victim's chain skips the poisoned day
        # and reparents on its last ok model; nobody else failed.
        vb = _bodies(week, victim)
        assert vb[args.poison_day - 1]["status"] == "failed"
        assert "PoisonedFeed" in vb[args.poison_day - 1]["error"]
        lin = tenant_lineage(week, victim)
        days_ok = [r["day"] for r in lin]
        assert args.poison_day not in days_ok
        after = days_ok.index(args.poison_day + 1)
        assert lin[after]["parent_digest"] \
            == lin[after - 1]["content_sha256"]
        for u in range(args.tenants):
            t = tenant_name(u)
            if t != victim:
                assert all(b["status"] == "ok" for b in _bodies(week, t))

        # Per-tenant warm/cold plant parity: day 7 (a warm chain six
        # refits deep) vs the SAME tenant's day-1 cold fit.
        cold_hits = _plant_hits(week, 1)
        warm_hits = _plant_hits(week, args.days)
        parity_fail = []
        for t, hc in cold_hits.items():
            hw = warm_hits[t]
            tol = max(2, round(0.5 * max(hc, 1)))
            if hw < hc - tol or (hc > 0 and hw == 0):
                parity_fail.append({"tenant": t, "cold": hc, "warm": hw})
        assert not parity_fail, (
            f"warm chains lost plants: {parity_fail[:5]}")
        mean_cold = sum(cold_hits.values()) / max(len(cold_hits), 1)
        mean_warm = sum(warm_hits.values()) / max(len(warm_hits), 1)
        assert mean_warm >= 0.8 * mean_cold, (
            f"aggregate warm plant detection collapsed: "
            f"{mean_warm:.2f} vs {mean_cold:.2f}")

        # ---- the sublinearity curve: seq vs fleet, one day ----------
        curve = []
        sizes = [int(n) for n in args.curve.split(",") if n.strip()]
        for n in sizes:
            for ns in ("fleet", "campaign", "daily", "faults", "ckpt"):
                counters.reset(ns)
            point = {"n_tenants": n}
            for label, batched in (("fleet", True), ("seq", False)):
                print(f"curve N={n} {label} arm", flush=True)
                m = run_fleet(1, n, td / f"curve-{label}-{n}",
                              plants={1: args.plant}, batched=batched,
                              **kw)
                assert m["aggregate"]["failed_tenant_days"] == 0
                point[f"fit_wall_{label}_s"] = \
                    m["aggregate"]["fit_wall_s"]
                if label == "fleet":
                    point["padding"] = m["padding"]
            point["fleet_speedup"] = round(
                point["fit_wall_seq_s"]
                / max(point["fit_wall_fleet_s"], 1e-9), 3)
            curve.append(point)

        sublinear = None
        if len(sizes) >= 2:
            lo, hi = curve[0], curve[-1]
            dn = hi["n_tenants"] - lo["n_tenants"]
            n_ratio = hi["n_tenants"] / lo["n_tenants"]
            fleet_growth = (hi["fit_wall_fleet_s"]
                            / max(lo["fit_wall_fleet_s"], 1e-9))
            seq_growth = (hi["fit_wall_seq_s"]
                          / max(lo["fit_wall_seq_s"], 1e-9))
            marg_fleet = (hi["fit_wall_fleet_s"]
                          - lo["fit_wall_fleet_s"]) / dn
            marg_seq = (hi["fit_wall_seq_s"]
                        - lo["fit_wall_seq_s"]) / dn
            sublinear = {
                "n_ratio": round(n_ratio, 2),
                "fleet_wall_growth": round(fleet_growth, 3),
                "seq_wall_growth": round(seq_growth, 3),
                "marginal_s_per_tenant": {
                    "fleet": round(marg_fleet, 4),
                    "seq": round(marg_seq, 4)},
            }
            # THE tentpole claim, in its compile-constant-robust form:
            # the fleet wall grows sublinearly in N, and each EXTRA
            # tenant costs the fused arm less than it costs the
            # sequential supervisor (the per-lane dispatch + program
            # overhead the vmap amortizes away). The absolute
            # crossover point depends on the one-time vmap compile —
            # per-point speedups ride in the curve unasserted.
            assert fleet_growth < 0.75 * n_ratio, (
                f"fleet fit wall not sublinear: x{fleet_growth:.2f} "
                f"over x{n_ratio:.0f} tenants")
            assert marg_fleet < marg_seq, (
                f"fused arm's marginal per-tenant cost not below the "
                f"sequential supervisor's: {marg_fleet:.4f} vs "
                f"{marg_seq:.4f} s/tenant")

    doc = {
        "harness": "exp_fleet r20",
        "platform": jax.default_backend(),
        "n_devices": len(jax.devices()),
        "config": {
            "days": args.days, "tenants": args.tenants,
            "events_per_tenant_day": args.events,
            "sweeps": args.sweeps, "topics": args.topics,
            "max_results": args.max_results, "seed": args.seed,
            "dp": args.dp,
            "plants": {str(k): v for k, v in plants.items()},
            "poisoned": {"tenant": victim, "day": args.poison_day},
        },
        "week": {
            "ok_tenant_days": agg["ok_tenant_days"],
            "failed_tenant_days": agg["failed_tenant_days"],
            "fit_wall_s": agg["fit_wall_s"],
            "wall_s": agg["wall_s"],
            "padding": week["padding"],
            "victim_ok_days": days_ok,
            "victim_reparented_over_poison_day": True,
            "plant_parity": {
                "mean_cold_day1": round(mean_cold, 2),
                "mean_warm_day7": round(mean_warm, 2),
                "per_tenant_failures": 0,
            },
        },
        "scaling_curve": curve,
        "sublinearity": sublinear,
        "resilience": week["resilience"],
        "wall_seconds_total": round(time.monotonic() - t_all, 1),
        "note": ("CPU rows include per-run re-jit in both curve arms "
                 "symmetrically (one program per shape class each); "
                 "not measured on the chip"),
    }
    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(doc, indent=2) + "\n")
    print(json.dumps({k: doc[k] for k in
                      ("week", "scaling_curve", "sublinearity")},
                     default=str))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
