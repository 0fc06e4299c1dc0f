"""Model-bank decision harness (r12): sequential loop vs banked program.

The measured table behind ISSUE 7's acceptance bar and the
`model_bank._BANK_GATHER_MIN_EVENTS` form gate. Arms, all over the SAME
mixed-tenant request stream (onix/serving/load_harness.py):

  sequential — the pre-bank serving shape: one `top_suspicious`
               dispatch per request against that tenant's own
               device-resident tables (N requests = N dispatches);
  banked     — the device-resident bank, one batched dispatch per
               request batch, measured under BOTH kernel forms (vmap
               lane-per-request / flat tenant-gather).

Timing is interleaved best-of-REPS (this host's wall clock swings
with multi-minute load waves, so alternating arms gives both the same
weather), winners are asserted BIT-IDENTICAL
between every banked form and the sequential oracle, and dispatch
counts record the N → 1 collapse. A second section replays a windowed
(cacheable) stream through a capacity-CAPPED bank for the serving
numbers — p50/p99 latency, cache hit rate, residency churn — plus the
LRU proof (capped winners identical to an uncapped run). A bank-size
ladder reruns the form pair at several tenant counts to seed the
crossover tables (not measured on the chip).

Runs on whatever JAX_PLATFORMS gives it and prints the device; the
shard ladder uses the devices it finds and SAYS which mesh sizes it
dropped (on a CPU host export
XLA_FLAGS=--xla_force_host_platform_device_count=8 for dp=2/4).

Run on this host:  python scripts/exp_model_bank.py --out docs/BANK_r12_cpu.json
Tiny tier-1 smoke (tests/test_model_bank_smoke.py):
  python scripts/exp_model_bank.py --tenants 4 --requests 12 --events 256 \
      --docs 128 --vocab 96 --capacity 2 --batch 6 --ladder ""
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description="model bank: sequential per-tenant loop vs one "
                    "batched program")
    ap.add_argument("--tenants", type=int, default=64)
    ap.add_argument("--docs", type=int, default=2048)
    ap.add_argument("--vocab", type=int, default=1024)
    ap.add_argument("--topics", type=int, default=20)
    ap.add_argument("--requests", type=int, default=192)
    ap.add_argument("--events", type=int, default=4096,
                    help="events per request")
    ap.add_argument("--windows", type=int, default=4,
                    help="windows per tenant in the CACHED replay "
                         "section (the timing arms run uncached)")
    ap.add_argument("--zipf", type=float, default=1.2)
    ap.add_argument("--batch", type=int, default=64,
                    help="requests per banked dispatch")
    ap.add_argument("--capacity", type=int, default=0,
                    help="residency cap for the LRU section "
                         "(0 = tenants//4)")
    ap.add_argument("--max-results", type=int, default=100)
    ap.add_argument("--tol", type=float, default=1.0)
    ap.add_argument("--reps", type=int, default=2,
                    help="interleaved best-of repetitions per arm")
    ap.add_argument("--ladder", default="8,64",
                    help="comma list of bank sizes for the form-"
                         "crossover ladder ('' skips)")
    ap.add_argument("--overload-cell", action="store_true",
                    help="run the r16 overload SLO cell (shed + bounded "
                         "p99 proof, docs/ROBUSTNESS.md 'serving "
                         "resilience') and embed its artifact")
    ap.add_argument("--shard-cell", default="1,2,4",
                    help="comma list of mesh sizes for the r20 shard "
                         "ladder — single vs dp virtual devices, parity "
                         "asserted ('' skips)")
    ap.add_argument("--replicas", type=int, default=2,
                    help="replica count for the r20 multi-replica "
                         "replay (<=1 skips)")
    ap.add_argument("--prefetch-depth", type=int, default=4,
                    help="host-tier prefetcher budget for the r20 "
                         "tier replay (0 skips the tier section)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import numpy as np

    from onix.serving import load_harness as lh
    from onix.serving.model_bank import select_bank_form
    from onix.utils.obs import (bank_score_bytes_per_event, counters,
                                device_peak_bytes_per_s, print_device,
                                roofline)

    spec = lh.HarnessSpec(
        n_tenants=args.tenants, n_docs=args.docs, n_vocab=args.vocab,
        n_topics=args.topics, n_requests=args.requests,
        events_per_request=args.events, n_windows=0, zipf_a=args.zipf,
        batch_requests=args.batch, capacity=0, tol=args.tol,
        max_results=args.max_results, seed=0)
    models = lh.make_tenants(spec)
    stream = lh.make_stream(spec)       # uncached: pure scoring arms

    t_start = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    doc: dict = {
        "host_utc": t_start,
        "backend": None,
        "spec": dataclasses.asdict(spec),
    }

    import jax
    doc["backend"] = jax.default_backend()
    doc["device"] = print_device()

    # -- timing arms: interleaved best-of --------------------------------
    # Services persist across reps (steady-state serving: models resident,
    # programs compiled); rep 0 of each arm is the warm-up and is ALSO
    # timed — best-of keeps the warm number.
    forms = ("vmap", "gather")
    services = {f: lh.build_service(spec, models, form=f) for f in forms}
    seq_res = None
    bank_runs: dict[str, dict] = {}
    best = {"sequential": float("inf"), **{f: float("inf") for f in forms}}
    for rep in range(max(args.reps, 1) + 1):    # +1: rep 0 warms
        sq = lh.sequential_control(models, stream, tol=spec.tol,
                                   max_results=spec.max_results)
        seq_res = sq if seq_res is None else seq_res
        if rep > 0:
            best["sequential"] = min(best["sequential"], sq["wall_s"])
        for f in forms:
            run = lh.replay(services[f], stream, tol=spec.tol,
                            max_results=spec.max_results)
            bank_runs[f] = run
            if rep > 0:
                best[f] = min(best[f], run["wall_s"])

    n_events = seq_res["n_events"]
    rates = {arm: round(n_events / w, 1) for arm, w in best.items()}
    for f in forms:
        lh.assert_parity(bank_runs[f], seq_res)
    best_form = min(forms, key=lambda f: best[f])
    doc["arms"] = {
        "sequential": {
            "events_per_sec": rates["sequential"],
            "wall_s_best": round(best["sequential"], 4),
            "dispatches": seq_res["dispatches"],
        },
        **{f"banked_{f}": {
            "events_per_sec": rates[f],
            "wall_s_best": round(best[f], 4),
            "dispatches": bank_runs[f]["dispatches"],
        } for f in forms},
    }
    doc["n_events_per_pass"] = n_events
    doc["n_requests"] = len(stream)
    doc["parity_bit_identical"] = True
    doc["best_form"] = best_form
    doc["auto_form_at_this_shape"] = select_bank_form(
        "auto", len(stream), args.events)
    doc["speedup_banked_vs_sequential"] = round(
        rates[best_form] / rates["sequential"], 3)
    doc["dispatch_collapse"] = (
        f"{seq_res['dispatches']} -> {bank_runs[best_form]['dispatches']} "
        f"per {len(stream)}-request pass")
    peak, peak_src = device_peak_bytes_per_s()
    rl = roofline(n_events, best[best_form],
                  bank_score_bytes_per_event(spec.n_topics), peak)
    rl["peak_source"] = peak_src
    doc["banked_roofline_modeled"] = rl

    # -- serving section: windowed cached replay under a residency cap ---
    cap = args.capacity or max(args.tenants // 4, 1)
    serve_spec = dataclasses.replace(spec, n_windows=max(args.windows, 1),
                                     capacity=min(cap, args.tenants))
    doc["serving_replay"] = lh.run_harness(serve_spec, form=best_form,
                                           with_sequential=True,
                                           with_uncapped_check=(
                                               serve_spec.capacity
                                               < args.tenants))

    # -- bank-size ladder: the form-crossover table's raw rows ------------
    ladder = [int(x) for x in args.ladder.split(",") if x.strip()]
    rows = []
    for b in ladder:
        lspec = dataclasses.replace(
            spec, n_tenants=b,
            n_requests=max(args.requests // max(len(ladder), 1), 2 * b,
                           8))
        lmodels = lh.make_tenants(lspec)
        lstream = lh.make_stream(lspec)
        row = {"bank_size": b, "n_requests": lspec.n_requests}
        lserv = {f: lh.build_service(lspec, lmodels, form=f)
                 for f in forms}
        lbest = {f: float("inf") for f in forms}
        for rep in range(max(args.reps, 1) + 1):
            for f in forms:
                r = lh.replay(lserv[f], lstream, tol=lspec.tol,
                              max_results=lspec.max_results)
                if rep > 0:
                    lbest[f] = min(lbest[f], r["wall_s"])
                row[f"n_events"] = r["n_events"]
        for f in forms:
            row[f"events_per_sec_{f}"] = round(
                row["n_events"] / lbest[f], 1)
        row["gather_over_vmap"] = round(lbest["vmap"] / lbest["gather"], 3)
        rows.append(row)
    if rows:
        doc["bank_size_ladder"] = rows

    # -- r20 shard ladder: single device vs dp=2/4 virtual meshes ---------
    # Same stream, same kernels; the ONLY change is tenant-hash
    # placement across the mesh and the per-device wave split. Parity
    # is asserted bit-identical across every mesh size (against dp=1,
    # itself parity-checked against the sequential oracle above), and
    # the compiled HLO collective-free check runs inside the bank on
    # every sharded shape.
    shard_sizes = [int(x) for x in args.shard_cell.split(",")
                   if x.strip()]
    if shard_sizes:
        n_dev = len(jax.devices())
        usable = [d for d in shard_sizes if d <= n_dev]
        dropped = [d for d in shard_sizes if d > n_dev]
        if dropped:
            # No silent caps: a 2-device TPU host drops the dp=4 rung
            # and the artifact says so.
            print(f"shard ladder: dropping mesh sizes {dropped} "
                  f"(host exposes {n_dev} devices)", file=sys.stderr)
        sserv = {}
        for dp in usable:
            sspec = dataclasses.replace(
                spec, devices=dp,
                shard_form="sharded" if dp > 1 else "single")
            sserv[dp] = lh.build_service(sspec, models, form=best_form)
        sbest = {dp: float("inf") for dp in usable}
        sruns: dict[int, dict] = {}
        for rep in range(max(args.reps, 1) + 1):
            for dp in usable:                   # interleaved best-of
                # Wave counters are process-global: the per-pass delta
                # must bracket THIS replay (the rungs share devices).
                wb = dict(counters.snapshot("bank"))
                r = lh.replay(sserv[dp], stream, tol=spec.tol,
                              max_results=spec.max_results)
                r["wave_dispatches_pass"] = {
                    k: v - wb.get(k, 0)
                    for k, v in counters.snapshot("bank").items()
                    if k.startswith("bank.wave.d")
                    and v - wb.get(k, 0)}
                sruns[dp] = r
                if rep > 0:
                    sbest[dp] = min(sbest[dp], r["wall_s"])
        ref = sruns[usable[0]]
        rows = []
        for dp in usable:
            r = sruns[dp]
            for i, (a, b) in enumerate(zip(ref["results"],
                                           r["results"])):
                if not (np.array_equal(a.topk.scores, b.topk.scores)
                        and np.array_equal(a.topk.indices,
                                           b.topk.indices)):
                    raise AssertionError(
                        f"dp={dp} request {i}: sharded winners "
                        "diverged from the single-device bank")
            bank = sserv[dp].bank
            rows.append({
                "devices": dp,
                "shard_form": bank.shard_form_resolved(),
                "events_per_sec": round(n_events / sbest[dp], 1),
                "wall_s_best": round(sbest[dp], 4),
                "dispatches_per_pass": r["dispatches"],
                "wave_dispatches": r["wave_dispatches_pass"],
                "fetch_wait_us_last_pass": r["fetch_wait_us"],
                "collective_free_shapes_checked":
                    len(bank.collective_checked),
            })
        doc["shard_ladder"] = {
            "rows": rows,
            "parity_bit_identical_across_meshes": True,
            "collective_free_asserted": any(
                row["devices"] > 1
                and row["collective_free_shapes_checked"] > 0
                for row in rows),
            "dropped_mesh_sizes": dropped,
            "note": ("on virtual CPU devices, which share the host's "
                     "cores, wall-clock ranks placement overhead only; "
                     "not measured on the chip"),
        }

    # -- r20 residency-tier replay: disk -> host RAM -> HBM ---------------
    # Loader-backed tenants under a tight device cap and a bounded host
    # registry, cold pass then warm pass: the per-tier p50/p99 and the
    # Zipf prefetch hit-rate the tier exists to buy.
    if args.prefetch_depth > 0:
        tier_spec = dataclasses.replace(
            spec, n_windows=0, capacity=max(2, args.tenants // 8),
            devices=min(2, len(jax.devices())),
            shard_form="sharded" if len(jax.devices()) > 1 else "auto",
            host_capacity=max(4, args.tenants // 2),
            prefetch_depth=args.prefetch_depth)
        tserv = lh.build_service(tier_spec, models, form=best_form)
        strip = lambda r: {k: v for k, v in r.items()  # noqa: E731
                           if k not in ("results", "raw_latencies")}
        cold = lh.replay(tserv, stream, tol=spec.tol,
                         max_results=spec.max_results)
        warm = lh.replay(tserv, stream, tol=spec.tol,
                         max_results=spec.max_results)
        doc["tier_replay"] = {
            "capacity": tier_spec.capacity,
            "host_capacity": tier_spec.host_capacity,
            "prefetch_depth": tier_spec.prefetch_depth,
            "devices": tier_spec.devices,
            "cold": strip(cold), "warm": strip(warm),
            "tier_stats": tserv.bank.tier_stats(),
        }

    # -- r20 multi-replica replay: N services behind one front -----------
    if args.replicas > 1:
        rep_spec = dataclasses.replace(spec, replicas=args.replicas)
        rserv = lh.build_service(rep_spec, models, form=best_form)
        rrun = lh.replay(rserv, stream, tol=spec.tol,
                         max_results=spec.max_results)
        lh.assert_parity(rrun, seq_res)     # routing changes nothing
        doc["replica_replay"] = {
            "replicas": args.replicas,
            "parity_bit_identical": True,
            "events_per_sec": rrun["events_per_sec"],
            "latency_p50_ms": rrun["latency_p50_ms"],
            "latency_p99_ms": rrun["latency_p99_ms"],
            "admission": rrun["admission"],
        }

    # -- overload SLO cell: shed + bounded-p99 proof (r16) ----------------
    if args.overload_cell:
        cell_spec = dataclasses.replace(
            spec, n_windows=max(args.windows, 1),
            n_requests=max(32, args.requests // 4),
            batch_requests=min(args.batch, 8))
        doc["overload_cell"] = lh.overload_cell(cell_spec, form=best_form)

    doc["bank_counters"] = counters.snapshot("bank")
    doc["serve_counters"] = counters.snapshot("serve")
    out = json.dumps(doc, indent=2)
    print(out)
    if args.out:
        pathlib.Path(args.out).write_text(out + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
