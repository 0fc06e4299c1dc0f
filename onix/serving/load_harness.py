"""Mixed-tenant load harness for the model bank (r12) + the r16
serving-resilience SLO cells.

Replays a skewed (Zipf) tenant traffic stream through `BankService`
and reports the serving numbers the bank is judged on: aggregate
events/s, per-OUTCOME latency histograms (served / degraded / shed /
deadline-expired / refused, p50/p99 each — the r16 SLO accounting),
winner-cache hit rate, and residency churn (admits/evicts) — plus the
proofs:

* **parity** — every scored request's bottom-M winners bit-identical
  to the single-tenant `top_suspicious` path run per request;
* **residency identity** — a capacity-capped replay produces winners
  identical to an uncapped replay of the same stream (eviction happens
  only at request-batch boundaries, so it can never change a score);
* **overload cell** (`overload_cell`) — at ≥2× sustainable offered
  load the service SHEDS (503-semantics `Overloaded`) while the
  served-request p99 stays within `p99_bound_factor`× the uncontended
  p99, and shed requests provably leave bank residency and the winner
  cache untouched (docs/ROBUSTNESS.md "serving resilience").

`scripts/exp_model_bank.py` is the CLI wrapper that adds interleaved
sequential-vs-banked timing arms and writes the measured artifact
(docs/BANK_r12_cpu.json); tests/test_model_bank_smoke.py and
tests/test_serve_resilience.py run this harness at tiny shapes in
tier-1 so it cannot rot between chip runs (the
test_fit_gap_smoke discipline).
"""

from __future__ import annotations

import dataclasses
import threading
import time

import numpy as np

from onix.serving.model_bank import (BankRefusal, BankService, ModelBank,
                                     ScoreRequest, TenantModel)
from onix.utils import telemetry
from onix.utils.obs import counters
from onix.utils.resilience import DeadlineExceeded, Overloaded


@dataclasses.dataclass
class HarnessSpec:
    """Shape of one harness run. Defaults are the acceptance shape
    (64 resident tenants); the tier-1 smoke shrinks everything."""
    n_tenants: int = 64
    n_docs: int = 2048          # per-tenant document count (D)
    n_vocab: int = 1024         # per-tenant product-vocabulary size (V)
    n_topics: int = 20
    n_requests: int = 256       # total requests in the replay stream
    events_per_request: int = 2048
    n_windows: int = 4          # windows per tenant; repeats -> cache hits
    #                             (0 = uncached stream: every request a
    #                             fresh window=None event set — the pure
    #                             scoring-throughput arm)
    zipf_a: float = 1.2         # tenant popularity skew
    batch_requests: int = 64    # service batching (requests per score())
    capacity: int = 0           # resident cap; 0 = all tenants resident
    tol: float = 1.0
    max_results: int = 100
    seed: int = 0
    # r16 admission control (serving.max_queue_depth /
    # serving.request_deadline_ms equivalents): 0 = disabled, the
    # pre-r16 shape. The overload cell sets max_queue_depth=1 so the
    # served-latency bound (depth+1)·service-time is provable.
    max_queue_depth: int = 0
    request_deadline_ms: float = 0.0
    # r20 scale-out: `devices` > 1 builds the bank over that many mesh
    # devices (jax.devices()[:n] — virtual on CPU) with `shard_form`
    # routed through select_shard_form; `replicas` > 1 stands up N
    # services behind a ReplicaFront; `prefetch_depth`/`host_capacity`
    # exercise the host-RAM residency tier.
    devices: int = 0
    shard_form: str = "auto"
    replicas: int = 1
    prefetch_depth: int = 0
    host_capacity: int = 0


def make_tenants(spec: HarnessSpec) -> dict[str, TenantModel]:
    """Synthetic per-tenant (θ, φ) tables — Dirichlet rows, one shared
    shape class (the common case: tenants of one datatype × day ladder
    into the same pow2 bucket)."""
    rng = np.random.default_rng(spec.seed)
    out = {}
    for t in range(spec.n_tenants):
        theta = rng.dirichlet(np.full(spec.n_topics, 0.5),
                              size=spec.n_docs).astype(np.float32)
        phi = rng.dirichlet(np.full(spec.n_topics, 0.5),
                            size=spec.n_vocab).astype(np.float32)
        out[f"t{t:04d}"] = TenantModel(theta, phi)
    return out


def make_stream(spec: HarnessSpec) -> list[ScoreRequest]:
    """Zipf-skewed request stream. Each (tenant, window) pair's event
    set is generated ONCE and reused on every repeat — the winner
    cache's immutable-window contract, and what real replay traffic
    (dashboards re-opening a scored day) looks like."""
    rng = np.random.default_rng(spec.seed + 1)
    ranks = (rng.zipf(spec.zipf_a, spec.n_requests) - 1) % spec.n_tenants
    # Scatter ranks so hot tenants aren't id-contiguous.
    tenant_ids = (ranks * 2654435761) % spec.n_tenants
    events: dict[tuple[int, int], tuple[np.ndarray, np.ndarray]] = {}
    stream = []

    def draw(n):
        return (rng.integers(0, spec.n_docs, n).astype(np.int32),
                rng.integers(0, spec.n_vocab, n).astype(np.int32))

    for t in tenant_ids:
        if spec.n_windows:
            w = int(rng.integers(spec.n_windows))
            key = (int(t), w)
            if key not in events:
                events[key] = draw(spec.events_per_request)
            d, wd = events[key]
            window = f"w{w}"
        else:
            d, wd = draw(spec.events_per_request)
            window = None
        stream.append(ScoreRequest(tenant=f"t{int(t):04d}", doc_ids=d,
                                   word_ids=wd, window=window))
    return stream


def build_service(spec: HarnessSpec, models: dict[str, TenantModel],
                  form: str = "auto", serve_form: str = "auto"):
    """One service (the pre-r20 shape), or the r20 scale-out fabric
    when the spec asks for it: a mesh-sharded bank (spec.devices > 1),
    the host-RAM tier (host_capacity / prefetch_depth — tenants arrive
    loader-backed so the tier actually churns), and/or N replicas
    behind a ReplicaFront (spec.replicas > 1)."""
    cap = spec.capacity or spec.n_tenants
    devices = None
    if spec.devices:
        import jax
        if spec.devices > len(jax.devices()):
            raise ValueError(
                f"spec.devices={spec.devices} > available "
                f"{len(jax.devices())} (set "
                "xla_force_host_platform_device_count)")
        devices = jax.devices()[:spec.devices]
    tiered = bool(spec.host_capacity or spec.prefetch_depth)

    def bulk_loader(names: list[str]) -> dict[str, TenantModel]:
        return {n: models[n] for n in names if n in models}

    def _one():
        bank = ModelBank(
            capacity=cap, form=form, serve_form=serve_form,
            devices=devices, shard_form=spec.shard_form,
            prefetch_depth=spec.prefetch_depth,
            host_capacity=spec.host_capacity,
            loader=(lambda t: models.get(t)) if tiered else None,
            bulk_loader=bulk_loader if tiered else None)
        if not tiered:
            # Pre-r20 shape: everything explicitly add()ed (pinned in
            # the host registry). The tiered path leaves tenants to
            # the loader so promote/demote across host RAM is real.
            for name, m in models.items():
                bank.add(name, m.theta, m.phi_wk)
        return BankService(bank,
                           max_batch_requests=spec.batch_requests,
                           max_queue_depth=spec.max_queue_depth,
                           request_deadline_s=(
                               spec.request_deadline_ms / 1e3))

    if spec.replicas > 1:
        from onix.serving.replicas import ReplicaFront
        return ReplicaFront([_one() for _ in range(spec.replicas)])
    return _one()


def _pctl(latencies: list[float]) -> dict:
    """Quantiles via the r18 log-bucketed `telemetry.Histogram` — the
    same machinery `/metrics` exposes, replacing the pre-r18 raw
    index-into-sorted-list math whose p99 on small n was whatever
    single sample the truncation landed on. The histogram's answer is
    exact-to-the-bucket with a declared relative error bound
    (`q_rel_error`), and parity against numpy nearest-rank percentile
    is asserted in tests/test_telemetry.py."""
    h = telemetry.Histogram()
    for v in latencies:
        h.observe(v)
    return {"n": len(latencies),
            "p50_ms": round(h.quantile(0.50) * 1e3, 3),
            "p99_ms": round(h.quantile(0.99) * 1e3, 3),
            "q_rel_error": round(h.rel_error, 4)}


def _slo(outcomes: dict[str, list[float]]) -> dict:
    """Per-outcome latency histograms — the r16 SLO accounting. Every
    request batch lands in exactly one outcome class: served (scored,
    current-epoch winners), degraded (served with the explicit
    overload/fallback stamp), shed (admission refusal — 503), deadline
    (budget expired in queue — 503), refused (BankRefusal — 404).
    Latency is recorded for ALL classes: a shed request's latency IS
    the shed path's cost, and it staying microseconds-flat under
    overload is the admission-control claim."""
    return {k: _pctl(v) for k, v in outcomes.items() if v}


def replay(service: BankService, stream: list[ScoreRequest], *,
           tol: float, max_results: int, shed_retries: int = 0,
           shed_backoff_s: float = 0.0, keep_raw: bool = False) -> dict:
    """Run the stream through the service in request batches via the
    admission-controlled submit() path; returns results + the serving
    numbers. A shed/deadline-refused batch is retried up to
    `shed_retries` times (honoring `shed_backoff_s` between tries —
    the harness's stand-in for a client honoring Retry-After), then
    recorded under its outcome with None results — parity asserts skip
    those slots. Each batch lands in exactly ONE outcome class (its
    FINAL attempt's — so `slo.*.n` sums to the batch count and
    reconciles with the admission deltas); retried attempts are
    tallied separately under `shed_attempts_retried`."""
    base = {k: counters.get(f"bank.{k}")
            for k in ("admit", "evict", "dispatch", "cache_hit",
                      "cache_miss", "h2d_bytes", "h2d_transfers",
                      "tier_hbm_hit", "tier_host_hit", "tier_disk_load",
                      "prefetch_promoted", "prefetch_hit",
                      "prefetch_waste", "prefetch_failed",
                      "fetch_wait_us")}
    # Serve-tier counters are process-global and cumulative; a replay's
    # artifact must report ITS OWN deltas (the bank-counter discipline
    # above) — warm passes and earlier arms in the same process would
    # otherwise inflate every later replay's admission numbers.
    serve_keys = ("shed", "shed_requests", "deadline_expired",
                  "degraded", "form_fallback", "served")
    serve_base = {k: counters.get(f"serve.{k}") for k in serve_keys}
    results: list = []
    outcomes: dict[str, list[float]] = {
        "served": [], "degraded": [], "shed": [], "deadline": [],
        "refused": []}
    # r20 per-tier latency: each SCORED batch classifies by the worst
    # residency tier it touched (disk > host RAM > HBM, read off the
    # per-batch bank.tier_* counter deltas) — "a request that had to
    # go to disk cost THIS much" is the number the tier exists to
    # improve, and the artifact's per-tier p50/p99 comes from here.
    _tier_keys = ("tier_disk_load", "tier_host_hit", "tier_hbm_hit")
    tier_lats: dict[str, list[float]] = {
        "hbm": [], "host": [], "disk": []}
    n_events = 0
    retried = 0
    t0 = time.perf_counter()
    for lo in range(0, len(stream), service.max_batch_requests):
        batch = stream[lo:lo + service.max_batch_requests]
        tier_base = {k: counters.get(f"bank.{k}") for k in _tier_keys}
        out, kind, lat = None, "shed", 0.0
        for attempt in range(shed_retries + 1):
            tb = time.perf_counter()
            try:
                out = service.submit(batch, tol=tol,
                                     max_results=max_results)
                kind = ("degraded" if any(r.degraded for r in out)
                        else "served")
            except Overloaded:
                kind = "shed"
            except DeadlineExceeded:
                kind = "deadline"
            except BankRefusal:
                kind = "refused"
            lat = time.perf_counter() - tb
            if out is not None or attempt == shed_retries \
                    or kind == "refused":
                break
            retried += 1
            if shed_backoff_s:
                time.sleep(shed_backoff_s)
        outcomes[kind].append(lat)        # final outcome only
        results.extend(out if out is not None else [None] * len(batch))
        if out is not None:
            n_events += sum(int(r.doc_ids.size) for r in batch)
            td = {k: counters.get(f"bank.{k}") - tier_base[k]
                  for k in _tier_keys}
            tier = ("disk" if td["tier_disk_load"] else
                    "host" if td["tier_host_hit"] else "hbm")
            tier_lats[tier].append(lat)
    wall = time.perf_counter() - t0
    delta = {k: counters.get(f"bank.{k}") - v for k, v in base.items()}
    cacheable = delta["cache_hit"] + delta["cache_miss"]
    scored = _pctl(outcomes["served"] + outcomes["degraded"] or [0.0])
    admission = {k: counters.get(f"serve.{k}") - serve_base[k]
                 for k in serve_keys}
    admission["shed_attempts_retried"] = retried
    admission["max_queue_depth"] = service.max_queue_depth
    admission["queue_depth_peak"] = service.peak_depth
    return {
        "results": results,
        "n_requests": len(stream),
        "n_events": n_events,
        "wall_s": round(wall, 4),
        "events_per_sec": round(n_events / max(wall, 1e-9), 1),
        "latency_p50_ms": scored["p50_ms"],
        "latency_p99_ms": scored["p99_ms"],
        "slo": _slo(outcomes),
        # Raw per-batch latencies, on request only (the histogram-vs-
        # numpy parity test; artifacts carry the histograms instead).
        **({"raw_latencies": {k: list(v) for k, v in outcomes.items()}}
           if keep_raw else {}),
        "admission": admission,
        "dispatches": delta["dispatch"],
        "cache_hit_rate": (round(delta["cache_hit"] / cacheable, 4)
                          if cacheable else None),
        "residency_churn": {"admits": delta["admit"],
                            "evicts": delta["evict"]},
        "h2d": {"bytes": delta["h2d_bytes"],
                "transfers": delta["h2d_transfers"]},
        # r20: per-tier latency + tier/prefetch accounting (deltas, the
        # same discipline as the bank counters above). `wave_dispatches`
        # is per-home-device (bank.wave.d<i>) — process-cumulative, so
        # it appears only when the sharded path ran at all.
        "tier_latency": {t: _pctl(v) for t, v in tier_lats.items()
                         if v},
        "tiers": {"hbm_hits": delta["tier_hbm_hit"],
                  "host_hits": delta["tier_host_hit"],
                  "disk_loads": delta["tier_disk_load"]},
        "prefetch": {
            "promoted": delta["prefetch_promoted"],
            "hits": delta["prefetch_hit"],
            "waste": delta["prefetch_waste"],
            "failed": delta["prefetch_failed"],
            "hit_rate": (round(delta["prefetch_hit"]
                               / delta["prefetch_promoted"], 4)
                         if delta["prefetch_promoted"] else None)},
        "fetch_wait_us": delta["fetch_wait_us"],
        "wave_dispatches": {
            k.split("bank.wave.", 1)[1]: v
            for k, v in counters.snapshot("bank").items()
            if k.startswith("bank.wave.d")},
    }


def sequential_control(models: dict[str, TenantModel],
                       stream: list[ScoreRequest], *, tol: float,
                       max_results: int) -> dict:
    """The pre-bank serving shape: one `top_suspicious` dispatch per
    request against that tenant's own tables (device-resident up
    front, so the comparison isolates the dispatch collapse — the
    sequential loop's per-tenant H2D staging is charged separately in
    the artifact's h2d counters). Winners are the parity oracle."""
    import jax.numpy as jnp

    from onix.models.scoring import top_suspicious

    dev = {name: (jnp.asarray(m.theta), jnp.asarray(m.phi_wk))
           for name, m in models.items()}
    results = []
    n_events = 0
    t0 = time.perf_counter()
    for req in stream:
        th, ph = dev[req.tenant]
        n = int(req.doc_ids.size)
        res = top_suspicious(th, ph, jnp.asarray(req.doc_ids),
                             jnp.asarray(req.word_ids),
                             jnp.ones(n, jnp.float32), tol=tol,
                             max_results=max_results)
        results.append((np.asarray(res.scores), np.asarray(res.indices)))
        n_events += n
    wall = time.perf_counter() - t0
    return {
        "results": results,
        "n_events": n_events,
        "wall_s": round(wall, 4),
        "events_per_sec": round(n_events / max(wall, 1e-9), 1),
        "dispatches": len(stream),
    }


def assert_parity(banked, sequential) -> None:
    """Bit-identical winners between the banked replay and the
    sequential oracle — scores AND indices, every request (cached
    results included: the cache stores exactly what the bank scored)."""
    for i, (b, (s_ref, i_ref)) in enumerate(
            zip(banked["results"], sequential["results"])):
        if b is None:
            raise AssertionError(
                f"request {i}: not served (shed/refused) — parity is "
                "undefined; run parity replays without admission limits")
        if not (np.array_equal(b.topk.scores, s_ref)
                and np.array_equal(b.topk.indices, i_ref)):
            raise AssertionError(
                f"request {i}: banked winners diverged from the "
                f"single-tenant path")


def assert_residency_identity(capped, uncapped) -> None:
    """A capacity-capped replay's winners are identical to the uncapped
    run's — the LRU proof (eviction on request boundaries only)."""
    for i, (a, b) in enumerate(zip(capped["results"],
                                   uncapped["results"])):
        if not (np.array_equal(a.topk.scores, b.topk.scores)
                and np.array_equal(a.topk.indices, b.topk.indices)):
            raise AssertionError(
                f"request {i}: capped-bank winners diverged from the "
                f"uncapped run")


def overload_cell(spec: HarnessSpec, *, n_producers: int = 4,
                  duration_s: float = 0.0,
                  p99_bound_factor: float = 2.0,
                  min_offered_factor: float = 2.0,
                  n_probes: int = 8, form: str = "auto") -> dict:
    """The r16 overload proof (ISSUE 12 acceptance; docs/ROBUSTNESS.md
    "serving resilience"): drive the service at >= `min_offered_factor`
    × its sustainable throughput and prove it DEGRADES PREDICTABLY —
    requests shed (503-semantics `Overloaded`) while the served-request
    p99 stays within `p99_bound_factor`× the uncontended p99 — instead
    of collapsing into an unbounded queue.

    Three phases, all asserted in-cell:

    1. **uncontended** — closed-loop passes over the stream on an
       unbounded service: pass 0 absorbs compiles + admissions, the
       later passes pool their per-batch latencies into the
       uncontended p50/p99 denominator (pooled across passes — a
       single pass's p99 is one scheduler hiccup wide) and the
       sustainable batches/s rate.
    2. **overload** — `n_producers` TIME-BOXED producers over a fresh
       pre-warmed service with `max_queue_depth=1`: exactly one batch
       in flight, zero queued, so a served request's latency is pure
       service time — no queue wait can inflate the tail, which is
       what makes the p99 bound structural rather than lucky.
       Everything that arrives while a batch is in flight SHEDS.
       Producers nap one median batch wall after a shed (the harness
       stand-in for honoring Retry-After) so offered load is a
       measured arrival rate, not a spin loop — each napper still
       arrives ~once per service time, so n producers offer ~n× the
       sustainable rate. Asserts: shed > 0, offered factor >=
       `min_offered_factor`, served p99 <= `p99_bound_factor` × the
       CALIBRATED denominator: max(uncontended p99, served p50 ×
       uncontended p99/p50). The second arm keys the bound to the
       host conditions measured DURING the overload run — a saturated
       host shifts the whole served distribution and the bound with
       it, while an unbounded queue (tail inflating relative to the
       served median) still fails.
    3. **shed probe** — with the scoring lock held (an in-flight batch)
       and the queue slot taken by a real blocked submit, `n_probes`
       windowed requests are fired and must ALL shed; bank residency
       (per-shard LRU order), the winner-cache keys, and the
       admit/evict counters are asserted byte-identical across the
       probes — shed requests provably mutate NOTHING.

    The overload stream is the spec's stream with windows stripped
    (window=None) so every batch scores — uniform batch cost is what
    makes the 2× bound tight rather than cache-hit noise."""
    models = make_tenants(spec)
    stream = make_stream(spec)
    nocache = [dataclasses.replace(r, window=None) for r in stream]
    n_batches = max(1, -(-len(stream) // spec.batch_requests))

    # -- phase 1: sustainable rate + uncontended p99 ---------------------
    base_spec = dataclasses.replace(spec, max_queue_depth=0,
                                    request_deadline_ms=0.0)
    unc_svc = build_service(base_spec, models, form=form)
    nocache_batches = [nocache[lo:lo + spec.batch_requests]
                       for lo in range(0, len(nocache),
                                       spec.batch_requests)]
    lat_by_pass: list[list[float]] = []
    for _ in range(3):
        lats = []
        for batch in nocache_batches:
            tb = time.perf_counter()
            unc_svc.submit(batch, tol=spec.tol,
                           max_results=spec.max_results)
            lats.append(time.perf_counter() - tb)
        lat_by_pass.append(lats)
    pooled = np.asarray([v for lats in lat_by_pass[1:] for v in lats])
    unc_p99_s = float(np.percentile(pooled, 99))
    unc_p50_s = float(np.percentile(pooled, 50))
    unc_wall_s = float(sum(lat_by_pass[-1]))
    sustainable_batches_per_s = n_batches / unc_wall_s

    # -- phase 2: overload ----------------------------------------------
    over_spec = dataclasses.replace(spec, max_queue_depth=1,
                                    request_deadline_ms=0.0)
    svc = build_service(over_spec, models, form=form)
    # Warm pass (single-threaded, never sheds at depth 1): residency +
    # compiles settle so overload batch walls are steady-state.
    replay(svc, nocache, tol=spec.tol, max_results=spec.max_results)
    duration_s = duration_s or max(0.5, 3.0 * unc_wall_s)
    # A full-batch nap after a shed: the napper wakes ~once per service
    # time (offered still n_producers x sustainable) without peppering
    # the scorer's cores with sub-ms wakeups — scheduler noise on a
    # small host would otherwise inflate the served tail with producer
    # wakeup costs the service never caused.
    shed_nap_s = max(unc_p50_s, 1e-4)
    out_lock = threading.Lock()
    lat_served: list[float] = []
    tally = {"served": 0, "degraded": 0, "shed": 0, "attempts": 0}
    batches = [stream[lo:lo + spec.batch_requests]
               for lo in range(0, len(stream), spec.batch_requests)]
    stop_t = [0.0]     # set after the threads are built, read by all

    # Pre-stripped batches: producers must not burn GIL time building
    # request objects inside the timed loop — that would inflate the
    # SERVED latencies with producer-side work the service never sees.
    stripped = [[dataclasses.replace(r, window=None) for r in b]
                for b in batches]

    def producer(pid: int) -> None:
        i = 0
        while time.perf_counter() < stop_t[0]:
            batch = stripped[(pid + i) % len(stripped)]
            i += 1
            tb = time.perf_counter()
            try:
                res = svc.submit(batch, tol=spec.tol,
                                 max_results=spec.max_results)
                lat = time.perf_counter() - tb
                with out_lock:
                    tally["attempts"] += 1
                    lat_served.append(lat)
                    tally["degraded" if any(r.degraded for r in res)
                          else "served"] += 1
            except Overloaded:
                with out_lock:
                    tally["attempts"] += 1
                    tally["shed"] += 1
                time.sleep(shed_nap_s)

    threads = [threading.Thread(target=producer, args=(p,))
               for p in range(n_producers)]
    t0 = time.perf_counter()
    stop_t[0] = t0 + duration_s
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    over_wall = time.perf_counter() - t0
    offered_batches_per_s = tally["attempts"] / over_wall
    offered_factor = offered_batches_per_s / sustainable_batches_per_s
    lat_arr = np.asarray(lat_served)
    served_p99_s = float(np.percentile(lat_arr, 99)) \
        if lat_served else float("inf")
    served_p50_s = float(np.percentile(lat_arr, 50)) \
        if lat_served else float("inf")

    # In-run calibration of the p99 bound. The uncontended phase ran on
    # whatever host quiet happened to hold THEN; the overload phase adds
    # n_producers runnable threads, and on a saturated host (tier-1
    # suites sharing cores) every served batch — median included — pays
    # scheduler contention the uncontended denominator never saw. A
    # fixed `factor × unc_p99` bound then flakes on slowness the
    # SERVICE didn't cause. The served p50 measures that contention
    # in-run: scale the uncontended tail RATIO (p99/p50, the shape of a
    # healthy latency distribution) up to the served median and take
    # the looser of the two denominators. An unbounded queue still
    # fails — queue wait inflates the tail relative to the served
    # median, not uniformly — while uniform host slowness passes.
    unc_tail_ratio = unc_p99_s / max(unc_p50_s, 1e-9)
    calibrated_floor = served_p50_s * unc_tail_ratio
    p99_bound_s = p99_bound_factor * max(unc_p99_s, calibrated_floor)

    assert tally["shed"] > 0, (
        "overload cell shed nothing — offered load never exceeded the "
        "queue; raise n_producers or shrink the batch")
    assert tally["served"] + tally["degraded"] > 0, \
        "overload cell served nothing — the service wedged"
    assert offered_factor >= min_offered_factor, (
        f"offered load {offered_factor:.2f}x sustainable — below the "
        f"{min_offered_factor}x overload bar (producers too slow)")
    assert served_p99_s <= p99_bound_s, (
        f"served p99 {served_p99_s * 1e3:.1f}ms exceeded the calibrated "
        f"bound {p99_bound_s * 1e3:.1f}ms ({p99_bound_factor}x "
        f"max(uncontended p99 {unc_p99_s * 1e3:.1f}ms, served p50 "
        f"{served_p50_s * 1e3:.1f}ms x tail ratio "
        f"{unc_tail_ratio:.2f})) — admission failed to bound latency")

    # -- phase 3: shed probe (shed mutates NOTHING) ----------------------
    def residency_snapshot():
        return {k: list(sh.lru) for k, sh in svc.bank._shards.items()}

    before = {"cache": set(svc._cache), "lru": residency_snapshot(),
              "admit": counters.get("bank.admit"),
              "evict": counters.get("bank.evict"),
              "cache_epoch_evictions":
                  counters.get("bank.cache_epoch_evictions")}
    errs: list[BaseException] = []

    def blocked_submit():
        try:
            svc.submit(batches[0], tol=spec.tol,
                       max_results=spec.max_results)
        except BaseException as e:  # surfaced to the cell, never lost
            counters.inc("serve.harness_blocked_submit_error")
            errs.append(e)

    probes_shed = 0
    with svc.lock:      # an in-flight batch holds the scorer...
        blockers = [threading.Thread(target=blocked_submit)]
        for b in blockers:
            b.start()   # ...and the depth-1 slot fills with a real waiter
        deadline = time.perf_counter() + 10.0
        while svc.admission_stats()["queue_depth"] < 1:
            if time.perf_counter() > deadline:
                raise AssertionError("queue slot never filled")
            time.sleep(0.001)
        for p in range(n_probes):
            probe = ScoreRequest(tenant=batches[0][0].tenant,
                                 doc_ids=batches[0][0].doc_ids,
                                 word_ids=batches[0][0].word_ids,
                                 window=f"probe{p}")
            try:
                svc.submit([probe], tol=spec.tol,
                           max_results=spec.max_results)
            except Overloaded as e:
                probes_shed += 1
                assert e.retry_after_s > 0
        # Asserted while the lock is still held — the blocked waiters
        # have not scored, so any mutation here came from a probe.
        assert probes_shed == n_probes, \
            f"{n_probes - probes_shed} probes were admitted past a " \
            "full queue"
        assert set(svc._cache) == before["cache"], \
            "a shed request touched the winner cache"
        assert residency_snapshot() == before["lru"], \
            "a shed request perturbed bank residency"
        for c in ("admit", "evict", "cache_epoch_evictions"):
            assert counters.get(f"bank.{c}") == before[c], \
                f"a shed request moved bank.{c}"
    for b in blockers:
        b.join(timeout=30)
    assert not errs, f"blocked submits failed: {errs!r}"

    return {
        "spec": dataclasses.asdict(spec), "form": form,
        "uncontended": {"wall_s": round(unc_wall_s, 4),
                        "p50_ms": round(unc_p50_s * 1e3, 3),
                        "p99_ms": round(unc_p99_s * 1e3, 3),
                        "sustainable_batches_per_s":
                            round(sustainable_batches_per_s, 2)},
        "overload": {
            "n_producers": n_producers,
            "duration_s": round(duration_s, 3),
            "attempts": tally["attempts"],
            "wall_s": round(over_wall, 4),
            "offered_batches_per_s": round(offered_batches_per_s, 2),
            "offered_factor_vs_sustainable": round(offered_factor, 2),
            "outcomes": dict(tally),
            "served_p50_ms": round(served_p50_s * 1e3, 3),
            "served_p99_ms": round(served_p99_s * 1e3, 3),
            "served_p99_vs_uncontended":
                round(served_p99_s / max(unc_p99_s, 1e-9), 3),
            "p99_bound_factor": p99_bound_factor,
            # Calibration evidence: which denominator the bound used
            # (uncontended p99, or the served-median-scaled tail floor
            # on a saturated host) and the resulting absolute bound.
            "unc_tail_ratio": round(unc_tail_ratio, 3),
            "p99_bound_ms": round(p99_bound_s * 1e3, 3),
            "p99_bound_calibrated": bool(calibrated_floor > unc_p99_s),
        },
        "shed_probe": {"probes": n_probes, "shed": probes_shed,
                       "state_untouched": True},
        "p99_bounded_while_shedding": True,
    }


def run_harness(spec: HarnessSpec, form: str = "auto",
                with_sequential: bool = True,
                with_uncapped_check: bool = True) -> dict:
    """One full harness pass: replay + parity + (optionally) the
    capped-vs-uncapped residency proof. Returns the artifact dict
    (results stripped)."""
    models = make_tenants(spec)
    stream = make_stream(spec)
    service = build_service(spec, models, form=form)
    # Warm pass compiles every program shape (serving runs warm; cold
    # compile is a one-time cost) — on a FRESH service so the timed
    # replay still exercises admission/caching from empty.
    warm = build_service(spec, models, form=form)
    replay(warm, stream, tol=spec.tol, max_results=spec.max_results)
    banked = replay(service, stream, tol=spec.tol,
                    max_results=spec.max_results)
    out = {"spec": dataclasses.asdict(spec), "form": form,
           "banked": {k: v for k, v in banked.items() if k != "results"}}
    if with_sequential:
        seq = sequential_control(models, stream, tol=spec.tol,
                                 max_results=spec.max_results)
        assert_parity(banked, seq)
        out["sequential"] = {k: v for k, v in seq.items()
                            if k != "results"}
        out["parity_bit_identical"] = True
        out["speedup_banked_vs_sequential"] = round(
            banked["events_per_sec"] / max(seq["events_per_sec"], 1e-9), 3)
    if with_uncapped_check and spec.capacity \
            and spec.capacity < spec.n_tenants:
        unspec = dataclasses.replace(spec, capacity=0)
        uncapped = replay(build_service(unspec, models, form=form), stream,
                          tol=spec.tol, max_results=spec.max_results)
        assert_residency_identity(banked, uncapped)
        out["capped_winners_identical_to_uncapped"] = True
        assert banked["residency_churn"]["evicts"] > 0, (
            "capped replay evicted nothing — the residency proof was "
            "vacuous; shrink capacity or skew the stream harder")
    return out
