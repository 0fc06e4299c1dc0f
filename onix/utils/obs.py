"""Observability: profiler trace collection, run event log, throughput meters.

The reference has no purpose-built tracing or metrics (SURVEY.md §5.1,
§5.5 — it leaned on the Spark web UI, YARN logs, and lda-c's stdout
likelihood prints). onix makes the three judged observables first-class:

- `maybe_trace(dir)` — dumps a full profiler trace when
  ONIX_PROFILE_DIR (or the call) asks for one; every `telemetry.TRACER`
  span lies in it as `onix.<name>` and every device op carries its
  `onix.*` scope (docs/OBSERVABILITY.md).
- `RunLog` — append-only JSONL event stream per run (stage boundaries,
  per-sweep likelihood, checkpoint saves, faults) next to the results.
- `Meter` — wall-clock + items/sec for the events-scored/sec/chip
  number (BASELINE.json `metric`), reported in the run manifest.
"""

from __future__ import annotations

import contextlib
import json
import os
import pathlib
import re
import threading
import time


#: Declared counter namespaces: the first dotted component of every
#: literal `counters.inc`/`note_max`/`get` key (and every f-string
#: key's literal prefix, and every `counter_prefix=` literal) must be a
#: key here — machine-checked by `python -m onix.analysis` (the
#: `counters` pass), because a typo'd namespace is a counter that
#: silently never aggregates into the manifests that snapshot by
#: prefix. Dead namespaces (declared, never used) are findings too.
#: Renders into docs/ROBUSTNESS.md (generated section
#: `counter-namespaces`).
COUNTER_NAMESPACES: dict[str, str] = {
    "bank": "model-bank residency/cache/dispatch events (onix/serving)",
    "campaign": "campaign orchestrator retries/preemptions (pipelines/campaign.py)",
    "ckpt": "checkpoint/model integrity events (digest mismatches)",
    "daily": "continuous-operation supervisor events (warm/cold refits, drift fallbacks, ledger refusals, poison-day rollbacks; pipelines/daily.py)",
    "faults": "injected chaos-plan firings, as faults.<stage>.<point>",
    "fit": "the sharded fit's programs built ahead of their first call: precompile.hit (a call served by an executable compiled on the fit's thread) and precompile.miss (a call that went through jax.jit: unplanned, failed to compile, or other arguments; parallel/sharded_gibbs.py ProgramsAhead)",
    "fleet": "fleet-batched refit supervisor events (warm/cold tenant-days, drift cold refits, per-tenant quarantines, nudge applications; pipelines/fleet.py)",
    "host": "multi-host fit fabric events (heartbeats, death detection, shard quarantine, restart/rebalance; parallel/hostfabric.py)",
    "feedback": "analyst feedback loop events (rescored events, skipped nudges)",
    "ingest": "watcher/mpingest retry + quarantine events",
    "jit": "programs JAX compiled or loaded from its persistent cache, as jax.monitoring reports them: compiles, cache_misses, compile_us (utils/telemetry.py watch_compiles; the jit.compile span beside them)",
    "resilience": "RetryPolicy/Deadline events (utils/resilience.py)",
    "salvage": "salvage-mode decode skip tallies, per format",
    "scale": "scale-runner resume/discard events (pipelines/scale.py)",
    "score": "selection-scan events (bf16-screened scans run, and the ones whose device-side proof did not certify and paid the f32 scan too; models/scoring.py)",
    "serve": "serving admission/degradation events (shed, deadline, fallback)",
    "stream": "streaming scorer shape-lattice + prefetch events",
    "telemetry": "telemetry layer self-reporting (spans recorded, flight-recorder dumps; utils/telemetry.py)",
}


class CounterRegistry:
    """Process-wide named event counters — the one place every
    resilience event (retry, quarantine, salvage, injected fault,
    checkpoint digest mismatch) is tallied, so watcher stats, streaming
    stage reports, and scale manifests all read the same numbers
    instead of each keeping a private ledger. Thread-safe; names are
    dotted paths (`ingest.quarantined`, `salvage.skipped_records`)."""

    #: Lock discipline, machine-checked by the `locks` analysis pass.
    GUARDED_BY = {"_counts": "_lock", "_observer": "_lock"}

    def __init__(self):
        self._lock = threading.Lock()
        self._counts: dict[str, int] = {}
        # Optional delta observer (utils/telemetry.py installs the
        # flight-recorder feed here at import): called as
        # observer(name, delta, total) AFTER the lock is released, so
        # an observer can never deadlock the registry. None = off.
        self._observer = None

    def set_observer(self, fn) -> None:
        with self._lock:
            self._observer = fn

    def inc(self, name: str, n: int = 1) -> int:
        with self._lock:
            self._counts[name] = total = self._counts.get(name, 0) + int(n)
        obs_fn = self._observer
        if obs_fn is not None:
            obs_fn(name, int(n), total)
        return total

    def get(self, name: str) -> int:
        with self._lock:
            return self._counts.get(name, 0)

    def note_max(self, name: str, value: int) -> int:
        """High-water-mark counter: keep the LARGEST value ever noted
        (e.g. `serve.queue_depth_peak`). Same namespace and snapshot
        path as the event counters, so manifests carry gauges and
        tallies through one registry."""
        moved = False
        with self._lock:
            cur = self._counts.get(name, 0)
            if int(value) > cur:
                self._counts[name] = int(value)
                cur = int(value)
                moved = True
        obs_fn = self._observer
        if moved and obs_fn is not None:
            obs_fn(name, 0, cur)
        return cur

    def snapshot(self, prefix: str = "") -> dict[str, int]:
        """Copy of the current counts (optionally only names under
        `prefix`) — what manifests embed."""
        with self._lock:
            return {k: v for k, v in sorted(self._counts.items())
                    if k.startswith(prefix)}

    def reset(self, prefix: str = "") -> None:
        with self._lock:
            if not prefix:
                self._counts.clear()
            else:
                for k in [k for k in self._counts if k.startswith(prefix)]:
                    del self._counts[k]


#: The process-global registry (tests reset() it between cases).
counters = CounterRegistry()


class OccupancyClock:
    """Overlap-exact wall accounting for pipelined multi-stage runs —
    the shared discipline behind the r14 campaign orchestrator
    (onix/pipelines/campaign.py), generalizing the streaming
    prefetcher's rule that only CONSUMER-BLOCKED seconds count as wait
    (streaming.py prefetch_wait).

    `busy(name)` marks a stage busy on the calling thread; stages may
    run concurrently on different threads. `blocked(name)` records
    consumer-blocked seconds — time a thread spent waiting on another
    stage's output, the pipeline's barrier stalls. Derived numbers:

      * busy_s[name]    — per-stage busy seconds (sum over threads);
      * union_busy_s    — wall seconds during which >= 1 stage was
                          busy (active-count 0→1/1→0 transitions);
      * overlap_s       — Σ busy − union: seconds of genuinely
                          concurrent stage work (0 in a sequential
                          run — the assertable difference between the
                          orchestrator's two arms);
      * the stage-sum identity — for any single thread, Σ its busy
                          spans + Σ its blocked spans + its idle ==
                          its elapsed span. The campaign asserts it
                          for the driver thread (check_stage_sum).

    Thread-safe; snapshot at quiescence (open busy spans are not yet
    in union_busy_s)."""

    #: Lock discipline, machine-checked by the `locks` analysis pass:
    #: stages run on several threads; every tally mutates under _lock.
    GUARDED_BY = {"busy_s": "_lock", "blocked_s": "_lock",
                  "_active": "_lock", "_active_since": "_lock",
                  "union_busy_s": "_lock"}

    def __init__(self):
        self._lock = threading.Lock()
        self._t0 = time.perf_counter()
        self.busy_s: dict[str, float] = {}
        self.blocked_s: dict[str, float] = {}
        self._active = 0
        self._active_since = 0.0
        self.union_busy_s = 0.0

    @contextlib.contextmanager
    def busy(self, name: str):
        t0 = time.perf_counter()
        with self._lock:
            if self._active == 0:
                self._active_since = t0
            self._active += 1
        try:
            yield
        finally:
            t1 = time.perf_counter()
            with self._lock:
                self._active -= 1
                if self._active == 0:
                    self.union_busy_s += t1 - self._active_since
                self.busy_s[name] = (self.busy_s.get(name, 0.0)
                                     + (t1 - t0))

    @contextlib.contextmanager
    def blocked(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            with self._lock:
                self.blocked_s[name] = (self.blocked_s.get(name, 0.0)
                                        + (time.perf_counter() - t0))

    @property
    def span_s(self) -> float:
        return time.perf_counter() - self._t0

    def check_stage_sum(self, stage_names, blocked_names=None,
                        span_s: float | None = None,
                        tol_s: float = 0.25) -> tuple[bool, float]:
        """The stage-sum identity for one thread's stages: Σ busy +
        Σ blocked must not exceed the thread's span, and the residual
        (idle) must be non-negative — accounted time can never exceed
        wall. Returns (ok, residual_idle_s); `tol_s` absorbs clock
        granularity."""
        span = self.span_s if span_s is None else span_s
        with self._lock:
            accounted = sum(self.busy_s.get(n, 0.0) for n in stage_names)
            accounted += sum(
                self.blocked_s.get(n, 0.0)
                for n in (blocked_names if blocked_names is not None
                          else self.blocked_s))
        residual = span - accounted
        return residual >= -tol_s, residual

    def snapshot(self) -> dict:
        with self._lock:
            total = sum(self.busy_s.values())
            return {
                "span_s": round(time.perf_counter() - self._t0, 3),
                "busy_s": {k: round(v, 3)
                           for k, v in sorted(self.busy_s.items())},
                "blocked_s": {k: round(v, 3)
                              for k, v in sorted(self.blocked_s.items())},
                "union_busy_s": round(self.union_busy_s, 3),
                "overlap_s": round(max(total - self.union_busy_s, 0.0), 3),
            }


def enable_compile_cache() -> None:
    """Persistent XLA compilation cache, placed from OUTSIDE the
    program: where JAX_COMPILATION_CACHE_DIR is set JAX reads it itself
    and this sets no directory at all; otherwise the cache lives at
    `<checkout>/.jax_cache` — a fixed path, because the path is part of
    the cache key and a directory that moves never hits. Cold compiles
    cost seconds to minutes per program; caching them makes every later
    cold process warm-start. EVERY program is cached (no minimum
    compile time): a day's run asks for dozens of sub-second programs,
    and a threshold near their compile time makes a warm process's
    compile count — and its cache writes — vary run to run. Safe to
    call repeatedly; the one place in the tree that sets the
    directory. From here on every compile is a `jit.compile` span
    whose `cache` attribute says whether this cache answered
    (`telemetry.watch_compiles`)."""
    import jax
    _telemetry.watch_compiles()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        path = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"
        path.mkdir(parents=True, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def device_scope(name: str):
    """`jax.named_scope(name)` for code under `jit`: the `onix.*` names
    the profiler's trace shows on each device op (the scope path is the
    op's `op_name`; the table of scopes is in docs/OBSERVABILITY.md).

    A scope is metadata, and JAX's persistent compile cache leaves
    metadata out of its key by default - a process then loads whatever
    executable the cache holds for the same arithmetic, compiled from
    other source, and its trace shows that source's names or none (seen
    on the chip in PR 26: a run that followed the parent commit's on
    one cache traced every op unscoped). So the first scope a process
    traces also makes the metadata part of the key: the executable that
    runs is the one compiled from this source. The price is a compile
    where only a line number moved; file names enter the key relative
    to the checkout, so a checkout that moves still hits."""
    import jax
    _telemetry.watch_compiles()
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    if not jax.config.jax_hlo_source_file_canonicalization_regex:
        root = pathlib.Path(__file__).resolve().parents[2]
        jax.config.update("jax_hlo_source_file_canonicalization_regex",
                          re.escape(str(root)) + "/")
    return jax.named_scope(name)


def device_summary() -> dict:
    """The default devices as JAX reports them — what every script and
    chip_smoke.py print and stamp beside their numbers."""
    import jax
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def print_device() -> dict:
    """Print the device line every script leads with (stderr, so a
    script's stdout stays its result) and return the summary."""
    import sys
    device = device_summary()
    print(f"device: {json.dumps(device)}", file=sys.stderr, flush=True)
    return device


def device_peak_bytes_in_use() -> list[int | None]:
    """Per-device `memory_stats()["peak_bytes_in_use"]` in
    jax.devices() order; None where the backend reports no stats (the
    CPU backend)."""
    import jax
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()]


@contextlib.contextmanager
def maybe_trace(out_dir: str | None = None):
    """Collect a full profiler trace if `out_dir` or ONIX_PROFILE_DIR is
    set; otherwise a no-op. View with TensorBoard or Perfetto."""
    import jax.profiler
    target = out_dir or os.environ.get("ONIX_PROFILE_DIR")
    if not target:
        yield None
        return
    pathlib.Path(target).mkdir(parents=True, exist_ok=True)
    jax.profiler.start_trace(target)
    try:
        yield target
    finally:
        jax.profiler.stop_trace()


class RunLog:
    """Append-only JSONL event log (SURVEY.md §5.5).

    One line per event: {"t": epoch_s, "event": ..., **fields}. The file
    is opened per-append so a preempted run loses at most one line.
    """

    def __init__(self, path: str | pathlib.Path | None):
        self.path = pathlib.Path(path) if path else None
        if self.path is not None:
            self.path.parent.mkdir(parents=True, exist_ok=True)

    def emit(self, event: str, **fields) -> None:
        if self.path is None:
            return
        rec = {"t": round(time.time(), 3), "event": event, **fields}
        with open(self.path, "a") as fh:
            fh.write(json.dumps(rec) + "\n")

    @contextlib.contextmanager
    def stage(self, name: str, **fields):
        """Log stage start/end (with wall seconds) around a block."""
        self.emit("stage_start", stage=name, **fields)
        t0 = time.perf_counter()
        try:
            yield
        except BaseException as e:
            self.emit("stage_error", stage=name, error=repr(e),
                      wall_s=round(time.perf_counter() - t0, 3))
            raise
        self.emit("stage_end", stage=name,
                  wall_s=round(time.perf_counter() - t0, 3))


# ---------------------------------------------------------------------------
# Roofline accounting.
#
# The hot loops are MEMORY-bound: the scoring scan is table-row gathers
# and a score write per event, and the Gibbs sweep is bounded by the
# n_dk scatter-add (PERF.md section 5). The honest efficiency number is
# therefore achieved bytes/s against the device's peak memory
# bandwidth, not FLOP/s. The benchmark's own byte models live with it
# (benchmark/models.py); here are the peaks, the one model a script of
# this tree still prices (bank_score_bytes_per_event) and the entry's
# arithmetic.
# ---------------------------------------------------------------------------

# Chip HBM peaks, bytes/s (vendor specs), keyed on jax device_kind
# prefixes. The chip this repo measures on reports "TPU v5 lite"
# (v5e: 819 GB/s HBM BW, Google Cloud "TPU v5e" documentation).
_HBM_PEAK_BYTES_PER_S = {
    "TPU v5 lite": 819e9,
    "TPU v5e": 819e9,
    "TPU v5": 2765e9,          # v5p spec (2765 GB/s HBM2e)
    "TPU v4": 1228e9,
    "TPU v6": 1640e9,
}


def measured_host_bandwidth(size_bytes: int = 1 << 28) -> float:
    """Live streaming-copy probe of the HOST's memory bandwidth
    (read + write bytes over the best of three big memcpys). A CPU
    host has no spec sheet to cite — this anchors its roofline
    denominator in a measurement on the same box, same run."""
    import numpy as np
    n = size_bytes // 8
    src = np.ones(n, np.float64)
    dst = np.empty_like(src)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        np.copyto(dst, src)
        best = min(best, time.perf_counter() - t0)
    return 2.0 * n * 8 / max(best, 1e-9)


def device_peak_bytes_per_s() -> tuple[float, str]:
    """(peak bytes/s, provenance string) for the default device: the
    HBM spec for known TPU kinds, a live copy probe for a CPU host. An
    accelerator kind missing from the table RAISES — a made-up
    denominator would fabricate the fraction-of-peak, and a silent None
    would hide that the roofline was never placed."""
    import jax
    dev = jax.devices()[0]
    kind = str(getattr(dev, "device_kind", ""))
    for prefix, peak in _HBM_PEAK_BYTES_PER_S.items():
        if kind.startswith(prefix):
            return peak, f"{prefix} HBM spec"
    if dev.platform == "cpu":
        return measured_host_bandwidth(), "host streaming-copy probe"
    raise LookupError(
        f"no HBM peak for device kind {kind!r} (platform "
        f"{dev.platform!r}); add it to obs._HBM_PEAK_BYTES_PER_S with "
        "its source")


def bank_score_bytes_per_event(k_topics: int, dtype_bytes: int = 4) -> float:
    """Modeled memory traffic per scored event through the model bank's
    batched program (onix/serving/model_bank.py;
    scripts/exp_model_bank.py prices its replay with it): the two
    bank-row gathers (θ_bank[slot, d], φ_bank[slot,
    w]: 2·K·dtype B — the tenant axis folds into the gather index, so
    the TENANT gather is these same rows, charged once), the per-event
    token stream (d, w ids + mask: 12 B), the request's tenant slot
    read amortized per event (≈4 B charged flat), and the f32 score
    write feeding selection (4 B). Identical per-event traffic to the
    single-tenant scan (2·K·dtype + 12 + 4 B) plus the slot read —
    which is exactly the claim: banking N tenants adds a slot
    gather, not N× dispatch overhead."""
    return 2 * k_topics * dtype_bytes + 12 + 4 + 4


def roofline(n_items: int, wall_s: float, bytes_per_item: float,
             peak_bytes_per_s: float | None) -> dict:
    """One component's roofline entry: achieved bytes/s from the
    modeled per-item traffic, and the fraction of the peak it reaches
    (None when no trustworthy peak exists)."""
    achieved = n_items * bytes_per_item / max(wall_s, 1e-9)
    return {
        "modeled_bytes_per_item": round(float(bytes_per_item), 1),
        "achieved_bytes_per_s": round(achieved, 1),
        "fraction_of_peak": (round(achieved / peak_bytes_per_s, 4)
                             if peak_bytes_per_s else None),
    }


class Meter:
    """items/sec over a wall-clock window (perf_counter based)."""

    def __init__(self):
        self.t0 = time.perf_counter()
        self.items = 0

    def add(self, n: int) -> None:
        self.items += int(n)

    @property
    def seconds(self) -> float:
        return time.perf_counter() - self.t0

    @property
    def rate(self) -> float:
        dt = self.seconds
        return self.items / dt if dt > 0 else 0.0


# Bottom import on purpose: obs is the one module every stage already
# imports, so pulling telemetry in here guarantees the flight-recorder
# counter observer (telemetry installs it at its own import) is live in
# EVERY process — chaos drills that only import faults/obs still get
# ring events, and a launcher's per-child exit snapshot (the
# _ONIX_TELEMETRY_SNAPSHOT handshake) is registered no matter which
# entry point the child runs. Safe against the obs<->telemetry cycle:
# everything telemetry needs from obs is defined above this line.
from onix.utils import telemetry as _telemetry  # noqa: E402,F401
