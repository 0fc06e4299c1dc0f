"""End-to-end telemetry: request-scoped spans, log-bucketed histograms,
Prometheus exposition, and the chaos flight recorder.

The pre-r18 observability stack is post-hoc only: `obs.CounterRegistry`
counts events, `obs.OccupancyClock` sums stage walls, and serving
quantiles were computed from ad-hoc latency lists after a harness run
ended. Nothing answered the live operator questions — "why was THIS
request slow", "what are the current p50/p99 per degradation rung", or
"what happened in the seconds before that fault fired". This module is
the live layer, four pieces sharing one discipline (near-zero cost when
off, no device-program changes ever — telemetry off is asserted
bit-identical in tier-1, tests/test_telemetry.py):

* **Spans** (`Tracer`) — monotonic-clock spans (each keeps its open
  time `t0` on `time.monotonic()`, the clock the benchmark's harness
  and drivers stamp their windows with) carrying a `trace_id`
  propagated through `contextvars` end-to-end: HTTP `X-Request-Id` on
  `/score` → `BankService.submit` → admission queue wait → bank wave
  dispatch; campaign stages and streaming batches get per-item trace
  ids. The hot path is LOCK-FREE: a disabled or sampled-out span takes
  no lock and allocates nothing beyond the context manager; a recorded
  span-close pays two appends (GIL-atomic `deque.append`: the span
  store that `Tracer.spans()` reads, `SPAN_STORE` records, and the
  flight ring) plus the histogram observe. Every program JAX compiles
  is a span too (`jit.compile`, from `jax.monitoring`'s events:
  `watch_compiles`), a child of whatever span asked for it. Spans
  FEED `OccupancyClock` accounting when given
  a clock (`span(..., clock=, clock_name=)` enters `clock.busy`
  unconditionally — occupancy numbers never depend on telemetry being
  on) instead of duplicating it. Every recorded span is also written
  into the profiler's trace as `onix.<name>`
  (`jax.profiler.TraceAnnotation`), so program spans and device ops
  share one clock in a collected xplane (ONIX_PROFILE_DIR). Literal
  span names are a declared contract: `SPAN_REGISTRY` below,
  machine-checked by the `spans` analysis pass (python -m
  onix.analysis) exactly like counter namespaces and env vars.

* **Histograms** (`Histogram`, `HistogramRegistry`) — log-bucketed
  (geometric buckets, growth `Histogram.GROWTH`): `observe(v)` lands v
  in bucket ⌈log_g v⌉, so any quantile read back is exact-to-the-bucket
  with a KNOWN relative error bound (`rel_error` = √g − 1, ~9% at the
  default g = 2^(1/4)). Every closed span observes its duration into
  the process registry under ``span.<name>`` (seconds), which is what
  `/metrics` renders and what replaced the ad-hoc quantile lists in
  `serving/load_harness.py` (parity-tested against numpy percentile).

* **Exposition** — `render_prometheus` writes the Prometheus text
  format (counters, histograms with cumulative `le` buckets, gauges,
  an info metric); `parse_prometheus_text` is the strict in-tree
  parser the tests and scripts/lint.sh check the output with, so the
  exposition can never drift into something a real scraper rejects.
  `GET /metrics` on `onix serve` (oa/serve.py) is the live endpoint.

* **Flight recorder** (`FlightRecorder`) — a bounded ring of recent
  span-close / counter-delta / fault events, and beside it the store
  of closed spans, which counter deltas cannot push out (counter
  deltas arrive via
  the observer hook this module installs on `obs.counters` at import).
  `dump(reason)` writes the ring + a full counter snapshot to a JSON
  artifact; the wired triggers are: any fault-plan site firing
  (faults.fire), a request shedding (BankService.submit), a model
  digest mismatch refusing (checkpoint.py), and a `faults`-marker test
  failing (tests/conftest.py) — so every chaos failure carries its own
  postmortem. Dumps only land when a directory is routed (config
  `telemetry.recorder_dir`, applied by `apply_config`, or the
  ONIX_TELEMETRY_DIR env fallback); an unrouted dump is counted
  (`telemetry.recorder_dump_unrouted`), never written into cwd.

Kill switches: config `telemetry.enabled=false` / `telemetry.sample=0`
(durable), ONIX_TELEMETRY=0 (env override for drills). Off means: no
spans recorded, no ring events, no histogram observations, no dumps —
and bit-identical winners with unchanged per-program dispatch counts,
asserted (the hard constraint this layer ships under).

docs/OBSERVABILITY.md is the operator page for all four pieces.
"""

from __future__ import annotations

import atexit
import collections
import contextlib
import contextvars
import dataclasses
import itertools
import json
import math
import os
import pathlib
import re
import sys
import threading
import time
import zlib

from onix.utils.obs import counters

#: Declared span names: the first argument of every literal
#: `TRACER.span(...)`/`TRACER.observe(...)` call must be a key here —
#: machine-checked by `python -m onix.analysis` (the `spans` pass),
#: because a typo'd span name is a latency series that silently never
#: aggregates with its siblings. Dead declarations (declared, never
#: opened) are findings too. Renders into docs/ROBUSTNESS.md
#: (generated section `span-registry`).
SPAN_REGISTRY: dict[str, str] = {
    "bank.admit": "ModelBank._ensure_resident: one wave's residency admission (LRU + H2D staging)",
    "bank.prefetch": "ModelBank.prefetch: one bulk host-tier promotion pass (Zipf-predicted tenants, disk -> host RAM)",
    "bank.score_wave": "one batched bank dispatch: kernel call + winner fetch for one wave (single-device path)",
    "bank.wave": "sharded bank: one per-device wave's admission + async program launch (fetch drains later)",
    "campaign.fit": "campaign orchestrator: one datatype's device fit (retries included)",
    "campaign.oa": "campaign orchestrator: one datatype's OA stage",
    "campaign.prepare": "campaign orchestrator: one datatype's host prepare (synth -> words -> corpus)",
    "campaign.score": "campaign orchestrator: one datatype's scoring stage",
    "daily.day": "daily supervisor: one simulated day end-to-end (campaign + model save + ledger write)",
    "daily.refit": "daily supervisor: one datatype's warm/cold refit decision — warm fit, drift check, and any drift-forced cold refit",
    "fit.checkpoint": "run_fit_segments: one checkpoint save at a superstep boundary (state to the host, then to disk)",
    "fit.compile_wait": "ShardedGibbsLDA.fit (ProgramsAhead): the main thread's first take of a program built ahead, waiting for its compile where that has not ended; attributes program, key (its static arguments), ready (False where it had to wait): the seconds the overlap with the layout did not hide",
    "fit.device_corpus": "ShardedGibbsLDA.fit: the blocked corpus to the device(s) (device_corpus)",
    "fit.estimates": "ShardedGibbsLDA.fit: final counts to the host and theta/phi in global order (estimates)",
    "fit.init_state": "ShardedGibbsLDA.fit: the chain's first state, drawn (cold: on the device; warm: on the host) and counted on the device, or restored from a checkpoint",
    "fit.notify": "run_fit_segments: the caller's per-boundary callback",
    "fit.precompile": "ShardedGibbsLDA._build_ahead, on the fit's own thread from the moment the layout's plan is known (under fit.prepare in time, and its child): trace, lowering and compile of init_fn and of every superstep the segments will call, in that order; attributes programs, count, failed; the parent of those programs' jit.compile spans",
    "fit.prepare": "ShardedGibbsLDA.fit: host layout of the corpus into shard blocks (prepare)",
    "fit.superstep": "run_fit_segments: the dispatch of one fused superstep program (returns with the device still running)",
    "fit.supersteps": "ShardedGibbsLDA.fit: the whole sweep loop (run_fit_segments) under one span; attributes say what one sweep's cross-chip merge moves (merge_bytes_per_sweep)",
    "fit.wait": "run_fit_segments: float(ll) at a superstep boundary, the host blocked on the device",
    "fleet.day": "fleet supervisor: one simulated day across every executing tenant (prepare, fleet refit, per-tenant accepts)",
    "fleet.refit": "fleet supervisor: the day's fused fleet refit — stacked warm/cold class dispatches plus the drift-gated cold second pass",
    "host.fit": "hostfabric coordinator: one multi-host fit end-to-end (spawn, monitor, deaths + restarts, result assembly)",
    "host.superstep": "hostfabric worker: one fused superstep segment dispatch, collective deadline + retry wrapper included",
    "jit.compile": "telemetry.watch_compiles: one program JAX compiled or loaded from its persistent cache, closed as its backend compile ends (jax.monitoring); attributes program, trace_s, lower_s, backend_s, cache (hit, miss, off); a child of the span open on the thread that compiled it (fit.precompile for the fit's programs, else the span that asked for the program)",
    "run.fit": "pipelines/run.py: the day's model fit, whichever engine",
    "run.score": "pipelines/run.py: scoring and selection of the day's events",
    "scan.checkpoint": "scale._stream_score: one chunk's progress checkpoint (_save_progress)",
    "scan.dispatch": "device_words.*_stream_bottom_k: the call of the jitted words+score+select scan for one chunk (async dispatch)",
    "scan.fetch": "scale._stream_score: one chunk's winners to the host, blocked on the scan",
    "scan.h2d_put": "device_words._put: jax.device_put of one staged column (the host's side of the copy; child of scan.stage)",
    "scan.partials": "device_words.stage_dns_cols, stage_proxy_cols: the per-unique string features of one chunk's dictionaries packed into partial keys (dns_partial_keys, proxy_partial_keys; child of scan.stage); its attributes count the dictionaries (names; uris, hosts, agents)",
    "scan.stage": "device_words.stage_*_cols: one chunk's host casts, per-unique string features and the start of its copies",
    "scan.synth": "scale._stream_score: the synthetic generator for one streamed chunk",
    "scan.tables": "device_words.build_*_tables: the trained tables re-encoded and copied for the device; its attributes say which form each look-up takes for them (word, doc: compare or join)",
    "serve.queue_wait": "BankService.submit: admitted-to-scoring-start wall (the admission queue wait)",
    "serve.request": "oa/serve.py /score: one HTTP request, receipt to response",
    "serve.score": "BankService.score body: cache lookups + bank dispatch for one batch",
    "serve.submit": "BankService.submit: one admitted request batch, queue wait + scoring",
    "stream.batch": "StreamingScorer.process: one streaming minibatch end-to-end",
    "stream.doc_growth": "StreamingScorer._grow_docs: the unseen addresses of a group's batches inserted into the document table on the host, the grown table (and store, where the rows run out) handed to the device (child of stream.superstep; only where the probe found a miss)",
    "stream.fetch": "StreamingScorer._resident_superstep: one superstep's winners and counters to the host, blocked on the program (child of stream.superstep)",
    "stream.h2d_put": "StreamingScorer._stage: jax.device_put of one staged [S, E] column (the host's side of the copy; child of stream.stage)",
    "stream.stage": "StreamingScorer._stage: one group's casts into the padded [S, E] buffers, the start of their copies, the protocol remaps and the probe's dispatch (child of the stream.superstep it is staged under)",
    "stream.superstep": "StreamingScorer.process_many: one group of S minibatches, dispatch of stream_svi_step to winners on the host (host-path batches of the group included)",
}

# ---------------------------------------------------------------------------
# Histograms.
# ---------------------------------------------------------------------------


class Histogram:
    """Log-bucketed histogram: bucket i covers (g^(i-1), g^i], values
    <= 0 land in a dedicated underflow bucket with upper edge 0. A
    quantile read returns the geometric midpoint of its bucket, so the
    true quantile lies within the bucket's edges — `quantile_bounds`
    returns them, and `rel_error` (= sqrt(g) - 1) bounds the midpoint's
    relative error. Exact-to-the-bucket by construction: no sampling,
    no decay, every observation counted. Thread-safe."""

    GROWTH = 2 ** 0.25          # ~19% bucket width, ~9% midpoint error
    _UNDERFLOW = -(10 ** 9)     # bucket index for values <= 0

    #: Lock discipline, machine-checked by the `locks` analysis pass.
    GUARDED_BY = {"_counts": "_lock", "n": "_lock", "sum": "_lock",
                  "min": "_lock", "max": "_lock"}

    def __init__(self, growth: float | None = None):
        self.growth = float(growth if growth is not None else self.GROWTH)
        if self.growth <= 1.0:
            raise ValueError("histogram growth must be > 1")
        self._log_g = math.log(self.growth)
        self._lock = threading.Lock()
        self._counts: dict[int, int] = {}
        self.n = 0
        self.sum = 0.0
        self.min = math.inf
        self.max = -math.inf

    @property
    def rel_error(self) -> float:
        """Worst-case relative error of `quantile`'s midpoint answer."""
        return math.sqrt(self.growth) - 1.0

    def _bucket(self, value: float) -> int:
        if value <= 0.0:
            return self._UNDERFLOW
        # ceil(log_g v): the smallest i with g^i >= v.
        return math.ceil(math.log(value) / self._log_g - 1e-12)

    def edge(self, bucket: int) -> float:
        """Upper edge of a bucket (0.0 for the underflow bucket)."""
        return 0.0 if bucket == self._UNDERFLOW else self.growth ** bucket

    def observe(self, value: float) -> None:
        b = self._bucket(float(value))
        with self._lock:
            self._counts[b] = self._counts.get(b, 0) + 1
            self.n += 1
            self.sum += float(value)
            if value < self.min:
                self.min = float(value)
            if value > self.max:
                self.max = float(value)

    def _sorted_counts(self) -> list[tuple[int, int]]:
        with self._lock:
            return sorted(self._counts.items())

    def quantile_bounds(self, q: float) -> tuple[float, float]:
        """(lower edge, upper edge) of the bucket holding the q-quantile
        (nearest-rank): the true quantile of the observed values lies in
        this closed interval. (0.0, 0.0) on an empty histogram."""
        items = self._sorted_counts()
        total = sum(c for _, c in items)
        if total == 0:
            return 0.0, 0.0
        # rank <= total for q <= 1, so the loop always returns; clamp
        # out-of-range q instead of walking past the last bucket.
        rank = min(max(1, math.ceil(q * total)), total)
        seen = 0
        for b, c in items:
            seen += c
            if seen >= rank:
                if b == self._UNDERFLOW:
                    return 0.0, 0.0
                return self.growth ** (b - 1), self.growth ** b
        raise AssertionError("unreachable: rank clamped to total")

    def quantile(self, q: float) -> float:
        """Geometric bucket midpoint of the q-quantile; within
        `rel_error` of the true nearest-rank quantile, clamped into the
        observed [min, max] so tiny samples don't report an edge no
        observation reached."""
        lo, hi = self.quantile_bounds(q)
        if hi == 0.0:
            return 0.0
        mid = math.sqrt(lo * hi)
        if self.n:
            mid = min(max(mid, self.min), self.max)
        return mid

    def snapshot(self) -> dict:
        """Manifest-ready summary: count/sum/min/max, the three judged
        quantiles, the error bound, and the (sparse) bucket table as
        [upper_edge, count] rows."""
        items = self._sorted_counts()
        with self._lock:
            n, s = self.n, self.sum
            mn = self.min if self.n else None
            mx = self.max if self.n else None
        return {
            "n": n,
            "sum": round(s, 9),
            "min": mn, "max": mx,
            "p50": self.quantile(0.50),
            "p99": self.quantile(0.99),
            "p999": self.quantile(0.999),
            "rel_error": round(self.rel_error, 4),
            "buckets": [[self.edge(b), c] for b, c in items],
        }


class HistogramRegistry:
    """Process-wide named histograms — the distribution analog of
    `obs.CounterRegistry` (dotted names, same prefix-snapshot
    discipline). `observe` is the one hot call: the per-name lookup
    rides a plain dict read (GIL-atomic); only histogram CREATION takes
    the registry lock."""

    #: Lock discipline, machine-checked by the `locks` analysis pass.
    GUARDED_BY = {"_hists": "_lock"}

    def __init__(self):
        self._lock = threading.Lock()
        self._hists: dict[str, Histogram] = {}

    def observe(self, name: str, value: float) -> None:
        h = self._hists.get(name)
        if h is None:
            with self._lock:
                h = self._hists.setdefault(name, Histogram())
        h.observe(value)

    def get(self, name: str) -> Histogram | None:
        return self._hists.get(name)

    def names(self, prefix: str = "") -> list[str]:
        return sorted(k for k in self._hists if k.startswith(prefix))

    def snapshot(self, prefix: str = "", buckets: bool = False) -> dict:
        """name -> histogram summary (bucket tables only on request —
        manifests want quantiles, not 200 rows per series)."""
        out = {}
        for name in self.names(prefix):
            h = self._hists.get(name)
            if h is None:
                continue
            snap = h.snapshot()
            if not buckets:
                snap.pop("buckets")
            out[name] = snap
        return out

    def reset(self, prefix: str = "") -> None:
        with self._lock:
            if not prefix:
                self._hists.clear()
            else:
                for k in [k for k in self._hists if k.startswith(prefix)]:
                    del self._hists[k]


#: The process-global histogram registry (tests reset() it).
histograms = HistogramRegistry()


# ---------------------------------------------------------------------------
# Flight recorder.
# ---------------------------------------------------------------------------


#: Closed spans kept for `Tracer.spans()`, apart from the ring: a
#: benchmark run records 54 to 390 of them, 15 to 29 of those compiles
#: (PERF.md section 3 has the count cell by cell); a day of `onix
#: serve` overwrites the oldest. Some 7 MB when full.
SPAN_STORE = 16384


class FlightRecorder:
    """Bounded ring of recent telemetry events (span closes, counter
    deltas, fault firings), and the store of closed spans beside it.
    `record` is lock-free — `deque.append` with
    a maxlen is GIL-atomic, and losing strict ordering between racing
    threads is acceptable for a postmortem buffer (each event carries
    its own monotonic stamp). `dump` snapshots the ring plus a full
    counter snapshot into a JSON artifact; dumps are capped per process
    (`max_dumps`) so a fault storm cannot fill a disk, and are counted
    either way (`telemetry.recorder_dumps` /
    `telemetry.recorder_dump_skipped` / `..._unrouted`).

    The ring (`capacity` events) is what a dump writes. The span store
    (`SPAN_STORE` records, whatever the ring's size) is what
    `Tracer.spans()` reads: a span leaves it only when `SPAN_STORE`
    later spans have closed, never because counters moved, and
    `telemetry.spans_recorded` less the store's length says how many
    have left."""

    #: Dump bookkeeping is the only locked state; the ring and the span
    #: store are deliberately lock-free (see class docstring).
    GUARDED_BY = {"_dumps": "_dump_lock"}

    def __init__(self, capacity: int = 1024, out_dir=None,
                 max_dumps: int = 32):
        self._ring: collections.deque = collections.deque(maxlen=capacity)
        self._spans: collections.deque = collections.deque(
            maxlen=SPAN_STORE)
        self.out_dir = pathlib.Path(out_dir) if out_dir else None
        self.max_dumps = max_dumps
        self._dump_lock = threading.Lock()
        self._dumps = 0

    def reconfigure(self, capacity: int | None = None,
                    out_dir=None) -> None:
        if capacity is not None and capacity != self._ring.maxlen:
            self._ring = collections.deque(self._ring, maxlen=capacity)
        if out_dir is not None:
            self.out_dir = pathlib.Path(out_dir)

    def record(self, kind: str, **fields) -> None:
        self._ring.append({"mono": round(time.perf_counter(), 6),
                           "t": round(time.time(), 3),
                           "kind": kind, **fields})

    def record_span(self, rec: "SpanRecord") -> None:
        """A closed span into the store and, as a `span` event with its
        open time `t0`, into the ring."""
        self._spans.append(rec)
        self.record("span", name=rec.name, trace_id=rec.trace_id,
                    span_id=rec.span_id, parent_id=rec.parent_id,
                    t0=round(rec.t0, 6), dur_s=round(rec.dur_s, 6),
                    error=rec.error, **rec.attrs)

    def events(self) -> list[dict]:
        return list(self._ring)

    def spans(self) -> list["SpanRecord"]:
        return list(self._spans)

    def clear(self) -> None:
        self._ring.clear()
        self._spans.clear()
        with self._dump_lock:
            self._dumps = 0

    def _resolve_dir(self) -> pathlib.Path | None:
        if self.out_dir is not None:
            return self.out_dir
        env = os.environ.get("ONIX_TELEMETRY_DIR")
        return pathlib.Path(env) if env else None

    def dump(self, reason: str, extra: dict | None = None):
        """Write the ring to `<dir>/flight-<pid>-<seq>-<reason>.json`.
        Returns the path, or None when unrouted (no dir configured),
        capped out, or telemetry is off — all counted, never silent."""
        if not TRACER.enabled:
            return None
        out_dir = self._resolve_dir()
        if out_dir is None:
            counters.inc("telemetry.recorder_dump_unrouted")
            return None
        with self._dump_lock:
            if self._dumps >= self.max_dumps:
                counters.inc("telemetry.recorder_dump_skipped")
                return None
            self._dumps += 1
            seq = self._dumps
        slug = re.sub(r"[^A-Za-z0-9._-]+", "-", reason)[:80] or "dump"
        path = out_dir / f"flight-{os.getpid()}-{seq:03d}-{slug}.json"
        doc = {
            "reason": reason,
            "pid": os.getpid(),
            "t": round(time.time(), 3),
            "counters": counters.snapshot(),
            "events": self.events(),
        }
        if extra:
            doc["extra"] = extra
        # Everything filesystem-shaped stays inside the except: an
        # unwritable recorder dir must degrade to a counted skip, never
        # leak an OSError into the TRIGGERING path's control flow (a
        # shed would 500 instead of 503, an injected fault would raise
        # the wrong class past its bounded retry).
        try:
            out_dir.mkdir(parents=True, exist_ok=True)
            path.write_text(json.dumps(doc, indent=2, default=repr) + "\n")
        except OSError:
            counters.inc("telemetry.recorder_dump_failed")
            return None
        counters.inc("telemetry.recorder_dumps")
        return path


# ---------------------------------------------------------------------------
# Spans.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class SpanRecord:
    """One closed span (what the span store and `Tracer.spans()` hold).

    `t0` is the open time on `time.monotonic()`, the clock
    `benchmark/harness.py` and its drivers stamp `t_start` and their
    windows with, so a reader can tell a span of set-up from one of the
    window; the span closed at `t0 + dur_s`. Durations are taken on
    `time.perf_counter()` for its resolution."""
    name: str
    trace_id: str
    span_id: int
    parent_id: int | None
    t0: float               # time.monotonic() at open
    dur_s: float
    error: str | None = None
    attrs: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass(frozen=True)
class _TraceCtx:
    trace_id: str
    sampled: bool


_TRACE: contextvars.ContextVar[_TraceCtx | None] = \
    contextvars.ContextVar("onix_trace", default=None)
_PARENT: contextvars.ContextVar[int | None] = \
    contextvars.ContextVar("onix_span", default=None)

_trace_seq = itertools.count(1)
_span_seq = itertools.count(1)


def new_trace_id() -> str:
    """Process-unique, human-sortable trace id (no host RNG: the id
    stream is deterministic per process, which keeps replays and tests
    reproducible)."""
    return f"t{os.getpid():x}-{next(_trace_seq):08d}"


def current_trace_id() -> str | None:
    ctx = _TRACE.get()
    return ctx.trace_id if ctx is not None else None


def _annotation(name: str, **meta):
    """The span as `onix.<name>` in the profiler's trace, on the clock
    the device ops are on (`jax.profiler.TraceAnnotation`; near-free
    while no trace is being collected). A process that never imported
    jax has no profiler to write into and gets a null context - this
    module must not be what imports jax (the analyzer and the fabric
    coordinator run without it)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return contextlib.nullcontext()
    if not _watching_compiles:
        watch_compiles()
    return jax.profiler.TraceAnnotation("onix." + name, **meta)


# ---------------------------------------------------------------------------
# Compiles: one `jit.compile` span a compiled program.
# ---------------------------------------------------------------------------

#: `jax.monitoring` duration events of one compilation, in the order
#: they come on the compiling thread (read on jax 0.9.0): the trace of
#: the function and, before it, of every function it calls
#: (`fun_name` "my_prog", "multiply", ...), the lowering
#: (`fun_name` "jit(my_prog)"), then - after `cache_hits` or
#: `cache_misses` where a persistent cache is configured, neither
#: where none is - the backend compile, which on a hit is the load.
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hit",
                 "/jax/compilation_cache/cache_misses": "miss"}

_watching_compiles = False
_watch_lock = threading.Lock()


class _Compiling(threading.local):
    """What this thread's compilation has reported so far."""

    def __init__(self):
        self.trace_s: dict[str, float] = {}
        self.lowered: tuple[str, float] = ("", 0.0)
        self.cache = "off"


_compiling = _Compiling()


def program_name(fun_name: str) -> str:
    """`jit(my_prog)` as `my_prog`: the name the function has in the
    source, which is also what its trace event carries."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name or "unknown"


def _on_compile_duration(event: str, duration: float, fun_name: str = "",
                         **_) -> None:
    if not TRACER.enabled:
        return
    st = _compiling
    if event == _TRACE_EVENT:
        st.trace_s[fun_name] = duration
    elif event == _LOWER_EVENT:
        st.lowered, st.cache = (fun_name, duration), "off"
    elif event == _BACKEND_EVENT:
        program = program_name(fun_name)
        # The trace events come for the inner functions too: the
        # program's own is the last one under its name, not their sum.
        trace_s = st.trace_s.get(program, 0.0)
        lower_s = st.lowered[1] if st.lowered[0] == fun_name else 0.0
        cache = st.cache
        st.trace_s, st.lowered, st.cache = {}, ("", 0.0), "off"
        dur_s = trace_s + lower_s + duration
        counters.inc("jit.compiles")
        counters.inc("jit.compile_us", int(dur_s * 1e6))
        if cache == "miss":
            counters.inc("jit.cache_misses")
        TRACER.observe("jit.compile", dur_s, program=program,
                       trace_s=trace_s, lower_s=lower_s,
                       backend_s=duration, cache=cache)


def _on_compile_event(event: str, **_) -> None:
    verdict = _CACHE_EVENTS.get(event)
    if verdict is not None and TRACER.enabled:
        _compiling.cache = verdict


def watch_compiles() -> None:
    """Listen to `jax.monitoring` from now on, once a process: every
    program JAX compiles (or loads from its persistent cache) becomes a
    closed span `jit.compile` when its backend compile ends, a child of
    the span open on the compiling thread, with attributes `program`
    (`program_name`), `trace_s`, `lower_s`, `backend_s` (`dur_s` is the
    three together) and `cache` ("hit", "miss", or "off" where no
    persistent cache is configured); and counts under `jit.compiles`,
    `jit.cache_misses`, `jit.compile_us`. A listener cannot be taken
    off again, so the callbacks themselves honour `TRACER.enabled` (and
    `observe` the sampling): with telemetry off nothing is recorded.

    Does nothing in a process that has not imported jax - this module
    is not what imports it. Called where jax is in hand:
    `obs.enable_compile_cache`, `obs.device_scope`, and the first
    recorded span after jax's import; programs compiled before any of
    the three are not seen."""
    global _watching_compiles
    if _watching_compiles or "jax" not in sys.modules:
        return
    import importlib
    monitoring = importlib.import_module("jax.monitoring")
    with _watch_lock:
        if _watching_compiles:
            return
        monitoring.register_event_duration_secs_listener(
            _on_compile_duration)
        monitoring.register_event_listener(_on_compile_event)
        _watching_compiles = True


class Tracer:
    """The span collector. `enabled=False` or `sample=0.0` turns every
    span into a context manager that only runs its optional clock —
    the lock-free hot path (no ring append, no histogram observe, no
    counter inc). Sampling is deterministic per trace id (crc32 hash),
    so one request's spans are all kept or all dropped together."""

    def __init__(self, enabled: bool = True, sample: float = 1.0):
        self.enabled = enabled and os.environ.get("ONIX_TELEMETRY",
                                                  "1") != "0"
        self.sample = float(sample)

    def configure(self, enabled: bool | None = None,
                  sample: float | None = None) -> None:
        if enabled is not None:
            self.enabled = bool(enabled) \
                and os.environ.get("ONIX_TELEMETRY", "1") != "0"
        if sample is not None:
            if not 0.0 <= sample <= 1.0:
                raise ValueError("telemetry sample must be in [0, 1]")
            self.sample = float(sample)

    def _sampled(self, trace_id: str) -> bool:
        if not self.enabled or self.sample <= 0.0:
            return False
        if self.sample >= 1.0:
            return True
        return (zlib.crc32(trace_id.encode()) & 0xFFFFFFFF) \
            < self.sample * 2 ** 32

    @contextlib.contextmanager
    def trace(self, trace_id: str | None = None):
        """Open a trace scope on the current context (thread/task):
        spans inside share the id and the sampling decision. Yields the
        trace id (the one to echo in X-Request-Id responses)."""
        tid = trace_id or new_trace_id()
        tok = _TRACE.set(_TraceCtx(tid, self._sampled(tid)))
        try:
            yield tid
        finally:
            _TRACE.reset(tok)

    @contextlib.contextmanager
    def span(self, name: str, *, clock=None, clock_name: str | None = None,
             **attrs):
        """One named span. `clock`/`clock_name` FEED an
        `obs.OccupancyClock` busy scope — entered unconditionally, so
        occupancy accounting is identical with telemetry off (the
        feeding-not-duplicating contract). A span that exits via an
        exception is recorded with `error` set and re-raises."""
        ctx = _TRACE.get()
        if ctx is None:
            # Root span with no surrounding trace (direct harness /
            # library calls): open an implicit per-span trace so child
            # spans still nest under one id.
            tid = new_trace_id()
            ctx = _TraceCtx(tid, self._sampled(tid))
            trace_tok = _TRACE.set(ctx)
        else:
            trace_tok = None
        clock_cm = (clock.busy(clock_name or name)
                    if clock is not None else None)
        if clock_cm is not None:
            clock_cm.__enter__()
        if not ctx.sampled:
            try:
                yield None
            finally:
                if clock_cm is not None:
                    clock_cm.__exit__(None, None, None)
                if trace_tok is not None:
                    _TRACE.reset(trace_tok)
            return
        span_id = next(_span_seq)
        parent_id = _PARENT.get()       # the span current BEFORE this one
        parent_tok = _PARENT.set(span_id)
        rec = SpanRecord(name=name, trace_id=ctx.trace_id, span_id=span_id,
                         parent_id=parent_id, t0=time.monotonic(),
                         dur_s=0.0, attrs=attrs)
        opened = time.perf_counter()
        err: str | None = None
        try:
            with _annotation(name):
                yield rec
        except BaseException as e:
            err = repr(e)
            raise
        finally:
            _PARENT.reset(parent_tok)
            rec.dur_s = time.perf_counter() - opened
            rec.error = err
            self._close(rec)
            if clock_cm is not None:
                clock_cm.__exit__(None, None, None)
            if trace_tok is not None:
                _TRACE.reset(trace_tok)

    def observe(self, name: str, dur_s: float, **attrs) -> None:
        """Synthesize a closed span of known duration (a wall measured
        inline, e.g. the admission queue wait) — same ring + histogram
        path as `span`, without restructuring the measured code. The
        span ends now and so opened `dur_s` ago; outside any trace it
        is a trace of its own, as a root `span` is."""
        ctx = _TRACE.get()
        if ctx is None:
            tid = new_trace_id()
            ctx = _TraceCtx(tid, self._sampled(tid))
        if not ctx.sampled:
            return
        # The wall was measured before this call, so the trace gets a
        # mark at the close that carries the duration.
        with _annotation(name, dur_s=dur_s):
            pass
        self._close(SpanRecord(
            name=name, trace_id=ctx.trace_id, span_id=next(_span_seq),
            parent_id=_PARENT.get(None), t0=time.monotonic() - dur_s,
            dur_s=dur_s, attrs=attrs))

    def _close(self, rec: SpanRecord) -> None:
        RECORDER.record_span(rec)
        histograms.observe(f"span.{rec.name}", rec.dur_s)
        counters.inc("telemetry.spans_recorded")

    def spans(self, trace_id: str | None = None) -> list[SpanRecord]:
        """Closed spans, oldest first (the recorder's span store: the
        last `SPAN_STORE` of them), optionally for one trace. Where
        `telemetry.spans_recorded` has passed the length of what this
        returns, the store has dropped its oldest."""
        return [s for s in RECORDER.spans()
                if trace_id is None or s.trace_id == trace_id]


#: Process-global singletons. `apply_config` (or `configure`) retunes
#: them; tests use `reset_for_tests`.
TRACER = Tracer()
RECORDER = FlightRecorder()


def configure(enabled: bool | None = None, sample: float | None = None,
              recorder_dir=None, recorder_events: int | None = None) -> None:
    TRACER.configure(enabled=enabled, sample=sample)
    RECORDER.reconfigure(capacity=recorder_events, out_dir=recorder_dir)


def apply_config(tcfg) -> None:
    """Apply a `config.TelemetryConfig` (serve and the CLI entry points
    call this once the resolved config exists)."""
    configure(enabled=tcfg.enabled, sample=tcfg.sample,
              recorder_dir=tcfg.recorder_dir or None,
              recorder_events=tcfg.recorder_events)


def reset_for_tests() -> None:
    """Clear the ring, the histogram registry, and the telemetry
    counters; re-enable with full sampling. Tests only."""
    RECORDER.clear()
    RECORDER.out_dir = None
    histograms.reset()
    counters.reset("telemetry")
    TRACER.configure(enabled=True, sample=1.0)


def snapshot(full: bool = False) -> dict:
    """The manifest telemetry block: enablement, span/dump tallies, and
    per-histogram quantile summaries (zeros included — an artifact that
    recorded nothing says so explicitly). `full=True` adds the complete
    counter snapshot and bucket tables (the launcher->child per-entry
    evidence record)."""
    out = {
        "enabled": TRACER.enabled,
        "sample": TRACER.sample,
        "spans_recorded": counters.get("telemetry.spans_recorded"),
        "recorder_dumps": counters.get("telemetry.recorder_dumps"),
        "recorder_dumps_unrouted":
            counters.get("telemetry.recorder_dump_unrouted"),
        "histograms": histograms.snapshot(buckets=full),
    }
    if full:
        out["counters"] = counters.snapshot()
    return out


# ---------------------------------------------------------------------------
# Prometheus exposition + the strict in-tree parser.
# ---------------------------------------------------------------------------

def _prom_name(dotted: str, suffix: str = "") -> str:
    name = "onix_" + re.sub(r"[^a-zA-Z0-9_:]", "_", dotted) + suffix
    return name


def _hist_suffix(name: str) -> str:
    """Prometheus unit suffix for a registry histogram. Span histograms
    are durations; anything else (e.g. the daily supervisor's
    `daily.drift`, a total-variation ratio in [0, 1]) renders WITHOUT
    the `_seconds` suffix — a unit suffix that lies about the unit is
    worse than none."""
    return "_seconds" if name.startswith("span.") else ""


def _prom_escape(value: str) -> str:
    return value.replace("\\", r"\\").replace("\n", r"\n").replace('"', r'\"')


def _fmt(v: float) -> str:
    if v == math.inf:
        return "+Inf"
    f = float(v)
    return repr(f) if f != int(f) else str(int(f))


def render_prometheus(counter_snap: dict[str, int] | None = None,
                      hist_reg: HistogramRegistry | None = None,
                      gauges: dict[str, float] | None = None,
                      info: dict[str, str] | None = None) -> str:
    """The Prometheus text format (version 0.0.4): every counter as
    `onix_<name>` (dots -> underscores), every histogram as
    `onix_<name>_seconds` with cumulative `le` buckets + `_sum` +
    `_count`, gauges as given, and one `onix_build_info{...} 1` info
    metric. Output is validated by `parse_prometheus_text` in tests
    and scripts/lint.sh."""
    lines: list[str] = []
    for name, value in sorted((counter_snap or {}).items()):
        pn = _prom_name(name)
        lines.append(f"# HELP {pn} onix counter {name}")
        lines.append(f"# TYPE {pn} counter")
        lines.append(f"{pn} {int(value)}")
    for name, value in sorted((gauges or {}).items()):
        pn = _prom_name(name)
        lines.append(f"# HELP {pn} onix gauge {name}")
        lines.append(f"# TYPE {pn} gauge")
        lines.append(f"{pn} {_fmt(float(value))}")
    reg = hist_reg if hist_reg is not None else histograms
    for name in reg.names():
        h = reg.get(name)
        if h is None:
            continue
        pn = _prom_name(name, _hist_suffix(name))
        lines.append(f"# HELP {pn} onix log-bucketed histogram {name} "
                     f"(rel error <= {h.rel_error:.3f})")
        lines.append(f"# TYPE {pn} histogram")
        cum = 0
        for b, c in h._sorted_counts():
            cum += c
            lines.append(f'{pn}_bucket{{le="{_fmt(h.edge(b))}"}} {cum}')
        lines.append(f'{pn}_bucket{{le="+Inf"}} {cum}')
        lines.append(f"{pn}_sum {_fmt(h.sum)}")
        lines.append(f"{pn}_count {cum}")
    kv = ",".join(f'{k}="{_prom_escape(str(v))}"'
                  for k, v in sorted((info or {}).items()))
    pn = "onix_build_info"
    lines.append(f"# HELP {pn} build/config identity of this process")
    lines.append(f"# TYPE {pn} gauge")
    lines.append(f"{pn}{{{kv}}} 1" if kv else f"{pn} 1")
    return "\n".join(lines) + "\n"


_SAMPLE_RE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?:\{(?P<labels>[^{}]*)\})?"
    r"\s+(?P<value>[-+]?(?:[0-9]*\.?[0-9]+(?:[eE][-+]?[0-9]+)?|Inf|NaN))"
    r"(?:\s+(?P<ts>[-+]?[0-9]+))?\s*$")
_LABEL_RE = re.compile(
    r'^(?P<k>[a-zA-Z_][a-zA-Z0-9_]*)="(?P<v>(?:[^"\\]|\\.)*)"$')


def parse_prometheus_text(text: str) -> dict[str, dict]:
    """Strict parser for the exposition format. Returns
    family base name -> {"type": ..., "samples": [(name, labels, value)]}.
    Raises ValueError on: malformed lines, samples typed before their
    TYPE line, duplicate TYPE lines, non-monotone histogram buckets, a
    histogram missing its +Inf bucket, or `_count` != the +Inf bucket.
    Deliberately strict — the in-tree gate that keeps /metrics
    scrapeable by real collectors."""
    families: dict[str, dict] = {}
    types: dict[str, str] = {}

    def base_of(name: str) -> str:
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix) and name[:-len(suffix)] in types \
                    and types[name[:-len(suffix)]] == "histogram":
                return name[:-len(suffix)]
        return name

    for i, line in enumerate(text.splitlines(), start=1):
        if not line.strip():
            continue
        if line.startswith("#"):
            parts = line.split(None, 3)
            if len(parts) < 3 or parts[1] not in ("HELP", "TYPE"):
                raise ValueError(f"line {i}: malformed comment {line!r}")
            if parts[1] == "TYPE":
                name, typ = parts[2], parts[3].strip()
                if typ not in ("counter", "gauge", "histogram", "summary",
                               "untyped"):
                    raise ValueError(f"line {i}: unknown type {typ!r}")
                if name in types:
                    raise ValueError(f"line {i}: duplicate TYPE for {name}")
                types[name] = typ
                families[name] = {"type": typ, "samples": []}
            continue
        m = _SAMPLE_RE.match(line)
        if m is None:
            raise ValueError(f"line {i}: malformed sample {line!r}")
        labels: dict[str, str] = {}
        raw = m.group("labels")
        if raw:
            for part in _split_labels(raw, i):
                lm = _LABEL_RE.match(part)
                if lm is None:
                    raise ValueError(f"line {i}: malformed label {part!r}")
                labels[lm.group("k")] = re.sub(
                    r"\\(.)", lambda m: {"n": "\n"}.get(m.group(1),
                                                        m.group(1)),
                    lm.group("v"))
        base = base_of(m.group("name"))
        if base not in families:
            raise ValueError(
                f"line {i}: sample for {m.group('name')} precedes its "
                "TYPE line")
        value = float(m.group("value").replace("Inf", "inf"))
        families[base]["samples"].append((m.group("name"), labels, value))
    for name, fam in families.items():
        if fam["type"] != "histogram":
            continue
        buckets = [(lab.get("le"), v) for n, lab, v in fam["samples"]
                   if n == name + "_bucket"]
        if not buckets or buckets[-1][0] != "+Inf":
            raise ValueError(f"histogram {name}: missing +Inf bucket")
        values = [v for _, v in buckets]
        if any(b > a for b, a in zip(values, values[1:])):
            raise ValueError(f"histogram {name}: non-cumulative buckets")
        count = [v for n, _, v in fam["samples"] if n == name + "_count"]
        if not count or count[0] != values[-1]:
            raise ValueError(
                f"histogram {name}: _count != +Inf bucket")
        if not any(n == name + "_sum" for n, _, _ in fam["samples"]):
            raise ValueError(f"histogram {name}: missing _sum")
    return families


def _split_labels(raw: str, line_no: int) -> list[str]:
    """Split `k="v",k2="v2"` honoring escaped quotes inside values."""
    out, buf, in_str, esc = [], [], False, False
    for ch in raw:
        if esc:
            buf.append(ch)
            esc = False
            continue
        if ch == "\\" and in_str:
            buf.append(ch)
            esc = True
            continue
        if ch == '"':
            in_str = not in_str
            buf.append(ch)
            continue
        if ch == "," and not in_str:
            out.append("".join(buf))
            buf = []
            continue
        buf.append(ch)
    if in_str:
        raise ValueError(f"line {line_no}: unterminated label string")
    if buf:
        out.append("".join(buf))
    return [p for p in out if p]


# ---------------------------------------------------------------------------
# Process wiring: the counter observer and the exit snapshot.
# ---------------------------------------------------------------------------


def _counter_observer(name: str, delta: int, total: int) -> None:
    """Installed on `obs.counters` at import: every counter delta lands
    in the flight ring (the `counter-delta` event class), EXCEPT the
    telemetry namespace itself (a dump incrementing recorder_dumps must
    not re-enter the ring it just snapshotted)."""
    if not TRACER.enabled or name.startswith("telemetry."):
        return
    RECORDER.record("counter", name=name, delta=delta, total=total)


def _register_exit_snapshot() -> None:
    # A launcher sets this to a per-child path; the child process
    # writes a full telemetry snapshot (counters + histograms) there at
    # exit, so its record carries dispatch/compile evidence, not bare
    # walls.
    path = os.environ.get("_ONIX_TELEMETRY_SNAPSHOT")
    if not path:
        return

    def _write():
        try:
            pathlib.Path(path).write_text(
                json.dumps(snapshot(full=True), indent=2,
                           default=repr) + "\n")
        except OSError:
            counters.inc("telemetry.snapshot_write_failed")

    atexit.register(_write)


counters.set_observer(_counter_observer)
_register_exit_snapshot()
