"""Typed configuration for onix.

The reference shares one untyped key-value file across every layer
(`/etc/duxbay.conf`-style, sourced by Bash, parsed by Python and Scala;
see SURVEY.md §5.6 — keys like DBNAME, NODES, TOL, TOPIC_COUNT, DUPFACTOR
are structurally required by the ml_ops.sh call stack, reference
README.md:41-43). onix replaces that with schema-validated dataclasses,
YAML/JSON loading, dotted-path CLI overrides, and an archived resolved
config per run.
"""

from __future__ import annotations

import dataclasses
import json
import hashlib
import os
import pathlib
from dataclasses import dataclass, field
from typing import Any, Callable

DATATYPES = ("flow", "dns", "proxy")


def resolve_form_gate(*, gate: str, choices: tuple[str, ...],
                      explicit: str | None = None,
                      env: str | None = None,
                      env_var: str | None = None,
                      measured: Callable[[], str | None] | None = None,
                      default: str) -> str:
    """The ONE precedence chain behind the option-bearing performance
    gates — `model_bank.select_bank_form` and `select_shard_form`,
    `pallas_serve.select_serve_form`, `lda_gibbs.select_sampler_form` —
    each resolves through this helper so their tables cannot drift in
    precedence order:

        env override  >  explicit form  >  measured table  >  default

    `env` is the raw override value (or `env_var` to read it here);
    empty and "auto" both mean "no override" — exporting FOO=auto resets
    an inherited override instead of crashing. Any other value outside
    `choices` raises, for env and explicit alike: a typo'd override must
    fail loudly, never silently mislabel an experiment's arms. The
    sampler gate passes no env — its engines resolve ONIX_SAMPLER_FORM
    themselves, behind the config field, and hand the result in as
    `explicit`. (`lda_gibbs.select_nwk_form` has no option to order and
    does not come here.) `measured` is the per-backend crossover-table
    lookup;
    None (unmeasured platform, or below the crossover) falls to
    `default` — never an unmeasured guess."""
    if env is None and env_var is not None:
        env = os.environ.get(env_var)
    for value, what in ((env, f"{gate} (env override)"),
                        (explicit, gate)):
        if value is None or value in ("", "auto"):
            continue
        if value not in choices:
            raise ValueError(
                f"{what} must be auto|{'|'.join(choices)}, got {value!r}")
        return value
    if measured is not None:
        got = measured()
        if got is not None:
            return got
    return default


#: The central registry of every `ONIX_*` environment variable the
#: linted tree (onix/, chip_smoke.py, scripts/) reads: name -> (type, doc).
#: Machine-checked by `python -m onix.analysis` (the `envs` pass): a
#: literal ONIX_* read of an undeclared name is a finding, and so is a
#: declaration nothing reads — this table can neither lag nor rot. The
#: table also renders into docs/ROBUSTNESS.md (generated section
#: `env-registry`). Leading-underscore names are internal parent/child
#: handshakes, never operator knobs. Envs are OVERRIDES for
#: experiments and drills; durable configuration belongs in the typed
#: config below.
ENV_REGISTRY: dict[str, tuple[str, str]] = {
    "ONIX_BANK_FORM": (
        "choice: auto|vmap|gather",
        "model-bank batched-scoring form override (model_bank.select_bank_form)"),
    "ONIX_BANK_SHARD": (
        "choice: auto|single|sharded",
        "model-bank mesh placement override (model_bank.select_shard_form)"),
    # lint: exempt[envs] -- read inside the generated notebook-cell SOURCE templates (oa/notebooks.py) and exported to kernels by oa/serve.py; no AST-visible read exists
    "ONIX_CONFIG": (
        "path",
        "notebook kernels: resolved config file the OA cells load"),
    # lint: exempt[envs] -- read inside the generated notebook-cell SOURCE templates (oa/notebooks.py); exported by oa/serve.py and the CLI
    "ONIX_DATE": (
        "string YYYY-MM-DD",
        "notebook kernels: the scored date the OA cells read"),
    "ONIX_DAILY_FORCE_COLD": (
        "flag: 1=cold every day",
        "daily supervisor drill override: ignore yesterday's model and "
        "fit every day cold (pipelines/daily.py) — daily.force_cold is "
        "the durable knob"),
    "ONIX_DP1_FAST": (
        "flag: 0=pin wrapped arm",
        "sharded engine dp=1/mp=1 shard_map-bypass fast path override"),
    "ONIX_FAULT_PLAN": (
        "plan: stage:point@N=action,...",
        "declarative chaos plan (utils/faults.py; docs/ROBUSTNESS.md)"),
    "ONIX_HOSTFABRIC_COORD": (
        "addr: host:port",
        "hostfabric worker: jax.distributed coordinator address (set by "
        "the local coordinator for spawned workers; real hosts export it "
        "when launching workers by hand — parallel/hostfabric.py)"),
    "ONIX_FABRIC_WORKER_PLATFORM": (
        "jax platform name (cpu, tpu)",
        "hostfabric coordinator: platform spawned fit workers run on. "
        "Default cpu (safe anywhere); tpu splits this host's chips "
        "across workers via TPU_VISIBLE_DEVICES — the coordinator must "
        "then run under JAX_PLATFORMS=cpu so it holds no chips "
        "(parallel/hostfabric.py)"),
    "ONIX_FAULT_SWEEP": (
        "int sweep number",
        "legacy one-off fit:sweep preemption hook (pre-r9 chaos drills)"),
    "ONIX_GTI_API_KEY": (
        "secret",
        "GTI reputation client credential (oa/repclients.py)"),
    "ONIX_HOST_WORDS": (
        "flag: 1=host builders",
        "force the host word-build cross-check arm (device_words gate)"),
    "ONIX_PALLAS_INTERPRET": (
        "flag: 1=interpret, 0=compiled",
        "Pallas kernels: force interpret/compiled mode (pallas_serve)"),
    "ONIX_PREFETCH_DEPTH": (
        "int >= 1",
        "streaming ingest pipeline depth override (ColumnPrefetcher)"),
    "ONIX_PREFETCH_MODE": (
        "choice: auto|thread|process",
        "streaming ingest pipeline worker mode override"),
    "ONIX_PROFILE_DIR": (
        "path",
        "collect a jax profiler trace into this dir (obs.maybe_trace)"),
    "ONIX_SAMPLER_FORM": (
        "choice: auto|dense|sparse",
        "Gibbs sampler-form override (lda_gibbs.select_sampler_form)"),
    "ONIX_SCREENED_SELECT": (
        "flag: 1=on, other=off",
        "bf16-screened bottom-k scan override (models/scoring.py)"),
    "ONIX_SERVE_FORM": (
        "choice: auto|xla|fused",
        "serving-scan form override (pallas_serve.select_serve_form)"),
    "ONIX_TELEMETRY": (
        "flag: 0=off",
        "kill-switch for the r18 telemetry layer (spans, flight recorder; utils/telemetry.py) — telemetry.* config is the durable knob"),
    "ONIX_TELEMETRY_DIR": (
        "path",
        "flight-recorder dump dir fallback when no telemetry.recorder_dir was applied (utils/telemetry.py)"),
    "ONIX_TX_ACCESS_TOKEN": (
        "secret",
        "ThreatExchange reputation client credential (oa/repclients.py)"),
    "_ONIX_TELEMETRY_SNAPSHOT": (
        "internal path",
        "launcher->child handshake: a child started with this set writes a counters+histograms snapshot there at exit (utils/telemetry.py)"),
}


@dataclass
class LDAConfig:
    """Topic-model hyperparameters.

    Mirrors the knobs of the reference LDA engine (oni-lda-c settings +
    the TOPIC_COUNT central-config key): K topics, Dirichlet priors, and
    iteration counts, plus TPU-batching knobs the reference has no analog
    for (block_size controls the token-block width of the batched
    collapsed-Gibbs sweep).
    """

    n_topics: int = 20
    alpha: float = 1.2          # doc-topic Dirichlet prior (lda-c style: ~50/K)
    eta: float = 0.01           # topic-word Dirichlet prior ("beta" in lda-c)
    n_sweeps: int = 60          # Gibbs sweeps / VB epochs
    burn_in: int = 20           # sweeps before averaging posterior estimates
    block_size: int = 65536     # tokens sampled per scatter round inside a sweep
    seed: int = 0
    # Online-VB (SVI) schedule: rho_t = (tau0 + t)^(-kappa)
    svi_tau0: float = 64.0
    svi_kappa: float = 0.7
    svi_batch_size: int = 4096  # documents per SVI minibatch
    svi_local_iters: int = 30   # local E-step fixed-point iteration CAP
    # E-step convergence stop (Hoffman's onlineldavb meanchange rule):
    # iteration ends early once mean |Δgamma| over the batch drops under
    # this. Converged batches stop in a handful of iterations instead of
    # always paying the svi_local_iters cap; 0 disables (fixed count).
    svi_meanchange_tol: float = 1e-3
    # Warm/cold E-step split (r10 streaming fast path): run this many
    # fixed-trip iterations over the full padded block, then COMPACT
    # the still-unconverged docs' tokens into a pow2 bucket and run the
    # extended while_loop only there (lda_svi._run_e_step). -1 = auto:
    # OFF for the batch SVI engine (bit-preserves the r6 loop), 4 for
    # the streaming scorer whose warm-started returning docs converge
    # inside the short pass. 0 forces the legacy loop everywhere; >0
    # forces the split at that warm length. Part of the streaming
    # checkpoint fingerprint — it changes what the E-step computes.
    svi_warm_iters: int = -1
    svi_max_epochs: int = 30    # batch-mode epoch cap (streaming: n/a)
    svi_epoch_tol: float = 1e-3  # stop when relative ll gain drops below
    checkpoint_every: int = 0   # sweeps between sampler checkpoints (0=off)
    # Independent Gibbs chains, batched on device via vmap; event scores
    # average over chains. Single chains are rank-unstable (recall on the
    # same data swings with the model seed — SURVEY.md §7.3.2's
    # "rank-stability tricks"); ≥4 chains stabilize the judged top-k.
    n_chains: int = 1
    # Sharded engine only: count synchronizations per sweep. 1 = psum at
    # sweep end (the reference's MPI cadence). Each extra sync halves
    # the cross-shard count staleness (which costs singleton-heavy
    # vocabularies like DNS ~0.01-0.02 of judged overlap at dp=8) for
    # one more K x Vc collective per sweep — cheap on ICI.
    sync_splits: int = 1
    # Gibbs fit superstep: sweeps chained inside ONE jitted program per
    # dispatch (one dispatch and one host sync per superstep, where a
    # sweep-at-a-time loop pays one per sweep plus separate likelihood
    # programs; the price of a dispatch on the chip is not measured).
    # The burn-in accumulate fold and the boundary log-likelihood run
    # on device inside the superstep; results are bit-identical to the
    # sweep-at-a-time loop for every superstep size (tested). 0 = auto
    # (lda_gibbs.SUPERSTEP_DEFAULT = 10, the old loop's ll cadence when
    # checkpointing is off). ll_history entries land at SEGMENT ends,
    # and segments also break at checkpoint boundaries — with
    # checkpointing on, entries land every min(superstep,
    # checkpoint_every)-ish sweeps: denser than the cap, never sparser.
    # Part of the checkpoint fingerprint: resuming under a different
    # superstep is refused, not silently different.
    superstep: int = 0
    # Gibbs sampler form: "dense" keeps the O(K)-per-token block
    # sampler; "sparse" engages the r11
    # O(K_active) arm — per-document top-A active-topic sets compacted
    # into a static pow2 block, the dense-phi remainder proposed from
    # stale F+-tree-style CDF tables rebuilt each sweep, corrected by
    # Metropolis–Hastings acceptance so the stationary distribution of
    # the blocked chain is exact (lda_gibbs.select_sampler_form /
    # make_sparse_sweep). "auto" defers to the measured per-backend
    # _SAMPLER_SPARSE_MIN_K crossover tables (empty entries keep dense,
    # so defaults are unchanged until a platform is measured);
    # ONIX_SAMPLER_FORM overrides for experiments. The sparse arm is a
    # different MCMC chain (same stationary
    # distribution, different draws), so the RESOLVED form is part of
    # the checkpoint fingerprint: a resume across an arm change is
    # refused, never silently different.
    sampler_form: str = "auto"
    # Static width A of the sparse arm's per-doc active-topic block
    # (topics beyond the stale top-A stay reachable through the
    # dense-phi proposal branch; MH keeps the chain exact either way).
    # 0 = auto: the smallest pow2 >= max(8, K/16), capped at K —
    # occupancy-driven, so cost tracks topics touched as K grows.
    sparse_active: int = 0
    # Metropolis–Hastings proposals per token per sweep for the sparse
    # arm (LightLDA-style cycle length). More proposals mix faster per
    # sweep at linearly more per-token cost.
    sparse_mh: int = 2
    # Sharded-engine count-merge form (r14; ROADMAP item 5's AD-LDA
    # extension, arxiv 0909.4603). "sync" keeps the synchronous psum
    # fold: every merge window (sync group) ends in a full-barrier
    # collective whose result gates the next window's sampling — the
    # reference's MPI_Reduce+Bcast cadence. "async" is the bounded-
    # staleness exchange: each shard sweeps against a count view that
    # carries its OWN updates fresh and its peers' deltas up to
    # merge_staleness merge windows late (Streaming Gibbs Sampling for
    # LDA, arxiv 1601.01142, gives the quality argument for sweeping on
    # bounded-stale counts), so the collective at window t no longer
    # gates the sampling of window t+1..t+τ and XLA can overlap it with
    # compute instead of stalling the pipeline. All pending deltas
    # flush at every fused-superstep boundary, so superstep-boundary
    # counts (checkpoints, the boundary ll, the accumulators) are
    # EXACT global counts in both forms. τ=0 degenerates to a path
    # bit-identical to the synchronous fold (tested); τ>0 is a
    # different chain with the same stationary target, held to the
    # LL_PARITY_BAND + winner-parity contract. The RESOLVED merge form
    # joins both engines' checkpoint fingerprints: a resume across a
    # merge-form/τ change is refused, and sync contributes nothing so
    # pre-r14 checkpoints keep resuming.
    merge_form: str = "sync"
    # Merge windows a peer delta may lag in the async arm (τ). A delta
    # produced at merge window t folds in at window t+τ — never later
    # (ring FIFO, sharded_gibbs.ring_push) — or at the superstep
    # flush, whichever comes first. Ignored under merge_form="sync".
    merge_staleness: int = 1
    # Streaming local-update family: "svi" (Hoffman's uncollapsed
    # variational E-step — the default, unchanged) or "scvb0" (the
    # SCVB0 collapsed zeroth-order minibatch arm, arxiv 1305.2452 —
    # no digammas, linear-space count responsibilities) riding the
    # same superstep + union gamma store machinery. A different
    # estimator: winner-set-parity discipline, part of the streaming
    # checkpoint fingerprint.
    stream_estep: str = "svi"

    def validate(self) -> None:
        if self.n_topics < 2:
            raise ValueError(f"n_topics must be >=2, got {self.n_topics}")
        if self.alpha <= 0 or self.eta <= 0:
            raise ValueError("alpha and eta must be positive")
        if self.block_size < 1:
            raise ValueError("block_size must be >=1")
        if not (0.5 < self.svi_kappa <= 1.0):
            raise ValueError("svi_kappa must be in (0.5, 1] for convergence")
        if self.svi_max_epochs < 1:
            raise ValueError("svi_max_epochs must be >= 1")
        if self.svi_epoch_tol < 0:
            raise ValueError("svi_epoch_tol must be >= 0")
        if self.svi_meanchange_tol < 0:
            raise ValueError("svi_meanchange_tol must be >= 0")
        if self.svi_warm_iters < -1:
            raise ValueError("svi_warm_iters must be >= -1 (-1 = auto)")
        if self.checkpoint_every < 0:
            raise ValueError("checkpoint_every must be >= 0")
        if self.n_chains < 1:
            raise ValueError("n_chains must be >= 1")
        if self.sync_splits < 1:
            raise ValueError("sync_splits must be >= 1")
        if self.superstep < 0:
            raise ValueError("superstep must be >= 0 (0 = auto)")
        if self.sampler_form not in ("auto", "dense", "sparse"):
            raise ValueError(
                "lda.sampler_form must be auto|dense|sparse, "
                f"got {self.sampler_form!r}")
        if self.sparse_active < 0:
            raise ValueError("sparse_active must be >= 0 (0 = auto)")
        if self.sparse_mh < 1:
            raise ValueError("sparse_mh must be >= 1")
        if self.stream_estep not in ("svi", "scvb0"):
            raise ValueError(
                "lda.stream_estep must be svi|scvb0, "
                f"got {self.stream_estep!r}")
        if self.merge_form not in ("sync", "async"):
            raise ValueError(
                f"lda.merge_form must be sync|async, got {self.merge_form!r}")
        if self.merge_staleness < 0:
            raise ValueError("lda.merge_staleness must be >= 0")


@dataclass
class MeshConfig:
    """Device-mesh layout for multi-chip runs.

    The reference parallelizes with MPI ranks over a machinefile of NODES
    (SURVEY.md §2.3). onix uses a jax.sharding.Mesh with a data axis ("dp",
    documents/tokens sharded) and a model axis ("mp", vocabulary sharded
    when K×V outgrows one chip's HBM — SURVEY.md §5.7).
    """

    dp: int = 1                 # data-parallel axis size (documents/tokens)
    mp: int = 1                 # model-parallel axis size (vocabulary shards)
    # Multi-host runtime (SURVEY.md §2.3 — replaces mpiexec+machinefile).
    # On a TPU pod leave these empty: jax.distributed.initialize
    # auto-detects the coordinator from the TPU metadata. Off-pod (CPU
    # tests, GPU clusters) set all three; the sharded engine then calls
    # multihost_init() before building the mesh.
    coordinator: str = ""       # host:port of process 0; "" = auto/single
    num_processes: int = 0      # 0 = auto (single host unless on a pod)
    process_id: int = -1        # -1 = auto

    def validate(self) -> None:
        if self.dp < 1 or self.mp < 1:
            raise ValueError("mesh axis sizes must be >=1")
        manual = (bool(self.coordinator), self.num_processes > 0,
                  self.process_id >= 0)
        if any(manual) and not all(manual):
            raise ValueError(
                "mesh.coordinator, mesh.num_processes, and mesh.process_id "
                "must be set together for an explicit multi-host launch")

    @property
    def n_devices(self) -> int:
        return self.dp * self.mp


@dataclass
class PipelineConfig:
    """One scoring run: a day of one datatype.

    Mirrors `ml_ops.sh <YYYYMMDD> <flow|dns|proxy> [TOL] [MAXRESULTS]`
    (SURVEY.md §3.1) plus the feedback DUPFACTOR of the OA noise-filter
    loop (reference README.md:48).
    """

    datatype: str = "flow"
    date: str = "2016-07-08"
    tol: float = 1.1            # score threshold: events with score < tol survive
    max_results: int = 2000     # top-N ascending by score emitted for OA
    dupfactor: int = 1000       # analyst-labeled rows duplicated x this in corpus
    stream_max_docs: int = 0    # streaming doc-state bound (0 = unbounded):
    #                             LRU-evict idle IPs past this population
    # Streaming supersteps: chain this many minibatch updates (E-step +
    # λ-step + incremental scoring) inside ONE jitted program per
    # dispatch, winners fetched once per superstep (streaming.py
    # process_many; the SVI analog of lda.superstep). 0/1 = the
    # per-batch path. Eviction and checkpointing move to superstep
    # boundaries (the doc bound gains up to S batches of slack).
    stream_superstep: int = 0
    # Host ingest pipeline ahead of the device step: how many batches
    # the ColumnPrefetcher decodes + converts ahead (bounded, in-order
    # handoff), and where that work runs — "thread" | "process" |
    # "auto" (auto measures the first batch's conversion wall against
    # its pickle round-trip cost and picks; process sidesteps the GIL
    # the pandas/string conversion holds).
    stream_prefetch_depth: int = 2
    stream_prefetch_mode: str = "auto"
    # Cap on the streaming pad-shape lattice: once this many distinct
    # (pad_to, pad_docs) pairs have compiled, new batches re-pad into a
    # covering existing shape (or grow one ceiling shape) instead of
    # silently compiling another program (streaming.py _pick_pad).
    stream_max_shapes: int = 8
    columnar: str = "auto"      # day-read mode for `onix score`: "on" always
    #                             reads the store part-by-part into numeric
    #                             columns (the 10^8+-row path), "off" keeps
    #                             the pandas/string reference path, "auto"
    #                             switches on COLUMNAR_AUTO_MIN_ROWS

    def validate(self) -> None:
        if self.datatype not in DATATYPES:
            raise ValueError(f"datatype must be one of {DATATYPES}")
        if self.max_results < 1:
            raise ValueError("max_results must be >=1")
        if self.columnar not in ("auto", "on", "off"):
            raise ValueError("pipeline.columnar must be auto|on|off")
        if self.dupfactor < 1:
            raise ValueError("dupfactor must be >=1")
        if self.stream_max_docs < 0:
            raise ValueError("stream_max_docs must be >=0")
        if self.stream_superstep < 0:
            raise ValueError("stream_superstep must be >= 0 (0 = off)")
        if self.stream_prefetch_depth < 1:
            raise ValueError("stream_prefetch_depth must be >= 1")
        if self.stream_prefetch_mode not in ("auto", "thread", "process"):
            raise ValueError(
                "pipeline.stream_prefetch_mode must be auto|thread|process, "
                f"got {self.stream_prefetch_mode!r}")
        if self.stream_max_shapes < 1:
            raise ValueError("stream_max_shapes must be >= 1")


@dataclass
class IngestConfig:
    """Telemetry decoding options (SURVEY.md §2.1 #1-#2).

    apply_sampling scales flow packet/byte counters by the announcing
    exporter's sampling interval (NetFlow v9 / IPFIX options records:
    field 34 or the sampler-table IEs 50/305; per source/domain id,
    with a pre-scan so flows ahead of a mid-file announcement scale
    too) — nfdump-style counter scaling for sampled exporters. Off by
    default: raw wire counters are the honest record of what was
    exported."""

    apply_sampling: bool = False


@dataclass
class StoreConfig:
    """Storage substrate: partitioned Parquet in place of HDFS+Hive.

    The reference stores telemetry in Hive tables flow/dns/proxy
    partitioned by y/m/d(/h) (SURVEY.md §2.1 #3). onix keeps the same
    logical layout as Parquet datasets under `root`.
    """

    # Empty sub-dirs mean "derive from root" (<root>/<name>) at
    # validate() time, so one --set store.root=... override relocates
    # the whole store (OA output included, see OAConfig).
    root: str = "data/onix"
    feedback_dir: str = ""
    results_dir: str = ""
    checkpoint_dir: str = ""
    # Hourly sub-partitions (y=/m=/d=/h=HH) on ingest — the reference's
    # /h Hive level. Readers fold hour parts into day scans either way.
    partition_hours: bool = False


@dataclass
class ServingConfig:
    """Model-bank serving (r12, `onix/serving/`): many tenants'
    (θ, φ) tables resident on device as stacked bank arrays, scored
    through one batched program per request batch. Consumed by the
    `/score` endpoint on `onix serve` and by the load harness."""

    # Empty means "derive from store.root" (<root>/models) at
    # validate() time — where run_scoring persists fitted models
    # (save_fitted) and where the serve layer's bank loads from.
    models_dir: str = ""
    # Resident tenants per shape class (tenants bucket by pow2-padded
    # (D_pad, V_pad, K)). Banks larger than this LRU-evict at request
    # batch boundaries; winners stay identical (model_bank.py).
    bank_capacity: int = 64
    # Batched scoring form: "vmap" | "gather" | "auto" (the measured
    # per-backend crossover table model_bank._BANK_GATHER_MIN_EVENTS;
    # ONIX_BANK_FORM overrides for experiments). Bit-identical forms —
    # pure performance.
    bank_form: str = "auto"
    # Serving-scan form: "xla" keeps the three-stage XLA path (batched
    # gather/matmul scoring, feedback membership search, chunked
    # bottom-M scan); "fused" engages the r15 one-kernel Pallas serving
    # path (onix/models/pallas_serve.py — score + filter membership +
    # bottom-M in one kernel, winners flushed once per request).
    # "auto" defers to the measured per-backend crossover table
    # (pallas_serve._SERVE_FUSED_MIN_EVENTS — deliberately EMPTY for
    # every backend, tpu included: the crossover is not measured on
    # the chip, so auto resolves to xla everywhere today);
    # ONIX_SERVE_FORM overrides for experiments. Both arms are
    # bit-identical (winners, scores, tie order) — pure performance.
    serve_form: str = "auto"
    # Requests per batched dispatch at the service layer; the bank
    # further splits a batch that exceeds bank_capacity distinct
    # tenants in one shape class.
    max_batch_requests: int = 64
    # Per-(tenant, window) winner cache entries kept by the service.
    winner_cache_size: int = 4096
    # run_scoring persists the fitted (θ, φ) under models_dir as
    # <datatype>/<yyyymmdd> so `onix serve` can score against it.
    save_fitted: bool = False
    # Loader-backed models kept in the HOST registry (0 = unbounded).
    # Device residency is bank_capacity; this bounds host RAM on a
    # long-lived server walking many (datatype, day, tenant) models —
    # past it the LRU re-fetchable, non-resident host copy is dropped
    # (bank.host_evict) and reloads from models_dir on next reference.
    host_model_cache: int = 1024
    # Admission control (r16, docs/ROBUSTNESS.md "serving resilience"):
    # request batches in flight + queued at the service before new ones
    # are SHED with 503 + Retry-After (`serve.shed`). 0 disables
    # shedding (unbounded queue — the pre-r16 behavior). Shed requests
    # never touch bank residency or winner caches.
    max_queue_depth: int = 64
    # Per-request wall-clock budget in milliseconds, measured from
    # request receipt THROUGH the admission queue: a request whose
    # budget expires before scoring starts is refused 503 + Retry-After
    # (`serve.deadline_expired`) instead of burning device time on an
    # answer the client has given up on. 0 disables the deadline. Once
    # scoring starts the request runs to completion — partial winner
    # sets are never served.
    request_deadline_ms: float = 0.0
    # Degradation ladder: a "fused" (r15 Pallas) serve-form dispatch
    # that fails falls back to the bit-identical xla form, counted
    # (`serve.form_fallback`) and stamped `degraded: true` on the
    # response. Off = the failure propagates (debugging the kernel).
    degrade_form_fallback: bool = True
    # Mesh placement (r20): "single" keeps every tenant's bank on one
    # device (the pre-r20 shape); "sharded" spreads shape-class banks
    # over the visible device mesh by tenant hash — per-device waves,
    # no cross-device collective, winners bit-identical. "auto"
    # defers to the measured per-backend crossover table
    # (model_bank._BANK_SHARD_MIN_TENANTS — deliberately EMPTY: not
    # measured on the chip, so auto resolves single everywhere
    # today); ONIX_BANK_SHARD
    # overrides for experiments.
    bank_shard: str = "auto"
    # Host-RAM tier prefetch budget (r20): tenants promoted from disk
    # into the host registry per request-batch boundary, ranked by the
    # bank's decayed Zipf demand estimate. 0 disables prefetch (misses
    # load on demand — the pre-r20 shape).
    prefetch_depth: int = 0
    # Serve replicas behind one front (r20, onix/serving/replicas.py):
    # N independent BankService replicas, tenant-hash routed, with the
    # epoch bulletin guaranteeing an out-of-band bump (feedback, daily
    # refit) reaches a tenant's serving replica before its next score.
    # 1 = a bare BankService (the pre-r20 shape).
    replicas: int = 1

    def validate(self) -> None:
        if self.bank_capacity < 1:
            raise ValueError("serving.bank_capacity must be >= 1")
        if self.host_model_cache < 0:
            raise ValueError("serving.host_model_cache must be >= 0")
        if self.max_queue_depth < 0:
            raise ValueError("serving.max_queue_depth must be >= 0 "
                             "(0 = unbounded)")
        if self.request_deadline_ms < 0:
            raise ValueError("serving.request_deadline_ms must be >= 0 "
                             "(0 = no deadline)")
        if self.bank_form not in ("auto", "vmap", "gather"):
            raise ValueError(
                "serving.bank_form must be auto|vmap|gather, "
                f"got {self.bank_form!r}")
        if self.serve_form not in ("auto", "xla", "fused"):
            raise ValueError(
                "serving.serve_form must be auto|xla|fused, "
                f"got {self.serve_form!r}")
        if self.max_batch_requests < 1:
            raise ValueError("serving.max_batch_requests must be >= 1")
        if self.winner_cache_size < 0:
            raise ValueError("serving.winner_cache_size must be >= 0")
        if self.bank_shard not in ("auto", "single", "sharded"):
            raise ValueError(
                "serving.bank_shard must be auto|single|sharded, "
                f"got {self.bank_shard!r}")
        if self.prefetch_depth < 0:
            raise ValueError("serving.prefetch_depth must be >= 0 "
                             "(0 = off)")
        if self.replicas < 1:
            raise ValueError("serving.replicas must be >= 1")


@dataclass
class FeedbackConfig:
    """The analyst feedback loop (r13, `onix/feedback/`): how captured
    verdicts turn into model behavior on two timescales — the immediate
    noise-filter rescoring (suppress/boost applied inside the scoring
    scans and the model bank) and the incremental online λ/φ update
    that rides the SVI machinery on feedback-weighted minibatches
    (PAPER.md §L5's noise filter + the Streaming-Gibbs/SCVB0 update
    family, arxiv 1601.01142 / 1305.2452)."""

    # Immediate rescoring on/off: the DEFAULT install gate — when
    # False, apply_feedback and the serve-side compile install no
    # filter unless the caller explicitly overrides (the
    # online-update-only configuration the replay harness's ≤5-batch
    # arm measures). An installed filter is always applied.
    filter_enabled: bool = True
    # Score multiplier for BOOSTED (analyst-confirmed threat) events in
    # the filtered scans: < 1 pushes a confirmed event further down the
    # ascending-suspicious order so it keeps surfacing. 1.0 disables
    # boosting while keeping suppression.
    boost_scale: float = 0.25
    # Token weight of a DISMISSED (benign) row in the online-update
    # minibatch — the streaming analog of the reference's ×DUPFACTOR
    # corpus duplication: weight-w feedback tokens update λ exactly as
    # w identical observed tokens would, raising p(word|doc) until the
    # dismissed traffic stops scoring suspicious. 0 disables the online
    # update (immediate filter only).
    dismiss_weight: float = 1000.0
    # Token weight of a CONFIRMED (threat) row in the online-update
    # minibatch. Default 0: confirmations must NOT add mass (that would
    # teach the model the attack pattern is common — the exact failure
    # load_feedback guards against); they act through the boost filter.
    confirm_weight: float = 0.0
    # SVI steps per feedback application (each step replays the
    # feedback-weighted minibatch once through svi_step).
    online_steps: int = 1
    # λ pseudo-count strength when nudging a fitted batch (θ, φ) model
    # (OnlineUpdater): λ0 = eta + prior_strength·φ, so the nudge moves
    # a posterior with this much prior mass, not a fresh model.
    prior_strength: float = 10000.0
    # θ pseudo-count strength for the nudged model's document rows:
    # new θ_d ∝ theta_strength·θ_d + (γ_d − α) after the weighted
    # E-step.
    theta_strength: float = 100.0

    def validate(self) -> None:
        if not (0.0 < self.boost_scale <= 1.0):
            raise ValueError("feedback.boost_scale must be in (0, 1]")
        if self.dismiss_weight < 0 or self.confirm_weight < 0:
            raise ValueError("feedback weights must be >= 0")
        if self.online_steps < 1:
            raise ValueError("feedback.online_steps must be >= 1")
        if self.prior_strength <= 0 or self.theta_strength <= 0:
            raise ValueError("feedback strengths must be > 0")


@dataclass
class TelemetryConfig:
    """The r18 telemetry layer (`onix/utils/telemetry.py`; operator
    page docs/OBSERVABILITY.md): request-scoped spans, log-bucketed
    latency histograms, the `/metrics` Prometheus exposition on
    `onix serve`, and the chaos flight recorder. Host-side only by
    construction — no knob here can change a device program, and
    `enabled=false` / `sample=0` is asserted winner-bit-identical with
    unchanged dispatch counts in tier-1 (tests/test_telemetry.py)."""

    # Master switch: off = no spans recorded, no flight-ring events,
    # no histogram observations, no recorder dumps. ONIX_TELEMETRY=0
    # is the env kill-switch for drills.
    enabled: bool = True
    # Trace sampling probability in [0, 1], decided once per trace id
    # (crc32 hash — deterministic, so a request's spans are all kept
    # or all dropped). 1.0 records every request; production fleets
    # drop this before they drop `enabled`.
    sample: float = 1.0
    # Flight-recorder ring capacity (recent span-close / counter-delta
    # / fault events kept for the postmortem dump).
    recorder_events: int = 1024
    # Where flight-recorder dumps land. Empty = derive
    # <store.root>/telemetry at validate() time. The recorder only
    # writes when a dir is routed (this, or ONIX_TELEMETRY_DIR for
    # processes that never applied a config) — unrouted dumps are
    # counted, never scattered into cwd.
    recorder_dir: str = ""

    def validate(self) -> None:
        if not 0.0 <= self.sample <= 1.0:
            raise ValueError("telemetry.sample must be in [0, 1], "
                             f"got {self.sample!r}")
        if self.recorder_events < 16:
            raise ValueError("telemetry.recorder_events must be >= 16")


@dataclass
class DailyConfig:
    """The r19 continuous-operation supervisor (`onix/pipelines/daily.py`;
    docs/ROBUSTNESS.md "continuous operation"): how a multi-day chain of
    campaign runs warm-starts, drift-gates, and rolls back. Production
    runs the pipeline EVERY day — these knobs govern the day-over-day
    lifecycle, not any single day's fit."""

    # Drift gate: max per-topic total-variation distance between
    # today's warm-fitted φ̂ and yesterday's φ̂ over the shared
    # vocabulary (columns renormalized over the matched rows). A warm
    # refit whose drift exceeds this is DISCARDED and the day re-fits
    # cold (counted `daily.drift_cold_refits`) — the bounded-staleness
    # quality posture of arxiv 0909.4603 applied across days: a warm
    # chain may coast on yesterday's posterior only while it provably
    # stays near it. 0 disables the gate (warm fits always accepted).
    drift_max: float = 0.5
    # Sweep budget for a warm-started fit (φ̂-as-prior z-init, the
    # Streaming Gibbs treatment of arxiv 1601.01142). 0 = auto: half
    # the cold budget, floor 2 — the chain starts near the posterior,
    # so the wall the daily loop pays is roughly halved (on a CPU:
    # docs/DAILY_r19_cpu.json).
    warm_sweeps: int = 0
    # Burn-in for a warm-started fit. 0 = auto: 1 sweep — the warm
    # chain needs settling, not re-convergence, so posterior averaging
    # starts almost immediately.
    warm_burn_in: int = 0
    # Per-day synthetic-feed seed offset: day d draws with
    # seed + stride*(d-1). 0 = a stationary week (identical background
    # every day — the dismissal-recurrence harness arm); 1 = fresh
    # traffic daily.
    day_seed_stride: int = 1
    # Durable spelling of the ONIX_DAILY_FORCE_COLD drill: never warm-
    # start, fit every day cold (the control arm of exp_daily.py).
    force_cold: bool = False

    def validate(self) -> None:
        if not 0.0 <= self.drift_max <= 1.0:
            raise ValueError("daily.drift_max must be in [0, 1] "
                             "(per-topic total variation), "
                             f"got {self.drift_max!r}")
        if self.warm_sweeps < 0:
            raise ValueError("daily.warm_sweeps must be >= 0 (0 = auto)")
        if self.warm_burn_in < 0:
            raise ValueError("daily.warm_burn_in must be >= 0 (0 = auto)")
        if self.warm_sweeps and self.warm_burn_in >= self.warm_sweeps:
            raise ValueError("daily.warm_burn_in must be < warm_sweeps")
        if self.day_seed_stride < 0:
            raise ValueError("daily.day_seed_stride must be >= 0")


@dataclass
class OAConfig:
    """Operational Analytics (SURVEY.md §2.1 #12-#13): enrichment inputs
    and the per-date UI data directory the dashboards read."""

    # Empty means "derive from store.root" (<root>/oa) at validate()
    # time, so one --set store.root=... override relocates the whole
    # store, OA outputs included.
    data_dir: str = ""
    # Per-cell wall deadline for the in-dashboard notebook kernels; a
    # cell past it is killed (the analyst restarts the session).
    kernel_cell_timeout_s: float = 120.0
    geoip_db: str = ""          # CSV: network,country,city,latitude,longitude,isp
    reputation: str = ""        # plugin specs, comma-separated: local:<path>|noop
    top_domains: str = ""       # popular-domains list file (rank order)


@dataclass
class OnixConfig:
    lda: LDAConfig = field(default_factory=LDAConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)
    pipeline: PipelineConfig = field(default_factory=PipelineConfig)
    ingest: IngestConfig = field(default_factory=IngestConfig)
    store: StoreConfig = field(default_factory=StoreConfig)
    oa: OAConfig = field(default_factory=OAConfig)
    serving: ServingConfig = field(default_factory=ServingConfig)
    feedback: FeedbackConfig = field(default_factory=FeedbackConfig)
    telemetry: TelemetryConfig = field(default_factory=TelemetryConfig)
    daily: DailyConfig = field(default_factory=DailyConfig)

    def validate(self) -> "OnixConfig":
        self.lda.validate()
        self.mesh.validate()
        self.pipeline.validate()
        self.serving.validate()
        self.feedback.validate()
        self.telemetry.validate()
        self.daily.validate()
        root = pathlib.Path(self.store.root)
        for attr, sub in (("feedback_dir", "feedback"),
                          ("results_dir", "results"),
                          ("checkpoint_dir", "checkpoints")):
            if not getattr(self.store, attr):
                setattr(self.store, attr, str(root / sub))
        if not self.oa.data_dir:
            self.oa.data_dir = str(root / "oa")
        if not self.serving.models_dir:
            self.serving.models_dir = str(root / "models")
        if not self.telemetry.recorder_dir:
            self.telemetry.recorder_dir = str(root / "telemetry")
        return self

    # -- serialization ----------------------------------------------------

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), indent=2, sort_keys=True)

    @property
    def config_hash(self) -> str:
        """Stable hash identifying a resolved config (run manifests, §5.5)."""
        return hashlib.sha256(self.to_json().encode()).hexdigest()[:16]

    def archive(self, path: str | pathlib.Path) -> None:
        """Write the resolved config next to the run outputs."""
        p = pathlib.Path(path)
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(self.to_json())


def _coerce(value: Any, target: type) -> Any:
    """Coerce a raw (possibly string, from a CLI override) value to the
    field's declared type — `pipeline.date=20160708` must stay a string."""
    if target is str:
        return str(value)
    if isinstance(value, str):
        if target is bool:
            if value.lower() in ("true", "false"):
                return value.lower() == "true"
            raise ValueError(f"expected bool, got {value!r}")
        if target in (int, float):
            return target(value)
    if target is float and isinstance(value, int):
        return float(value)
    if not isinstance(value, target):
        raise TypeError(f"expected {target.__name__}, got {type(value).__name__}")
    return value


def _build(cls, data: dict[str, Any]):
    """Recursively build a dataclass from a dict, rejecting unknown keys."""
    import typing
    fields = {f.name: f for f in dataclasses.fields(cls)}
    unknown = set(data) - set(fields)
    if unknown:
        raise KeyError(f"unknown config keys for {cls.__name__}: {sorted(unknown)}")
    hints = typing.get_type_hints(cls)
    kwargs = {}
    for name, value in data.items():
        sub = _NESTED.get((cls, name))
        if sub is not None:
            kwargs[name] = _build(sub, value or {})
        else:
            kwargs[name] = _coerce(value, hints[name])
    return cls(**kwargs)


_NESTED = {
    (OnixConfig, "lda"): LDAConfig,
    (OnixConfig, "mesh"): MeshConfig,
    (OnixConfig, "pipeline"): PipelineConfig,
    (OnixConfig, "ingest"): IngestConfig,
    (OnixConfig, "store"): StoreConfig,
    (OnixConfig, "oa"): OAConfig,
    (OnixConfig, "serving"): ServingConfig,
    (OnixConfig, "feedback"): FeedbackConfig,
    (OnixConfig, "telemetry"): TelemetryConfig,
    (OnixConfig, "daily"): DailyConfig,
}


def from_dict(data: dict[str, Any]) -> OnixConfig:
    return _build(OnixConfig, data).validate()


def load_config(path: str | pathlib.Path | None = None,
                overrides: list[str] | None = None) -> OnixConfig:
    """Load config from a YAML/JSON file with `a.b.c=value` CLI overrides."""
    data: dict[str, Any] = {}
    if path is not None:
        text = pathlib.Path(path).read_text()
        if str(path).endswith((".yaml", ".yml")):
            import yaml
            data = yaml.safe_load(text) or {}
        else:
            data = json.loads(text)
    for ov in overrides or []:
        if "=" not in ov:
            raise ValueError(f"override must be key.path=value, got {ov!r}")
        key, _, raw = ov.partition("=")
        node = data
        parts = key.split(".")
        for part in parts[:-1]:
            nxt = node.get(part)
            if not isinstance(nxt, dict):   # missing, or a bare YAML null
                nxt = {}
                node[part] = nxt
            node = nxt
        # Raw string; _coerce converts it against the field's declared type.
        node[parts[-1]] = raw
    return from_dict(data)
