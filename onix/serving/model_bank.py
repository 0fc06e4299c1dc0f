"""Device-resident model bank: one batched program scores N tenants.

The single-tenant scoring path (`onix/models/scoring.py`, `oa/serve.py`)
costs N separate dispatches, N H2D transfers, and N compiled-program
round-trips for N tenants — fatal at "millions of users" where the
model axis is per-datatype × per-day × per-tenant. The bank makes the
per-model axis a batched ARRAY dimension instead of a host-side loop
(the AD-LDA decomposition argument, arxiv 0909.4603, applied to
serving): tenants' (θ, φ) tables are stacked/padded into bank-shaped
device arrays

    theta_bank [B, D_pad, K]      phi_bank [B, V_pad, K]

grouped by a pow2 pad ladder (`onix/models/compaction.pow2_bucket`) so
tenants of similar size share one compiled shape class, and a
mixed-tenant request batch is scored by ONE jitted program: a
tenant-slot gather feeding the exact chunked bottom-M machinery of
`scoring._scan_bottom_k`, so per-tenant winners are bit-identical to
the single-tenant `top_suspicious` path (asserted in
tests/test_model_bank.py).

Two batched forms, gated by shape and backend:

* ``vmap``   — `jax.vmap` over the request axis; each lane slices its
  tenant's tables out of the bank (`theta_bank[slot]`) and runs the
  shared scan. The bank axis rides XLA's batched gather.
* ``gather`` — the bank flattens to [(B·D_pad), K] and every EVENT
  gathers through a flat tenant-composed index `slot·D_pad + d`; one
  fused stream scores all requests, then the same bottom-M machinery
  selects per request row. No per-request table slice ever
  materializes.

Both forms compute `score_events`' exact gather-dot, so winners are
bit-identical between forms AND against the single-tenant scan; the
choice is pure performance. `_BANK_GATHER_MIN_EVENTS` is the measured
per-backend crossover (events per dispatch), `ONIX_BANK_FORM` pins a
form for experiments, and unmeasured backends keep the vmap default
(docs/BANK_r12_cpu.json; not measured on the chip).

Residency: each shape class holds a fixed number of resident slots
(`capacity`). Admission stages ALL newly-needed tenants of a request
batch host-side and ships ONE `device_put` per table family (not
per-tenant round-trips); eviction is LRU and happens ONLY at request
batch boundaries — a tenant's tables can never change mid-scan, so a
capped bank's winners are identical to an uncapped run (tested, and
proven at harness scale in scripts/exp_model_bank.py). Admits, evicts,
hits, H2D bytes/transfers, and dispatches are all counted in
`onix.utils.obs.counters` under ``bank.*``.

Sharding (r20): the bank optionally spreads its shape-class banks over
a dp device mesh by TENANT HASH — each tenant's tables live wholly on
its stable home device (crc32 placement), a mixed-tenant batch splits
into per-device waves, and each wave dispatches as an INDEPENDENT
device program. No array is ever partitioned across devices, so the
compiled scoring HLO is psum-free BY CONSTRUCTION (asserted: the first
compile of every sharded shape is scanned for collective ops), and
per-tenant winners are bit-identical to the single-device bank — the
same `_scan_bottom_k` runs over the same per-tenant tables, only the
device it runs on changes (the AD-LDA locality argument, arxiv
0909.4603, applied one level up: placement, not decomposition).
`select_shard_form` gates single vs sharded through the shared
`resolve_form_gate` chain; `_BANK_SHARD_MIN_TENANTS` starts EMPTY per
the r15 discipline, so auto resolves single-device everywhere (the
crossover is not measured on the chip).

Residency tiers (r20): three explicit tiers — HBM (shard slots), host
RAM (`_models`, bounded by `host_capacity`), disk (`bulk_loader` →
`checkpoint.load_models`). A demand-tracked PREFETCHER sits between
disk and the host tier: per-tenant request counts decay into a Zipf
demand estimate, and at request-batch boundaries the hottest
not-host-resident tenants are promoted in one bulk pass
(`bank.prefetch_*` counters; chaos site `bank:prefetch` fires at
entry, pre-mutation, so one bounded retry replays safely — and the
prefetch is best-effort: exhaustion never fails scoring). Device
admission is untouched: one `device_put` per table family per wave
boundary, exactly as before.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
import time
import zlib
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from onix.config import resolve_form_gate
from onix.feedback.filter import (FILTER_FLOOR, FilterTables, HostFilter,
                                  _pad_sorted, apply_filter, split_key)
from onix.models.compaction import pow2_bucket
from onix.models.scoring import TopK, _scan_bottom_k, _subscan_scores, score_events
from onix.utils import faults, telemetry
from onix.utils.obs import counters
from onix.utils.resilience import (Deadline, DeadlineExceeded, Overloaded,
                                   RetryPolicy, retry_call)

# Bounded absorb-and-replay budgets for the serve-path fault sites
# (docs/ROBUSTNESS.md "serving resilience"). Injected faults fire at
# ENTRY points — before any cache/residency/filter mutation — so one
# bounded retry replays the call safely (the stream:batch discipline);
# zero backoff because the sites are in-process, not I/O.
_SERVE_RETRY = RetryPolicy(max_attempts=2, base_backoff_s=0.0, jitter=0.0,
                           salvage_on_final=False)
# Model loads ARE I/O (models_dir may be network-backed): transient
# OSErrors get one backed-off retry, then the batch is REFUSED
# (BankRefusal) instead of wedging on a dead filesystem.
_LOAD_RETRY = RetryPolicy(max_attempts=2, base_backoff_s=0.05,
                          max_backoff_s=1.0, jitter=0.0,
                          salvage_on_final=False)

# Pad floors for the bank shape ladder: smallest [D_pad]/[V_pad] a
# tenant occupies. Low floors would mint a compiled shape class per
# tiny tenant; high floors waste bank HBM on padding. 256 keeps the
# ladder at most log2(D_max/256) classes deep.
BANK_DOC_FLOOR = 256
BANK_VOCAB_FLOOR = 256
# Pow2 floor for the per-request event axis (requests pad up to the
# smallest covering pow2 so the jit cache stays bounded).
BANK_EVENTS_FLOOR = 64

# Measured crossover: total (padded) events per dispatch above which
# the flat tenant-gather form beats the vmap form. Keyed by backend
# like lda_gibbs._NWK_MATMUL_MIN_DENSITY; an ABSENT backend keeps the
# vmap default (never an unmeasured guess). cpu: 0 — the gather form
# won at EVERY dispatch size measured on this host (1.5k..512k events
# per dispatch, bank sizes 4..64: 1.7-6x over vmap; the vmap lanes
# batch-gather whole [D_pad, K] table slices where the flat form
# gathers exactly the 2K-float rows each event touches —
# docs/BANK_r12_cpu.json `bank_size_ladder`). tpu: ABSENT (not
# measured on the chip) — the vmap default rides XLA's batched gather
# there, and the CPU result must not be assumed to transfer.
_BANK_GATHER_MIN_EVENTS = {
    "cpu": 0,
}


def select_bank_form(form: str, n_requests: int, n_pad: int,
                     backend: str | None = None) -> str:
    """Resolve the batched scoring form for one dispatch.

    Priority (config.resolve_form_gate — the ONE precedence chain
    shared with `select_nwk_form` and `pallas_serve.select_serve_form`
    so the three gate tables cannot drift): ONIX_BANK_FORM env
    override > explicit config form > the measured
    `_BANK_GATHER_MIN_EVENTS` table for this backend > vmap. The forms
    are bit-identical, so this is pure performance and safe to flip
    between dispatches."""
    def measured() -> str | None:
        b = backend if backend is not None else jax.default_backend()
        min_events = _BANK_GATHER_MIN_EVENTS.get(b)
        if min_events is not None and n_requests * n_pad >= min_events:
            return "gather"
        return None

    return resolve_form_gate(gate="bank form", choices=("vmap", "gather"),
                             explicit=form, env_var="ONIX_BANK_FORM",
                             measured=measured, default="vmap")


# Measured crossover for the r20 sharded placement: registered tenants
# above which spreading the shape-class banks over the dp mesh beats
# one device (per-device waves dispatch independently, so the win is
# parallel occupancy minus the per-device compile + admission
# duplication). Keyed by backend like `_BANK_GATHER_MIN_EVENTS`;
# DELIBERATELY EMPTY for every backend — cpu included — because it
# is not measured on the chip: a CPU host's virtual devices share
# the same cores, so a CPU
# "crossover" would be scheduler noise, never a chip decision. Auto
# therefore resolves single-device everywhere today; the forms are
# bit-identical, so pinning `sharded` (config or ONIX_BANK_SHARD) is
# always safe.
_BANK_SHARD_MIN_TENANTS: dict[str, int] = {}


def select_shard_form(form: str, n_tenants: int, n_devices: int,
                      backend: str | None = None) -> str:
    """Resolve the bank placement form: "single" (every tenant on the
    default device — the pre-r20 shape) vs "sharded" (tenant-hash
    placement over the mesh). Same precedence chain as every measured
    gate (config.resolve_form_gate): ONIX_BANK_SHARD env override >
    explicit config form > the measured `_BANK_SHARD_MIN_TENANTS`
    table > single. Resolved ONCE per bank (first score) and frozen —
    placement keys device residency, so flipping mid-life would strand
    resident tenants on devices the router no longer picks."""
    def measured() -> str | None:
        b = backend if backend is not None else jax.default_backend()
        min_tenants = _BANK_SHARD_MIN_TENANTS.get(b)
        if min_tenants is not None and n_devices >= 2 \
                and n_tenants >= min_tenants:
            return "sharded"
        return None

    return resolve_form_gate(gate="bank shard", choices=("single", "sharded"),
                             explicit=form, env_var="ONIX_BANK_SHARD",
                             measured=measured, default="single")


#: Substrings that name a cross-device collective in optimized HLO.
#: The sharded bank's psum-free-by-construction claim is machine-
#: checked against these: every per-device wave is an independent
#: single-device program, so NONE may appear in its compiled text.
_COLLECTIVE_MARKERS = ("all-reduce", "all-gather", "all-to-all",
                       "collective-permute", "reduce-scatter",
                       "collective-broadcast")


def assert_collective_free(kernel, args, *, max_results: int) -> None:
    """Compile `kernel` for `args` and assert the optimized HLO names
    no cross-device collective (`_COLLECTIVE_MARKERS`). Cheap where it
    runs: lowering hits the same jit cache the scoring call populates,
    so the text render is the only extra work — and it runs once per
    compiled shape (the caller's `collective_checked` set)."""
    txt = kernel.lower(*args, max_results=max_results).compile().as_text()
    found = [m for m in _COLLECTIVE_MARKERS if m in txt]
    if found:
        raise AssertionError(
            f"sharded bank program compiled a cross-device collective "
            f"({', '.join(found)}) — per-device waves must be "
            "independent single-device programs")


class BankRefusal(ValueError):
    """A request the bank refuses to score (unknown tenant, out-of-range
    token ids, or a single batch needing more distinct tenants than the
    residency capacity). Refusal semantics: the request is REJECTED
    before any device work — never scored against wrong or padded
    tables (docs/ROBUSTNESS.md "model bank refusals")."""


@dataclasses.dataclass(frozen=True)
class TenantModel:
    """One tenant's fitted tables, host-side (f32 [D,K] / [V,K]).
    `epoch` is the persisted model epoch (checkpoint meta
    `model_epoch`) — 0 for a fresh fit, bumped by online feedback
    updates; the bank's winner-cache invalidation keys on it."""
    theta: np.ndarray
    phi_wk: np.ndarray
    epoch: int = 0

    @property
    def n_docs(self) -> int:
        return int(self.theta.shape[0])

    @property
    def n_vocab(self) -> int:
        return int(self.phi_wk.shape[0])

    @property
    def n_topics(self) -> int:
        return int(self.theta.shape[1])


@dataclasses.dataclass
class ScoreRequest:
    """One (tenant, window) scoring request: bottom-`max_results`
    suspicious events among the request's (doc, word) tokens, exactly
    the single-tenant `top_suspicious` contract. `window` identifies an
    immutable replay window for the serve layer's winner cache; None
    disables caching for the request."""
    tenant: str
    doc_ids: np.ndarray
    word_ids: np.ndarray
    window: str | None = None


# ---------------------------------------------------------------------------
# The two batched kernels. Both end in scoring's _scan_bottom_k, so the
# merge/tie/sentinel semantics (-1 on unfilled slots, lower-index wins
# ties) are the single-tenant scan's by construction. Both apply the
# per-tenant NOISE FILTER (r13, onix/feedback/) as the same fused
# post-score adjustment before the tol screen: per request row, four
# sorted sentinel-padded key tables (word/pair × suppress/boost) plus a
# boost scale. A tenant with no feedback rides all-sentinel rows, whose
# membership mask is constant False — scores bit-identical to the
# pre-filter kernels (the filter.py exactness contract, tested).
# ---------------------------------------------------------------------------


def _row_filter_adjust(s, dc, wc, filt):
    """One request row's fused adjustment: word key = the event's word
    id, pair key = the packed (doc, word) identity the serve-layer
    feedback rows label (filter.pack_pair — here as (hi, lo) = (doc,
    word) uint32 halves, the x32-safe rendering)."""
    wl = wc.astype(jnp.uint32)
    wk = (jnp.zeros_like(wl), wl)
    pk = (dc.astype(jnp.uint32), wl)
    return apply_filter(s, wk, pk, filt)


@functools.partial(jax.jit, static_argnames=("max_results",))
def _bank_score_vmap(theta_bank, phi_bank, slots, doc_ids, word_ids, mask,
                     tol, filt_rows, *, max_results: int) -> TopK:
    """vmap form: one lane per request; the lane slices its tenant's
    tables from the bank and runs the shared chunked bottom-M scan
    (chunk = the padded row, so the scan is one merge — identical
    result to the single-tenant path at any chunking). `filt_rows` is
    a FilterTables pytree with a leading request axis on every leaf,
    or None — the static no-feedback fast path that compiles without
    any membership search (a wave with no filtered tenant must cost
    exactly what it did pre-filter)."""
    n_pad = doc_ids.shape[1]

    def make_one(filtered):
        def one(slot, dr, wr, mr, *filt):
            th = theta_bank[slot]
            ph = phi_bank[slot]

            def score_chunk(dc, wc, mc):
                s = _subscan_scores(th, ph, dc, wc)
                if filtered:
                    s = _row_filter_adjust(s, dc, wc, filt[0])
                return jnp.where((mc > 0) & (s < tol), s, jnp.inf)

            return _scan_bottom_k((dr, wr, mr), n_pad, score_chunk,
                                  max_results=max_results, chunk=n_pad)
        return one

    if filt_rows is None:
        return jax.vmap(make_one(False))(slots, doc_ids, word_ids, mask)
    return jax.vmap(make_one(True))(slots, doc_ids, word_ids, mask,
                                    filt_rows)


@functools.partial(jax.jit, static_argnames=("max_results",))
def _bank_score_gather(theta_bank, phi_bank, slots, doc_ids, word_ids, mask,
                       tol, filt_rows, *, max_results: int) -> TopK:
    """gather form: the bank flattens to [(B·D_pad), K] and every event
    gathers via the tenant-composed flat index — one fused stream, no
    per-request table slice. Selection reuses the same bottom-M scan
    per request row over the precomputed (masked, filter-adjusted)
    scores. filt_rows=None is the static no-feedback fast path."""
    b, d_pad, _ = theta_bank.shape
    v_pad = phi_bank.shape[1]
    theta_flat = theta_bank.reshape(b * d_pad, -1)
    phi_flat = phi_bank.reshape(b * v_pad, -1)
    n_pad = doc_ids.shape[1]
    gd = (slots[:, None] * jnp.int32(d_pad) + doc_ids).reshape(-1)
    gw = (slots[:, None] * jnp.int32(v_pad) + word_ids).reshape(-1)
    s = score_events(theta_flat, phi_flat, gd, gw).reshape(doc_ids.shape)
    if filt_rows is not None:
        s = jax.vmap(_row_filter_adjust)(s, doc_ids, word_ids, filt_rows)
    s = jnp.where((mask > 0) & (s < tol), s, jnp.inf)

    def sel(sr):
        return _scan_bottom_k((sr,), n_pad, lambda sc: sc,
                              max_results=max_results, chunk=n_pad)

    return jax.vmap(sel)(s)


_BANK_KERNELS = {"vmap": _bank_score_vmap, "gather": _bank_score_gather}


def _bank_kernel_for(form: str, serve: str):
    """The compiled program for one (bank form, serve form) pair. The
    "fused" serve arm swaps the scan+filter stages for the r15
    one-kernel Pallas path (onix/models/pallas_serve.py) — same
    gathers, same scores, same winners, bit-identical (tested); the
    interpret/compile decision rides pallas_serve's shared
    `_default_interpret` (Mosaic on real TPUs, XLA emulation
    elsewhere)."""
    if serve != "fused":
        return _BANK_KERNELS[form]
    from onix.models import pallas_serve
    fused = {"vmap": pallas_serve.bank_score_vmap_fused,
             "gather": pallas_serve.bank_score_gather_fused}[form]
    interpret = pallas_serve._default_interpret()
    return functools.partial(fused, interpret=interpret)


class _Shard:
    """One (shape class, home device)'s resident bank: [C, D_pad, K] /
    [C, V_pad, K] device arrays plus the tenant→slot LRU bookkeeping.
    `device` pins the arrays (sharded placement); None keeps jax's
    default device — the pre-r20 single-device shape."""

    def __init__(self, d_pad: int, v_pad: int, k: int, capacity: int,
                 device=None, device_index: int = 0):
        self.d_pad, self.v_pad, self.k = d_pad, v_pad, k
        self.capacity = capacity
        self.device = device
        self.device_index = device_index
        theta = jnp.zeros((capacity, d_pad, k), jnp.float32)
        phi = jnp.zeros((capacity, v_pad, k), jnp.float32)
        if device is not None:
            theta = jax.device_put(theta, device)
            phi = jax.device_put(phi, device)
        self.theta = theta
        self.phi = phi
        self.lru: OrderedDict[str, int] = OrderedDict()  # tenant -> slot
        self.free: list[int] = list(range(capacity - 1, -1, -1))


class ModelBank:
    """The device-resident bank: registry + residency + batched scoring.

    `capacity` is resident tenants PER SHAPE CLASS (tenants land in the
    class of their pow2-padded (D_pad, V_pad, K); same-scale tenants
    share arrays and compiled programs). `loader(tenant)` supplies
    models not in the host registry one at a time; `bulk_loader(names)`
    (the serve layer wires it to `checkpoint.load_models` over
    `serving.models_dir`) fetches a request batch's unknown tenants in
    one host-side pass before scoring. A loader miss is a
    `BankRefusal`. `host_capacity` (0 = unbounded) caps how many
    loader-backed models stay in the HOST registry: beyond it, the
    least-recently-used re-fetchable tenant that is not device-resident
    is dropped (`bank.host_evict`) — without it a long-lived server
    walking the per-datatype × per-day × per-tenant model space grows
    host RAM monotonically. Explicitly `add()`ed models are never
    host-evicted (no loader can bring them back)."""

    def __init__(self, capacity: int = 64, form: str = "auto",
                 loader=None, bulk_loader=None, host_capacity: int = 0,
                 filter_loader=None, epoch_loader=None,
                 serve_form: str = "auto",
                 degrade_form_fallback: bool = True,
                 devices=None, shard_form: str = "auto",
                 prefetch_depth: int = 0):
        if capacity < 1:
            raise ValueError("bank capacity must be >= 1")
        if host_capacity < 0:
            raise ValueError("host_capacity must be >= 0 (0 = unbounded)")
        if prefetch_depth < 0:
            raise ValueError("prefetch_depth must be >= 0 (0 = off)")
        self.capacity = capacity
        self.form = form
        # r15 serving-scan form (serving.serve_form): "xla" | "fused" |
        # "auto" (pallas_serve.select_serve_form — resolves to xla on
        # every backend until a measured crossover lands).
        self.serve_form = serve_form
        self._loader = loader
        self._bulk_loader = bulk_loader
        self._filter_loader = filter_loader
        self._epoch_loader = epoch_loader
        self.host_capacity = host_capacity
        self._models: OrderedDict[str, TenantModel] = OrderedDict()
        self._loader_backed: set[str] = set()
        # Shard key = (D_pad, V_pad, K, home-device index): the r20
        # mesh placement just widens the pre-r20 shape-class key with
        # the tenant-hash device axis (index 0 everywhere when the
        # resolved form is "single").
        self._shards: dict[tuple[int, int, int, int], _Shard] = {}
        # r20 sharded placement. `devices` is the candidate mesh (a
        # jax.devices() subset, order-significant: the crc32 hash
        # indexes into it); None = the default device only. The form
        # resolves LAZILY at first score (select_shard_form — the gate
        # sees the registered-tenant count) and FREEZES: placement
        # keys residency, so it must never flip mid-life.
        self.devices = list(devices) if devices else None
        self.shard_form = shard_form
        self._resolved_shard: str | None = None
        #: Shape keys whose compiled HLO passed the collective-free
        #: scan (sharded mode asserts it once per compiled shape).
        self.collective_checked: set[tuple] = set()
        # r20 host-tier prefetcher: decayed per-tenant request counts
        # (the Zipf demand estimate), the promote budget per batch
        # boundary, and the promoted-but-not-yet-referenced set the
        # hit/waste accounting keys on.
        self.prefetch_depth = prefetch_depth
        self._demand: dict[str, float] = {}
        self._prefetched: set[str] = set()
        self._demand_batches = 0
        # r13 feedback loop: per-tenant compiled noise filter
        # (onix/feedback/filter.HostFilter) + MODEL EPOCH. The epoch
        # bumps on every event that can change a tenant's winners —
        # add() (new/updated tables) and set_filter() — and the serve
        # layer's winner cache keys on it, so post-feedback requests
        # can never be served pre-feedback winners.
        self._filters: dict[str, HostFilter] = {}
        self._epochs: dict[str, int] = {}
        # Last PERSISTED model_epoch seen per tenant (add() adopt/bump
        # logic): distinguishes "same file reloaded" from "new file
        # whose stamp trails the filter-inflated in-memory epoch".
        self._disk_epochs: dict[str, int] = {}
        # Degradation ladder (r16): a failed "fused" dispatch re-runs
        # through the bit-identical xla kernels instead of failing the
        # wave (`serve.form_fallback`; docs/ROBUSTNESS.md "serving
        # resilience"). Winners are identical by the r15 contract.
        self.degrade_form_fallback = degrade_form_fallback
        self.dispatches = 0
        # Per-BANK fallback tally: the service's degraded stamp keys on
        # THIS bank's dispatches, never the process-global counter (two
        # services in one process must not stamp each other degraded).
        self.fallback_dispatches = 0
        self.compiled_shapes: set[tuple] = set()

    # -- registry ---------------------------------------------------------

    def add(self, tenant: str, theta, phi_wk,
            epoch: int | None = None) -> None:
        theta = np.ascontiguousarray(theta, np.float32)
        phi_wk = np.ascontiguousarray(phi_wk, np.float32)
        if theta.ndim != 2 or phi_wk.ndim != 2 \
                or theta.shape[1] != phi_wk.shape[1]:
            raise ValueError(
                f"tenant {tenant!r}: want theta [D,K] / phi_wk [V,K] with a "
                f"shared K, got {theta.shape} / {phi_wk.shape}")
        self._models[tenant] = TenantModel(theta, phi_wk,
                                           epoch=int(epoch or 0))
        # New tables invalidate cached winners. An EXPLICIT epoch is a
        # persisted stamp (loader path): reloading the SAME file after
        # a host-evict (stamp unchanged since last seen) must NOT
        # invalidate its cached winners — but a CHANGED stamp means a
        # genuinely new file, and the in-memory epoch must move PAST
        # its current value even when set_filter bumps (never
        # persisted) have inflated it numerically ahead of the disk
        # stamp; comparing magnitudes alone would let a re-fit hide
        # behind filter bumps and serve pre-refit cached winners. A
        # bare add() means new tables of unknown provenance: always
        # bump.
        cur = self._epochs.get(tenant)
        if epoch is not None:
            prev_disk = self._disk_epochs.get(tenant)
            self._disk_epochs[tenant] = int(epoch)
            if prev_disk is not None and int(epoch) != prev_disk:
                self._epochs[tenant] = max((cur or 0) + 1, int(epoch))
            else:
                self._epochs[tenant] = max(cur or 0, int(epoch))
        else:
            self._epochs[tenant] = (cur + 1) if cur is not None else 0
        # Device residency of the OLD tables must not survive the new
        # ones — evict from every shard (the update may have changed
        # the tenant's shape class) so the next wave re-admits the
        # updated copy.
        for shard in self._shards.values():
            if tenant in shard.lru:
                shard.free.append(shard.lru.pop(tenant))
                counters.inc("bank.evict")

    def epoch(self, tenant: str) -> int:
        """Current model epoch (0 for a tenant never seen)."""
        return self._epochs.get(tenant, 0)

    def set_filter(self, tenant: str, filt: HostFilter | None) -> None:
        """Install (or clear, with None/empty) a tenant's compiled
        noise filter. Always bumps the epoch — the winner cache must
        drop entries scored under the previous filter either way."""
        if filt is None or filt.empty_filter:
            self._filters.pop(tenant, None)
        else:
            self._filters[tenant] = filt
        self._epochs[tenant] = self._epochs.get(tenant, 0) + 1

    def get_filter(self, tenant: str) -> HostFilter | None:
        return self._filters.get(tenant)

    def refresh_from_disk(self, tenant: str) -> None:
        """Adopt an OUT-OF-PROCESS re-save: re-read the tenant's
        persisted epoch stamp (`epoch_loader`, serve wires it to
        checkpoint.model_meta_epoch — one small json read) and, when
        it differs from the last stamp seen, bump the in-memory epoch
        and drop the host copy + device residency so the next score
        loads the NEW tables. Without this, a nudge_and_save or
        re-fit by another process is invisible to a live server — its
        winner cache would serve pre-update winners until restart.
        Only loader-backed tenants refresh (an explicitly add()ed
        model has no file of record to re-fetch)."""
        if self._epoch_loader is None or tenant not in self._loader_backed:
            return
        stamp = self._epoch_loader(tenant)
        prev = self._disk_epochs.get(tenant)
        if stamp is None or prev is None or stamp == prev:
            return
        self._disk_epochs[tenant] = int(stamp)
        self._epochs[tenant] = max(self._epochs.get(tenant, 0) + 1,
                                   int(stamp))
        self._models.pop(tenant, None)
        self._loader_backed.discard(tenant)
        for shard in self._shards.values():
            if tenant in shard.lru:
                shard.free.append(shard.lru.pop(tenant))
                counters.inc("bank.evict")
        counters.inc("bank.disk_epoch_refresh")

    def set_filter_tree(self, base: str, filt: HostFilter | None) -> int:
        """Install the filter on `base` AND every known sub-tenant
        (`base/<sub>`): sub-tenants share the per-(datatype, date)
        feedback CSV — filter_loader compiles them the same filter on
        first load, so the live-update path must reach them too or
        their cached winners would keep serving dismissed events until
        a restart. "Known" = registered models plus tenants that
        already carry a filter; an unloaded sub-tenant still gets the
        filter from filter_loader when it loads. Returns base's new
        epoch."""
        prefix = base + "/"
        targets = {base} | {t for t in
                            set(self._models) | set(self._filters)
                            if t.startswith(prefix)}
        for t in targets:
            self.set_filter(t, filt)
        return self.epoch(base)

    def _load_retried(self, what: str, fn):
        """Drive a model load under the bounded `_LOAD_RETRY` policy.
        Loads are the one serve-path stage that touches a filesystem
        (models_dir may be network-backed), so transient OSErrors get
        one backed-off retry; exhaustion REFUSES with BankRefusal
        (`bank.load_refusal`) instead of wedging the batch — the
        degradation ladder's refuse-never-wedge rung
        (docs/ROBUSTNESS.md "serving resilience"). Non-I/O errors
        (ModelIntegrityError, BankRefusal) propagate untouched: a
        digest mismatch is not transient."""
        try:
            return retry_call(lambda strict: fn(), policy=_LOAD_RETRY,
                              counter_prefix="bank.load",
                              retry_on=OSError)
        except OSError as e:
            counters.inc("bank.load_refusal")
            raise BankRefusal(
                f"{what}: model load failed after "
                f"{_LOAD_RETRY.max_attempts} attempts: {e}") from e

    def model(self, tenant: str) -> TenantModel:
        m = self._models.get(tenant)
        if m is not None:
            self._models.move_to_end(tenant)
        if m is None and self._loader is not None:
            m = self._load_retried(f"tenant {tenant!r}",
                                   lambda: self._loader(tenant))
            if m is not None:
                self.add(tenant, m.theta, m.phi_wk, epoch=m.epoch)
                self._loader_backed.add(tenant)
                self._load_filter(tenant)
                self._trim_host_registry(keep={tenant})
                m = self._models[tenant]
        if m is None:
            raise BankRefusal(f"unknown tenant {tenant!r}")
        return m

    def _load_filter(self, tenant: str) -> None:
        """Attach the tenant's persisted feedback filter on first load
        (serve wires `filter_loader` to the feedback CSV compile), so
        a restarted server suppresses dismissed winners from its very
        first /score — no re-labeling needed."""
        if self._filter_loader is None or tenant in self._filters:
            return
        filt = self._filter_loader(tenant)
        if filt is not None and not filt.empty_filter:
            # Through set_filter — the attach must BUMP the epoch:
            # winner-cache entries for this tenant may predate a
            # host-evict, and they were scored without this filter.
            self.set_filter(tenant, filt)

    def _trim_host_registry(self, keep: set[str] = frozenset()) -> None:
        """Drop the oldest re-fetchable, non-device-resident host
        copies down to `host_capacity` loader-backed entries. Device
        residency is untouched; a dropped tenant simply reloads from
        the loader on its next reference. `keep` names tenants in
        flight (just loaded, not yet admitted) that must survive even
        over the cap."""
        if not self.host_capacity:
            return
        n_backed = len(self._loader_backed)
        if n_backed <= self.host_capacity:
            return
        for t in list(self._models):        # OrderedDict: oldest first
            if n_backed <= self.host_capacity:
                break
            if t in keep or t not in self._loader_backed:
                continue
            if any(t in sh.lru for sh in self._shards.values()):
                continue                    # still on device: keep host copy
            del self._models[t]
            self._loader_backed.discard(t)
            counters.inc("bank.host_evict")
            if t in self._prefetched:
                # Promoted ahead of demand, evicted before any request
                # referenced it: the prefetcher's false positive.
                self._prefetched.discard(t)
                counters.inc("bank.prefetch_waste")
            n_backed -= 1

    # -- host-RAM residency tier: demand-tracked prefetch (r20) -----------

    def _note_demand(self, requests) -> None:
        """Fold one request batch into the decayed per-tenant demand
        counts — the Zipf estimate the prefetcher ranks promotion
        candidates by. Halving every 32 batches (and dropping cold
        entries) keeps the table a bounded sliding window rather than
        an all-time popularity census that could never forget a
        formerly-hot tenant."""
        for req in requests:
            self._demand[req.tenant] = self._demand.get(req.tenant, 0.) + 1.
        self._demand_batches += 1
        if self._demand_batches % 32 == 0:
            self._demand = {t: v / 2 for t, v in self._demand.items()
                            if v >= 0.5}

    def _note_tiers(self, requests) -> None:
        """Per-request residency-tier accounting, BEFORE the batch
        mutates anything: hbm (device-resident), host (registry copy,
        needs admission only), disk (absent — the bulk/bulk-miss
        loaders will fetch it). The /bank/stats per-tier hit/miss
        picture and the harness's per-tier latency classes both read
        these counters."""
        for req in requests:
            t = req.tenant
            if t not in self._models:
                counters.inc("bank.tier_disk_load")
            elif self.resident(t):
                counters.inc("bank.tier_hbm_hit")
                self._touch_prefetched(t)
            else:
                counters.inc("bank.tier_host_hit")
                self._touch_prefetched(t)

    def _touch_prefetched(self, tenant: str) -> None:
        if tenant in self._prefetched:
            self._prefetched.discard(tenant)
            counters.inc("bank.prefetch_hit")

    def prefetch(self, tenants: list[str]) -> int:
        """Promote `tenants` from disk into the host-RAM tier in ONE
        bulk pass (`bulk_loader` → checkpoint.load_models), ahead of
        the demand the Zipf tracker predicts. Chaos site
        `bank:prefetch` fires at ENTRY — before any registry, filter,
        or epoch mutation — so the caller's bounded retry replays the
        whole promotion safely. Returns tenants actually promoted
        (absent-on-disk names are simply skipped: a prefetch is a
        prediction, not a demand)."""
        want = [t for t in tenants if t not in self._models]
        if not want or self._bulk_loader is None:
            return 0
        with telemetry.TRACER.span("bank.prefetch", tenants=len(want)):
            faults.fire("bank", "prefetch")
            loaded = self._load_retried(f"prefetch of {len(want)} tenants",
                                        lambda: self._bulk_loader(want))
            for t, m in loaded.items():
                self.add(t, m.theta, m.phi_wk, epoch=m.epoch)
                self._loader_backed.add(t)
                self._load_filter(t)
                self._prefetched.add(t)
                counters.inc("bank.prefetch_promoted")
            self._trim_host_registry(keep=set(loaded))
        return len(loaded)

    def _maybe_prefetch(self) -> None:
        """One prefetch pass at a request-batch boundary: promote up to
        `prefetch_depth` of the hottest demanded-but-not-host-resident
        tenants. BEST-EFFORT by contract — an injected fault is
        absorbed by one bounded replay, and exhaustion (a second
        injected fault, a dead filesystem) is counted and dropped,
        never surfaced to the scoring path: losing a prefetch costs
        latency on a later miss, failing a scored batch costs answers."""
        if not self.prefetch_depth or self._bulk_loader is None:
            return
        hot = sorted(self._demand.items(), key=lambda kv: -kv[1])
        cands = [t for t, _ in hot if t not in self._models]
        cands = cands[:self.prefetch_depth]
        if not cands:
            return
        counters.inc("bank.prefetch")
        try:
            retry_call(lambda strict: self.prefetch(cands),
                       policy=_SERVE_RETRY, counter_prefix="bank.prefetch",
                       retry_on=faults.InjectedFault)
        except (faults.InjectedFault, BankRefusal):
            counters.inc("bank.prefetch_failed")

    def tier_stats(self) -> dict:
        """The per-tier residency picture `/bank/stats` exposes: HBM
        (shard slots), host RAM (registry copies), disk (loads), plus
        the prefetcher's hit/waste accounting and the resolved
        placement form."""
        hbm_resident = sum(len(sh.lru) for sh in self._shards.values())
        per_device: dict[str, int] = {}
        for sh in self._shards.values():
            key = f"d{sh.device_index}"
            per_device[key] = per_device.get(key, 0) + len(sh.lru)
        return {
            "hbm": {"resident": hbm_resident,
                    "capacity_per_class": self.capacity,
                    "shape_classes": len(self._shards),
                    "per_device_resident": per_device,
                    "hits": counters.get("bank.tier_hbm_hit")},
            "host": {"resident": len(self._models),
                     "loader_backed": len(self._loader_backed),
                     "capacity": self.host_capacity,
                     "hits": counters.get("bank.tier_host_hit"),
                     "evictions": counters.get("bank.host_evict")},
            "disk": {"loads": counters.get("bank.tier_disk_load")},
            "prefetch": {"depth": self.prefetch_depth,
                         "passes": counters.get("bank.prefetch"),
                         "promoted": counters.get("bank.prefetch_promoted"),
                         "hits": counters.get("bank.prefetch_hit"),
                         "waste": counters.get("bank.prefetch_waste"),
                         "failed": counters.get("bank.prefetch_failed"),
                         "tracked_tenants": len(self._demand)},
            "shard_form": self._resolved_shard or "unresolved",
            "n_devices": self.n_devices(),
        }

    def tenants(self) -> list[str]:
        return sorted(self._models)

    def _class_of(self, m: TenantModel) -> tuple[int, int, int]:
        return (pow2_bucket(m.n_docs, BANK_DOC_FLOOR),
                pow2_bucket(m.n_vocab, BANK_VOCAB_FLOOR), m.n_topics)

    # -- sharded placement (r20) ------------------------------------------

    def n_devices(self) -> int:
        return len(self.devices) if self.devices else 1

    def shard_form_resolved(self) -> str:
        """The frozen placement form. First call resolves through the
        gate (env > explicit > measured > single) against the tenant
        count registered AT THAT POINT — placement keys device
        residency, so later registrations must not flip it."""
        if self._resolved_shard is None:
            self._resolved_shard = select_shard_form(
                self.shard_form, n_tenants=len(self._models),
                n_devices=self.n_devices())
            counters.inc(f"bank.shard_form_{self._resolved_shard}")
        return self._resolved_shard

    def _home_index(self, tenant: str) -> int:
        """The tenant's stable home-device index: crc32 placement, so
        every process (and every serve replica) agrees without any
        coordination state. Single form / one device ⇒ always 0."""
        n = self.n_devices()
        if n < 2 or self.shard_form_resolved() != "sharded":
            return 0
        return zlib.crc32(tenant.encode()) % n

    def _device_at(self, index: int):
        return self.devices[index] if self.devices else None

    # -- residency --------------------------------------------------------

    def resident(self, tenant: str) -> bool:
        m = self._models.get(tenant)
        if m is None:
            return False
        shard = self._shards.get(self._class_of(m)
                                 + (self._home_index(tenant),))
        return shard is not None and tenant in shard.lru

    def _ensure_resident(self, shard: _Shard, needed: list[str]) -> None:
        """Admit every tenant in `needed` (distinct, order-preserving)
        into `shard`, LRU-evicting non-needed residents as required.
        Called only at request batch boundaries — the winners-identity
        argument for capped banks rests on that."""
        # Chaos site `bank:admit` fires BEFORE any LRU mutation or H2D
        # staging, so the bounded retry in _score_wave replays the
        # whole admission safely (the stream:batch discipline). The
        # span wraps the site: an injected admission fault closes as an
        # error span, which is exactly the flight-recorder breadcrumb
        # a faults-marker postmortem needs.
        with telemetry.TRACER.span("bank.admit", tenants=len(needed)):
            faults.fire("bank", "admit")
            self._admit_locked(shard, needed)

    def _admit_locked(self, shard: _Shard, needed: list[str]) -> None:
        missing = [t for t in needed if t not in shard.lru]
        for t in needed:
            if t in shard.lru:
                shard.lru.move_to_end(t)
                counters.inc("bank.resident_hit")
        if not missing:
            return
        if len(needed) > shard.capacity:
            raise BankRefusal(
                f"request batch needs {len(needed)} distinct tenants in one "
                f"shape class; residency capacity is {shard.capacity} "
                "(split the batch)")
        needed_set = set(needed)
        while len(shard.free) < len(missing):
            for t in shard.lru:        # OrderedDict: oldest first
                if t not in needed_set:
                    shard.free.append(shard.lru.pop(t))
                    counters.inc("bank.evict")
                    break
        # Stage ALL admits host-side and ship ONE device_put per table
        # family — the bank-aware bulk load (never B round-trips).
        n = len(missing)
        th = np.zeros((n, shard.d_pad, shard.k), np.float32)
        ph = np.zeros((n, shard.v_pad, shard.k), np.float32)
        slots = np.empty(n, np.int32)
        for i, t in enumerate(missing):
            m = self.model(t)   # not _models[]: a tiny host_capacity may
                                # have trimmed a copy loaded this batch
            th[i, :m.n_docs] = m.theta
            ph[i, :m.n_vocab] = m.phi_wk
            slots[i] = shard.free.pop()
            shard.lru[t] = int(slots[i])
            counters.inc("bank.admit")
        # device=None (single form) keeps jax's default placement —
        # the pre-r20 shape; a sharded shard stages straight onto the
        # wave's home device, still ONE transfer per table family.
        th_d = jax.device_put(th, shard.device)
        ph_d = jax.device_put(ph, shard.device)
        counters.inc("bank.h2d_transfers", 2)
        counters.inc("bank.h2d_bytes", th.nbytes + ph.nbytes)
        idx = jnp.asarray(slots)
        shard.theta = shard.theta.at[idx].set(th_d)
        shard.phi = shard.phi.at[idx].set(ph_d)

    # -- scoring ----------------------------------------------------------

    def _validate(self, req: ScoreRequest, m: TenantModel) -> None:
        d = np.asarray(req.doc_ids)
        w = np.asarray(req.word_ids)
        if d.shape != w.shape or d.ndim != 1:
            raise BankRefusal(
                f"tenant {req.tenant!r}: doc_ids/word_ids must be equal-"
                f"length 1-d arrays, got {d.shape} / {w.shape}")
        if d.size and (int(d.min()) < 0 or int(d.max()) >= m.n_docs
                       or int(w.min()) < 0 or int(w.max()) >= m.n_vocab):
            # Out-of-range ids would gather PADDING rows (score 0 — a
            # fabricated top winner). Refuse, never clamp.
            raise BankRefusal(
                f"tenant {req.tenant!r}: token ids out of range for its "
                f"model (D={m.n_docs}, V={m.n_vocab})")

    def score_batch(self, requests: list[ScoreRequest], *, tol: float,
                    max_results: int) -> list[TopK]:
        """Score a mixed-tenant request batch; returns host-side TopK
        per request, in request order. Requests group by shape class
        and split into residency-capacity waves; each wave is ONE
        jitted dispatch (the N→1 collapse the bank exists for)."""
        out: list[TopK | None] = [None] * len(requests)
        # Tier + demand accounting first, BEFORE the bulk load mutates
        # the registry — "which tier answered this request" is a
        # property of the bank's state at receipt.
        self._note_tiers(requests)
        self._note_demand(requests)
        if self._bulk_loader is not None:
            # Fetch the batch's unknown tenants in ONE host-side pass
            # (checkpoint.load_models) instead of per-tenant loader
            # round-trips; model() below still backstops stragglers.
            unknown: list[str] = []
            for req in requests:
                if req.tenant not in self._models \
                        and req.tenant not in unknown:
                    unknown.append(req.tenant)
            if unknown:
                loaded = self._load_retried(
                    f"{len(unknown)} tenants",
                    lambda: self._bulk_loader(unknown))
                for t, m in loaded.items():
                    self.add(t, m.theta, m.phi_wk, epoch=m.epoch)
                    self._loader_backed.add(t)
                    self._load_filter(t)
                self._trim_host_registry(
                    keep={req.tenant for req in requests})
        # Group by (shape class, home device): the r20 placement axis
        # rides the same grouping the shape ladder always used. With
        # the single form every home index is 0 — the pre-r20 shape.
        by_group: dict[tuple, list[int]] = {}
        for i, req in enumerate(requests):
            m = self.model(req.tenant)
            self._validate(req, m)
            key = self._class_of(m) + (self._home_index(req.tenant),)
            by_group.setdefault(key, []).append(i)
        sharded = self.shard_form_resolved() == "sharded" \
            and self.n_devices() > 1
        pending: list[tuple[TopK, list[int]]] = []
        for key, idxs in by_group.items():
            shard = self._shards.get(key)
            if shard is None:
                shard = self._shards[key] = _Shard(
                    *key[:3], self.capacity,
                    device=self._device_at(key[3]), device_index=key[3])
            for wave in self._waves(requests, idxs, shard.capacity):
                if sharded:
                    # Dispatch phase: launch the wave's independent
                    # device program and move on — jax dispatch is
                    # async, so waves routed to different devices
                    # overlap; the winner fetches drain afterwards.
                    with telemetry.TRACER.span("bank.wave",
                                               device=key[3],
                                               requests=len(wave)):
                        res = self._dispatch_wave(shard, requests, wave,
                                                  tol=tol,
                                                  max_results=max_results)
                    counters.inc(f"bank.wave.d{key[3]}")
                    pending.append((res, wave))
                else:
                    self._score_wave(shard, requests, wave, out, tol=tol,
                                     max_results=max_results)
        for res, wave in pending:
            # Fetch phase (sharded): drain in dispatch order; the wall
            # spent blocked here is the cross-device stall the
            # artifact's accounting reports.
            t_fetch = time.perf_counter()
            self._fetch_wave(res, wave, out)
            counters.inc("bank.fetch_wait_us",
                         int((time.perf_counter() - t_fetch) * 1e6))
        # Device eviction above may have freed host copies for trimming
        # (request-batch boundary — same place residency may change).
        self._trim_host_registry()
        # Prefetch at the batch boundary: promote predicted-hot tenants
        # into the host tier so the NEXT batch's misses start warm.
        self._maybe_prefetch()
        return out  # type: ignore[return-value]

    @staticmethod
    def _waves(requests, idxs: list[int], capacity: int):
        """Split one class's request indices into waves of <= capacity
        distinct tenants, preserving order (eviction then happens only
        BETWEEN waves — request boundaries)."""
        wave: list[int] = []
        tenants: set[str] = set()
        for i in idxs:
            t = requests[i].tenant
            if t not in tenants and len(tenants) == capacity:
                yield wave
                wave, tenants = [], set()
            wave.append(i)
            tenants.add(t)
        if wave:
            yield wave

    def _filter_rows(self, requests, wave: list[int],
                     r_pad: int) -> FilterTables:
        """Stack the wave's per-tenant filter tables into a
        FilterTables pytree with a leading [r_pad] request axis: per
        family a ([r_pad, F] hi, [r_pad, F] lo) uint32 pair of sorted
        sentinel-padded rows, plus the per-row boost scale. F is the
        pow2 cover of the wave's largest table per family (floor
        FILTER_FLOOR), so no-feedback waves stay in one tiny shape
        class and the key-table ladder adds O(log entries) compiles."""
        filts = [self._filters.get(requests[i].tenant) for i in wave]

        def fam_rows(fam):
            f_pad = pow2_bucket(
                max([FILTER_FLOOR]
                    + [len(getattr(x, fam)) for x in filts if x]),
                FILTER_FLOOR)
            rows = np.tile(_pad_sorted(np.empty(0, np.uint64), f_pad),
                           (r_pad, 1))
            for row, x in enumerate(filts):
                if x is not None:
                    keys = getattr(x, fam)
                    rows[row, :len(keys)] = keys
            hi, lo = split_key(rows.ravel())
            return (jnp.asarray(hi.reshape(r_pad, f_pad)),
                    jnp.asarray(lo.reshape(r_pad, f_pad)))

        scale = np.ones(r_pad, np.float32)
        for row, x in enumerate(filts):
            if x is not None:
                scale[row] = x.boost_scale
        return FilterTables(word_suppress=fam_rows("word_suppress"),
                            word_boost=fam_rows("word_boost"),
                            pair_suppress=fam_rows("pair_suppress"),
                            pair_boost=fam_rows("pair_boost"),
                            boost_scale=jnp.asarray(scale))

    def _prepare_wave(self, shard: _Shard, requests, wave: list[int], *,
                      tol: float, max_results: int):
        """Admission + host-side staging for one wave: returns the
        kernel args plus the resolved (form, serve) pair and the shape
        key. Shared verbatim by the single-device path (_score_wave)
        and the sharded dispatch phase (_dispatch_wave) — the
        bit-identity argument between the two is that everything
        except the device the program runs on comes from here."""
        needed: list[str] = []
        for i in wave:
            if requests[i].tenant not in needed:
                needed.append(requests[i].tenant)
        # One bounded replay for injected admission faults (the site
        # fires at _ensure_resident entry, pre-mutation); real load
        # I/O failures are retried-then-refused inside _load_retried.
        retry_call(lambda strict: self._ensure_resident(shard, needed),
                   policy=_SERVE_RETRY, counter_prefix="bank.admit",
                   retry_on=faults.InjectedFault)

        r = len(wave)
        n_events = [int(np.asarray(requests[i].doc_ids).size) for i in wave]
        n_pad = pow2_bucket(max(n_events), BANK_EVENTS_FLOOR)
        r_pad = pow2_bucket(r, 1)
        d = np.zeros((r_pad, n_pad), np.int32)
        w = np.zeros((r_pad, n_pad), np.int32)
        m = np.zeros((r_pad, n_pad), np.float32)
        slots = np.zeros(r_pad, np.int32)
        for row, i in enumerate(wave):
            n = n_events[row]
            d[row, :n] = np.asarray(requests[i].doc_ids, np.int32)
            w[row, :n] = np.asarray(requests[i].word_ids, np.int32)
            m[row, :n] = 1.0
            slots[row] = shard.lru[requests[i].tenant]
        # Static no-feedback fast path: a wave with no filtered tenant
        # ships filt_rows=None and compiles WITHOUT the membership
        # search — identical cost to the pre-filter kernels (the
        # common case; the filtered variant is its own compiled shape).
        if any(requests[i].tenant in self._filters for i in wave):
            filt_rows = self._filter_rows(requests, wave, r_pad)
            filt_dims = (filt_rows.word_suppress[0].shape[1],
                         filt_rows.word_boost[0].shape[1],
                         filt_rows.pair_suppress[0].shape[1],
                         filt_rows.pair_boost[0].shape[1])
        else:
            filt_rows, filt_dims = None, None

        form = select_bank_form(self.form, r_pad, n_pad)
        from onix.models.pallas_serve import select_serve_form
        # Gate on n_pad — the PER-LANE event count each fused kernel
        # actually runs at — so the crossover table keeps one unit
        # (per-scan events) across every consumer.
        serve = select_serve_form(self.serve_form, n_pad)
        # The RESOLVED serve form joins the shape key so manifests
        # record what actually compiled (gate artifacts must name the
        # arm, not the request).
        shape_key = (form, serve, shard.d_pad, shard.v_pad, shard.k,
                     r_pad, n_pad, max_results, filt_dims)
        self.compiled_shapes.add(shape_key)
        args = (shard.theta, shard.phi, jnp.asarray(slots), jnp.asarray(d),
                jnp.asarray(w), jnp.asarray(m), jnp.float32(tol),
                filt_rows)
        return args, form, serve, shape_key, r, sum(n_events)

    def _launch(self, args, form: str, serve: str, shape_key: tuple, *,
                max_results: int) -> TopK:
        """One wave's kernel call (device-side result — the caller
        fetches) behind the r16 degradation ladder."""
        try:
            res = _bank_kernel_for(form, serve)(
                *args, max_results=max_results)
        except Exception:                   # noqa: BLE001 — the
            # degradation ladder's first rung: a fused-kernel
            # failure (Mosaic lowering, VMEM overflow, injected
            # chaos) falls back to the bit-identical xla kernels —
            # same winners by the r15 identity contract — instead
            # of failing the wave. Counted + stamped degraded
            # upstream; never silent.
            if serve != "fused" or not self.degrade_form_fallback:
                raise
            counters.inc("serve.form_fallback")
            self.fallback_dispatches += 1
            self.compiled_shapes.add(shape_key[:1] + ("xla",)
                                     + shape_key[2:])
            res = _bank_kernel_for(form, "xla")(
                *args, max_results=max_results)
        self.dispatches += 1
        counters.inc("bank.dispatch")
        return res

    def _score_wave(self, shard: _Shard, requests, wave: list[int],
                    out: list, *, tol: float, max_results: int) -> None:
        """The single-device wave: prepare + launch + fetch, all under
        the pre-r20 `bank.score_wave` span (one batched program + ONE
        winner fetch — the latency building block every serve-side
        quantile decomposes into; attrs carry the resolved forms so a
        slow trace names the arm that compiled, not the request)."""
        args, form, serve, shape_key, r, events = self._prepare_wave(
            shard, requests, wave, tol=tol, max_results=max_results)
        with telemetry.TRACER.span("bank.score_wave", form=form,
                                   serve=serve, requests=r,
                                   events=events):
            res = self._launch(args, form, serve, shape_key,
                               max_results=max_results)
            counters.inc("bank.requests", r)
            counters.inc("bank.events", events)
            self._fetch_wave(res, wave, out)

    def _dispatch_wave(self, shard: _Shard, requests, wave: list[int], *,
                       tol: float, max_results: int) -> TopK:
        """The sharded dispatch phase: prepare + launch WITHOUT the
        fetch — jax's async dispatch returns as soon as the program is
        enqueued on the wave's home device, so the caller can launch
        the next device's wave before this one drains. The first
        launch of every shape also proves the psum-free claim: the
        compiled HLO is scanned for cross-device collectives
        (`assert_collective_free`), once per shape key."""
        args, form, serve, shape_key, r, events = self._prepare_wave(
            shard, requests, wave, tol=tol, max_results=max_results)
        if shape_key not in self.collective_checked:
            kernel = _bank_kernel_for(form, serve)
            # The fused arm is a pallas partial without .lower(); its
            # collective-freedom follows from the xla twin it falls
            # back to (same args, same single-device placement).
            if hasattr(kernel, "lower"):
                assert_collective_free(kernel, args,
                                       max_results=max_results)
                counters.inc("bank.collective_checks")
            self.collective_checked.add(shape_key)
        res = self._launch(args, form, serve, shape_key,
                           max_results=max_results)
        counters.inc("bank.requests", r)
        counters.inc("bank.events", events)
        return res

    @staticmethod
    def _fetch_wave(res: TopK, wave: list[int], out: list) -> None:
        scores = np.asarray(res.scores)        # ONE fetch per dispatch
        indices = np.asarray(res.indices)
        for row, i in enumerate(wave):
            out[i] = TopK(scores=scores[row], indices=indices[row])


@dataclasses.dataclass
class BankResult:
    """One request's outcome through the service: winners + provenance.
    `degraded` stamps a response served under the degradation ladder —
    the service was past its soft overload watermark, or the wave fell
    back from the fused to the xla kernel. Degraded NEVER means stale:
    winners are current-epoch by the same cache contract as any other
    response; the stamp is latency/arm provenance, not a correctness
    hedge (docs/ROBUSTNESS.md "serving resilience")."""
    topk: TopK
    cached: bool
    degraded: bool = False


class BankService:
    """Request batching + per-(tenant, window) winner caching in front
    of the bank — the serve layer's entry point (`/score`).

    The cache asserts the (tenant, window) contract: a window names one
    immutable event set (a finished day/hour), so its winners are a
    pure function of (tenant, window, tol, max_results) AND the
    tenant's MODEL EPOCH — the epoch at score time is stored with the
    entry, and a hit whose stored epoch trails the tenant's current one
    (feedback applied, model updated/re-saved) is EVICTED and re-scored
    (`bank.cache_epoch_evictions`): a post-feedback request can never
    be served pre-feedback winners. tol and max_results join the key,
    so a repeat of the same window at a different threshold or result
    count is scored fresh, never served the other parameterization's
    winners. A repeat with a DIFFERENT event count is treated as a
    conflict: scored fresh, re-cached, and counted
    (`bank.cache_conflict`) — never served stale."""

    #: Lock discipline, machine-checked by the `locks` analysis pass
    #: (python -m onix.analysis): these attributes are shared across
    #: handler threads and may only be mutated under their declared
    #: lock. `_cache` mutators run under `lock` via submit()'s scoring
    #: section and the serve layer's install path (methods marked
    #: `# lint: holds[lock]`); the admission tallies live under the
    #: separate `_admit_lock` so a shed request never waits on scoring.
    GUARDED_BY = {"_cache": "lock",
                  "_pending": "_admit_lock",
                  "peak_depth": "_admit_lock",
                  "_ewma_wall_s": "_admit_lock"}

    def __init__(self, bank: ModelBank, max_batch_requests: int = 64,
                 cache_size: int = 4096, max_queue_depth: int = 0,
                 request_deadline_s: float = 0.0):
        if max_batch_requests < 1:
            raise ValueError("max_batch_requests must be >= 1")
        if max_queue_depth < 0 or request_deadline_s < 0:
            raise ValueError("max_queue_depth and request_deadline_s "
                             "must be >= 0 (0 = disabled)")
        self.bank = bank
        self.max_batch_requests = max_batch_requests
        self.cache_size = cache_size
        self._cache: OrderedDict[tuple[str, str, float, int],
                                 tuple[int, int, TopK]] = OrderedDict()
        # r16 admission control (docs/ROBUSTNESS.md "serving
        # resilience"): `lock` serializes scoring + filter installs
        # (host-side cache/residency state is shared across handler
        # threads — the serve layer used to hold its own lock here);
        # `max_queue_depth` bounds in-flight + queued submit() calls,
        # beyond which requests SHED (Overloaded → 503 + Retry-After)
        # BEFORE touching any bank state; `request_deadline_s` bounds
        # receipt→scoring-start wall (queue time included).
        self.lock = threading.RLock()
        self.max_queue_depth = max_queue_depth
        self.request_deadline_s = request_deadline_s
        self._admit_lock = threading.Lock()
        self._pending = 0
        self.peak_depth = 0
        # EWMA of recent scoring walls — the Retry-After hint (how long
        # until a queue slot likely frees). Seeded pessimistically low;
        # the first real call corrects it.
        self._ewma_wall_s = 0.05
        # r18: the REAL distribution behind the hint — a log-bucketed
        # histogram of scoring walls (telemetry.Histogram, internally
        # locked). Once it holds enough observations the Retry-After
        # hint uses its median instead of the EWMA point estimate: a
        # bimodal wall (cache hits vs cold waves) no longer averages
        # into a hint that is wrong for both modes. Service-local on
        # purpose — two services in one process must not blend walls.
        self._wall_hist = telemetry.Histogram()

    def _retry_hint_s(self, depth: int) -> float:
        """Seconds until a queue slot likely frees: depth x the median
        scoring wall (the histogram once seeded, the EWMA before)."""
        wall = (self._wall_hist.quantile(0.5) if self._wall_hist.n >= 8
                else self._ewma_wall_s)
        return max(0.1, round(depth * wall, 2))

    # -- admission control + deadline (the submit path) -------------------

    def submit(self, requests: list[ScoreRequest], *, tol: float,
               max_results: int,
               deadline: Deadline | None = None) -> list[BankResult]:
        """The admission-controlled, deadline-bounded serve entry point
        (`/score` and the load harness both come through here).

        Order of refusals, all BEFORE any bank mutation:
          1. depth — `max_queue_depth` submit() calls already in flight
             or queued ⇒ shed (`serve.shed`, Overloaded → HTTP 503 with
             Retry-After). A shed request never touches residency or
             the winner cache (asserted by the overload cell).
          2. deadline — the budget (passed in, or request_deadline_s
             from admission) is checked once scoring WOULD start, i.e.
             after the queue wait; expired ⇒ refused
             (`serve.deadline_expired`, DeadlineExceeded → 503). Once
             scoring starts the request runs to completion — partial
             winner sets are never served.

        Served responses past the soft watermark (depth > half the
        max) or scored through the form-fallback rung are stamped
        `degraded: true` (`serve.degraded`) — an explicit overload
        signal, never stale winners: the epoch-keyed cache contract is
        unchanged on every rung."""
        t_recv = time.perf_counter()
        shed_pending = None
        with self._admit_lock:
            if self.max_queue_depth \
                    and self._pending >= self.max_queue_depth:
                shed_pending = self._pending
            else:
                self._pending += 1
                depth = self._pending
                # Two scopes on purpose: peak_depth is THIS service's
                # high-water (admission_stats / GET /bank/stats — one
                # service per server); the registry gauge is the
                # process-wide max across services (a harness running
                # several services reports the worst one).
                self.peak_depth = max(self.peak_depth, depth)
        if shed_pending is not None:
            counters.inc("serve.shed")
            counters.inc("serve.shed_requests", len(requests))
            # Flight-recorder trigger (r18): the ring at shed time IS
            # the overload postmortem — what was in flight, which
            # tenants, which counters moved in the runup. OUTSIDE
            # _admit_lock on purpose: the dump is file I/O over ~1k
            # ring events, and at peak overload every concurrent
            # admission check would otherwise serialize behind it —
            # inflating the served p99 exactly when the r16 bound is
            # being measured.
            telemetry.RECORDER.dump(
                "serve-shed", extra={"pending": shed_pending,
                                     "requests": len(requests)})
            raise Overloaded(
                f"serving queue full ({shed_pending} batches in "
                f"flight, max_queue_depth={self.max_queue_depth})",
                retry_after_s=self._retry_hint_s(shed_pending))
        counters.note_max("serve.queue_depth_peak", depth)
        soft = bool(self.max_queue_depth
                    and depth > max(1, self.max_queue_depth // 2))
        if deadline is None and self.request_deadline_s > 0:
            deadline = Deadline(self.request_deadline_s)
        try:
            with telemetry.TRACER.span("serve.submit",
                                       requests=len(requests),
                                       depth=depth), \
                    self.lock:
                # Clock starts INSIDE the lock: the EWMA must track
                # scoring wall only — folding queue wait in would make
                # the Retry-After hint compound quadratically under
                # sustained contention (wait ≈ depth × ewma ⇒ ewma ≈
                # depth × service ⇒ hint ≈ depth² × service).
                t0 = time.perf_counter()
                # The admission queue wait, as its own span: receipt
                # (submit entry) to scoring start. This is the "why was
                # THIS request slow" number — a fat serve.submit with a
                # fat serve.queue_wait is contention, without one it is
                # scoring cost.
                telemetry.TRACER.observe("serve.queue_wait", t0 - t_recv)
                if deadline is not None and deadline.expired():
                    # counters: resilience.deadline_exceeded is inc'd
                    # by Deadline.check; serve.deadline_expired is the
                    # serve-tier view artifacts carry.
                    counters.inc("serve.deadline_expired")
                    deadline.check("serve request (queued past its "
                                   "deadline budget)")
                fb0 = self.bank.fallback_dispatches
                # Bounded replay for the injected `serve:score` site —
                # it fires at score() entry, before any cache or
                # residency mutation, so the retry is a safe replay.
                results = retry_call(
                    lambda strict: self.score(requests, tol=tol,
                                              max_results=max_results),
                    policy=_SERVE_RETRY, counter_prefix="serve.score",
                    retry_on=faults.InjectedFault)
                fell_back = self.bank.fallback_dispatches > fb0
                wall = time.perf_counter() - t0
            # Histogram first (internally locked): the Retry-After
            # median must see every wall the EWMA sees.
            self._wall_hist.observe(wall)
            # Under _admit_lock: concurrent submits racing this += would
            # lose updates (read-modify-write), skewing the Retry-After
            # hint shed responses derive from it (r17 locks-pass fix).
            with self._admit_lock:
                self._ewma_wall_s += 0.3 * (wall - self._ewma_wall_s)
        finally:
            with self._admit_lock:
                self._pending -= 1
        if soft or fell_back:
            counters.inc("serve.degraded")
            counters.inc("serve.degraded_requests", len(requests))
            results = [dataclasses.replace(r, degraded=True)
                       for r in results]
        counters.inc("serve.served", len(requests))
        return results

    def admission_stats(self) -> dict:
        with self._admit_lock:
            depth = self._pending
        return {"queue_depth": depth,
                "queue_depth_peak": self.peak_depth,
                "max_queue_depth": self.max_queue_depth,
                "request_deadline_s": self.request_deadline_s,
                "shed": counters.get("serve.shed"),
                "shed_requests": counters.get("serve.shed_requests"),
                "deadline_expired": counters.get("serve.deadline_expired"),
                "degraded": counters.get("serve.degraded"),
                "form_fallback": counters.get("serve.form_fallback"),
                "served": counters.get("serve.served")}

    # lint: holds[lock] -- every production call arrives through submit()'s `with self.lock` scoring section; the bank/cache state it touches is serialized there
    def score(self, requests: list[ScoreRequest], *, tol: float,
              max_results: int) -> list[BankResult]:
        with telemetry.TRACER.span("serve.score", requests=len(requests)):
            # Chaos site `serve:score`: entry, pre-mutation (before the
            # disk-epoch probes and cache bookkeeping), so submit()'s
            # bounded retry replays the whole call safely. Inside the
            # span: an injected fault closes it as an error span.
            faults.fire("serve", "score")
            return self._score_locked(requests, tol=tol,
                                      max_results=max_results)

    # lint: holds[lock] -- called only from score(), which submit() serializes (see above)
    def _score_locked(self, requests: list[ScoreRequest], *, tol: float,
                      max_results: int) -> list[BankResult]:
        out: list[BankResult | None] = [None] * len(requests)
        # Out-of-process update probe, once per distinct tenant per
        # call (ModelBank.refresh_from_disk): a re-save by another
        # process moves the epoch BEFORE the hit checks below, so the
        # cache can never serve winners computed under the old file.
        for tenant in {r.tenant for r in requests}:
            self.bank.refresh_from_disk(tenant)
        misses: list[int] = []
        for i, req in enumerate(requests):
            key = (req.tenant, req.window, float(tol), int(max_results)) \
                if req.window is not None else None
            hit = self._cache.get(key) if key is not None else None
            if hit is not None:
                n_cached, epoch_cached, topk = hit
                if epoch_cached != self.bank.epoch(req.tenant):
                    # Scored under an older model epoch: stale by
                    # construction, never serveable.
                    del self._cache[key]
                    counters.inc("bank.cache_epoch_evictions")
                elif n_cached == int(np.asarray(req.doc_ids).size):
                    self._cache.move_to_end(key)
                    counters.inc("bank.cache_hit")
                    out[i] = BankResult(topk, cached=True)
                    continue
                else:
                    counters.inc("bank.cache_conflict")
            if key is not None:     # uncacheable requests don't dilute
                counters.inc("bank.cache_miss")
            misses.append(i)
        for lo in range(0, len(misses), self.max_batch_requests):
            chunk = misses[lo:lo + self.max_batch_requests]
            topks = self.bank.score_batch([requests[i] for i in chunk],
                                          tol=tol, max_results=max_results)
            for i, topk in zip(chunk, topks):
                out[i] = BankResult(topk, cached=False)
                req = requests[i]
                if req.window is not None:
                    # Epoch AFTER scoring: score_batch may have loaded
                    # the tenant (adopting its persisted epoch) — the
                    # entry must carry the epoch its winners were
                    # computed under.
                    self._put(
                        (req.tenant, req.window, float(tol),
                         int(max_results)),
                        (int(np.asarray(req.doc_ids).size),
                         self.bank.epoch(req.tenant), topk))
        return out  # type: ignore[return-value]

    # lint: holds[lock] -- the serve layer's /feedback handler wraps compile+install in `with service.lock` (oa/serve.py), serializing installs against scoring
    def apply_feedback_filter(self, base: str, filt) -> int:
        """The serve layer's one-call feedback install: filter + epoch
        bumps for every KNOWN tenant under `base`
        (bank.set_filter_tree), plus an outright drop of every cache
        entry under the base — an UNLOADED sub-tenant's name is
        unknowable here, so its stale entries cannot be reached
        through epochs (its filter attaches, with a bump, when it next
        loads; but a cached pre-evict entry would hit before any load
        runs). Returns base's new epoch.

        Chaos site `feedback:install` fires at entry — before the
        filter, epochs, or cache are touched — and is absorbed by one
        bounded in-place retry (the install is deterministic in its
        inputs, so the replay installs the identical filter): a fault
        can delay an install by one retry, never lose it or leave a
        half-installed filter live."""
        def _install(strict: bool = True) -> int:
            faults.fire("feedback", "install")
            epoch = self.bank.set_filter_tree(base, filt)
            prefix = base + "/"
            for key in [k for k in self._cache
                        if k[0] == base or k[0].startswith(prefix)]:
                del self._cache[key]
            return epoch
        return retry_call(_install, policy=_SERVE_RETRY,
                          counter_prefix="serve.feedback_install",
                          retry_on=faults.InjectedFault)

    # lint: holds[lock] -- called only from score(), which holds it (see above)
    def _put(self, key, value) -> None:
        self._cache[key] = value
        self._cache.move_to_end(key)
        while len(self._cache) > self.cache_size:
            self._cache.popitem(last=False)

    def cache_stats(self) -> dict:
        return {"entries": len(self._cache),
                "hits": counters.get("bank.cache_hit"),
                "misses": counters.get("bank.cache_miss"),
                "conflicts": counters.get("bank.cache_conflict"),
                "epoch_evictions":
                    counters.get("bank.cache_epoch_evictions")}


# ---------------------------------------------------------------------------
# Refit -> bank epoch propagation (r20, pipelines/fleet.py).
# ---------------------------------------------------------------------------


def publish_refit(bank: ModelBank, tenant: str, theta, phi_wk, *,
                  epoch: int) -> int:
    """Propagate one accepted refit into a live serving bank.

    The fleet supervisor calls this per accepted tenant-day with the
    tenant's LINEAGE epoch (the per-tenant ok-day counter that also
    stamps the persisted model), which rides `add`'s explicit-epoch
    path: the in-memory epoch moves past the previous stamp, the
    tenant's cached winners invalidate, and its device residency
    evicts — for exactly this tenant, no other (the same surgical
    radius the per-tenant quarantine gives the fit side). Returns the
    bank's resulting epoch for the tenant."""
    bank.add(tenant, theta, phi_wk, epoch=int(epoch))
    counters.inc("bank.refit_published")
    return bank.epoch(tenant)
