"""Pallas TPU kernel: one-kernel serving path — fused score +
filter-membership + bottom-M.

The serving hot path was three fused-but-separate XLA stages — the
batched gather/matmul scoring, the r13 feedback membership search
(measured as a 4x tax on the filtered flow path: 147M -> 37M ev/s on
CPU, docs/FEEDBACK_r13_cpu.json), and the chunked bottom-M scan — each
round-tripping the [chunk] candidate scores through HBM between
programs. This module collapses them into ONE `pallas_call` per
request (the bounded-staleness literature the fit layer builds on —
AD-LDA, arxiv 0909.4603; Streaming Gibbs, arxiv 1601.01142 — makes the
same argument: keep hot state resident, defer the global exchange;
here the hot state is the winner buffer and the filter tables).

One grid step per token tile. Per tile (all VMEM-resident):

  1. scoring — mode "dot": the gathered theta[d]/phi[w] rows come in as
     [tile, K] blocks (gathered OUTSIDE the kernel: Mosaic has no
     gather lowering) and the kernel takes the
     row-wise product-sum — the float ops of `scoring.score_events`,
     but NOT its accumulation order: the K-term sum's association is
     the compiler's, so this mode's scores sit within a couple of ulp
     of the XLA arm's rather than on them (2 ulp on the chip in PR 21,
     1 ulp in the CPU interpreter under jax 0.9.0 — PERF.md). Mode
     "min2": two
     pre-gathered score columns, pair-min inside
     (the `table_pair_bottom_k` / streaming flow-tail shape). Mode
     "scores": precomputed scores (the bank gather tail, plain
     bottom_k).
  2. filter membership — the r13 sorted-uint64 filter's four key
     families ride in as their packed (hi, lo) uint32 half columns,
     SENTINEL-padded pow2 (the exact `feedback/filter.py` device
     rendering), resident in VMEM across the whole grid. Membership is
     an exact BRANCHLESS search: the sorted table is swept in
     `_FILTER_SEARCH_TILE`-wide VMEM tiles and each tile answers with
     one lane-parallel compare-reduce (eq-AND-eq, reduce-or). This is
     the membership semantics of `filter._member` to the bit — the
     log2(F) gather-probe bisection itself cannot lower (Mosaic in
     this jax has NO gather rule, see the lowering-rules table), so
     the kernel trades the O(log F) serial probes for O(F/lanes)
     fully-parallel compares against tables that are typically tens of
     entries (the filter size at which that trade stops winning: not
     measured on the chip). The adjustment is the exact
     `filter.apply_filter` order: boost members scale by boost_scale,
     suppress members go to +inf, BEFORE the tol screen.
  3. bottom-M — the per-request winner buffer ([M] scores + [M]
     indices, lexicographically sorted ascending) lives in VMEM across
     every grid step (constant out index map) and is flushed to HBM
     ONCE per request — not once per chunk. Each tile merges by exact
     rank arithmetic: strict lexicographic (score, index) comparisons
     (global indices are unique, so the order is total and every rank
     is distinct), int32 rank sums, and a one-hot select-sum scatter —
     compare/reduce/select ops only, all with Mosaic lowerings. The
     tie rule is `_merge_bottom_k`'s by construction: at equal scores
     the lower global index wins, which is exactly what lexicographic
     rank implements, so winners, scores AND order are bit-identical
     to `_scan_bottom_k` (+inf slots get the -1 index sentinel in the
     same finalize step).

Exactness: modes "min2" and "scores" move scores without arithmetic,
so they are bit-identical to the XLA scans (asserted compiled, on the
chip); mode "dot" is within a couple of ulp (item 1). Membership
is equality against the same tables; rank sums and the scatter are
int32/select ops (no float accumulation of indices), and the score
scatter moves values by select, never arithmetic. The only float
arithmetic beyond scoring is the boost multiply — the same single f32
op `apply_filter` issues. Interpret mode (the default off-TPU,
`ONIX_PALLAS_INTERPRET` override) lowers to plain XLA ops, so tier-1
asserts bit-identity on CPU (tests/test_pallas_serve.py) and the same
code compiles through Mosaic on a real TPU (the `tpu`-marked tests;
what the chip said is in PERF.md).

The gate (`select_serve_form`, `serving.serve_form`, ONIX_SERVE_FORM)
resolves through `config.resolve_form_gate` next to
`model_bank.select_bank_form`; `_SERVE_FUSED_MIN_EVENTS` is
DELIBERATELY EMPTY — tpu included: the crossover is not measured on
the chip, so `auto` resolves to "xla" on every backend today and nothing changes
behavior without a measurement.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from onix.config import resolve_form_gate
from onix.models.scoring import TopK, _empty_topk

# Token-tile width of the serving grid. 256 rows keeps every per-tile
# temporary comfortably inside VMEM (the [M, tile] cross-rank matrix is
# the big one) while amortizing the per-tile merge over enough events.
_SERVE_TILE = 256
# Filter entries compared per VMEM search tile: 2048 entries = 8 KB
# per half column, a [tile, 2048] compare temporary of 2 MB. Tables
# larger than one search tile are swept tile-by-tile (trace-time
# unrolled, branchless) — the "tiled search" arm, exercised in tier-1
# with a 4096-entry filter.
_FILTER_SEARCH_TILE = 2048
# Output rows scattered per select-sum block inside the merge, bounding
# the [block, M + tile] equality temporary.
_SCATTER_BLOCK = 256

# Measured per-backend crossover: events per request above which the
# fused one-kernel path beats the three-stage XLA path. Same
# measured-platforms-only policy as `_BANK_GATHER_MIN_EVENTS`:
# DELIBERATELY EMPTY — including "tpu":
# the fused-vs-xla crossover is not measured on the chip, so
# serve_form="auto" resolves to "xla" everywhere today. CPU gets no
# entry either way: the interpret-mode
# emulation is a correctness vehicle, never a fast path
# (docs/FUSED_r15_cpu.json records the measured emulation rate).
_SERVE_FUSED_MIN_EVENTS: dict[str, float] = {}


def _default_interpret() -> bool:
    """Interpret everywhere but a real TPU (Mosaic is TPU-only; the
    emulation is trace-time, so it jits and vmaps like any jnp code).
    ONIX_PALLAS_INTERPRET=0/1 pins either way for experiments.

    Keyed off the PHYSICAL device platform, not jax.default_backend():
    the verify/test idiom for driving TPU trace arms on CPU mocks
    default_backend, and the kernel must keep emulating there — only
    hardware that can actually run Mosaic should compile it. A device
    probe that fails propagates: on a TPU the mode is compiled or the
    call fails, never a quiet emulation."""
    env = os.environ.get("ONIX_PALLAS_INTERPRET")
    if env in ("0", "1"):
        return env == "1"
    return jax.devices()[0].platform != "tpu"


def pallas_mode() -> str:
    """"compiled" (Mosaic) or "interpret" (XLA emulation) — the mode
    the kernel runs in here; chip_smoke.py stamps it."""
    return "interpret" if _default_interpret() else "compiled"


def select_serve_form(form: str, n_events: int,
                      backend: str | None = None) -> str:
    """Resolve the serving-scan form for one request/dispatch.

    Priority (config.resolve_form_gate — the shared chain with
    select_bank_form): ONIX_SERVE_FORM env override >
    explicit config form > the measured `_SERVE_FUSED_MIN_EVENTS`
    table for this backend > "xla". Both forms are bit-identical
    (winners, scores, tie order), so this is pure performance."""
    def measured() -> str | None:
        b = backend if backend is not None else jax.default_backend()
        min_events = _SERVE_FUSED_MIN_EVENTS.get(b)
        if min_events is not None and n_events >= min_events:
            return "fused"
        return None

    return resolve_form_gate(gate="serve_form", choices=("xla", "fused"),
                             explicit=form, env_var="ONIX_SERVE_FORM",
                             measured=measured, default="xla")


# ---------------------------------------------------------------------------
# The kernel.
# ---------------------------------------------------------------------------

# Sentinel index base for the empty winner-buffer slots: distinct
# int32 values above any real event index (per-call event counts are
# int32-indexed, far below 2^31 - M), so every (score, index) pair in
# the merge is unique and the rank arithmetic stays a permutation.
# They only ever pair with +inf, and +inf rows finalize to index -1.
def _sentinel_base(max_results: int) -> int:
    return (1 << 31) - max_results


def _lt(sa, ia, sb, ib):
    """Strict lexicographic (score, index) less-than — the total order
    `_merge_bottom_k` + `_finalize_topk` implement (ties keep the
    lower global index)."""
    return (sa < sb) | ((sa == sb) & (ia < ib))


def _member_cols(khi, klo, hi_ref, lo_ref):
    """bool [tile, 1]: (hi, lo) keys present in a sorted sentinel-
    padded (hi, lo) table ref of shape [1, F] — filter._member's
    semantics as a branchless tiled compare-reduce (module doc, item
    2). The all-sentinel (empty) table yields constant False for any
    real key."""
    f = int(hi_ref.shape[1])
    hit = jnp.zeros(khi.shape, jnp.bool_)
    for lo0 in range(0, f, _FILTER_SEARCH_TILE):
        width = min(_FILTER_SEARCH_TILE, f - lo0)
        hi_row = hi_ref[0:1, lo0:lo0 + width]
        lo_row = lo_ref[0:1, lo0:lo0 + width]
        eq = (khi == hi_row) & (klo == lo_row)      # [tile, width]
        hit = hit | jnp.any(eq, axis=1, keepdims=True)
    return hit


def _make_kernel(*, tile, n, max_results, mode, filtered, token_words,
                 use_mask, return_scores):
    """Build the fused kernel body for one static configuration. The
    ref order must match the in_specs/out_specs built in _fused_call."""

    def kernel(*refs):
        it = iter(refs)
        if mode == "dot":
            t_ref, p_ref = next(it), next(it)
        elif mode == "min2":
            sa_ref, sb_ref = next(it), next(it)
        else:                                       # "scores"
            s_ref = next(it)
        m_ref = next(it) if use_mask else None
        if filtered:
            if token_words:
                wa_ref, wb_ref = next(it), next(it)
            else:
                wl_ref = next(it)
            ph_ref, pl_ref = next(it), next(it)
            ws_hi, ws_lo = next(it), next(it)
            wb_hi, wb_lo = next(it), next(it)
            ps_hi, ps_lo = next(it), next(it)
            pb_hi, pb_lo = next(it), next(it)
            scale_ref = next(it)
        tol_ref = next(it)
        best_s_ref, best_i_ref = next(it), next(it)
        ev_ref = next(it) if return_scores else None

        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            # Empty buffer: +inf scores, distinct sentinel indices
            # (see _sentinel_base) so the merge order stays total.
            best_s_ref[:] = jnp.full((max_results, 1), jnp.inf,
                                     jnp.float32)
            best_i_ref[:] = _sentinel_base(max_results) \
                + jax.lax.broadcasted_iota(jnp.int32, (max_results, 1), 0)

        def word_adjust(s, wlo):
            """Token-level word adjustment (streaming tail order):
            HostFilter.apply_word's boost-then-suppress on one score
            column."""
            whi = jnp.zeros_like(wlo)
            boo = _member_cols(whi, wlo, wb_hi, wb_lo)
            s = jnp.where(boo, s * scale_ref[0, 0], s)
            sup = _member_cols(whi, wlo, ws_hi, ws_lo)
            return jnp.where(sup, jnp.inf, s)

        # 1. scores ------------------------------------------------------
        if mode == "dot":
            # The exact ops of scoring.score_events on the same
            # gathered rows: elementwise product, sum over K.
            s = jnp.sum(t_ref[:].astype(jnp.float32)
                        * p_ref[:].astype(jnp.float32),
                        axis=1, keepdims=True)
        elif mode == "min2":
            sa, sb = sa_ref[:], sb_ref[:]
            if filtered and token_words:
                sa = word_adjust(sa, wa_ref[:])
                sb = word_adjust(sb, wb_ref[:])
            s = jnp.minimum(sa, sb)
        else:
            s = s_ref[:]

        # 2. filter membership ------------------------------------------
        if filtered:
            if token_words:
                # Word stage already ran per token; pair stage here —
                # HostFilter.apply_pair's boost-then-suppress.
                boo = _member_cols(ph_ref[:], pl_ref[:], pb_hi, pb_lo)
                s = jnp.where(boo, s * scale_ref[0, 0], s)
                sup = _member_cols(ph_ref[:], pl_ref[:], ps_hi, ps_lo)
                s = jnp.where(sup, jnp.inf, s)
            else:
                # filter.apply_filter's exact order: ONE combined
                # boost where (word | pair members scale once), then
                # one combined suppress where.
                wlo = wl_ref[:]
                whi = jnp.zeros_like(wlo)
                boo = _member_cols(whi, wlo, wb_hi, wb_lo) \
                    | _member_cols(ph_ref[:], pl_ref[:], pb_hi, pb_lo)
                s = jnp.where(boo, s * scale_ref[0, 0], s)
                sup = _member_cols(whi, wlo, ws_hi, ws_lo) \
                    | _member_cols(ph_ref[:], pl_ref[:], ps_hi, ps_lo)
                s = jnp.where(sup, jnp.inf, s)

        if return_scores:
            # Post-filter, pre-screen: the full adjusted score stream
            # (the streaming tail's BatchResult.scores contract).
            ev_ref[:] = s

        # tol screen + tail-pad/mask rejection, the _scan_bottom_k
        # order: score_chunk's (mask & s < tol) then the global-index
        # pad mask.
        idx = i * tile + jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
        valid = idx < n
        if use_mask:
            valid = valid & (m_ref[:] > 0)
        s = jnp.where(valid & (s < tol_ref[0, 0]), s, jnp.inf)

        # 3. bottom-M merge by exact rank arithmetic --------------------
        bs, bi = best_s_ref[:], best_i_ref[:]           # [M, 1] sorted
        ts, ti = s, idx                                 # [tile, 1]
        # cross[k, j] = lt(buffer_k, tile_j); the order is total and
        # strict (indices unique), so lt(tile_j, buffer_k) == ~cross.
        cross = _lt(bs, bi, ts.T, ti.T)                 # [M, tile]
        lt_tt = _lt(ts, ti, ts.T, ti.T)                 # [tile, tile]
        rank_t = jnp.sum(lt_tt.astype(jnp.int32), axis=0,
                         keepdims=True).T               # [tile, 1]
        cross_i = cross.astype(jnp.int32)
        c_t = jnp.sum(cross_i, axis=0, keepdims=True).T  # [tile, 1]
        b_off = tile - jnp.sum(cross_i, axis=1, keepdims=True)  # [M, 1]
        pos_b = jax.lax.broadcasted_iota(jnp.int32, (max_results, 1), 0) \
            + b_off
        pos_t = rank_t + c_t
        pos = jnp.concatenate([pos_b, pos_t], axis=0).T  # [1, M + tile]
        s_row = jnp.concatenate([bs, ts], axis=0).T
        i_row = jnp.concatenate([bi, ti], axis=0).T
        # Select-sum scatter: positions are a permutation of
        # 0..M+tile-1, so each output row matches EXACTLY one
        # candidate; where() moves the value (never inf * 0), the sum
        # collapses the zeros.
        for m0 in range(0, max_results, _SCATTER_BLOCK):
            mb = min(_SCATTER_BLOCK, max_results - m0)
            rows = m0 + jax.lax.broadcasted_iota(jnp.int32, (mb, 1), 0)
            eq = rows == pos                            # [mb, M + tile]
            best_s_ref[m0:m0 + mb] = jnp.sum(
                jnp.where(eq, s_row, 0.0), axis=1, keepdims=True)
            best_i_ref[m0:m0 + mb] = jnp.sum(
                jnp.where(eq, i_row, 0), axis=1, keepdims=True)

    return kernel


def _col(a, dtype=None):
    a = jnp.asarray(a)
    if dtype is not None:
        a = a.astype(dtype)
    return a.reshape(-1, 1)


def _row(a):
    return jnp.asarray(a).reshape(1, -1)


@functools.partial(jax.jit, static_argnames=(
    "mode", "max_results", "token_words", "return_scores", "interpret"))
def _fused_call(ops, mask, word_keys, pair_keys, filt, tol, *, mode,
                max_results, token_words=False, return_scores=False,
                interpret=True):
    """Shared wrapper: pad the event streams to a tile multiple, build
    the spec lists to match _make_kernel's ref order, run the one
    fused program, finalize (+inf slots -> index -1, the
    _finalize_topk contract).

    ops: ("dot": (theta_rows [N,K], phi_rows [N,K])) | ("min2":
    (sa [N], sb [N])) | ("scores": (s [N],)).
    mask: f32 [N] or None. word_keys: uint32 [N] event word lo-half, or
    (wa, wb) token pair under token_words, or None when filt is None.
    pair_keys: (hi, lo) uint32 [N] or None. filt: FilterTables or None
    (the static unfiltered fast path — compiles without any membership
    search)."""
    n = int(ops[0].shape[0])
    filtered = filt is not None
    if n == 0:
        empty = _empty_topk(max_results)
        if return_scores:
            return empty, jnp.zeros((0,), jnp.float32)
        return empty
    tile = min(_SERVE_TILE, max(-(-n // 8) * 8, 8))
    bp = -(-n // tile) * tile
    pad = bp - n

    def padded(a):
        a = jnp.asarray(a)
        return jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1)) \
            if pad else a

    inputs, specs = [], []

    def add_tiled(a, width):
        inputs.append(padded(a))
        specs.append(pl.BlockSpec((tile, width), lambda i: (i, 0)))

    def add_const(a, width):
        inputs.append(a)
        specs.append(pl.BlockSpec((1, width), lambda i: (0, 0)))

    if mode == "dot":
        k = int(ops[0].shape[1])
        add_tiled(ops[0], k)
        add_tiled(ops[1], k)
    elif mode == "min2":
        add_tiled(_col(ops[0], jnp.float32), 1)
        add_tiled(_col(ops[1], jnp.float32), 1)
    elif mode == "scores":
        add_tiled(_col(ops[0], jnp.float32), 1)
    else:
        raise ValueError(f"mode must be dot|min2|scores, got {mode!r}")
    use_mask = mask is not None
    if use_mask:
        add_tiled(_col(mask, jnp.float32), 1)
    if filtered:
        if token_words:
            add_tiled(_col(word_keys[0], jnp.uint32), 1)
            add_tiled(_col(word_keys[1], jnp.uint32), 1)
        else:
            add_tiled(_col(word_keys, jnp.uint32), 1)
        add_tiled(_col(pair_keys[0], jnp.uint32), 1)
        add_tiled(_col(pair_keys[1], jnp.uint32), 1)
        for fam in (filt.word_suppress, filt.word_boost,
                    filt.pair_suppress, filt.pair_boost):
            hi, lo = fam
            add_const(_row(hi), int(hi.shape[-1]))
            add_const(_row(lo), int(lo.shape[-1]))
        add_const(jnp.reshape(jnp.asarray(filt.boost_scale,
                                          jnp.float32), (1, 1)), 1)
    add_const(jnp.reshape(jnp.asarray(tol, jnp.float32), (1, 1)), 1)

    out_specs = [
        # Constant index maps: the winner buffer stays VMEM-resident
        # across the whole grid and flushes to HBM once per request.
        pl.BlockSpec((max_results, 1), lambda i: (0, 0)),
        pl.BlockSpec((max_results, 1), lambda i: (0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((max_results, 1), jnp.float32),
        jax.ShapeDtypeStruct((max_results, 1), jnp.int32),
    ]
    if return_scores:
        out_specs.append(pl.BlockSpec((tile, 1), lambda i: (i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((bp, 1), jnp.float32))

    kern = _make_kernel(tile=tile, n=n, max_results=max_results,
                        mode=mode, filtered=filtered,
                        token_words=token_words, use_mask=use_mask,
                        return_scores=return_scores)
    out = pl.pallas_call(kern, grid=(bp // tile,), in_specs=specs,
                         out_specs=out_specs, out_shape=out_shape,
                         interpret=interpret)(*inputs)
    best_s, best_i = out[0][:, 0], out[1][:, 0]
    topk = TopK(scores=best_s,
                indices=jnp.where(jnp.isfinite(best_s), best_i, -1))
    if return_scores:
        return topk, out[2][:n, 0]
    return topk


def _resolve_interpret(interpret):
    return _default_interpret() if interpret is None else bool(interpret)


# ---------------------------------------------------------------------------
# Entry points — one per consumer of the scan machinery.
# ---------------------------------------------------------------------------


def fused_top_suspicious(theta, phi_wk, doc_ids, word_ids, mask,
                         pair_hi=None, pair_lo=None, filt=None, *,
                         tol: float, max_results: int,
                         interpret=None) -> TopK:
    """The fused arm of `scoring.top_suspicious` /
    `rescore.top_suspicious_filtered`: theta/phi rows gather outside
    (Mosaic has no gather rule — the r8 discipline), score + filter +
    bottom-M run in one kernel. filt=None compiles the static
    unfiltered program. Single-estimate tables only (combine chains
    upstream, like the screened variants)."""
    theta = jnp.asarray(theta)
    if theta.ndim != 2:
        raise ValueError("fused serving covers single-estimate tables; "
                         "combine chains upstream")
    rows_t = theta[jnp.asarray(doc_ids)]
    rows_p = jnp.asarray(phi_wk)[jnp.asarray(word_ids)]
    return _fused_call(
        (rows_t, rows_p), mask,
        None if filt is None else jnp.asarray(word_ids),
        None if filt is None else (pair_hi, pair_lo), filt, tol,
        mode="dot", max_results=max_results,
        interpret=_resolve_interpret(interpret))


def fused_table_pair_bottom_k(table_flat, idx_src, idx_dst,
                              word_ids=None, pair_hi=None, pair_lo=None,
                              filt=None, *, tol: float, max_results: int,
                              interpret=None) -> TopK:
    """The fused arm of `table_pair_bottom_k(_filtered)` — the flow
    10^8+-event serving path: the two table gathers run outside, the
    pair-min + filter + bottom-M in one kernel."""
    table_flat = jnp.asarray(table_flat)
    sa = table_flat[jnp.asarray(idx_src)]
    sb = table_flat[jnp.asarray(idx_dst)]
    return _fused_call(
        (sa, sb), None,
        None if filt is None else jnp.asarray(word_ids),
        None if filt is None else (pair_hi, pair_lo), filt, tol,
        mode="min2", max_results=max_results,
        interpret=_resolve_interpret(interpret))


def fused_table_bottom_k(table_flat, idx, word_ids=None, pair_hi=None,
                         pair_lo=None, filt=None, *, tol: float,
                         max_results: int, interpret=None) -> TopK:
    """The fused arm of `table_bottom_k(_filtered)` (dns/proxy)."""
    table_flat = jnp.asarray(table_flat)
    return _fused_call(
        (table_flat[jnp.asarray(idx)],), None,
        None if filt is None else jnp.asarray(word_ids),
        None if filt is None else (pair_hi, pair_lo), filt, tol,
        mode="scores", max_results=max_results,
        interpret=_resolve_interpret(interpret))


def fused_bottom_k_scores(scores, word_ids=None, pair_hi=None,
                          pair_lo=None, filt=None, *, tol: float,
                          max_results: int, interpret=None) -> TopK:
    """Fused filter + bottom-M over precomputed scores — the
    `scoring.bottom_k` shape, and the tail the bank's gather form
    reuses."""
    return _fused_call(
        (jnp.asarray(scores),), None,
        None if filt is None else jnp.asarray(word_ids),
        None if filt is None else (pair_hi, pair_lo), filt, tol,
        mode="scores", max_results=max_results,
        interpret=_resolve_interpret(interpret))


def fused_stream_tail(tok_src, tok_dst, word_src=None, word_dst=None,
                      pair_hi=None, pair_lo=None, filt=None, *,
                      tol: float, max_results: int, interpret=None):
    """The streaming winner-selection tail (flow device layout): the
    host tail's exact op order — per-token word adjustment, the
    src/dst min-reduce, the pair adjustment, tol screen, bottom-M —
    in one kernel, returning (TopK, adjusted event scores). The score
    stream is the f32 twin of the host float64 tail: identical when
    boost_scale is dyadic (the 0.25 default) and no score sits inside
    the one-ulp f32(tol) gap — StreamingScorer documents the
    contract."""
    return _fused_call(
        (jnp.asarray(tok_src), jnp.asarray(tok_dst)), None,
        None if filt is None else (word_src, word_dst),
        None if filt is None else (pair_hi, pair_lo), filt, tol,
        mode="min2", max_results=max_results, token_words=True,
        return_scores=True, interpret=_resolve_interpret(interpret))


# ---------------------------------------------------------------------------
# The model bank's fused kernels (the r12 vmap/gather pair with the
# scan+filter stages replaced by the fused kernel). Request batching,
# residency, refusals, and the filter-row stacking stay in
# model_bank.py — these are drop-in replacements for
# _bank_score_vmap/_bank_score_gather, bit-identical per request.
# ---------------------------------------------------------------------------


def _bank_row_call(rows_t, rows_p, mr, dr, wr, filt_row, tol, *,
                   max_results, interpret):
    """One request row: the bank's word key is the event word id, the
    pair key the packed (doc, word) identity (model_bank.
    _row_filter_adjust's exact key construction). `filt_row` is one
    request's FilterTables slice (leaves [F]) or None."""
    wl = ph = plo = None
    if filt_row is not None:
        wl = wr.astype(jnp.uint32)
        ph, plo = dr.astype(jnp.uint32), wl
    return _fused_call((rows_t, rows_p), mr, wl,
                       None if filt_row is None else (ph, plo),
                       filt_row, tol, mode="dot",
                       max_results=max_results, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("max_results", "interpret"))
def bank_score_vmap_fused(theta_bank, phi_bank, slots, doc_ids, word_ids,
                          mask, tol, filt_rows, *, max_results: int,
                          interpret=True) -> TopK:
    """Fused twin of `_bank_score_vmap`: one lane per request, the
    lane's table slice + row gathers outside, the fused kernel per
    lane. filt_rows=None is the static no-feedback fast path (no
    membership search compiles)."""
    def one(slot, dr, wr, mr, filt_row=None):
        rows_t = theta_bank[slot][dr]
        rows_p = phi_bank[slot][wr]
        return _bank_row_call(rows_t, rows_p, mr, dr, wr, filt_row,
                              tol, max_results=max_results,
                              interpret=interpret)

    if filt_rows is None:
        return jax.vmap(one)(slots, doc_ids, word_ids, mask)
    return jax.vmap(one)(slots, doc_ids, word_ids, mask, filt_rows)


@functools.partial(jax.jit, static_argnames=("max_results", "interpret"))
def bank_score_gather_fused(theta_bank, phi_bank, slots, doc_ids,
                            word_ids, mask, tol, filt_rows, *,
                            max_results: int, interpret=True) -> TopK:
    """Fused twin of `_bank_score_gather`: the tenant-composed flat
    row gathers run as ONE fused stream outside the kernel (the gather
    form's whole point), then the per-request fused kernel scores,
    filters and selects from the gathered rows."""
    b, d_pad, _ = theta_bank.shape
    v_pad = phi_bank.shape[1]
    gd = (slots[:, None] * jnp.int32(d_pad) + doc_ids).reshape(-1)
    gw = (slots[:, None] * jnp.int32(v_pad) + word_ids).reshape(-1)
    rows_t = theta_bank.reshape(b * d_pad, -1)[gd].reshape(
        (*doc_ids.shape, -1))
    rows_p = phi_bank.reshape(b * v_pad, -1)[gw].reshape(
        (*word_ids.shape, -1))

    def one(rt, rp, dr, wr, mr, filt_row=None):
        return _bank_row_call(rt, rp, mr, dr, wr, filt_row, tol,
                              max_results=max_results,
                              interpret=interpret)

    if filt_rows is None:
        return jax.vmap(one)(rows_t, rows_p, doc_ids, word_ids, mask)
    return jax.vmap(one)(rows_t, rows_p, doc_ids, word_ids, mask,
                         filt_rows)
