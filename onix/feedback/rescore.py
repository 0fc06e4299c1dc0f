"""Filtered selection scans: the noise filter fused into bottom-k.

Each entry point is its unfiltered `onix.models.scoring` twin plus the
`apply_filter` adjustment inside the per-chunk score function — the
SAME `_scan_bottom_k` machinery (chunking, pad masking, running
bottom-k merge, tie rule, -1 sentinel), so a fix to selection logic
still lands in exactly one place and a filtered scan with an empty
filter is bit-identical to the unfiltered scan (filter.py exactness
contract; tests/test_feedback.py).

Key streams ride the scan as extra chunked columns: the event's word
id (its word key — hi half is an implicit 0) and the packed pair
identity as uint32 (hi, lo) halves (`filter.split_key` of
`filter.pack_pair` keys — (src, dst) docs for flow, (doc, word) for
the single-doc datatypes; 64-bit columns cannot ride the device in
x32). The filter applies BEFORE the tol screen: a boosted
(confirmed-threat) event whose scaled score clears tol stays in the
winner set; a suppressed event never reaches the merge.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from onix.feedback.filter import FilterTables, apply_filter
from onix.models.scoring import TopK, _scan_bottom_k, _subscan_scores


def _word_halves(wc):
    """Word ids → (hi, lo) uint32 key halves (word keys are < 2^32, so
    hi is constant 0)."""
    lo = wc.astype(jnp.uint32)
    return jnp.zeros_like(lo), lo


@functools.partial(jax.jit,
                   static_argnames=("max_results", "chunk", "merge_buffer"))
def top_suspicious_filtered(
    theta: jax.Array,
    phi_wk: jax.Array,
    doc_ids: jax.Array,       # int32 [N]
    word_ids: jax.Array,      # int32 [N]
    mask: jax.Array,          # float32 [N] 0.0 for padding
    pair_hi: jax.Array,       # uint32 [N] packed-pair high half
    pair_lo: jax.Array,       # uint32 [N] packed-pair low half
    filt: FilterTables,
    *,
    tol: float,
    max_results: int,
    chunk: int = 1 << 20,
    merge_buffer: int | None = None,
) -> TopK:
    """`top_suspicious` with the fused noise-filter adjustment. The
    word key is the event's own word id; the pair halves carry
    whatever pair identity the caller filters on."""

    def score_chunk(dc, wc, ph, pl, mc):
        s = _subscan_scores(theta, phi_wk, dc, wc)
        s = apply_filter(s, _word_halves(wc), (ph, pl), filt)
        return jnp.where((mc > 0) & (s < tol), s, jnp.inf)

    return _scan_bottom_k((doc_ids, word_ids, pair_hi, pair_lo, mask),
                          doc_ids.shape[0], score_chunk,
                          max_results=max_results, chunk=chunk,
                          merge_buffer=merge_buffer)


@functools.partial(jax.jit,
                   static_argnames=("max_results", "chunk", "merge_buffer"))
def table_bottom_k_filtered(
    table_flat: jax.Array,   # float32 [D*V] from score_table().ravel()
    idx: jax.Array,          # int32 [N] flat index d*V + w per event
    word_ids: jax.Array,     # int32/uint32 [N] the event's word id
    pair_hi: jax.Array,      # uint32 [N]
    pair_lo: jax.Array,      # uint32 [N]
    filt: FilterTables,
    *,
    tol: float,
    max_results: int,
    chunk: int = 1 << 21,
    merge_buffer: int | None = None,
) -> TopK:
    """`table_bottom_k` (dns/proxy fused path) with the filter fused
    into the same scan."""

    def score_chunk(ii, wc, ph, pl):
        s = table_flat[ii]
        s = apply_filter(s, _word_halves(wc), (ph, pl), filt)
        return jnp.where(s < tol, s, jnp.inf)

    return _scan_bottom_k((idx, word_ids, pair_hi, pair_lo),
                          idx.shape[0], score_chunk,
                          max_results=max_results, chunk=chunk,
                          merge_buffer=merge_buffer)


@functools.partial(jax.jit,
                   static_argnames=("max_results", "chunk", "merge_buffer"))
def table_pair_bottom_k_filtered(
    table_flat: jax.Array,   # float32 [D*V] from score_table().ravel()
    idx_src: jax.Array,      # int32 [N] flat index d_src*V + w per event
    idx_dst: jax.Array,      # int32 [N] flat index d_dst*V + w per event
    word_ids: jax.Array,     # int32/uint32 [N] the event's word id
    pair_hi: jax.Array,      # uint32 [N] src-doc half of the pair key
    pair_lo: jax.Array,      # uint32 [N] dst-doc half
    filt: FilterTables,
    *,
    tol: float,
    max_results: int,
    chunk: int = 1 << 21,
    merge_buffer: int | None = None,
) -> TopK:
    """`table_pair_bottom_k` (the flow 10⁸⁺-event path) with the
    filter fused into the same scan — the (src, dst)-pair suppression
    of PAPER.md §L5's noise filter, applied after the pair-min and
    before the tol screen."""

    def score_chunk(si, di, wc, ph, pl):
        s = jnp.minimum(table_flat[si], table_flat[di])
        s = apply_filter(s, _word_halves(wc), (ph, pl), filt)
        return jnp.where(s < tol, s, jnp.inf)

    return _scan_bottom_k((idx_src, idx_dst, word_ids, pair_hi, pair_lo),
                          idx_src.shape[0], score_chunk,
                          max_results=max_results, chunk=chunk,
                          merge_buffer=merge_buffer)


# ---------------------------------------------------------------------------
# Serve-gated dispatchers (r15): each is its filtered scan above plus
# the one-kernel fused arm behind `pallas_serve.select_serve_form`
# (serving.serve_form / ONIX_SERVE_FORM; "auto" resolves to the XLA
# scan on every backend until a measured crossover table entry lands).
# Both arms are bit-identical — winners, scores, tie order — so the
# dispatch is pure performance (tests/test_pallas_serve.py).
# ---------------------------------------------------------------------------


def top_suspicious_filtered_fast(theta, phi_wk, doc_ids, word_ids, mask,
                                 pair_hi, pair_lo, filt: FilterTables, *,
                                 tol: float, max_results: int,
                                 serve_form: str = "auto") -> TopK:
    """`top_suspicious_filtered` behind the serve gate. Chained tables
    (theta.ndim == 3) always take the XLA scan — the fused arm covers
    single-estimate tables only."""
    from onix.models import pallas_serve
    form = pallas_serve.select_serve_form(serve_form, doc_ids.shape[0])
    if form == "fused" and jnp.asarray(theta).ndim == 2:
        return pallas_serve.fused_top_suspicious(
            theta, phi_wk, doc_ids, word_ids, mask, pair_hi, pair_lo,
            filt, tol=tol, max_results=max_results)
    return top_suspicious_filtered(theta, phi_wk, doc_ids, word_ids,
                                   mask, pair_hi, pair_lo, filt,
                                   tol=tol, max_results=max_results)


def table_bottom_k_filtered_fast(table_flat, idx, word_ids, pair_hi,
                                 pair_lo, filt: FilterTables, *,
                                 tol: float, max_results: int,
                                 serve_form: str = "auto") -> TopK:
    """`table_bottom_k_filtered` behind the serve gate."""
    from onix.models import pallas_serve
    form = pallas_serve.select_serve_form(serve_form, idx.shape[0])
    if form == "fused":
        return pallas_serve.fused_table_bottom_k(
            table_flat, idx, word_ids, pair_hi, pair_lo, filt,
            tol=tol, max_results=max_results)
    return table_bottom_k_filtered(table_flat, idx, word_ids, pair_hi,
                                   pair_lo, filt, tol=tol,
                                   max_results=max_results)


def table_pair_bottom_k_filtered_fast(table_flat, idx_src, idx_dst,
                                      word_ids, pair_hi, pair_lo,
                                      filt: FilterTables, *, tol: float,
                                      max_results: int,
                                      serve_form: str = "auto") -> TopK:
    """`table_pair_bottom_k_filtered` (the judged filtered flow path)
    behind the serve gate."""
    from onix.models import pallas_serve
    form = pallas_serve.select_serve_form(serve_form, idx_src.shape[0])
    if form == "fused":
        return pallas_serve.fused_table_pair_bottom_k(
            table_flat, idx_src, idx_dst, word_ids, pair_hi, pair_lo,
            filt, tol=tol, max_results=max_results)
    return table_pair_bottom_k_filtered(table_flat, idx_src, idx_dst,
                                        word_ids, pair_hi, pair_lo,
                                        filt, tol=tol,
                                        max_results=max_results)
