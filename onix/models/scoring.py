"""Post-LDA event scoring and suspicious-connects selection.

The reference's FlowPostLDA/DNSPostLDA/ProxyPostLDA Spark jobs broadcast
theta and phi to executors and score every raw event as
`score(event) = sum_k theta[ip,k] * phi[k,word]`, then filter `< TOL`,
sort ascending, and keep MAXRESULTS (SURVEY.md §2.1 #11, §3.1 hot loop
POST-LDA; reference README.md:42 "filter billion of events to a few
thousands"). Low probability under the topic model == suspicious.

onix renders this as a chunked `lax.scan` carrying a running bottom-M
set, so 1B events stream through a single compiled program with O(M)
memory — the throughput-critical path of the judged metric
"netflow events scored/sec/chip" (BASELINE.json).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from onix.utils.obs import device_scope


def score_events(theta: jax.Array, phi_wk: jax.Array,
                 doc_ids: jax.Array, word_ids: jax.Array) -> jax.Array:
    """p(word | doc) = sum_k theta[d,k] * phi_wk[w,k] — one gather-dot per
    event; K rides the VPU lanes.

    Multi-chain estimates (theta [C,D,K], phi_wk [C,V,K] from
    `LDAConfig.n_chains > 1`) combine the per-chain probabilities with a
    GEOMETRIC mean — score-averaging, not matrix-averaging, so topic
    label switching between chains cannot corrupt the estimate. Geometric
    beats arithmetic for rank stability of the suspicious tail (an event
    must be low under EVERY chain to stay in the bottom-k): measured
    top-1k ensemble-vs-ensemble overlap 0.959 vs 0.950 at C=8 on the
    100k-event flow rehearsal (docs/OVERLAP.md).
    """
    if theta.ndim == 2:
        # Upcast AFTER the gather: with bf16 tables-at-rest the gather
        # moves half the bytes and the dot still accumulates in f32
        # (free when the tables are already f32).
        return jnp.sum(theta[doc_ids].astype(jnp.float32)
                       * phi_wk[word_ids].astype(jnp.float32), axis=-1)
    p = jnp.sum(theta[:, doc_ids].astype(jnp.float32)
                * phi_wk[:, word_ids].astype(jnp.float32), axis=-1)
    return jnp.exp(jnp.log(jnp.maximum(p, 1e-38)).mean(axis=0))


class TopK(NamedTuple):
    scores: jax.Array   # float32 [M] ascending-suspicious (smallest first)
    indices: jax.Array  # int32 [M] global event index; -1 where fewer than
    #                     M events qualified (score is +inf there)


def _finalize_topk(scores: jax.Array, indices: jax.Array) -> TopK:
    order = jnp.argsort(scores)
    scores, indices = scores[order], indices[order]
    # Unfilled slots (fewer than max_results qualifying events) carry +inf
    # scores; force their indices to the -1 sentinel so a consumer can
    # never gather a real event row through a padding slot.
    indices = jnp.where(jnp.isfinite(scores), indices, -1)
    return TopK(scores=scores, indices=indices)


def _padded_cols(arrays: tuple, n: int, chunk: int):
    """Pad arrays to a chunk multiple; the scan slices chunk `ci` out
    of each at `ci * chunk`. Shapes are static under jit, so the pad
    amount is compile-time. (Not reshaped to [n_chunks, chunk] scan
    columns: on the TPU that is a tiled relayout of every column, done
    by loops the compiler makes and names nothing - 2% of a fused-scan
    chunk once the look-ups were cheap; PERF.md section 6, PR 27.)"""
    chunk = min(chunk, max(n, 1))
    pad = (-n) % chunk
    if pad:
        arrays = tuple(jnp.pad(a, (0, pad)) for a in arrays)
    base = jnp.arange(chunk, dtype=jnp.int32)
    return arrays, base, (n + pad) // chunk, chunk


def _empty_topk(max_results: int) -> TopK:
    return TopK(scores=jnp.full((max_results,), jnp.inf, jnp.float32),
                indices=jnp.full((max_results,), -1, jnp.int32))


def _merge_bottom_k(best_s, best_i, s, idx, max_results: int):
    """Merge chunk scores into the running bottom-k. Ties keep the
    lower concat position, so incumbents always beat later arrivals at
    an equal score — every _scan_bottom_k entry point relies on this
    for determinism."""
    cat_s = jnp.concatenate([best_s, s])
    cat_i = jnp.concatenate([best_i, idx])
    neg, pos = jax.lax.top_k(-cat_s, max_results)
    return -neg, cat_i[pos]


def _scan_bottom_k(arrays: tuple, n: int, score_chunk, *,
                   max_results: int, chunk: int,
                   merge_buffer: int | None = None) -> TopK:
    """Shared running-bottom-k machinery: chunk the input arrays
    together, score each chunk with `score_chunk(*chunk_cols)` (which
    must already return +inf for rows it rejects), mask the tail pad by
    global index, and merge a running bottom-`max_results` through one
    `lax.scan`. Every selection entry point (bottom_k, top_suspicious,
    table_pair_bottom_k) is this scan plus a per-chunk score function —
    a fix to the selection logic lands in exactly one place.

    `merge_buffer=B` turns on the two-phase merge: count the chunk's
    candidates (scores below the running k-th best); when they fit in
    B, merge only the chunk's bottom-B instead of concatenating the
    whole chunk into top_k. Once the threshold tightens (a few chunks
    in), expected candidates per chunk fall toward k/chunks_seen, so
    the steady-state merge is O(k+B), not O(k+chunk). EXACT either way:
    count > B falls back to the full merge inside the same lax.cond —
    never a lossy cap."""
    if n == 0:     # static shape: resolved at trace time, not per-call
        return _empty_topk(max_results)
    # `onix.select` names everything here but `score_chunk` (device
    # scopes: docs/OBSERVABILITY.md); the caller names its own scoring.
    with device_scope("onix.select"):
        cols, base, n_chunks, chunk = _padded_cols(arrays, n, chunk)

    def step(carry, ci):
        best_s, best_i = carry
        with device_scope("onix.select"):
            cs = [jax.lax.dynamic_slice_in_dim(a, ci * chunk, chunk)
                  for a in cols]
        scored = score_chunk(*cs)
        with device_scope("onix.select"):
            idx = ci * chunk + base
            s = jnp.where(idx < n, scored, jnp.inf)
            if merge_buffer is None or merge_buffer >= chunk:
                return _merge_bottom_k(best_s, best_i, s, idx,
                                       max_results), None

            def small_merge():
                # All candidates fit the buffer: the chunk's bottom-B is
                # a superset of them (anything outside is >= the
                # threshold and loses to an incumbent at the final
                # top_k's tie rule).
                neg, pos = jax.lax.top_k(-s, merge_buffer)
                return _merge_bottom_k(best_s, best_i, -neg, idx[pos],
                                       max_results)

            n_cand = jnp.sum(s < best_s[-1])    # running k-th best
            return jax.lax.cond(
                n_cand <= merge_buffer, small_merge,
                lambda: _merge_bottom_k(best_s, best_i, s, idx,
                                        max_results)), None

    (out_s, out_i), _ = jax.lax.scan(
        step, tuple(_empty_topk(max_results)),
        jnp.arange(n_chunks, dtype=jnp.int32))
    with device_scope("onix.select"):
        return _finalize_topk(out_s, out_i)


@functools.partial(jax.jit,
                   static_argnames=("max_results", "chunk", "merge_buffer"))
def bottom_k(
    scores: jax.Array,        # float32 [N] precomputed event scores
    *,
    tol: float,
    max_results: int,
    chunk: int = 1 << 20,
    merge_buffer: int | None = None,
) -> TopK:
    """Bottom-`max_results` among precomputed scores < tol — the selection
    half of `top_suspicious` for callers that aggregate scores before
    selecting (e.g. flow events take the min over src/dst-doc tokens)."""
    return _scan_bottom_k(
        (scores,), scores.shape[0],
        lambda sc: jnp.where(sc < tol, sc, jnp.inf),
        max_results=max_results, chunk=chunk, merge_buffer=merge_buffer)


@functools.partial(jax.jit, static_argnames=("max_results", "chunk",
                                             "merge_buffer", "table_dtype"))
def top_suspicious(
    theta: jax.Array,
    phi_wk: jax.Array,
    doc_ids: jax.Array,       # int32 [N]
    word_ids: jax.Array,      # int32 [N]
    mask: jax.Array,          # float32 [N] 0.0 for padding
    *,
    tol: float,
    max_results: int,
    chunk: int = 1 << 20,
    merge_buffer: int | None = None,
    table_dtype: str | None = None,
) -> TopK:
    """Bottom-`max_results` events by score among those with score < tol.

    N is padded internally to a chunk multiple (shapes are static under
    jit, so the pad amount is compile-time). Padding and above-threshold
    events are pushed to +inf so they never enter the result set. Single
    fused scan — no host round-trips.

    The chunk's scores are computed through an inner scan over 1/8-chunk
    slices: with top_k as the gather-dot's direct consumer XLA
    materializes both gathered [chunk, K] operands in lane-padded
    [chunk, 128] layout (~6.4x traffic); the inner scan gives the
    gather-dot a cheap [sub] consumer so it fuses, and only [chunk]
    f32 scores reach top_k.

    A branch-and-bound variant (prune events whose score lower bound
    `θmax[d]·φ[w, argmax θ[d]]` beats the running k-th best) was built,
    proven exact, and REJECTED on measurement: the single-coordinate
    bound underestimates the score so badly that 11-61% of events stay
    candidates in every regime tried — diffuse tables, peaked tables,
    even model-generated (fitted-telemetry-like) events — so the scan
    always fell back to exhaustive scoring plus bound overhead (2.8x
    slower, over a remote device link, before PR 21). Don't rebuild it
    without a fundamentally tighter bound.

    `merge_buffer` enables the exact two-phase merge (_scan_bottom_k);
    `table_dtype="bfloat16"` stores the gathered tables at half width
    (1.52x on the materialization-bound form, over a remote device
    link, before PR 21 — scores then round at bf16 precision, so keep
    it off where the 0.95 overlap bar is being judged unless the
    overlap study revalidates it).
    """
    if table_dtype is not None:
        theta = theta.astype(table_dtype)
        phi_wk = phi_wk.astype(table_dtype)

    def score_chunk(dc, wc, mc):
        s = _subscan_scores(theta, phi_wk, dc, wc)
        return jnp.where((mc > 0) & (s < tol), s, jnp.inf)

    return _scan_bottom_k((doc_ids, word_ids, mask), doc_ids.shape[0],
                          score_chunk, max_results=max_results, chunk=chunk,
                          merge_buffer=merge_buffer)


def _subscan_scores(theta, phi_wk, dc, wc):
    """score_events over a chunk via an inner scan of 1/8-chunk slices
    — the fusion-isolating form shared by every full-scoring chunk:
    it keeps top_k away from the gather-dot."""
    sub = max(dc.shape[0] // 8, 1)
    if dc.shape[0] % sub:
        return score_events(theta, phi_wk, dc, wc)
    ns = dc.shape[0] // sub

    def sub_step(_, xs):
        sd, sw = xs
        return None, score_events(theta, phi_wk, sd, sw)

    _, s = jax.lax.scan(sub_step, None,
                        (dc.reshape(ns, sub), wc.reshape(ns, sub)))
    return s.reshape(dc.shape[0])


_score_events_jit = jax.jit(score_events)


@jax.jit
def score_table(theta: jax.Array, phi_wk: jax.Array) -> jax.Array:
    """The full [D, V] score matrix θ·φᵀ as ONE matmul.

    Product vocabularies are small by construction (packed words, coarse
    bins — V is hundreds to a few thousand), so D×V usually fits HBM
    comfortably; a single MXU matmul replaces per-event gather-dot pairs
    and per-event scoring becomes the read of one 4-byte entry (the
    gathered-operand dot wastes 108/128 lanes). What that read costs on
    a v5e is measured, not the 250 GB/s once written here: XLA's gather
    of one scalar per index takes 13.4 ns an element (0.3 GB/s), and the
    fused day scan reads the table by rows of 128 lanes instead
    (`device_words._take`, 9.2 ns; PERF.md section 6, PR 30).
    Multi-chain inputs combine with the geometric mean, matching
    score_events."""
    if theta.ndim == 2:
        return theta @ phi_wk.T
    per_chain = jnp.einsum("cdk,cvk->cdv", theta, phi_wk)
    return jnp.exp(jnp.log(jnp.maximum(per_chain, 1e-38)).mean(axis=0))


@jax.jit
def _gather_scores(table_flat: jax.Array, d: jax.Array, w: jax.Array,
                   n_vocab: int) -> jax.Array:
    # int32 flat index is safe: the table is capped at TABLE_MAX_ELEMS
    # (1<<27) elements, far under int32 range.
    return table_flat[d.astype(jnp.int32) * jnp.int32(n_vocab) + w]


# D*V budget for materializing the score table (f32 elements). 1<<27 =
# 512 MB — small next to 16 GB HBM, large enough for D=200k x V=640.
TABLE_MAX_ELEMS = 1 << 27

@functools.partial(jax.jit,
                   static_argnames=("max_results", "chunk", "merge_buffer"))
def table_pair_bottom_k(
    table_flat: jax.Array,   # float32 [D*V] from score_table().ravel()
    idx_src: jax.Array,      # int32 [N] flat index d_src*V + w per event
    idx_dst: jax.Array,      # int32 [N] flat index d_dst*V + w per event
    *,
    tol: float,
    max_results: int,
    chunk: int = 1 << 21,
    merge_buffer: int | None = None,
) -> TopK:
    """Fused flow-event scoring + selection, entirely on device: per
    event, score = min over its two tokens (src-doc and dst-doc gather
    from the θ·φᵀ table), filter < tol, keep the running bottom-k.

    Exists for the 10⁸⁺-event path: the unfused pipeline ships every
    token score to the host (hundreds of MB over the host link),
    takes the pair-min there, and ships event scores back for selection.
    Here only the final [max_results] rows ever leave the device."""

    def score_chunk(si, di):
        s = jnp.minimum(table_flat[si], table_flat[di])
        return jnp.where(s < tol, s, jnp.inf)

    return _scan_bottom_k((idx_src, idx_dst), idx_src.shape[0],
                          score_chunk, max_results=max_results, chunk=chunk,
                          merge_buffer=merge_buffer)


@functools.partial(jax.jit,
                   static_argnames=("max_results", "chunk", "merge_buffer"))
def table_bottom_k(
    table_flat: jax.Array,   # float32 [D*V] from score_table().ravel()
    idx: jax.Array,          # int32 [N] flat index d*V + w per event
    *,
    tol: float,
    max_results: int,
    chunk: int = 1 << 21,
    merge_buffer: int | None = None,
) -> TopK:
    """Fused single-token scoring + selection, entirely on device: the
    dns/proxy analog of `table_pair_bottom_k` (one document — the
    client IP — per event, so score = one flat table gather). Only the
    final [max_results] rows leave the device on the 10⁸⁺-event path."""

    def score_chunk(ii):
        s = table_flat[ii]
        return jnp.where(s < tol, s, jnp.inf)

    return _scan_bottom_k((idx,), idx.shape[0], score_chunk,
                          max_results=max_results, chunk=chunk,
                          merge_buffer=merge_buffer)


# ---------------------------------------------------------------------------
# bf16-screened exact selection
#
# bf16 tables-at-rest halve the gather traffic of the selection scan, but
# raw bf16 scores round at 2^-8 and can flip the top-k set near the
# boundary. The screened variants below keep the bf16 scan as
# a SCREEN only: they retain an oversized candidate buffer by bf16 score,
# rescore just those candidates with the f32 tables, and certify exactness
# on device from the rounding bound.
#
# Soundness argument. Inputs are f32 probabilities in [0,1] rounded once to
# bf16 (8 significand bits incl. the implicit one, unit roundoff u = 2^-8):
# each factor carries relative error <= u/(1+u) < 2^-8, a product of two
# <= (1+2^-8)^2 - 1 < 2^-6.99, and a nonnegative K-term sum accumulated in
# f32 preserves the relative bound while adding < K*2^-23 of its own. So
# for every event
#   bf16_s in [f32_s/(1+REL), f32_s*(1+REL)]   with REL = 2^-6,
# which leaves ~2x headroom over the 2^-6.99 product bound and absorbs the
# f32-accumulation term for any plausible K (equality would need
# K*2^-23 > 2^-6 - 2^-6.99, i.e. K > ~60k topics).
# Let B_max be the WORST bf16 score retained in the candidate buffer and
# s_k the k-th-best f32 score after rescoring. Any excluded event has
# bf16_s >= B_max; if B_max > s_k*(1+REL) then its f32 score is
#   f32_s >= bf16_s/(1+REL) >= B_max/(1+REL) > s_k
# — strictly worse than the k-th result, so the exclusion was safe (and
# strictness rules out boundary ties with excluded events). If the buffer
# never filled, every event passing the inflated tol screen is IN it, which
# covers every event with f32_s < tol outright. Either condition => the
# returned top-k equals the full-f32 scan's, including its
# lower-global-index tie rule (candidates are ordered by (score, index),
# which is the rule _merge_bottom_k + _finalize_topk implement). When
# neither holds the `sound` flag is False and the caller must fall back to
# the f32 path — never silently accept the screened result.
#
# Identity strength differs by variant. The table_* screened variants
# rescore by gathering the SAME f32 table the exact scan gathers — scores
# are bit-identical by construction, so sound=True certifies a
# bit-identical result. top_suspicious_screened's rescore recomputes the
# gather-dot in a separately compiled XLA program, and separately compiled
# programs can differ in the dot's last ulp; sound=True there certifies
# the result up to last-ulp ties at the k-th boundary.
# ---------------------------------------------------------------------------

_SCREEN_REL = 2.0 ** -6


class ScreenedTopK(NamedTuple):
    result: TopK
    sound: jax.Array    # bool [] — True: provably identical to the f32 scan


def _screened_scan(arrays: tuple, n: int, screen_chunk, rescore, *,
                   tol: float, max_results: int, chunk: int,
                   merge_buffer: int | None,
                   buffer_mult: int) -> ScreenedTopK:
    """Screen with bf16 chunk scores into a bottom-(k*buffer_mult) buffer,
    rescore the buffer in f32, and prove exactness (see block comment).

    `screen_chunk(*cols)` returns bf16-rounded scores with mask/tol-screen
    rejects already at +inf (the screen tol must be tol*(1+2*REL) — the
    inflation keeps every f32-qualifying event eligible); `rescore(gidx)`
    returns f32 scores for global event indices, bit-matching the f32
    path's scoring of the same events."""
    if n == 0:
        return ScreenedTopK(_empty_topk(max_results), jnp.asarray(True))
    n_buffer = max_results * buffer_mult
    screen = _scan_bottom_k(arrays, n, screen_chunk,
                            max_results=n_buffer, chunk=chunk,
                            merge_buffer=merge_buffer)
    s32 = rescore(screen.indices)
    s32 = jnp.where((screen.indices >= 0) & (s32 < tol), s32, jnp.inf)
    # (score, global index) ascending == the f32 scan's deterministic
    # order: merges keep the lower concat position at equal scores and
    # the final stable argsort preserves it.
    order = jnp.lexsort((screen.indices, s32))
    s_fin = s32[order][:max_results]
    i_fin = jnp.where(jnp.isfinite(s_fin), screen.indices[order][:max_results],
                      -1)
    buffer_full = jnp.isfinite(screen.scores[-1])
    s_k = s_fin[-1]
    margin_ok = jnp.isfinite(s_k) & (
        screen.scores[-1] > s_k * (1.0 + _SCREEN_REL))
    return ScreenedTopK(TopK(s_fin, i_fin), ~buffer_full | margin_ok)


@functools.partial(jax.jit, static_argnames=("max_results", "chunk",
                                             "merge_buffer", "buffer_mult"))
def top_suspicious_screened(
    theta: jax.Array,         # float32 [D,K] (single-estimate tables only)
    phi_wk: jax.Array,        # float32 [V,K]
    doc_ids: jax.Array,       # int32 [N]
    word_ids: jax.Array,      # int32 [N]
    mask: jax.Array,          # float32 [N] 0.0 for padding
    *,
    tol: float,
    max_results: int,
    chunk: int = 1 << 20,
    merge_buffer: int | None = 128,
    buffer_mult: int = 4,
) -> ScreenedTopK:
    """`top_suspicious` at bf16-scan speed with an f32-rescored result:
    bf16 gathers drive the selection scan, the f32 tables rescore only
    the ~max_results*buffer_mult survivors. `result` is valid only when
    `sound` is True — otherwise rerun the f32 `top_suspicious` (the
    screen cannot prove it kept every true bottom-k member). sound=True
    certifies identity with the f32 scan up to last-ulp boundary ties
    (the rescore is a separately compiled dot — module block comment);
    the table_* variants below carry the strictly bit-identical claim."""
    if theta.ndim != 2:
        raise ValueError("screened selection covers single-estimate "
                         "tables; combine chains upstream")
    theta_b = theta.astype(jnp.bfloat16)
    phi_b = phi_wk.astype(jnp.bfloat16)
    tol_screen = tol * (1.0 + 2.0 * _SCREEN_REL)

    def screen_chunk(dc, wc, mc):
        s = _subscan_scores(theta_b, phi_b, dc, wc)
        return jnp.where((mc > 0) & (s < tol_screen), s, jnp.inf)

    def rescore(gidx):
        safe = jnp.maximum(gidx, 0)
        return score_events(theta, phi_wk, doc_ids[safe], word_ids[safe])

    return _screened_scan((doc_ids, word_ids, mask), doc_ids.shape[0],
                          screen_chunk, rescore, tol=tol,
                          max_results=max_results, chunk=chunk,
                          merge_buffer=merge_buffer, buffer_mult=buffer_mult)


@functools.partial(jax.jit, static_argnames=("max_results", "chunk",
                                             "merge_buffer", "buffer_mult"))
def table_bottom_k_screened(
    table_flat: jax.Array,   # float32 [D*V] from score_table().ravel()
    idx: jax.Array,          # int32 [N] flat index d*V + w per event
    table_bf16: jax.Array | None = None,   # optional precomputed bf16 copy
    *,
    tol: float,
    max_results: int,
    chunk: int = 1 << 21,
    merge_buffer: int | None = 128,
    buffer_mult: int = 4,
) -> ScreenedTopK:
    """`table_bottom_k` with a bf16 screen: the scan gathers a bf16 copy
    of the score table (half the bytes of the bandwidth-bound gather),
    f32 rescoring covers only the candidate buffer. Batch-loop callers
    should build `table_bf16 = table_flat.astype(jnp.bfloat16)` ONCE and
    pass it in — converting inside is a full extra pass over the table
    per call."""
    table_b = (table_flat.astype(jnp.bfloat16) if table_bf16 is None
               else table_bf16)
    tol_screen = tol * (1.0 + 2.0 * _SCREEN_REL)

    def screen_chunk(ii):
        s = table_b[ii].astype(jnp.float32)
        return jnp.where(s < tol_screen, s, jnp.inf)

    def rescore(gidx):
        return table_flat[idx[jnp.maximum(gidx, 0)]]

    return _screened_scan((idx,), idx.shape[0], screen_chunk, rescore,
                          tol=tol, max_results=max_results, chunk=chunk,
                          merge_buffer=merge_buffer, buffer_mult=buffer_mult)


@functools.partial(jax.jit, static_argnames=("max_results", "chunk",
                                             "merge_buffer", "buffer_mult"))
def table_pair_bottom_k_screened(
    table_flat: jax.Array,   # float32 [D*V] from score_table().ravel()
    idx_src: jax.Array,      # int32 [N]
    idx_dst: jax.Array,      # int32 [N]
    table_bf16: jax.Array | None = None,   # optional precomputed bf16 copy
    *,
    tol: float,
    max_results: int,
    chunk: int = 1 << 21,
    merge_buffer: int | None = 128,
    buffer_mult: int = 4,
) -> ScreenedTopK:
    """`table_pair_bottom_k` with a bf16 screen. min() of two
    once-rounded values stays within the same relative bound as a single
    rounded value, so the shared REL covers the pair-min too. See
    `table_bottom_k_screened` on precomputing `table_bf16`."""
    table_b = (table_flat.astype(jnp.bfloat16) if table_bf16 is None
               else table_bf16)
    tol_screen = tol * (1.0 + 2.0 * _SCREEN_REL)

    def screen_chunk(si, di):
        s = jnp.minimum(table_b[si], table_b[di]).astype(jnp.float32)
        return jnp.where(s < tol_screen, s, jnp.inf)

    def rescore(gidx):
        safe = jnp.maximum(gidx, 0)
        return jnp.minimum(table_flat[idx_src[safe]],
                           table_flat[idx_dst[safe]])

    return _screened_scan((idx_src, idx_dst), idx_src.shape[0],
                          screen_chunk, rescore, tol=tol,
                          max_results=max_results, chunk=chunk,
                          merge_buffer=merge_buffer, buffer_mult=buffer_mult)


def _screened_enabled() -> bool:
    # Platform default, env-overridable. On TPU the screened scan was
    # the fastest certified form over a remote device link, before
    # PR 21 (132.2M ev/s vs 118.6M exact on the same run, sound +
    # set-identical; no cell drives it: ROADMAP D6); everywhere else —
    # CPU (no gather-bandwidth win) and unmeasured accelerators (an
    # uncertifiable screen would pay BOTH scans via the fallback) — the
    # f32 scan stays the default. Any env value other than "1"
    # disables, so legacy spellings like "0"/"false"/"off" all mean
    # off; unset means the platform default.
    import os
    env = os.environ.get("ONIX_SCREENED_SELECT")
    if env is not None:
        return env == "1"
    return jax.default_backend() == "tpu"


def _certified(scr: ScreenedTopK) -> bool:
    """Fetch one screened scan's device-side proof and count it: a scan
    that does not certify pays the f32 scan too, and a stream of those
    must show in the manifests (`score.*`), not only in the wall."""
    from onix.utils.obs import counters
    counters.inc("score.screened_scans")
    sound = bool(scr.sound)
    if not sound:
        counters.inc("score.screened_uncertified")
    return sound


def table_bottom_k_fast(table_flat, idx, table_bf16=None, *, tol: float,
                        max_results: int, serve_form: str = "auto") -> TopK:
    """Drop-in `table_bottom_k`: the r15 fused one-kernel arm when the
    serve gate resolves to it (pallas_serve.select_serve_form —
    `serve_form` lets config-bearing callers pass
    serving.serve_form; "auto" resolves to "xla" on every backend
    until a measured crossover lands, ONIX_SERVE_FORM overrides), else
    the bf16-screened scan when enabled (_screened_enabled: default on
    TPU, ONIX_SCREENED_SELECT overrides), falling back to the f32 scan
    whenever the device-side proof does not certify; plain f32 scan
    otherwise."""
    from onix.models import pallas_serve
    if pallas_serve.select_serve_form(serve_form,
                                      idx.shape[0]) == "fused":
        return pallas_serve.fused_table_bottom_k(
            table_flat, idx, tol=tol, max_results=max_results)
    if _screened_enabled():
        scr = table_bottom_k_screened(table_flat, idx, table_bf16,
                                      tol=tol, max_results=max_results)
        if _certified(scr):
            return scr.result
    return table_bottom_k(table_flat, idx, tol=tol,
                          max_results=max_results)


def table_pair_bottom_k_fast(table_flat, idx_src, idx_dst, table_bf16=None,
                             *, tol: float, max_results: int,
                             serve_form: str = "auto") -> TopK:
    """Drop-in `table_pair_bottom_k` with the same serve-gate +
    screened/fallback policy (and platform default) as
    `table_bottom_k_fast`."""
    from onix.models import pallas_serve
    if pallas_serve.select_serve_form(
            serve_form, idx_src.shape[0]) == "fused":
        return pallas_serve.fused_table_pair_bottom_k(
            table_flat, idx_src, idx_dst, tol=tol,
            max_results=max_results)
    if _screened_enabled():
        scr = table_pair_bottom_k_screened(table_flat, idx_src, idx_dst,
                                           table_bf16, tol=tol,
                                           max_results=max_results)
        if _certified(scr):
            return scr.result
    return table_pair_bottom_k(table_flat, idx_src, idx_dst, tol=tol,
                               max_results=max_results)


# Dedup pays once the device scan shrinks enough to cover the host-side
# np.unique sort; real telemetry is Zipf over (ip, word) pairs, so the
# unique-pair count is typically a small fraction of the event count.
# Uniform-random data dedups to ~nothing and
# takes the direct path.
_DEDUP_THRESHOLD = 0.7


def score_all(theta, phi_wk, doc_ids, word_ids, chunk: int = 1 << 22,
              dedup: bool = True) -> np.ndarray:
    """Score every event, chunked on host to bound device memory.

    Strategy selection:
    1. D×V small (the product regime): materialize θ·φᵀ once on the MXU
       and score each event with a flat gather.
    2. Otherwise, with `dedup`, duplicate (doc, word) pairs are scored
       once on device and broadcast back through the inverse index —
       same scores bit-for-bit (scoring is a pure function of the pair).
    3. Fallback: chunked gather-dot scan.
    """
    doc_ids = np.asarray(doc_ids)
    word_ids = np.asarray(word_ids)
    n = doc_ids.shape[0]
    theta_a = np.asarray(theta)
    n_docs = int(theta_a.shape[-2])
    n_vocab = int(np.asarray(phi_wk).shape[-2])
    chains = theta_a.shape[0] if theta_a.ndim == 3 else 1
    # Table strategy gates: (a) the [C,D,V] build (plus its log/exp
    # temporaries on the chain path) must respect the memory budget;
    # (b) the D*V*4B of table traffic must amortize over the events
    # (each event replaces ~2K*8B of gathered-operand traffic, so the
    # break-even is D*V ≈ 40n; 32 keeps margin). Small batches — the
    # streaming scorer — fall through to the gather-dot/dedup paths.
    if (n and chains * n_docs * n_vocab <= TABLE_MAX_ELEMS
            and n_docs * n_vocab <= 32 * n):
        table = score_table(jnp.asarray(theta), jnp.asarray(phi_wk)).ravel()
        out = np.empty(n, np.float32)
        for lo in range(0, n, chunk):
            hi = min(lo + chunk, n)
            out[lo:hi] = np.asarray(_gather_scores(
                table, jnp.asarray(doc_ids[lo:hi]),
                jnp.asarray(word_ids[lo:hi]), n_vocab))
        return out
    if dedup and n:
        from onix.utils.arrays import unique_inverse
        key = doc_ids.astype(np.int64) * n_vocab + word_ids
        # Chunked unique-merge + searchsorted inverse — same output as
        # np.unique(return_inverse=True), ~4x faster at 10^8 keys
        # (cache-sized sorts; the cardinality is tiny vs the array).
        uniq, inv = unique_inverse(key)
        if uniq.shape[0] <= _DEDUP_THRESHOLD * n:
            pair_scores = score_all(
                theta, phi_wk, (uniq // n_vocab).astype(doc_ids.dtype),
                (uniq % n_vocab).astype(word_ids.dtype), chunk=chunk,
                dedup=False)
            return pair_scores[inv]
    out = np.empty(n, np.float32)
    for lo in range(0, n, chunk):
        hi = min(lo + chunk, n)
        out[lo:hi] = np.asarray(_score_events_jit(theta, phi_wk,
                                                  jnp.asarray(doc_ids[lo:hi]),
                                                  jnp.asarray(word_ids[lo:hi])))
    return out


def select_suspicious(scores: np.ndarray, tol: float,
                      max_results: int) -> np.ndarray:
    """Host-side suspicious selection: indices of events with score <
    tol, ascending by score, capped at max_results — the POST-LDA
    filter/sort/take contract (SURVEY.md §3.1) shared by the batch run
    and the benches."""
    cand = np.flatnonzero(scores < tol)
    if cand.size > max_results:
        part = np.argpartition(scores[cand], max_results - 1)
        cand = cand[part[:max_results]]
    return cand[np.argsort(scores[cand], kind="stable")]


def doc_rarity(theta: jax.Array, doc_weights: jax.Array) -> jax.Array:
    """Per-DOCUMENT suspiciousness: expected log corpus-popularity of
    the document's topics. Returns float32 [D], LOW = suspicious.

    Event scoring ranks words by rarity, which fades exactly when an
    attack is sustained: a campaign of hundreds of near-identical
    events accumulates word count (and, with enough mass, its own
    topic) until its events stop being individually rare — measured on
    the independent session generator, where 300-event tunnel/exfil
    campaigns score ~0 event recall while 15-event ones score 1.0
    (docs/RECALL_r05_sessions*.json). The campaign's signature is at
    the DOCUMENT level instead: its client concentrates token mass on
    a topic almost no other document uses.

        share_k = sum_d n_d * theta[d, k] / sum_d n_d   (corpus topic mass)
        score_d = sum_k theta[d, k] * log(share_k)

    A document riding globally-popular topics scores near the
    corpus-entropy baseline; a document whose mixture sits on a
    globally-rare topic scores far below it. One [D,K] contraction +
    one [D,K]@[K] matvec — MXU change, host round-trip only for the
    [D] result. Chained estimates ([C, D, K]) average the per-chain
    scores (arithmetic: log-space values, same label-switching
    robustness argument as score_events' geometric mean in p-space).
    """
    theta = jnp.asarray(theta)
    w = jnp.asarray(doc_weights, jnp.float32)

    def one(th):
        th = th.astype(jnp.float32)
        mass = w @ th                       # [K] token mass per topic
        share = mass / jnp.maximum(mass.sum(), 1e-30)
        return th @ jnp.log(jnp.maximum(share, 1e-30))

    if theta.ndim == 2:
        return one(theta)
    return jnp.mean(jax.vmap(one)(theta), axis=0)
