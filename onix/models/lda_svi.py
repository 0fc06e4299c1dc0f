"""Online variational Bayes (SVI) LDA — the streaming engine.

Covers BASELINE.json configs[4]: "streaming online-VB LDA over
oni-ingest minibatches (incremental scoring)". The reference has no
streaming ML at all — oni-lda-c re-fits from scratch each day
(SURVEY.md §3.1); onix adds the stochastic variational inference of
Hoffman et al. (per PAPERS.md "Stochastic Collapsed Variational Bayesian
Inference for LDA"): each minibatch of ingested events performs a local
E-step on its documents and a natural-gradient step on the global
topic-word variational parameter lambda.

Everything is fixed-shape and jit-compiled: the local E-step is a
`lax.fori_loop` of dense [T,K] updates (K=20 rides the VPU), the global
update a scatter-add into lambda [V,K].
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from onix.config import LDAConfig


class SVIState(NamedTuple):
    lam: jax.Array       # float32 [V, K] topic-word variational parameter
    step: jax.Array      # int32 [] global update counter


class MiniBatch(NamedTuple):
    """A minibatch of token events, documents re-indexed densely [0, Bd).

    Both the token axis and the document axis are padded to static sizes
    so a stream of differently-shaped minibatches hits one compiled
    svi_step (no per-batch retrace). `doc_map[i]` recovers the original
    document (IP) id of local doc i (-1 for padding rows) — gamma rows
    are meaningless without it.

    `mask` carries per-row token MULTIPLICITY, not just validity: the
    deduped streaming path feeds unique (doc, word) pairs with their
    counts as weights, and every E-step/λ-step contribution multiplies
    by mask — so a weight-w row contributes exactly what w identical
    rows would (same math, a fraction of the memory passes). Plain
    callers get 1.0 per real token, 0.0 padding, as before.
    """
    doc_ids: jax.Array   # int32 [T] local-dense doc index per token
    word_ids: jax.Array  # int32 [T]
    mask: jax.Array      # float32 [T] token multiplicity; 0.0 padding
    doc_map: jax.Array   # int32 [Bd] local doc -> original doc id (-1 pad)
    n_docs: int          # Bd (padded) — static


def minibatch_arrays(doc_ids: np.ndarray, word_ids: np.ndarray,
                     pad_to: int | None = None,
                     pad_docs: int | None = None,
                     weights: np.ndarray | None = None):
    """Host half of make_minibatch: densify + pad, returning plain
    NumPy arrays (doc_ids, word_ids, mask, doc_map, n_docs)."""
    uniq, local = np.unique(np.asarray(doc_ids), return_inverse=True)
    t = len(local)
    pad_to = t if pad_to is None else pad_to
    if pad_to < t:
        raise ValueError("pad_to smaller than batch")
    n_docs = pad_docs if pad_docs is not None else len(uniq)
    if n_docs < len(uniq):
        raise ValueError("pad_docs smaller than distinct docs in batch")
    rem = pad_to - t
    doc_map = np.full(n_docs, -1, np.int32)
    doc_map[: len(uniq)] = uniq
    w = (np.ones(t, np.float32) if weights is None
         else np.asarray(weights, np.float32))
    if w.shape[0] != t:
        raise ValueError("weights must match the token count")
    return (np.concatenate([local.astype(np.int32), np.zeros(rem, np.int32)]),
            np.concatenate([np.asarray(word_ids, np.int32),
                            np.zeros(rem, np.int32)]),
            np.concatenate([w, np.zeros(rem, np.float32)]),
            doc_map, int(n_docs))


def make_minibatch(doc_ids: np.ndarray, word_ids: np.ndarray,
                   pad_to: int | None = None,
                   pad_docs: int | None = None,
                   weights: np.ndarray | None = None) -> MiniBatch:
    """Densify document ids; pad tokens to `pad_to` and docs to
    `pad_docs`. `weights` (float32 [T]) sets per-row multiplicities for
    the deduped-pair path; default 1.0 per row."""
    d, w_ids, m, doc_map, n_docs = minibatch_arrays(
        doc_ids, word_ids, pad_to=pad_to, pad_docs=pad_docs,
        weights=weights)
    return MiniBatch(doc_ids=jnp.asarray(d), word_ids=jnp.asarray(w_ids),
                     mask=jnp.asarray(m), doc_map=jnp.asarray(doc_map),
                     n_docs=n_docs)


def init_state(n_vocab: int, n_topics: int, seed: int = 0) -> SVIState:
    key = jax.random.PRNGKey(seed)
    lam = jax.random.gamma(key, 100.0, (n_vocab, n_topics)) * 0.01
    return SVIState(lam=lam.astype(jnp.float32), step=jnp.zeros((), jnp.int32))


def _e_log_dirichlet(x: jax.Array, axis: int) -> jax.Array:
    return jax.scipy.special.digamma(x) - jax.scipy.special.digamma(
        x.sum(axis=axis, keepdims=True))


# Hoisted to onix/models/compaction.py (r11): the pow2 active-set
# compaction idiom is shared with the sparse Gibbs arm. Re-exported
# under the original name; the E-step below is bit-preserved.
from onix.models.compaction import (compact_front, ladder_index,  # noqa: E402
                                    pow2_ladder as _active_ladder)


# With the words' table handed over, a pass works through its rows in
# runs of this many: a float32 [T, K] array is laid out on the TPU with
# K padded to 128 lanes (4.3 GB at T = 2^23, K = 20, and an E-step pass
# keeps two or three), a run's rows are 64 MB. The Gibbs kernel's block:
# its scatter-add of 2^17 K-lane rows is what PERF.md's unit costs were
# read at. The rows of `svi_store_step`'s E-step and lambda step are the
# batch's unique (document, word) pairs (`_unique_pairs`), in front of
# the token axis, and those passes stop after the runs that hold pairs;
# its scores go over every token slot.
_TOKEN_RUN = 1 << 17


def _token_runs(n: int) -> int:
    """How many runs a pass over `n` tokens is made in: one (the whole
    axis at once) unless `n` is a multiple of `_TOKEN_RUN` longer than
    one run."""
    return 1 if n <= _TOKEN_RUN or n % _TOKEN_RUN else n // _TOKEN_RUN


def _in_token_runs(fn, init, arrays: tuple, n_runs=None):
    """`fn(carry, *arrays) -> (carry, y)` over the token axis, the whole
    of it at once or a run at a time (`_token_runs`; the y's joined
    again). `n_runs` (traced) stops after that many runs, for a pass
    whose rows of weight sit in front; the y's of the runs behind them
    read 0."""
    n, runs = arrays[0].shape[0], _token_runs(arrays[0].shape[0])
    if runs == 1:
        return fn(init, *arrays)
    arrays = tuple(a.reshape(runs, _TOKEN_RUN) for a in arrays)
    if n_runs is None:
        carry, ys = jax.lax.scan(lambda c, xs: fn(c, *xs), init, arrays)
    else:
        def run(i, c):
            carry, y = fn(c[0], *(a[i] for a in arrays))
            return carry, jax.tree.map(lambda ys, v: ys.at[i].set(v), c[1], y)

        carry, ys = jax.lax.fori_loop(0, n_runs, run, (init, jax.tree.map(
            lambda y: jnp.zeros((runs, *y.shape), y.dtype),
            jax.eval_shape(lambda: fn(init, *(a[0] for a in arrays))[1]))))
    return carry, jax.tree.map(lambda y: y.reshape(n, *y.shape[2:]), ys)


def _runs_holding(n_rows, n: int):
    """The runs of a pass over `n` slots that hold its first `n_rows`
    (traced) rows, for `_in_token_runs`; None where the pass is made at
    once or the count is not known."""
    if n_rows is None or _token_runs(n) == 1:
        return None
    return -(-n_rows // _TOKEN_RUN)


def _unique_pairs(doc_ids, word_ids, mask):
    """A batch's token columns reduced to its unique (document, word)
    pairs, each with the sum of its tokens' weights: what
    `StreamingScorer._prep_batch` makes on the host with `np.unique`,
    made on the device with two sorts and a running sum. Returns
    (doc_ids, word_ids, weights, n_pairs): columns as long as the token
    columns, the `n_pairs` (int32 scalar) pairs in front, word by word
    and within a word by document, every row behind them of weight 0
    (and pointing at some document and word of the batch, or at 0).

    A token of weight 0 belongs to no pair. Weights are multiplicities:
    the running sum is float32, exact for whole numbers while the
    batch's weights sum to less than 2^24.

    `doc * n_words + word` does not fit 32 bits at the stream's shapes,
    so the first sort has two keys; pairs are found by their last token
    (the one whose successor differs), whose running sum less the pair
    before's is the pair's weight once the second, stable, sort has put
    the last tokens side by side."""
    from onix.pipelines.device_words import _running_sum

    t = doc_ids.shape[0]
    big = jnp.iinfo(jnp.int32).max
    real = mask > 0.0
    # Weightless tokens sort behind every pair, under a word no token has.
    w, d, m = jax.lax.sort(
        (jnp.where(real, word_ids, big), doc_ids, jnp.where(real, mask, 0.0)),
        num_keys=2, is_stable=False)
    last = jnp.concatenate([(w[1:] != w[:-1]) | (d[1:] != d[:-1]),
                            jnp.ones((1,), bool)]) & (w != big)
    n_pairs = last.sum(dtype=jnp.int32)
    _, d, w, upto = jax.lax.sort((~last, d, w, _running_sum(m)),
                                 num_keys=1, is_stable=True)
    held = jax.lax.iota(jnp.int32, t) < n_pairs
    weights = jnp.where(
        held, upto - jnp.concatenate([jnp.zeros((1,), upto.dtype),
                                      upto[:-1]]), 0.0)
    return d, jnp.where(w == big, 0, w), weights, n_pairs


def _run_e_step(gamma0, elog_beta_t, doc_ids, mask, *, alpha: float,
                local_iters: int, meanchange_tol: float,
                warm_iters: int, estep_form: str = "svi",
                with_stats: bool = False, elog_beta=None, n_rows=None):
    """The local E-step over one minibatch's tokens. Returns gamma; with
    `with_stats` (static) also what ran, as int32 scalars: the passes
    over the full padded block, the passes of the extended loop, and the
    tokens of the compacted active set that loop worked on.

    With `elog_beta` ([V, K]) given, `elog_beta_t` holds the tokens'
    WORD IDS in place of their rows: every pass gathers the rows it
    needs a run of tokens at a time (`_TOKEN_RUN`) and no [T, K] array
    is ever whole; the sums are the same sums in another order. The
    rows may then be weighted (document, word) pairs with those of
    weight in front (`_unique_pairs`), `n_rows` (traced) of them: every
    pass stops after the runs that hold them. The stats are five: the
    tokens of the active set are the sum of its rows' weights, and
    after them stand `n_rows` (the length of the axis if not given) and
    the rows of the active set.

    `estep_form` picks the update family (static):

    * ``"svi"`` — Hoffman's uncollapsed variational update: token
      responsibilities from exp(E[log theta] + E[log beta]) under the
      Dirichlet variational posteriors (digamma terms).
    * ``"scvb0"`` — the SCVB0 zeroth-order collapsed update
      (arxiv 1305.2452): responsibilities directly proportional to
      (N_theta[d,k] + alpha) · phi_hat[w,k] — no digammas, plain
      linear-space counts. The caller passes log(phi_hat) rows as
      `elog_beta_t` and the gamma store carries alpha + N_theta, so
      the same store/scoring machinery (theta = gamma / sum gamma)
      serves both forms.

    Three iteration regimes, chosen statically:

    * ``meanchange_tol == 0`` — the original fixed-count fori_loop.
    * ``warm_iters == 0`` — the r6 per-document while_loop: the FULL
      padded [T,K] block iterates until the slowest doc converges
      (kept bit-identical: existing streaming checkpoints and the
      batch SVI engine ride this path unchanged).
    * ``warm_iters > 0`` — the r10 warm/cold split (with `elog_beta`
      given and the tokens a multiple of `_TOKEN_RUN`, the extended
      loop stops each pass after the runs that hold the active tokens,
      in place of the pow2 bucket). Warm-started
      returning docs (the stream's common case) converge within a
      short fixed-trip pass over the full block; the unconverged
      remainder is then COMPACTED — its docs' tokens gathered to the
      front and sliced into the smallest pow2 bucket that fits
      (`_active_ladder`) — and only that block runs the extended
      while_loop. Converged docs' gamma is frozen at its warm-pass
      value (each active doc keeps ALL its tokens, so its update is
      exact); the per-document Hoffman stopping rule is unchanged.
      Extended iterations therefore cost O(T_active · K), not
      O(T · K) — the r6 loop charged every token until the SLOWEST
      doc converged.
    """
    def e_step(gamma, d_ids, eb_t, m, n_runs=None):
        if estep_form == "scvb0":
            # Collapsed zeroth-order responsibilities: gamma holds
            # alpha + N_theta (> 0 always), eb_t holds log(phi_hat)
            # rows, so softmax(log gamma + log phi_hat) is exactly the
            # normalized (N_theta + alpha) · phi_hat of SCVB0.
            elog_theta = jnp.log(gamma)                  # [Bd,K]
        else:
            elog_theta = _e_log_dirichlet(gamma, axis=1)  # [Bd,K]

        def add(acc, d, e, mm):
            logp = elog_theta[d] + (e if elog_beta is None
                                    else elog_beta[e])   # [T,K]
            phi = jax.nn.softmax(logp, axis=-1) * mm[:, None]
            return acc.at[d].add(phi), None

        zero = jnp.zeros_like(gamma)
        if elog_beta is None:
            return alpha + add(zero, d_ids, eb_t, m)[0]
        return alpha + _in_token_runs(add, zero, (d_ids, eb_t, m),
                                      n_runs)[0]

    t = doc_ids.shape[0]
    # The runs every pass over the whole axis stops after (ids mode).
    all_runs = _runs_holding(n_rows, t)

    def out(gamma, full, ext=0, n_act=0, act_rows=0):
        if not with_stats:
            return gamma
        stats = (jnp.int32(full), jnp.int32(ext), jnp.int32(n_act))
        if elog_beta is not None:
            stats += (jnp.int32(t if n_rows is None else n_rows),
                      jnp.int32(act_rows))
        return gamma, stats

    if meanchange_tol <= 0.0:
        return out(jax.lax.fori_loop(
            0, local_iters,
            lambda _, g: e_step(g, doc_ids, elog_beta_t, mask, all_runs),
            gamma0), local_iters)

    if warm_iters <= 0:
        def body(carry):
            gamma, _, i = carry
            g2 = e_step(gamma, doc_ids, elog_beta_t, mask, all_runs)
            # Per-DOCUMENT convergence, as in Hoffman's rule: iterate
            # until EVERY doc's mean |Δgamma| is under tol. A
            # batch-global mean would let a majority of converged
            # (warm-started, recurring) docs dilute away exactly the
            # still-moving first-seen docs the rarity detector needs
            # converged. Padding rows collapse to alpha after one
            # iteration and stop contributing.
            return g2, jnp.abs(g2 - gamma).mean(axis=1).max(), i + 1

        def cond(carry):
            _, delta, i = carry
            return (i < local_iters) & (delta > meanchange_tol)

        gamma, _, i = jax.lax.while_loop(
            cond, body, (gamma0, jnp.float32(jnp.inf), jnp.int32(0)))
        return out(gamma, i)

    warm = min(int(warm_iters), int(local_iters))
    rem_iters = int(local_iters) - warm

    def warm_body(_, carry):
        g, _ = carry
        g2 = e_step(g, doc_ids, elog_beta_t, mask, all_runs)
        return g2, jnp.abs(g2 - g).mean(axis=1)

    gamma, delta_d = jax.lax.fori_loop(
        0, warm, warm_body,
        (gamma0, jnp.full((gamma0.shape[0],), jnp.inf, jnp.float32)))
    if rem_iters <= 0:
        return out(gamma, warm)

    active_d = delta_d > meanchange_tol              # [Bd]
    if elog_beta is None:
        act_tok = active_d[doc_ids] & (mask > 0.0)   # [T]
    else:
        act_tok = _in_token_runs(
            lambda _, d, m: (None, active_d[d] & (m > 0.0)), None,
            (doc_ids, mask), all_runs)[1]
    n_act = act_tok.sum()
    # Stable compaction: active docs' tokens to the front, order kept.
    if elog_beta is None:
        perm = compact_front(act_tok)
        c_doc = doc_ids[perm]
        c_eb = elog_beta_t[perm]
        c_mask = jnp.where(act_tok, mask, 0.0)[perm]
    else:
        # The same order, the three 1-D columns carried through the one
        # sort (a gather of a scalar an index costs the chip more than
        # the sort does).
        _, c_doc, c_eb, c_mask = jax.lax.sort(
            (~act_tok, doc_ids, elog_beta_t, jnp.where(act_tok, mask, 0.0)),
            num_keys=1, is_stable=True)
    # The loop's bound counts rows; the stats count tokens too, which a
    # row of weight w holds w of.
    act_tokens = n_act if elog_beta is None else c_mask.astype(
        jnp.int32).sum()

    def make_branch(size, n_runs=None):
        d_ids = jax.lax.slice_in_dim(c_doc, 0, size)
        eb_t = jax.lax.slice_in_dim(c_eb, 0, size)
        m = jax.lax.slice_in_dim(c_mask, 0, size)

        def body(carry):
            g, _, i = carry
            g2 = e_step(g, d_ids, eb_t, m, n_runs)
            # Converged docs stay frozen; active docs' updates are
            # exact (every token of an active doc sits inside the
            # compacted slice — activity is per-doc, and the slice is
            # chosen to cover n_act).
            g2 = jnp.where(active_d[:, None], g2, g)
            delta = jnp.where(active_d,
                              jnp.abs(g2 - g).mean(axis=1), 0.0).max()
            return g2, delta, i + 1

        def cond(carry):
            _, delta, i = carry
            return (i < rem_iters) & (delta > meanchange_tol)

        def branch(g):
            g2, _, i = jax.lax.while_loop(
                cond, body,
                # n_act == 0 skips the extended phase outright (the
                # init delta fails cond on entry).
                (g, jnp.where(n_act > 0, jnp.float32(jnp.inf),
                              jnp.float32(0.0)), jnp.int32(0)))
            return g2, i
        return branch

    if elog_beta is not None and _token_runs(t) > 1:
        # A pass made in runs needs no ladder: the one loop stops after
        # the runs that hold the active rows.
        gamma, ext = make_branch(t, _runs_holding(n_act, t))(gamma)
        return out(gamma, warm, ext, act_tokens, n_act)
    sizes = _active_ladder(t)
    # Smallest rung that still holds every active token (compaction
    # preserves order, so the first n_act compacted slots are exactly
    # the active tokens).
    idx = ladder_index(n_act, sizes)
    gamma, ext = jax.lax.switch(idx, [make_branch(s) for s in sizes], gamma)
    return out(gamma, warm, ext, act_tokens, n_act)


def svi_step(
    state: SVIState,
    batch: MiniBatch,
    corpus_docs: jax.Array,  # D — total docs the stream represents; a
    #                          TRACED scalar so a streaming driver can
    #                          grow its running estimate without retracing
    gamma0: jax.Array | None = None,   # [Bd,K] E-step warm start
    *,
    alpha: float,
    eta: float,
    tau0: float,
    kappa: float,
    local_iters: int,
    batch_docs: int,         # static Bd for gamma shape
    meanchange_tol: float = 0.0,
    warm_iters: int = 0,
    estep_form: str = "svi",
) -> tuple[SVIState, jax.Array]:
    """One SVI update. Returns (new_state, gamma [Bd,K]) for scoring.

    `estep_form` ("svi" | "scvb0", static) picks the local-update
    family (_run_e_step docstring). The scvb0 arm is the SCVB0
    minibatch estimator of arxiv 1305.2452 riding the SAME schedule
    machinery: the lambda step below is unchanged (lambda = eta +
    N_phi, so the natural-gradient averaging IS the SCVB0 online
    average of the expected topic-word counts), with the minibatch
    scaled by documents rather than the paper's tokens — the scale
    the streaming driver already tracks. A different estimator, NOT
    bit-comparable to the svi arm; parity is winner-set discipline
    (tests/test_scvb0.py).

    The local E-step iterates to convergence (mean |Δgamma| under
    `meanchange_tol` — Hoffman's onlineldavb stopping rule) with
    `local_iters` as the hard cap; tol 0 keeps the fixed-count loop,
    and `warm_iters > 0` engages the warm/cold compacted split
    (`_run_e_step` docstring). Token weights ride `batch.mask`
    (MiniBatch docstring), so deduped (doc, word) pairs update gamma
    and lambda exactly as their multiplicity of identical tokens
    would. `gamma0` warm-starts the fixed point (a streaming driver
    passes each returning doc's LAST gamma — recurring docs then
    converge in a few iterations instead of re-walking from the
    prior); None keeps the cold start."""
    k = state.lam.shape[1]
    if estep_form == "scvb0":
        # log phi_hat rows: the collapsed arm's word term (log space so
        # the shared softmax form serves both arms).
        elog_beta = jnp.log(state.lam / state.lam.sum(axis=0,
                                                      keepdims=True))
    else:
        elog_beta = _e_log_dirichlet(state.lam, axis=0)  # [V,K]
    elog_beta_t = elog_beta[batch.word_ids]              # [T,K]

    if gamma0 is None:
        gamma0 = jnp.full((batch_docs, k), alpha + 1.0, jnp.float32)
    gamma = _run_e_step(gamma0, elog_beta_t, batch.doc_ids, batch.mask,
                        alpha=alpha, local_iters=local_iters,
                        meanchange_tol=meanchange_tol,
                        warm_iters=warm_iters, estep_form=estep_form)

    # Final responsibilities under converged gamma.
    if estep_form == "scvb0":
        elog_theta = jnp.log(gamma)
    else:
        elog_theta = _e_log_dirichlet(gamma, axis=1)
    phi = jax.nn.softmax(elog_theta[batch.doc_ids] + elog_beta_t, axis=-1)
    phi = phi * batch.mask[:, None]

    # Natural-gradient step on lambda, scaled to the full corpus by the
    # number of REAL documents in the batch (doc_map == -1 rows are padding).
    n_real = (batch.doc_map >= 0).sum().astype(jnp.float32)
    scale = jnp.asarray(corpus_docs, jnp.float32) / jnp.maximum(n_real, 1.0)
    lam_hat = eta + scale * jnp.zeros_like(state.lam).at[batch.word_ids].add(phi)
    rho = (tau0 + state.step.astype(jnp.float32)) ** (-kappa)
    lam = (1.0 - rho) * state.lam + rho * lam_hat
    return SVIState(lam=lam, step=state.step + 1), gamma


def phi_estimate(state: SVIState) -> jax.Array:
    """Posterior-mean topic-word distribution phi_wk [V,K]."""
    return state.lam / state.lam.sum(axis=0, keepdims=True)


def svi_store_step(
    state: SVIState,
    store: jax.Array,         # float32 [cap, K]: EVERY document's gamma
    doc_ids: jax.Array,       # int32 [T] rows of `store`, one per token
    word_ids: jax.Array,      # int32 [T]
    mask: jax.Array,          # float32 [T] token multiplicity; 0 padding
    corpus_docs: jax.Array,   # float32 []: running D of this batch
    *,
    alpha: float,
    eta: float,
    tau0: float,
    kappa: float,
    local_iters: int,
    meanchange_tol: float = 0.0,
    warm_iters: int = 0,
    estep_form: str = "svi",
):
    """One minibatch's update against the WHOLE per-document store: the
    exact `svi_step` (E-step from each touched document's last gamma,
    natural-gradient lambda step) followed by the incremental scores of
    the batch's own tokens under the updated model - the order the
    stream states. `doc_ids` index `store` directly, so the store stays
    where it is from batch to batch (the streaming scorer keeps it on
    the device and donates it); there is no per-batch document re-index
    and no union of a group's documents to build.

    The E-step and the lambda step run over the batch's unique
    (document, word) pairs, each weighted by the sum of its tokens'
    weights (`_unique_pairs`: made here, on the device, for every
    batch; by `MiniBatch`'s contract a pair of weight w contributes
    what w identical tokens would), and stop after the runs of
    `_TOKEN_RUN` that hold pairs; the scores are per token, over
    `doc_ids` and `word_ids` as they came, in their order.

    A document the batch does not touch sits in the E-step as a padding
    row does in `svi_step` (it holds `alpha` and no token reaches it)
    and keeps its stored gamma; a row never written holds whatever the
    caller put there, `alpha + 1` for a cold start. The lambda step
    scales by the documents the batch touches (a token of weight 0
    touches none). Padding tokens may point at any row.

    Returns (state, store, touched bool [cap], scores float32 [T],
    stats), `stats` as `_run_e_step(with_stats=True)` gives it: the
    passes over every pair, the passes of the extended loop, the TOKENS
    of its active set (the sum of its pairs' weights), the batch's
    pairs, the active set's pairs."""
    from onix.models.scoring import score_events
    from onix.utils.obs import device_scope

    lam = state.lam
    scvb0 = estep_form == "scvb0"

    def elog_rows(x, axis):
        if scvb0:
            return jnp.log(x / x.sum(axis=0, keepdims=True) if axis == 0
                           else x)
        return _e_log_dirichlet(x, axis=axis)

    with device_scope("onix.svi.estep"):
        with device_scope("onix.svi.estep.pairs"):
            p_doc, p_word, p_weight, n_pairs = _unique_pairs(
                doc_ids, word_ids, mask)
        pair_runs = _runs_holding(n_pairs, p_doc.shape[0])
        touched = _in_token_runs(
            lambda acc, d, m: (acc.at[d].add(m), None),
            jnp.zeros((store.shape[0],), jnp.float32), (p_doc, p_weight),
            pair_runs)[0] > 0.0
        elog_beta = elog_rows(lam, 0)
        gamma, stats = _run_e_step(
            jnp.where(touched[:, None], store, alpha), p_word,
            p_doc, p_weight, alpha=alpha, local_iters=local_iters,
            meanchange_tol=meanchange_tol, warm_iters=warm_iters,
            estep_form=estep_form, with_stats=True, elog_beta=elog_beta,
            n_rows=n_pairs)
    with device_scope("onix.svi.lambda"):
        elog_theta = elog_rows(gamma, 1)

        def add(acc, d, w, m):
            # Final responsibilities under the converged gamma.
            phi = jax.nn.softmax(elog_theta[d] + elog_beta[w], axis=-1)
            return acc.at[w].add(phi * m[:, None]), None

        sstats, _ = _in_token_runs(add, jnp.zeros_like(lam),
                                   (p_doc, p_word, p_weight), pair_runs)
        n_real = touched.sum().astype(jnp.float32)
        scale = corpus_docs / jnp.maximum(n_real, 1.0)
        rho = (tau0 + state.step.astype(jnp.float32)) ** (-kappa)
        lam = (1.0 - rho) * lam + rho * (eta + scale * sstats)
        store = jnp.where(touched[:, None], gamma, store)
    with device_scope("onix.svi.score"):
        theta = store / store.sum(axis=1, keepdims=True)
        phi_wk = lam / lam.sum(axis=0, keepdims=True)
        _, scores = _in_token_runs(
            lambda _, d, w: (None, score_events(theta, phi_wk, d, w)),
            None, (doc_ids, word_ids))
    return (SVIState(lam=lam, step=state.step + 1), store, touched, scores,
            stats)


class SVILda:
    """Driver for streaming fits over ingest minibatches."""

    def __init__(self, config: LDAConfig, n_vocab: int, corpus_docs: int):
        config.validate()
        self.config = config
        self.n_vocab = n_vocab
        self.corpus_docs = corpus_docs
        warm = max(config.svi_warm_iters, 0)
        # lda.stream_estep gates the local-update family: "svi" (the
        # default, unchanged) or the SCVB0 collapsed minibatch arm
        # (svi_step docstring). Static — one compiled program per form.
        estep = config.stream_estep
        step = functools.partial(
            svi_step,
            alpha=config.alpha, eta=config.eta,
            tau0=config.svi_tau0, kappa=config.svi_kappa,
            local_iters=config.svi_local_iters,
            meanchange_tol=config.svi_meanchange_tol,
            warm_iters=warm, estep_form=estep,
        )
        # JAX names the compiled program after the function: a partial
        # has no name, and a trace or a `jit.compile` span would read
        # `<unknown>`.
        step.__name__ = svi_step.__name__
        self._step = jax.jit(step, static_argnames=("batch_docs",))

    def init(self) -> SVIState:
        return init_state(self.n_vocab, self.config.n_topics, self.config.seed)

    def update(self, state: SVIState, batch: MiniBatch,
               corpus_docs: float | None = None, gamma0=None):
        """One SVI step. `corpus_docs` overrides the construction-time D —
        streaming callers pass their running distinct-doc estimate (traced,
        so a growing value never retraces). `gamma0` warm-starts the
        E-step (svi_step docstring)."""
        d = float(self.corpus_docs if corpus_docs is None else corpus_docs)
        return self._step(state, batch, d, gamma0, batch_docs=batch.n_docs)
