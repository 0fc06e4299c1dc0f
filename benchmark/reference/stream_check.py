"""Plain reference for the stream: Hoffman's online variational Bayes for
one minibatch of flow events, in numpy and float64. It imports nothing
of the program. It is given what stood before the batch (lambda, the
step count, the corpus size, every document's gamma row), the batch's
raw columns, the stream's fixed parts (the fitted bin edges, the hash's
salt, the document table in first-seen order) and what the timed path
left behind (lambda, the gamma rows, every event's score, the winners),
and says how far each of those is from what the mathematics gives.

A word: pbin | bbin << 6 | hbin << 12 | pclass << 18 | proto << 35, with
pclass the privileged port of the pair (the smaller if both are, 65536 if
neither), proto the index in the fitted protocol table (255 if absent),
the bins searchsorted(side=right) of hour, log1p(bytes), log1p(packets)
in float32 against the float32 edges; its bucket is splitmix64(word ^
salt) mod n_buckets. An event has two tokens, the word in its source's
document and in its destination's. (The three bins alone are taken with
`jax.numpy` on the device the run has, block by block: log1p rounds to
the last bit as the backend's does, and a bin one over is another word.)

The update, for a batch of tokens (d, w) with lambda, step t, corpus D:
  E-step, per document, to its fixed point from the row it held:
    phi[n,k] ~ exp(psi(gamma[d,k]) - psi(sum gamma[d]) +
                   psi(lambda[w,k]) - psi(sum_w lambda[:,k])),
    gamma[d] = alpha + sum_n phi[n];
  lambda step: rho = (tau0 + t) ** -kappa,
    lambda' = (1 - rho) lambda + rho (eta + D / docs_in_batch * sum phi);
  a token's score sum_k theta[d,k] beta[w,k] under gamma' and lambda';
  an event's score the smaller of its two tokens'; the winners the
  `max_results` least under tol, ties to the lower index.

What is compared, and against what (`compare`):
  doc_mismatch  tokens whose document id is not the table's (exact);
  gamma_gap     over a sample of the batch's documents (`sample_docs`
                drawn from the seed, and the `sample_big` with the most
                tokens), the mean |gamma - fixed point| over the mean of
                the fixed point's row: the program stops a document when
                its mean change in a pass is under 1e-3 (or at 30
                passes), in float32, so it stands short of the point by
                what the last passes would have moved it;
  store_mismatch  documents the batch does not touch whose row is not
                the row that stood (exact): the store is what the next
                batch warm-starts from;
  pass_gap      the passes the E-step took (every token in each of the
                first svi_warm_iters, then the tokens of the documents
                still moving) against the passes the same stopping rule
                takes in float64 from the rows that stood before the
                batch, over every document. The fixed point does not
                say where the E-step started (a cold start reaches it
                too, within the rule's slack); the passes do. A
                document at the threshold stops a pass or two earlier
                or later in float32; a warm start dropped takes many
                more;
  lam_gap       lambda' against the lambda step taken in float64 from
                the gamma rows the program left (every token), largest
                relative departure: float32 sums of up to 1e5 terms;
  score_gap     every event's score against the score under the gamma
                rows and lambda the program left, largest relative
                departure: one float32 dot product of K terms;
  winner_gap, answer_mismatch   the winners against the reference's own
                scores, as `scan_check.judge` has them.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np
from scipy.special import psi

PCLASS_NONE = 65536
PROTO_ABSENT = 255
BLOCK = 1 << 22
SLABS = 4
_POOL = ThreadPoolExecutor(SLABS)


def _bins(edges: dict, hour, byt, pkt):
    """The three bin indices of every event, float32 on the device."""
    import jax
    import jax.numpy as jnp

    e = {k: jnp.asarray(np.asarray(edges[k], np.float32).ravel())
         for k in ("hour", "log_ibyt", "log_ipkt")}

    @jax.jit
    def block(h, b, p):
        return (jnp.searchsorted(e["hour"], h, side="right"),
                jnp.searchsorted(e["log_ibyt"], jnp.log1p(b), side="right"),
                jnp.searchsorted(e["log_ipkt"], jnp.log1p(p), side="right"))

    n = len(hour)
    out = [np.empty(n, np.int64) for _ in range(3)]
    for lo in range(0, n, BLOCK):
        hi = min(lo + BLOCK, n)
        pad = BLOCK - (hi - lo)
        got = block(*(jnp.asarray(np.pad(np.asarray(a[lo:hi], np.float32),
                                         (0, pad)))
                      for a in (hour, byt, pkt)))
        for o, g in zip(out, got):
            o[lo:hi] = np.asarray(g)[:hi - lo]
    return out


def splitmix64(x: np.ndarray) -> np.ndarray:
    x = x + np.uint64(0x9E3779B97F4A7C15)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def buckets(model: dict, cols: dict) -> np.ndarray:
    """Every event's word bucket."""
    sport = np.asarray(cols["sport"], np.int64)
    dport = np.asarray(cols["dport"], np.int64)
    s_low, d_low = sport <= 1024, dport <= 1024
    pclass = np.where(s_low & d_low, np.minimum(sport, dport),
                      np.where(s_low, sport,
                               np.where(d_low, dport, PCLASS_NONE)))
    fitted = [str(p) for p in model["edges"]["proto_classes"]]
    remap = np.asarray([fitted.index(p) if p in fitted else PROTO_ABSENT
                        for p in map(str, cols["proto_classes"])], np.int64)
    hbin, bbin, pbin = _bins(model["edges"], cols["hour"], cols["ibyt"],
                             cols["ipkt"])
    word = (pbin | bbin << 6 | hbin << 12 | pclass << 18
            | remap[np.asarray(cols["proto_id"], np.int64)] << 35)
    with np.errstate(over="ignore"):
        h = splitmix64(word.astype(np.uint64) ^ np.uint64(model["salt"]))
    return (h % np.uint64(model["n_buckets"])).astype(np.int64)


def doc_ids(model: dict, cols: dict) -> np.ndarray:
    """Every token's document, [sources | destinations]: the address's
    place in the table's first-seen order; -1 where the table lacks it."""
    keys = np.asarray(model["doc_keys"], np.uint32)
    order = np.argsort(keys, kind="stable")
    addr = np.concatenate([np.asarray(cols["sip_u32"], np.uint32),
                           np.asarray(cols["dip_u32"], np.uint32)])
    pos = np.minimum(np.searchsorted(keys[order], addr), len(keys) - 1)
    return np.where(keys[order][pos] == addr, order[pos], -1)


def _pairs(d: np.ndarray, w: np.ndarray, n_buckets: int):
    """Distinct (document, word) pairs, how often each occurs (the
    update of a pair of weight c is that of c equal tokens), and each
    token's pair."""
    uniq, of_token, cnt = np.unique(d * n_buckets + w, return_inverse=True,
                                    return_counts=True)
    return (uniq // n_buckets, uniq % n_buckets, cnt.astype(np.float64),
            of_token)


def _elog(x: np.ndarray, axis: int) -> np.ndarray:
    return psi(x) - psi(x.sum(axis=axis, keepdims=True))


def _sum_rows(idx, values, n):
    return np.stack([np.bincount(idx, weights=values[:, k], minlength=n)
                     for k in range(values.shape[1])], axis=1)


def soft_counts(elog_theta, d, rows_beta, cnt, into, n, r=None):
    """sum over the pairs of cnt x softmax_k(elog_theta[d] + rows_beta),
    added up by the rows `into` (the pairs' documents for the E-step,
    their words for the lambda step) of an [n, K] result. The pairs are
    worked in a few slabs on threads (numpy lets go of the interpreter
    in its loops; the sums are independent). No largest term is taken
    off before the exponential: the exponents are sums of two E[log]
    terms, far above float64's -745. `r` rounds every intermediate (the
    lower-precision control), on one thread."""
    if r is not None:
        logp = r(elog_theta[d] + rows_beta)
        p = r(np.exp(logp - logp.max(axis=1, keepdims=True)))
        phi = r(p / p.sum(axis=1, keepdims=True))
        return _sum_rows(into, r(phi * cnt[:, None]), n)

    def slab(lo):
        hi = min(lo + step, len(d))
        p = elog_theta[d[lo:hi]]
        p += rows_beta[lo:hi]
        np.exp(p, out=p)
        p *= (cnt[lo:hi] / p.sum(axis=1))[:, None]
        return _sum_rows(into[lo:hi], p, n)

    step = max(-(-len(d) // SLABS), 1 << 16)
    return sum(_POOL.map(slab, range(0, len(d), step)))


def fixed_point(gamma0, d_local, w, cnt, elog_beta, alpha: float,
                tol: float = 1e-8, max_iters: int | None = None,
                precision=None):
    """The E-step of documents 0..len(gamma0)-1 iterated until no row
    moves by more than `tol` in the mean. `precision`, where given,
    rounds every intermediate to it (the control that reads what a lower
    precision would)."""
    r = precision or (lambda x: x)
    gamma, rows_beta = r(gamma0.astype(np.float64)), r(elog_beta)[w]
    for it in range(max_iters or (200 if precision else 2000)):
        new = r(alpha + soft_counts(r(_elog(gamma, 1)), d_local, rows_beta,
                                    cnt, d_local, len(gamma), precision))
        moved = np.abs(new - gamma).mean(axis=1).max()
        gamma = new
        if moved < tol:
            break
    return gamma, it + 1


def stopped_by_rule(gamma0, d, w, cnt, elog_beta, alpha: float, warm: int,
                    cap: int, tol: float) -> int:
    """In how many passes the program's own stopping rule is through
    with every document of the batch from `gamma0`: `warm` passes over
    all of them, then the documents whose last pass moved them by more
    than `tol` in the mean go on, the rest frozen, until none moves that
    much or `cap` passes are spent."""
    gamma, rows_beta = gamma0.astype(np.float64), elog_beta[w]
    moved = np.full(len(gamma), np.inf)
    for _ in range(min(warm, cap)):
        new = alpha + soft_counts(_elog(gamma, 1), d, rows_beta, cnt, d,
                                  len(gamma))
        moved, gamma = np.abs(new - gamma).mean(axis=1), new
    passes = min(warm, cap)
    # The documents still moving are fixed here: they go on together
    # until the slowest is through.
    docs = np.flatnonzero(moved > tol)
    mine = np.isin(d, docs)
    local, rows_beta, cnt = np.searchsorted(docs, d[mine]), rows_beta[mine], \
        cnt[mine]
    gamma = gamma[docs]
    while passes < cap and len(docs):
        new = alpha + soft_counts(_elog(gamma, 1), local, rows_beta, cnt,
                                  local, len(docs))
        worst = np.abs(new - gamma).mean(axis=1).max()
        gamma = new
        passes += 1
        if worst <= tol:
            break
    return passes


def bf16(x):
    """Round to bfloat16's eight bits of mantissa."""
    b = np.asarray(x, np.float32).view(np.uint32)
    b = (b + np.uint32(0x7FFF) + ((b >> np.uint32(16)) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return b.view(np.float32).astype(np.float64)


def judge(ref_scores: np.ndarray, answer_idx, answer_scores,
          max_results: int) -> dict:
    """`scan_check.judge` in numpy: the answer for one batch against the
    reference's score of every event (inf where not under tol)."""
    n = len(ref_scores)
    due = min(max_results, int(np.isfinite(ref_scores).sum()))
    keep = answer_idx >= 0
    idx = answer_idx[keep].astype(np.int64)
    got = np.asarray(answer_scores, np.float64)[keep]
    bad = abs(len(idx) - due) + int((idx >= n).sum())
    bad += len(idx) - len(np.unique(idx))
    bad += int((np.diff(got) < 0).sum())
    ref = ref_scores[np.minimum(idx, n - 1)]
    bad += int((~np.isfinite(ref)).sum())
    out = {"answer_mismatch": bad, "winner_gap": 0.0, "n_due": due}
    ok = np.isfinite(ref)
    if due and ok.any():
        kth = float(np.partition(ref_scores, due - 1)[due - 1])
        out["winner_gap"] = float(np.maximum(ref[ok] / kth - 1.0, 0).max())
        out["kth_score"] = kth
    return out


def compare(config: dict, model: dict, cols: dict, before: dict, left: dict,
            seed: int, precision=None, words=None) -> dict:
    """Every number of the module docstring for one batch. `before`:
    `lam`, `step`, `corpus_docs`, `gamma` (every document's row);
    `left`: `lam`, `gamma`, `events` (per-event scores), `indices` and
    `scores` (the winners), `passes` (of the E-step), `doc_ids` (per
    token, -1 or any row past the table for an address it lacks).
    `words` takes `buckets(model, cols)` where the caller has them (the
    one part that touches the device)."""
    alpha, eta = float(config["alpha"]), float(config["eta"])
    n_buckets, n_docs = int(model["n_buckets"]), len(model["doc_keys"])
    n = len(cols["sip_u32"])
    w_ev = buckets(model, cols) if words is None else words
    d, w = doc_ids(model, cols), np.concatenate([w_ev, w_ev])
    got_d = np.asarray(left["doc_ids"], np.int64)
    out = {"doc_mismatch": int((np.where(got_d < n_docs, got_d, -1)
                                != d).sum()),
           "unknown_addresses": int((d < 0).sum())}
    if out["unknown_addresses"]:
        raise ValueError("the batch holds addresses the table lacks")

    lam0 = np.asarray(before["lam"], np.float64)
    elog_beta = _elog(lam0, 0)
    pd_, pw, cnt, of_token = _pairs(d, w, n_buckets)

    # gamma: a sample of the batch's documents, to their fixed point.
    rng = np.random.default_rng(seed)
    in_batch, tokens_of = np.unique(d, return_counts=True)
    big = in_batch[np.argsort(-tokens_of, kind="stable")
                   [:int(config["check"]["sample_big"])]]
    some = rng.choice(in_batch, min(int(config["check"]["sample_docs"]),
                                    len(in_batch)), replace=False)
    sample = np.union1d(big, some)
    at = np.searchsorted(sample, pd_)
    mine = (at < len(sample)) & (sample[np.minimum(at, len(sample) - 1)]
                                 == pd_)
    ref_gamma, passes = fixed_point(
        np.asarray(before["gamma"])[sample], at[mine], pw[mine], cnt[mine],
        elog_beta, alpha, precision=precision)
    got_gamma = np.asarray(left["gamma"], np.float64)
    out["gamma_gap"] = float(
        (np.abs(got_gamma[sample] - ref_gamma).mean(axis=1)
         / ref_gamma.mean(axis=1)).max())
    out["fixed_point_passes"] = passes
    touched = np.zeros(len(got_gamma), bool)
    touched[in_batch] = True
    out["passes_due"] = stopped_by_rule(
        np.where(touched[:, None], np.asarray(before["gamma"]), alpha),
        pd_, pw, cnt, elog_beta, alpha, int(config["svi_warm_iters"]),
        int(config["svi_local_iters"]), float(config["svi_meanchange_tol"]))
    out["pass_gap"] = abs(int(left["passes"]) - out["passes_due"])
    kept = ~touched[:n_docs]
    out["store_mismatch"] = int(
        (got_gamma[:n_docs][kept]
         != np.asarray(before["gamma"], np.float64)[:n_docs][kept])
        .any(axis=1).sum())

    # lambda: the step from the rows the program left, every token.
    r = precision or (lambda x: x)
    sstats = soft_counts(r(_elog(got_gamma[:n_docs], 1)), pd_, r(elog_beta)[pw],
                         cnt, pw, n_buckets, precision)
    rho = (float(config["svi_tau0"]) + float(before["step"])) \
        ** -float(config["svi_kappa"])
    scale = float(before["corpus_docs"]) / len(in_batch)
    ref_lam = r((1.0 - rho) * lam0 + rho * (eta + scale * sstats))
    got_lam = np.asarray(left["lam"], np.float64)
    out["lam_gap"] = float(np.abs(got_lam / ref_lam - 1.0).max())

    # scores: every event under the rows and the lambda the program left.
    theta = got_gamma[:n_docs] / got_gamma[:n_docs].sum(axis=1,
                                                        keepdims=True)
    beta = got_lam / got_lam.sum(axis=0, keepdims=True)
    tok = np.einsum("pk,pk->p", theta[pd_], beta[pw])[of_token]
    ref_ev = np.minimum(tok[:n], tok[n:])
    got_ev = np.asarray(left["events"], np.float64)[:n]
    out["score_gap"] = float(np.abs(got_ev / ref_ev - 1.0).max())
    kept = np.where(ref_ev < float(config["tol"]), ref_ev, np.inf)
    out.update(judge(kept, np.asarray(left["indices"]),
                     np.asarray(left["scores"]), int(config["max_results"])))
    win = np.asarray(left["indices"])
    win = win[win >= 0]
    if len(win):            # the winners' scores are their events' scores
        out["score_gap"] = max(out["score_gap"], float(np.abs(
            np.asarray(left["scores"], np.float64)[:len(win)]
            / ref_ev[np.minimum(win, n - 1)] - 1.0).max()))
    return out
