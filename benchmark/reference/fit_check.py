"""Plain reference for the blocked collapsed-Gibbs sweep, in numpy and
float64. It imports nothing of the program and takes none of its tables:
it is given the corpus, the token layout (checked here against the
corpus), and the sampler's state before and after the window's last
sweep, and says whether that sweep did what the configuration states.

The sweep, as `flow-k20.json` states it: tokens lie in blocks of B. For
each block in order, every token of the block is resampled from

    p(k) ~ (n_dk[d,k] - own + alpha) (n_wk[w,k] - own + eta)
           / (n_k[k] - own + V eta)

with the counts as they stood when the block began and `own` the token's
present assignment; then the counts take the block's changes. So the
counts at the start of any block follow from the state before the sweep,
the state after it, and the assignments of the blocks between: nothing
of the program's arithmetic is needed to rebuild them.
"""

from __future__ import annotations

import numpy as np


def hist2(rows: np.ndarray, cols: np.ndarray, n_rows: int, n_cols: int
          ) -> np.ndarray:
    flat = rows.astype(np.int64) * n_cols + cols
    return np.bincount(flat, minlength=n_rows * n_cols).reshape(
        n_rows, n_cols)


def layout_mismatch(docs, words, mask, corpus_docs, corpus_words,
                    n_docs: int, n_vocab: int) -> int:
    """Tokens the layout lost, made up or moved: it has to hold each
    document's and each word's tokens, no more and no fewer."""
    m = mask.ravel() > 0
    d, w = docs.ravel()[m], words.ravel()[m]
    bad = abs(int(m.sum()) - int(corpus_docs.shape[0]))
    bad += int(np.abs(np.bincount(d, minlength=n_docs)
                      - np.bincount(corpus_docs, minlength=n_docs)).sum())
    bad += int(np.abs(np.bincount(w, minlength=n_vocab)
                      - np.bincount(corpus_words, minlength=n_vocab)).sum())
    return bad


def count_tables(docs, words, mask, z, n_docs: int, n_vocab: int, k: int):
    """The histograms of the assignments: what n_dk and n_wk have to be.
    Blocks are taken a few at a time so that the int64 keys stay small."""
    ref_dk = np.zeros((n_docs, k), np.int64)
    ref_wk = np.zeros((n_vocab, k), np.int64)
    step = max(1, (1 << 25) // docs.shape[1])
    for lo in range(0, docs.shape[0], step):
        m = mask[lo:lo + step].ravel() > 0
        zz = z[lo:lo + step].ravel()[m]
        ref_dk += hist2(docs[lo:lo + step].ravel()[m], zz, n_docs, k)
        ref_wk += hist2(words[lo:lo + step].ravel()[m], zz, n_vocab, k)
    return ref_dk, ref_wk


def count_mismatch(ref_dk, ref_wk, n_dk, n_wk, n_k) -> int:
    """Cells of the three count tables that differ from the histograms of
    the assignments they are said to count."""
    return (int((ref_dk != n_dk).sum()) + int((ref_wk != n_wk).sum())
            + int((ref_wk.sum(axis=0) != n_k).sum()))


def _conditional(n_dk, n_wk, n_k, d, w, z_old, alpha, eta, v_eta):
    k = n_k.shape[0]
    own = np.zeros((d.shape[0], k))
    own[np.arange(d.shape[0]), z_old] = 1.0
    p = ((n_dk[d] - own + alpha) * np.maximum(n_wk[w] - own + eta, 1e-10)
         / (n_k[None, :] - own + v_eta))
    return p / p.sum(axis=1, keepdims=True)


def _block_stats(acc, n_dk, n_wk, n_k, d, w, z_old, z_new, alpha, eta, v_eta):
    p = _conditional(n_dk, n_wk, n_k, d, w, z_old, alpha, eta, v_eta)
    rows = np.arange(d.shape[0])
    acc["n"] += d.shape[0]
    acc["moved"] += float((z_new != z_old).sum())
    stay = p[rows, z_old]
    logp = np.log(np.maximum(p, 1e-300))
    mean = (p * logp).sum(axis=1)
    acc["moved_expected"] += float((1.0 - stay).sum())
    acc["moved_var"] += float((stay * (1.0 - stay)).sum())
    acc["loglik"] += float(logp[rows, z_new].sum())
    acc["loglik_expected"] += float(mean.sum())
    acc["loglik_var"] += float(((p * logp * logp).sum(axis=1)
                                - mean * mean).sum())


def _apply(n_dk, n_wk, n_k, d, w, z_from, z_to, sign):
    k = n_k.shape[0]
    np.add.at(n_dk, (d, z_to), sign)
    np.add.at(n_dk, (d, z_from), -sign)
    np.add.at(n_wk, (w, z_to), sign)
    np.add.at(n_wk, (w, z_from), -sign)
    n_k += sign * (np.bincount(z_to, minlength=k)
                   - np.bincount(z_from, minlength=k))


def sampler_stats(head: dict, tail: dict, before: dict, after: dict, *,
                  alpha: float, eta: float, n_vocab: int) -> dict:
    """Statistics of the last sweep over its first and its last blocks.

    `head` and `tail` hold docs, words, mask, z_before and z_after of
    those blocks ([m, B] each); `before` and `after` the count tables at
    the two ends of the sweep. Walks forward from `before` through the
    head and backward from `after` through the tail, so each block is
    judged against the counts it began with."""
    v_eta = n_vocab * eta
    acc = dict.fromkeys(
        ("n", "moved", "moved_expected", "moved_var", "loglik",
         "loglik_expected", "loglik_var"), 0.0)
    n_dk, n_wk, n_k = (before[x].astype(np.int64).copy()
                       for x in ("n_dk", "n_wk", "n_k"))
    for b in range(head["docs"].shape[0]):
        m = head["mask"][b] > 0
        d, w = head["docs"][b][m], head["words"][b][m]
        zo, zn = head["z_before"][b][m], head["z_after"][b][m]
        _block_stats(acc, n_dk, n_wk, n_k, d, w, zo, zn, alpha, eta, v_eta)
        _apply(n_dk, n_wk, n_k, d, w, zo, zn, +1)
    n_dk, n_wk, n_k = (after[x].astype(np.int64).copy()
                       for x in ("n_dk", "n_wk", "n_k"))
    for b in range(tail["docs"].shape[0] - 1, -1, -1):
        m = tail["mask"][b] > 0
        d, w = tail["docs"][b][m], tail["words"][b][m]
        zo, zn = tail["z_before"][b][m], tail["z_after"][b][m]
        _apply(n_dk, n_wk, n_k, d, w, zo, zn, -1)   # back to the block's start
        _block_stats(acc, n_dk, n_wk, n_k, d, w, zo, zn, alpha, eta, v_eta)
    n = max(acc["n"], 1.0)
    return {
        "n_tokens": acc["n"],
        "move_gap": abs(acc["moved"] / max(acc["moved_expected"], 1e-30) - 1.0),
        "loglik_gap": abs(acc["loglik"] - acc["loglik_expected"]) / n,
        "moved_share": acc["moved"] / n,
        # One standard deviation of each gap under the reference's own
        # sampler: what a sound run's reading is expected to scatter by.
        "move_gap_sigma": max(acc["moved_var"], 0.0) ** 0.5
        / max(acc["moved_expected"], 1e-30),
        "loglik_gap_sigma": max(acc["loglik_var"], 0.0) ** 0.5 / n,
    }


def resample_blocks(head: dict, before: dict, *, alpha: float, eta: float,
                    n_vocab: int, rng: np.random.Generator,
                    keep_every: int = 0) -> np.ndarray:
    """The reference put in the program's place for the head blocks: draws
    each token's assignment from the conditional above. `keep_every=2`
    breaks the guarantee that every token is resampled: every second
    token keeps its assignment (the control)."""
    v_eta = n_vocab * eta
    n_dk, n_wk, n_k = (before[x].astype(np.int64).copy()
                       for x in ("n_dk", "n_wk", "n_k"))
    out = head["z_before"].copy()
    for b in range(head["docs"].shape[0]):
        m = np.flatnonzero(head["mask"][b] > 0)
        d, w, zo = head["docs"][b][m], head["words"][b][m], head["z_before"][b][m]
        p = _conditional(n_dk, n_wk, n_k, d, w, zo, alpha, eta, v_eta)
        u = rng.random(d.shape[0])[:, None]
        zn = np.minimum((p.cumsum(axis=1) < u).sum(axis=1),
                        n_k.shape[0] - 1).astype(zo.dtype)
        if keep_every:
            zn[::keep_every] = zo[::keep_every]
        out[b][m] = zn
        _apply(n_dk, n_wk, n_k, d, w, zo, zn, +1)
    return out
