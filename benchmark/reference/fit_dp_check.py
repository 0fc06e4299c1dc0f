"""Plain reference for the doc-sharded sweep, in numpy and float64. It
imports nothing of the program (only `fit_check`, the one-chip
reference beside it) and takes none of its tables.

The sweep, as `flow-k20-dp4.json` states it: documents are dealt over P
chips, a document's tokens all on one of them; a chip holds its own
rows of n_dk and its own copy of n_wk and n_k. Within a sweep chip p
resamples its tokens block by block from `fit_check`'s conditional,
over the counts as they stood when the sweep began plus *its own*
changes in the blocks before; it sees nothing of its peers. At the
sweep's end every chip's changes of n_wk and n_k are summed into every
copy. So the counts at the start of block b of chip p follow from the
tables before the sweep and that chip's assignments of its blocks
before b, before and after: nothing of the program's arithmetic is
needed for a chip's first blocks. For its last blocks the chip's
whole-sweep change of n_wk would be needed, which the state does not
hold; they are not judged here (the configuration says so).

The exact checks cover every token of every chip, a chip at a time and
without a copy of the whole corpus: a bucket's live tokens are a prefix
of it, which is checked, so they are views.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import fit_check

_CHUNK = 1 << 25        # tokens a histogram pass: bounds the int64 keys


def live_prefix(mask: np.ndarray) -> tuple[int, int]:
    """(live tokens, slots that break the rule) of one bucket's mask:
    the live tokens have to come first, 1.0 each, zeros after them."""
    flat = mask.reshape(-1)
    n = int(np.count_nonzero(flat))
    bad = int(n - np.count_nonzero(flat[:n] == 1.0))
    bad += int(np.count_nonzero(flat[n:]))
    return n, bad


def shard_tables(docs, words, z, n_live: int, n_docs_local: int,
                 n_vocab: int, k: int):
    """What one chip's rows of n_dk and its share of n_wk have to be:
    the histograms of its live tokens' assignments, and the live tokens
    whose assignment is no topic at all (they fit no table)."""
    d, w, zz = (a.reshape(-1)[:n_live] for a in (docs, words, z))
    ref_dk = np.zeros((n_docs_local, k), np.int64)
    ref_wk = np.zeros((n_vocab, k), np.int64)
    key = np.empty(min(n_live, _CHUNK), np.int64)   # id * K + z, reused
    stray = 0
    for lo in range(0, n_live, _CHUNK):
        zc = zz[lo:lo + _CHUNK]
        ok = None
        if zc.min() < 0 or zc.max() >= k:
            ok = (zc >= 0) & (zc < k)
            stray += int(zc.shape[0] - ok.sum())
            zc = zc[ok]
        for ids, ref in ((d, ref_dk), (w, ref_wk)):
            ic = ids[lo:lo + _CHUNK]
            kc = key[:zc.shape[0]]
            np.multiply(ic if ok is None else ic[ok], k, out=kc)
            kc += zc
            ref += np.bincount(kc, minlength=ref.size).reshape(ref.shape)
    return ref_dk, ref_wk, stray


def layout_and_split(doc_tokens: list[np.ndarray], word_tokens: np.ndarray,
                     doc_map: np.ndarray, n_live: int, prefix_bad: int,
                     corpus_doc_tokens: np.ndarray,
                     corpus_word_tokens: np.ndarray) -> tuple[int, int]:
    """(`layout_mismatch`, `doc_split`). The layout has to hold each
    document's and each word's tokens, no more and no fewer, as live
    prefixes; and no document may have tokens on two chips.
    `doc_tokens[p][i]` counts the tokens of chip p's local document i,
    `doc_map[p, i]` names it (-1: none)."""
    n_docs = corpus_doc_tokens.shape[0]
    got = np.zeros(n_docs, np.int64)
    chips_of = np.zeros(n_docs, np.int64)
    bad = prefix_bad + abs(n_live - int(corpus_doc_tokens.sum()))
    for p, cnt in enumerate(doc_tokens):
        named = doc_map[p] >= 0
        bad += int(cnt[~named].sum())       # tokens of no document
        np.add.at(got, doc_map[p][named], cnt[named])
        np.add.at(chips_of, doc_map[p][named], cnt[named] > 0)
    bad += int(np.abs(got - corpus_doc_tokens).sum())
    bad += int(np.abs(word_tokens - corpus_word_tokens).sum())
    return bad, int((chips_of > 1).sum())


def count_mismatch(ref_dk: list[np.ndarray], ref_wk: np.ndarray, stray: int,
                   n_dk: np.ndarray, n_wk: np.ndarray, n_k: np.ndarray
                   ) -> int:
    """Cells of the count tables that differ from the histograms of the
    assignments they are said to count: every chip's rows of n_dk
    (`n_dk[p]`), and n_wk and n_k against the sum over the chips."""
    bad = stray + sum(int((r != n_dk[p]).sum()) for p, r in enumerate(ref_dk))
    return bad + int((ref_wk != n_wk).sum()) + int(
        (ref_wk.sum(axis=0) != n_k).sum())


def replica_mismatch(copies: list[np.ndarray]) -> int:
    """Cells in which any chip's own copy of a replicated table differs
    from the first chip's."""
    return sum(int((c != copies[0]).sum()) for c in copies[1:])


def acc_mismatch(acc_ndk, acc_nwk, n_acc: int, before: dict, after: dict,
                 want: int) -> int:
    """The accumulators against what `want` folded sweeps leave: nothing,
    the last sweep's counts, or the last two sweeps' (more cannot be
    rebuilt from two states; then only the count is held). They are
    float32 sums, as the configuration states, and are held to the
    float32 sum exactly: a count past 2^24 is rounded as it is folded in
    (at 6e8 tokens one or two cells of n_wk are that large)."""
    bad = abs(int(n_acc) - want)
    f32 = {x: {y: t.astype(np.float32) for y, t in s.items()}
           for x, s in (("before", before), ("after", after))}
    if want == 0:
        bad += int((acc_ndk != 0).sum() + (acc_nwk != 0).sum())
    elif want == 1:
        bad += int((acc_ndk != f32["after"]["n_dk"]).sum()
                   + (acc_nwk != f32["after"]["n_wk"]).sum())
    elif want == 2:
        bad += int((acc_ndk != f32["before"]["n_dk"]
                    + f32["after"]["n_dk"]).sum()
                   + (acc_nwk != f32["before"]["n_wk"]
                      + f32["after"]["n_wk"]).sum())
    return bad


def sampler_stats(heads: list[dict], before: dict, *, alpha: float,
                  eta: float, n_vocab: int) -> dict:
    """Statistics of the last sweep over every chip's first blocks, as
    one sample. `heads[p]` holds docs, words, mask, z_before and z_after
    of chip p's first blocks ([m, B] each); `before` the tables as the
    sweep began: `n_dk` [P, Dl, K], `n_wk`, `n_k`. Each chip walks
    forward from `before` through its own blocks alone: its peers'
    changes are not in its counts. With one chip this is
    `fit_check.sampler_stats` over the head, to the bit."""
    v_eta = n_vocab * eta
    acc = dict.fromkeys(
        ("n", "moved", "moved_expected", "moved_var", "loglik",
         "loglik_expected", "loglik_var"), 0.0)
    for p, head in enumerate(heads):
        n_dk = before["n_dk"][p].astype(np.int64)
        n_wk, n_k = (before[x].astype(np.int64) for x in ("n_wk", "n_k"))
        for b in range(head["docs"].shape[0]):
            m = head["mask"][b] > 0
            d, w = head["docs"][b][m], head["words"][b][m]
            zo, zn = head["z_before"][b][m], head["z_after"][b][m]
            fit_check._block_stats(acc, n_dk, n_wk, n_k, d, w, zo, zn,
                                   alpha, eta, v_eta)
            fit_check._apply(n_dk, n_wk, n_k, d, w, zo, zn, +1)
    n = max(acc["n"], 1.0)
    return {
        "n_tokens": acc["n"],
        "move_gap": abs(acc["moved"] / max(acc["moved_expected"], 1e-30) - 1.0),
        "loglik_gap": abs(acc["loglik"] - acc["loglik_expected"]) / n,
        "moved_share": acc["moved"] / n,
        "move_gap_sigma": max(acc["moved_var"], 0.0) ** 0.5
        / max(acc["moved_expected"], 1e-30),
        "loglik_gap_sigma": max(acc["loglik_var"], 0.0) ** 0.5 / n,
    }


def resample_heads(heads: list[dict], before: dict, *, alpha: float,
                   eta: float, n_vocab: int, rng: np.random.Generator,
                   keep_every: int = 0) -> list[np.ndarray]:
    """The reference in the program's place for every chip's first
    blocks (`fit_check.resample_blocks`, a chip at a time from the
    tables before the sweep; `keep_every=2` is the broken control)."""
    return [fit_check.resample_blocks(
        head, {"n_dk": before["n_dk"][p], "n_wk": before["n_wk"],
               "n_k": before["n_k"]},
        alpha=alpha, eta=eta, n_vocab=n_vocab, rng=rng,
        keep_every=keep_every) for p, head in enumerate(heads)]


def head_changes(head: dict, n_vocab: int, k: int):
    """One chip's changes of n_wk and n_k in its first blocks: what the
    sweep's merge owes its peers for them."""
    m = head["mask"] > 0
    w = head["words"][m]
    d_wk = (fit_check.hist2(w, head["z_after"][m], n_vocab, k)
            - fit_check.hist2(w, head["z_before"][m], n_vocab, k))
    return d_wk, d_wk.sum(axis=0)
