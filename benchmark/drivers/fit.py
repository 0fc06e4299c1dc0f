"""Driver `fit`: the window drives the public
`ShardedGibbsLDA.fit(corpus, callback=...)`, the product's per-sweep path
(one sweep and one boundary log-likelihood per dispatch).

Set-up builds the corpus from the seed through the product's own front
(`SYNTH_ARRAYS` -> `*_words_from_arrays` -> `build_corpus`) at the mix's
`base_events`, and tiles it `copies` times with the document ids of each
copy offset: `copies` statistically equal sites, the product vocabulary,
the real skew of document lengths. `fit` then pays `prepare`,
`init_state` and the transfer itself. The first two callbacks absorb the
two programs the per-sweep path compiles (with and without the initial
log-likelihood); the window opens at the second, counts every later
sweep, and closes at the first callback at or after `--seconds`, where
the callback raises `WindowClosed`. The rate divides by the true time.

What is compared is in `reference/fit_check.py`: the state the timed
sweeps left, against the corpus and against the state one sweep earlier.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import harness


class WindowClosed(Exception):
    """Raised by the callback to end `fit` when the window has closed."""


def tile_corpus(base, copies: int):
    """`copies` sites: the same tokens, document ids offset per copy."""
    from onix.corpus import Corpus
    n = base.n_tokens
    docs = np.empty(n * copies, np.int32)
    words = np.empty(n * copies, np.int32)
    for c in range(copies):
        np.add(base.doc_ids, np.int32(c * base.n_docs),
               out=docs[c * n:(c + 1) * n])
        words[c * n:(c + 1) * n] = base.word_ids
    return Corpus(docs, words, base.n_docs * copies, base.n_vocab)


def base_corpus(config: dict, traffic: dict, seed: int):
    from onix.pipelines import words as words_mod
    from onix.pipelines.corpus_build import build_corpus
    from onix.pipelines.synth import SYNTH_ARRAYS
    dt = config["datatype"]
    cols = SYNTH_ARRAYS[dt](
        int(traffic["base_events"]), n_hosts=int(traffic["base_hosts"]),
        n_anomalies=int(traffic["base_anomalies"]), seed=seed)
    builder = getattr(words_mod, f"{dt}_words_from_arrays")
    keep = {k: v for k, v in cols.items() if k != "anomaly_idx"}
    return build_corpus(builder(**keep)).corpus


def _snapshot(state, m_head: int, m_tail: int) -> dict:
    """Small device copies, dispatched before the next sweep takes the
    state's buffers: the first and last blocks of z and the counts."""
    import jax.numpy as jnp
    nb = state.z.shape[3]
    return {"z_head": state.z[0, 0, 0, :m_head] + 0,
            "z_tail": state.z[0, 0, 0, nb - m_tail:] + 0,
            "n_dk": jnp.copy(state.n_dk[0, 0]),
            "n_wk": jnp.copy(state.n_wk[0, 0]),
            "n_k": jnp.copy(state.n_k[0])}


def run(run: dict) -> dict:
    from onix.config import LDAConfig
    from onix.parallel.mesh import make_mesh
    from onix.parallel.sharded_gibbs import ShardedGibbsLDA

    config, traffic, spans = run["config"], run["traffic"], run["spans"]
    seed = harness.fold_seed(run["seed"])
    seconds, tracer, compiles = run["seconds"], run["tracer"], run["compiles"]

    with spans.span("front"):
        base = base_corpus(config, traffic, seed)
    with spans.span("tile"):
        corpus = tile_corpus(base, int(traffic["copies"]))
    lda = LDAConfig(
        n_topics=config["n_topics"], alpha=config["alpha"], eta=config["eta"],
        n_sweeps=10 ** 6,          # the window closes the fit, not a count
        burn_in=config["burn_in"], block_size=config["block_size"], seed=seed)
    model = ShardedGibbsLDA(lda, corpus.n_vocab, mesh=make_mesh(dp=1, mp=1))

    layout = {}
    prepare = model.prepare

    def prepare_and_keep(c):
        t0 = time.monotonic()
        layout["sc"] = prepare(c)
        spans.add("prepare", t0, time.monotonic())
        return layout["sc"]

    model.prepare = prepare_and_keep    # the layout the reference checks
    open_at = int(traffic["open_at_callback"])
    m = int(traffic["check_blocks"])
    w = {"times": [], "t_open": None, "prev": None, "last": None,
         "last_prev": None, "compiles_open": 0, "m": None}

    def callback(sweep: int, state):
        now = time.monotonic()
        with spans.span("callback"):
            w["times"].append(now)
            n = len(w["times"])
            if w["m"] is None:
                nb = state.z.shape[3]
                mh = min(m, (nb + 1) // 2)
                w["m"] = (mh, min(m, nb - mh))
            if n == open_at:
                w["t_open"] = now
                w["compiles_open"] = compiles.n
                tracer.start()
            elif n > open_at:
                spans.add("sweep", w["cb_end"], now)   # less the callback
                if n - open_at >= int(traffic["trace_sweeps"]):
                    tracer.stop()
                if now - w["t_open"] >= seconds:
                    w.update(last=state, last_prev=w["prev"], sweep=sweep,
                             compiles_close=compiles.n)
                    raise WindowClosed
            w["prev"] = _snapshot(state, *w["m"])
            w["cb_end"] = time.monotonic()

    t_fit = time.monotonic()
    try:
        with spans.span("fit_loop"):
            model.fit(corpus, callback=callback)
        raise RuntimeError("fit ended before the window closed")
    except WindowClosed:
        pass
    tracer.stop()
    t_close = w["times"][-1]
    sweeps = len(w["times"]) - open_at
    elapsed = t_close - w["t_open"]
    spans.add("fit_setup", t_fit, w["t_open"])
    peak = harness.memory_peak_bytes()

    # What the check needs, to the host; then the program's state goes.
    state, prev, sc = w["last"], w["last_prev"], layout["sc"]
    mh, mt = w["m"]
    nb = sc.doc_blocks.shape[2]
    after = {"n_dk": np.asarray(state.n_dk[0, 0]),
             "n_wk": np.asarray(state.n_wk[0, 0]),
             "n_k": np.asarray(state.n_k[0])}
    acc = {"acc_ndk": np.asarray(state.acc_ndk[0, 0]),
           "acc_nwk": np.asarray(state.acc_nwk[0, 0]),
           "n_acc": int(state.n_acc)}
    z_last = np.asarray(state.z[0, 0, 0])
    before = {k: np.asarray(prev[k]) for k in ("n_dk", "n_wk", "n_k")}
    z_head_before, z_tail_before = (np.asarray(prev["z_head"]),
                                    np.asarray(prev["z_tail"]))
    compiled = w["compiles_close"] - w["compiles_open"]
    del state, prev, model
    w.clear()

    docs, words, mask = (a[0, 0] for a in (sc.doc_blocks, sc.word_blocks,
                                           sc.mask_blocks))
    head = {"docs": docs[:mh], "words": words[:mh], "mask": mask[:mh],
            "z_before": z_head_before, "z_after": z_last[:mh].copy()}
    tail = {"docs": docs[nb - mt:], "words": words[nb - mt:],
            "mask": mask[nb - mt:], "z_before": z_tail_before,
            "z_after": z_last[nb - mt:].copy()}
    from benchmark.reference import fit_check
    whole = {   # the passes over the whole corpus, made once
        "layout_mismatch": fit_check.layout_mismatch(
            docs, words, mask, corpus.doc_ids, corpus.word_ids,
            corpus.n_docs, corpus.n_vocab),
        "tables": fit_check.count_tables(
            docs, words, mask, z_last, after["n_dk"].shape[0],
            after["n_wk"].shape[0], config["n_topics"]),
        "n_vocab": corpus.n_vocab, "sweeps_done": sweeps + open_at}
    _compare(run["check"], config, whole, head, tail, before, after, acc)
    controls = {}
    for name in filter(None, (run["control"] or "").split(",")):
        # The same run judged again with a control in the program's place.
        c_head, c_after = (dict(head, z_after=head["z_after"].copy()),
                           {k: v.copy() for k, v in after.items()})
        _apply_control(name, config, corpus, seed, c_head, before, c_after,
                       z_last, docs, mask)
        check = harness.Check()
        _compare(check, config, whole, c_head,
                 {k: v[:0] for k, v in head.items()}, before, c_after, acc)
        controls[name] = {"correct": check.correct, "check": check.as_dict()}

    tokens = sweeps * corpus.n_tokens
    return {
        "end_to_end": {
            "fit_tokens_per_s": tokens / elapsed,
            "setup_s": (t_close - elapsed) - run["t_start"],
        },
        "attempted": sweeps, "failed": 0,
        "memory_peak_bytes": peak,
        "compiles_in_window": compiled,
        "controls": controls,
        "window": {"elapsed_s": elapsed, "sweeps": sweeps, "tokens": tokens,
                   "items_per_call": corpus.n_tokens,
                   "n_docs": corpus.n_docs, "n_vocab": corpus.n_vocab,
                   "n_blocks": int(nb)},
    }


def _apply_control(name: str, config, corpus, seed, head, before, after,
                   z_last, docs, mask) -> None:
    """Puts the reference, sound or broken, in the program's place for the
    head blocks (the tail is then left out of the statistics)."""
    from benchmark.reference import fit_check
    kw = dict(alpha=config["alpha"], eta=config["eta"],
              n_vocab=corpus.n_vocab, rng=np.random.default_rng(seed))
    if name == "reference":
        head["z_after"] = fit_check.resample_blocks(head, before, **kw)
    elif name == "half_kept":
        head["z_after"] = fit_check.resample_blocks(head, before,
                                                    keep_every=2, **kw)
    elif name == "token_shift":
        # Every token of the head altered where it is produced.
        live = head["mask"] > 0
        head["z_after"] = np.where(
            live, (head["z_after"] + 1) % config["n_topics"], head["z_after"])
    elif name == "counts_stale":
        # The counts miss the changes of every second token of block 0.
        m = np.flatnonzero(mask[0] > 0)[::2]
        np.add.at(after["n_dk"], (docs[0][m], z_last[0][m]), -1)
        np.add.at(after["n_dk"], (docs[0][m], head["z_before"][0][m]), 1)
    else:
        raise ValueError(f"unknown control {name!r}")


def _compare(check, config, whole, head, tail, before, after, acc) -> None:
    from benchmark.reference import fit_check
    lim = config["limits"]
    check.compare("layout_mismatch", whole["layout_mismatch"],
                  lim["layout_mismatch"])
    check.compare("count_mismatch", fit_check.count_mismatch(
        *whole["tables"], after["n_dk"], after["n_wk"], after["n_k"]),
        lim["count_mismatch"])
    st = fit_check.sampler_stats(head, tail, before, after,
                                 alpha=config["alpha"], eta=config["eta"],
                                 n_vocab=whole["n_vocab"])
    check.compare("move_gap", st["move_gap"], lim["move_gap"])
    check.compare("loglik_gap", st["loglik_gap"], lim["loglik_gap"])
    check.note("move_gap_sigma", st["move_gap_sigma"])
    check.note("loglik_gap_sigma", st["loglik_gap_sigma"])
    check.note("moved_share", st["moved_share"])
    check.note("checked_tokens", st["n_tokens"])
    # Accumulators: sweep s (from 0) is folded in once s >= burn_in.
    want = max(0, whole["sweeps_done"] - config["burn_in"])
    bad = abs(acc["n_acc"] - want)
    if want == 0:
        bad += int((acc["acc_ndk"] != 0).sum() + (acc["acc_nwk"] != 0).sum())
    elif want == 1:
        bad += int((acc["acc_ndk"] != after["n_dk"]).sum()
                   + (acc["acc_nwk"] != after["n_wk"]).sum())
    elif want == 2:
        bad += int((acc["acc_ndk"] != before["n_dk"] + after["n_dk"]).sum()
                   + (acc["acc_nwk"] != before["n_wk"] + after["n_wk"]).sum())
    check.compare("acc_mismatch", bad, lim["acc_mismatch"])
