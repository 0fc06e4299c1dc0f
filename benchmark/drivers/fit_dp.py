"""Driver `fit_dp`: `fit`'s window over a mesh of the cell's chips. The
public `ShardedGibbsLDA.fit(corpus, callback=...)` runs on
`make_mesh(dp=<chips>, mp=1)`: documents dealt over the chips, the
`shard_map` superstep, one sum of the chips' changes of n_wk and n_k a
sweep. The corpus, the two warm-up callbacks, the window's rule and the
rate are driver `fit`'s (its functions are loaded, not copied); the rate
counts every chip's tokens over the window's wall time.

What differs is what is kept and compared: every chip's layout, every
chip's first blocks of z and the tables one sweep earlier, every chip's
own copy of the replicated tables (`reference/fit_dp_check.py`; a chip's
last blocks are not judged, see there). `window.items_per_call` is the
tokens ONE chip sweeps a call: the trace readers average programs and
scopes over the device planes, so the shares of a roofline and of a peak
stay shares of one chip's. Controls (`--control`): `fit`'s four
(`reference`, sound; `half_kept`, `token_shift`, `counts_stale`) and
`merge_dropped`.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import harness

def tiled_counts(base, copies: int) -> tuple[np.ndarray, np.ndarray]:
    """Tokens of every document and of every word of
    `fit.tile_corpus(base, copies)`, from the one site: no pass over the
    tiled corpus."""
    return (np.tile(np.bincount(base.doc_ids, minlength=base.n_docs), copies),
            np.bincount(base.word_ids, minlength=base.n_vocab) * copies)


def _snapshot(state, m_head: int) -> dict:
    """Small device copies, dispatched before the next sweep takes the
    state's buffers: every chip's first blocks of z, and the counts."""
    import jax.numpy as jnp
    return {"z_head": state.z[:, 0, 0, :m_head] + 0,
            "n_dk": jnp.copy(state.n_dk[:, 0]),
            "n_wk": jnp.copy(state.n_wk[0, 0]),
            "n_k": jnp.copy(state.n_k[0])}


def _per_chip(array) -> list[np.ndarray]:
    """Every chip's own piece of a device array, in the order of the
    first axis' shards (a replicated array: every chip's whole copy)."""
    shards = sorted(
        array.addressable_shards,
        key=lambda s: ((s.index[0].start or 0) if s.index else 0, s.device.id))
    for s in shards:
        s.data.copy_to_host_async()
    return [np.asarray(s.data) for s in shards]


def run(run: dict) -> dict:
    from onix.config import LDAConfig
    from onix.parallel.mesh import make_mesh
    from onix.parallel.sharded_gibbs import ShardedGibbsLDA

    config, traffic, spans = run["config"], run["traffic"], run["spans"]
    fit = run["manifest"].load("drivers", "fit")
    seed = harness.fold_seed(run["seed"])
    seconds, tracer, compiles = run["seconds"], run["tracer"], run["compiles"]
    chips, cluster = int(run["cell"]["chips"]), config.get("cluster", {})

    with spans.span("front"):
        base = fit.base_corpus(config, traffic, seed)
    with spans.span("tile"):
        corpus = fit.tile_corpus(base, int(traffic["copies"]))
    lda = LDAConfig(
        n_topics=config["n_topics"], alpha=config["alpha"], eta=config["eta"],
        n_sweeps=10 ** 6,          # the window closes the fit, not a count
        burn_in=config["burn_in"], block_size=config["block_size"], seed=seed,
        sync_splits=int(cluster.get("sync_splits", 1)),
        merge_form=cluster.get("merge_form", "sync"))
    model = ShardedGibbsLDA(lda, corpus.n_vocab,
                            mesh=make_mesh(dp=chips, mp=1))

    layout = {}
    prepare = model.prepare

    def prepare_and_keep(c):
        t0 = time.monotonic()
        layout["sc"] = prepare(c)
        spans.add("prepare", t0, time.monotonic())
        return layout["sc"]

    model.prepare = prepare_and_keep    # the layout the reference checks
    open_at = int(traffic["open_at_callback"])
    w = {"times": [], "t_open": None, "prev": None, "last": None,
         "last_prev": None, "compiles_open": 0, "m": None}

    def callback(sweep: int, state):
        now = time.monotonic()
        with spans.span("callback"):
            w["times"].append(now)
            n = len(w["times"])
            if w["m"] is None:
                w["m"] = min(int(traffic["check_blocks"]), state.z.shape[3])
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
                    raise fit.WindowClosed
            w["prev"] = _snapshot(state, w["m"])
            w["cb_end"] = time.monotonic()

    t_fit = time.monotonic()
    try:
        with spans.span("fit_loop"):
            model.fit(corpus, callback=callback)
        raise RuntimeError("fit ended before the window closed")
    except fit.WindowClosed:
        pass
    tracer.stop()
    t_close = w["times"][-1]
    sweeps = len(w["times"]) - open_at
    elapsed = t_close - w["t_open"]
    spans.add("fit_setup", t_fit, w["t_open"])
    import jax
    peak = harness.memory_peak_bytes()      # the fullest chip's
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in jax.devices()[:chips]]

    # What the check needs, to the host; then the program's state goes.
    with spans.span("check_fetch"):
        state, prev, sc, mh = w["last"], w["last_prev"], layout["sc"], w["m"]
        after = {"n_dk": np.asarray(state.n_dk)[:, 0]}
        replicas = {"n_wk": [a[0, 0] for a in _per_chip(state.n_wk)],
                    "n_k": [a[0] for a in _per_chip(state.n_k)]}
        after.update(n_wk=replicas["n_wk"][0], n_k=replicas["n_k"][0])
        acc = {"acc_ndk": np.asarray(state.acc_ndk)[:, 0],
               "acc_nwk": np.asarray(state.acc_nwk)[0, 0],
               "n_acc": int(state.n_acc)}
        z_last = [a[0, 0, 0] for a in _per_chip(state.z)]     # [nb, B] a chip
        before = {k: np.asarray(prev[k]) for k in ("n_dk", "n_wk", "n_k")}
        z_head_before = np.asarray(prev["z_head"])
        compiled = w["compiles_close"] - w["compiles_open"]
        del state, prev, model
        w.clear()

    from benchmark.reference import fit_dp_check as ref
    n_shards, _, nb, _ = sc.doc_blocks.shape
    k, n_dl = config["n_topics"], sc.n_docs_local
    heads, ref_dk, doc_tokens, per_shard = [], [], [], []
    ref_wk = np.zeros((corpus.n_vocab, k), np.int64)
    prefix_bad = stray = 0
    with spans.span("check_whole"):     # every token, a chip at a time
        for p in range(n_shards):
            docs, words, mask = (a[p, 0] for a in (
                sc.doc_blocks, sc.word_blocks, sc.mask_blocks))
            n_live, bad = ref.live_prefix(mask)
            dk, wk, lost = ref.shard_tables(docs, words, z_last[p], n_live,
                                            n_dl, corpus.n_vocab, k)
            per_shard.append(n_live)
            prefix_bad += bad
            stray += lost
            ref_dk.append(dk)
            doc_tokens.append(dk.sum(axis=1))
            ref_wk += wk
            heads.append({"docs": docs[:mh], "words": words[:mh],
                          "mask": mask[:mh], "z_before": z_head_before[p],
                          "z_after": z_last[p][:mh].copy()})
        # A token with no topic is in no histogram: it is a count fault
        # (`stray`) and shows in the layout's sums as well.
        layout_bad, doc_split = ref.layout_and_split(
            doc_tokens, ref_wk.sum(axis=1), sc.doc_map, sum(per_shard),
            prefix_bad, *tiled_counts(base, int(traffic["copies"])))
    whole = {"layout_mismatch": layout_bad, "doc_split": doc_split,
             "ref_dk": ref_dk, "ref_wk": ref_wk, "stray": stray,
             "n_vocab": corpus.n_vocab, "sweeps_done": sweeps + open_at}
    with spans.span("check_stats"):
        _compare(run["check"], config, whole, heads, before, after, acc,
                 replicas)
    controls = {}
    for name in filter(None, (run["control"] or "").split(",")):
        # The same run judged again with a control in the program's place.
        c_heads = [dict(h, z_after=h["z_after"].copy()) for h in heads]
        c_after = {x: v.copy() for x, v in after.items()}
        c_replicas = {x: [c.copy() for c in v] for x, v in replicas.items()}
        _apply_control(name, config, corpus.n_vocab, seed, c_heads, before,
                       c_after, c_replicas)
        check = harness.Check()
        _compare(check, config, whole, c_heads, before, c_after, acc,
                 c_replicas)
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
                   "items_per_call": corpus.n_tokens / chips,
                   "n_docs": corpus.n_docs, "n_vocab": corpus.n_vocab,
                   "n_blocks": int(nb), "shards": int(n_shards),
                   "tokens_per_shard": per_shard,
                   "peak_bytes_per_chip": peaks},
    }


def _apply_control(name: str, config, n_vocab: int, seed: int, heads, before,
                   after, replicas) -> None:
    """Puts the reference, sound or broken, in the program's place for
    every chip's first blocks, or breaks the judged tables."""
    from benchmark.reference import fit_dp_check as ref
    k = config["n_topics"]
    kw = dict(alpha=config["alpha"], eta=config["eta"], n_vocab=n_vocab,
              rng=np.random.default_rng(seed))
    if name in ("reference", "half_kept"):
        drawn = ref.resample_heads(heads, before,
                                   keep_every=2 * (name == "half_kept"), **kw)
        for head, z in zip(heads, drawn):
            head["z_after"] = z
    elif name == "token_shift":
        # Every token of the heads altered where it is produced.
        for head in heads:
            head["z_after"] = np.where(head["mask"] > 0,
                                       (head["z_after"] + 1) % k,
                                       head["z_after"])
    elif name == "counts_stale":
        # Chip 0's n_dk misses the changes of every second token of its
        # block 0.
        head = heads[0]
        m = np.flatnonzero(head["mask"][0] > 0)[::2]
        np.add.at(after["n_dk"][0], (head["docs"][0][m],
                                     head["z_after"][0][m]), -1)
        np.add.at(after["n_dk"][0], (head["docs"][0][m],
                                     head["z_before"][0][m]), 1)
    elif name == "merge_dropped":
        # A merge that dropped its peers' changes: every chip's copy of
        # n_wk and n_k lacks what the other chips changed in their first
        # blocks (the judged tables are chip 0's).
        owed = [ref.head_changes(h, n_vocab, k) for h in heads]
        for p in range(len(heads)):
            for q, (d_wk, d_k) in enumerate(owed):
                if q != p:
                    replicas["n_wk"][p] -= d_wk.astype(np.int32)
                    replicas["n_k"][p] -= d_k.astype(np.int32)
        after.update(n_wk=replicas["n_wk"][0], n_k=replicas["n_k"][0])
    else:
        raise ValueError(f"unknown control {name!r}")


def _compare(check, config, whole, heads, before, after, acc, replicas
             ) -> None:
    from benchmark.reference import fit_dp_check as ref
    lim = config["limits"]
    check.compare("layout_mismatch", whole["layout_mismatch"],
                  lim["layout_mismatch"])
    check.compare("doc_split", whole["doc_split"], lim["doc_split"])
    check.compare("count_mismatch", ref.count_mismatch(
        whole["ref_dk"], whole["ref_wk"], whole["stray"], after["n_dk"],
        after["n_wk"], after["n_k"]), lim["count_mismatch"])
    check.compare("replica_mismatch",
                  ref.replica_mismatch(replicas["n_wk"])
                  + ref.replica_mismatch(replicas["n_k"]),
                  lim["replica_mismatch"])
    st = ref.sampler_stats(heads, before, alpha=config["alpha"],
                           eta=config["eta"], n_vocab=whole["n_vocab"])
    check.compare("move_gap", st["move_gap"], lim["move_gap"])
    check.compare("loglik_gap", st["loglik_gap"], lim["loglik_gap"])
    check.note("move_gap_sigma", st["move_gap_sigma"])
    check.note("loglik_gap_sigma", st["loglik_gap_sigma"])
    check.note("moved_share", st["moved_share"])
    check.note("checked_tokens", st["n_tokens"])
    # Accumulators: sweep s (from 0) is folded in once s >= burn_in.
    check.compare("acc_mismatch", ref.acc_mismatch(
        acc["acc_ndk"], acc["acc_nwk"], acc["n_acc"], before, after,
        max(0, whole["sweeps_done"] - config["burn_in"])),
        lim["acc_mismatch"])
