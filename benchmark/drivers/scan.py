"""Driver `scan`: the window drives the calls that `scale._stream_score`
makes for every streamed chunk, in its own one-ahead order: dispatch the
fused words+score+select program on chunk c, stage chunk c+1
(`device_words.STAGE_FNS`: host casts and the start of the copies), then
block on chunk c's winners.

Set-up makes one day's chunk of `chunk_events` raw events through the
product's `SYNTH_ARRAYS` from the mix's `data_seed`; fits the model on
the first `train_events` of them through the product's front and
`ShardedGibbsLDA.fit` (the model is the scan's input, not its work); and
builds the extended model, the score table and the device tables as
`_stream_score` builds them. `--seed` then deals the day's events out in
another order (`order_blocks` runs of events, shuffled): every seed gets
the same events, the same model and the same shapes. That is on purpose.
With the day itself drawn from the seed the rate followed the day (5%
between seeds, the same to 1e-5 for one seed; in the trace one gather
fusion of the look-ups took 1.4 s on one day and 2.5 s on another: my
chip runs, PR 25), which no bound under 10% can carry. The chunk is
handed over again and again as the raw columns, dtypes untouched, as a
deployment reads landed telemetry: synthesis stays outside the window.
The window opens with the chunk staged, closes at the first chunk whose
winners reach the host at or after `--seconds`, and the rate divides by
the true time.

What is compared is in `reference/scan_check.py`: the winners the timed
chunks returned, against the reference's score of every event.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import harness


def per_event(cols: dict, n: int) -> list[str]:
    return [k for k, v in cols.items()
            if isinstance(v, np.ndarray) and v.shape[:1] == (n,)
            and k != "anomaly_idx"]


def deal(cols: dict, n: int, n_blocks: int, seed: int) -> dict:
    """The same events in another order: `n_blocks` runs of consecutive
    events, shuffled by the seed."""
    cuts = np.linspace(0, n, n_blocks + 1).astype(np.int64)
    order = np.random.default_rng(seed).permutation(n_blocks)
    out = {k: v for k, v in cols.items() if k != "anomaly_idx"}
    for k in per_event(cols, n):
        out[k] = np.concatenate([cols[k][cuts[b]:cuts[b + 1]]
                                 for b in order])
    return out


def build_model(config: dict, cols: dict, n_train: int, seed: int, spans):
    """The product's front and fit on the first `n_train` events."""
    from onix.config import LDAConfig
    from onix.parallel.mesh import make_mesh
    from onix.parallel.sharded_gibbs import ShardedGibbsLDA
    from onix.pipelines import words as words_mod
    from onix.pipelines.corpus_build import build_corpus

    n = len(cols[config["host_column"]])
    train = {k: (v[:n_train] if k in per_event(cols, n) else v)
             for k, v in cols.items() if k != "anomaly_idx"}
    with spans.span("model_front"):
        wt = getattr(words_mod, f"{config['datatype']}_words_from_arrays")(
            **train)
        bundle = build_corpus(wt)
    with spans.span("model_fit"):
        lda = LDAConfig(
            n_topics=config["n_topics"], alpha=config["alpha"],
            eta=config["eta"], n_sweeps=config["scan_model_sweeps"],
            burn_in=config["burn_in"], block_size=config["block_size"],
            seed=seed)
        fit = ShardedGibbsLDA(lda, bundle.corpus.n_vocab,
                              mesh=make_mesh(dp=1, mp=1)).fit(bundle.corpus)
    return bundle, wt.edges, fit["theta"], fit["phi_wk"]


def run(run: dict) -> dict:
    import jax.numpy as jnp

    from onix.models import scoring
    from onix.pipelines import device_words as dw
    from onix.pipelines.scale import extend_model_for_unseen
    from onix.pipelines.synth import SYNTH_ARRAYS

    config, traffic, spans = run["config"], run["traffic"], run["spans"]
    seed = harness.fold_seed(run["seed"])
    seconds, tracer, compiles = run["seconds"], run["tracer"], run["compiles"]
    dt, control = config["datatype"], run["control"]
    n_chunk, day = int(traffic["chunk_events"]), int(traffic["data_seed"])

    with spans.span("synth"):
        cols = SYNTH_ARRAYS[dt](n_chunk, n_hosts=config["n_hosts"],
                                n_anomalies=int(traffic["anomalies"]),
                                seed=day)
    bundle, edges, theta, phi = build_model(
        config, cols, int(traffic["train_events"]), day, spans)
    with spans.span("tables"):
        theta_x, phi_x = extend_model_for_unseen(theta, phi)
        d_x, v_x = theta_x.shape[-2], phi_x.shape[-2]
        table = scoring.score_table(jnp.asarray(theta_x),
                                    jnp.asarray(phi_x)).ravel()
        dev_tables = (
            dw.build_flow_tables(bundle, edges, list(cols["proto_classes"]))
            if dt == "flow" else dw.build_dns_tables(bundle, edges))
    kw = dict(v_x=v_x, unseen_w=v_x - 1, unseen_d=d_x - 1,
              tol=float(config["tol"]), max_results=int(config["max_results"]))

    prog = {"table": table, "dev_tables": dev_tables}   # freed as one
    del table, dev_tables

    def fused(staged, table=None):
        table = prog["table"] if table is None else table
        if dt == "flow":
            return dw.flow_stream_bottom_k(prog["dev_tables"], table, staged,
                                           **kw)
        return dw.dns_stream_bottom_k(prog["dev_tables"], table, staged,
                                      edges, **kw)

    def stage(chunk):
        return dw.STAGE_FNS[dt](chunk, edges)

    with spans.span("deal"):
        chunk = deal(cols, n_chunk, int(traffic["order_blocks"]), seed)
    model = {"theta": np.asarray(theta), "phi_wk": np.asarray(phi),
             "word_key_sorted": np.asarray(bundle.word_key_sorted),
             "word_key_ids": np.asarray(bundle.word_key_ids),
             "doc_u32_sorted": np.asarray(bundle.doc_u32_sorted),
             "doc_u32_ids": np.asarray(bundle.doc_u32_ids), "edges": edges}
    del cols, bundle

    with spans.span("warmup"):          # every shape the window will use
        top = fused(stage(chunk))
        np.asarray(top.indices), np.asarray(top.scores)
        staged = stage(chunk)

    answers, done = [], 0
    t_open = time.monotonic()
    compiles_open = compiles.n
    tracer.start()
    while True:
        t0 = time.monotonic()
        with spans.span("dispatch"):
            top = fused(staged)
        with spans.span("stage"):
            nxt = stage(chunk)
        with spans.span("fetch"):
            ti, ts = np.asarray(top.indices), np.asarray(top.scores)
        answers.append((ti, ts))
        done += 1
        now = time.monotonic()
        spans.add("chunk", t0, now)
        if done >= int(traffic["trace_chunks"]):
            tracer.stop()
        if now - t_open >= seconds:
            break
        staged = nxt
    tracer.stop()
    elapsed = now - t_open
    compiled = compiles.n - compiles_open
    peak = harness.memory_peak_bytes()
    del staged, nxt, top
    names = list(filter(None, (control or "").split(",")))
    if not names:
        prog.clear()        # the program's state goes before the reference

    scores = _compare(run["check"], config, model, chunk, n_chunk, answers,
                      seed)
    controls = {}
    for name in names:
        # The program with a lower-precision table, or with a guarantee
        # broken, answers the checked chunk again at the cell's own size.
        if name == "bf16_table":
            top = fused(stage(chunk), prog["table"].astype(
                jnp.bfloat16).astype(jnp.float32))
        elif name == "half_chunk":      # the second half is never scored
            top = fused(stage({
                k: (v[:n_chunk // 2] if k in per_event(chunk, n_chunk) else v)
                for k, v in chunk.items()}))
        else:
            raise ValueError(f"unknown control {name!r}")
        check = harness.Check()
        _judge(check, config, scores, np.asarray(top.indices),
               np.asarray(top.scores))
        controls[name] = {"correct": check.correct, "check": check.as_dict()}
    prog.clear()
    del scores
    events = done * n_chunk
    return {
        "end_to_end": {"scan_events_per_s": events / elapsed,
                       "setup_s": t_open - run["t_start"]},
        "attempted": done, "failed": 0,
        "memory_peak_bytes": peak,
        "compiles_in_window": compiled,
        "controls": controls,
        "window": {"elapsed_s": elapsed, "chunks": done, "events": events,
                   "items_per_call": n_chunk, "n_docs": int(d_x),
                   "n_vocab": int(v_x)},
    }


def _judge(check, config, scores, ti, ts) -> None:
    from benchmark.reference import scan_check
    lim = config["limits"]
    got = scan_check.judge(scores, ti, ts, int(config["max_results"]))
    check.compare("answer_mismatch", got["answer_mismatch"],
                  lim["answer_mismatch"])
    check.compare("winner_gap", got["winner_gap"], lim["winner_gap"])
    check.compare("score_gap", got["score_gap"], lim["score_gap"])
    check.note("winners_due", got["n_due"])
    check.note("kth_score", got.get("kth_score", float("nan")))


def _compare(check, config, model, chunk, n_chunk, answers, seed):
    """One answer, drawn from the seed, against the reference's scores of
    every event of the chunk; and every answer against the first, which
    it has to equal. Returns the reference's scores."""
    from benchmark.reference import scan_check
    replay_bad = sum(int((answers[0][0] != ti).sum()
                         + (answers[0][1] != ts).sum())
                     for ti, ts in answers[1:])
    pick = int(np.random.default_rng(seed).integers(len(answers)))
    scores = scan_check.all_scores(config, model, chunk, n_chunk)
    _judge(check, config, scores, *answers[pick])
    check.compare("replay_mismatch", replay_bad,
                  config["limits"]["replay_mismatch"])
    check.note("checked_chunk", pick)
    return scores
