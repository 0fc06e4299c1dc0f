"""Driver `dayscan`: the day scan of any datatype the program has, with
no datatype spelled out. The window drives the calls that
`scale._stream_score` makes for every streamed chunk, in its own
one-ahead order, through the program's datatype-keyed entry
(`device_words.TABLE_FNS`, `STAGE_FNS`, `SCAN_FNS`): dispatch the fused
words+score+select program on chunk c, stage chunk c+1 (host casts, the
per-unique string features, the start of the copies), then block on
chunk c's winners.

It is the loop of `drivers/scan.py` (same set-up, same spans, same window
rule, same controls, same numbers compared; `deal`, `per_event`,
`build_model` and `_judge` are that file's own, loaded through the
manifest), so that file's docstring says why the day is fixed by the
mix's `data_seed` and only dealt out by `--seed`. What differs: the
window opens once the staged chunk has reached the device; and which
tables to build, how to stage and which program to run are the
program's to say, keyed by `config["datatype"]`; and so is the one
choice left here, the plain reference that decides `correct`
(`REFERENCE`: `reference/proxy_scan_check.py` for proxy,
`reference/scan_check.py` for the datatypes that file knows).
"""

from __future__ import annotations

import importlib
import time

import numpy as np

from benchmark import harness

REFERENCE = {"proxy": "proxy_scan_check"}


def reference_of(config: dict):
    return importlib.import_module(
        "benchmark.reference."
        + REFERENCE.get(config["datatype"], "scan_check"))


def run(run: dict) -> dict:
    import jax
    import jax.numpy as jnp

    from onix.models import scoring
    from onix.pipelines import device_words as dw
    from onix.pipelines.scale import extend_model_for_unseen
    from onix.pipelines.synth import SYNTH_ARRAYS

    config, traffic, spans = run["config"], run["traffic"], run["spans"]
    scan = run["manifest"].load("drivers", "scan")
    seed = harness.fold_seed(run["seed"])
    seconds, tracer, compiles = run["seconds"], run["tracer"], run["compiles"]
    dt, control = config["datatype"], run["control"]
    n_chunk, day = int(traffic["chunk_events"]), int(traffic["data_seed"])
    # The program's three steps for this datatype. A program from before
    # the keyed entry fails here, at once, and not after the set-up.
    build_tables, stage_cols, scan_chunk = (
        dw.TABLE_FNS[dt], dw.STAGE_FNS[dt], dw.SCAN_FNS[dt])

    with spans.span("synth"):
        cols = SYNTH_ARRAYS[dt](n_chunk, n_hosts=config["n_hosts"],
                                n_anomalies=int(traffic["anomalies"]),
                                seed=day)
    bundle, edges, theta, phi = scan.build_model(
        config, cols, int(traffic["train_events"]), day, spans)
    with spans.span("tables"):
        theta_x, phi_x = extend_model_for_unseen(theta, phi)
        d_x, v_x = theta_x.shape[-2], phi_x.shape[-2]
        table = scoring.score_table(jnp.asarray(theta_x),
                                    jnp.asarray(phi_x)).ravel()
        dev_tables = build_tables(bundle, edges, cols)
    kw = dict(v_x=v_x, unseen_w=v_x - 1, unseen_d=d_x - 1,
              tol=float(config["tol"]), max_results=int(config["max_results"]))

    prog = {"table": table, "dev_tables": dev_tables}   # freed as one
    del table, dev_tables

    def fused(staged, table=None):
        return scan_chunk(
            prog["dev_tables"], prog["table"] if table is None else table,
            staged, edges, **kw)

    def stage(chunk):
        return stage_cols(chunk, edges)

    with spans.span("deal"):
        chunk = scan.deal(cols, n_chunk, int(traffic["order_blocks"]), seed)
    model = {"theta": np.asarray(theta), "phi_wk": np.asarray(phi),
             "word_key_sorted": np.asarray(bundle.word_key_sorted),
             "word_key_ids": np.asarray(bundle.word_key_ids),
             "doc_u32_sorted": np.asarray(bundle.doc_u32_sorted),
             "doc_u32_ids": np.asarray(bundle.doc_u32_ids), "edges": edges}
    del cols, bundle

    with spans.span("warmup"):          # every shape the window will use
        top = fused(stage(chunk))
        np.asarray(top.indices), np.asarray(top.scores)
        # The window opens with the chunk staged: on the device, not on
        # its way. Every later chunk's copy lies beside the scan before
        # it; this one would lie in the window with the device idle
        # (0.07-0.26 s of 31.6, by the run: my chip runs, PR 29).
        staged = jax.block_until_ready(stage(chunk))

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

    # One answer, drawn from the seed, against the reference's scores of
    # every event of the chunk; and every answer against the first, which
    # it has to equal.
    check = run["check"]
    replay_bad = sum(int((answers[0][0] != ti).sum()
                         + (answers[0][1] != ts).sum())
                     for ti, ts in answers[1:])
    pick = int(np.random.default_rng(seed).integers(len(answers)))
    scores = reference_of(config).all_scores(config, model, chunk, n_chunk)
    scan._judge(check, config, scores, *answers[pick])
    check.compare("replay_mismatch", replay_bad,
                  config["limits"]["replay_mismatch"])
    check.note("checked_chunk", pick)

    controls = {}
    for name in names:
        # The program with a lower-precision table, or with a guarantee
        # broken, answers the checked chunk again at the cell's own size.
        if name == "bf16_table":
            top = fused(stage(chunk), prog["table"].astype(
                jnp.bfloat16).astype(jnp.float32))
        elif name == "half_chunk":      # the second half is never scored
            keep = scan.per_event(chunk, n_chunk)
            top = fused(stage({k: (v[:n_chunk // 2] if k in keep else v)
                               for k, v in chunk.items()}))
        else:
            raise ValueError(f"unknown control {name!r}")
        judged = harness.Check()
        scan._judge(judged, config, scores, np.asarray(top.indices),
                    np.asarray(top.scores))
        controls[name] = {"correct": judged.correct,
                          "check": judged.as_dict()}
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
