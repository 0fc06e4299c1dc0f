"""Driver `stream`: the streaming scorer draining a backlog of ingest
minibatches through its own entry, `StreamingScorer.process_many`, with
`pipeline.stream_superstep` set to the mix's `superstep_batches`: a closed
loop of one superstep, the next group handed over as `stage_next` so the
scorer stages it under the running dispatch, the next superstep
dispatched when the last one's winners have reached the host.

Set-up synthesizes the backlog (`backlog_batches` x `batch_events` raw
flow events through the program's `SYNTH_ARRAYS`, from the mix's
`data_seed`: one fixed backlog, as the scan mixes fix their day), cuts
it into its batches in order, and deals the events of every batch out in
another order for every `--seed` (`order_blocks` runs of consecutive
events over the backlog, so `order_blocks / backlog_batches` a batch,
shuffled within the batch). A batch holds the same events for every
seed, on purpose: the E-step iterates every document to its stopping
rule, 20 passes a batch where the busiest documents hold half the
tokens, so the rate follows what a batch holds; with other events in
each batch for every seed the rate would follow the seed as the scans'
followed the day before their day was fixed. The first batch goes
through the scorer alone: the bin edges are fitted on it, on the host
path (`first_batch`). Then the whole backlog once (`first_sight`: every
host enters the document table, the resident programs compile, and the
last of its supersteps runs warm, with its group staged ahead), so that
the window opens in the steady state the configuration states: known
hosts only, the next group staged. The window hands the backlog's groups over in order again and again, and
closes at the first superstep that ends at or after `--seconds`;
`scan_events_per_s` is every raw event of the batches whose winners
reached the host over the window's wall time. A batch is a minimal frame
of its numeric columns (what the alert rows are cut from) beside the
column dict the scorer's prefetcher would hand over.

What is compared (`reference/stream_check.py`, numpy float64): the LAST
timed batch. The program hands back lambda and the gamma store as they
stood before the last batch of a superstep (`before_last_batch`); with
the step count, the corpus size and the batch's columns that is all the
reference needs to say what the batch had to leave, against what it
left: lambda, the store, every event's score, the winners, and the
document ids its look-up gave the batch's tokens (handed back the same
way). `replay_mismatch`: the last timed superstep run again from a
snapshot of the state before it gives the same winners, bit for bit (on
a thread beside the reference: the one is the device's work, the other
the host's).

Mix keys: `batch_events`, `superstep_batches`, `backlog_batches`,
`anomalies`, `data_seed`, `order_blocks`, `trace_supersteps`; stated
for the record and fixed by this loop: `staged_ahead` (1), `loop`
(closed), `new_hosts_share` (0).

Controls (`--control a,b`), each judged in the program's place at the
cell's size and due to read `correct: false`:
  lam_stale    the lambda step dropped: lambda left as it stood;
  cold_start   the warm start dropped: the batch run again (a superstep
               of one) from a store whose every row holds the cold
               start, judged against the rows that really stood (the
               fixed point is the same one; the untouched documents'
               rows and, at the cell's size, the passes are not);
  half_scored  the second half of the batch's events never scored: the
               batch run again, cut to its first half;
  bf16_estep   the reference's own E-step and lambda step rounded to
               bfloat16 at every step, in the reference's place.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from benchmark import harness

COLUMNS = ("sip_u32", "dip_u32", "sport", "dport", "proto_id", "hour",
           "ibyt", "ipkt")


def make_batches(cols: dict, n_batch: int, n_batches: int, deal=None):
    """The backlog cut into its batches in order, each a (frame, columns)
    pair; `deal(columns, b)` puts batch b's events in another order."""
    import pandas as pd
    out = []
    for b in range(n_batches):
        part = {k: cols[k][b * n_batch:(b + 1) * n_batch] for k in COLUMNS}
        part["proto_classes"] = list(cols["proto_classes"])
        if deal is not None:
            part = deal(part, b)
        out.append((pd.DataFrame({k: part[k] for k in COLUMNS}), part))
    return out


def make_scorer(config: dict, superstep: int):
    from onix.config import OnixConfig
    from onix.pipelines.streaming import StreamingScorer

    cfg = OnixConfig()
    cfg.lda.n_topics = int(config["n_topics"])
    cfg.lda.alpha, cfg.lda.eta = float(config["alpha"]), float(config["eta"])
    cfg.lda.svi_tau0 = float(config["svi_tau0"])
    cfg.lda.svi_kappa = float(config["svi_kappa"])
    cfg.lda.svi_local_iters = int(config["svi_local_iters"])
    cfg.lda.svi_meanchange_tol = float(config["svi_meanchange_tol"])
    cfg.lda.stream_estep = config["stream_estep"]
    cfg.lda.checkpoint_every = 0
    cfg.pipeline.stream_superstep = superstep
    cfg.pipeline.tol = float(config["tol"])
    cfg.pipeline.max_results = int(config["max_results"])
    return StreamingScorer(cfg.validate(), config["datatype"],
                           n_buckets=int(config["n_buckets"]))


def winners_of(results) -> list:
    return [(r.alerts["event_idx"].to_numpy(), r.alerts["score"].to_numpy())
            for r in results]


def left_by(scorer, result, n_events: int) -> dict:
    """What a superstep left for its last batch, on the host."""
    ids = np.asarray(scorer.before_last_batch[2])
    half = len(ids) // 2
    return {"lam": np.asarray(scorer.state.lam),
            "passes": int(scorer.last_estep_stats[-1, :2].sum()),
            "gamma": np.asarray(scorer._res.store),
            "events": result.scores,
            "indices": result.alerts["event_idx"].to_numpy(),
            "scores": result.alerts["score"].to_numpy(),
            "doc_ids": np.concatenate([ids[:n_events],
                                       ids[half:half + n_events]])}


def judged(config, model, cols, before, left, seed, precision=None,
           words=None):
    from benchmark.reference import stream_check
    lim, check = config["limits"], harness.Check()
    got = stream_check.compare(config, model, cols, before, left, seed,
                               precision=precision, words=words)
    for name in ("doc_mismatch", "store_mismatch", "gamma_gap", "pass_gap",
                 "lam_gap",
                 "score_gap", "winner_gap", "answer_mismatch"):
        check.compare(name, got[name], lim[name])
    for name in ("n_due", "kth_score", "passes_due", "fixed_point_passes"):
        if name in got:
            check.note(name, got[name])
    return check


def run(run: dict) -> dict:
    import jax
    import jax.numpy as jnp

    from onix.models.lda_svi import SVIState
    # A program from before the resident superstep fails here, at once,
    # and not after the set-up.
    from onix.pipelines.streaming import stream_svi_step  # noqa: F401
    from onix.pipelines.synth import SYNTH_ARRAYS
    from onix.utils.obs import counters

    config, traffic, spans = run["config"], run["traffic"], run["spans"]
    scan = run["manifest"].load("drivers", "scan")
    seed = harness.fold_seed(run["seed"])
    seconds, tracer, compiles = run["seconds"], run["tracer"], run["compiles"]
    n_batch, s = int(traffic["batch_events"]), int(traffic["superstep_batches"])
    n_batches = int(traffic["backlog_batches"])
    assert n_batches % s == 0 and n_batches // s >= 2, (n_batches, s)
    n_all = n_batch * n_batches

    with spans.span("synth"):
        cols = SYNTH_ARRAYS[config["datatype"]](
            n_all, n_hosts=int(config["n_hosts"]),
            n_anomalies=int(traffic["anomalies"]),
            seed=int(traffic["data_seed"]))
    with spans.span("deal"):
        per_batch = int(traffic["order_blocks"]) // n_batches
        batches = make_batches(
            cols, n_batch, n_batches,
            lambda part, b: scan.deal(part, n_batch, per_batch, seed + b))
    del cols
    groups = [batches[i:i + s] for i in range(0, n_batches, s)]
    scorer = make_scorer(config, s)

    def superstep(i: int):
        """Group i of the backlog (in order, again and again), the group
        after it named as the one to stage."""
        return scorer.process_many(groups[i % len(groups)],
                                   stage_next=groups[(i + 1) % len(groups)])

    with spans.span("first_batch"):     # the edges fit: the host path
        scorer.process_many(batches[:1])
    with spans.span("first_sight"):     # every host, every program
        for i in range(len(groups)):
            scorer.snapshot_resident()
            superstep(i)
    done = len(groups)

    counted = ("stream.estep_iters", "stream.active_tokens",
               "stream.new_docs")
    at_open = {c: counters.get(c) for c in counted}
    token_passes, n_steps = [], 0
    t_open = time.monotonic()
    compiles_open = compiles.n
    tracer.start()
    while True:
        t0 = time.monotonic()
        snap = scorer.snapshot_resident()
        results = superstep(done)
        now = time.monotonic()
        spans.add("superstep", t0, now)
        stats = scorer.last_estep_stats
        token_passes.append(int(
            (stats[:, 0].astype(np.int64) * 2 * n_batch
             + stats[:, 1].astype(np.int64) * stats[:, 2]).sum()))
        done, n_steps = done + 1, n_steps + 1
        if n_steps >= int(traffic["trace_supersteps"]):
            tracer.stop()
        if now - t_open >= seconds:
            break
    tracer.stop()
    elapsed = now - t_open
    compiled = compiles.n - compiles_open
    peak = harness.memory_peak_bytes()
    window_counters = {c: counters.get(c) - at_open[c] for c in counted}

    # The last timed batch, against the reference; and the last timed
    # superstep again, from the state before it.
    check = run["check"]
    last_group = groups[(done - 1) % len(groups)]
    last_cols = last_group[-1][1]
    lam0, store0, _ = scorer.before_last_batch
    step_after = int(scorer.state.step)
    model = {"edges": scorer.edges, "salt": scorer._salt,
             "n_buckets": scorer.n_buckets,
             "doc_keys": np.asarray(scorer.docs.keys)}
    before = {"lam": np.asarray(lam0), "gamma": np.asarray(store0),
              "step": step_after - 1, "corpus_docs": scorer.docs.n_docs}
    left = left_by(scorer, results[-1], n_batch)
    first = winners_of(results)
    end = scorer.snapshot_resident()
    del results

    # The replay is the device's work and the reference the host's
    # (past the words' bins, which it takes on the device first): side
    # by side, a thread for the replay.

    from benchmark.reference import stream_check
    words = stream_check.buckets(model, last_cols)
    again = []

    def replay():
        with spans.span("replay"):
            scorer.restore_resident(snap)
            again.extend(winners_of(superstep(done - 1)))

    beside = threading.Thread(target=replay)
    beside.start()
    with spans.span("reference"):
        rows = judged(config, model, last_cols, before, left, seed,
                      words=words).as_dict()
    beside.join()
    replay_bad = abs(len(first) - len(again)) + sum(
        int(len(a[0]) != len(b[0])) or int((a[0] != b[0]).sum()
                                           + (a[1] != b[1]).sum())
        for a, b in zip(first, again))
    for name, row in rows.items():
        if "limit" in row:
            check.compare(name, row["value"], row["limit"])
        else:
            check.note(name, row["value"])
    check.compare("replay_mismatch", replay_bad,
                  config["limits"]["replay_mismatch"])
    check.note("new_docs_in_window", window_counters["stream.new_docs"])

    controls = {}
    for name in filter(None, (run["control"] or "").split(",")):
        c_before, c_left, c_cols, precision = before, left, last_cols, None
        if name == "lam_stale":
            c_left = dict(left, lam=before["lam"])
        elif name == "bf16_estep":
            from benchmark.reference import stream_check
            precision = stream_check.bf16
        elif name in ("cold_start", "half_scored"):
            # The batch again, alone, from the state that stood before it
            # but for what the control breaks.
            cold = jnp.full_like(store0, float(config["alpha"]) + 1.0)
            scorer.restore_resident({
                "state": SVIState(lam0, jnp.int32(step_after - 1)),
                "store": cold if name == "cold_start" else store0,
                "last_seen": end["last_seen"],
                "batch_no": end["batch_no"] - 1})
            table, c_cols = last_group[-1]
            if name == "half_scored":
                half = len(table) // 2
                part = {k: (v[:half] if k in COLUMNS else v)
                        for k, v in c_cols.items()}
                table = table.iloc[:half]
            else:
                part = c_cols
            res = scorer.process_many([(table, part)], superstep=2)
            c_left = left_by(scorer, res[0], len(table))
            if name == "half_scored":   # judged over the whole batch
                c_left["events"] = np.concatenate(
                    [c_left["events"], np.full(n_batch - half, np.inf)])
                c_left["doc_ids"] = left["doc_ids"]
        else:
            raise ValueError(f"unknown control {name!r}")
        got = judged(config, model, c_cols, c_before, c_left, seed,
                     precision=precision)
        controls[name] = {"correct": got.correct, "check": got.as_dict()}
    events = n_steps * s * n_batch
    return {
        "end_to_end": {"scan_events_per_s": events / elapsed,
                       "setup_s": t_open - run["t_start"]},
        "attempted": n_steps, "failed": 0,
        "memory_peak_bytes": peak,
        "compiles_in_window": compiled,
        "controls": controls,
        "window": {"elapsed_s": elapsed, "supersteps": n_steps,
                   "batches": n_steps * s, "events": events,
                   "items_per_call": s * n_batch, "batches_per_call": s,
                   "token_passes_by_call": token_passes,
                   "counters": window_counters,
                   "n_docs": scorer.docs.n_docs,
                   "store_rows": int(store0.shape[0])},
    }
