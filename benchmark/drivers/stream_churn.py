"""Driver `stream_churn`: the stream of driver `stream`, with addresses
it has never seen in every file and the document table held to
`stream_max_docs` by the program's LRU eviction. The same scorer, entry
(`StreamingScorer.process_many`, `pipeline.stream_superstep` set to the
mix's `superstep_batches`), closed loop, next group staged ahead,
backlog, deal and window as driver `stream` (its docstring), with these
differences.

Every file handed over after the first is one of the backlog's batches
relabelled: in the file numbered f (1, 2, ... in the order they are
handed over; batch (f - 1) mod `backlog_batches`), some of the
backlog's client addresses (its distinct sources), chosen from the
mix's `data_seed` and f, carry an address never used before in the run,
in every event of that client and in either column. So a file holds the
same events for every `--seed`, which deals them in another order, as
driver `stream`'s do (its docstring says why). Fresh addresses lie in
64.0.0.0/4, a block of one address per client for each file, handed to
the chosen clients in ascending order; the generator's own addresses
(10/8, 192.168/16, 203/8) lie outside it. The first file is one more
draw of the generator (`data_seed` + 1): the backlog is made and dealt,
and the files of set-up, of one cycle of the window and of the group
staged after it cut, on a thread while the first batch and its compile
hold the main thread; a file past those is made when its turn comes.

Set-up: the first batch (the edges fit, on the host path); then two
supersteps whose files but the last carry `setup_fresh_clients_per_batch`
fresh clients each; every other file, the last of those two groups
included (the most recent, so the backlog's clients it holds survive an
eviction), carries `fresh_clients_per_batch`. The first takes the table
to between half the bound and the bound, so that the store grows to the
capacity every superstep after an eviction runs at (2^20 rows in the
cell); the second takes it past the bound: the store regrows to the
next capacity and the first eviction fires. So set-up compiles the two
capacities the window runs and no other. After an eviction the table
holds 0.75 x `stream_max_docs`; a superstep adds `superstep_batches` x
`fresh_clients_per_batch` documents and the backlog's clients that
were evicted, so with the cell's numbers every second superstep
regrows the store and evicts. The window opens on the superstep after
an eviction, every program compiled, and holds whole cycles of the two:
it closes at the first superstep that evicts at or after `--seconds`
(the two take different times, and a window cut between them would
read a rate that swings with where the cut falls).

What is compared, against what the driver knows of the files it handed
over and not what the program says of them: the table as the last timed
group ran is the table before it with each file's unseen addresses
appended in ascending order, file by file; each document's stamp is the
number of the last batch that held its address (the first batch is 1,
file f batch f + 1). The last timed batch is judged as driver `stream`
judges it (`reference/stream_check.py`), its documents' ids from that
table, with `cold_mismatch`: documents first seen in that batch whose
row before it is not the cold start. The rows it left are read from the
store the eviction that closed the window left, each document at its
new id; a document the eviction dropped (none that the batch touched,
where the eviction is right) is read as it stood before the batch.
`replay_mismatch`: the last timed superstep run again from a snapshot
of the state and the table before it, with no bound on the table, so
that it stops where the eviction would start; `evict_mismatch`
(`reference/stream_churn_check.py`): from that table, the derived
stamps and the replay's rows, the survivors, their new ids, rows and
stamps and the cold rows past them, against the table, stamps and
store the timed superstep left.

Mix keys: driver `stream`'s but `new_hosts_share`, and
`fresh_clients_per_batch`, `setup_fresh_clients_per_batch`.

Controls (`--control a,b`), judged in the program's place, each due to
read `correct: false`:
  evict_newest      the most recent quarter dropped instead of the least
                    recent;
  rows_unmoved      the ids compacted and the rows not gathered: the
                    survivors' keys over the rows and stamps that stood
                    at their new ids before;
  stamps_unwritten  no batch stamps a document: every stamp 0, so the
                    lowest ids survive and carry stamp 0;
  bf16_estep        the reference's own E-step and lambda step rounded
                    to bfloat16 at every step, in the reference's place
                    (driver `stream`);
  cold_start        the warm start dropped: the last batch run again
                    alone from a store whose every row holds the cold
                    start (driver `stream`).
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark import harness

FRESH_BASE = 64 << 24
COUNTED = ("stream.estep_iters", "stream.active_tokens", "stream.new_docs",
           "stream.evicted_docs", "stream.evictions", "stream.store_regrows",
           "stream.host_probes")
EVICTION_CONTROLS = ("evict_newest", "rows_unmoved", "stamps_unwritten")
BATCH_CONTROLS = ("bf16_estep", "cold_start")


class Files:
    """The backlog's dealt batches, and file f with its fresh clients;
    `n_fresh(f)` says how many file f has."""

    def __init__(self, parts: list, columns: tuple, seed: int, n_fresh):
        self.parts, self.columns, self.seed = parts, columns, seed
        self.n_fresh = n_fresh
        # The sources lie in one block of addresses (the generator's
        # hosts): a table over the block says which are clients, and
        # each client's place among them.
        lo = int(min(p["sip_u32"].min() for p in parts))
        span = int(max(p["sip_u32"].max() for p in parts)) - lo + 1
        assert span <= 1 << 28, span
        present = np.zeros(span, bool)
        for p in parts:
            present[p["sip_u32"] - np.uint32(lo)] = True
        place = (np.cumsum(present) - 1).astype(np.int32)
        self.n_clients = int(present.sum())
        self.client_addr = (lo + np.flatnonzero(present)).astype(np.uint32)
        # Each event's client in either column, or n_clients for an
        # address that is no client's; each batch's clients, and its
        # addresses that are no client's.
        self.client_of, self.clients_in, self.others_in = [], [], []
        for p in parts:
            idx, held, others = {}, np.zeros(self.n_clients + 1, bool), []
            for name in ("sip_u32", "dip_u32"):
                off = p[name].astype(np.int64) - lo
                inside = (off >= 0) & (off < span)
                off = np.where(inside, off, 0)
                idx[name] = np.where(inside & present[off], place[off],
                                     self.n_clients).astype(np.int32)
                held[idx[name]] = True
                others.append(p[name][idx[name] == self.n_clients])
            self.client_of.append(idx)
            self.clients_in.append(held[:-1])
            self.others_in.append(np.unique(np.concatenate(others))
                                  .astype(np.uint32))

    def chosen(self, f: int) -> np.ndarray:
        """The clients of file f under a fresh address, ascending: the
        k-th of them carries `FRESH_BASE + f n_clients + k`."""
        return np.sort(np.random.default_rng([self.seed, f]).choice(
            self.n_clients, min(self.n_fresh(f), self.n_clients),
            replace=False))

    def fresh_block(self, f: int, n: int) -> np.ndarray:
        return (FRESH_BASE + f * self.n_clients
                + np.arange(n, dtype=np.int64)).astype(np.uint32)

    def columns_of(self, f: int) -> dict:
        b = (f - 1) % len(self.parts)
        part, client_of = self.parts[b], self.client_of[b]
        chosen = self.chosen(f)
        fresh_of = np.zeros(self.n_clients + 1, np.uint32)
        fresh_of[chosen] = self.fresh_block(f, len(chosen))
        out = dict(part)
        for name in ("sip_u32", "dip_u32"):
            new = fresh_of[client_of[name]]
            out[name] = np.where(new != 0, new, part[name])
        return out

    def file(self, f: int):
        import pandas as pd
        cols = self.columns_of(f)
        return pd.DataFrame({k: cols[k] for k in self.columns}), cols

    def addresses(self, f: int) -> np.ndarray:
        """Every address file f holds, ascending, from the batch's
        clients and other addresses (no pass over its events)."""
        b = (f - 1) % len(self.parts)
        held = self.clients_in[b].copy()
        chosen = self.chosen(f)
        fresh = self.fresh_block(f, len(chosen))[held[chosen]]
        held[chosen] = False
        return np.union1d(np.concatenate([self.client_addr[held],
                                          self.others_in[b]]), fresh)


def grown(keys, files: Files, group: list[int]) -> np.ndarray:
    """The table after files `group` in order: each file's addresses the
    table lacks appended in ascending order."""
    keys = np.asarray(keys, np.uint32)
    for f in group:
        held = files.addresses(f)
        keys = np.concatenate([keys, held[~np.isin(held, keys)]])
    return keys


def stamps(keys, files: Files, last: int, first: np.ndarray) -> np.ndarray:
    """The number of the last batch that held each address, up to file
    `last`: file f is batch f + 1, the first batch (addresses `first`)
    1; 0 for an address no batch held."""
    keys = np.asarray(keys, np.uint32)
    order = np.argsort(keys, kind="stable")
    ranked, out = keys[order], np.zeros(len(keys), np.int64)
    for f, held in [(f, None) for f in range(last, 0, -1)] + [(0, first)]:
        held = files.addresses(f) if held is None else held
        at = np.minimum(np.searchsorted(ranked, held), len(ranked) - 1)
        at = at[ranked[at] == held]
        out[at[out[at] == 0]] = f + 1
        if out.all():
            break
    stamped = np.empty_like(out)
    stamped[order] = out
    return stamped


def as_left(keys, after: dict, fallback: np.ndarray) -> np.ndarray:
    """Each document of `keys` its row in the state `after` (table and
    store), found by address; `fallback`'s row where `after` lacks it."""
    rows = np.array(fallback, np.float32)
    got = np.asarray(after["doc_keys"], np.uint32)
    order = np.argsort(got, kind="stable")
    at = np.minimum(np.searchsorted(got[order], keys), len(got) - 1)
    found = got[order][at] == keys
    rows[found] = np.asarray(after["store"])[order[at[found]]]
    return rows


def judged_eviction(config: dict, before: dict, after: dict,
                    control: str | None = None):
    from benchmark.reference import stream_churn_check as ref
    max_docs, alpha = int(config["stream_max_docs"]), float(config["alpha"])
    if control is not None:
        after = ref.broken(before, after, max_docs, alpha, control)
    got = ref.compare(before, after, max_docs, alpha)
    check = harness.Check()
    check.compare("evict_mismatch", got["evict_mismatch"],
                  config["limits"]["evict_mismatch"])
    for name in ("evict_before", "evict_after"):
        check.note(name, got[name])
    return check


def run(run: dict) -> dict:
    import jax.numpy as jnp

    from onix.models.lda_svi import SVIState
    # A program from before the resident superstep fails here, at once,
    # and not after the set-up.
    from onix.pipelines.streaming import stream_svi_step  # noqa: F401
    from onix.pipelines.synth import SYNTH_ARRAYS
    from onix.utils.obs import counters

    config, traffic, spans = run["config"], run["traffic"], run["spans"]
    stream = run["manifest"].load("drivers", "stream")
    scan = run["manifest"].load("drivers", "scan")
    seed = harness.fold_seed(run["seed"])
    seconds, tracer, compiles = run["seconds"], run["tracer"], run["compiles"]
    n_batch, s = int(traffic["batch_events"]), int(traffic["superstep_batches"])
    n_batches = int(traffic["backlog_batches"])
    churn = int(traffic["fresh_clients_per_batch"])
    fill = int(traffic["setup_fresh_clients_per_batch"])
    data_seed = int(traffic["data_seed"])
    alpha = float(config["alpha"])
    synth = SYNTH_ARRAYS[config["datatype"]]
    controls_asked = list(filter(None, (run["control"] or "").split(",")))
    for name in controls_asked:
        if name not in EVICTION_CONTROLS + BATCH_CONTROLS:
            raise ValueError(f"unknown control {name!r}")

    def n_fresh(f: int) -> int:
        """Set-up's two groups carry `fill` fresh clients a file, but
        their last files."""
        g, i = divmod(f - 1, s)
        return fill if g < 2 and i < s - 1 else churn

    def backlog() -> Files:
        t0 = time.monotonic()
        cols = synth(n_batch * n_batches, n_hosts=int(config["n_hosts"]),
                     n_anomalies=int(traffic["anomalies"]), seed=data_seed)
        t1 = time.monotonic()
        per_batch = int(traffic["order_blocks"]) // n_batches
        parts = []
        for b in range(n_batches):
            part = {k: cols[k][b * n_batch:(b + 1) * n_batch]
                    for k in stream.COLUMNS}
            part["proto_classes"] = list(cols["proto_classes"])
            parts.append(scan.deal(part, n_batch, per_batch, seed + b))
        spans.add("synth", t0, t1)
        spans.add("deal", t1, time.monotonic())
        return Files(parts, stream.COLUMNS, data_seed, n_fresh)

    def files_of(g: int) -> list[int]:
        """Group g: files 1 + g s .. (g + 1) s (backlog batches
        g s .. (g + 1) s - 1, mod its length); groups 0 and 1 set-up's."""
        return [1 + g * s + i for i in range(s)]

    def make_group(g: int):
        files = made_files.result()
        return [files.file(f) for f in files_of(g)]

    scorer = stream.make_scorer(config, s)
    scorer.max_docs = int(config["stream_max_docs"])
    # The replay and the judge need the table back with the state; a
    # program whose snapshot lacks it fails here, at once.
    if "doc_keys" not in scorer.snapshot_resident():
        raise RuntimeError("snapshot_resident carries no document table: "
                           "the superstep cannot be replayed")

    # One thread makes the backlog and the files while the first batch
    # (and its compile) holds the main thread: the groups of set-up, of
    # one cycle of the window and the one staged after it, so that
    # nothing but the program works the host in a window of one cycle.
    # A group past those is made when its turn comes.
    maker = ThreadPoolExecutor(1)
    made_files = maker.submit(backlog)
    made = {g: maker.submit(make_group, g) for g in range(5)}
    keep: dict[int, list] = {}

    def group(g: int) -> list:
        if g not in keep:
            keep[g] = made.pop(g).result() if g in made else make_group(g)
        for old in [k for k in keep if k < g - 1]:
            del keep[old]
        return keep[g]

    def superstep(g: int):
        """Group g, the group after it named as the one to stage."""
        ahead = group(g + 1)
        return scorer.process_many(group(g), stage_next=ahead)

    with spans.span("first_batch"):     # the edges fit: the host path
        first = synth(n_batch, n_hosts=int(config["n_hosts"]),
                      n_anomalies=int(traffic["anomalies"]) // n_batches,
                      seed=data_seed + 1)
        batch = stream.make_batches(first, n_batch, 1)[0]
        scorer.process_many([batch])
        first = np.unique(np.concatenate([batch[1]["sip_u32"],
                                          batch[1]["dip_u32"]]))
        del batch
    with spans.span("fill"):    # the store to 2^k rows, then past the
        for g in (0, 1):        # bound: regrow to 2^(k+1), evict
            scorer.snapshot_resident()      # its copies compiled
            superstep(g)
    done = 2

    at_open = counters.snapshot()
    token_passes, n_steps = [], 0
    t_open = time.monotonic()
    compiles_open = compiles.n
    tracer.start()
    while True:
        t0 = time.monotonic()
        with spans.span("snapshot"):
            snap = scorer.snapshot_resident()
        fired = counters.get("stream.evictions")
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
        # Whole cycles of a superstep that stays under the bound and one
        # that evicts: the window closes on an eviction.
        evicted = counters.get("stream.evictions") > fired
        if now - t_open >= seconds and (evicted or now - t_open >= 4 * seconds):
            break
    tracer.stop()
    elapsed = now - t_open
    compiled = compiles.n - compiles_open
    peak = harness.memory_peak_bytes()
    at_close = counters.snapshot()
    window_counters = {c: at_close[c] - at_open.get(c, 0)
                       for c in COUNTED if c in at_close}

    # What the last superstep left (after its eviction, the compacted
    # table pushed), and what it ran on, from the files alone.
    check = run["check"]
    end = scorer.snapshot_resident()
    files = made_files.result()
    last_files = files_of(done - 1)
    doc_keys = grown(snap["doc_keys"], files, last_files)
    n_pre = len(doc_keys)
    last_group = group(done - 1)
    last_cols = last_group[-1][1]
    lam0, store0, ids = scorer.before_last_batch
    store0 = np.asarray(store0)
    step_after = int(scorer.state.step)
    model = {"edges": scorer.edges, "salt": scorer._salt,
             "n_buckets": scorer.n_buckets, "doc_keys": doc_keys}
    before = {"lam": np.asarray(lam0), "gamma": store0[:n_pre],
              "step": step_after - 1, "corpus_docs": n_pre}
    ids = np.asarray(ids)
    half = len(ids) // 2
    left = {"lam": np.asarray(scorer.state.lam),
            "passes": int(scorer.last_estep_stats[-1, :2].sum()),
            "gamma": as_left(doc_keys, end, store0[:n_pre]),
            "events": results[-1].scores,
            "indices": results[-1].alerts["event_idx"].to_numpy(),
            "scores": results[-1].alerts["score"].to_numpy(),
            "doc_ids": np.concatenate([ids[:n_batch],
                                       ids[half:half + n_batch]])}
    first_new = n_pre - results[-1].n_new_docs
    winners = stream.winners_of(results)
    del results

    from benchmark.reference import stream_check
    again, pre = [], {}

    def replay():
        with spans.span("replay"):
            scorer.restore_resident(snap)
            scorer.max_docs = None      # stop where the eviction starts
            again.extend(stream.winners_of(superstep(done - 1)))
            pre.update(scorer.snapshot_resident())
        pre["seen"] = stamps(doc_keys, files, last_files[-1], first)

    beside = threading.Thread(target=replay)
    beside.start()
    with spans.span("reference"):
        words = stream_check.buckets(model, last_cols)
        rows = stream.judged(config, model, last_cols, before, left, seed,
                             words=words).as_dict()
        in_batch = np.unique(left["doc_ids"])
        fresh = in_batch[in_batch >= first_new]
        cold = before["gamma"][fresh]
        cold_bad = int((cold != np.float32(alpha + 1.0)).any(axis=1).sum())
    beside.join()
    maker.shutdown()
    replay_bad = abs(len(winners) - len(again)) + sum(
        int(len(a[0]) != len(b[0])) or int((a[0] != b[0]).sum()
                                           + (a[1] != b[1]).sum())
        for a, b in zip(winners, again))
    # The eviction: the table, the stamps from the files, and the rows
    # the replay left where the eviction would start; against what the
    # timed superstep left.
    ev_before = {"keys": doc_keys, "last_seen": pre["seen"],
                 "gamma": np.asarray(pre["store"])[:n_pre]}
    ev_after = {"keys": end["doc_keys"], "gamma": np.asarray(end["store"]),
                "last_seen": np.asarray(end["last_seen"])}
    ev_rows = judged_eviction(config, ev_before, ev_after).as_dict()
    if window_counters.get("stream.evictions", 0) < 1 or not evicted:
        # The window closed on no eviction: nothing of the kind judged.
        ev_rows["evict_mismatch"]["value"] = n_pre
    for name, row in list(rows.items()) + list(ev_rows.items()):
        if "limit" in row:
            check.compare(name, row["value"], row["limit"])
        else:
            check.note(name, row["value"])
    check.compare("cold_mismatch", cold_bad, config["limits"]["cold_mismatch"])
    check.compare("replay_mismatch", replay_bad,
                  config["limits"]["replay_mismatch"])
    check.note("new_docs_in_window", window_counters.get(
        "stream.new_docs", 0))
    check.note("evictions_in_window", window_counters.get(
        "stream.evictions", 0))
    check.note("fresh_in_last_batch", len(fresh))

    controls = {}
    for name in controls_asked:
        if name in EVICTION_CONTROLS:
            got = judged_eviction(config, ev_before, ev_after, control=name)
        else:
            c_left, precision = left, None
            if name == "bf16_estep":
                precision = stream_check.bf16
            else:       # cold_start: the last batch again, alone
                scorer.restore_resident({
                    "state": SVIState(lam0, jnp.int32(step_after - 1)),
                    "store": jnp.full(store0.shape, alpha + 1.0, jnp.float32),
                    "last_seen": pre["last_seen"],
                    "batch_no": pre["batch_no"] - 1, "doc_keys": doc_keys})
                res = scorer.process_many([last_group[-1]], superstep=2)
                c_left = stream.left_by(scorer, res[0], n_batch)
                c_left["gamma"] = c_left["gamma"][:n_pre]
            got = stream.judged(config, model, last_cols, before, c_left,
                                seed, precision=precision, words=words)
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
                   "n_docs": int(len(end["doc_keys"])),
                   "store_rows": int(store0.shape[0])},
    }
