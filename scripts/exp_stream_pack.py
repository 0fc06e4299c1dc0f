"""The stream's passes on the chip, with gamma one document a K-lane row
("rows", `[cap, K]`) and four documents a 128-lane row ("packed",
`[cap / 4, 128]`, `lda_svi._gamma_group`), at the shapes of the
benchmark's `flow-stream-catchup` cell. `--mode` picks the pass:

    python3 scripts/exp_stream_pack.py --out <dir>                  # E-step
    python3 scripts/exp_stream_pack.py --mode lambda --out <dir>
    python3 scripts/exp_stream_pack.py --mode score --out <dir>
    python3 scripts/exp_stream_pack.py --forms packed,local \
        --cap 262144,1048576,2097152 --out <dir>     # the working table

A batch is made as the cell's program sees it after the dedupe: 2^23 pair
slots, 2,340,000 weighted (document, word) pairs in front of them, word by
word and within a word by document (`lda_svi._unique_pairs`' order), over
a store of 262144 rows: 196,103 client documents with about twelve pairs
each and 24 server documents with the rest; 32768 words, K = 20.

* `estep`: `lda_svi._run_e_step` in ids mode, in each form, with a
  tolerance no document meets (every pair stays active) for `--short`
  and for `--long` passes: one warm pass, the compaction, the extended
  loop (the gathers of E[log theta] and E[log beta] rows, the softmax,
  the scatter-add into the new gamma, each document's change, the
  freeze). A pass costs the difference of the two programs' device times
  over the difference of the pair rows they worked, which takes the
  pack, the unpack, the compaction's sort and the first pass out.
  `--cap` reads it at other store capacities (a comma list: one reading
  each), the batch's documents spread over the store's rows, as a
  stream whose table has grown holds them; the form `local` runs the
  passes as `svi_store_step` does past `lda_svi._WORKING_ROWS` packed
  rows, on a working table of the batch's documents gathered from the
  store and written back (`lda_svi._in_rounds`, its lambda statistics
  with it), packed; `packed` works the whole store.
* `lambda`: `lda_svi._pair_sstats` over the pairs, from the store packed
  as the E-step hands gamma over: one program a form, run over the
  first `--short-pairs` pairs and over all of them (the pass stops after
  the runs that hold pairs). A pair row costs the difference of the two
  device times over the difference of the pairs, which takes E[log
  theta], the lanes' layout and the fold out.
* `score`: `lda_svi._slot_scores` (the store packed, theta and phi laid
  out, one row of each a token slot) over the first 2^22 and over all
  2^23 token slots of a batch of 3,472,222 events, two tokens each, half
  of them a server's, the padding slots at the store's last row. A slot
  costs the difference of the two programs' device times over 2^22.

Each program runs once to compile, `--reps` times on the host's clock,
and once alone under the profiler (device time of the whole execution,
its longest ops). The compiled text goes to `--out`: which arrays the
compiler keeps in fast memory, `S(1)`, is printed. On a CPU the script
runs (a rehearsal, with `--scale`) and says so; its times mean nothing
there.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CAP, K, N_WORDS, SLOTS = 262144, 20, 32768, 1 << 23
N_PAIRS, CLIENTS, SERVERS, SERVER_PAIRS = 2_340_000, 196_103, 24, 30_000
EVENTS = 3_472_222


def batch(seed: int, scale: float, cap: int = CAP):
    """(store [cap, K], lam [V, K], elog_beta [V, K], docs, words,
    weights [slots], n_pairs) at the cell's shapes times `scale`; past
    `CAP` store rows the documents lie on rows drawn from the seed."""
    rng = np.random.default_rng(seed)
    spread = cap > CAP
    cap = max(64, int(cap * scale))
    slots = max(1024, int(SLOTS * scale))
    n_pairs = int(N_PAIRS * scale)
    clients = max(8, int(CLIENTS * scale))
    server_pairs = int(SERVER_PAIRS * scale)
    words_n = max(64, int(N_WORDS * scale))
    d = np.concatenate([
        rng.integers(0, clients, n_pairs - server_pairs),
        clients + rng.integers(0, SERVERS, server_pairs)]).astype(np.int32)
    if spread:
        d = rng.choice(cap - 1, clients + SERVERS,
                       replace=False).astype(np.int32)[d]
    w = rng.integers(0, words_n, n_pairs).astype(np.int32)
    order = np.lexsort((d, w))
    pad = slots - n_pairs
    docs = np.concatenate([d[order], np.full(pad, cap - 1, np.int32)])
    words = np.concatenate([w[order], np.zeros(pad, np.int32)])
    weights = np.concatenate([rng.integers(1, 6, n_pairs).astype(np.float32),
                              np.zeros(pad, np.float32)])
    store = (1.2 + rng.gamma(2.0, 2.0, (cap, K))).astype(np.float32)
    lam = (0.01 + rng.gamma(0.5, 20.0, (words_n, K))).astype(np.float32)
    from scipy.special import digamma
    elog_beta = (digamma(lam) - digamma(lam.sum(0, keepdims=True))
                 ).astype(np.float32)
    return store, lam, elog_beta, docs, words, weights, n_pairs


def token_slots(seed: int, scale: float, cap: int, words_n: int):
    """(docs, words [slots]) as the scores see a batch: each event's
    client and server token in event order, the servers' half spread
    over 24 documents, the padding slots at row cap - 1."""
    rng = np.random.default_rng(seed + 1)
    slots = max(1024, int(SLOTS * scale))
    events = int(EVENTS * scale)
    clients = max(8, int(CLIENTS * scale))
    docs = np.full(slots, cap - 1, np.int32)
    words = np.zeros(slots, np.int32)
    half = slots // 2
    docs[:events] = rng.integers(0, clients, events)
    docs[half:half + events] = clients + rng.integers(0, SERVERS, events)
    w = rng.integers(0, words_n, events)
    words[:events] = w
    words[half:half + events] = w
    return docs, words


def program(mode: str, form: str, size: int):
    """The jitted unit of `mode` in `form` ("packed" as the chip takes it
    at K=20, "rows" one document a row, as K over 64, and the CPU, run;
    "local" packed, on the working table) and the patch that traces that
    form; `size` the passes (estep) or the token slots (score), and
    nothing for lambda, whose size is an operand."""
    from unittest import mock

    import jax.numpy as jnp

    from onix.models import lda_gibbs, lda_svi

    if mode == "estep" and form == "local":
        def run(store, elog_beta, docs, words, weights, n_pairs):
            cap, k = store.shape
            touched = jnp.zeros((cap,), jnp.float32).at[docs].add(
                weights) > 0.0
            new, _, stats, _ = lda_svi._in_rounds(
                store, touched, touched.sum(dtype=jnp.int32),
                lda_svi._WORKING_ROWS * lda_svi._gamma_group(k), docs,
                words, weights, lda_svi._runs_holding(n_pairs, docs.shape[0]),
                dict(alpha=1.2, local_iters=size, meanchange_tol=1e-30,
                     warm_iters=1, estep_form="svi", with_stats=True,
                     elog_beta=elog_beta, n_rows=n_pairs, with_carried=True))
            # Untouched rows as `_run_e_step` hands them back.
            return jnp.where(touched[:, None], new, 1.2), stats
        run.__name__ = f"unit_local_{size}"
    elif mode == "estep":
        def run(store, elog_beta, docs, words, weights, n_pairs):
            return lda_svi._run_e_step(
                store, words, docs, weights, alpha=1.2, local_iters=size,
                meanchange_tol=1e-30, warm_iters=1, with_stats=True,
                elog_beta=elog_beta, n_rows=n_pairs)
        run.__name__ = f"unit_{form}_{size}"
    elif mode == "lambda":
        def run(store, elog_beta, docs, words, weights, n_pairs):
            rows = lda_svi._pack_rows(store, lda_svi._gamma_group(K), 1.2)
            return lda_svi._pair_sstats(
                rows, elog_beta, docs, words, weights,
                lda_svi._runs_holding(n_pairs, docs.shape[0]))
        run.__name__ = f"unit_lambda_{form}"
    else:
        def run(store, lam, docs, words):
            return lda_svi._slot_scores(store, lam, docs[:size], words[:size])
        run.__name__ = f"unit_score_{form}_{size}"
    patch = mock.patch.object(
        lda_svi, "_gamma_group",
        (lambda k: 1) if form == "rows" else lda_gibbs._ndk_group)
    return run, patch


def fast_memory(hlo: str) -> list[str]:
    """The distinct shapes of the compiled program laid out in fast
    memory (`S(1)`), largest first."""
    shapes = set(re.findall(r"([a-z0-9]+\[[0-9,]+\])\{[^}]*S\(1\)", hlo))

    def size(s):
        dims = s[s.index("[") + 1:-1].split(",")
        return int(np.prod([int(x) for x in dims]))
    return sorted(shapes, key=lambda s: -size(s))


def reading(args, cap: int, seed: int) -> dict:
    """One reading of `args.mode` at a store of `cap` rows: every form
    at both sizes, and the per-unit costs from their differences."""
    import jax
    import jax.numpy as jnp
    import jax.profiler

    from benchmark import tracered

    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    t0 = time.monotonic()
    store, lam, elog_beta, docs, words, weights, n_pairs = batch(
        seed, args.scale, cap)
    forms = args.forms.split(",")
    if args.mode == "estep":
        unit = "pair_row_pass"
        sizes = (args.short, args.long)
    elif args.mode == "lambda":
        unit = "pair_row"
        sizes = (int(args.short_pairs * args.scale), n_pairs)
    else:
        unit = "token_slot"
        sizes = (docs.shape[0] // 2, docs.shape[0])
    if args.mode == "score":
        operands = tuple(jnp.asarray(a) for a in (store, lam) + token_slots(
            seed, args.scale, store.shape[0], lam.shape[0]))
    else:
        operands = tuple(jnp.asarray(a) for a in (
            store, elog_beta, docs, words, weights))
    result = {"device": {"platform": dev.platform, "kind": dev.device_kind},
              "mode": args.mode,
              "times_are_device_times": on_chip,
              "store_rows": store.shape[0], "slots": docs.shape[0],
              "pairs": n_pairs, "batch_s": time.monotonic() - t0,
              "programs": {}}
    outs, compiled_by_name = {}, {}
    for form in forms:
        for size in sizes:
            fn, patch = program(args.mode, form, size)
            ops = operands + ((jnp.int32(size if args.mode == "lambda"
                                         else n_pairs),)
                              if args.mode != "score" else ())
            name = fn.__name__
            rec = {}
            if name not in compiled_by_name:
                t0 = time.monotonic()
                with patch:
                    compiled = jax.jit(fn).lower(*ops).compile()
                rec["compile_s"] = time.monotonic() - t0
                hlo = compiled.as_text()
                with open(os.path.join(args.out, f"{name}.{cap}.hlo.txt"),
                          "w") as fh:
                    fh.write(hlo)
                rec["fast_memory"] = fast_memory(hlo)
                compiled_by_name[name] = compiled
            compiled = compiled_by_name[name]
            out = jax.block_until_ready(compiled(*ops))
            walls = []
            for _ in range(args.reps):
                t0 = time.monotonic()
                jax.block_until_ready(compiled(*ops))
                walls.append(time.monotonic() - t0)
            rec["wall_s"] = min(walls)
            if args.mode == "estep":
                out, stats = out
                stats = [int(s) for s in stats]
                rec["stats"] = stats
                # warm passes x pairs + extended passes x active rows
                rec["units"] = stats[0] * stats[3] + stats[1] * stats[4]
            else:
                rec["units"] = size
            trace_dir = os.path.join(args.out, f"trace.{name}.{size}")
            jax.profiler.start_trace(trace_dir)
            with jax.profiler.TraceAnnotation("bench.trace_open"):
                pass
            jax.block_until_ready(compiled(*ops))
            with jax.profiler.TraceAnnotation("bench.trace_close"):
                pass
            jax.profiler.stop_trace()
            if on_chip:
                red = tracered.reduce_trace(trace_dir)
                mods = [m for n, m in red["modules"].items() if name in n]
                rec["device_s"] = sum(m["whole_seconds"] for m in mods)
                rec["top_ops"] = red["device_ops"][:8]
            shutil.rmtree(trace_dir, ignore_errors=True)
            key = f"{form}_{size}"
            outs[key] = np.asarray(out)
            result["programs"][key] = rec
            print(json.dumps({f"{cap}.{key}": rec}), flush=True)
    for form in forms:
        a = result["programs"][f"{form}_{sizes[0]}"]
        b = result["programs"][f"{form}_{sizes[1]}"]
        units = b["units"] - a["units"]
        for key in ("device_s", "wall_s"):
            if units and a.get(key) and b.get(key):
                result[f"{form}_ns_per_{unit}.{key}"] = (
                    (b[key] - a[key]) / units * 1e9)
    if len(forms) == 2:
        a, b = (outs[f"{f}_{sizes[1]}"] for f in forms)
        result["max_abs_diff"] = float(np.abs(a - b).max())
        result["max_rel_diff"] = float(
            (np.abs(a - b) / np.maximum(np.abs(b), 1e-30)).max())
    return result


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("estep", "lambda", "score"),
                    default="estep")
    ap.add_argument("--out", default=".bench_trace/stream_pack")
    ap.add_argument("--forms", default="rows,packed")
    ap.add_argument("--cap", default=str(CAP),
                    help="the store's rows, a comma list: a reading each")
    ap.add_argument("--short", type=int, default=3)
    ap.add_argument("--long", type=int, default=9)
    ap.add_argument("--short-pairs", type=int, default=N_PAIRS - 8 * (1 << 17),
                    help="lambda: the pairs of the shorter reading")
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--seed", type=int, default=4000100101)
    ap.add_argument("--scale", type=float, default=1.0,
                    help="shrink the batch (a CPU rehearsal)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import jax.profiler

    from benchmark import harness

    os.makedirs(args.out, exist_ok=True)
    seed = harness.fold_seed(args.seed)
    # A profiler session's first program may come back without its
    # module's events: one session ahead of the readings.
    jax.profiler.start_trace(os.path.join(args.out, "trace.warm"))
    jax.block_until_ready(jnp.zeros(8) + 1)
    jax.profiler.stop_trace()
    shutil.rmtree(os.path.join(args.out, "trace.warm"), ignore_errors=True)
    caps = [int(c) for c in args.cap.split(",")]
    results = {cap: reading(args, cap, seed) for cap in caps}
    result = results[caps[0]] if len(caps) == 1 else {
        str(cap): r for cap, r in results.items()}
    suffix = "" if caps == [CAP] else ".cap"
    with open(os.path.join(args.out, f"stream_pack.{args.mode}{suffix}.json"),
              "w") as fh:
        json.dump(result, fh, indent=1)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
