"""The stream's document probe for a group after an eviction, on the
device and on the host, at the shapes of the benchmark's
`flow-stream-churn` cell:

    python3 scripts/exp_stream_probe.py --out <dir>
    python3 scripts/exp_stream_probe.py --scale 0.01 --out <dir>   # rehearsal

The table is what an eviction leaves: 786,432 documents (0.75 x 2^20),
their addresses sorted and padded to 2^20 rows as
`StreamingScorer._push_table` hands them over. The group is 8 batches of
3,472,222 events padded to 2^22, both columns drawn from the table's
addresses but 25,000 clients a batch under an address it lacks.

* `device`: `streaming.stream_docs_probe` on the staged `[8, 2^22]`
  columns, compiled once, then `--reps` times on the host's clock: the
  dispatch and the counts on the host, and the same with every batch's
  mask fetched too (what `_grow_docs` reads where a batch has misses).
* `host`: `StreamingScorer._probe_on_host` on the same group, against
  the same table (one sort of its keys, a searchsorted a column).

The two answers are compared bit for bit. On a CPU the script runs and
says so; its times mean nothing there.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import types

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_DOCS, CAP, S, EVENTS, E_PAD, FRESH = 786_432, 1 << 20, 8, 3_472_222, 1 << 22, 25_000


def group(seed: int, scale: float):
    """(keys in first-seen order, cap, [(len, cols)] of S batches, e_pad)."""
    rng = np.random.default_rng(seed)
    n_docs, cap = int(N_DOCS * scale), max(256, int(CAP * scale))
    events, e_pad = int(EVENTS * scale), max(256, int(E_PAD * scale))
    fresh = max(1, int(FRESH * scale))
    keys = np.unique(rng.integers(10 << 24, 11 << 24, 2 * n_docs,
                                  dtype=np.uint32))
    keys = rng.permutation(keys)[:n_docs]
    out = []
    for b in range(S):
        cols = {name: keys[rng.integers(0, n_docs, events)]
                for name in ("sip_u32", "dip_u32")}
        new = (64 << 24) + b * fresh + np.arange(fresh, dtype=np.uint32)
        at = rng.integers(0, events, fresh)
        cols["sip_u32"][at] = new
        out.append((range(events), cols))
    return keys, cap, out, e_pad


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=4500100101)
    ap.add_argument("--out", default="chiprun_out/exp_stream_probe")
    args = ap.parse_args()

    import jax

    from onix.pipelines import streaming

    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind}", flush=True)
    keys, cap, batches, e_pad = group(args.seed, args.scale)
    order = np.argsort(keys, kind="stable")
    table = np.full(cap, np.iinfo(np.uint32).max, np.uint32)
    table[:len(keys)] = keys[order]
    ids = np.full(cap, cap - 1, np.int32)
    ids[:len(keys)] = order
    n_valid = np.asarray([len(t) for t, _ in batches], np.int32)
    staged_cols = {}
    for name in ("sip_u32", "dip_u32"):
        buf = np.zeros((S, e_pad), np.uint32)
        for i, (_, cols) in enumerate(batches):
            buf[i, :n_valid[i]] = cols[name]
        staged_cols[name] = jax.device_put(buf)
    d_keys, d_ids = jax.device_put(table), jax.device_put(ids)

    def on_device(masks: bool):
        counts, mask = streaming.stream_docs_probe(
            d_keys, d_ids, staged_cols["sip_u32"], staged_cols["dip_u32"],
            n_valid)
        counts = np.asarray(counts)
        return counts, (np.asarray(mask) if masks else None)

    t0 = time.perf_counter()
    counts_d, masks_d = on_device(True)
    first_s = time.perf_counter() - t0
    reading = {"device": dev.device_kind, "scale": args.scale,
               "docs": len(keys), "cap": cap, "group": [S, e_pad],
               "device_first_s": first_s}
    for name, masks in (("device_counts_s", False), ("device_masks_s", True)):
        walls = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            on_device(masks)
            walls.append(time.perf_counter() - t0)
        reading[name] = walls

    staged = types.SimpleNamespace(group=batches, cols=staged_cols,
                                   probe=None)
    owner = types.SimpleNamespace(docs=types.SimpleNamespace(keys=keys))
    walls = []
    for _ in range(max(1, args.reps // 2)):
        t0 = time.perf_counter()
        streaming.StreamingScorer._probe_on_host(owner, staged)
        walls.append(time.perf_counter() - t0)
    reading["host_s"] = walls
    counts_h, masks_h = staged.probe
    reading["counts"] = counts_h.tolist()
    reading["counts_equal"] = bool((counts_h == counts_d).all())
    reading["masks_equal"] = bool((masks_h == masks_d).all())
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "reading.json"), "w") as f:
        json.dump(reading, f, indent=1)
    if dev.platform != "tpu":
        print("not a TPU: the times below mean nothing", flush=True)
    print(json.dumps(reading), flush=True)


if __name__ == "__main__":
    main()
