"""ISSUE 38's go/no-go, on the chip: does the compile of the fit's two
per-sweep programs hide beside `shard_corpus`, at `flow-fit`'s size?

    chiprun -- python3 scripts/exp_compile_ahead.py

The corpus is made as the cell's driver makes it. Then, each on an
engine of its own (a `lower()` of the same jitted function hands back
the executable it has) and with the persistent compile cache off:

  a  `shard_corpus` alone on the main thread
  b  `lower().compile()` of the superstep with and without the first
     log-likelihood, alone on the main thread
  c  both at once: the two compiles on a thread, `shard_corpus` on the
     main thread (twice, `--both` times)

Go (ISSUE 38): `shard_corpus` in c not more than 5% over a, and the
compiles of c within 20% of b. Printed: one JSON line. On a CPU the
script runs (a rehearsal, `--scale 0.01`) and says so; its times mean
nothing there.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

FLOW_FIT = (10_000_000, 20_000, 1000, 10)   # benchmark/traffic/fit-1e8.json


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=3800100101)
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--both", type=int, default=2)
    args = ap.parse_args()

    import jax

    from benchmark import harness
    from benchmark.drivers.fit import base_corpus, tile_corpus
    from onix.config import LDAConfig
    from onix.parallel.mesh import make_mesh
    from onix.parallel.sharded_gibbs import (ShardedGibbsLDA, plan_of,
                                             shard_corpus)

    jax.config.update("jax_enable_compilation_cache", False)
    events, hosts, anomalies, copies = FLOW_FIT
    seed = harness.fold_seed(args.seed)
    traffic = {"base_events": int(events * args.scale),
               "base_hosts": max(8, int(hosts * args.scale)),
               "base_anomalies": max(1, int(anomalies * args.scale))}
    corpus = tile_corpus(base_corpus({"datatype": "flow"}, traffic, seed),
                         copies)
    lda = LDAConfig(n_topics=20, alpha=1.2, eta=0.01, n_sweeps=10 ** 6,
                    burn_in=10, block_size=131072, seed=seed)

    def engine():
        return ShardedGibbsLDA(lda, corpus.n_vocab,
                               mesh=make_mesh(dp=1, mp=1))

    def layout():
        t0 = time.monotonic()
        sc = shard_corpus(corpus, 1, lda.block_size, lda.seed)
        return time.monotonic() - t0, sc

    def compiles(model, plan, out: dict):
        """Both per-sweep programs from the plan alone, as the fit
        builds them ahead (`_build_ahead`; at the go/no-go's reading
        the script lowered them itself, from the same shapes); seconds
        a program into `out`."""
        build = model._build_ahead(plan, False)
        t_all = time.monotonic()
        for with_ll in (True, False):
            t0 = time.monotonic()
            build((1, with_ll))
            out[f"with_ll_{with_ll}"] = time.monotonic() - t0
        out["both"] = time.monotonic() - t_all

    result = {"device": str(jax.devices()[0].device_kind),
              "platform": jax.devices()[0].platform,
              "tokens": int(corpus.n_tokens), "docs": int(corpus.n_docs),
              "vocab": int(corpus.n_vocab)}
    result["layout_alone_s"], sc = layout()
    plan = plan_of(sc, corpus.n_tokens)
    del sc
    alone: dict = {}
    compiles(engine(), plan, alone)
    result["compile_alone_s"] = alone
    result["together"] = []
    for _ in range(args.both):
        beside: dict = {}
        worker = threading.Thread(
            target=compiles, args=(engine(), plan, beside))
        t0 = time.monotonic()
        worker.start()
        layout_s, sc = layout()
        del sc
        worker.join()
        result["together"].append({
            "layout_s": layout_s, "compile_s": beside,
            "wall_s": time.monotonic() - t0,
            "layout_over_alone": layout_s / result["layout_alone_s"],
            "compile_over_alone": beside["both"] / alone["both"]})
    print(json.dumps(result))


if __name__ == "__main__":
    main()
