"""ISSUE 34's unit readings, on the chip: what the sweep's n_dk
scatter-add and the whole block step cost with n_dk one document a
K-lane row ([D, K], "rows") and G documents a 128-lane row ("packed"),
at a fit cell's shapes and real document ids.

    chiprun -- python3 scripts/exp_ndk_pack.py --out chiprun_out/ndk_pack

For each size (`flow-fit`'s corpus, and one of the documents and
tokens-a-document that a chip of `flow-fit-4chip` holds) the corpus is
made as the cell's driver makes it, laid out by `shard_corpus`, and the
first `--blocks` blocks are swept once by each program:

  a  the rows form's scatter-add alone (`n_dk.at[d].add(delta)`)
  b  the packed scatter-add alone, at the G `select_ndk_form` takes
  c  the rows form's whole block step (`make_block_step` bare)
  d  the packed whole block step, once a G of `--groups` (and once a
     lane pick of `--picks`)
  e, f, g  with `--chains`, the whole sweep kernel under a vmap over a
     chain axis of one, as the engines run it: rows, packed and
     vmapped, packed and squeezed (`lda_gibbs._squeeze_one_chain`)

Each runs once to compile, `--reps` times on the host's clock, and once
under the profiler, where `benchmark/readers/scope_seconds.py` books
the device's time to the `onix.sweep.*` scopes. Printed: ns a token.
The programs' compiled text goes to `--out` (which arrays the compiler
keeps in fast memory, `S(1)`). On a CPU the script runs (a rehearsal)
and says so; its times mean nothing there.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SIZES = {   # name: (base_events, base_hosts, base_anomalies, copies)
    "flow-fit": (10_000_000, 20_000, 1000, 10),
    "a-chip-of-four": (10_000_000, 6667, 1000, 8),
}


def corpus_blocks(size, seed: int, block: int, n_blocks: int, scale: float):
    from benchmark.drivers.fit import base_corpus, tile_corpus
    from onix.parallel.sharded_gibbs import shard_corpus
    events, hosts, anomalies, copies = SIZES[size]
    traffic = {"base_events": int(events * scale),
               "base_hosts": max(8, int(hosts * scale)),
               "base_anomalies": max(1, int(anomalies * scale))}
    corpus = tile_corpus(base_corpus({"datatype": "flow"}, traffic, seed),
                         copies)
    sc = shard_corpus(corpus, 1, block, seed)
    take = min(n_blocks, sc.doc_blocks.shape[2])
    docs, words, mask = (np.ascontiguousarray(a[0, 0, :take]) for a in
                         (sc.doc_blocks, sc.word_blocks, sc.mask_blocks))
    return corpus, docs, words, mask


def pick_variants():
    """Other ways to take a document's K lanes out of its 128-lane row
    than `lda_gibbs._pick_lanes`, the one the sweep has (the rows
    turned over once, slices along the tokens): the readings that
    chose it."""
    import jax.numpy as jnp

    def slices(rows, slot, k, group):
        out = rows[:, :k]
        for j in range(1, group):
            out = jnp.where((slot == j)[:, None],
                            rows[:, j * k:(j + 1) * k], out)
        return out

    def roll(rows, slot, k, group):
        step = 1
        while step < group:
            rows = jnp.where((slot & step != 0)[:, None],
                             jnp.roll(rows, -step * k, axis=1), rows)
            step *= 2
        return rows[:, :k]

    return {"slices": slices, "roll": roll}


def programs(k, n_vocab, groups, picks=(), chains=False):
    """name -> (fn(n_dk, n_wk, n_k, key, docs, words, mask, z, z_to) ->
    arrays, group): every program a scan over the blocks."""
    import jax

    from onix.models import lda_gibbs as lg
    from onix.utils.obs import device_scope

    def scatter_alone(group):
        def step(n_dk, xs):
            d, z_old, z_new = xs
            if group == 1:
                delta = lg._one_hot(z_new, k) - lg._one_hot(z_old, k)
                with device_scope("onix.sweep.scatter"):
                    return n_dk.at[d].add(delta), None
            delta = lg._lane_delta(d % group, z_old, z_new, k)
            with device_scope("onix.sweep.scatter"):
                return n_dk.at[d // group].add(delta), None

        def run(n_dk, n_wk, n_k, key, docs, words, mask, z, z_to):
            if group > 1:
                n_dk = lg.pack_ndk(n_dk, group)
            n_dk, _ = jax.lax.scan(step, n_dk, (docs, z, z_to))
            return n_dk
        return run

    def whole_step(group, pick=None):
        step = lg.make_block_step(alpha=1.2, eta=0.01, n_vocab=n_vocab,
                                  k_topics=k, ndk_group=group)
        if pick is not None:
            from unittest import mock
            plain = step

            def step(carry, xs):        # traced with the variant's pick
                with mock.patch.object(lg, "_pick_lanes", pick):
                    return plain(carry, xs)

        def run(n_dk, n_wk, n_k, key, docs, words, mask, z, z_to):
            n_docs = n_dk.shape[0]
            if group > 1:
                with device_scope("onix.sweep.pack"):
                    n_dk = lg.pack_ndk(n_dk, group)
            (n_dk, n_wk, n_k, key), z = jax.lax.scan(
                step, (n_dk, n_wk, n_k, key), (docs, words, mask, z))
            if group > 1:
                with device_scope("onix.sweep.pack"):
                    n_dk = lg.unpack_ndk(n_dk, n_docs, k, group)
            return n_dk, n_wk, n_k, z
        return run

    def chain_vmap(form, squeeze):
        """The sweep as the engines run it: `make_sweep_kernel`'s
        kernel under a vmap over a chain axis of one; `squeeze` False
        takes `_squeeze_one_chain`'s rule off the packed kernel."""
        from unittest import mock
        with mock.patch.object(lg, "_squeeze_one_chain",
                               lg._squeeze_one_chain if squeeze
                               else lambda kernel: kernel):
            kernel = lg.make_sweep_kernel(
                alpha=1.2, eta=0.01, n_vocab=n_vocab, k_topics=k,
                ndk_form=form, sampler_form="dense")

        def run(n_dk, n_wk, n_k, key, docs, words, mask, z, z_to):
            z, n_dk, n_wk, n_k, key = jax.vmap(
                lambda zc, dk, wk, nk, kc: kernel(
                    zc, dk, wk, nk, kc, docs, words, mask))(
                z[None], n_dk[None], n_wk[None], n_k[None], key[None])
            return n_dk[0], n_wk[0], n_k[0], z[0]
        return run

    g_gate = lg.select_ndk_form(backend="tpu", k_topics=k)[1]
    out = {"a_rows_scatter": (scatter_alone(1), 1),
           f"b_packed{g_gate}_scatter": (scatter_alone(g_gate), g_gate),
           "c_rows_step": (whole_step(1), 1)}
    for g in groups:
        out[f"d_packed{g}_step"] = (whole_step(g), g)
        for name, pick in pick_variants().items():
            if name in picks:
                out[f"d_packed{g}_step_{name}"] = (whole_step(g, pick), g)
    if chains:
        out["e_rows_chain_vmap"] = (chain_vmap("rows", True), 1)
        out["f_packed_chain_vmap"] = (chain_vmap("packed", False), g_gate)
        out["g_packed_chain_squeezed"] = (chain_vmap("packed", True), g_gate)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="chiprun_out/ndk_pack")
    ap.add_argument("--sizes", default="flow-fit,a-chip-of-four")
    ap.add_argument("--groups", default="4,6")
    ap.add_argument("--picks", default="",
                    help="also the packed step with these lane picks: "
                         "slices,roll")
    ap.add_argument("--chains", action="store_true",
                    help="also the sweep kernel under a vmap over one "
                         "chain, as the engines run it: e rows, f packed "
                         "and vmapped, g packed and squeezed")
    ap.add_argument("--blocks", type=int, default=64)
    ap.add_argument("--block", type=int, default=1 << 17)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=3400100101)
    ap.add_argument("--scale", type=float, default=1.0,
                    help="shrink the corpus (a CPU rehearsal)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import jax.profiler

    from benchmark import harness, tracered
    from benchmark.readers import scope_seconds
    from onix.models import lda_gibbs as lg

    os.makedirs(args.out, exist_ok=True)
    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    k = 20
    groups = [int(g) for g in args.groups.split(",")]
    seed = harness.fold_seed(args.seed)
    result = {"device": {"platform": dev.platform, "kind": dev.device_kind},
              "times_are_device_times": on_chip, "blocks": args.blocks,
              "block": args.block, "sizes": {}}

    for size in args.sizes.split(","):
        t0 = time.monotonic()
        corpus, docs, words, mask = corpus_blocks(
            size, seed, args.block, args.blocks, args.scale)
        n_tok = int(docs.size)
        distinct = float(np.mean([np.unique(b).size / b.size for b in docs]))
        distinct_g = {g: float(np.mean([np.unique(b // g).size / b.size
                                        for b in docs])) for g in groups}
        d_, w_, m_ = (jnp.asarray(a) for a in (docs, words, mask))
        st = lg.init_state(d_, w_, m_, corpus.n_docs, corpus.n_vocab, k,
                           seed=seed % (2 ** 31))
        # A second assignment for the scatter-alone programs: a quarter
        # of the tokens move, about what a sweep of the cell moves.
        rng = np.random.default_rng(seed)
        z0 = np.asarray(st.z)
        moved = (rng.random(z0.shape) < 0.25) & (z0 < k)
        z_to = jnp.asarray(np.where(
            moved, (z0 + 1 + rng.integers(0, k - 1, z0.shape)) % k,
            z0).astype(np.int32))
        progs = programs(k, corpus.n_vocab, groups,
                         picks=args.picks.split(","), chains=args.chains)
        operands = (st.n_dk, st.n_wk, st.n_k, st.key, d_, w_, m_, st.z, z_to)
        rec = {"n_docs": corpus.n_docs, "n_vocab": corpus.n_vocab,
               "tokens": n_tok, "distinct_rows_per_token": distinct,
               "distinct_rows_per_token_packed": distinct_g,
               "corpus_s": time.monotonic() - t0, "programs": {}}
        jitted = {}
        for name, (fn, group) in progs.items():
            fn.__name__ = f"unit_{name}"
            f = jax.jit(fn)
            t0 = time.monotonic()
            compiled = f.lower(*operands).compile()
            compile_s = time.monotonic() - t0
            with open(os.path.join(args.out, f"{size}.{name}.hlo.txt"),
                      "w") as fh:
                fh.write(compiled.as_text())
            jax.block_until_ready(f(*operands))
            walls = []
            for _ in range(args.reps):
                t0 = time.monotonic()
                jax.block_until_ready(f(*operands))
                walls.append(time.monotonic() - t0)
            jitted[name] = f
            rec["programs"][name] = {
                "group": group, "compile_s": compile_s,
                "wall_ns_per_token": min(walls) / n_tok * 1e9}
        ref = jitted["c_rows_step"](*operands)
        for dn in [n for n in jitted if n[0] in "defg"]:
            got = jitted[dn](*operands)
            rec["programs"][dn]["same_as_rows"] = all(
                bool(jnp.array_equal(a, b)) for a, b in zip(ref, got))
        trace_dir = os.path.join(args.out, f"trace.{size}")
        jax.profiler.start_trace(trace_dir)
        with jax.profiler.TraceAnnotation("bench.trace_open"):
            pass
        for f in jitted.values():
            jax.block_until_ready(f(*operands))
        with jax.profiler.TraceAnnotation("bench.trace_close"):
            pass
        jax.profiler.stop_trace()
        if on_chip:
            planes = scope_seconds.read_planes(tracered.find_xplane(trace_dir))
            for name in jitted:
                booked = scope_seconds.book(planes, f"unit_{name}")
                if booked:
                    by_scope, whole = booked
                    rec["programs"][name]["device_ns_per_token"] = {
                        s.replace("onix.sweep.", ""): v / whole / n_tok * 1e9
                        for s, v in sorted(by_scope.items())}
                    rec["programs"][name]["device_total_ns_per_token"] = (
                        sum(by_scope.values()) / whole / n_tok * 1e9)
        shutil.rmtree(trace_dir, ignore_errors=True)
        result["sizes"][size] = rec
        print(json.dumps({size: rec}), flush=True)
        del operands, st, jitted

    with open(os.path.join(args.out, "unit_table.json"), "w") as fh:
        json.dump(result, fh, indent=1)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
