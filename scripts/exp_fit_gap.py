"""Diagnose the gibbs_fit vs sweep-microbench gap (round 3; promoted to
the decision table in round 7).

bench.py's sweep microbench posts ~35M tokens/s/chip (8.4M tokens,
V=4096, 4 sweeps in one program), but the 1e8-token scale artifacts'
gibbs_fit stage runs at ~7-11M tokens/s effective. Candidate causes,
each isolated here on the real corpus shape:

  A. per-sweep Python dispatch (the pre-r7 fit called _sweep once per
     sweep; the microbench chains sweeps inside one program). The fused
     superstep (lda_gibbs.superstep) is the fix — the *_fit arms below
     measure it against a reconstruction of the per-sweep loop.
  B. the sharded engine's shard_map/psum overhead at dp=1. The dp=1
     fast path (sharded_gibbs superstep_dp1_fn) is the fix; the
     ONIX_DP1_FAST=0 arm measures the wrapped form.
  C. the accumulate phase (posterior-mean running sums after burn-in)
  D. the likelihood evals (on-device at superstep boundaries since r7)
  E. shape effects — in particular n_wk scatter COLLISION DENSITY
     (block_size / V colliding row-updates per vocab row): the
     raw_nwk_scatter / raw_nwk_matmul / raw_nwk_pallas rows feed the
     lda_gibbs._NWK_MATMUL_MIN_DENSITY and _NWK_PALLAS_MIN_DENSITY
     decision tables (docs/PERF.md; not measured on the chip),
     bit-identity asserted across all three forms.
  F. sampler form (r11) — the dense O(K)-per-token block sampler vs
     the sparse O(K_active) arm (top-A active sets + stale F+-tree
     proposals + MH correction) swept over K (--k-sweep, default
     16,64,256): the `sampler_k_sweep` rows ARE the decision table
     behind lda_gibbs._SAMPLER_SPARSE_MIN_K (docs/SPARSE_r11_*.json;
     not measured on the chip). Interleaved best-of
     timing, per-K perplexity-band parity ASSERTED (the sparse arm is
     a different chain with the same stationary distribution, so the
     gate-arm contract is an ll band, not bit-identity).

Run on the TPU host:  python scripts/exp_fit_gap.py [n_tokens]
Tiny tier-1 smoke (so this harness cannot rot between TPU windows):
  python scripts/exp_fit_gap.py 4000 --hosts 200 --sweeps 2 --block 512 \
      --k-sweep 4,8
Emits one JSON block; safe to rerun (compile cache persists).
"""

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description="isolate the gibbs_fit vs sweep-microbench gap")
    ap.add_argument("n_events", nargs="?", type=float, default=50_000_000)
    ap.add_argument("--hosts", type=int, default=200_000)
    ap.add_argument("--anomalies", type=int, default=1000)
    ap.add_argument("--sweeps", type=int, default=8)
    ap.add_argument("--block", type=int, default=1 << 17)
    ap.add_argument("--out", default=None,
                    help="also write the JSON block to this path")
    ap.add_argument("--k-sweep", default="",
                    help="comma-separated K values for the sampler-form "
                         "arms (dense vs sparse, interleaved best-of); "
                         "empty (the default) skips them so existing "
                         "callers — the fitgap_tpu queue entry included "
                         "— don't silently inherit the expensive sweep")
    args = ap.parse_args(argv)
    n_events = int(args.n_events)
    n_sweeps = int(args.sweeps)

    import jax
    import numpy as np

    from onix.config import LDAConfig
    from onix.models.lda_gibbs import GibbsLDA
    from onix.parallel.mesh import make_mesh
    from onix.parallel.sharded_gibbs import ShardedGibbsLDA
    from onix.pipelines.corpus_build import build_corpus
    from onix.pipelines.scale import _words_from_cols
    from onix.pipelines.synth import SYNTH_ARRAYS
    from onix.utils.obs import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]
    out = {"device": str(dev), "backend": jax.default_backend(),
           "n_events": n_events, "n_sweeps": n_sweeps}

    cols = SYNTH_ARRAYS["dns"](n_events, n_hosts=min(args.hosts, n_events),
                               n_anomalies=min(args.anomalies,
                                               max(n_events // 100, 1)),
                               seed=0)
    bundle = build_corpus(_words_from_cols("dns", cols))
    corpus = bundle.corpus
    out["n_docs"] = int(corpus.n_docs)
    out["n_vocab"] = int(corpus.n_vocab)
    out["n_tokens"] = int(corpus.n_tokens)
    del cols

    block = min(args.block, max(corpus.n_tokens, 1))
    cfg = LDAConfig(n_topics=20, n_sweeps=n_sweeps,
                    burn_in=max(n_sweeps // 2, 1),
                    block_size=block, seed=0)

    def timed_fit(tag, model, **kw):
        # Warm-up compiles every program the timed fit will run
        # (burn_in+1 sweeps crosses the accumulate boundary inside the
        # fused superstep, so both phases warm in one pass).
        model.fit(corpus, n_sweeps=model.config.burn_in + 1, **kw)
        t0 = time.monotonic()
        model.fit(corpus, **kw)
        dt = time.monotonic() - t0
        rate = n_sweeps * corpus.n_tokens / dt / 1e6
        out[tag] = {"wall_s": round(dt, 2),
                    "mtok_per_s_effective": round(rate, 2)}
        print(f"{tag}: {dt:.1f}s  {rate:.1f} Mtok/s", flush=True)

    # B: sharded at dp=1 (the scale runner's single-chip config) vs the
    # plain single-device engine, identical corpus — dp is PINNED to 1
    # so this isolates shard_map/psum overhead, not data parallelism.
    # The engine's dp=1 fast path bypasses the wrapping since r7;
    # sharded_dp1_shardmap pins the wrapped form (the pre-r7 path) via
    # ONIX_DP1_FAST=0 so the overhead stays a measured number.
    # Each arm PINS the env gate (an ambient ONIX_DP1_FAST=0 would
    # silently turn the fast arm into a second shard_map measurement),
    # and the caller's value is restored afterward.
    import os
    prior = os.environ.get("ONIX_DP1_FAST")
    try:
        os.environ["ONIX_DP1_FAST"] = "1"
        timed_fit("sharded_dp1_fast", ShardedGibbsLDA(
            cfg, corpus.n_vocab, mesh=make_mesh(dp=1, mp=1)))
        os.environ["ONIX_DP1_FAST"] = "0"
        timed_fit("sharded_dp1_shardmap", ShardedGibbsLDA(
            cfg, corpus.n_vocab, mesh=make_mesh(dp=1, mp=1)))
    finally:
        if prior is None:
            del os.environ["ONIX_DP1_FAST"]
        else:
            os.environ["ONIX_DP1_FAST"] = prior
    timed_fit("plain_single", GibbsLDA(cfg, corpus.n_docs, corpus.n_vocab))

    # C: accumulate phase on for every sweep vs off for every sweep.
    cfg_acc = LDAConfig(n_topics=20, n_sweeps=n_sweeps, burn_in=0,
                        block_size=block, seed=0)
    cfg_noacc = LDAConfig(n_topics=20, n_sweeps=n_sweeps, burn_in=n_sweeps,
                          block_size=block, seed=0)
    timed_fit("all_accumulate", GibbsLDA(cfg_acc, corpus.n_docs,
                                         corpus.n_vocab))
    timed_fit("no_accumulate", GibbsLDA(cfg_noacc, corpus.n_docs,
                                        corpus.n_vocab))

    # A/D: the PRE-r7 fit loop, reconstructed — one _sweep dispatch per
    # sweep plus the old standalone estimates+ll programs at its
    # cadence (init + every 10th + final). The fit arms above already
    # run the fused superstep, so this pair IS the adoption measurement.
    from onix.models.lda_gibbs import init_state

    model = GibbsLDA(cfg, corpus.n_docs, corpus.n_vocab)
    docs, words, mask = model.prepare(corpus)

    def per_sweep_loop():
        st = init_state(docs, words, mask, corpus.n_docs, corpus.n_vocab,
                        cfg.n_topics, cfg.seed)
        theta, phi = model._estimates(st)
        lls = [float(model._ll(theta, phi, docs, words, mask))]
        for s in range(n_sweeps):
            st = model._sweep(st, docs, words, mask,
                              accumulate=s >= cfg.burn_in)
            if s == n_sweeps - 1 or s % 10 == 9:
                theta, phi = model._estimates(st)
                lls.append(float(model._ll(theta, phi, docs, words, mask)))
        return st

    def superstep_loop():
        state = init_state(docs, words, mask, corpus.n_docs,
                           corpus.n_vocab, cfg.n_topics, cfg.seed)
        state, ll0, ll = model._superstep(state, docs, words, mask, 0,
                                          n_steps=n_sweeps,
                                          with_initial_ll=True)
        float(ll)                                  # forces completion
        return state

    # The A/D adoption pair rides INTERLEAVED best-of-2 timing: this
    # host's wall clock swings ±30% in multi-minute load waves, and a
    # wave landing on one arm of a single-shot A/B fabricates (or
    # hides) a 1.5x. Interleaving + min puts both arms through the
    # same weather.
    st_seq = per_sweep_loop()                      # compile + warm
    st_fused = superstep_loop()
    best = {"per_sweep_loop": float("inf"), "superstep_loop": float("inf")}
    for _ in range(2):
        t0 = time.monotonic()
        st_seq = per_sweep_loop()
        best["per_sweep_loop"] = min(best["per_sweep_loop"],
                                     time.monotonic() - t0)
        t0 = time.monotonic()
        st_fused = superstep_loop()
        best["superstep_loop"] = min(best["superstep_loop"],
                                     time.monotonic() - t0)
    for tag, dt in best.items():
        out[tag] = {"wall_s": round(dt, 2),
                    "mtok_per_s_effective": round(
                        n_sweeps * corpus.n_tokens / dt / 1e6, 2)}
        print(f"{tag}:", out[tag], flush=True)
    out["superstep_speedup_vs_per_sweep"] = round(
        best["per_sweep_loop"] / best["superstep_loop"], 3)
    # Bit-identity of the two loop forms on this very shape (the tests
    # assert it at unit scale; asserting here keeps the measurement
    # honest at experiment scale too).
    np.testing.assert_array_equal(np.asarray(st_seq.n_wk),
                                  np.asarray(st_fused.n_wk))

    import jax.numpy as jnp

    from onix.models.lda_gibbs import make_block_step

    def timed_raw(tag, step):
        """Chained raw sweeps of `step` — the microbench form on the
        REAL corpus shape (no ll, no estimates, no accumulate). Returns
        the final (n_wk, z) so the form arms can assert bit-identity."""
        @jax.jit
        def sweepsN(carry, z):
            def one(c_z, _):
                c, z = c_z
                c, z = jax.lax.scan(step, c, (docs, words, mask, z))
                return (c, z), None
            (carry, z), _ = jax.lax.scan(one, (carry, z),
                                         jnp.arange(n_sweeps))
            return carry, z

        st = init_state(docs, words, mask, corpus.n_docs, corpus.n_vocab,
                        cfg.n_topics, cfg.seed)
        carry = (st.n_dk, st.n_wk, st.n_k, st.key)
        carry, z = sweepsN(carry, st.z)            # compile + warm
        jax.block_until_ready(carry[1])
        t0 = time.monotonic()
        carry, z = sweepsN(carry, z)
        jax.block_until_ready(carry[1])
        dt = time.monotonic() - t0
        out[tag] = {"wall_s": round(dt, 2),
                    "mtok_per_s": round(
                        n_sweeps * corpus.n_tokens / dt / 1e6, 2)}
        print(tag, out[tag], flush=True)
        return np.asarray(carry[1]), np.asarray(z)

    timed_raw("raw_sweeps_no_fit",
              make_block_step(alpha=cfg.alpha, eta=cfg.eta,
                              n_vocab=corpus.n_vocab,
                              k_topics=cfg.n_topics))

    # E: n_wk delta form — scatter-add vs MXU one-hot matmul vs the
    # Pallas fused sample+count kernel, raw sweeps. Product
    # vocabularies are collision-dense for the n_wk scatter (density =
    # B/V colliding updates per row); all three forms are bit-identical
    # (test_gibbs, test_pallas_gibbs — and re-asserted HERE at
    # experiment scale), and these rows ARE the decision table behind
    # lda_gibbs._NWK_MATMUL_MIN_DENSITY / _NWK_PALLAS_MIN_DENSITY
    # (docs/PERF.md; not measured on the chip).
    # Off-TPU the pallas arm runs the interpret-mode emulation — its
    # CPU rate is a correctness diagnostic, not a speed claim.
    out["nwk_collision_density"] = round(block / corpus.n_vocab, 1)
    finals = {}
    for form in ("scatter", "matmul", "pallas"):
        finals[form] = timed_raw(
            f"raw_nwk_{form}",
            make_block_step(alpha=cfg.alpha, eta=cfg.eta,
                            n_vocab=corpus.n_vocab,
                            k_topics=cfg.n_topics, nwk_form=form))
    for form in ("matmul", "pallas"):
        np.testing.assert_array_equal(finals["scatter"][0],
                                      finals[form][0])
        np.testing.assert_array_equal(finals["scatter"][1],
                                      finals[form][1])
    out["nwk_forms_bit_identical"] = True

    # F: sampler form over K — the r11 sparse O(K_active) arm vs the
    # dense block sampler, raw chained sweeps on the SAME corpus
    # tokens at each K. Interleaved best-of-2 (same weather for both
    # arms, like the A/D pair above); per-K parity is the
    # perplexity-band contract: both arms' post-sweep predictive ll
    # from identical inits must land within 5% of each other.
    from onix.models.lda_gibbs import (LL_PARITY_BAND,
                                       counts_log_likelihood,
                                       make_sweep_kernel,
                                       resolve_sparse_active)

    k_list = [int(s) for s in args.k_sweep.split(",") if s.strip()]
    if k_list:
        import jax.numpy as jnp  # noqa: F811 (also imported above)

        k_rows = {}
        for k_topics in k_list:
            def run_form(form):
                kern = make_sweep_kernel(
                    alpha=cfg.alpha, eta=cfg.eta, n_vocab=corpus.n_vocab,
                    k_topics=k_topics, sampler_form=form)

                @jax.jit
                def sweepsN(z, ndk, nwk, nk, key):
                    def one(c, _):
                        return kern(*c, docs, words, mask), None
                    (z, ndk, nwk, nk, key), _ = jax.lax.scan(
                        one, (z, ndk, nwk, nk, key),
                        jnp.arange(n_sweeps))
                    return z, ndk, nwk, nk, key

                st = init_state(docs, words, mask, corpus.n_docs,
                                corpus.n_vocab, k_topics, cfg.seed)
                return sweepsN, (st.z, st.n_dk, st.n_wk, st.n_k, st.key)

            arms = {f: run_form(f) for f in ("dense", "sparse")}
            best = {f: float("inf") for f in arms}
            states = {}
            for f, (fn, carry) in arms.items():
                states[f] = fn(*carry)          # compile + warm
                jax.block_until_ready(states[f][1])
            for _ in range(2):
                for f, (fn, _) in arms.items():
                    t0 = time.monotonic()
                    states[f] = fn(*states[f])
                    jax.block_until_ready(states[f][1])
                    best[f] = min(best[f], time.monotonic() - t0)

            def counts_ll(stf):
                _, ndk, nwk, nk, _ = stf
                return counts_log_likelihood(ndk, nwk, nk, docs, words,
                                             mask, alpha=cfg.alpha,
                                             eta=cfg.eta)

            lls = {f: counts_ll(states[f]) for f in arms}
            band = LL_PARITY_BAND * abs(lls["dense"])
            assert abs(lls["sparse"] - lls["dense"]) < band, (
                f"sampler parity broken at K={k_topics}: {lls}")
            row = {"n_active": resolve_sparse_active(k_topics),
                   "ll_dense": round(lls["dense"], 4),
                   "ll_sparse": round(lls["sparse"], 4)}
            for f in arms:
                row[f"{f}_wall_s"] = round(best[f], 2)
                row[f"{f}_mtok_per_s"] = round(
                    n_sweeps * corpus.n_tokens / best[f] / 1e6, 2)
            row["sparse_speedup"] = round(best["dense"] / best["sparse"],
                                          3)
            k_rows[str(k_topics)] = row
            print(f"sampler_k_sweep K={k_topics}:", row, flush=True)
        out["sampler_k_sweep"] = k_rows
        out["sampler_parity_ll_band"] = True

    text = json.dumps(out)
    print(text)
    if args.out:
        import pathlib
        p = pathlib.Path(args.out)
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(json.dumps(out, indent=2) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
