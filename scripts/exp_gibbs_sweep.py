"""TPU experiment: Gibbs sweep sampler/scatter variants. Runs on
whatever JAX_PLATFORMS gives it and prints the device; on a CPU backend
it shrinks to a tiny smoke of the same code.
Companion to docs/PERF.md "exponential race" — run on a real chip:

    python scripts/exp_gibbs_sweep.py


A: current Gumbel-argmax (baseline, 5 transcendentals/token-topic)
B: exponential-race in linear space (argmax p/e, 1 log) — statistically
   identical sampler family (the Gumbel trick IS the exponential race in
   log space); per-element linear products keep full relative precision
   (no cumsum, so no rare-topic rounding).
C: B + within-block word-sorted tokens + indices_are_sorted scatter on
   n_wk (block partition unchanged -> same stationary behavior; order
   within a block is irrelevant to the blocked sampler).
"""
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

sys.path.insert(0, str(__import__("pathlib").Path(__file__).resolve().parent.parent))

from onix.models import lda_gibbs  # noqa: E402

N_DOCS, N_VOCAB, K = 200_000, 4_096, 20
_CPU_SMOKE = jax.default_backend() == "cpu"
N_TOKENS = (1 << 18) if _CPU_SMOKE else (1 << 23)
BLOCK = (1 << 14) if _CPU_SMOKE else (1 << 17)
REPS = 4

rng = np.random.default_rng(0)
nb = N_TOKENS // BLOCK
docs_h = rng.integers(0, N_DOCS, N_TOKENS).astype(np.int32)
words_h = rng.integers(0, N_VOCAB, N_TOKENS).astype(np.int32)


def make_sweep(variant):
    v_eta = N_VOCAB * 0.01

    def block_step(carry, xs):
        n_dk, n_wk, n_k, key = carry
        d, w, m, z_old = xs
        key, skey = jax.random.split(key)
        oh_old = lda_gibbs._one_hot(z_old, K)
        ohf = oh_old.astype(jnp.float32)
        ndk = n_dk[d].astype(jnp.float32) - ohf
        nwk = n_wk[w].astype(jnp.float32) - ohf
        nk = n_k.astype(jnp.float32)[None, :] - ohf
        if variant == "gumbel":
            logp = (jnp.log(ndk + 1.2)
                    + jnp.log(jnp.maximum(nwk + 0.01, 1e-10))
                    - jnp.log(nk + v_eta))
            g = jax.random.gumbel(skey, logp.shape, dtype=jnp.float32)
            z_new = jnp.argmax(logp + g, axis=-1).astype(jnp.int32)
        else:
            p = (ndk + 1.2) * jnp.maximum(nwk + 0.01, 1e-10) / (nk + v_eta)
            u = jax.random.uniform(skey, p.shape, dtype=jnp.float32,
                                   minval=1e-38)
            e = -jnp.log(u)
            z_new = jnp.argmax(p / e, axis=-1).astype(jnp.int32)
        z_new = jnp.where(m > 0, z_new, z_old)
        delta = lda_gibbs._one_hot(z_new, K) - oh_old
        n_dk = n_dk.at[d].add(delta)
        if variant == "race_sorted":
            n_wk = n_wk.at[w].add(delta, indices_are_sorted=True)
        else:
            n_wk = n_wk.at[w].add(delta)
        n_k = n_k + delta.sum(axis=0, dtype=jnp.int32)
        return (n_dk, n_wk, n_k, key), z_new

    def sweep(state, docs, words, mask):
        (n_dk, n_wk, n_k, key), z = jax.lax.scan(
            block_step, (state.n_dk, state.n_wk, state.n_k, state.key),
            (docs, words, mask, state.z))
        return state._replace(z=z, n_dk=n_dk, n_wk=n_wk, n_k=n_k, key=key)

    return sweep


def run(variant):
    if variant == "race_sorted":
        # sort WITHIN each block only
        order = np.concatenate([
            b * BLOCK + np.argsort(words_h[b * BLOCK:(b + 1) * BLOCK],
                                   kind="stable")
            for b in range(nb)])
        dh, wh = docs_h[order], words_h[order]
    else:
        dh, wh = docs_h, words_h
    docs = jnp.asarray(dh.reshape(nb, BLOCK))
    words = jnp.asarray(wh.reshape(nb, BLOCK))
    mask = jnp.ones((nb, BLOCK), jnp.float32)
    state = lda_gibbs.init_state(docs, words, mask, N_DOCS, N_VOCAB, K, 0)
    sweep = make_sweep(variant)

    @jax.jit
    def bench(state):
        def one(st, _):
            return sweep(st, docs, words, mask), None
        st, _ = jax.lax.scan(one, state, jnp.arange(REPS))
        return st

    np.asarray(bench(state).n_k)
    t0 = time.perf_counter()
    out = bench(state)
    nk = np.asarray(out.n_k)
    dt = time.perf_counter() - t0
    assert int(nk.sum()) == N_TOKENS
    rate = REPS * N_TOKENS / dt
    # quick mixing sanity: topic-use entropy near log K after REPS sweeps
    pk = nk / nk.sum()
    ent = float(-(pk * np.log(np.maximum(pk, 1e-12))).sum())
    print(f"{variant:12s} {rate/1e6:8.1f} Mtok/s  wall={dt:6.3f}s  "
          f"topic-entropy={ent:.3f}/{np.log(K):.3f}", flush=True)


from onix.utils.obs import print_device  # noqa: E402

print_device()
for v in ["gumbel", "race", "race_sorted"]:
    run(v)
