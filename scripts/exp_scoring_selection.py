"""TPU experiment (bench.py methodology, product top_suspicious):
measure the subscan-fused selection path on the uniform headline shape
and on peaked (fitted-like) tables, at two chunk widths. Companion to
docs/PERF.md "round-2 selection experiments" — run on a real chip:

    python scripts/exp_scoring_selection.py
"""
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

sys.path.insert(0, str(__import__("pathlib").Path(__file__).resolve().parent.parent))
from onix.utils.obs import enable_compile_cache, print_device  # noqa: E402
enable_compile_cache()
print_device()
from onix.models.scoring import top_suspicious  # noqa: E402

N_DOCS, N_VOCAB, K = 100_000, 65_536, 20
N_EVENTS = 1 << 24
REPS = 8
MAX_RESULTS = 1000


def run(tag, theta, phi_wk, **kw):
    rng = np.random.default_rng(0)
    d_d = jnp.asarray(rng.integers(0, N_DOCS, N_EVENTS).astype(np.int32))
    w_d = jnp.asarray(rng.integers(0, N_VOCAB, N_EVENTS).astype(np.int32))
    theta_d = jnp.asarray(theta)
    phi_d = jnp.asarray(phi_wk)
    m_d = jnp.ones(N_EVENTS, jnp.float32)

    @jax.jit
    def bench(theta, phi, d, w, m):
        def one_pass(carry, i):
            best_s, best_i = carry
            di = jax.lax.rem(d + i, jnp.int32(N_DOCS))
            wi = jax.lax.rem(w + i, jnp.int32(N_VOCAB))
            out = top_suspicious(theta, phi, di, wi, m, tol=1.0,
                                 max_results=MAX_RESULTS, **kw)
            cat_s = jnp.concatenate([best_s, out.scores])
            cat_i = jnp.concatenate([best_i, out.indices])
            neg, pos = jax.lax.top_k(-cat_s, MAX_RESULTS)
            return (-neg, cat_i[pos]), None

        init = (jnp.full((MAX_RESULTS,), jnp.inf, jnp.float32),
                jnp.full((MAX_RESULTS,), -1, jnp.int32))
        (scores, idx), _ = jax.lax.scan(
            one_pass, init, jnp.arange(REPS, dtype=jnp.int32))
        return scores, idx

    t0 = time.perf_counter()
    np.asarray(bench(theta_d, phi_d, d_d, w_d, m_d)[0])
    tc = time.perf_counter() - t0
    t0 = time.perf_counter()
    scores, _ = bench(theta_d, phi_d, d_d, w_d, m_d)
    sh = np.asarray(scores)
    dt = time.perf_counter() - t0
    assert np.isfinite(sh).all()
    print(f"{tag:52s} {REPS*N_EVENTS/dt/1e6:8.1f} Mev/s  wall={dt:6.3f}s"
          f"  compile={tc:5.1f}s", flush=True)
    return sh


rng = np.random.default_rng(0)
diffuse_t = rng.dirichlet(np.full(K, 0.5), size=N_DOCS).astype(np.float32)
diffuse_p = rng.dirichlet(np.full(K, 0.5), size=N_VOCAB).astype(np.float32)
peaked_t = rng.dirichlet(np.full(K, 0.05), size=N_DOCS).astype(np.float32)
peaked_p = rng.dirichlet(np.full(K, 0.05), size=N_VOCAB).astype(np.float32)

a = run("uniform diffuse, default (subscan fused)", diffuse_t, diffuse_p)
b = run("uniform diffuse, chunk=1<<22", diffuse_t, diffuse_p, chunk=1 << 22)
c = run("peaked (fitted-like), default", peaked_t, peaked_p)

# Round-3 levers (both EXACT unless noted; see scoring.py docstrings):
# two-phase candidate-buffer merge, bf16 tables-at-rest, and the combo.
d = run("uniform, merge_buffer=128", diffuse_t, diffuse_p,
        merge_buffer=128)
e = run("uniform, merge_buffer=128, chunk=1<<22", diffuse_t, diffuse_p,
        merge_buffer=128, chunk=1 << 22)
f = run("uniform, bf16 tables (APPROX at bf16 rounding)", diffuse_t,
        diffuse_p, table_dtype="bfloat16")
g = run("uniform, bf16 + merge_buffer=128", diffuse_t, diffuse_p,
        table_dtype="bfloat16", merge_buffer=128)
np.testing.assert_array_equal(a, d)   # exactness holds on-chip too
np.testing.assert_array_equal(b, e)
