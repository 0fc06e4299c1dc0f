"""Hypothesis property tests for the sparse arm's proposal tables
(ISSUE 6 satellite): alias/F+-tree-style table draws must match exact
categorical probabilities, and the MH correction must recover the
exact blocked conditional. Skipped (like test_properties.py) where
hypothesis is absent; seeded sweeps of the same invariants run
unconditionally in tests/test_sparse_gibbs.py.
"""

import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings  # noqa: E402
from hypothesis import strategies as st  # noqa: E402

from onix.models.lda_gibbs import (build_sparse_tables,  # noqa: E402
                                   cdf_lower_bound,
                                   make_sparse_block_step)

settings.register_profile("sparse_ci", max_examples=40, deadline=None)
settings.load_profile("sparse_ci")


@given(st.lists(st.floats(1e-4, 1e3, allow_nan=False), min_size=1,
                max_size=24),
       st.integers(0, 2 ** 31 - 1))
def test_cdf_lower_bound_matches_searchsorted(weights, seed):
    """The F+-tree-style bisection agrees with np.searchsorted
    lower_bound on arbitrary CDFs and draw points."""
    import jax.numpy as jnp
    w = np.asarray(weights, np.float32)
    cdf = np.cumsum(w)
    k = len(w)
    rng = np.random.default_rng(seed)
    t = (rng.random(64) * cdf[-1]).astype(np.float32)
    got = np.asarray(cdf_lower_bound(jnp.asarray(cdf),
                                     jnp.zeros(64, jnp.int32),
                                     jnp.asarray(t), k))
    want = np.searchsorted(cdf, t, side="left")
    np.testing.assert_array_equal(got, want)


@settings(max_examples=15, deadline=None)
@given(st.lists(st.floats(1e-3, 100.0, allow_nan=False), min_size=2,
                max_size=16))
def test_cdf_draws_match_categorical_probabilities(weights):
    """Stratified draws through the table reproduce the exact
    categorical distribution to within one grid cell per topic."""
    import jax.numpy as jnp
    w = np.asarray(weights, np.float64)
    cdf = np.cumsum(w).astype(np.float32)
    k = len(w)
    n = 4096
    t = ((np.arange(n) + 0.5) / n * cdf[-1]).astype(np.float32)
    idx = np.asarray(cdf_lower_bound(jnp.asarray(cdf),
                                     jnp.zeros(n, jnp.int32),
                                     jnp.asarray(t), k))
    idx = np.minimum(idx, k - 1)
    freq = np.bincount(idx, minlength=k) / n
    p = w / w.sum()
    assert np.abs(freq - p).max() <= 2.0 / n + 1e-3


@settings(max_examples=5, deadline=None)
@given(st.integers(0, 2 ** 16))
def test_mh_corrected_draws_within_tolerance(seed):
    """Random count tables: a long MH proposal chain on one token
    converges to the exact blocked conditional within sampling
    tolerance — the 'MH-corrected' half of the property."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    K, V, D = 6, 10, 4
    n_dk = rng.integers(0, 8, (D, K)).astype(np.int32)
    n_wk = rng.integers(0, 5, (V, K)).astype(np.int32)
    alpha, eta = 0.4, 0.05
    v_eta = V * eta
    d0 = int(rng.integers(0, D))
    w0 = int(rng.integers(0, V))
    z0 = int(rng.integers(0, K))
    # The token's own assignment is counted in every table — a state
    # without it is not a sampler state (and its exclusion below would
    # go negative).
    n_dk[d0, z0] = max(n_dk[d0, z0], 1)
    n_wk[w0, z0] = max(n_wk[w0, z0], 1)
    n_dk, n_wk = jnp.asarray(n_dk), jnp.asarray(n_wk)
    n_k = n_wk.sum(axis=0)
    nd = np.asarray(n_dk)[d0].astype(np.float64)
    nw = np.asarray(n_wk)[w0].astype(np.float64)
    nk = np.asarray(n_k).astype(np.float64)
    e = np.zeros(K)
    e[z0] = 1
    p = ((nd - e + alpha) * np.maximum(nw - e + eta, 1e-10)
         / (nk - e + v_eta))
    p /= p.sum()
    tables = build_sparse_tables(n_dk, n_wk, n_k, eta=eta, v_eta=v_eta,
                                 n_active=2)
    step = make_sparse_block_step(alpha=alpha, eta=eta, v_eta=v_eta,
                                  k_topics=K, n_mh=48, tables=tables)

    @jax.jit
    def draw(key):
        carry = (n_dk, n_wk, n_k, key)
        xs = (jnp.full((1,), d0, jnp.int32),
              jnp.full((1,), w0, jnp.int32),
              jnp.ones((1,), jnp.float32),
              jnp.full((1,), z0, jnp.int32))
        _, z = step(carry, xs)
        return z[0]

    keys = jax.random.split(jax.random.PRNGKey(seed), 8000)
    zs = np.asarray(jax.vmap(draw)(keys))
    freq = np.bincount(zs, minlength=K) / len(zs)
    assert np.abs(freq - p).max() < 0.03, (freq, p)
