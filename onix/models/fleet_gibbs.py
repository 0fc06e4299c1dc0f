"""Fleet-batched warm refit: every tenant's daily Gibbs refit as ONE
vmapped program per pow2 shape class (r20 tentpole; ROADMAP item 3).

The r12 bank already *serves* thousands of tenants per dispatch; this
module gives the daily loop the matching FIT path. The chains-vmap axis
of `make_sweep_kernel` (lda_gibbs.init_chains: independent lanes, one
batched program) is extended to a TENANT axis:

* **Shape classes** — tenants are grouped by pow2-padded
  (n_docs, n_vocab, n_tokens) through `compaction.pow2_bucket`, the
  model-bank padding discipline, so a thousand-tenant fleet compiles a
  handful of programs instead of a thousand. Padding rides the
  engine's existing sentinel contract: pad tokens carry mask 0 and
  z == K, whose one-hot is a zero row, so padded mass never enters a
  count table (`padding_stats` accounts the waste).

* **One fused program per class** — host-drawn per-tenant z init
  (warm: the φ̂-as-prior CDF draw of the r19 daily chain; cold:
  uniform), exact blockwise count build, the dismissal count nudge,
  S sweeps with burn-in-gated posterior accumulation, posterior
  estimates, and per-tenant boundary log-likelihoods, all inside one
  `jax.vmap` of the ONE shared sweep kernel. Tenant lanes are
  mathematically independent — a lane's results depend only on its own
  inputs and PRNG stream (`fold_in(fold_in(key(seed), uid), day)` on a
  STABLE roster uid), which is what makes per-tenant quarantine
  surgical: dropping or rolling back one tenant cannot perturb any
  other lane's bits.

* **Dismissal count nudge** — the ×DUPFACTOR corpus rebuild of the
  reference's noise-filter loop re-synthesizes and re-tokenizes the
  corpus per dismissal weight, which cannot amortize across a fleet.
  `nudge_counts` folds an analyst dismissal (doc, word, weight)
  directly into the stacked count tables before the refit sweep: one
  collapsed-Gibbs draw k̂ ~ p(k|d,w) from the current counts, then an
  int32 scatter of the weight into n_dk/n_wk/n_k — frozen pseudo-mass
  in the Streaming Gibbs style of arXiv:1601.01142 (the sweeps never
  resample it, exactly like the ×dupfactor tokens the reference never
  scores). The dismissed pair GAINS probability mass, the r13
  OnlineUpdater direction, so it leaves the suspicious bottom-k.

* **Sparse-form compatible** — the kernel keeps its sampler-form gate,
  so a large-K fleet runs the O(K_active) partially-collapsed sampler
  of arXiv:1506.03784 per lane unchanged.

The tenant axis shards over the dp mesh through
`parallel/fleet_shard.py` (lane-parallel, collective-free), and the
`pipelines/fleet.py` supervisor owns the per-tenant lifecycle (ledger
shards, drift gates, lineage, quarantine).
"""

from __future__ import annotations

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np

from onix.config import LDAConfig
from onix.models.compaction import pow2_bucket
from onix.models.lda_gibbs import (_one_hot, log_likelihood,
                                   make_sweep_kernel)

#: pow2 floors for the three padded dims — documents and vocab rows pad
#: to at least 8 (the compaction floor), token streams to at least one
#: SIMD-friendly block.
DOC_FLOOR = 8
VOCAB_FLOOR = 8
TOKEN_FLOOR = 64

#: Token-block width cap inside a lane: classes at or below the cap run
#: one block (n_blocks == 1); bigger classes split pow2-evenly so the
#: kernel's blockwise scan bounds its [B, K] temporaries exactly like
#: the single-tenant engines.
BLOCK_CAP = 1 << 16


# ---------------------------------------------------------------------------
# Inputs: one tenant-day, host-side.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class TenantDay:
    """One tenant's refit inputs for one day (host arrays).

    `uid` is the tenant's STABLE roster integer — the PRNG lane
    identity. It must survive quarantines and roster churn unchanged
    (never an enumeration index of today's batch), so a tenant's chain
    is reproducible regardless of which other tenants fit beside it.
    """

    name: str
    uid: int
    docs: np.ndarray                    # int32 [N] token -> doc id
    words: np.ndarray                   # int32 [N] token -> vocab id
    n_docs: int
    n_vocab: int
    init_phi: np.ndarray | None = None  # [n_vocab, K] warm prior (today's vocab)
    fb_docs: np.ndarray | None = None   # int32 [F] dismissal doc ids
    fb_words: np.ndarray | None = None  # int32 [F] dismissal word ids
    fb_weights: np.ndarray | None = None  # int32 [F] nudge weights

    @property
    def n_tokens(self) -> int:
        return int(len(self.docs))

    @property
    def n_feedback(self) -> int:
        return 0 if self.fb_docs is None else int(len(self.fb_docs))


def class_key(t: TenantDay) -> tuple[int, int, int]:
    """The tenant-day's pow2 shape class: (D_pad, V_pad, N_pad)."""
    return (pow2_bucket(t.n_docs, DOC_FLOOR),
            pow2_bucket(t.n_vocab, VOCAB_FLOOR),
            pow2_bucket(t.n_tokens, TOKEN_FLOOR))


def _block_shape(n_pad: int) -> tuple[int, int]:
    """(n_blocks, block_size) for a pow2-padded token stream."""
    b = min(n_pad, BLOCK_CAP)
    return n_pad // b, b


def _z_init(t: TenantDay, k_topics: int, rng: np.random.Generator
            ) -> np.ndarray:
    """Host-side per-tenant z draw, deterministic in the rng: warm
    lanes draw z ~ p(k|w) ∝ init_phi[w] by inverse CDF (the
    sharded_gibbs.init_state warm recipe), cold lanes draw uniform."""
    n = t.n_tokens
    if t.init_phi is None:
        return rng.integers(0, k_topics, size=n).astype(np.int32)
    prior = np.asarray(t.init_phi, np.float64)
    if prior.shape != (t.n_vocab, k_topics):
        raise ValueError(
            f"tenant {t.name}: init_phi shape {prior.shape} != "
            f"({t.n_vocab}, {k_topics}) — map the prior into TODAY's "
            "vocabulary first (campaign.map_phi_prior)")
    p = np.maximum(prior[t.words], 1e-30)
    cdf = np.cumsum(p, axis=1)
    cdf /= cdf[:, -1:]
    u = rng.random((n, 1))
    z = (cdf < u).sum(axis=1).astype(np.int32)
    return np.minimum(z, k_topics - 1)


# ---------------------------------------------------------------------------
# Stacking: tenants -> shape classes of bank-style padded arrays.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ShapeClass:
    """One shape class's stacked, padded, device-ready arrays. The
    leading axis is the tenant lane (the vmap/sharding axis)."""

    key: tuple[int, int, int]           # (D_pad, V_pad, N_pad)
    tenants: list[TenantDay]
    docs: np.ndarray                    # int32 [T, n_blocks, B]
    words: np.ndarray                   # int32 [T, n_blocks, B]
    mask: np.ndarray                    # float32 [T, n_blocks, B]
    z0: np.ndarray                      # int32 [T, n_blocks, B]
    fb_docs: np.ndarray                 # int32 [T, F_pad]
    fb_words: np.ndarray                # int32 [T, F_pad]
    fb_weights: np.ndarray              # int32 [T, F_pad]
    keys: np.ndarray                    # uint32 [T, 2] per-lane PRNG keys

    @property
    def n_lanes(self) -> int:
        return len(self.tenants)

    @property
    def tokens_real(self) -> int:
        return sum(t.n_tokens for t in self.tenants)

    @property
    def tokens_padded(self) -> int:
        return int(self.mask.size)


def stack_tenants(tenants: list[TenantDay], *, k_topics: int, seed: int,
                  day: int) -> list[ShapeClass]:
    """Group tenant-days into pow2 shape classes and stack each class's
    arrays bank-style (classes sorted by key, lanes sorted by uid, so
    the stacking — and therefore every lane's bits — is a pure function
    of the tenant set, never of arrival order)."""
    groups: dict[tuple[int, int, int], list[TenantDay]] = {}
    for t in tenants:
        groups.setdefault(class_key(t), []).append(t)
    base = jax.random.fold_in(jax.random.PRNGKey(seed), np.uint32(day))
    out = []
    for key in sorted(groups):
        members = sorted(groups[key], key=lambda t: t.uid)
        d_pad, v_pad, n_pad = key
        n_blocks, bsz = _block_shape(n_pad)
        tn = len(members)
        docs = np.zeros((tn, n_pad), np.int32)
        words = np.zeros((tn, n_pad), np.int32)
        mask = np.zeros((tn, n_pad), np.float32)
        z0 = np.full((tn, n_pad), k_topics, np.int32)   # pad sentinel K
        f_pad = pow2_bucket(max((t.n_feedback for t in members),
                                default=0), 1) \
            if any(t.n_feedback for t in members) else 0
        fb_d = np.zeros((tn, f_pad), np.int32)
        fb_w = np.zeros((tn, f_pad), np.int32)
        fb_wt = np.zeros((tn, f_pad), np.int32)
        lane_keys = np.empty((tn, 2), np.uint32)
        for i, t in enumerate(members):
            n = t.n_tokens
            docs[i, :n] = t.docs
            words[i, :n] = t.words
            mask[i, :n] = 1.0
            rng = np.random.default_rng([abs(int(seed)), int(day),
                                         int(t.uid)])
            z0[i, :n] = _z_init(t, k_topics, rng)
            if t.n_feedback:
                f = t.n_feedback
                fb_d[i, :f] = t.fb_docs
                fb_w[i, :f] = t.fb_words
                fb_wt[i, :f] = t.fb_weights
            lane_keys[i] = np.asarray(jax.random.fold_in(
                base, np.uint32(t.uid)), np.uint32)
        shape3 = (tn, n_blocks, bsz)
        out.append(ShapeClass(
            key=key, tenants=members,
            docs=docs.reshape(shape3), words=words.reshape(shape3),
            mask=mask.reshape(shape3), z0=z0.reshape(shape3),
            fb_docs=fb_d, fb_words=fb_w, fb_weights=fb_wt,
            keys=lane_keys))
    return out


def padding_stats(classes: list[ShapeClass]) -> dict:
    """Shape-class padding waste accounting: how much of the stacked
    token/table volume is pow2 padding rather than real tenant mass."""
    real = sum(c.tokens_real for c in classes)
    padded = sum(c.tokens_padded for c in classes)
    return {
        "n_classes": len(classes),
        "n_tenants": sum(c.n_lanes for c in classes),
        "class_shapes": {str(c.key): c.n_lanes for c in classes},
        "tokens_real": int(real),
        "tokens_padded": int(padded),
        "token_pad_waste_frac": round(1.0 - real / max(padded, 1), 4),
    }


# ---------------------------------------------------------------------------
# The dismissal count nudge (arXiv:1601.01142 streaming recipe).
# ---------------------------------------------------------------------------


def nudge_counts(n_dk, n_wk, n_k, key, fb_docs, fb_words, fb_weights, *,
                 alpha: float, eta: float):
    """Fold dismissal rows into the count tables as frozen pseudo-mass.

    Each (d, w, weight) row draws ONE hard topic
    k̂ ~ p(k|d,w) ∝ (n_dk[d]+α)(n_wk[w]+η)/(n_k+Vη) from the current
    collapsed counts, then scatter-adds its integer weight at k̂ —
    int32-exact, so a crash-replayed nudge reproduces the same tables.
    Rows with weight 0 are no-ops (the padding contract). The sweeps
    that follow never resample this mass (it is attached to no z
    token): it acts as a per-pair prior shift that RAISES
    p(word | doc) for the dismissed pair, which is the r13
    dismiss-weight direction — benign traffic must gain probability
    until it stops looking anomalous."""
    v_eta = n_wk.shape[0] * eta
    logp = (jnp.log(n_dk[fb_docs].astype(jnp.float32) + alpha)
            + jnp.log(jnp.maximum(
                n_wk[fb_words].astype(jnp.float32) + eta, 1e-10))
            - jnp.log(n_k.astype(jnp.float32) + v_eta))
    k_hat = jax.random.categorical(key, logp, axis=-1).astype(jnp.int32)
    hot = _one_hot(k_hat, n_dk.shape[1]) * fb_weights[:, None]
    return (n_dk.at[fb_docs].add(hot),
            n_wk.at[fb_words].add(hot),
            n_k + hot.sum(axis=0, dtype=jnp.int32))


def nudge_digest(t: TenantDay) -> str | None:
    """sha256[:16] identity of a tenant-day's nudge rows — joins the
    model fingerprint/meta as the `nudge` extra (the warm_init
    discipline: semantics that bypass LDAConfig still refuse a
    mismatched resume)."""
    if not t.n_feedback:
        return None
    h = hashlib.sha256()
    for a in (t.fb_docs, t.fb_words, t.fb_weights):
        arr = np.ascontiguousarray(np.asarray(a, np.int64))
        h.update(str(arr.shape).encode())
        h.update(arr.tobytes())
    return h.hexdigest()[:16]


# ---------------------------------------------------------------------------
# The fused per-class refit program.
# ---------------------------------------------------------------------------


def _make_refit_body(cfg: LDAConfig, *, n_docs: int, n_vocab: int,
                     sampler_form: str | None,
                     sparse_active: int, sampler: str | None):
    """One tenant lane's refit, sweep kernel shared with every other
    engine: count build -> nudge -> S sweeps (burn-in-gated posterior
    accumulation) -> (θ̂, φ̂, boundary lls)."""
    alpha, eta, k = cfg.alpha, cfg.eta, cfg.n_topics
    n_sweeps, burn_in = cfg.n_sweeps, cfg.burn_in
    kernel = make_sweep_kernel(alpha=alpha, eta=eta, n_vocab=n_vocab,
                               k_topics=k, sampler_form=sampler_form,
                               sparse_active=sparse_active,
                               sampler=sampler)

    def count_block(carry, xs):
        n_dk, n_wk, n_k = carry
        d, w, zb = xs
        oh = _one_hot(zb, k)                    # padding (z==K) -> zero row
        return (n_dk.at[d].add(oh), n_wk.at[w].add(oh),
                n_k + oh.sum(axis=0, dtype=jnp.int32)), None

    def one_tenant(z, docs, words, mask, fb_d, fb_w, fb_wt, key):
        (n_dk, n_wk, n_k), _ = jax.lax.scan(
            count_block,
            (jnp.zeros((n_docs, k), jnp.int32),
             jnp.zeros((n_vocab, k), jnp.int32),
             jnp.zeros((k,), jnp.int32)),
            (docs, words, z))
        key, nkey = jax.random.split(key)
        n_dk, n_wk, n_k = nudge_counts(n_dk, n_wk, n_k, nkey,
                                       fb_d, fb_w, fb_wt,
                                       alpha=alpha, eta=eta)

        def estimates(ndk_f, nwk_f):
            theta = (ndk_f + alpha) / (ndk_f.sum(-1, keepdims=True)
                                       + k * alpha)
            phi = (nwk_f + eta) / (nwk_f.sum(0, keepdims=True)
                                   + n_vocab * eta)
            return theta, phi

        theta0, phi0 = estimates(n_dk.astype(jnp.float32),
                                 n_wk.astype(jnp.float32))
        ll0 = log_likelihood(theta0, phi0, docs, words, mask)

        def body(carry, i):
            z, n_dk, n_wk, n_k, key, acc_ndk, acc_nwk, n_acc = carry
            z, n_dk, n_wk, n_k, key = kernel(z, n_dk, n_wk, n_k, key,
                                             docs, words, mask)
            take = (i >= burn_in).astype(jnp.float32)
            return (z, n_dk, n_wk, n_k, key,
                    acc_ndk + take * n_dk.astype(jnp.float32),
                    acc_nwk + take * n_wk.astype(jnp.float32),
                    n_acc + take), None

        carry = (z, n_dk, n_wk, n_k, key,
                 jnp.zeros((n_docs, k), jnp.float32),
                 jnp.zeros((n_vocab, k), jnp.float32),
                 jnp.float32(0.0))
        (z, n_dk, n_wk, n_k, key, acc_ndk, acc_nwk, n_acc), _ = \
            jax.lax.scan(body, carry, jnp.arange(n_sweeps))
        use_acc = n_acc > 0
        denom = jnp.maximum(n_acc, 1.0)
        ndk_f = jnp.where(use_acc, acc_ndk / denom,
                          n_dk.astype(jnp.float32))
        nwk_f = jnp.where(use_acc, acc_nwk / denom,
                          n_wk.astype(jnp.float32))
        theta, phi = estimates(ndk_f, nwk_f)
        ll = log_likelihood(theta, phi, docs, words, mask)
        return theta, phi, ll0, ll

    return one_tenant


def make_fleet_refit(cfg: LDAConfig, *, n_docs: int, n_vocab: int,
                     sampler_form: str | None = None,
                     sparse_active: int = 0,
                     sampler: str | None = None):
    """The fused per-shape-class fleet program: `one_tenant` vmapped
    over the lane axis and jitted — T tenants' warm refits in ONE
    dispatch. Returns fn(z0, docs, words, mask, fb_d, fb_w, fb_wt,
    keys) -> (theta [T,D,K], phi_wk [T,V,K], ll0 [T], ll_final [T]);
    `keys` is the uint32 [T, 2] lane-key array from stack_tenants."""
    body = _make_refit_body(cfg, n_docs=n_docs, n_vocab=n_vocab,
                            sampler_form=sampler_form,
                            sparse_active=sparse_active, sampler=sampler)

    def fleet(z0, docs, words, mask, fb_d, fb_w, fb_wt, keys):
        return jax.vmap(body)(z0, docs, words, mask, fb_d, fb_w, fb_wt,
                              keys)
    return jax.jit(fleet)


def make_tenant_refit(cfg: LDAConfig, *, n_docs: int, n_vocab: int,
                      sampler_form: str | None = None,
                      sparse_active: int = 0,
                      sampler: str | None = None):
    """The SAME refit body without the tenant vmap — the sequential
    supervisor arm (one dispatch per tenant), and the per-lane parity
    reference (tests/test_fleet.py)."""
    body = _make_refit_body(cfg, n_docs=n_docs, n_vocab=n_vocab,
                            sampler_form=sampler_form,
                            sparse_active=sparse_active, sampler=sampler)

    def one(z0, docs, words, mask, fb_d, fb_w, fb_wt, key):
        return body(z0, docs, words, mask, fb_d, fb_w, fb_wt, key)
    return jax.jit(one)


def unstack_results(sc: ShapeClass, theta, phi_wk, ll0, ll_final) -> dict:
    """Per-tenant host views of a class program's stacked outputs, pow2
    padding stripped back to each tenant's true (D, V)."""
    theta = np.asarray(theta, np.float32)
    phi_wk = np.asarray(phi_wk, np.float32)
    ll0 = np.asarray(ll0, np.float32)
    ll_final = np.asarray(ll_final, np.float32)
    out = {}
    for i, t in enumerate(sc.tenants):
        out[t.name] = {
            "theta": theta[i, :t.n_docs],
            "phi_wk": phi_wk[i, :t.n_vocab],
            "ll_initial": float(ll0[i]),
            "ll_final": float(ll_final[i]),
        }
    return out
