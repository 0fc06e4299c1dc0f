"""Doc- and vocabulary-sharded collapsed Gibbs over a device mesh.

This is the TPU-native rendering of oni-lda-c's one true parallelism
(SURVEY.md §2.2): MPI ranks each own a shard of documents, run the local
sampler, and allreduce the K×V topic-word sufficient statistics every
iteration. Here:

- documents (and their tokens) are sharded over the **data axes** — a
  single-slice ``dp`` axis, or ``(dcn, dp)`` on a multislice mesh where
  the outer axis crosses slices over DCN (SURVEY.md §2.3);
- the vocabulary is optionally sharded over the ``mp`` axis (SURVEY.md
  §5.7 — the honest "tensor" axis of LDA, for K×V matrices that outgrow
  one chip's HBM): word w lives on mp shard ``w % mp`` with local row
  ``w // mp``, and each device holds only the tokens whose words fall in
  its chunk. Hashing words round-robin over chunks balances Zipf
  hotspots without a frequency-aware partitioner;
- each device sweeps its local token blocks against its local count
  replicas (stale w.r.t. other shards within a sweep — the same
  staleness the reference accepts between MPI reductions);
- at sweep end the count *deltas* are `psum`'d and folded in, replacing
  MPI_Reduce + MPI_Bcast with XLA collectives (BASELINE.json north star
  names this exact mapping): topic-word chunk deltas reduce over the
  data axes (ICI within a slice, DCN across), doc-topic deltas reduce
  over mp, and topic totals over both;
- `lda.merge_form = "async"` (r14) swaps the full-barrier fold for the
  AD-LDA-style bounded-staleness exchange (arxiv 0909.4603; quality
  argument arxiv 1601.01142): each shard's count view carries its OWN
  updates fresh while peers' psum'd deltas ride a τ-deep FIFO
  (`ring_push`) and fold in exactly `lda.merge_staleness` merge
  windows late — so the collective issued at window t no longer gates
  the sampling of windows t+1..t+τ and XLA overlaps it with compute
  instead of stalling the superstep at every barrier. All pending
  deltas flush at the fused-superstep boundary, so boundary counts
  (checkpoints, the boundary ll, the accumulators) are EXACT global
  counts in both forms, and τ=0 degenerates to a program whose count
  arithmetic is bit-identical to the synchronous fold (int32 adds are
  exact and commutative; asserted in tests/test_merge_async.py).

Equivalence: with one device this is bit-identical in distribution to
the single-device engine; tests assert count invariants and topic
recovery on a virtual 8-device CPU mesh (SURVEY.md §4.3) for dp-only,
dp×mp, and dcn×dp×mp meshes.
"""

from __future__ import annotations

import bisect
import contextvars
import logging
import threading
from concurrent.futures import Future
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from onix.config import LDAConfig
from onix.corpus import Corpus
from onix.models import lda_gibbs
from onix.parallel.mesh import MP_AXIS, data_axes_of, make_mesh
from onix.utils import telemetry
from onix.utils.obs import counters, device_scope


class ShardedCorpus(NamedTuple):
    """Host-prepared, shard-major corpus layout.

    Documents are partitioned into `n_data` balanced groups; each
    group's tokens are split over `n_mp` vocabulary chunks (bucket of
    token t = word % n_mp) and every (data, mp) bucket is padded to the
    same [n_blocks, block] shape. Word ids inside the buckets are LOCAL
    chunk rows (word // n_mp). `doc_map[p, i]` is the global doc id of
    data-shard p's local doc i (-1 padding).
    """

    doc_blocks: np.ndarray    # int32 [P, M, nb, B] local doc ids
    word_blocks: np.ndarray   # int32 [P, M, nb, B] local (chunk) word ids
    mask_blocks: np.ndarray   # float32 [P, M, nb, B]
    doc_map: np.ndarray       # int32 [P, Dl]
    n_docs_local: int         # Dl
    n_vocab: int              # global V
    n_vocab_local: int        # Vc = ceil(V / M)


class ShardPlan(NamedTuple):
    """What `shard_corpus` knows of its layout before it deals a token:
    every number the fit's programs are shaped by (the blocks are
    `[n_data, n_mp, nb, block]`), and those a checkpoint's fingerprint
    is made of."""

    n_data: int
    n_mp: int
    nb: int
    block: int
    n_docs_local: int
    n_vocab: int
    n_vocab_local: int
    n_tokens: int


def plan_of(sc: ShardedCorpus, n_tokens: int) -> ShardPlan:
    """The plan a finished layout was made to."""
    n_data, n_mp, nb, block = sc.doc_blocks.shape
    return ShardPlan(n_data, n_mp, nb, block, sc.n_docs_local,
                     sc.n_vocab, sc.n_vocab_local, n_tokens)


# A token of a bucket on its way into the blocks: local doc id and
# chunk word row as one 8-byte item, so one in-place shuffle deals both.
_TOKEN_PAIR = np.dtype([("d", np.int32), ("w", np.int32)])


def shard_corpus(corpus: Corpus, n_data: int, block_size: int,
                 seed: int = 0, n_mp: int = 1,
                 n_groups: int = 1, on_plan=None) -> ShardedCorpus:
    """Partition documents (greedy balance) over data shards and tokens
    over vocabulary chunks; lay out every bucket in blocked form.
    `n_groups` pads the block count to a multiple so the sweep can
    synchronize counts after every group (cfg.sync_splits).
    `on_plan(ShardPlan)` is called once the shapes are known, before
    the costly half (the takes and the shuffles): what depends on the
    shapes alone can start there (`ShardedGibbsLDA.fit` compiles)."""
    n_docs = corpus.n_docs
    # Snake round-robin over docs sorted by length (desc): near-optimal
    # load balance, fully vectorized — no per-document Python loop (the
    # partitioner must handle ~10^6 IP documents, SURVEY.md §7.3.4).
    # One shard takes every document whatever the order: no lengths.
    lengths = (corpus.doc_lengths() if n_data > 1
               else np.zeros(n_docs, np.int32))
    order = np.argsort(lengths, kind="stable")[::-1]
    pos = np.arange(n_docs)
    fwd = pos % n_data
    snake = np.where((pos // n_data) % 2 == 0, fwd, n_data - 1 - fwd)
    shard_of_doc = np.empty(n_docs, np.int32)
    shard_of_doc[order] = snake.astype(np.int32)

    # Local doc numbering per shard (rank within shard, by global doc id).
    sort_idx = np.argsort(shard_of_doc, kind="stable")
    counts = np.bincount(shard_of_doc, minlength=n_data)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    local_sorted = np.arange(n_docs) - np.repeat(starts, counts)
    local_of_doc = np.empty(n_docs, np.int32)
    local_of_doc[sort_idx] = local_sorted.astype(np.int32)
    d_local = int(counts.max()) if n_docs else 1
    doc_map = np.full((n_data, d_local), -1, np.int32)
    doc_map[shard_of_doc, local_of_doc] = np.arange(n_docs, dtype=np.int32)

    # Bucket = (doc's data shard, word % n_mp), padded to the largest.
    # Every token is touched once: a bucket's (local doc, chunk row)
    # pairs are packed side by side, shuffled in place (the same
    # Fisher-Yates draws, so the same order, as `d[perm], w[perm]` with
    # `perm = rng.permutation(n)`) and unpacked straight into the padded
    # blocks. Where the mesh's shape says an answer (one data shard: a
    # document's local id is its own; one chunk: a word's row is its id;
    # one bucket: every token is in it) the arithmetic is not done.
    rng = np.random.default_rng(seed)
    doc_ids, word_ids = corpus.doc_ids, corpus.word_ids
    tok_bucket = None                       # int32, like the ids
    if n_data > 1:
        tok_bucket = shard_of_doc[doc_ids]
        if n_mp > 1:
            tok_bucket *= np.int32(n_mp)
    if n_mp > 1:
        tok_chunk = word_ids % np.int32(n_mp)
        tok_bucket = tok_chunk if tok_bucket is None else (
            np.add(tok_bucket, tok_chunk, out=tok_bucket))
    if tok_bucket is None:
        bucket_counts = np.array([corpus.n_tokens])
    else:
        bucket_counts = np.bincount(tok_bucket, minlength=n_data * n_mp)
    max_tokens = int(bucket_counts.max()) if corpus.n_tokens else 1
    block = min(block_size, max(max_tokens, 1))
    nb = -(-max_tokens // block)
    nb = -(-nb // n_groups) * n_groups     # sync groups need equal splits
    padded_len = nb * block
    n_vocab_local = -(-corpus.n_vocab // n_mp)
    if on_plan is not None:
        on_plan(ShardPlan(n_data, n_mp, nb, block, d_local, corpus.n_vocab,
                          n_vocab_local, corpus.n_tokens))

    doc_blocks = np.zeros((n_data, n_mp, padded_len), np.int32)
    word_blocks = np.zeros((n_data, n_mp, padded_len), np.int32)
    mask_blocks = np.zeros((n_data, n_mp, padded_len), np.float32)
    by_bucket = None
    if tok_bucket is not None:
        # One stable partition of the tokens by bucket (numpy sorts keys
        # of a byte or two by radix: one pass), not a pass over every
        # token per bucket: a bucket's tokens are a run of `by_bucket`,
        # in the corpus's order, as `tok_bucket == q` picked them.
        by_bucket = np.argsort(
            tok_bucket.astype(np.min_scalar_type(n_data * n_mp - 1)),
            kind="stable")
        del tok_bucket
    start = 0
    for q, n in enumerate(bucket_counts.tolist()):
        p, m = divmod(q, n_mp)
        d, w = doc_ids, word_ids
        if by_bucket is not None:
            sel = by_bucket[start:start + n]
            d, w = d[sel], w[sel]
            start += n
        pairs = np.empty(n, _TOKEN_PAIR)
        pairs["d"] = d if n_data == 1 else local_of_doc[d]
        pairs["w"] = w if n_mp == 1 else w // np.int32(n_mp)
        rng.shuffle(pairs)
        doc_blocks[p, m, :n] = pairs["d"]
        word_blocks[p, m, :n] = pairs["w"]
        mask_blocks[p, m, :n] = 1.0
    return ShardedCorpus(
        doc_blocks=doc_blocks.reshape(n_data, n_mp, nb, block),
        word_blocks=word_blocks.reshape(n_data, n_mp, nb, block),
        mask_blocks=mask_blocks.reshape(n_data, n_mp, nb, block),
        doc_map=doc_map,
        n_docs_local=d_local,
        n_vocab=corpus.n_vocab,
        n_vocab_local=n_vocab_local,
    )


def bucket_tokens(sc: ShardedCorpus) -> np.ndarray:
    """Live tokens of every (data shard, chunk) bucket, int64 [P, M]. A
    bucket's live tokens come first (`shard_corpus`), so its count is
    where its mask falls to 0: a search, not a pass over the tokens."""
    p, m = sc.mask_blocks.shape[:2]
    flat = sc.mask_blocks.reshape(p, m, -1)
    return np.array([[bisect.bisect_left(flat[q, c], True,
                                         key=lambda slot: slot == 0)
                      for c in range(m)] for q in range(p)], np.int64)


def ring_push(ring, delta):
    """Bounded-staleness FIFO step for the async merge arm: returns
    (entry folding NOW, new ring). A peer delta pushed at merge window
    t is emitted at window t+τ where τ == ring.shape[0] — exactly τ
    windows late, NEVER later (the staleness bound is the ring length,
    a static property of the compiled program; the superstep flush
    folds whatever is still pending at the boundary, so a delta's
    realized lag is min(τ, windows to the boundary)). `ring is None`
    spells τ=0: the delta folds immediately, which is what makes the
    τ=0 arm's count arithmetic bit-identical to the synchronous fold.
    Pure function of arrays — unit-tested directly
    (tests/test_merge_async.py::test_ring_push_staleness_bound)."""
    if ring is None:                    # tau == 0: immediate fold
        return delta, None
    return ring[0], jnp.concatenate([ring[1:], delta[None]], axis=0)


def _ring_sum(ring):
    """Sum of a ring's pending entries (0 for the τ=0 spelling) — the
    flush term that turns a shard's stale view back into exact global
    counts: view + pending == N(0) + Σ all shards' deltas so far, at
    every merge-window boundary."""
    return 0 if ring is None else ring.sum(axis=0)


def chunked_to_global_nwk(nwk_chunks: np.ndarray, n_vocab: int) -> np.ndarray:
    """[M, Vc, K] chunked counts -> [V, K] global (w = local*M + chunk)."""
    m, vc, k = nwk_chunks.shape
    out = np.zeros((m * vc, k), nwk_chunks.dtype)
    for c in range(m):
        out[c::m] = nwk_chunks[c][: len(out[c::m])]
    return out[:n_vocab]


def put_global(a, mesh, spec) -> jax.Array:
    """Host array (identical on every process) -> device array under
    `spec` on `mesh`.

    Single-process this is a plain sharded device_put. On a process-
    spanning mesh (hostfabric) jax.device_put refuses arrays with
    non-addressable shards, so the global array is assembled from a
    callback that materializes only this process's addressable blocks —
    every process holds the same full host array (state init and corpus
    sharding are deterministic in cfg.seed), so the per-block slices
    agree across hosts by construction."""
    sharding = NamedSharding(mesh, spec)
    if jax.process_count() == 1:
        # Straight from the host into each chip's shard. Through
        # `jnp.asarray` first the whole array landed on the first chip
        # and was dealt out from there: at dp=4 that chip peaked at
        # 14.4 GB where it holds 2.5 (PERF.md section 6, PR 33).
        return jax.device_put(a, sharding)
    host = np.asarray(a)
    return jax.make_array_from_callback(host.shape, sharding,
                                        lambda idx: host[idx])


class ShardedGibbsState(NamedTuple):
    """Device-sharded sampler state with an UNSHARDED chain axis C.

    C > 1 gives the sharded engine the same restart-ensemble estimator
    the judged overlap bar rides on the single-device engine
    (docs/OVERLAP.md): each device vmaps C independent chains over its
    local tokens, so C chains cost ~one sweep of C× the tokens and the
    per-sweep psum reduces all chains' deltas in one collective. The
    chain axis sits BEHIND the device axes so the PartitionSpecs are
    identical for every C (chains are replicated work, not sharded)."""

    z: jax.Array         # int32 [P, M, C, nb, B] (K sentinel = padding)
    n_dk: jax.Array      # int32 [P, C, Dl, K] doc-topic, data-sharded
    n_wk: jax.Array      # int32 [M, C, Vc, K] topic-word chunks, mp-sharded
    n_k: jax.Array       # int32 [C, K] replicated
    keys: jax.Array      # [P, M, C, 2] uint32 per-device/chain PRNG keys
    acc_ndk: jax.Array   # float32 [P, C, Dl, K]
    acc_nwk: jax.Array   # float32 [M, C, Vc, K]
    n_acc: jax.Array     # int32 []


class ProgramsAhead:
    """The executables a fit builds ahead of their first call.

    `build(key)` hands back the executable of one program. It is called
    for every key in turn, in the order the fit will ask for them, on a
    thread of this object's own, in a copy of the caller's context (so
    the thread's span, `fit.precompile`, and the `jit.compile` spans
    under it are the caller's trace's). `call` then runs a program
    through its executable where that is there and takes the arguments
    it is given, and through the jitted function otherwise: a program
    nobody planned, a build that raised (the jitted call meets the
    fault again and reports it where it always did), arguments of
    another shape or sharding than planned (an executable refuses them
    before it runs or donates anything). `fit.precompile.hit` and
    `.miss` count the two."""

    def __init__(self, keys, build):
        self._futures = {key: Future() for key in keys}
        self._taken: dict = {}
        self._closed = False
        self._thread = threading.Thread(
            target=contextvars.copy_context().run,
            args=(self._build_all, build), name="onix-fit-precompile")
        self._thread.start()

    def _build_all(self, build) -> None:
        """The thread: only it settles the futures, and it leaves none
        unsettled for a caller to wait on."""
        futures = self._futures
        try:
            with telemetry.TRACER.span(
                    "fit.precompile", count=len(futures),
                    programs=[str(key) for key in futures]) as span:
                for key, fut in futures.items():
                    if self._closed:
                        break
                    try:
                        fut.set_result(build(key))
                    except Exception as e:
                        # The thread's boundary: the fit goes on through
                        # the jitted function, which raises this again,
                        # on the caller's thread, if it was the
                        # program's fault.
                        logging.getLogger(__name__).warning(
                            "fit.precompile: %s not built ahead: %r", key, e)
                        if span is not None:
                            span.attrs.setdefault("failed", []).append(
                                str(key))
                        fut.set_exception(e)
        finally:
            for fut in futures.values():
                if not fut.done():
                    fut.set_exception(RuntimeError("not built ahead"))

    def _take(self, key, program: str):
        """The executable of `key`, waited for under `fit.compile_wait`
        the first time it is asked for; None where there is none."""
        if key not in self._taken:
            exe, fut = None, self._futures.get(key)
            if fut is not None:
                with telemetry.TRACER.span("fit.compile_wait",
                                           program=program, key=str(key),
                                           ready=fut.done()):
                    if fut.exception() is None:
                        exe = fut.result()
            self._taken[key] = exe
        return self._taken[key]

    def call(self, key, jitted, *args, **static):
        exe = self._take(key, jitted.__name__)
        if exe is not None:
            try:
                out = exe(*args)
            except (TypeError, ValueError):
                # Not the arguments it was built for.
                self._taken[key] = None
            else:
                counters.inc("fit.precompile.hit")
                return out
        counters.inc("fit.precompile.miss")
        return jitted(*args, **static)

    def close(self) -> None:
        """Nothing of it outlives the fit: programs not begun are
        dropped, the one being compiled is waited for."""
        self._closed = True
        self._thread.join()


class _FitPlan(NamedTuple):
    """What `ShardedGibbsLDA.fit` decides once the layout's plan is
    known (`plan_fit` there)."""

    plan: ShardPlan
    fingerprint: str
    checkpoint_dir: object      # <checkpoint_dir>/<fingerprint>, or None
    saved: object               # the checkpoint to restore, or None
    start: int
    segments: list
    ahead: ProgramsAhead


def _local_sweep(z, n_dk, n_wk, n_k, key, docs, words, mask, *,
                 alpha, eta, n_vocab, k_topics,
                 sampler_form=None, sparse_active=0, sparse_mh=2):
    """The per-device sweep body — the single-device engine's sweep
    kernel, shared via lda_gibbs.make_sweep_kernel so the math (and
    the sampler-form gate) stays identical. `n_wk` may be a vocabulary
    CHUNK with local word ids; the denominator terms (n_k + V*eta)
    stay global. The n_wk count-update form (scatter | matmul) gates
    on the LOCAL chunk width — under mp sharding each chunk's
    collision density is what matters. The sparse sampler arm
    is chunk-clean too: its stale proposal tables are built from this
    device's local rows (doc-sharded n_dk, the local n_wk chunk) and
    every per-token gather is a local-row gather, so mp sharding needs
    no global rebuild."""
    kernel = lda_gibbs.make_sweep_kernel(
        alpha=alpha, eta=eta, n_vocab=n_vocab, k_topics=k_topics,
        sampler_form=sampler_form,
        sparse_active=sparse_active, sparse_mh=sparse_mh)
    return kernel(z, n_dk, n_wk, n_k, key, docs, words, mask)


class ShardedGibbsLDA:
    """Multi-chip Gibbs driver: docs on the data axes, vocabulary chunks
    on mp, psum of topic sufficient statistics.

    Covers BASELINE.json configs[3]: "1B-row synthetic netflow, 20
    topics, multi-chip doc-sharded Gibbs"; the mp axis covers the
    K×V-beyond-HBM regime of SURVEY.md §5.7, and a (dcn, dp[, mp]) mesh
    spans multiple slices (§2.3).
    """

    def __init__(self, config: LDAConfig, n_vocab: int, mesh=None):
        config.validate()
        self.config = config
        self.n_vocab = n_vocab
        self.mesh = mesh if mesh is not None else make_mesh()
        self.data_axes = data_axes_of(self.mesh)
        if not self.data_axes:
            raise ValueError(
                f"mesh axes {tuple(self.mesh.shape)} carry no data axis")
        self.n_data = int(np.prod([self.mesh.shape[a]
                                   for a in self.data_axes]))
        self.n_mp = int(self.mesh.shape.get(MP_AXIS, 1))
        k = config.n_topics
        D = self.data_axes
        M = MP_AXIS if MP_AXIS in self.mesh.shape else None
        both = D + ((M,) if M else ())

        S = max(1, int(config.sync_splits))
        burn = config.burn_in
        # Sampler form: resolved ONCE at construction via the shared
        # lda_gibbs.resolve_sampler (config, then ONIX_SAMPLER_FORM,
        # then the measured gate) — the resolved value feeds every
        # compiled sweep AND the checkpoint fingerprint, and sharing
        # the resolver with GibbsLDA is what keeps the two engines
        # from ever resolving different arms for the same config. The sparse arm is a different chain, so a
        # resume across an arm change must be refused, not silently
        # continued.
        self.sampler_form, self.sparse_active, sampler_kw = \
            lda_gibbs.resolve_sampler(config, k_topics=k)
        # Count-merge form (r14): resolved once at construction like
        # the sampler form — the value feeds the compiled superstep AND
        # the checkpoint fingerprint (merge_fingerprint), so the
        # program and the resume identity can never disagree. τ is
        # pinned to 0 under sync so the fingerprint entry (async only)
        # is a function of what actually runs.
        self.merge_form = config.merge_form
        use_async = self.merge_form == "async"
        tau = int(config.merge_staleness) if use_async else 0
        self.merge_tau = tau
        # The async merge arm's count views are genuinely device-
        # VARYING mid-superstep (own deltas fresh, peers' stale) and only
        # the boundary flush restores replication-in-value, so shard_map's
        # static replication check has nothing true to check there: the
        # async arm drops it, the sync arm keeps it.
        sweep_smap_kw = {"check_vma": False} if use_async else {}

        def _group_sweep(z_g, n_dk_l, n_wk_l, n_k_l, key_c,
                         d_g, w_g, m_g):
            """ONE full sweep of this device's tokens: scan the S sync
            groups, psum-folding count deltas after each (S=1 is the
            reference's MPI cadence). Shapes are shard-LOCAL with the
            leading shard axes already dropped; z_g is the grouped
            layout [S, C, nb/S, B]. Shared by the per-sweep program and
            the fused superstep so the math can never diverge."""
            def group_step(carry, xs):
                ndk_r, nwk_r, nk_r, key_c = carry
                dg, wg, mg, zg = xs
                # Replicated bases become device-varying once each
                # device starts updating them locally — mark them
                # per group; the psum fold below restores the
                # replication the carry (and out_specs) demand.
                nwk_v = jax.lax.pcast(nwk_r, D, to="varying")
                ndk_v = (jax.lax.pcast(ndk_r, M, to="varying")
                         if M else ndk_r)
                nk_v = jax.lax.pcast(nk_r, both, to="varying")

                def one_chain(zc, ndkc, nwkc, nkc, keyc):
                    return _local_sweep(
                        zc, ndkc, nwkc, nkc, keyc, dg, wg, mg,
                        alpha=config.alpha, eta=config.eta,
                        n_vocab=n_vocab, k_topics=k, **sampler_kw)

                z_new, ndk_new, nwk_new, nk_new, key_new = \
                    jax.vmap(one_chain)(zg, ndk_v, nwk_v, nk_v, key_c)
                # The MPI_Reduce+Bcast of the reference, as psums:
                # chunk deltas over the data axes (ICI, then DCN),
                # doc-topic deltas over mp, topic totals over both.
                # All chains' deltas ride ONE collective (leading C
                # axis reduces elementwise). Under the scope: the
                # all-reduces, and in them the wait for the slowest chip.
                with device_scope("onix.sweep.merge"):
                    d_wk = jax.lax.psum(nwk_new - nwk_v, D)
                    d_dk = (jax.lax.psum(ndk_new - ndk_v, M)
                            if M else ndk_new - ndk_v)
                    d_k = jax.lax.psum(nk_new - nk_v, both)
                    return (ndk_r + d_dk, nwk_r + d_wk, nk_r + d_k,
                            key_new), z_new

            (ndk_f, nwk_f, nk_f, key_f), z_out = jax.lax.scan(
                group_step, (n_dk_l, n_wk_l, n_k_l, key_c),
                (d_g, w_g, m_g, z_g))
            return z_out, ndk_f, nwk_f, nk_f, key_f

        def _zero_rings(n_dk_l, n_wk_l, n_k_l):
            """Fresh pending-delta FIFOs at superstep entry: τ slots of
            zeros per collective-reduced table — peers' first τ windows
            of deltas arrive late by construction. n_dk only rides a
            ring when mp shards exist (without mp every shard owns its
            docs' rows outright: no collective, no staleness)."""
            if tau == 0:
                return (None, None, None)
            mk = lambda a: jnp.zeros((tau,) + a.shape, a.dtype)
            return (mk(n_dk_l) if M else None, mk(n_wk_l), mk(n_k_l))

        def _group_sweep_async(z_g, n_dk_l, n_wk_l, n_k_l, key_c,
                               d_g, w_g, m_g, rings):
            """The bounded-staleness rendering of _group_sweep: the
            count carry is each shard's VIEW (own updates fresh; peer
            deltas folded from the ring exactly τ windows late), not
            the replicated fold. The psum still issues every window —
            its RESULT just stops gating the next window's sampling
            for τ>0, which is the stall the async arm removes. At τ=0
            the ring is the identity and the arithmetic
            (view + own + (psum − own) == base + psum) is bit-identical
            to _group_sweep's fold in exact int32. View + pending ==
            exact global counts at every window boundary — the
            invariant the superstep flush and the accumulator fold
            lean on."""
            def group_step(carry, xs):
                ndk_v, nwk_v, nk_v, key_c, rg = carry
                r_dk, r_wk, r_k = rg
                dg, wg, mg, zg = xs

                def one_chain(zc, ndkc, nwkc, nkc, keyc):
                    return _local_sweep(
                        zc, ndkc, nwkc, nkc, keyc, dg, wg, mg,
                        alpha=config.alpha, eta=config.eta,
                        n_vocab=n_vocab, k_topics=k, **sampler_kw)

                z_new, ndk_new, nwk_new, nk_new, key_new = \
                    jax.vmap(one_chain)(zg, ndk_v, nwk_v, nk_v, key_c)
                # Peers' deltas = the collective total minus our own;
                # own deltas stay in the view immediately (the AD-LDA
                # discipline — a shard is never stale w.r.t. itself).
                with device_scope("onix.sweep.merge"):
                    own_wk = nwk_new - nwk_v
                    peer_wk = jax.lax.psum(own_wk, D) - own_wk
                    own_k = nk_new - nk_v
                    peer_k = jax.lax.psum(own_k, both) - own_k
                    fold_wk, r_wk = ring_push(r_wk, peer_wk)
                    fold_k, r_k = ring_push(r_k, peer_k)
                    if M:
                        own_dk = ndk_new - ndk_v
                        peer_dk = jax.lax.psum(own_dk, M) - own_dk
                        fold_dk, r_dk = ring_push(r_dk, peer_dk)
                        ndk_new = ndk_new + fold_dk
                    return (ndk_new, nwk_new + fold_wk, nk_new + fold_k,
                            key_new, (r_dk, r_wk, r_k)), z_new

            (ndk_f, nwk_f, nk_f, key_f, rings_f), z_out = jax.lax.scan(
                group_step, (n_dk_l, n_wk_l, n_k_l, key_c, rings),
                (d_g, w_g, m_g, z_g))
            return z_out, ndk_f, nwk_f, nk_f, key_f, rings_f

        def _grouped(d, w, m, z):
            """Shard-local token blocks + z in sync-group layout."""
            C = z.shape[2]
            nb, B = d.shape[2], d.shape[3]
            assert nb % S == 0, (
                f"block count {nb} not divisible by "
                f"sync_splits={S}: the corpus was laid out without "
                "this engine's prepare() (shard_corpus needs "
                "n_groups=sync_splits)")
            return (d[0, 0].reshape(S, nb // S, B),
                    w[0, 0].reshape(S, nb // S, B),
                    m[0, 0].reshape(S, nb // S, B),
                    z[0, 0].reshape(C, S, nb // S, B).swapaxes(0, 1),
                    C, nb, B)

        def _chain_ll_local(ndk_f, nwk_f, nk_v, d0, w0, m0, zero):
            """Per-chain (sum log p, token sum) over this shard's tokens
            from explicit local counts — the predictive-ll math shared
            by the standalone ll program, the superstep boundary ll, and
            the dp=1 fast path (which passes plain f32 zeros)."""
            def one_chain(ndkc, nwkc, nkc):
                ndk = ndkc.astype(jnp.float32)
                theta = ((ndk + config.alpha)
                         / (ndk.sum(-1, keepdims=True)
                            + k * config.alpha))
                nwk = nwkc.astype(jnp.float32)
                phi = ((nwk + config.eta)
                       / (nkc.astype(jnp.float32)
                          + n_vocab * config.eta))

                def block(carry, xs):
                    sm, t = carry
                    db, wb, mb = xs
                    p = jnp.sum(theta[db] * phi[wb], axis=-1)
                    p = jnp.maximum(p, 1e-30)
                    return (sm + jnp.sum(mb * jnp.log(p)),
                            t + jnp.sum(mb)), None

                (sm, t), _ = jax.lax.scan(block, (zero, zero),
                                          (d0, w0, m0))
                return sm, t

            with device_scope("onix.sweep.loglik"):
                return jax.vmap(one_chain)(ndk_f, nwk_f, nk_v)

        def _ll_merge(sm, t):
            """The chips' log-likelihood sums as one: a merge across
            chips like the sweep's, booked where that is."""
            with device_scope("onix.sweep.merge"):
                return jax.lax.psum(sm, both), jax.lax.psum(t, both)

        mp_spec = (M,) if M else ()

        def sweep_fn(state: ShardedGibbsState, docs, words, mask,
                     accumulate: bool) -> ShardedGibbsState:
            def shard_fn(z, n_dk, n_wk, n_k, keys, d, w, m):
                # Leading shard axes of size (1, 1) inside shard_map;
                # the remaining leading axis is the chain axis C: the
                # SAME local token blocks, C independent sampler states,
                # batched by vmap into one program. Blocks split into S
                # sync groups (shard_corpus pads nb to a multiple): each
                # group sweeps against counts at most 1/S of a sweep
                # stale, psums its deltas, and folds them in before the
                # next group — S=1 is the reference's MPI cadence.
                d_g, w_g, m_g, z_g, C, nb, B = _grouped(d, w, m, z)
                z_out, ndk_f, nwk_f, nk_f, key_f = _group_sweep(
                    z_g, n_dk[0], n_wk[0], n_k, keys[0, 0],
                    d_g, w_g, m_g)
                z_full = z_out.swapaxes(0, 1).reshape(C, nb, B)
                return (z_full[None, None], ndk_f[None], nwk_f[None],
                        nk_f, key_f[None, None])

            z, n_dk, n_wk, n_k, keys = jax.shard_map(
                shard_fn, mesh=self.mesh,
                in_specs=(P(D, *mp_spec), P(D), P(*mp_spec), P(),
                          P(D, *mp_spec), P(D, *mp_spec), P(D, *mp_spec),
                          P(D, *mp_spec)),
                out_specs=(P(D, *mp_spec), P(D), P(*mp_spec), P(),
                           P(D, *mp_spec)),
                **sweep_smap_kw,
            )(state.z, state.n_dk, state.n_wk, state.n_k, state.keys,
              docs, words, mask)
            do_acc = jnp.float32(accumulate)
            return ShardedGibbsState(
                z=z, n_dk=n_dk, n_wk=n_wk, n_k=n_k, keys=keys,
                acc_ndk=state.acc_ndk + do_acc * n_dk.astype(jnp.float32),
                acc_nwk=state.acc_nwk + do_acc * n_wk.astype(jnp.float32),
                n_acc=state.n_acc + jnp.int32(accumulate),
            )

        def superstep_fn(state: ShardedGibbsState, docs, words, mask,
                         start, n_steps: int, with_initial_ll=False):
            """`n_steps` fused sweeps + the boundary predictive ll in
            ONE program with ONE shard_map: the sweep chain runs as a
            lax.scan INSIDE the shard region, the burn-in accumulate
            fold rides the scan carry (sweep start+i accumulates iff
            past burn_in, decided on device), and the final counts feed
            the psum-reduced ll before anything returns to the host —
            one dispatch and one sync per superstep instead of per
            sweep. `with_initial_ll` also evaluates ll on the INCOMING
            counts (fit's pre-sweep history point) inside the same
            program. Bit-identical to n_steps sweep_fn dispatches."""
            def shard_fn(z, n_dk, n_wk, n_k, keys, accd, accw, nacc,
                         d, w, m, start_s):
                d_g, w_g, m_g, z_g, C, nb, B = _grouped(d, w, m, z)
                zero = jax.lax.pcast(jnp.float32(0), both, to="varying")
                d0, w0, m0 = d[0, 0], w[0, 0], m[0, 0]
                if with_initial_ll:
                    nk0_v = jax.lax.pcast(n_k, both, to="varying")
                    sm0, t0 = _chain_ll_local(n_dk[0], n_wk[0], nk0_v,
                                              d0, w0, m0, zero)
                    sm0, t0 = _ll_merge(sm0, t0)

                def one_sweep(carry, i):
                    zg, ndk_r, nwk_r, nk_r, key_c, ad, aw, na = carry
                    zg, ndk_r, nwk_r, nk_r, key_c = _group_sweep(
                        zg, ndk_r, nwk_r, nk_r, key_c, d_g, w_g, m_g)
                    do = start_s + i >= burn
                    do_f = do.astype(jnp.float32)
                    ad = ad + do_f * ndk_r.astype(jnp.float32)
                    aw = aw + do_f * nwk_r.astype(jnp.float32)
                    na = na + do.astype(jnp.int32)
                    return (zg, ndk_r, nwk_r, nk_r, key_c,
                            ad, aw, na), None

                carry0 = (z_g, n_dk[0], n_wk[0], n_k, keys[0, 0],
                          accd[0], accw[0], nacc)
                (z_g2, ndk_f, nwk_f, nk_f, key_f, ad, aw, na), _ = \
                    jax.lax.scan(one_sweep, carry0,
                                 jnp.arange(n_steps, dtype=jnp.int32))
                nk_v = jax.lax.pcast(nk_f, both, to="varying")
                sm, t = _chain_ll_local(ndk_f, nwk_f, nk_v,
                                        d0, w0, m0, zero)
                sm, t = _ll_merge(sm, t)
                z_full = z_g2.swapaxes(0, 1).reshape(C, nb, B)
                outs = (z_full[None, None], ndk_f[None], nwk_f[None],
                        nk_f, key_f[None, None], ad[None], aw[None],
                        na, sm, t)
                return outs + ((sm0, t0) if with_initial_ll else ())

            out_specs = (P(D, *mp_spec), P(D), P(*mp_spec), P(),
                         P(D, *mp_spec), P(D), P(*mp_spec), P(),
                         P(), P())
            if with_initial_ll:
                out_specs = out_specs + (P(), P())
            outs = jax.shard_map(
                shard_fn, mesh=self.mesh,
                in_specs=(P(D, *mp_spec), P(D), P(*mp_spec), P(),
                          P(D, *mp_spec), P(D), P(*mp_spec), P(),
                          P(D, *mp_spec), P(D, *mp_spec),
                          P(D, *mp_spec), P()),
                out_specs=out_specs,
                **sweep_smap_kw,
            )(state.z, state.n_dk, state.n_wk, state.n_k, state.keys,
              state.acc_ndk, state.acc_nwk, state.n_acc,
              docs, words, mask, jnp.asarray(start, jnp.int32))
            z, n_dk, n_wk, n_k, keys, accd, accw, nacc, sm, t = outs[:10]
            new_state = ShardedGibbsState(
                z=z, n_dk=n_dk, n_wk=n_wk, n_k=n_k, keys=keys,
                acc_ndk=accd, acc_nwk=accw, n_acc=nacc)
            # Per-chain corpus mean ll, averaged over chains (the same
            # series ll_fn exposes).
            ll = (sm / jnp.maximum(t, 1.0)).mean()
            if with_initial_ll:
                sm0, t0 = outs[10:]
                return new_state, (sm0 / jnp.maximum(t0, 1.0)).mean(), ll
            return new_state, ll

        def superstep_async_fn(state: ShardedGibbsState, docs, words,
                               mask, start, n_steps: int,
                               with_initial_ll=False):
            """The bounded-staleness superstep (merge_form="async"):
            identical host contract to superstep_fn — same inputs, same
            outputs, same out_specs — with the sweep chain riding
            _group_sweep_async's stale views and the pending-delta
            rings FLUSHED before anything returns, so the state handed
            back (and checkpointed, and ll-evaluated, and accumulated)
            is exact replicated global counts. The accumulator fold at
            each sweep boundary adds view + pending — the exact counts
            at that boundary — so posterior means are computed from the
            same count semantics as the sync arm's. τ=0 compiles a
            genuinely different program (varying carry, deferred-fold
            structure) whose results are bit-identical to superstep_fn
            (tests/test_merge_async.py); τ>0 is a different chain with
            the same stationary target, held to the ll band + winner
            parity contract."""
            def shard_fn(z, n_dk, n_wk, n_k, keys, accd, accw, nacc,
                         d, w, m, start_s):
                d_g, w_g, m_g, z_g, C, nb, B = _grouped(d, w, m, z)
                zero = jnp.float32(0)
                d0, w0, m0 = d[0, 0], w[0, 0], m[0, 0]
                if with_initial_ll:
                    # Incoming counts are exact (superstep boundaries
                    # always flush), so the pre-sweep ll needs no
                    # staleness correction.
                    sm0, t0 = _chain_ll_local(n_dk[0], n_wk[0], n_k,
                                              d0, w0, m0, zero)
                    sm0, t0 = _ll_merge(sm0, t0)

                def one_sweep(carry, i):
                    (zg, ndk_r, nwk_r, nk_r, key_c, rings,
                     ad, aw, na) = carry
                    zg, ndk_r, nwk_r, nk_r, key_c, rings = \
                        _group_sweep_async(zg, ndk_r, nwk_r, nk_r,
                                           key_c, d_g, w_g, m_g, rings)
                    r_dk, r_wk, r_k = rings
                    do = start_s + i >= burn
                    do_f = do.astype(jnp.float32)
                    # Accumulate EXACT boundary counts (view + pending)
                    # so the posterior-mean estimator is arm-invariant
                    # in semantics AND replicated-in-value where the
                    # out_specs demand it (acc_ndk over mp, acc_nwk
                    # over the data axes).
                    ndk_x = ndk_r + _ring_sum(r_dk) if M else ndk_r
                    ad = ad + do_f * ndk_x.astype(jnp.float32)
                    aw = aw + do_f * ((nwk_r + _ring_sum(r_wk))
                                      .astype(jnp.float32))
                    na = na + do.astype(jnp.int32)
                    return (zg, ndk_r, nwk_r, nk_r, key_c, rings,
                            ad, aw, na), None

                carry0 = (z_g, n_dk[0], n_wk[0], n_k, keys[0, 0],
                          _zero_rings(n_dk[0], n_wk[0], n_k),
                          accd[0], accw[0], nacc)
                (z_g2, ndk_f, nwk_f, nk_f, key_f, rings_f,
                 ad, aw, na), _ = jax.lax.scan(
                    one_sweep, carry0,
                    jnp.arange(n_steps, dtype=jnp.int32))
                # The boundary FLUSH: fold every still-pending peer
                # delta, restoring exact replicated global counts —
                # what the host contract (and the ll below) reads.
                r_dk, r_wk, r_k = rings_f
                if M:
                    ndk_f = ndk_f + _ring_sum(r_dk)
                nwk_f = nwk_f + _ring_sum(r_wk)
                nk_f = nk_f + _ring_sum(r_k)
                sm, t = _chain_ll_local(ndk_f, nwk_f, nk_f,
                                        d0, w0, m0, zero)
                sm, t = _ll_merge(sm, t)
                z_full = z_g2.swapaxes(0, 1).reshape(C, nb, B)
                outs = (z_full[None, None], ndk_f[None], nwk_f[None],
                        nk_f, key_f[None, None], ad[None], aw[None],
                        na, sm, t)
                return outs + ((sm0, t0) if with_initial_ll else ())

            out_specs = (P(D, *mp_spec), P(D), P(*mp_spec), P(),
                         P(D, *mp_spec), P(D), P(*mp_spec), P(),
                         P(), P())
            if with_initial_ll:
                out_specs = out_specs + (P(), P())
            outs = jax.shard_map(
                shard_fn, mesh=self.mesh,
                in_specs=(P(D, *mp_spec), P(D), P(*mp_spec), P(),
                          P(D, *mp_spec), P(D), P(*mp_spec), P(),
                          P(D, *mp_spec), P(D, *mp_spec),
                          P(D, *mp_spec), P()),
                out_specs=out_specs,
                **sweep_smap_kw,
            )(state.z, state.n_dk, state.n_wk, state.n_k, state.keys,
              state.acc_ndk, state.acc_nwk, state.n_acc,
              docs, words, mask, jnp.asarray(start, jnp.int32))
            z, n_dk, n_wk, n_k, keys, accd, accw, nacc, sm, t = outs[:10]
            new_state = ShardedGibbsState(
                z=z, n_dk=n_dk, n_wk=n_wk, n_k=n_k, keys=keys,
                acc_ndk=accd, acc_nwk=accw, n_acc=nacc)
            ll = (sm / jnp.maximum(t, 1.0)).mean()
            if with_initial_ll:
                sm0, t0 = outs[10:]
                return new_state, (sm0 / jnp.maximum(t0, 1.0)).mean(), ll
            return new_state, ll

        def superstep_dp1_fn(state: ShardedGibbsState, docs, words, mask,
                             start, n_steps: int, with_initial_ll=False):
            """dp=1/mp=1 fast path: the identical superstep math with NO
            shard_map/psum wrapping — at one device every psum is an
            identity on integer deltas, so the collective wrapper buys
            nothing (its cost on the chip: not measured). Bit-identical
            to the shard_map path (asserted in
            tests/test_sharded_gibbs.py), including under
            sync_splits > 1, whose grouping is pure staleness
            bookkeeping when there is nothing to be stale against."""
            start_s = jnp.asarray(start, jnp.int32)
            d0, w0, m0 = docs[0, 0], words[0, 0], mask[0, 0]
            ll0 = None
            if with_initial_ll:
                sm0, t0 = _chain_ll_local(state.n_dk[0], state.n_wk[0],
                                          state.n_k, d0, w0, m0,
                                          jnp.float32(0))
                ll0 = (sm0 / jnp.maximum(t0, 1.0)).mean()
            sweep_kernel = lda_gibbs.make_sweep_kernel(
                alpha=config.alpha, eta=config.eta, n_vocab=n_vocab,
                k_topics=k, **sampler_kw)

            def one_sweep(carry, i):
                z, ndk, nwk, nk, keys, ad, aw, na = carry

                def one_chain(zc, ndkc, nwkc, nkc, keyc):
                    return sweep_kernel(zc, ndkc, nwkc, nkc, keyc,
                                        d0, w0, m0)

                z, ndk, nwk, nk, keys = jax.vmap(one_chain)(
                    z, ndk, nwk, nk, keys)
                do = start_s + i >= burn
                do_f = do.astype(jnp.float32)
                ad = ad + do_f * ndk.astype(jnp.float32)
                aw = aw + do_f * nwk.astype(jnp.float32)
                na = na + do.astype(jnp.int32)
                return (z, ndk, nwk, nk, keys, ad, aw, na), None

            carry0 = (state.z[0, 0], state.n_dk[0], state.n_wk[0],
                      state.n_k, state.keys[0, 0],
                      state.acc_ndk[0], state.acc_nwk[0], state.n_acc)
            (z, ndk, nwk, nk, keys, ad, aw, na), _ = jax.lax.scan(
                one_sweep, carry0, jnp.arange(n_steps, dtype=jnp.int32))
            sm, t = _chain_ll_local(ndk, nwk, nk, d0, w0, m0,
                                    jnp.float32(0))
            new_state = ShardedGibbsState(
                z=z[None, None], n_dk=ndk[None], n_wk=nwk[None], n_k=nk,
                keys=keys[None, None], acc_ndk=ad[None],
                acc_nwk=aw[None], n_acc=na)
            ll = (sm / jnp.maximum(t, 1.0)).mean()
            if with_initial_ll:
                return new_state, ll0, ll
            return new_state, ll

        def ll_fn(state: ShardedGibbsState, docs, words, mask):
            """Predictive mean log-likelihood from the CURRENT counts,
            computed where the data lives: per-shard token sums, then a
            psum — the convergence series the reference reads from
            lda-c's likelihood.dat (SURVEY.md §5.4–5.5), without
            gathering θ or the corpus to the host. The fit loop now
            evaluates ll inside the superstep program (superstep_fn);
            this standalone form serves the initial (pre-sweep) point
            and external callers."""
            def shard_fn(n_dk, n_wk, n_k, d, w, m):
                n_k_v = jax.lax.pcast(n_k, both, to="varying")
                zero = jax.lax.pcast(jnp.float32(0), both, to="varying")
                s, t = _chain_ll_local(n_dk[0], n_wk[0], n_k_v,
                                       d[0, 0], w[0, 0], m[0, 0], zero)
                return _ll_merge(s, t)

            s, t = jax.shard_map(
                shard_fn, mesh=self.mesh,
                in_specs=(P(D), P(*mp_spec), P(),
                          P(D, *mp_spec), P(D, *mp_spec), P(D, *mp_spec)),
                out_specs=(P(), P()),
            )(state.n_dk, state.n_wk, state.n_k, docs, words, mask)
            # Per-chain corpus mean log-likelihood, averaged over chains
            # (matches GibbsLDA's ll_chains).
            return (s / jnp.maximum(t, 1.0)).mean()

        def init_fn(keys, docs, words, mask, z, n_docs_local: int,
                    n_vocab_local: int):
            """The chain's first state, made where the token blocks
            already are: per (data shard, vocabulary chunk) ONE scan
            over the blocks that draws every chain's assignments of a
            block (uniform over K, a key a block split off the chain's
            key; `z` None) or takes them as given (`z`: the warm start's
            host draw), plants the sentinel K on the padding and adds
            the block to each chain's count tables
            (lda_gibbs.count_block, the single-device engine's). The
            tables then reduce as the sweep's deltas do - n_dk over the
            chunks of a data shard, n_wk over the data shards of a
            chunk, n_k over both - and land in the shardings `_specs`
            names. The accumulators are zeros made here too. Returns
            the state's arrays in its fields' order, all but n_acc
            (init_state)."""
            drawn = z is None
            given = () if drawn else (z,)

            def shard_fn(keys, d, w, m, *z_in):
                d0, w0, m0 = d[0, 0], w[0, 0], m[0, 0]
                nb = d0.shape[0]
                key_c = keys[0, 0]                          # [C, 2]
                if drawn:
                    key_c, zkey = jnp.moveaxis(
                        jax.vmap(jax.random.split)(key_c), 1, 0)
                    src = jax.vmap(                         # [C, nb, 2]
                        lambda kc: jax.random.split(kc, nb))(zkey)
                else:
                    src = z_in[0][0, 0]                     # [C, nb, B]
                # A chain's tables are a carry of their own, not a row
                # of a stacked one: under a chain vmap every scatter-add
                # copied its whole table twice (PERF.md section 6, PR 32).
                tables0 = (tuple(
                    jax.lax.pcast(t, both, to="varying")
                    for t in lda_gibbs.zero_counts(
                        n_docs_local, n_vocab_local, k)),) * key_c.shape[0]

                def block(tables, xs):
                    db, wb, mb, i = xs
                    zb = src[:, i]          # the block's keys, or its z
                    if drawn:
                        zb = jax.vmap(lambda kb: jax.random.randint(
                            kb, mb.shape, 0, k, dtype=jnp.int32))(zb)
                    zb = jnp.where(mb > 0, zb, k)           # [C, B]
                    return tuple(
                        lda_gibbs.count_block(t, (db, wb, zc),
                                              n_topics=k)[0]
                        for t, zc in zip(tables, zb)), zb

                tables, z_f = jax.lax.scan(
                    block, tables0, (d0, w0, m0, jnp.arange(nb)))
                ndk, nwk, nk = (jnp.stack(t) for t in zip(*(
                    lda_gibbs.shape_counts(t, k) for t in tables)))
                with device_scope("onix.init.merge"):
                    ndk = jax.lax.psum(ndk, M) if M else ndk
                    nwk = jax.lax.psum(nwk, D)
                    nk = jax.lax.psum(nk, both)
                return (z_f.swapaxes(0, 1)[None, None], ndk[None],
                        nwk[None], nk, key_c[None, None],
                        jnp.zeros_like(ndk, jnp.float32)[None],
                        jnp.zeros_like(nwk, jnp.float32)[None])

            tok = P(D, *mp_spec)
            return jax.shard_map(
                shard_fn, mesh=self.mesh,
                in_specs=(tok,) * (4 + len(given)),
                out_specs=(tok, P(D), P(*mp_spec), P(), tok,
                           P(D), P(*mp_spec)),
            )(keys, docs, words, mask, *given)

        self._sweep = jax.jit(sweep_fn, static_argnames=("accumulate",),
                              donate_argnums=(0,))
        self._ll = jax.jit(ll_fn)
        # dp=1 fast path: engaged when the mesh has exactly one device
        # (scale.py's single-chip configuration and every CPU run of the
        # judged pipelines); ONIX_DP1_FAST=0 pins the shard_map form —
        # the cross-check arm the equality tests compare against.
        import os
        self.dp1_fast = (self.n_data == 1 and self.n_mp == 1
                         and os.environ.get("ONIX_DP1_FAST") != "0")
        # Merge-form dispatch: the dp=1/mp=1 fast path has no peers so
        # async ≡ sync there bit-for-bit (the fast path IS the τ=0
        # degenerate on one device); off the fast path the async form
        # swaps superstep_fn for the bounded-staleness program. The
        # per-sweep _sweep dispatch keeps the synchronous fold on every
        # form — it exists for the pre-r7 cross-check arms, and a merge
        # window shorter than its dispatch cannot overlap anything.
        wrapped_superstep = (superstep_async_fn if use_async
                            else superstep_fn)
        self._superstep = jax.jit(
            superstep_dp1_fn if self.dp1_fast else wrapped_superstep,
            static_argnames=("n_steps", "with_initial_ll"),
            donate_argnums=(0,))
        # The shard_map superstep stays constructible regardless, for
        # the fast-path equality tests and hostfabric's workers (no
        # donation: test callers reuse their input states). It carries
        # the RESOLVED merge form, so a dp=1 async model can still be
        # compared bit-for-bit against a sync model's wrapped path.
        self._superstep_shardmap = jax.jit(
            wrapped_superstep,
            static_argnames=("n_steps", "with_initial_ll"))
        self._mp_axis = M
        # Where a fit's `prepare` reports the layout's plan to
        # (`shard_corpus`'s `on_plan`); None outside a fit. On the
        # instance, not an argument of `prepare`: callers put functions
        # of the corpus alone in its place (the benchmark's drivers).
        self._on_plan = None
        self._init = jax.jit(
            init_fn, static_argnames=("n_docs_local", "n_vocab_local"),
            donate_argnames=("z",),
            out_shardings=tuple(
                NamedSharding(self.mesh, spec)
                for spec in self._specs().values() if spec is not None))

    # -- sharding specs ----------------------------------------------------

    def _specs(self) -> dict:
        D = self.data_axes
        mp = (self._mp_axis,) if self._mp_axis else ()
        return {"z": P(D, *mp), "n_dk": P(D), "n_wk": P(*mp),
                "n_k": P(), "keys": P(D, *mp), "acc_ndk": P(D),
                "acc_nwk": P(*mp), "n_acc": None}

    # -- state construction ----------------------------------------------

    def _chain_keys(self, p: int, m: int) -> jax.Array:
        """Independent per-device/per-chain streams: split, never
        adjacent raw seeds (seed and seed+1 would otherwise share most
        streams)."""
        C = self.config.n_chains
        return jax.random.split(jax.random.PRNGKey(self.config.seed),
                                p * m * C).reshape(p, m, C, -1)

    def init_state(self, sc: ShardedCorpus,
                   init_phi: np.ndarray | None = None,
                   device_blocks=None,
                   ahead: ProgramsAhead | None = None) -> ShardedGibbsState:
        """The chain's first state, drawn and counted on the device
        (`init_fn`): no table crosses the host link, and on the cold
        path no assignment either. `device_blocks` are `sc`'s blocks
        where the caller has put them already (`device_corpus`);
        `ahead` holds `init_fn`'s executable where `fit` has built it
        ahead."""
        cfg = self.config
        k = cfg.n_topics
        C = cfg.n_chains
        p, m, nb, b = sc.doc_blocks.shape
        specs = self._specs()
        z = None
        if init_phi is not None:
            # φ̂-as-prior warm start (Streaming Gibbs, arxiv
            # 1601.01142): draw each token's initial topic from
            # p(k|w) ∝ init_phi[w, k] — yesterday's posterior word-
            # topic distribution — instead of uniform, so the chain
            # starts near the previous day's mode and needs a fraction
            # of the cold sweep budget (daily.warm_sweeps). Host-side,
            # deterministic in cfg.seed; the device counts it as it
            # counts its own draw. init_phi rows are GLOBAL vocab ids;
            # the blocked layout holds local chunk ids (word // n_mp
            # for chunk word % n_mp).
            init_phi = np.asarray(init_phi, np.float64)
            if init_phi.shape[0] != sc.n_vocab:
                raise ValueError(
                    f"init_phi covers {init_phi.shape[0]} words, corpus "
                    f"has {sc.n_vocab} — map the prior into TODAY's "
                    "vocabulary first (campaign.map_phi_prior)")
            rng = np.random.default_rng(cfg.seed)
            z = np.empty((p, m, C, nb * b), np.int32)
            flat_w = sc.word_blocks.reshape(p, m, -1)
            step = 1 << 18       # bound the [T, K] cdf temp, not z
            for q in range(p):
                for c in range(m):
                    w_global = np.minimum(flat_w[q, c] * np.int32(m)
                                          + np.int32(c), sc.n_vocab - 1)
                    for s in range(0, w_global.shape[0], step):
                        sl = slice(s, s + step)
                        # The cdf depends only on the words — build it
                        # once per slice, draw uniforms per chain.
                        cdf = np.cumsum(init_phi[w_global[sl]], axis=1)
                        cdf /= np.maximum(cdf[:, -1:], 1e-30)
                        for ch in range(C):
                            u = rng.random(cdf.shape[0])
                            z[q, c, ch, sl] = np.minimum(
                                (cdf < u[:, None]).sum(axis=1),
                                k - 1).astype(np.int32)
            z = put_global(z.reshape(p, m, C, nb, b), self.mesh,
                           specs["z"])
        keys = put_global(self._chain_keys(p, m), self.mesh, specs["keys"])
        docs, words, mask = device_blocks or self.device_corpus(sc)
        run = self._init if ahead is None else (
            lambda *a, **static: ahead.call("init", self._init, *a, **static))
        arrays = run(keys, docs, words, mask, z,
                     n_docs_local=sc.n_docs_local,
                     n_vocab_local=sc.n_vocab_local)
        # n_acc's None spec means "leave uncommitted" single-process; a
        # process-spanning mesh needs every jit input globally placed,
        # so it rides an explicitly replicated P() there.
        n_acc = (jnp.zeros((), jnp.int32) if jax.process_count() == 1
                 else put_global(np.zeros((), np.int32), self.mesh, P()))
        return ShardedGibbsState(*arrays, n_acc=n_acc)

    def restore_state(self, arrays: dict[str, np.ndarray]) -> ShardedGibbsState:
        """Rebuild a device-sharded state from checkpointed host arrays,
        re-applying the same shardings init_state lays down."""
        specs = self._specs()
        put = {}
        for name, spec in specs.items():
            a = arrays[name]
            put[name] = (jnp.asarray(a)
                         if spec is None and jax.process_count() == 1
                         else put_global(a, self.mesh, spec or P()))
        return ShardedGibbsState(**put)

    def prepare(self, corpus: Corpus) -> ShardedCorpus:
        return shard_corpus(corpus, self.n_data, self.config.block_size,
                            self.config.seed, n_mp=self.n_mp,
                            n_groups=self.config.sync_splits,
                            on_plan=self._on_plan)

    # -- programs built ahead ---------------------------------------------

    def _abstract_args(self, plan: ShardPlan, warm: bool):
        """What `init_fn` and the superstep will be handed, as shapes
        with their shardings, from the layout's plan alone: the chains'
        keys, the token blocks as `device_corpus` puts them, the warm
        start's assignments (`warm`; None cold) and the state as
        `init_fn` returns it. -> (keys, (docs, words, mask), z, state)"""
        specs = self._specs()
        tok_at = NamedSharding(self.mesh, specs["z"])
        shape = (plan.n_data, plan.n_mp, plan.nb, plan.block)
        ids = jax.ShapeDtypeStruct(shape, jnp.int32, sharding=tok_at)
        blocks = (ids, ids,
                  jax.ShapeDtypeStruct(shape, jnp.float32, sharding=tok_at))
        keys = jax.eval_shape(
            lambda: self._chain_keys(plan.n_data, plan.n_mp))
        keys = jax.ShapeDtypeStruct(
            keys.shape, keys.dtype,
            sharding=NamedSharding(self.mesh, specs["keys"]))
        z = None
        if warm:
            z = jax.ShapeDtypeStruct(
                shape[:2] + (self.config.n_chains,) + shape[2:], jnp.int32,
                sharding=tok_at)
        arrays = self._init.eval_shape(
            keys, *blocks, z, n_docs_local=plan.n_docs_local,
            n_vocab_local=plan.n_vocab_local)
        # n_acc as init_state and restore_state leave it.
        n_acc = jax.ShapeDtypeStruct(
            (), jnp.int32,
            sharding=(None if jax.process_count() == 1
                      else NamedSharding(self.mesh, P())))
        return keys, blocks, z, ShardedGibbsState(*arrays, n_acc=n_acc)

    def _build_ahead(self, plan: ShardPlan, warm: bool):
        """`ProgramsAhead`'s `build` for a fit on `plan`: trace, lower
        and compile `init_fn` (key "init") or the superstep of a key
        `(n_steps, with_initial_ll)` from the plan's shapes. All three
        steps of a program on the one thread that asks: `telemetry.
        watch_compiles` gathers a compile's events by thread, and its
        `jit.compile` span hangs under the span open on that thread."""
        args = []

        def build(key):
            if not args:
                args.extend(self._abstract_args(plan, warm))
            keys, blocks, z, state = args
            if key == "init":
                lowered = self._init.lower(
                    keys, *blocks, z, n_docs_local=plan.n_docs_local,
                    n_vocab_local=plan.n_vocab_local)
            else:
                n_steps, with_initial_ll = key
                lowered = self._superstep.lower(
                    state, *blocks, jax.ShapeDtypeStruct((), jnp.int32),
                    n_steps=n_steps, with_initial_ll=with_initial_ll)
            return lowered.compile()

        return build

    def device_corpus(self, sc: ShardedCorpus):
        D = self.data_axes
        mp = (self._mp_axis,) if self._mp_axis else ()
        spec = P(D, *mp)
        return (put_global(sc.doc_blocks, self.mesh, spec),
                put_global(sc.word_blocks, self.mesh, spec),
                put_global(sc.mask_blocks, self.mesh, spec))

    # -- fit --------------------------------------------------------------

    def fit(self, corpus: Corpus, n_sweeps: int | None = None,
            callback=None, checkpoint_dir=None, resume: bool = True,
            fault_inject_sweep: int | None = None,
            init_phi: np.ndarray | None = None) -> dict:
        """Sharded fit loop as fused supersteps, with optional
        checkpoint/resume — the recovery story the reference's MPI job
        lacks (SURVEY.md §5.3: "an MPI rank failure kills the LDA job");
        mandatory for preemptible TPU capacity.

        Sweeps run S at a time inside one jitted program (one shard_map,
        or the dp=1 fast path) with the burn-in accumulate fold and the
        boundary ll on device; segment boundaries land exactly on
        checkpoint/fault/final sweeps (lda_gibbs.plan_segments), so a
        checkpoint is never demanded mid-superstep and every resume
        point is an exact sweep boundary. Mesh shape AND superstep size
        are part of the checkpoint fingerprint: a state sharded dp=8
        must not resume on a dp=4 mesh, and a run fused at a different S
        is refused rather than resumed into a different ll cadence.

        `fault_inject_sweep` (or env ONIX_FAULT_SWEEP) raises
        SimulatedPreemption right after completing that sweep — the
        same §5.3 fault hook GibbsLDA has, so scale runs on the sharded
        engine can exercise their resume path too.

        `init_phi` ([n_vocab, K], today's vocab order) warm-starts the
        chain from a φ̂-as-prior z draw (init_state) — the r19 daily
        supervisor's warm refit. A warm chain is a DIFFERENT chain from
        the cold one, so the prior's content digest joins the checkpoint
        fingerprint: a cold resume can never continue a warm run or
        vice versa, and two different priors never share checkpoints.

        The programs are built ahead of their first call: as soon as
        `prepare` reports the layout's plan (`shard_corpus`'s `on_plan`,
        before the shuffle) the fit decides what it restores and which
        segments it runs, and a thread of its own compiles `init_fn`
        and every superstep they name while this one lays the corpus
        out, puts it and makes the state (`ProgramsAhead`). No switch:
        where the layout is short the first call waits for its
        executable as long as it would have compiled it."""
        import os

        from onix import checkpoint as ckpt
        from onix.models.lda_gibbs import SUPERSTEP_DEFAULT, plan_segments

        if fault_inject_sweep is None:
            env = os.environ.get("ONIX_FAULT_SWEEP")
            fault_inject_sweep = int(env) if env else None

        cfg = self.config
        n_sweeps = cfg.n_sweeps if n_sweeps is None else n_sweeps
        S_step = cfg.superstep or SUPERSTEP_DEFAULT
        # layout=4: the fused-superstep layout — the jitted carry holds
        # the accumulator state, checkpoints land only at superstep
        # boundaries, and the superstep size joins the identity
        # (checkpoint.fingerprint's superstep arg). layout=3 was the
        # chained state layout (chain axis C behind the shard axes);
        # bumping rejects earlier layouts instead of crashing on
        # restore. n_chains is part of the config hash.
        # Warm-init identity (r19): the prior changes the chain's
        # initial state, so it must join the resume identity exactly
        # like a sampler-arm change. Cold fits contribute nothing —
        # pre-r19 checkpoints keep resuming.
        warm_extra = {}
        if init_phi is not None:
            import hashlib
            a = np.asarray(init_phi, np.float32)
            hh = hashlib.sha256(repr(a.shape).encode())
            hh.update(a.tobytes())
            warm_extra["warm_init"] = hh.hexdigest()[:16]

        def plan_fit(plan: ShardPlan) -> _FitPlan:
            """Everything the fit decides from the layout's plan alone,
            and so before a token is dealt: the checkpoint's identity
            and whether one is restored, the segments, and the programs
            they will call, which start to compile here."""
            fp = ckpt.fingerprint(
                cfg, plan.n_data * plan.n_docs_local, plan.n_vocab,
                plan.n_tokens,
                extra={"mesh": list(self.mesh.shape.values()),
                       "layout": 4,
                       **warm_extra,
                       # RESOLVED sampler arm: a resume across an arm
                       # change is refused (GibbsLDA.fit has the same
                       # rule).
                       **lda_gibbs.sampler_fingerprint(
                           self.sampler_form, self.sparse_active,
                           cfg.sparse_mh),
                       # RESOLVED merge form (r14): τ>0 is a different
                       # chain, and even the bit-identical τ=0 async
                       # arm refuses a cross-form resume by spec; sync
                       # contributes nothing so pre-r14 checkpoints
                       # resume.
                       **lda_gibbs.merge_fingerprint(
                           self.merge_form, self.merge_tau)},
                superstep=S_step)
            fp_dir = saved = None
            if checkpoint_dir is not None:
                import pathlib
                fp_dir = pathlib.Path(checkpoint_dir) / fp
                if resume:
                    saved = ckpt.load_latest(fp_dir)
                    if (saved is not None
                            and saved.meta.get("fingerprint") != fp):
                        saved = None
            start = 0 if saved is None else saved.sweep + 1
            segments = plan_segments(
                start, n_sweeps, S_step,
                checkpoint_every=(cfg.checkpoint_every
                                  if fp_dir is not None else 0),
                fault_sweep=fault_inject_sweep,
                per_sweep=callback is not None)
            # The programs in the order the fit asks for them: the
            # state's where none is restored, then a superstep for every
            # distinct (n_steps, with_initial_ll) of the segments.
            keys = dict.fromkeys(
                (() if saved is not None else ("init",))
                + tuple((n, i == 0) for i, (_, n) in enumerate(segments)))
            ahead = ProgramsAhead(
                keys, self._build_ahead(plan, init_phi is not None))
            return _FitPlan(plan, fp, fp_dir, saved, start, segments, ahead)

        plans: list[_FitPlan] = []
        self._on_plan = lambda plan: plans.append(plan_fit(plan))
        try:
            with telemetry.TRACER.span("fit.prepare", tokens=corpus.n_tokens,
                                       docs=corpus.n_docs) as span:
                sc = self.prepare(corpus)
                if span is not None:
                    # The shards' balance: the sweep ends with its fullest.
                    per_shard = bucket_tokens(sc).sum(axis=1)
                    span.attrs.update(
                        shards=self.n_data,
                        tokens_max_shard=int(per_shard.max()),
                        tokens_min_shard=int(per_shard.min()),
                        pad_slots=int(sc.mask_blocks.size - per_shard.sum()))
            # A `prepare` that is not `shard_corpus`'s own (it reported
            # no plan, or not this layout's) is planned for now.
            plan = plan_of(sc, corpus.n_tokens)
            if not plans or plans[-1].plan != plan:
                plans.append(plan_fit(plan))
            return self._fit_planned(corpus, sc, plans[-1], n_sweeps,
                                     callback, fault_inject_sweep, init_phi)
        finally:
            self._on_plan = None
            for planned in plans:
                planned.ahead.close()

    def _fit_planned(self, corpus: Corpus, sc: ShardedCorpus,
                     planned: _FitPlan, n_sweeps: int, callback,
                     fault_inject_sweep, init_phi) -> dict:
        """`fit` from the laid-out corpus on: transfer, state, sweeps,
        estimates."""
        from onix import checkpoint as ckpt
        from onix.models.lda_gibbs import run_fit_segments

        cfg = self.config
        _, fp, checkpoint_dir, saved, start, segments, ahead = planned
        with telemetry.TRACER.span(
                "fit.device_corpus",
                bytes=sum(int(a.nbytes) for a in (
                    sc.doc_blocks, sc.word_blocks, sc.mask_blocks))):
            docs, words, mask = self.device_corpus(sc)
        with telemetry.TRACER.span("fit.init_state") as span:
            resumed = saved is not None
            if resumed:
                state = self.restore_state(saved.arrays)
            else:
                # The span ends when the device has made the state, not
                # when the host has asked for it.
                state = jax.block_until_ready(self.init_state(
                    sc, init_phi=init_phi,
                    device_blocks=(docs, words, mask), ahead=ahead))
            if span is not None:
                nbytes = sum(int(a.nbytes) for a in state)
                span.attrs.update(resumed=resumed, bytes=nbytes)
                # h2d_bytes, what crossed the host link for the state:
                # all of a restored one, the assignments of a warm
                # start's host draw, nothing of a cold start's.
                if resumed:
                    span.attrs.update(h2d_bytes=nbytes)
                elif init_phi is not None:
                    span.attrs.update(draw="host", counts="device",
                                      h2d_bytes=int(state.z.nbytes))
                else:
                    span.attrs.update(draw="device", counts="device",
                                      h2d_bytes=0)
        # What one sweep's merge moves per chip: every chain's n_wk
        # chunk and n_k (n_dk's rows too where mp shards them).
        merge_bytes = (int(state.n_wk.nbytes) // self.n_mp
                       + int(state.n_k.nbytes))
        if self.n_mp > 1:
            merge_bytes += int(state.n_dk.nbytes) // self.n_data
        with telemetry.TRACER.span(
                "fit.supersteps", sweeps=n_sweeps - start,
                merge_form=self.merge_form,
                merge_bytes_per_sweep=(merge_bytes
                                       * max(1, int(cfg.sync_splits))),
                # n_dk as a chip's block scan carries it.
                **lda_gibbs.ndk_layout(sc.n_docs_local, cfg.n_topics,
                                       sampler_form=self.sampler_form)):
            state, ll_history = run_fit_segments(
                state, start, segments,
                # The start sweep as the int32 scalar the executables
                # built ahead were lowered for.
                superstep_fn=lambda st, s0, n, init: ahead.call(
                    (n, init), self._superstep,
                    st, docs, words, mask, np.int32(s0), n_steps=n,
                    with_initial_ll=init),
                initial_ll_fn=lambda st: self._ll(st, docs, words, mask),
                checkpoint_every=cfg.checkpoint_every,
                checkpoint_dir=checkpoint_dir,
                save_fn=lambda st, s: ckpt.save(
                    checkpoint_dir, s,
                    {k: np.asarray(v) for k, v in st._asdict().items()},
                    {"fingerprint": fp, "engine": "sharded_gibbs"}),
                fault_sweep=fault_inject_sweep,
                notify=(None if callback is None
                        else lambda s, st, ll: callback(s, st)))
        with telemetry.TRACER.span("fit.estimates"):
            theta, phi_wk = self.estimates(state, sc, corpus.n_docs)
        return {"state": state, "sharded_corpus": sc,
                "theta": theta, "phi_wk": phi_wk,
                "ll_history": ll_history}

    def estimates(self, state: ShardedGibbsState, sc: ShardedCorpus,
                  n_docs: int) -> tuple[np.ndarray, np.ndarray]:
        """Gather per-shard counts back to global doc/word order.

        Matches GibbsLDA's contract: n_chains == 1 returns theta [D, K]
        and phi_wk [V, K]; n_chains > 1 stacks a leading chain axis
        (theta [C, D, K], phi_wk [C, V, K]) that scoring.score_events
        ensemble-averages over."""
        cfg = self.config
        use_acc = int(state.n_acc) > 0
        denom = max(float(state.n_acc), 1.0)
        ndk_s = (np.asarray(state.acc_ndk) / denom if use_acc
                 else np.asarray(state.n_dk, dtype=np.float64))
        nwk_c = (np.asarray(state.acc_nwk) / denom if use_acc
                 else np.asarray(state.n_wk, dtype=np.float64))
        C = ndk_s.shape[1]
        valid = sc.doc_map >= 0
        thetas, phis = [], []
        for ch in range(C):
            nwk = chunked_to_global_nwk(nwk_c[:, ch], sc.n_vocab)
            ndk = np.zeros((n_docs, cfg.n_topics))
            ndk[sc.doc_map[valid]] = ndk_s[:, ch][valid]
            thetas.append((ndk + cfg.alpha)
                          / (ndk.sum(-1, keepdims=True)
                             + cfg.n_topics * cfg.alpha))
            phis.append((nwk + cfg.eta) / (nwk.sum(0, keepdims=True)
                                           + self.n_vocab * cfg.eta))
        theta = np.stack(thetas).astype(np.float32)
        phi_wk = np.stack(phis).astype(np.float32)
        if C == 1:
            return theta[0], phi_wk[0]
        return theta, phi_wk
