"""Batched collapsed-Gibbs LDA in pure JAX — the TPU replacement for
oni-lda-c (reference README.md:84, .gitmodules absent; SURVEY.md §2.1 #10).

The reference engine is a C/MPI program: documents sharded across ranks,
a sequential per-token sampler per rank, topic-word sufficient statistics
MPI-reduced each iteration. A token-sequential sampler cannot use a TPU,
so onix uses the standard SIMD compromise (SURVEY.md §7.3.1, PAPERS.md
"Sparse Partially Collapsed MCMC"): tokens are sampled in blocks of
`block_size`; within a block every token sees counts that exclude its own
assignment but are stale w.r.t. its block-mates; counts are exactly
updated between blocks via scatter-add. As block_size → 1 this is exact
collapsed Gibbs; at practical sizes the stationary distribution is close
enough that topic recovery and the top-k overlap metric survive (tested
in tests/test_gibbs.py).

Shapes: K topics, V vocabulary, D documents, N tokens.
State counts: n_dk [D,K], n_wk [V,K], n_k [K] (int32, exact — deltas are
scattered as int32, never round-tripped through float32, so counts stay
exact past 2^24 at the billion-event scale of README.md:42).
Padding tokens carry the sentinel assignment z == K: `jax.nn.one_hot`
maps out-of-range indices to all-zero rows, so padding contributes
nothing to any count without a mask multiply.
A sweep is `lax.scan` over N/block_size blocks — one fused XLA program,
no host round-trips, no Python control flow inside jit.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from onix.config import LDAConfig
from onix.corpus import Corpus
from onix.utils.obs import device_scope


class GibbsState(NamedTuple):
    z: jax.Array          # int32 [n_blocks, B] topic per token (K = padding)
    n_dk: jax.Array       # int32 [D, K] doc-topic counts
    n_wk: jax.Array       # int32 [V, K] word-topic counts
    n_k: jax.Array        # int32 [K]    topic totals
    key: jax.Array        # PRNG key
    # Posterior-mean accumulators (populated after burn-in; improves the
    # rank stability needed for the judged top-k overlap, SURVEY.md §7.3.2).
    acc_ndk: jax.Array    # float32 [D, K]
    acc_nwk: jax.Array    # float32 [V, K]
    n_acc: jax.Array      # int32 [] number of accumulated sweeps


def _one_hot(z: jax.Array, k: int) -> jax.Array:
    """int32 one-hot; out-of-range z (the padding sentinel K) -> zero row."""
    return jax.nn.one_hot(z, k, dtype=jnp.int32)


# Tokens one step of a count build adds to the tables: a scatter-add of
# up to 2^14 updates compiles without the sort that 2^15 and more bring
# (15 s to compile at 2^17; PERF.md section 6, PR 32).
_COUNT_ROWS = 1 << 13


def zero_counts(n_docs: int, n_vocab: int, n_topics: int):
    """Empty count tables for `count_block`: n_dk FLAT [D*K], n_wk [V, K]."""
    if n_docs * n_topics >= 2 ** 31:
        raise ValueError(
            f"{n_docs} documents x {n_topics} topics pass the int32 "
            "range the flat doc-topic table is indexed in")
    return (jnp.zeros((n_docs * n_topics,), jnp.int32),
            jnp.zeros((n_vocab, n_topics), jnp.int32))


def count_block(tables, xs, *, n_topics: int):
    """One token block added to the count tables: the `lax.scan` step
    both engines build their first counts with (`build_counts` here,
    the draw-and-count scan of `ShardedGibbsLDA.init_state`).

    carry = (n_dk, n_wk) as `zero_counts` makes them; xs = (docs, words,
    z) of one block, added in runs of `_COUNT_ROWS` tokens where they
    divide the block (the adds are exact, so their order is free). Each
    token adds ONE to entry `doc * K + z` of the flat n_dk (the padding
    sentinel z == K aims past its end and is dropped) and its one-hot
    row to n_wk (the sentinel's is zero). The forms are the ones each
    table is fast in on the chip, a token: n_dk 9.3 ns flat, 44 ns as
    rows of K lanes (12.7 ns where the scatter sorts, which takes 15 s
    to compile); n_wk 7.5 ns as rows, 27 ns flat (PERF.md section 6,
    PR 32)."""
    rows = xs[0].shape[0]
    if rows % _COUNT_ROWS == 0:
        rows = _COUNT_ROWS

    def run(tables, xs):
        n_dk, n_wk = tables
        d, w, z = xs
        at = jnp.where(z < n_topics, d * n_topics + z, n_dk.shape[0])
        return (n_dk.at[at].add(1, mode="drop"),
                n_wk.at[w].add(_one_hot(z, n_topics))), None

    return jax.lax.scan(run, tables,
                        tuple(a.reshape(-1, rows) for a in xs))


def shape_counts(tables, n_topics: int):
    """`count_block`'s tables -> (n_dk [D, K], n_wk [V, K], n_k [K])."""
    n_dk, n_wk = tables
    # Through uint32 and back (exact: the counts are not negative): the
    # TPU compiler takes 17-24 s over a reshape that reads the flat table
    # where the scan left it, in fast memory, and 2 s once a convert in
    # front has copied it out (PERF.md section 6, PR 32).
    n_dk = n_dk.astype(jnp.uint32).reshape(-1, n_topics).astype(jnp.int32)
    return n_dk, n_wk, n_wk.sum(axis=0, dtype=jnp.int32)


def build_counts(doc_blocks, word_blocks, z, n_docs: int, n_vocab: int,
                 n_topics: int):
    """Exact (n_dk, n_wk, n_k) of the assignments `z`, blockwise under
    `lax.scan`: a flat one-hot over the whole corpus would materialize
    an [N, K]-padded temp that OOMs HBM past ~10M tokens (hit at 40M)."""
    tables, _ = jax.lax.scan(
        functools.partial(count_block, n_topics=n_topics),
        zero_counts(n_docs, n_vocab, n_topics),
        (doc_blocks, word_blocks, z))
    return shape_counts(tables, n_topics)


def init_state_keyed(
    key: jax.Array,
    doc_blocks: jax.Array,
    word_blocks: jax.Array,
    mask_blocks: jax.Array,
    n_docs: int,
    n_vocab: int,
    n_topics: int,
) -> GibbsState:
    """Random topic init + exact count build (`build_counts`)."""
    key, zkey = jax.random.split(key)
    shape = doc_blocks.shape
    z = jax.random.randint(zkey, shape, 0, n_topics, dtype=jnp.int32)
    z = jnp.where(mask_blocks > 0, z, n_topics)   # sentinel for padding
    n_dk, n_wk, n_k = build_counts(doc_blocks, word_blocks, z,
                                   n_docs, n_vocab, n_topics)
    return GibbsState(
        z=z, n_dk=n_dk, n_wk=n_wk, n_k=n_k, key=key,
        acc_ndk=jnp.zeros((n_docs, n_topics), jnp.float32),
        acc_nwk=jnp.zeros((n_vocab, n_topics), jnp.float32),
        n_acc=jnp.zeros((), jnp.int32),
    )


def init_state(
    doc_blocks: jax.Array,
    word_blocks: jax.Array,
    mask_blocks: jax.Array,
    n_docs: int,
    n_vocab: int,
    n_topics: int,
    seed: int,
) -> GibbsState:
    return init_state_keyed(jax.random.PRNGKey(seed), doc_blocks,
                            word_blocks, mask_blocks, n_docs, n_vocab,
                            n_topics)


def init_chains(
    doc_blocks: jax.Array,
    word_blocks: jax.Array,
    mask_blocks: jax.Array,
    n_docs: int,
    n_vocab: int,
    n_topics: int,
    seed: int,
    n_chains: int,
) -> GibbsState:
    """Stacked state for `n_chains` independent chains (leading chain
    axis on every array). Chains differ only in their PRNG streams. Each
    is made on its own and the states stacked: under a chain vmap every
    scatter-add of the count build copied its whole stacked table twice
    (PERF.md section 6, PR 32)."""
    keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
        jax.random.PRNGKey(seed), jnp.arange(n_chains, dtype=jnp.uint32))
    return jax.tree.map(
        lambda *chains: jnp.stack(chains),
        *(init_state_keyed(k, doc_blocks, word_blocks, mask_blocks,
                           n_docs, n_vocab, n_topics) for k in keys))


# The n_wk count update has two bit-identical forms (the tests compare
# them, tests/test_nwk_form.py): a scatter-add of the [B, K] delta rows,
# and onehot(w)^T @ delta as one bf16 matmul with f32 accumulation,
# which has no serialized collisions where a block lands hundreds of
# row updates on each word of a product vocabulary. The n_dk update
# stays a scatter: documents hardly collide within a block and D is far
# too large to one-hot. select_nwk_form picks from the backend and two
# static shapes, under three caps:
#   * exactness - operands are {-1, 0, 1} (exact in bf16) and every
#     output is a sum of block_size of them, so block_size < 2^24 keeps
#     the f32 accumulation an exact integer;
#   * _NWK_MATMUL_MAX_V - the widest table worth a one-hot;
#   * _NWK_MATMUL_MAX_ELEMS - the [B, V] bf16 one-hot temporary, 2^27
#     elements = 256 MB (x n_chains under vmap); block 2^17 at V 4096
#     would otherwise grow a 1 GiB temporary the scatter never had.
# _NWK_MATMUL_MIN_DENSITY: block_size / V from which a backend takes
# the matmul form. cpu has no entry: tier-1 runs there, on the scatter.
# The tpu threshold is a placement, not a measured crossover; the one
# chip reading is flow-fit's, on the matmul side of it: onix.sweep.nwk
# 0.323 s of a 5.742 s sweep at V 537-555, B 2^17 (ledger PR 30;
# PERF.md section 5). A backend without an entry keeps the scatter.
_NWK_MATMUL_MAX_V = 4096
_NWK_MATMUL_MAX_ELEMS = 1 << 27
_NWK_MATMUL_MIN_DENSITY = {"tpu": 32.0}


# lint: exempt[gates] -- no env or config layer to order: pin, else table
def select_nwk_form(*, backend: str, block_size: int, n_rows: int,
                    nwk_form: str | None = None) -> str:
    """The n_wk count-update form, "scatter" or "matmul", decided at
    trace time and nowhere else: the tests' pin `nwk_form` if given,
    else "matmul" where `_NWK_MATMUL_MIN_DENSITY` has an entry for
    `backend`, the block is that dense (block_size / n_rows) and the
    exactness and memory caps hold, else "scatter". Reads no
    environment and no config: every engine gets what its backend and
    shapes resolve to."""
    if nwk_form is not None:
        if nwk_form not in ("scatter", "matmul"):
            raise ValueError(
                f"nwk_form must be scatter|matmul, got {nwk_form!r}")
        return nwk_form
    # lint: exempt[gates] -- this function is the table's one gate
    min_density = _NWK_MATMUL_MIN_DENSITY.get(backend)
    if (min_density is not None
            and block_size >= min_density * n_rows
            and n_rows <= _NWK_MATMUL_MAX_V
            and block_size < (1 << 24)
            and block_size * n_rows <= _NWK_MATMUL_MAX_ELEMS):
        return "matmul"
    return "scatter"


# The n_dk table as the block scan carries it has two bit-identical
# forms (tests/test_ndk_form.py): "rows", one document a K-lane row
# ([D, K]: K=20 fills 20 of a row's 128 lanes, so the table, every row
# read and every row added to is 84% padding on the chip), and
# "packed", G documents a 128-lane row ([ceil(D/G), 128]): the block
# step reads row d // G and picks the document's K lanes by d % G, and
# adds a 128-lane delta to that row. G is the largest power of two of
# documents that fit, so K=20 packs 4 (80 lanes) and not 6: the chip
# read the block step 0.9 ns a token faster at 4 (three selects for
# five in the pick; the scatter-add the same). The one chip reading,
# flow-fit's shapes, ns a token (PERF.md section 6, PR 34): the block
# step 17.1 packed for 21.5 rows at 209000 documents, 13.2 for 15.7 at
# 60600; of it the scatter-add 10.9 for 13.5 and 7.3 for 9.9.
# make_sweep_kernel packs once at a sweep's start and unpacks once at
# its end; everywhere else n_dk is [D, K]. _NDK_PACKED_BACKENDS lists
# the backends with such a reading; one without (cpu: tier-1 runs
# there) keeps "rows", and so does K over 64, where G would be 1.
_NDK_LANES = 128
_NDK_PACKED_BACKENDS = ("tpu",)


def _ndk_group(k_topics: int) -> int:
    """The largest power of two of K-lane documents a row holds."""
    fit = _NDK_LANES // k_topics if k_topics > 0 else 0
    return 1 << (fit.bit_length() - 1) if fit else 0


# lint: exempt[gates] -- no env or config layer to order: pin, else table
def select_ndk_form(*, backend: str, k_topics: int,
                    ndk_form: str | None = None) -> tuple[str, int]:
    """The form of n_dk inside a sweep and its group size G, ("rows",
    1) or ("packed", G >= 2), decided at trace time and nowhere else:
    the tests' pin `ndk_form` if given, else "packed" where
    `_NDK_PACKED_BACKENDS` names `backend` and two documents or more
    fit a 128-lane row. Reads no environment and no config."""
    group = _ndk_group(k_topics)
    if ndk_form is not None:
        if ndk_form not in ("rows", "packed"):
            raise ValueError(
                f"ndk_form must be rows|packed, got {ndk_form!r}")
        if ndk_form == "packed" and group < 2:
            raise ValueError(
                f"ndk_form packed needs two documents a {_NDK_LANES}-lane "
                f"row, K={k_topics} fits {group}")
        return ("packed", group) if ndk_form == "packed" else ("rows", 1)
    if backend in _NDK_PACKED_BACKENDS and group >= 2:
        return "packed", group
    return "rows", 1


def ndk_layout(n_docs: int, k_topics: int, *, sampler_form: str) -> dict:
    """What a sweep of `n_docs` documents carries its n_dk as, for the
    fit's spans: `ndk_form`, `ndk_group` and `ndk_rows_packed`, the
    rows of the table the block scan reads and adds to. The sparse
    sampler keeps [D, K]."""
    form, group = (select_ndk_form(backend=jax.default_backend(),
                                   k_topics=k_topics)
                   if sampler_form == "dense" else ("rows", 1))
    return {"ndk_form": form, "ndk_group": group,
            "ndk_rows_packed": -(-n_docs // group)}


def pack_ndk(n_dk: jax.Array, group: int) -> jax.Array:
    """[D, K] -> [ceil(D / group), 128]: document d's K counts in lanes
    (d % group) * K onward of row d // group; the tail documents and
    the lanes past group * K are zero."""
    n_docs, k = n_dk.shape
    n_rows = -(-n_docs // group)
    rows = jnp.pad(n_dk, ((0, n_rows * group - n_docs), (0, 0)))
    rows = rows.reshape(n_rows, group * k)
    return jnp.pad(rows, ((0, 0), (0, _NDK_LANES - group * k)))


def _pick_lanes(rows: jax.Array, slot: jax.Array, k_topics: int,
                group: int) -> jax.Array:
    """rows [B, 128] of pack_ndk's table, slot [B] in [0, group) ->
    [B, K]: lanes slot * K onward of each row. Picked with the rows
    turned over, [128, B]: the chip lays a [B, K] array out transposed
    whatever is asked, so K-lane slices of the rows cost a transposing
    copy each, and this way the 128 lanes are turned once, full, and
    the slices and selects run along the tokens (18.0 ns a token the
    block step for 26.2, PERF.md section 6, PR 34)."""
    rows_t = rows.T
    out = rows_t[:k_topics]
    for j in range(1, group):
        out = jnp.where((slot == j)[None, :],
                        rows_t[j * k_topics:(j + 1) * k_topics], out)
    return out.T


def _lane_delta(slot: jax.Array, z_old: jax.Array, z_new: jax.Array,
                k_topics: int) -> jax.Array:
    """The block's update of pack_ndk's table, [B, 128] int32: +1 in
    lane slot * K + z_new and -1 in lane slot * K + z_old of each
    token's row. A sentinel z (== K, padding) gives a zero row: slot *
    K + K is the next document's first lane."""
    base = slot * k_topics
    lanes = jnp.arange(_NDK_LANES, dtype=jnp.int32)[None, :]
    lane_new = jnp.where(z_new < k_topics, base + z_new, -1)
    lane_old = jnp.where(z_old < k_topics, base + z_old, -1)
    return ((lanes == lane_new[:, None]).astype(jnp.int32)
            - (lanes == lane_old[:, None]).astype(jnp.int32))


def unpack_ndk(packed: jax.Array, n_docs: int, k_topics: int,
               group: int) -> jax.Array:
    """pack_ndk's reverse: [ceil(D / group), 128] -> [D, K]."""
    rows = packed[:, :group * k_topics]
    return rows.reshape(-1, k_topics)[:n_docs]


# ---------------------------------------------------------------------------
# Sampler-form gate (r11): dense O(K) block sampler vs the sparse
# O(K_active) arm.
#
# Every arm of the n_wk gate above still pays O(K) per token in three
# places — the [B,K] probability block, the K-argmax, and the one-hot
# delta — so a K=256 per-tenant model pays for every topic ALLOCATED
# even when each document touches a handful. The sparse arm (Sparse
# Partially Collapsed MCMC, arxiv 1506.03784; LightLDA-style alias/MH
# cycling) replaces all three with work that scales with topics
# TOUCHED: per-document top-A active-topic blocks (static pow2 width,
# onix/models/compaction.py), a stale F+-tree-style CDF proposal for
# the dense-phi remainder (O(log K) bisection, tables rebuilt from the
# sweep-start counts), Metropolis–Hastings acceptance against the
# FRESH blocked target so the stationary distribution is exactly the
# dense arm's blocked-chain target, and rank-1 count scatters. Same
# key-stream discipline as every other arm (the carry key splits once
# per block), so accepted states replay deterministically — but the
# DRAWS differ from the dense arm: this is a different chain with the
# same stationary distribution, tested under winner-parity +
# perplexity-band (tests/test_sparse_gibbs.py), NOT bit-identity.
#
# Crossover tables follow the measured-platforms-only policy of the
# n_wk gate: auto engages the sparse arm only where a committed
# measurement says it wins, keyed by K (the axis the win scales with).
#   * cpu — K >= 64: measured on a 2-core host
#     (docs/SPARSE_r11_cpu.json, a 2e6-token K sweep {16,64,256}):
#     sparse/dense per-token fit cost 0.87x at K=16 (A=8), 1.80x at
#     K=64 (A=8), 4.46x at K=256 (A=16, mh=2); 2.80x at K=256 on a
#     second shape (docs/SPARSE_r11_bench_cpu.json). 64 is the LOWEST
#     MEASURED K where the sparse arm wins (the true crossover sits
#     somewhere in (16, 64), unmeasured). The crossover sits above the
#     judged K=20 pipelines — defaults there are unchanged.
#   * tpu — NO entry (not measured on the chip): the dense arm's
#     [B,K] blocks ride the VPU lanes that gathers do not, so the CPU
#     crossover must not be assumed to transfer.
_SAMPLER_SPARSE_MIN_K: dict[str, float] = {"cpu": 64.0}


def env_sampler_form() -> str | None:
    """Resolve the ONIX_SAMPLER_FORM experiment override. "auto" (and
    empty) mean None — defer to the measured gate. Engines read this
    ONCE at construction: the resolved
    form joins the checkpoint fingerprint, so the compiled sampler and
    the resume identity can never disagree."""
    import os
    env = os.environ.get("ONIX_SAMPLER_FORM")
    if not env or env == "auto":
        return None
    return env


def select_sampler_form(*, backend: str, k_topics: int,
                        sampler_form: str | None = None) -> str:
    """Trace-time decision for the sampler form ("dense" | "sparse") —
    the gate shared by GibbsLDA and ShardedGibbsLDA.

    Priority (config.resolve_form_gate — the ONE precedence chain
    shared with select_bank_form / select_serve_form): explicit
    `sampler_form`, then the measured per-backend K crossover
    (_SAMPLER_SPARSE_MIN_K; unmeasured platforms keep dense). No env
    layer HERE: the engines resolve ONIX_SAMPLER_FORM themselves
    (_resolved_sampler_form), config first, and hand the result in as
    `sampler_form`. An explicit "sparse" is honored at ANY K — at tiny
    K the top-A block simply saturates (A == K)."""
    from onix.config import resolve_form_gate

    def measured() -> str | None:
        min_k = _SAMPLER_SPARSE_MIN_K.get(backend)
        if min_k is not None and k_topics >= min_k:
            return "sparse"
        return None

    return resolve_form_gate(gate="sampler_form",
                             choices=("dense", "sparse"),
                             explicit=sampler_form, measured=measured,
                             default="dense")


def sampler_fingerprint(form: str, sparse_active: int,
                        sparse_mh: int) -> dict:
    """Checkpoint-identity entry for the RESOLVED sampler form (shared
    by GibbsLDA and ShardedGibbsLDA fit). Dense contributes NOTHING:
    the dense chain is bit-identical to the pre-r11 code, so pre-r11
    dense checkpoints keep resuming. The sparse arm adds the form plus
    its live knobs (A and the MH cycle length change what the chain
    samples) — which is also what refuses a resume across an arm
    change in either direction."""
    if form != "sparse":
        return {}
    return {"sampler": form,
            "sparse": [int(sparse_active), int(sparse_mh)]}


def merge_fingerprint(form: str, staleness: int) -> dict:
    """Checkpoint-identity entry for the RESOLVED count-merge form
    (r14; shared by GibbsLDA and ShardedGibbsLDA fit, mirroring
    sampler_fingerprint). Sync contributes NOTHING: the synchronous
    fold is bit-identical to the pre-r14 code, so pre-r14 checkpoints
    keep resuming. The async arm adds the form plus its live staleness
    bound τ (τ>0 changes what the chain samples; τ=0 is bit-identical
    to sync but still a distinct configuration whose resume the spec
    refuses rather than silently crossing) — which is also what
    refuses a resume across a merge-form/τ change in either
    direction."""
    if form != "async":
        return {}
    return {"merge": [form, int(staleness)]}


def _resolved_sampler_form(sampler_form: str | None, *,
                           k_topics: int) -> str:
    """The ONE deference chain behind every sampler-form decision —
    explicit form, then ONIX_SAMPLER_FORM, then the measured gate.
    Shared by resolve_sampler (both engines) and make_sweep_kernel
    (standalone callers) so a policy change can never make them resolve
    different arms for the same config/env."""
    form = sampler_form
    if form is None:
        form = env_sampler_form()
    return select_sampler_form(backend=jax.default_backend(),
                               k_topics=k_topics, sampler_form=form)


def resolve_sampler(config, *, k_topics: int) -> tuple[str, int, dict]:
    """The ONE construction-time sampler resolution shared by GibbsLDA
    and ShardedGibbsLDA: config (explicit lda.sampler_form beats all),
    then ONIX_SAMPLER_FORM, then _SAMPLER_SPARSE_MIN_K. Returns
    (form, resolved_active, kwargs-for-make_sweep_kernel); the form
    feeds both the compiled programs and the checkpoint fingerprint,
    so keeping this in one place is what keeps the two engines from
    ever resolving different arms for the same config."""
    sform = (None if config.sampler_form == "auto"
             else config.sampler_form)
    form = _resolved_sampler_form(sform, k_topics=k_topics)
    active = resolve_sparse_active(k_topics, config.sparse_active)
    return form, active, dict(sampler_form=form, sparse_active=active,
                              sparse_mh=config.sparse_mh)


def resolve_sparse_active(k_topics: int, sparse_active: int = 0) -> int:
    """Static width A of the per-doc active-topic block. 0 = auto: the
    smallest pow2 >= max(8, K/16), capped at K — sized to realistic
    per-doc topic occupancy so cost tracks topics touched; truncation
    below a doc's true active count costs proposal quality only (the
    dense-phi branch keeps every topic reachable and MH keeps the
    chain exact)."""
    from onix.models.compaction import pow2_bucket
    if sparse_active > 0:
        return min(int(k_topics), int(sparse_active))
    return min(int(k_topics), pow2_bucket(max(8, k_topics // 16)))


class SparseTables(NamedTuple):
    """Stale proposal tables for the sparse arm, a pure function of the
    sweep-start counts (rebuilt each sweep inside the fused superstep,
    so the sampled chain is independent of the superstep size S — the
    same S-invariance every other arm has).

    act_ids/act_cnt: per-doc top-A stale topics and their counts
    (zero-count slots carry no proposal mass). phi_cdf: row cumsum of
    the stale phi-hat (n_wk+eta)/(n_k+V*eta) — the F+-tree the dense
    branch bisects; its last column is the row total Q_w, and its f32
    interval widths are the REALIZED dense-branch proposal densities
    the acceptance ratio charges. nwk/nk are the raw stale counts for
    O(A) phi-hat evaluation over each token's active block."""

    act_ids: jax.Array   # int32  [D, A]
    act_cnt: jax.Array   # float32 [D, A] stale n_dk at act_ids
    phi_cdf: jax.Array   # float32 [V, K]
    nwk: jax.Array       # int32  [V, K] sweep-start snapshot
    nk: jax.Array        # int32  [K]


def build_sparse_tables(n_dk: jax.Array, n_wk: jax.Array, n_k: jax.Array,
                        *, eta: float, v_eta: float,
                        n_active: int) -> SparseTables:
    vals, ids = jax.lax.top_k(n_dk, n_active)
    phi = ((n_wk.astype(jnp.float32) + eta)
           / (n_k.astype(jnp.float32)[None, :] + v_eta))
    return SparseTables(act_ids=ids.astype(jnp.int32),
                        act_cnt=vals.astype(jnp.float32),
                        phi_cdf=jnp.cumsum(phi, axis=1),
                        nwk=n_wk, nk=n_k)


def cdf_lower_bound(cdf_flat: jax.Array, row: jax.Array, t: jax.Array,
                    k: int) -> jax.Array:
    """Vectorized lower_bound over rows of a flattened [*, k] CDF
    table: the count of entries cdf[row, :] < t, in [0, k] — the
    F+-tree-style bisection of the dense-phi proposal branch. log2(k)
    scalar-gather rounds per element instead of gathering the whole
    [B, K] row block (which would re-pay the O(K) the sparse arm
    exists to avoid). Matches np.searchsorted(cdf[row], t, 'left')
    exactly (tests/test_sparse_gibbs.py hypothesis property)."""
    pos = jnp.zeros(row.shape, jnp.int32)
    base = row.astype(jnp.int32) * k
    s = 1 << max(0, int(k).bit_length() - 1)   # largest pow2 <= k
    while s:
        cand = pos + s
        # Safe gather index (cand can momentarily exceed k); the move
        # condition re-checks the bound.
        val = jnp.take(cdf_flat, base + jnp.minimum(cand, k) - 1)
        pos = jnp.where((cand <= k) & (val < t), cand, pos)
        s >>= 1
    return pos


# Weight of the uniform escape branch in the sparse arm's proposal
# mixture, as a fraction of the (doc block + dense CDF) mass. It buys
# two guarantees the two main branches cannot give in f32: (i) every
# topic has NONZERO realized proposal probability even when its CDF
# interval rounds to zero width (a linear f32 cumsum makes draws of
# topics below ~2^-24 of the row total exactly impossible — the same
# failure mode the dense sampler's race replaced inverse-CDF over),
# so the chain's support is the full target support; (ii) a state
# outside both branches' realized support can still be LEFT (its
# proposal density q(z) >= u_mass/K > 0 keeps the acceptance ratio
# finite and the realized-width correction honest). 1/64 costs <2% of
# proposal draws; the MH correction absorbs the quality loss.
_SPARSE_UNIFORM_FRAC = 1.0 / 64.0


def make_sparse_block_step(*, alpha: float, eta: float, v_eta: float,
                           k_topics: int, n_mh: int,
                           tables: SparseTables):
    """The sparse-arm block step: for each token, `n_mh` independence-
    sampler MH moves whose proposal mixes (i) the doc's stale top-A
    active-topic mass — (n_dk-ish) x phi-stale over the compacted
    block, O(A) — (ii) the dense-phi remainder alpha * phi-stale drawn
    by CDF bisection, O(log K), and (iii) a thin uniform escape branch
    (_SPARSE_UNIFORM_FRAC) that keeps every topic reachable under f32;
    acceptance evaluates the FRESH blocked target at just the two
    topics involved, O(1) gathers. The acceptance ratio uses the
    REALIZED f32 proposal densities — the exact cumsum interval widths
    the inverse-CDF draws land in, not the ideal per-topic masses — so
    q() in the ratio is the distribution the sampler actually draws
    from and the corrected chain's stationary distribution matches the
    dense arm's block-stale conditional (counts exclude the token's
    own sweep-start assignment, stale w.r.t. block-mates) up to the
    uniform-draw quantization every sampler shares. Count updates are
    rank-1 scalar scatters — O(1) per token, not a [B,K] one-hot."""
    k = k_topics
    a_width = tables.act_ids.shape[1]
    cdf_flat = tables.phi_cdf.reshape(-1)
    nwk_stale = tables.nwk.reshape(-1).astype(jnp.float32)
    nk_stale = tables.nk.astype(jnp.float32)

    def block_step(carry, xs):
        n_dk, n_wk, n_k, key = carry
        d, w, m, z_old = xs
        key, skey = jax.random.split(key)   # same carry key stream as
        #                                     the dense arm
        b = d.shape[0]
        u = jax.random.uniform(skey, (n_mh, b, 3), dtype=jnp.float32,
                               minval=1e-38)
        valid = m > 0.0
        zf = jnp.where(valid, z_old, 0)     # gather-safe padding index

        # Per-token stale doc-side block: top-A ids/counts + their
        # stale phi values — the O(A) "topics touched" work. The
        # REALIZED per-slot proposal masses are the f32 cumsum interval
        # widths (exact subtractions), which is what the inverse-CDF
        # draw below actually samples; they are what q() must charge.
        a_ids = tables.act_ids[d]                       # [B, A]
        a_cnt = tables.act_cnt[d]                       # [B, A]
        phi_a = ((jnp.take(nwk_stale, w[:, None] * k + a_ids) + eta)
                 / (jnp.take(nk_stale, a_ids) + v_eta))
        s_cum = jnp.cumsum(a_cnt * phi_a, axis=1)
        s_width = jnp.diff(s_cum, axis=1,
                           prepend=jnp.zeros((b, 1), jnp.float32))
        s_mass = s_cum[:, -1]                           # [B]
        q_w = jnp.take(cdf_flat, w * k + (k - 1))       # row total
        dense_mass = alpha * q_w
        u_mass = jnp.float32(_SPARSE_UNIFORM_FRAC) * (s_mass + dense_mass)
        tot_mass = s_mass + dense_mass + u_mass

        # Fresh target (counts exclude the token's own sweep-start
        # assignment z_old — the same exclusion the dense arm applies
        # via its one-hot subtraction), evaluated at single topics.
        # Gather int32 FIRST, convert the [B]-sized result: casting the
        # live [D,K]/[V,K] here would materialize full f32 copies every
        # block, swamping the arm's O(K_active)-per-token traffic.
        ndk_flat = n_dk.reshape(-1)
        nwk_flat = n_wk.reshape(-1)

        def target(kk):
            e = (kk == zf).astype(jnp.int32)
            ndk = (jnp.take(ndk_flat, d * k + kk) - e).astype(jnp.float32)
            nwk = (jnp.take(nwk_flat, w * k + kk) - e).astype(jnp.float32)
            nk = (jnp.take(n_k, kk) - e).astype(jnp.float32)
            return ((ndk + alpha) * jnp.maximum(nwk + eta, 1e-10)
                    / (nk + v_eta))

        def proposal_weight(kk):
            """REALIZED unnormalized mixture density at kk: the f32
            interval widths the three branches actually draw — doc
            block slots matching kk (zero-count slots have exactly
            zero width), the word's CDF row interval at kk, and the
            uniform escape floor. Always >= u_mass/K > 0."""
            hit = a_ids == kk[:, None]
            doc_term = jnp.sum(jnp.where(hit, s_width, 0.0), axis=1)
            hi = jnp.take(cdf_flat, w * k + kk)
            lo = jnp.where(kk > 0,
                           jnp.take(cdf_flat, w * k
                                    + jnp.maximum(kk - 1, 0)), 0.0)
            return doc_term + alpha * (hi - lo) + u_mass / k

        def mh_step(i, carry_z):
            z_cur, t_cur, q_cur = carry_z
            u_sel, u_pos, u_acc = u[i, :, 0], u[i, :, 1], u[i, :, 2]
            # Branch pick + draw. Doc branch: inverse-CDF over the
            # [B, A] compacted block. Dense branch: bisect the word's
            # stale CDF row. Uniform branch: floor(u*K).
            t_s = u_pos * s_mass
            j = jnp.sum((s_cum < t_s[:, None]).astype(jnp.int32), axis=1)
            j = jnp.minimum(j, a_width - 1)
            k_sparse = jnp.take_along_axis(a_ids, j[:, None], axis=1)[:, 0]
            pos = cdf_lower_bound(cdf_flat, w, u_pos * q_w, k)
            k_dense = jnp.minimum(pos, k - 1)
            k_unif = jnp.minimum((u_pos * k).astype(jnp.int32), k - 1)
            t_sel = u_sel * tot_mass
            k_prop = jnp.where(t_sel < s_mass, k_sparse,
                               jnp.where(t_sel < s_mass + dense_mass,
                                         k_dense, k_unif))
            # Independence-sampler acceptance: pi(k')q(z) / pi(z)q(k').
            # target/proposal of the CURRENT state ride the loop carry
            # (counts are frozen for the token's whole MH cycle, so the
            # carried values are bit-identical to recomputation at half
            # the gather traffic of this gather-bound arm).
            t_p, q_p = target(k_prop), proposal_weight(k_prop)
            ratio = t_p * q_cur / jnp.maximum(t_cur * q_p, 1e-38)
            acc = u_acc < ratio
            return (jnp.where(acc, k_prop, z_cur),
                    jnp.where(acc, t_p, t_cur),
                    jnp.where(acc, q_p, q_cur))

        z_cur, _, _ = jax.lax.fori_loop(
            0, n_mh, mh_step, (zf, target(zf), proposal_weight(zf)))
        z_new = jnp.where(valid, z_cur, z_old)   # padding keeps sentinel

        # Rank-1 exact int32 updates; padding (index K) drops out of
        # bounds. Collisions within the block serialize inside the
        # scatter exactly as the dense delta's row updates do.
        one = jnp.ones_like(z_new)
        n_dk = (n_dk.at[d, z_new].add(one, mode="drop")
                     .at[d, z_old].add(-one, mode="drop"))
        n_wk = (n_wk.at[w, z_new].add(one, mode="drop")
                     .at[w, z_old].add(-one, mode="drop"))
        n_k = (n_k.at[z_new].add(one, mode="drop")
                   .at[z_old].add(-one, mode="drop"))
        return (n_dk, n_wk, n_k, key), z_new

    return block_step


def _squeeze_one_chain(kernel):
    """`kernel` with a vmap rule of its own: an axis of one (the
    engines' chain axis, every cell's) is taken off the arguments and
    put back on the results, not vmapped over; a longer one is
    `jax.vmap`'s as before. The chip lays the block's [B, K] arrays out
    with the tokens along the lanes, and [1, B, K] ones K to a 128-lane
    row: under a chain vmap over one chain the sampler's arithmetic and
    the packed form's pick ran on rows 84% padding, and the packed
    sweep read the rows form's 5.74 s (PERF.md section 6, PR 34)."""
    from jax.custom_batching import custom_vmap

    # A call of its own: the scopes opened inside keep their names (one
    # opened under a vmap reads `vmap(onix...)`, which no reader books).
    kernel = jax.jit(kernel)
    wrapped = custom_vmap(kernel)

    @wrapped.def_vmap
    def rule(axis_size, in_batched, *args):
        if axis_size == 1:
            outs = kernel(*(a[0] if b else a
                            for a, b in zip(args, in_batched)))
            outs = tuple(o[None] for o in outs)
        else:
            outs = jax.vmap(kernel, in_axes=[0 if b else None
                                             for b in in_batched])(*args)
        return outs, (True,) * len(outs)

    return wrapped


def make_sweep_kernel(*, alpha: float, eta: float, n_vocab: int,
                      k_topics: int, nwk_form: str | None = None,
                      ndk_form: str | None = None,
                      sampler_form: str | None = None,
                      sparse_active: int = 0, sparse_mh: int = 2,
                      sampler: str | None = None):
    """One FULL sweep over blocked tokens with the sampler-form gate
    applied — the shared kernel behind sweep(), the sharded engine's
    per-device sweep, and the dp=1 fast path, so the gate can never
    diverge between engines.

    Returns fn(z, n_dk, n_wk, n_k, key, docs, words, mask) ->
    (z, n_dk, n_wk, n_k, key). The sparse form rebuilds its stale
    proposal tables from the sweep-start counts on every call (table
    freshness is a per-sweep property, independent of how many sweeps
    a dispatch fuses). `nwk_form` and `sampler` are make_block_step's
    test pins, handed on to the dense form; `ndk_form` is the tests'
    pin of `select_ndk_form`. Where that resolves to "packed" the
    dense form packs n_dk at the sweep's start, scans the blocks over
    the packed table and unpacks at its end: n_dk is [D, K] on both
    sides of the kernel whatever the form."""
    form = _resolved_sampler_form(sampler_form, k_topics=k_topics)
    if form == "dense":
        _, group = select_ndk_form(backend=jax.default_backend(),
                                   k_topics=k_topics, ndk_form=ndk_form)
        block_step = make_block_step(alpha=alpha, eta=eta,
                                     n_vocab=n_vocab, k_topics=k_topics,
                                     nwk_form=nwk_form, sampler=sampler,
                                     ndk_group=group)

        def kernel(z, n_dk, n_wk, n_k, key, docs, words, mask):
            n_docs = n_dk.shape[0]
            if group > 1:
                with device_scope("onix.sweep.pack"):
                    n_dk = pack_ndk(n_dk, group)
            # onix.sweep.blocks names the loop's own work (a block's
            # docs, words, mask and z sliced out, its new z written
            # back); the block step's ops keep their inner scopes.
            with device_scope("onix.sweep.blocks"):
                (n_dk, n_wk, n_k, key), z = jax.lax.scan(
                    block_step, (n_dk, n_wk, n_k, key),
                    (docs, words, mask, z))
            if group > 1:
                with device_scope("onix.sweep.pack"):
                    n_dk = unpack_ndk(n_dk, n_docs, k_topics, group)
            return z, n_dk, n_wk, n_k, key
        return _squeeze_one_chain(kernel) if group > 1 else kernel

    a = resolve_sparse_active(k_topics, sparse_active)
    v_eta = n_vocab * eta

    def kernel(z, n_dk, n_wk, n_k, key, docs, words, mask):
        tables = build_sparse_tables(n_dk, n_wk, n_k, eta=eta,
                                     v_eta=v_eta, n_active=a)
        block_step = make_sparse_block_step(
            alpha=alpha, eta=eta, v_eta=v_eta, k_topics=k_topics,
            n_mh=sparse_mh, tables=tables)
        (n_dk, n_wk, n_k, key), z = jax.lax.scan(
            block_step, (n_dk, n_wk, n_k, key), (docs, words, mask, z))
        return z, n_dk, n_wk, n_k, key
    return kernel


def make_block_step(*, alpha: float, eta: float, n_vocab: int,
                    k_topics: int, nwk_form: str | None = None,
                    sampler: str | None = None, ndk_group: int = 1):
    """The collapsed-Gibbs block sampler shared by the single-device and
    sharded engines — one definition so the documented dp=1 equivalence
    can never silently diverge.

    carry = (n_dk, n_wk, n_k, key); xs = (docs, words, mask, z_old).

    `nwk_form` is the tests' pin of the n_wk count-update form
    ("scatter" | "matmul"; the bit-identity tests compare the two
    through it). No engine passes it: None lets `select_nwk_form` pick
    at trace time from the backend and the block's static shapes. Both
    forms produce bit-identical int32 counts and the same z stream.

    `sampler`: force the categorical draw form ("gumbel" | "race");
    None keeps the per-backend pick (gumbel on accelerators, race on
    CPU). Test-only knob: it lets CPU tier-1 assert the TPU sampler's
    math bit-for-bit.

    `ndk_group`: the documents a row of the carry's n_dk holds. 1, the
    bare call's, is [D, K]; G > 1 is `pack_ndk`'s [ceil(D/G), 128],
    which `make_sweep_kernel` hands in where `select_ndk_form` says
    so. The [B, K] counts read, the draw and the other tables are the
    same to the bit.
    """
    if not 1 <= ndk_group <= max(1, _NDK_LANES // k_topics):
        raise ValueError(
            f"ndk_group {ndk_group}: K={k_topics} fits "
            f"{_NDK_LANES // k_topics} documents a {_NDK_LANES}-lane row")
    v_eta = n_vocab * eta
    # Sampler form is picked once at trace time; it is a platform
    # property, not runtime state, so the traced program is static.
    backend = jax.default_backend()
    if sampler is None:
        use_gumbel = backend not in ("cpu",)
    elif sampler in ("gumbel", "race"):
        use_gumbel = sampler == "gumbel"
    else:
        raise ValueError(f"sampler must be gumbel|race, got {sampler!r}")

    def block_step(carry, xs):
        n_dk, n_wk, n_k, key = carry
        d, w, m, z_old = xs
        key, skey = jax.random.split(key)
        # n_wk's shape is static under trace, so the form resolves to
        # ONE compiled path.
        form = select_nwk_form(backend=backend, block_size=w.shape[0],
                               n_rows=n_wk.shape[0], nwk_form=nwk_form)
        if form == "matmul" and w.shape[0] >= (1 << 24):
            raise ValueError(
                f"nwk matmul form with block size {w.shape[0]} >= 2^24: "
                "the one-hot matmul's f32 accumulation is no longer "
                "bit-exact at this block size")
        # Device scopes onix.sweep.* (docs/OBSERVABILITY.md): the
        # profiler books each op's time to the scope it was traced in.
        with device_scope("onix.sweep.gather"):
            oh_old = _one_hot(z_old, k_topics)      # zero row for padding
            ohf = oh_old.astype(jnp.float32)
            # Counts excluding each token's own current assignment.
            if ndk_group > 1:
                # The document's row of ndk_group documents, then its
                # own K lanes of it.
                row, slot = d // ndk_group, d % ndk_group
                ndk_d = _pick_lanes(n_dk[row], slot, k_topics, ndk_group)
            else:
                ndk_d = n_dk[d]
            ndk = ndk_d.astype(jnp.float32) - ohf
            nwk = n_wk[w].astype(jnp.float32) - ohf
            nk = n_k.astype(jnp.float32)[None, :] - ohf
        # Categorical sampling — two statistically identical forms,
        # chosen per backend at trace time:
        #   * CPU: exponential race z = argmax p_k/e_k, e~Exp(1) — the
        #     Gumbel-argmax trick in LINEAR space at one log per
        #     element instead of four (the transcendentals dominate on
        #     CPU). Per-element products keep full relative precision —
        #     no cumsum, so no rare-topic rounding (why inverse-CDF was
        #     rejected: a linear f32 cumsum makes transitions to topics
        #     below ~2^-24 of the total exactly impossible).
        #   * TPU: classic log-space Gumbel-argmax — the sweep is
        #     scatter-bound there (onix.sweep.sample is 16% of a sweep:
        #     PERF.md section 5), so the extra transcendentals hide.
        with device_scope("onix.sweep.sample"):
            if use_gumbel:
                logp = (jnp.log(ndk + alpha)
                        + jnp.log(jnp.maximum(nwk + eta, 1e-10))
                        - jnp.log(nk + v_eta))
                g = jax.random.gumbel(skey, logp.shape, dtype=jnp.float32)
                z_new = jnp.argmax(logp + g, axis=-1).astype(jnp.int32)
            else:
                p = ((ndk + alpha) * jnp.maximum(nwk + eta, 1e-10)
                     / (nk + v_eta))
                u = jax.random.uniform(skey, p.shape, dtype=jnp.float32,
                                       minval=1e-38)
                z_new = jnp.argmax(p / -jnp.log(u),
                                   axis=-1).astype(jnp.int32)
            z_new = jnp.where(m > 0, z_new, z_old)  # padding keeps sentinel
            # Dense one-hot delta rows, NOT per-element scalar scatters:
            # XLA's TPU scatter adds a whole row an index, so the dense
            # delta costs one add a token where the "only 2 of K entries
            # change" rank-1 formulation costs two (on the chip, into
            # flow-fit's n_dk: 12.7 ns a K-lane row, 9.3 ns a scalar
            # into the flat table and so 18.6 a moved token; PERF.md
            # section 6, PR 32).
            delta = _one_hot(z_new, k_topics) - oh_old  # int32-exact
        with device_scope("onix.sweep.scatter"):
            if ndk_group > 1:
                # The same two entries, in the document's lanes of its
                # 128-lane row.
                n_dk = n_dk.at[row].add(
                    _lane_delta(slot, z_old, z_new, k_topics))
            else:
                n_dk = n_dk.at[d].add(delta)
        with device_scope("onix.sweep.nwk"):
            if form == "matmul":
                oh_w = jax.nn.one_hot(w, n_wk.shape[0], dtype=jnp.bfloat16)
                d_wk = jax.lax.dot_general(
                    oh_w, delta.astype(jnp.bfloat16),
                    (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                n_wk = n_wk + d_wk.astype(jnp.int32)
            else:
                n_wk = n_wk.at[w].add(delta)
            n_k = n_k + delta.sum(axis=0, dtype=jnp.int32)
        return (n_dk, n_wk, n_k, key), z_new

    return block_step


def sweep(
    state: GibbsState,
    doc_blocks: jax.Array,   # int32 [n_blocks, B]
    word_blocks: jax.Array,  # int32 [n_blocks, B]
    mask_blocks: jax.Array,  # float32 [n_blocks, B]
    *,
    alpha: float,
    eta: float,
    n_vocab: int,
    accumulate,
    sampler_form: str | None = None,
    sparse_active: int = 0,
    sparse_mh: int = 2,
) -> GibbsState:
    """One full Gibbs sweep over all token blocks (jit-friendly).

    `accumulate` may be a Python bool OR a traced 0-d array — the fused
    superstep derives it from the sweep counter on device. Both forms
    produce bit-identical updates: the accumulate fold is `acc + a * n`
    with a in {0.0, 1.0} and n >= 0, so a=0 adds an exact +0.0 whether
    or not XLA can constant-fold it away.

    `sampler_form`/`sparse_active`/`sparse_mh` gate the r11 sparse
    O(K_active) arm (make_sweep_kernel); None defers to the measured
    per-backend _SAMPLER_SPARSE_MIN_K gate (dense on unmeasured
    platforms and everywhere below the crossover)."""
    k_topics = state.n_dk.shape[1]
    kernel = make_sweep_kernel(alpha=alpha, eta=eta, n_vocab=n_vocab,
                               k_topics=k_topics,
                               sampler_form=sampler_form,
                               sparse_active=sparse_active,
                               sparse_mh=sparse_mh)
    z, n_dk, n_wk, n_k, key = kernel(
        state.z, state.n_dk, state.n_wk, state.n_k, state.key,
        doc_blocks, word_blocks, mask_blocks)
    do_acc = jnp.asarray(accumulate, jnp.float32)
    return GibbsState(
        z=z, n_dk=n_dk, n_wk=n_wk, n_k=n_k, key=key,
        acc_ndk=state.acc_ndk + do_acc * n_dk.astype(jnp.float32),
        acc_nwk=state.acc_nwk + do_acc * n_wk.astype(jnp.float32),
        n_acc=state.n_acc + jnp.asarray(accumulate, jnp.int32),
    )


# Auto superstep size (config.lda.superstep == 0): 10 sweeps per fused
# program reproduces the old fit loop's every-10-sweeps ll cadence
# (exactly, when checkpointing is off; checkpoint boundaries further
# split segments, making the cadence denser, never sparser) while
# amortizing the per-dispatch host cost 10x (its price on the chip
# is not measured).
SUPERSTEP_DEFAULT = 10


def superstep(
    state: GibbsState,
    doc_blocks: jax.Array,
    word_blocks: jax.Array,
    mask_blocks: jax.Array,
    *,
    alpha: float,
    eta: float,
    n_vocab: int,
    burn_in: int,
    start_sweep,
    n_steps: int,
    sampler_form: str | None = None,
    sparse_active: int = 0,
    sparse_mh: int = 2,
) -> GibbsState:
    """Chain `n_steps` full sweeps inside ONE lax.scan — one dispatch,
    one compiled program per distinct n_steps (static), any start sweep
    (traced). The burn-in accumulate phase is folded into the scan
    carry: sweep start_sweep + i accumulates iff it is past burn_in,
    decided on device, so the posterior-mean sums never leave the chip
    between sweeps. Bit-identical to n_steps sequential sweep()
    dispatches under the same key stream (tests/test_gibbs.py) — for
    the sparse arm too: its stale proposal tables are rebuilt per
    SWEEP inside the fused program (sweep() calls make_sweep_kernel),
    so the chain is independent of the superstep size S."""
    start_sweep = jnp.asarray(start_sweep, jnp.int32)

    def one(st, i):
        return sweep(st, doc_blocks, word_blocks, mask_blocks,
                     alpha=alpha, eta=eta, n_vocab=n_vocab,
                     accumulate=start_sweep + i >= burn_in,
                     sampler_form=sampler_form,
                     sparse_active=sparse_active,
                     sparse_mh=sparse_mh), None

    state, _ = jax.lax.scan(one, state,
                            jnp.arange(n_steps, dtype=jnp.int32))
    return state


def run_fit_segments(state, start: int, segments, *, superstep_fn,
                     initial_ll_fn, checkpoint_every: int, checkpoint_dir,
                     save_fn, fault_sweep: int | None, notify):
    """Drive the fused-superstep fit loop — ONE implementation shared by
    GibbsLDA and ShardedGibbsLDA so segment/ll/checkpoint/fault
    semantics can never diverge between the engines.

    Per segment: one superstep dispatch (the first also evaluates the
    pre-sweep ll on device — no standalone warm-up dispatch), an
    ll_history entry at the boundary, then checkpoint save, fault
    raise, and callback in that order (the order the pre-superstep
    loops used). `superstep_fn(state, start_sweep, n_steps,
    with_initial_ll)` returns (state, ll) or (state, ll0, ll);
    `initial_ll_fn(state)` serves the no-segments case (resume landed
    at/after n_sweeps); `save_fn(state, sweep)` persists a checkpoint;
    `notify(sweep, state, ll)` adapts each engine's public callback
    signature. Returns (state, ll_history)."""
    from onix import checkpoint as ckpt
    from onix.utils import faults, telemetry

    ll_history: list[tuple[int, float]] = []
    if not segments:
        # Nothing left to sweep: the pre-sweep ll point still belongs
        # in the history.
        ll_history.append((start - 1, float(initial_ll_fn(state))))
    for i, (seg_start, seg_len) in enumerate(segments):
        # The dispatch returns with the device still running; the host
        # then blocks in float(ll): two spans, so a trace tells them
        # apart (docs/OBSERVABILITY.md).
        with telemetry.TRACER.span("fit.superstep", start=seg_start,
                                   sweeps=seg_len, with_initial_ll=i == 0):
            state, *lls = superstep_fn(state, seg_start, seg_len, i == 0)
        with telemetry.TRACER.span("fit.wait"):
            lls = [float(ll) for ll in lls]
        if i == 0:
            ll_history.append((seg_start - 1, lls[0]))
        s = seg_start + seg_len - 1
        ll_history.append((s, lls[-1]))
        if (checkpoint_dir is not None and checkpoint_every > 0
                and (s + 1) % checkpoint_every == 0):
            with telemetry.TRACER.span("fit.checkpoint", sweep=s):
                save_fn(state, s)
        if fault_sweep is not None and s == fault_sweep:
            raise ckpt.SimulatedPreemption(
                f"fault injected after sweep {s} "
                f"(checkpoint_dir={checkpoint_dir})")
        # Declarative chaos plan (ONIX_FAULT_PLAN `fit:sweep@N=...`):
        # fires at the first superstep boundary at or after sweep N —
        # the generalized form of the legacy ONIX_FAULT_SWEEP hook.
        faults.fire("fit", "sweep", index=s)
        if notify is not None:
            with telemetry.TRACER.span("fit.notify", sweep=s):
                notify(s, state, ll_history[-1][1])
    return state, ll_history


def plan_segments(start: int, n_sweeps: int, superstep_size: int, *,
                  checkpoint_every: int = 0,
                  fault_sweep: int | None = None,
                  per_sweep: bool = False) -> list[tuple[int, int]]:
    """Split sweeps [start, n_sweeps) into fused superstep segments.

    Every segment ends exactly at a host-interaction boundary — a
    checkpoint sweep ((s+1) % checkpoint_every == 0), the fault-
    injection sweep, the final sweep — or at the superstep cap, so a
    checkpoint can never be demanded mid-superstep and every resume
    point is an exact sweep boundary. `per_sweep` collapses segments to
    length 1 (a per-sweep callback is registered). Returns a list of
    (segment_start, segment_length)."""
    cap = 1 if per_sweep else max(1, int(superstep_size))
    segs: list[tuple[int, int]] = []
    s = start
    while s < n_sweeps:
        end = min(s + cap, n_sweeps)
        if checkpoint_every and checkpoint_every > 0:
            next_ckpt = s + checkpoint_every - (s % checkpoint_every)
            end = min(end, next_ckpt)
        if fault_sweep is not None and s <= fault_sweep < end - 1:
            end = fault_sweep + 1
        segs.append((s, end - s))
        s = end
    return segs


def posterior_estimates(
    state: GibbsState, *, alpha: float, eta: float
) -> tuple[jax.Array, jax.Array]:
    """(theta [D,K], phi_wk [V,K]) from averaged (or instantaneous) counts."""
    use_acc = state.n_acc > 0
    denom = jnp.maximum(state.n_acc.astype(jnp.float32), 1.0)
    ndk = jnp.where(use_acc, state.acc_ndk / denom, state.n_dk.astype(jnp.float32))
    nwk = jnp.where(use_acc, state.acc_nwk / denom, state.n_wk.astype(jnp.float32))
    theta = (ndk + alpha) / (ndk.sum(-1, keepdims=True) + ndk.shape[1] * alpha)
    nk = nwk.sum(axis=0, keepdims=True)
    phi_wk = (nwk + eta) / (nk + nwk.shape[0] * eta)
    return theta, phi_wk


def log_likelihood(
    theta: jax.Array, phi_wk: jax.Array,
    doc_blocks: jax.Array, word_blocks: jax.Array, mask_blocks: jax.Array,
) -> jax.Array:
    """Mean per-token log p(w|d) — the convergence series the reference
    prints to likelihood.dat (SURVEY.md §5.4). Accumulated block by
    block: gathering theta/phi rows for the whole corpus at once
    materializes an [N, K]-padded temp that OOMs HBM past ~10M tokens."""
    def block(carry, xs):
        d, w, m = xs
        p = jnp.sum(theta[d] * phi_wk[w], axis=-1)
        lp = jnp.log(jnp.maximum(p, 1e-30)) * m
        return (carry[0] + lp.sum(), carry[1] + m.sum()), None

    with device_scope("onix.sweep.loglik"):
        (total, n), _ = jax.lax.scan(
            block, (jnp.float32(0.0), jnp.float32(0.0)),
            (doc_blocks, word_blocks, mask_blocks))
        return total / jnp.maximum(n, 1.0)


# Relative predictive-ll band within which a different chain with the
# same stationary target (the sparse arm, the async merge) must land on
# its reference, and below which a refit's final ll may not fall under
# its initial one (pipelines/daily.py, pipelines/fleet.py).
LL_PARITY_BAND = 0.05


class GibbsLDA:
    """Host-side driver around the functional kernel.

    Equivalent role to oni-lda-c's `lda estimate` entry point, but runs
    in-process on the accelerator instead of via ssh + mpiexec
    (SURVEY.md §3.1 hot loop #2).
    """

    def __init__(self, config: LDAConfig, n_docs: int, n_vocab: int):
        config.validate()
        self.config = config
        self.n_docs = n_docs
        self.n_vocab = n_vocab
        chains = config.n_chains
        # Sampler form resolves ONCE here (resolve_sampler: config,
        # then ONIX_SAMPLER_FORM, then the measured gate) — the
        # RESOLVED value feeds both the compiled programs and the
        # checkpoint fingerprint, so the two can never disagree and a
        # resume across an arm change is refused (the sparse arm is a
        # different chain, not a bit-identical form like n_wk's two).
        self.sampler_form, self.sparse_active, sampler_kw = \
            resolve_sampler(config, k_topics=config.n_topics)
        base_sweep = functools.partial(
            sweep, alpha=config.alpha, eta=config.eta, n_vocab=n_vocab,
            **sampler_kw)
        base_super = functools.partial(
            superstep, alpha=config.alpha, eta=config.eta,
            n_vocab=n_vocab, burn_in=config.burn_in, **sampler_kw)
        base_est = functools.partial(
            posterior_estimates, alpha=config.alpha, eta=config.eta)
        # donate_argnums=(0,): the incoming GibbsState's buffers are
        # dead the moment the dispatch returns (every caller rebinds),
        # so XLA reuses them for the output counts instead of copying
        # the [D,K]+[V,K] tables every sweep, as the sharded engine
        # does.
        if chains == 1:
            self._sweep = jax.jit(base_sweep,
                                  static_argnames=("accumulate",),
                                  donate_argnums=(0,))
            self._estimates = jax.jit(base_est)
            self._ll = jax.jit(log_likelihood)

            # The fit loop's unit of dispatch: n_steps sweeps chained in
            # one program, with the boundary log-likelihood fused in —
            # the ll gathers run on device right behind the last sweep
            # instead of costing two more dispatches.
            # `with_initial_ll` additionally evaluates ll on the
            # INCOMING state (fit's pre-sweep ll_history point), so the
            # whole first segment — initial ll, S sweeps, boundary ll —
            # is ONE dispatch and one host sync.
            def superstep_ll(state, d, w, m, start, n_steps,
                             with_initial_ll=False):
                ll0 = None
                if with_initial_ll:
                    theta0, phi0 = base_est(state)
                    ll0 = log_likelihood(theta0, phi0, d, w, m)
                st = base_super(state, d, w, m, start_sweep=start,
                                n_steps=n_steps)
                theta, phi = base_est(st)
                ll = log_likelihood(theta, phi, d, w, m)
                return ((st, ll0, ll) if with_initial_ll else (st, ll))
        else:
            # vmap over the chain axis of the state; token blocks are
            # shared (broadcast). theta/phi keep a leading chain axis —
            # scoring averages probabilities over it.
            def sweep_chains(state, d, w, m, accumulate):
                return jax.vmap(lambda s: base_sweep(
                    s, d, w, m, accumulate=accumulate))(state)

            def ll_chains(theta, phi_wk, d, w, m):
                return jax.vmap(lambda t, p: log_likelihood(
                    t, p, d, w, m))(theta, phi_wk).mean()

            self._sweep = jax.jit(sweep_chains,
                                  static_argnames=("accumulate",),
                                  donate_argnums=(0,))
            self._estimates = jax.jit(jax.vmap(base_est))
            self._ll = jax.jit(ll_chains)

            def superstep_ll(state, d, w, m, start, n_steps,
                             with_initial_ll=False):
                ll0 = None
                if with_initial_ll:
                    theta0, phi0 = jax.vmap(base_est)(state)
                    ll0 = jax.vmap(lambda t, p: log_likelihood(
                        t, p, d, w, m))(theta0, phi0).mean()
                st = jax.vmap(lambda s: base_super(
                    s, d, w, m, start_sweep=start, n_steps=n_steps))(state)
                theta, phi = jax.vmap(base_est)(st)
                ll = jax.vmap(lambda t, p: log_likelihood(
                    t, p, d, w, m))(theta, phi).mean()
                return ((st, ll0, ll) if with_initial_ll else (st, ll))

        self._superstep = jax.jit(
            superstep_ll, static_argnames=("n_steps", "with_initial_ll"),
            donate_argnums=(0,))

    def prepare(self, corpus: Corpus, shuffle: bool = True):
        if shuffle:
            corpus = corpus.shuffled(self.config.seed)
        block = min(self.config.block_size, max(corpus.n_tokens, 1))
        padded, mask = corpus.padded(block)
        nb = padded.n_tokens // block
        return (
            jnp.asarray(padded.doc_ids.reshape(nb, block)),
            jnp.asarray(padded.word_ids.reshape(nb, block)),
            jnp.asarray(mask.reshape(nb, block)),
        )

    def fit(self, corpus: Corpus, n_sweeps: int | None = None,
            callback=None, checkpoint_dir=None, resume: bool = True,
            fault_inject_sweep: int | None = None) -> dict:
        """Run the fit loop as fused supersteps: sweeps are chained S at
        a time inside one jitted program (`superstep`), with the burn-in
        accumulate fold and the boundary log-likelihood on device — one
        dispatch and one host sync per S sweeps instead of per sweep.
        Segment boundaries land exactly on checkpoint/fault/final sweeps
        (`plan_segments`), and a per-sweep `callback` collapses segments
        to single sweeps, so host-visible behavior at every boundary is
        unchanged; the chained loop is bit-identical to sweep-at-a-time
        (tested). Like the sharded engine, the dispatch
        donates the incoming state's buffers: a `callback` that wants
        to RETAIN anything across sweeps must materialize it
        (np.asarray) inside the callback — holding the state's jax
        arrays past the next dispatch reads deleted buffers.

        Optionally checkpoint every `config.checkpoint_every` sweeps
        into `checkpoint_dir` and resume from the newest matching
        checkpoint there (SURVEY.md §5.3-5.4: resume-on-preemption).
        Resumed runs are bit-identical to uninterrupted ones — the sweep
        is a pure function of the state, and the superstep size is part
        of the checkpoint fingerprint so a resume under a different S is
        refused rather than producing a different ll cadence.

        `fault_inject_sweep` (or env ONIX_FAULT_SWEEP) simulates a
        preemption by raising SimulatedPreemption right after completing
        that sweep — the §5.3 fault-injection hook; a caller that
        retries `fit` resumes from the last checkpoint."""
        import os

        from onix import checkpoint as ckpt

        if fault_inject_sweep is None:
            env = os.environ.get("ONIX_FAULT_SWEEP")
            fault_inject_sweep = int(env) if env else None

        cfg = self.config
        n_sweeps = cfg.n_sweeps if n_sweeps is None else n_sweeps
        S = cfg.superstep or SUPERSTEP_DEFAULT
        docs, words, mask = self.prepare(corpus)
        # The RESOLVED sparse arm joins the identity (an auto gate
        # flipping arms between runs — new measured table, different
        # backend — must refuse the resume, not continue a dense chain
        # with sparse draws); dense contributes nothing, so pre-r11
        # dense checkpoints keep resuming.
        fp = ckpt.fingerprint(cfg, self.n_docs, self.n_vocab,
                              corpus.n_tokens, superstep=S,
                              extra={**sampler_fingerprint(
                                         self.sampler_form,
                                         self.sparse_active,
                                         cfg.sparse_mh),
                                     # Merge form: inert on one device
                                     # (no peers), but the identity rule
                                     # is shared with the sharded engine
                                     # — a merge-form/τ change refuses
                                     # the resume on BOTH engines.
                                     **merge_fingerprint(
                                         cfg.merge_form,
                                         cfg.merge_staleness)})
        # Per-fingerprint subdir: checkpoints of runs with a different
        # identity can neither be adopted nor pruned by this run.
        if checkpoint_dir is not None:
            import pathlib
            checkpoint_dir = pathlib.Path(checkpoint_dir) / fp
        start = 0
        state = None
        if checkpoint_dir is not None and resume:
            saved = ckpt.load_latest(checkpoint_dir)
            if saved is not None and saved.meta.get("fingerprint") == fp:
                state = GibbsState(**{k: jnp.asarray(v)
                                      for k, v in saved.arrays.items()})
                start = saved.sweep + 1
        if state is None:
            if cfg.n_chains == 1:
                state = init_state(docs, words, mask, self.n_docs,
                                   self.n_vocab, cfg.n_topics, cfg.seed)
            else:
                state = init_chains(docs, words, mask, self.n_docs,
                                    self.n_vocab, cfg.n_topics, cfg.seed,
                                    cfg.n_chains)
        segments = plan_segments(
            start, n_sweeps, S,
            checkpoint_every=(cfg.checkpoint_every
                              if checkpoint_dir is not None else 0),
            fault_sweep=fault_inject_sweep,
            per_sweep=callback is not None)
        state, ll_history = run_fit_segments(
            state, start, segments,
            superstep_fn=lambda st, s0, n, init: self._superstep(
                st, docs, words, mask, s0, n_steps=n,
                with_initial_ll=init),
            initial_ll_fn=lambda st: self._ll(*self._estimates(st),
                                              docs, words, mask),
            checkpoint_every=cfg.checkpoint_every,
            checkpoint_dir=checkpoint_dir,
            save_fn=lambda st, s: ckpt.save(
                checkpoint_dir, s,
                {k: np.asarray(v) for k, v in st._asdict().items()},
                {"fingerprint": fp, "engine": "gibbs"}),
            fault_sweep=fault_inject_sweep,
            notify=(None if callback is None
                    else lambda s, st, ll: callback(s, st, ll)))
        theta, phi_wk = self._estimates(state)
        return {
            "state": state,
            # n_chains>1 stacks a leading chain axis: theta [C,D,K],
            # phi_wk [C,V,K]; scoring.score_events averages over it.
            "theta": np.asarray(theta),
            "phi_wk": np.asarray(phi_wk),   # [V,K]; phi[k,v] = phi_wk[v,k]
            "ll_history": ll_history,
        }
