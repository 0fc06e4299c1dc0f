"""Pallas TPU kernel: fused categorical sample + count-merge block step.

docs/PERF.md measured (twice — the r2 sampling-only ablation and the r7
fit-gap harness) that the n_wk scatter-add IS the Gibbs sweep's ceiling
on TPU: on the judged product vocabularies (V~500, block 2^17) every
row of the count table collects 128-250 colliding updates per block and
XLA serializes them. The r7 answer out-muscled the scatter with an MXU
one-hot matmul that still materializes a [B, V] one-hot in HBM. This
module is the TPU-native answer — the framework pillar named in
onix/__init__.py:5 that no code exercised until now: a Pallas kernel
that OWNS the collision-dense count update, with the same block-
parallel count-merge structure as AD-LDA (PAPERS.md, arxiv 0909.4603).

One `pallas_call` per block step, grid over tiles of the block's B
tokens. Per tile (all VMEM-resident):

  1. sampling on the VPU — the gathered n_dk[d]/n_wk[w] rows and the
     pre-generated noise come in as [tile, K] blocks, and the kernel
     runs the EXACT float ops of `lda_gibbs.make_block_step` (exclusion
     of the token's own assignment, Gumbel-argmax in log space or the
     exponential race in linear space) to draw z_new;
  2. count-merge on the MXU — the per-token delta one-hots contract
     against the tile's vocabulary one-hot ([tile, V], built and
     consumed INSIDE VMEM, never materialized to HBM) into a dense
     [V, K] per-tile partial;
  3. accumulation — the partial folds into a [V, K] int32 accumulator
     that lives in VMEM across the whole grid (constant out-block
     index map) and is flushed to HBM once, at the last tile.

There is no scatter anywhere in the n_wk update: the serialized
collision chain the r2/r7 measurements identified is gone, not merely
overpowered. The n_dk update stays an XLA scatter outside the kernel —
documents are nearly collision-free within a block (PERF.md) and the
[D, K] table is orders of magnitude too large for a dense VMEM
accumulator.

Exactness: the MXU contraction's operands are {0,1} and {-1,0,1} in
f32 and every output magnitude is bounded by the tile size (<= 1024 <<
2^24), so the per-tile partial is exact integer math; the cross-tile
accumulation is int32. Combined with noise generated OUTSIDE the
kernel from the reference's own key stream (`key, skey = split(key)`
then one draw at [B, K] — the identical sequence), the kernel is
BIT-IDENTICAL to the scatter block step IN INTERPRET MODE: same z
sequence, same counts, same accumulators (asserted in
tests/test_pallas_gibbs.py at every tested shape). COMPILED on the chip
it is not quite: Mosaic's and XLA's float ops part in the last ulp, so
~5e-7 of the tokens draw a different z at a near-tie (PR 21: 4 of 8.4M
at the judged width; PERF.md) — the count delta stays exact for the z
it drew, but whole sweeps are a different chain, and bench.py's
gibbs_sweep_pallas identity assert fails on the chip.

Interpret mode: `interpret=True` (the default off-TPU) lowers the
kernel to plain XLA ops — traceable, jittable, vmappable — so tier-1
asserts bit-identity on CPU and the same code compiles through Mosaic
on a real TPU (the `tpu`-marked tests in tests/test_pallas_gibbs.py;
what the chip said is in PERF.md).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# VMEM budget for the per-tile [tile, V] vocabulary one-hot (f32
# bytes). 2 MB leaves the rest of the ~16 MB/core for the [tile, K]
# sampling blocks (lane-padded to 128), the [V, K] accumulator, and
# double-buffered input tiles — the worked budget is in docs/PERF.md
# ("Pallas fused sample+count"). tile is clamped to [8, 1024]: 8 is
# the f32 sublane minimum, 1024 keeps the MXU contraction's per-output
# accumulation bound far under 2^24 (exact integers in f32).
_ONEHOT_VMEM_BYTES = 2 << 20
_TILE_MAX = 1024
_TILE_MIN = 8


def tile_for(n_rows: int) -> int:
    """Token-tile size for a count table of `n_rows` vocabulary rows."""
    t = _ONEHOT_VMEM_BYTES // (4 * max(n_rows, 1))
    t = max(_TILE_MIN, min(_TILE_MAX, t))
    return (t // _TILE_MIN) * _TILE_MIN


def _default_interpret() -> bool:
    """Interpret everywhere but a real TPU (Mosaic is TPU-only; the
    emulation is trace-time, so it jits/vmaps/shard_maps like any jnp
    code). ONIX_PALLAS_INTERPRET=0/1 pins either way for experiments.

    Keyed off the PHYSICAL device platform, not jax.default_backend():
    the verify/test idiom for driving TPU trace arms on CPU mocks
    default_backend (so the gumbel sampler and the density gate trace
    their TPU forms), and the kernel must keep emulating there — only
    hardware that can actually run Mosaic should compile it. A device
    probe that fails propagates: on a TPU the mode is compiled or the
    call fails, never a quiet emulation."""
    env = os.environ.get("ONIX_PALLAS_INTERPRET")
    if env in ("0", "1"):
        return env == "1"
    return jax.devices()[0].platform != "tpu"


def pallas_mode() -> str:
    """"compiled" (Mosaic) or "interpret" (XLA emulation) — the mode
    the kernels run in here; chip_smoke.py and bench.py stamp it."""
    return "interpret" if _default_interpret() else "compiled"


def _kernel(ndk_ref, nwk_ref, nk_ref, noise_ref, w_ref, z_ref, m_ref,
            z_out_ref, dwk_ref, *, tile, k_topics, n_rows, alpha, eta,
            v_eta, use_gumbel):
    i = pl.program_id(0)
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (tile, k_topics), 1)
    # Equality one-hot: the padding sentinel (z == K) matches no topic
    # column and yields a zero row, exactly like jax.nn.one_hot's
    # out-of-range behavior in the reference step.
    oh_old = (z_ref[:] == iota_k).astype(jnp.int32)
    ohf = oh_old.astype(jnp.float32)
    # The same float ops, in the same order, on the same values as
    # lda_gibbs.make_block_step — bit-identity depends on it.
    ndk = ndk_ref[:].astype(jnp.float32) - ohf
    nwk = nwk_ref[:].astype(jnp.float32) - ohf
    nk = nk_ref[:].astype(jnp.float32) - ohf
    if use_gumbel:
        logp = (jnp.log(ndk + alpha)
                + jnp.log(jnp.maximum(nwk + eta, 1e-10))
                - jnp.log(nk + v_eta))
        z_new = jnp.argmax(logp + noise_ref[:], axis=-1).astype(jnp.int32)
    else:
        p = ((ndk + alpha) * jnp.maximum(nwk + eta, 1e-10)
             / (nk + v_eta))
        z_new = jnp.argmax(p / -jnp.log(noise_ref[:]),
                           axis=-1).astype(jnp.int32)
    z_new = jnp.where(m_ref[:, 0] > 0, z_new, z_ref[:, 0])
    z_out_ref[:] = z_new[:, None]
    # Count-merge: delta one-hots against the tile's vocab one-hot on
    # the MXU — [tile, V]^T @ [tile, K] -> [V, K], all in VMEM.
    delta = (z_new[:, None] == iota_k).astype(jnp.int32) - oh_old
    iota_v = jax.lax.broadcasted_iota(jnp.int32, (tile, n_rows), 1)
    oh_w = (w_ref[:] == iota_v).astype(jnp.float32)
    part = jax.lax.dot_general(oh_w, delta.astype(jnp.float32),
                               (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)

    @pl.when(i == 0)
    def _():
        dwk_ref[:] = jnp.zeros_like(dwk_ref)

    dwk_ref[:] += part.astype(jnp.int32)


def sample_count_block(ndk_rows, nwk_rows, n_k, noise, w, z_old, mask, *,
                       alpha, eta, v_eta, k_topics, n_rows, use_gumbel,
                       interpret=None):
    """Fused sample + n_wk count-merge for one token block.

    Args (B = block size, K = k_topics, V = `n_rows` count-table rows —
    the LOCAL chunk width under the sharded engine's mp axis):
      ndk_rows  int32 [B, K]  gathered n_dk[d] rows (block-start counts)
      nwk_rows  int32 [B, K]  gathered n_wk[w] rows
      n_k       int32 [K]     topic totals
      noise     f32  [B, K]   jax.random.gumbel (use_gumbel=True) or
                              uniform(minval=1e-38) (race form), drawn
                              from the reference step's own skey
      w         int32 [B]     LOCAL word ids (rows of the count table)
      z_old     int32 [B]     current assignments (K = padding sentinel)
      mask      f32  [B]      1 real token, 0 padding

    Returns (z_new int32 [B], d_wk int32 [n_rows, K]) with
    d_wk == sum_t onehot(w_t) ⊗ (onehot(z_new_t) - onehot(z_old_t)) —
    the exact integer delta the scatter form produces, so the caller's
    `n_wk + d_wk` is bit-identical to `n_wk.at[w].add(delta)`.
    """
    if interpret is None:
        interpret = _default_interpret()
    b = int(w.shape[0])
    v = int(n_rows)
    if b == 0:
        # Degenerate empty block: nothing to sample, zero delta.
        return (jnp.zeros((0,), jnp.int32), jnp.zeros((v, k_topics),
                                                      jnp.int32))
    # Grid sizing: pad B up to a tile multiple. Padded rows carry
    # mask=0 and the z sentinel, so they keep their (sentinel)
    # assignment and contribute an all-zero delta — they cannot touch
    # the counts, and their z output is sliced off.
    tile = min(tile_for(v), -(-b // _TILE_MIN) * _TILE_MIN)
    bp = -(-b // tile) * tile
    pad = bp - b
    if pad:
        ndk_rows = jnp.pad(ndk_rows, ((0, pad), (0, 0)))
        nwk_rows = jnp.pad(nwk_rows, ((0, pad), (0, 0)))
        # Pad value 1.0 keeps -log(noise) finite for the race form;
        # padded rows are masked out either way.
        noise = jnp.pad(noise, ((0, pad), (0, 0)), constant_values=1.0)
        w = jnp.pad(w, (0, pad))
        z_old = jnp.pad(z_old, (0, pad), constant_values=k_topics)
        mask = jnp.pad(mask, (0, pad))
    kern = functools.partial(
        _kernel, tile=tile, k_topics=k_topics, n_rows=v,
        alpha=float(alpha), eta=float(eta), v_eta=float(v_eta),
        use_gumbel=bool(use_gumbel))
    z_new, d_wk = pl.pallas_call(
        kern,
        grid=(bp // tile,),
        in_specs=[
            pl.BlockSpec((tile, k_topics), lambda i: (i, 0)),
            pl.BlockSpec((tile, k_topics), lambda i: (i, 0)),
            pl.BlockSpec((1, k_topics), lambda i: (0, 0)),
            pl.BlockSpec((tile, k_topics), lambda i: (i, 0)),
            pl.BlockSpec((tile, 1), lambda i: (i, 0)),
            pl.BlockSpec((tile, 1), lambda i: (i, 0)),
            pl.BlockSpec((tile, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tile, 1), lambda i: (i, 0)),
            # Constant index map: the [V, K] accumulator stays resident
            # in VMEM across every grid step and flushes to HBM once.
            pl.BlockSpec((v, k_topics), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, 1), jnp.int32),
            jax.ShapeDtypeStruct((v, k_topics), jnp.int32),
        ],
        interpret=interpret,
    )(ndk_rows, nwk_rows, n_k[None, :], noise, w[:, None], z_old[:, None],
      mask[:, None])
    return z_new[:b, 0], d_wk
