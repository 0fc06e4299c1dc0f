"""On-device word creation + id mapping — the DEFAULT hot path.

The 1B-event artifact's dominant pipeline stage was host-side word
creation + trained-id mapping (`stream_words_map`, 48% of the round-3
pipeline wall) — and this host exposes ONE CPU core, so the numpy path
cannot be parallelized sideways. The TPU-first answer is to move the
transform onto the chip: raw numeric telemetry columns stream to the
device (~25 B/event) and ONE fused program does binning → word packing
→ vocab/doc lookup → θ·φᵀ gather → pair-min → running bottom-k, so only
the winners ever come back. This renders SURVEY.md §2.1 #5's word
creation (reference FlowWordCreation, a Spark executor map) as device
compute on the VPU instead of a host preprocessing stage.

As of round 6 this path is the DEFAULT for all three datatypes in both
the scale runner's streaming stage and the SVI streaming scorer
(`ONIX_HOST_WORDS=1` pins the host reference builders, kept as the
cross-check arm the parity tests compare winners against). Two
supporting pieces live here too:

* **Double-buffered chunk staging** (`stage_*_cols` / STAGE_FNS; with
  TABLE_FNS and SCAN_FNS the datatype-keyed entry of the day scan):
  `jax.device_put` returns with the H2D copy in flight, so the scale
  runner stages chunk i+1's columns while chunk i's fused scan occupies
  the compute units — the transfer overlaps compute, not serializes.
* **Hashed-vocabulary streaming buckets** (`*_stream_buckets`): the SVI
  stream has no trained vocabulary, so the fused program ends in
  splitmix64 bucketing (32-bit-limb arithmetic, bit-identical to the
  host hash) instead of a vocab lookup.

How ids are found: `_lookup_sorted` compares a short table (the words)
whole against every key, and joins a long one (the addresses) to the
keys by sorting both together; neither gathers. Not by binary search: on
a v5e a gather costs 7-9 ns per element whatever it fetches, and
`jnp.searchsorted` pays one per step, 18 to an address (PERF.md).

How `table[idx]` is read (the score table, the dictionaries' partial
keys): through `_take`, never by XLA's gather of one scalar per index,
which costs 13.4 ns an element from the 280 MB score table and 5.5-8.3
ns from a dictionary of 2 KB. A short table is compared whole against
every index (1.0-1.7 ns); a long one is gathered by rows of 128 lanes
and the lane picked by a compare (9.2 ns: one DMA a row, whatever the
row holds and wherever it lies). PERF.md section 6, PR 30.

Why a compact key: the host path packs words into 43-bit int64 keys
(words.FLOW_SPEC). JAX runs x64-disabled, so the device path re-encodes
the TRAINED vocabulary once on the host into an equivalent <=31-bit
int32 key (pclass 17 | proto 3 | hbin 3 | bbin 3 | pbin 3) and the
device packs events the same way — the event→vocab-id mapping is
identical; only the key representation differs.

Fidelity: binning compares f32 values against f32-cast edges while the
host compares f64; a value within half an f32 ulp of a quantile edge
can land one bin over (expected ~1e-7/event; tests assert agreement on
synthetic days). The stream scorer's contract is the suspicious tail,
not bit-stable word strings, and the planted-detection metric is
unaffected.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from onix.models import scoring
from onix.pipelines.words import (FLOW_SPEC, _PCLASS_HH, _PROTO_UNK,
                                  N_BINS_DEFAULT)
from onix.utils import telemetry
from onix.utils.obs import device_scope

def host_words_forced() -> bool:
    """True when the env pins the HOST word builders. Device-resident
    word creation is the default hot path in the scale and streaming
    pipelines; `ONIX_HOST_WORDS=1` selects the host reference
    implementation — kept as the cross-check arm the device-vs-host
    parity tests and artifacts compare against."""
    import os

    return os.environ.get("ONIX_HOST_WORDS") == "1"


# Compact-key layout (int32), LSB-first: pbin | bbin | hbin | proto |
# pclass. Shifts must match between build() (host) and _pack() (device).
_BIN_BITS = 3
_PROTO_BITS = 3
_PROTO_SHIFT = 3 * _BIN_BITS
_PCLASS_SHIFT = _PROTO_SHIFT + _PROTO_BITS
_COMPACT_UNK = (1 << _PROTO_BITS) - 1     # _PROTO_UNK re-encoded


class FlowDeviceTables(NamedTuple):
    """Trained lookup state, re-encoded for on-device mapping.

    A NamedTuple so the whole bundle is a pytree — it rides into the
    jitted scan as one argument and stays device-resident across
    chunks.
    """

    word_key_c: jax.Array     # int32 [V] compact keys, ascending
    word_ids: jax.Array       # int32 [V] compact key -> trained vocab id
    doc_u32: jax.Array        # uint32 [D] trained doc IPs, ascending
    doc_ids: jax.Array        # int32 [D]
    hour_edges: jax.Array     # f32 [n_bins-1]
    byt_edges: jax.Array      # f32 [n_bins-1] (log1p space)
    pkt_edges: jax.Array      # f32 [n_bins-1]
    proto_remap: jax.Array    # int32 [n_proto_classes] caller id -> compact


def _tables_span(datatype: str, bundle):
    """The `scan.tables` span around one `build_*_tables`: how long the
    re-encoding and its copies took, and which form each of the two
    look-ups takes for this model (`lookup_form`), so that an operator
    and chip_smoke.py can see that none is a binary search."""
    n_w, n_d = len(bundle.word_key_sorted), len(bundle.doc_u32_sorted)
    return telemetry.TRACER.span(
        "scan.tables", datatype=datatype, words=n_w, docs=n_d,
        word=lookup_form(n_w), doc=lookup_form(n_d))


def build_flow_tables(bundle, edges: dict,
                      proto_classes: list[str]) -> FlowDeviceTables:
    """Re-encode the trained bundle once per run (host side, O(V+D)).

    `edges` are the FITTED bin edges/proto table archived by the
    training corpus build; `proto_classes` is the caller's proto id
    order for the streamed columns (synth/ingest contract)."""
    with _tables_span("flow", bundle):
        fields = FLOW_SPEC.unpack(np.asarray(bundle.word_key_sorted))
        for name in ("pbin", "bbin", "hbin"):
            if fields[name].max(initial=0) >= (1 << _BIN_BITS):
                raise ValueError(
                    "n_bins too large for the compact key; "
                    "raise _BIN_BITS")
        table = np.asarray(edges["proto_classes"], dtype=object)
        if len(table) >= _COMPACT_UNK:
            raise ValueError(
                "too many protocol classes for the compact key")
        proto = np.where(fields["proto"] == _PROTO_UNK, _COMPACT_UNK,
                         np.minimum(fields["proto"], _COMPACT_UNK))
        key_c = (fields["pclass"] << _PCLASS_SHIFT
                 | proto << _PROTO_SHIFT
                 | fields["hbin"] << (2 * _BIN_BITS)
                 | fields["bbin"] << _BIN_BITS
                 | fields["pbin"]).astype(np.int64)
        assert key_c.max(initial=0) < 2 ** 31, "compact key overflows int32"
        order = np.argsort(key_c, kind="stable")
        # Caller proto id -> compact code (the shared remap rule: absent
        # from the fitted table -> UNK).
        from onix.pipelines.words import proto_remap_codes
        remap = proto_remap_codes(table, proto_classes,
                                  _COMPACT_UNK).astype(np.int32)
        return FlowDeviceTables(
            word_key_c=jnp.asarray(key_c[order].astype(np.int32)),
            word_ids=jnp.asarray(
                np.asarray(bundle.word_key_ids)[order].astype(np.int32)),
            doc_u32=jnp.asarray(np.asarray(bundle.doc_u32_sorted)),
            doc_ids=jnp.asarray(
                np.asarray(bundle.doc_u32_ids).astype(np.int32)),
            hour_edges=_edges1d(edges, "hour"),
            byt_edges=_edges1d(edges, "log_ibyt"),
            pkt_edges=_edges1d(edges, "log_ipkt"),
            proto_remap=jnp.asarray(remap),
        )


def _edges1d(edges: dict, name: str) -> "jnp.ndarray":
    """Fitted edge array as f32 [n_edges] for device searchsorted.

    Sized from the FITTED edges, not N_BINS_DEFAULT: magnitude features
    carry two extra tail-resolution cut points (words._bins tail=True),
    so edge counts differ per feature and the old fixed reshape(nb)
    crashed the flow path / silently disabled the dns path."""
    e = np.asarray(edges[name], np.float32).ravel()
    if e.size and np.any(np.diff(e) < 0):
        raise ValueError(f"fitted edges for {name!r} are not sorted")
    return jnp.asarray(e)


# A table of at most this many entries is compared whole against every
# key; a longer one is joined to the keys by sorting. On a v5e, inside
# the fused scan, the compare of 2^21 keys takes 1.6 ms at 349 entries
# and grows with the table; the join takes 9.3 ms whatever the table
# holds, 13.5 ms with the word table joined too (PERF.md section 6,
# PR 27). `_lookup_sorted`'s threshold only: what reads `table[idx]`
# has its own (`_TAKE_COMPARE_MAX`).
_COMPARE_MAX = 1024


def lookup_form(n_table: int) -> str:
    """How `_lookup_sorted` answers for a table of `n_table` entries:
    "compare" or "join". The table's length decides, which is static
    under jit; there is no binary search to fall back to."""
    return "compare" if n_table <= _COMPARE_MAX else "join"


def _rows_of_128(x: jax.Array) -> jax.Array:
    """1-D `x` as rows of 128 lanes, the last one padded with zeros."""
    rows = -(-x.shape[0] // 128)
    return jnp.pad(x, (0, rows * 128 - x.shape[0])).reshape(rows, 128)


def _running_sum(x: jax.Array) -> jax.Array:
    """`jnp.cumsum` of a long 1-D int32 array, in rows of 128 lanes:
    sums within the rows, the rows' totals summed the same way, added
    back. These are the ops the TPU compiler makes of `jnp.cumsum`
    itself; written out because JAX lowers `cumsum` there through a
    shared function whose ops carry no caller's scope, so the trace
    booked a look-up's sum to no one (seen on the chip, PR 27)."""
    n = x.shape[0]
    if n <= 128:
        return jax.lax.reduce_window(x, 0, jax.lax.add, (n,), (1,),
                                     ((n - 1, 0),))
    within = jax.lax.reduce_window(
        _rows_of_128(x), 0, jax.lax.add, (1, 128), (1, 1),
        ((0, 0), (127, 0)))
    total = within[:, -1]
    return (within + (_running_sum(total) - total)[:, None]).reshape(-1)[:n]


def _lookup_sorted(table: jax.Array, ids: jax.Array, keys: jax.Array,
                   fill: int) -> jax.Array:
    """ids[p] where table[p] == keys, else fill — the device rendering
    of CorpusBundle's sorted-table lookups. `table` ascends; of equal
    table keys the first answers, as `searchsorted(side="left")` has
    it. A constant number of passes and no gather of the block (module
    docstring)."""
    assert table.dtype == keys.dtype, (table.dtype, keys.dtype)
    d, n = table.shape[0], keys.shape[0]
    fill = jnp.int32(fill)
    first = jnp.concatenate([jnp.ones_like(table[:1], bool),
                             table[1:] != table[:-1]])
    # What a hit adds to `fill`. int32 wraps, and wraps back.
    gain = jnp.where(first, ids - fill, 0)
    if lookup_form(d) == "compare":
        eq = keys[:, None] == table[None, :]        # at most one gains
        return fill + jnp.sum(jnp.where(eq, gain[None, :], 0), axis=1)
    # Sort-merge join. Every table key k stands twice: at k it brings
    # its gain, at k + 1 it takes it back, so the running sum over the
    # entries at or below a key is that key's answer less `fill`. One
    # sort of entries and keys together (entries first where they tie),
    # one running sum, one sort back into the keys' order.
    top = table == jnp.array(jnp.iinfo(table.dtype).max, table.dtype)
    k = jnp.concatenate([jnp.where(top, table, table + 1), table, keys])
    change = jnp.concatenate([jnp.where(top, 0, -gain), gain,
                              jnp.zeros((n,), jnp.int32)])
    pos = jax.lax.iota(jnp.int32, 2 * d + n)
    # `pos` makes either order total: no stable sort's extra operand.
    _, pos, change = jax.lax.sort((k, pos, change), num_keys=2,
                                  is_stable=False)
    _, out = jax.lax.sort((pos, fill + _running_sum(change)), num_keys=1,
                          is_stable=False)
    return out[2 * d:]


# `_take` compares a table of at most this many entries whole against
# every index and gathers a longer one by rows. On a v5e a block of 2^21
# indices takes 2.1, 2.3 and 3.5 ms against 512, 1024 and 2048 entries
# compared, 5.3 ms by rows from any of them, and 19.2 ms by rows from
# the 70 M-entry score table (PERF.md section 6, PR 30); the
# dictionaries are 512 to 2048 entries, past that not measured.
_TAKE_COMPARE_MAX = 2048
# The row form works through a block in runs of this many indices, so
# that a run's rows ([2^15, 128], 16 MiB) stay in the chip's fast memory
# between the gather and the pick; a whole block's (1 GiB) go through
# HBM: 11.0 ns an element, not 9.2.
_TAKE_RUN = 1 << 15


def take_form(n_table: int) -> str:
    """How `_take` reads a table of `n_table` entries: "compare" or
    "rows". The table's length decides, which is static under jit."""
    return "compare" if n_table <= _TAKE_COMPARE_MAX else "rows"


def _take(table: jax.Array):
    """`read`, where `read(idx)` is `table[idx]` bit for bit, for a 1-D
    table of 4-byte entries and int32 indices (negative ones count from
    the end and the rest are clamped, as `table[idx]` has it), without a
    gather of one scalar per index (module docstring). Call `_take` once
    a program, outside its loop over blocks: the long table's view as
    rows is made here, `read` only reads. Entries travel as their bits
    and are selected, never multiplied: `inf`, `-0.0` and a NaN's
    payload come back as they are."""
    n, dtype = table.shape[0], table.dtype
    bits = jax.lax.bitcast_convert_type(table, jnp.int32)

    def in_range(idx):
        return jnp.clip(jnp.where(idx < 0, idx + n, idx), 0, n - 1)

    def pick(rows, lane):
        # rows[i, lane[i]]: all other lanes of a row are zeroed, so the
        # sum is the one left.
        hit = lane[:, None] == jax.lax.iota(jnp.int32, rows.shape[1])[None, :]
        return jnp.sum(jnp.where(hit, rows, 0), axis=1)

    if take_form(n) == "compare":
        def read_bits(idx):
            return pick(bits[None, :], idx)
    else:
        rows = _rows_of_128(bits)

        def read_run(idx):
            return pick(rows[idx >> 7], idx & 127)

        def read_bits(idx):
            m = idx.shape[0]
            if m <= _TAKE_RUN or m % _TAKE_RUN:
                return read_run(idx)
            _, picked = jax.lax.scan(
                lambda _, run: (None, read_run(run)), None,
                idx.reshape(m // _TAKE_RUN, _TAKE_RUN))
            return picked.reshape(m)

    def read(idx):
        return jax.lax.bitcast_convert_type(read_bits(in_range(idx)), dtype)
    return read


def _flow_flat_idx(t: FlowDeviceTables, v_x: int, unseen_w: int,
                   unseen_d: int, sip, dip, sport, dport, proto, hour,
                   byt, pkt):
    """Per-chunk device transform: raw columns -> (idx_src, idx_dst)
    flat score-table indices. Mirrors flow_words_from_arrays +
    word_ids_packed/doc_ids_u32 field for field."""
    with device_scope("onix.words.bin"):
        sport = sport.astype(jnp.int32)
        dport = dport.astype(jnp.int32)
        s_low = sport <= 1024
        d_low = dport <= 1024
        pclass = jnp.where(
            s_low & d_low, jnp.minimum(sport, dport),
            jnp.where(s_low, sport,
                      jnp.where(d_low, dport, jnp.int32(_PCLASS_HH))))
        hbin = jnp.searchsorted(t.hour_edges, hour, side="right")
        bbin = jnp.searchsorted(t.byt_edges, jnp.log1p(byt), side="right")
        pbin = jnp.searchsorted(t.pkt_edges, jnp.log1p(pkt), side="right")
        key = (pclass << _PCLASS_SHIFT
               | t.proto_remap[proto.astype(jnp.int32)] << _PROTO_SHIFT
               | hbin.astype(jnp.int32) << (2 * _BIN_BITS)
               | bbin.astype(jnp.int32) << _BIN_BITS
               | pbin.astype(jnp.int32))
    with device_scope("onix.words.lookup_word"):
        wid = _lookup_sorted(t.word_key_c, t.word_ids, key, unseen_w)
    with device_scope("onix.words.lookup_doc"):
        did_s = _lookup_sorted(t.doc_u32, t.doc_ids, sip, unseen_d)
        did_d = _lookup_sorted(t.doc_u32, t.doc_ids, dip, unseen_d)
    return did_s * jnp.int32(v_x) + wid, did_d * jnp.int32(v_x) + wid


@functools.partial(jax.jit, static_argnames=("v_x", "unseen_w", "unseen_d",
                                             "tol", "max_results", "chunk"))
def _flow_stream_scan(tables: FlowDeviceTables, table_flat: jax.Array,
                      sip, dip, sport, dport, proto, hour, byt, pkt, *,
                      v_x: int, unseen_w: int, unseen_d: int, tol: float,
                      max_results: int, chunk: int) -> scoring.TopK:
    with device_scope("onix.score.gather"):
        score_of = _take(table_flat)

    def score_chunk(s_ip, d_ip, s_p, d_p, pr, hr, by, pk):
        idx_s, idx_d = _flow_flat_idx(tables, v_x, unseen_w, unseen_d,
                                      s_ip, d_ip, s_p, d_p, pr, hr, by, pk)
        with device_scope("onix.score.gather"):
            s = jnp.minimum(score_of(idx_s), score_of(idx_d))
            return jnp.where(s < tol, s, jnp.inf)

    return scoring._scan_bottom_k(
        (sip, dip, sport, dport, proto, hour, byt, pkt), sip.shape[0],
        score_chunk, max_results=max_results, chunk=chunk,
        merge_buffer=128)


# ---------------------------------------------------------------------------
# DNS / proxy device paths.
#
# Same design as flow with one extra split: the string-derived features
# (subdomain entropy, URI length, user-agent class, ...) are computed
# per UNIQUE value on the host — thousands of strings, microseconds —
# and packed into per-unique PARTIAL compact keys; the device gathers
# the partials through the dictionary codes and packs in the per-event
# numeric fields. Compact layouts (LSB-first):
#   dns:   flbin 3 | hbin 3 | ebin 3 | slbin 3 | nlabels 3 | qtype 8 |
#          rcode 4 | tld 1                                   (28 bits)
#   proxy: cclass 3 | hbin 3 | uebin 3 | ulbin 3 | hostip 1 | ua 7
#                                                            (20 bits)
# build_*_tables validates that the TRAINED vocab fits these ranges
# (qtype < 256, rcode < 16, <126 common user agents, ...) and raises
# otherwise — the caller then stays on the host path. Streamed events
# outside the ranges get key -1 (matches no table entry), landing on
# the UNSEEN word row exactly as the host lookup would.
# ---------------------------------------------------------------------------

_DNS_HBIN_SHIFT = 3
_DNS_EBIN_SHIFT = 6
_DNS_SLBIN_SHIFT = 9
_DNS_NLABELS_SHIFT = 12
_DNS_QTYPE_SHIFT = 15
_DNS_RCODE_SHIFT = 23
_DNS_TLD_SHIFT = 27
_PROXY_HBIN_SHIFT = 3
_PROXY_UEBIN_SHIFT = 6
_PROXY_ULBIN_SHIFT = 9
_PROXY_HOSTIP_SHIFT = 12
_PROXY_UA_SHIFT = 13
_PROXY_UA_RARE_C = 126     # words._UA_RARE (1023) re-encoded to 7 bits


class DnsDeviceTables(NamedTuple):
    word_key_c: jax.Array     # int32 [V] compact keys, ascending
    word_ids: jax.Array       # int32 [V]
    doc_u32: jax.Array        # uint32 [D] trained client IPs, ascending
    doc_ids: jax.Array        # int32 [D]
    hour_edges: jax.Array     # f32 [n_bins-1]
    flen_edges: jax.Array     # f32 [n_bins-1]


def build_dns_tables(bundle, edges: dict) -> DnsDeviceTables:
    from onix.pipelines.words import DNS_SPEC

    with _tables_span("dns", bundle):
        fields = DNS_SPEC.unpack(np.asarray(bundle.word_key_sorted))
        if fields["qtype"].max(initial=0) >= 256:
            raise ValueError("trained qtype exceeds the compact key range")
        if fields["rcode"].max(initial=0) >= 16:
            raise ValueError("trained rcode exceeds the compact key range")
        for name in ("flbin", "hbin", "ebin", "slbin", "nlabels"):
            if fields[name].max(initial=0) >= 8:
                raise ValueError(
                    f"trained {name} exceeds the compact key range")
        key_c = (fields["flbin"]
                 | fields["hbin"] << _DNS_HBIN_SHIFT
                 | fields["ebin"] << _DNS_EBIN_SHIFT
                 | fields["slbin"] << _DNS_SLBIN_SHIFT
                 | fields["nlabels"] << _DNS_NLABELS_SHIFT
                 | fields["qtype"] << _DNS_QTYPE_SHIFT
                 | fields["rcode"] << _DNS_RCODE_SHIFT
                 | fields["tld"] << _DNS_TLD_SHIFT).astype(np.int64)
        order = np.argsort(key_c, kind="stable")
        return DnsDeviceTables(
            word_key_c=jnp.asarray(key_c[order].astype(np.int32)),
            word_ids=jnp.asarray(
                np.asarray(bundle.word_key_ids)[order].astype(np.int32)),
            doc_u32=jnp.asarray(np.asarray(bundle.doc_u32_sorted)),
            doc_ids=jnp.asarray(
                np.asarray(bundle.doc_u32_ids).astype(np.int32)),
            hour_edges=_edges1d(edges, "hour"),
            flen_edges=_edges1d(edges, "frame_len"),
        )


def _pad_pow2(a: np.ndarray) -> np.ndarray:
    """Pad a per-unique table to the next power of two so the jitted
    per-chunk scan sees a handful of distinct shapes, not one per
    chunk's unique count (each distinct shape is a recompile)."""
    n = max(1, int(a.shape[0]))
    size = 1 << (n - 1).bit_length()
    return np.pad(a, (0, size - a.shape[0]))


def _dns_unique_bins(qnames: np.ndarray, edges: dict) -> dict:
    """Per-UNIQUE qname word fields under the fitted edges — the ONE
    string-feature pipeline shared by the trained-vocab compact
    partials and the streaming full-spec partials (a drifted copy
    would silently break host/device word identity)."""
    from onix.utils.features import digitize, qname_features

    qf = qname_features(qnames)
    return {
        "slbin": digitize(qf["sub_len"], edges["sub_len"]).astype(np.int64),
        "ebin": digitize(qf["sub_entropy"].astype(np.float64),
                         edges["sub_entropy"]).astype(np.int64),
        "nlabels": qf["n_labels"],
        "tld": qf["tld_ok"],
    }


def dns_partial_keys(qnames: np.ndarray, edges: dict) -> np.ndarray:
    """Per-UNIQUE compact partials (ebin|slbin|nlabels|tld at their
    shifts) from the fitted edges — host side, O(uniques)."""
    b = _dns_unique_bins(qnames, edges)
    return (b["ebin"] << _DNS_EBIN_SHIFT
            | b["slbin"] << _DNS_SLBIN_SHIFT
            | b["nlabels"] << _DNS_NLABELS_SHIFT
            | b["tld"] << _DNS_TLD_SHIFT).astype(np.int32)


@functools.partial(jax.jit, static_argnames=("v_x", "unseen_w", "unseen_d",
                                             "tol", "max_results", "chunk"))
def _dns_stream_scan(tables: DnsDeviceTables, table_flat: jax.Array,
                     partial_u: jax.Array, client, codes, qtype, rcode,
                     flen, hour, *, v_x: int, unseen_w: int, unseen_d: int,
                     tol: float, max_results: int,
                     chunk: int) -> scoring.TopK:
    with device_scope("onix.words.dict_gather"):
        partial_of = _take(partial_u)
    with device_scope("onix.score.gather"):
        score_of = _take(table_flat)

    def score_chunk(cl, co, qt, rc, fl, hr):
        with device_scope("onix.words.bin"):
            flbin = jnp.searchsorted(tables.flen_edges, fl, side="right")
            hbin = jnp.searchsorted(tables.hour_edges, hr, side="right")
            # The innermost scope is an op's own: the dictionary's read
            # is booked to dict_gather, the searches and the packing to
            # bin.
            with device_scope("onix.words.dict_gather"):
                partial = partial_of(co)
            key = (partial
                   | flbin.astype(jnp.int32)
                   | hbin.astype(jnp.int32) << _DNS_HBIN_SHIFT
                   | qt << _DNS_QTYPE_SHIFT
                   | rc << _DNS_RCODE_SHIFT)
            valid = ((qt >= 0) & (qt < 256) & (rc >= 0) & (rc < 16))
            key = jnp.where(valid, key, jnp.int32(-1))
        with device_scope("onix.words.lookup_word"):
            wid = _lookup_sorted(tables.word_key_c, tables.word_ids, key,
                                 unseen_w)
        with device_scope("onix.words.lookup_doc"):
            did = _lookup_sorted(tables.doc_u32, tables.doc_ids, cl,
                                 unseen_d)
        with device_scope("onix.score.gather"):
            s = score_of(did * jnp.int32(v_x) + wid)
            return jnp.where(s < tol, s, jnp.inf)

    return scoring._scan_bottom_k(
        (client, codes, qtype, rcode, flen, hour), client.shape[0],
        score_chunk, max_results=max_results, chunk=chunk,
        merge_buffer=128)


# ---------------------------------------------------------------------------
# Chunk staging (double-buffered ingestion).
#
# `jax.device_put` returns immediately with the H2D copy in flight, so a
# scale runner can stage chunk i+1's columns WHILE chunk i's fused scan
# occupies the compute units — the transfer overlaps compute instead of
# serializing with it (scale.py's double-buffered stream loop). Each
# stage_* helper does the per-chunk HOST work too (dtype casts; for
# dns/proxy the per-UNIQUE string partials), so once a staged dict
# exists the stream_bottom_k call is pure device dispatch. Staged dicts
# are marked with "_staged" and pass through the stream_bottom_k entry
# points untouched; raw numpy column dicts still work (staged on the
# spot) so existing callers and tests see one API.
# ---------------------------------------------------------------------------


def _put(a) -> jax.Array:
    with telemetry.TRACER.span("scan.h2d_put", bytes=int(a.nbytes)):
        return jax.device_put(a)


def stage_flow_cols(cols: dict) -> dict:
    """Cast + async-transfer one flow chunk's raw columns (~25 B/event)."""
    with telemetry.TRACER.span("scan.stage",
                               events=len(cols["sip_u32"])):
        return {
            "_staged": True,
            "sip_u32": _put(np.asarray(cols["sip_u32"], np.uint32)),
            "dip_u32": _put(np.asarray(cols["dip_u32"], np.uint32)),
            "sport": _put(np.asarray(cols["sport"], np.int32)),
            "dport": _put(np.asarray(cols["dport"], np.int32)),
            "proto_id": _put(np.asarray(cols["proto_id"], np.int32)),
            "hour": _put(np.asarray(cols["hour"], np.float32)),
            "ibyt": _put(np.asarray(cols["ibyt"], np.float32)),
            "ipkt": _put(np.asarray(cols["ipkt"], np.float32)),
            "proto_classes": list(cols["proto_classes"]),
        }


def stage_dns_cols(cols: dict, edges: dict) -> dict:
    """Host string features per UNIQUE qname, then async-transfer."""
    with telemetry.TRACER.span("scan.stage",
                               events=len(cols["client_u32"])):
        with telemetry.TRACER.span("scan.partials",
                                   names=len(cols["qnames"])):
            partial_u = dns_partial_keys(cols["qnames"], edges)
        return {
            "_staged": True,
            "partial_u": _put(_pad_pow2(partial_u)),
            "client_u32": _put(np.asarray(cols["client_u32"], np.uint32)),
            "qname_codes": _put(np.asarray(cols["qname_codes"], np.int32)),
            "qtype": _put(np.asarray(cols["qtype"], np.int32)),
            "rcode": _put(np.asarray(cols["rcode"], np.int32)),
            "frame_len": _put(np.asarray(cols["frame_len"], np.float32)),
            "hour": _put(np.asarray(cols["hour"], np.float32)),
        }


def stage_proxy_cols(cols: dict, edges: dict) -> dict:
    """Host string features per UNIQUE uri/host/agent, then transfer."""
    with telemetry.TRACER.span("scan.stage",
                               events=len(cols["client_u32"])):
        with telemetry.TRACER.span(
                "scan.partials", uris=len(cols["uris"]),
                hosts=len(cols["hosts"]), agents=len(cols["agents"])):
            uri_p, host_p, ua_p = proxy_partial_keys(
                cols["uris"], cols["hosts"], cols["agents"], edges)
        return {
            "_staged": True,
            "uri_p": _put(_pad_pow2(uri_p)),
            "host_p": _put(_pad_pow2(host_p)),
            "ua_p": _put(_pad_pow2(ua_p)),
            "client_u32": _put(np.asarray(cols["client_u32"], np.uint32)),
            "uri_codes": _put(np.asarray(cols["uri_codes"], np.int32)),
            "host_codes": _put(np.asarray(cols["host_codes"], np.int32)),
            "ua_codes": _put(np.asarray(cols["ua_codes"], np.int32)),
            "respcode": _put(np.asarray(cols["respcode"], np.int32)),
            "hour": _put(np.asarray(cols["hour"], np.float32)),
        }


STAGE_FNS = {"flow": lambda cols, edges: stage_flow_cols(cols),
             "dns": stage_dns_cols,
             "proxy": stage_proxy_cols}

# The scan's other two steps, keyed the same way, so that a driver of
# the day scan (scale._stream_score, benchmark/drivers/dayscan.py) names
# no datatype: TABLE_FNS[dt](bundle, edges, cols) builds the device
# tables (`cols` is one raw chunk; only the datatypes of
# TABLES_FROM_CHUNK read it, so the others can be built before any chunk
# exists, with None), SCAN_FNS[dt](tables, table_flat, staged, edges,
# **kw) runs the fused scan of one chunk. The named functions are looked
# up when called.
TABLE_FNS = {
    "flow": lambda bundle, edges, cols: build_flow_tables(
        bundle, edges, list(cols["proto_classes"])),
    "dns": lambda bundle, edges, cols: build_dns_tables(bundle, edges),
    "proxy": lambda bundle, edges, cols: build_proxy_tables(bundle, edges),
}
# Flow's remap is keyed on the caller's protocol order, which only a
# chunk carries (build_flow_tables' contract).
TABLES_FROM_CHUNK = frozenset({"flow"})
SCAN_FNS = {
    "flow": lambda tables, table_flat, staged, edges, **kw:
        flow_stream_bottom_k(tables, table_flat, staged, **kw),
    "dns": lambda tables, table_flat, staged, edges, **kw:
        dns_stream_bottom_k(tables, table_flat, staged, edges, **kw),
    "proxy": lambda tables, table_flat, staged, edges, **kw:
        proxy_stream_bottom_k(tables, table_flat, staged, edges, **kw),
}


def dns_stream_bottom_k(tables: DnsDeviceTables, table_flat: jax.Array,
                        cols: dict, edges: dict, *, v_x: int, unseen_w: int,
                        unseen_d: int, tol: float, max_results: int,
                        chunk: int = 1 << 21) -> scoring.TopK:
    """Fused words→map→score→select for one streamed DNS chunk: string
    features run per unique name on the host, everything per-event on
    the device. `cols` may be raw numpy columns or a stage_dns_cols
    dict (double-buffered callers stage the next chunk early)."""
    if not cols.get("_staged"):
        cols = stage_dns_cols(cols, edges)
    with telemetry.TRACER.span("scan.dispatch",
                               events=cols["client_u32"].shape[0]):
        return _dns_stream_scan(
            tables, table_flat, cols["partial_u"], cols["client_u32"],
            cols["qname_codes"], cols["qtype"], cols["rcode"],
            cols["frame_len"], cols["hour"],
            v_x=v_x, unseen_w=unseen_w, unseen_d=unseen_d, tol=tol,
            max_results=max_results, chunk=chunk)


class ProxyDeviceTables(NamedTuple):
    word_key_c: jax.Array     # int32 [V] compact keys, ascending
    word_ids: jax.Array       # int32 [V]
    doc_u32: jax.Array        # uint32 [D]
    doc_ids: jax.Array        # int32 [D]
    hour_edges: jax.Array     # f32 [n_bins-1]


def build_proxy_tables(bundle, edges: dict) -> ProxyDeviceTables:
    from onix.pipelines.words import _UA_RARE, PROXY_SPEC

    with _tables_span("proxy", bundle):
        fields = PROXY_SPEC.unpack(np.asarray(bundle.word_key_sorted))
        if len(edges.get("ua_common", ())) >= _PROXY_UA_RARE_C:
            raise ValueError("too many common user agents for the compact key")
        ua = fields["ua"]
        bad_ua = (ua >= len(edges.get("ua_common", ()))) & (ua != _UA_RARE)
        if bad_ua.any():
            raise ValueError("trained ua code outside the fitted common table")
        ua_c = np.where(ua == _UA_RARE, _PROXY_UA_RARE_C, ua)
        if fields["cclass"].max(initial=0) >= 8:
            raise ValueError("trained cclass exceeds the compact key range")
        for name in ("hbin", "uebin", "ulbin"):
            if fields[name].max(initial=0) >= 8:
                raise ValueError(
                    f"trained {name} exceeds the compact key range")
        key_c = (fields["cclass"]
                 | fields["hbin"] << _PROXY_HBIN_SHIFT
                 | fields["uebin"] << _PROXY_UEBIN_SHIFT
                 | fields["ulbin"] << _PROXY_ULBIN_SHIFT
                 | fields["hostip"] << _PROXY_HOSTIP_SHIFT
                 | ua_c << _PROXY_UA_SHIFT).astype(np.int64)
        order = np.argsort(key_c, kind="stable")
        return ProxyDeviceTables(
            word_key_c=jnp.asarray(key_c[order].astype(np.int32)),
            word_ids=jnp.asarray(
                np.asarray(bundle.word_key_ids)[order].astype(np.int32)),
            doc_u32=jnp.asarray(np.asarray(bundle.doc_u32_sorted)),
            doc_ids=jnp.asarray(
                np.asarray(bundle.doc_u32_ids).astype(np.int32)),
            hour_edges=_edges1d(edges, "hour"),
        )


def proxy_partial_keys(uris: np.ndarray, hosts: np.ndarray,
                       agents: np.ndarray, edges: dict) -> tuple:
    """Per-UNIQUE compact partials for the three dictionary columns —
    host side, O(uniques). Returns (uri_p, host_p, ua_p) int32."""
    from onix.pipelines.words import _IP_RE, _UA_RARE, _categorical
    from onix.utils.features import digitize, entropy_array

    uri_len = np.fromiter((len(str(u)) for u in uris), np.float64,
                          len(uris))
    ulbin = digitize(uri_len, edges["uri_len"]).astype(np.int64)
    uebin = digitize(entropy_array(uris).astype(np.float64),
                     edges["uri_entropy"]).astype(np.int64)
    uri_p = (uebin << _PROXY_UEBIN_SHIFT
             | ulbin << _PROXY_ULBIN_SHIFT).astype(np.int32)
    host_p = (np.fromiter((int(bool(_IP_RE.match(str(h)))) for h in hosts),
                          np.int64, len(hosts))
              << _PROXY_HOSTIP_SHIFT).astype(np.int32)
    ua = _categorical(np.asarray(agents, dtype=object), "ua_common", edges,
                      _UA_RARE)
    ua_c = np.where(ua == _UA_RARE, _PROXY_UA_RARE_C, ua)
    return uri_p, host_p, (ua_c << _PROXY_UA_SHIFT).astype(np.int32)


@functools.partial(jax.jit, static_argnames=("v_x", "unseen_w", "unseen_d",
                                             "tol", "max_results", "chunk"))
def _proxy_stream_scan(tables: ProxyDeviceTables, table_flat: jax.Array,
                       uri_p: jax.Array, host_p: jax.Array, ua_p: jax.Array,
                       client, uri_c, host_c, ua_c, respcode, hour, *,
                       v_x: int, unseen_w: int, unseen_d: int, tol: float,
                       max_results: int, chunk: int) -> scoring.TopK:
    with device_scope("onix.words.dict_gather"):
        uri_of, host_of, ua_of = _take(uri_p), _take(host_p), _take(ua_p)
    with device_scope("onix.score.gather"):
        score_of = _take(table_flat)

    def score_chunk(cl, uc, hc, ac, rc, hr):
        with device_scope("onix.words.bin"):
            hbin = jnp.searchsorted(tables.hour_edges, hr, side="right")
            cclass = rc // 100
            with device_scope("onix.words.dict_gather"):    # as in dns
                partial = uri_of(uc) | host_of(hc) | ua_of(ac)
            key = (partial
                   | cclass
                   | hbin.astype(jnp.int32) << _PROXY_HBIN_SHIFT)
            valid = (rc >= 0) & (cclass < 8)
            key = jnp.where(valid, key, jnp.int32(-1))
        with device_scope("onix.words.lookup_word"):
            wid = _lookup_sorted(tables.word_key_c, tables.word_ids, key,
                                 unseen_w)
        with device_scope("onix.words.lookup_doc"):
            did = _lookup_sorted(tables.doc_u32, tables.doc_ids, cl,
                                 unseen_d)
        with device_scope("onix.score.gather"):
            s = score_of(did * jnp.int32(v_x) + wid)
            return jnp.where(s < tol, s, jnp.inf)

    return scoring._scan_bottom_k(
        (client, uri_c, host_c, ua_c, respcode, hour), client.shape[0],
        score_chunk, max_results=max_results, chunk=chunk,
        merge_buffer=128)


def proxy_stream_bottom_k(tables: ProxyDeviceTables, table_flat: jax.Array,
                          cols: dict, edges: dict, *, v_x: int,
                          unseen_w: int, unseen_d: int, tol: float,
                          max_results: int,
                          chunk: int = 1 << 21) -> scoring.TopK:
    """Fused words→map→score→select for one streamed proxy chunk.
    `cols` may be raw numpy columns or a stage_proxy_cols dict."""
    if not cols.get("_staged"):
        cols = stage_proxy_cols(cols, edges)
    with telemetry.TRACER.span("scan.dispatch",
                               events=cols["client_u32"].shape[0]):
        return _proxy_stream_scan(
            tables, table_flat, cols["uri_p"], cols["host_p"], cols["ua_p"],
            cols["client_u32"], cols["uri_codes"], cols["host_codes"],
            cols["ua_codes"], cols["respcode"], cols["hour"],
            v_x=v_x, unseen_w=unseen_w, unseen_d=unseen_d, tol=tol,
            max_results=max_results, chunk=chunk)


# ---------------------------------------------------------------------------
# Hashed-vocabulary streaming path (onix/pipelines/streaming.py).
#
# The SVI stream has no trained vocabulary to look keys up in — words
# hash into a fixed bucket space (streaming.py `_bucket_of_keys`:
# splitmix64 over the packed int64 `word_key`, mod n_buckets). The
# device rendering below computes the SAME buckets on-chip: binning →
# full-spec int64 key packing (as two uint32 limbs — x64 stays
# disabled) → splitmix64 in 32-bit limb arithmetic → low-bits mod for
# power-of-two bucket counts. Bucket identity is therefore preserved
# EXACTLY against the host path given identical bin indices; the one
# divergence source is the f32-vs-f64 bin-edge comparison documented in
# the module docstring (~1e-7/event). Per-UNIQUE string features
# (dns/proxy) stay host-side, pre-packed into int64 partial keys whose
# uint32 halves the device gathers through the dictionary codes.
# ---------------------------------------------------------------------------

_SM64_C1 = 0x9E3779B97F4A7C15
_SM64_C2 = 0xBF58476D1CE4E5B9
_SM64_C3 = 0x94D049BB133111EB


def _u32(x: int) -> "jnp.ndarray":
    return jnp.uint32(x & 0xFFFFFFFF)


def _shr64(hi, lo, s: int):
    """(hi, lo) >> s for static 0 < s < 32."""
    return hi >> s, (lo >> s) | (hi << (32 - s))


def _mul64(ah, al, b: int):
    """Low 64 bits of (ah, al) * constant b, in uint32 limbs (16-bit
    partial products for the 32x32→64 low half; upper cross terms wrap
    into hi, exactly like uint64 multiplication)."""
    bh, bl = _u32(b >> 32), _u32(b)
    a0 = al & _u32(0xFFFF)
    a1 = al >> 16
    b0 = bl & _u32(0xFFFF)
    b1 = bl >> 16
    p00 = a0 * b0
    p01 = a0 * b1
    p10 = a1 * b0
    mid = (p01 & _u32(0xFFFF)) + (p10 & _u32(0xFFFF)) + (p00 >> 16)
    lo = (p00 & _u32(0xFFFF)) | ((mid & _u32(0xFFFF)) << 16)
    hi = (a1 * b1 + (p01 >> 16) + (p10 >> 16) + (mid >> 16)
          + al * bh + ah * bl)
    return hi, lo


def _splitmix64_bucket(hi, lo, salt: int, n_buckets: int):
    """splitmix64(key ^ salt) % n_buckets on (hi, lo) uint32 limbs —
    bit-identical to streaming._bucket_of_keys for power-of-two
    n_buckets (the mod is the low bits of the finalized value)."""
    hi = hi ^ _u32(salt >> 32)
    lo = lo ^ _u32(salt)
    lo2 = lo + _u32(_SM64_C1)
    hi = hi + _u32(_SM64_C1 >> 32) + (lo2 < lo).astype(jnp.uint32)
    lo = lo2
    sh, sl = _shr64(hi, lo, 30)
    hi, lo = hi ^ sh, lo ^ sl
    hi, lo = _mul64(hi, lo, _SM64_C2)
    sh, sl = _shr64(hi, lo, 27)
    hi, lo = hi ^ sh, lo ^ sl
    hi, lo = _mul64(hi, lo, _SM64_C3)
    sh, sl = _shr64(hi, lo, 31)
    hi, lo = hi ^ sh, lo ^ sl
    return (lo & _u32(n_buckets - 1)).astype(jnp.int32)


def _pack64(spec, vals: dict):
    """Device twin of WordSpec.pack: field values → packed int64 key as
    (hi, lo) uint32 limbs. Shifts/masks are Python ints (static), so
    each field contributes one or two OR terms — no 64-bit ops."""
    hi = lo = None
    shift = 0
    for name, bits in spec.fields:
        v = vals[name].astype(jnp.uint32) & _u32((1 << bits) - 1)
        parts_lo = []
        parts_hi = []
        if shift < 32:
            parts_lo.append(v << shift if shift else v)
            if shift + bits > 32:
                parts_hi.append(v >> (32 - shift))
        else:
            parts_hi.append(v << (shift - 32) if shift > 32 else v)
        for p in parts_lo:
            lo = p if lo is None else lo | p
        for p in parts_hi:
            hi = p if hi is None else hi | p
        shift += bits
    zero = jnp.zeros_like(lo if lo is not None else hi)
    return (zero if hi is None else hi), (zero if lo is None else lo)


def _partial_halves(partial: np.ndarray):
    """Host int64 partial keys → (hi, lo) uint32 arrays, pow2-padded."""
    p = _pad_pow2(np.asarray(partial, np.int64))
    return (p >> 32).astype(np.uint32), (p & 0xFFFFFFFF).astype(np.uint32)


class FlowStreamTables(NamedTuple):
    hour_edges: jax.Array     # f32 — frozen fitted edges (f32 caveat)
    byt_edges: jax.Array
    pkt_edges: jax.Array
    proto_remap: jax.Array    # int32 [n_caller_protos] -> fitted id / UNK


def build_flow_stream_tables(edges: dict,
                             proto_classes: list[str]) -> FlowStreamTables:
    """Frozen-edge tables for the hashed streaming path. The proto
    remap keys on the CALLER's per-batch proto order (same rule as
    flow_words_from_arrays: absent from the fitted table -> UNK), so it
    is rebuilt per batch — O(#protos), trivially cheap."""
    from onix.pipelines.words import _PROTO_UNK, proto_remap_codes

    remap = proto_remap_codes(edges["proto_classes"], proto_classes,
                              _PROTO_UNK).astype(np.int32)
    return FlowStreamTables(
        hour_edges=_edges1d(edges, "hour"),
        byt_edges=_edges1d(edges, "log_ibyt"),
        pkt_edges=_edges1d(edges, "log_ipkt"),
        proto_remap=jnp.asarray(remap))


@functools.partial(jax.jit, static_argnames=("salt", "n_buckets"))
def flow_stream_buckets(t: FlowStreamTables, sport, dport, proto, hour,
                        byt, pkt, *, salt: int,
                        n_buckets: int) -> jax.Array:
    """Per-event word bucket ids [n] for one flow minibatch — binning,
    FLOW_SPEC packing, and splitmix64 bucketing in one program. Both
    tokens of a flow event (src-doc, dst-doc) carry the same word, so
    one bucket per event covers the [src|dst] token layout."""
    sport = sport.astype(jnp.int32)
    dport = dport.astype(jnp.int32)
    s_low = sport <= 1024
    d_low = dport <= 1024
    pclass = jnp.where(
        s_low & d_low, jnp.minimum(sport, dport),
        jnp.where(s_low, sport,
                  jnp.where(d_low, dport, jnp.int32(_PCLASS_HH))))
    hi, lo = _pack64(FLOW_SPEC, {
        "pbin": jnp.searchsorted(t.pkt_edges, jnp.log1p(pkt),
                                 side="right").astype(jnp.uint32),
        "bbin": jnp.searchsorted(t.byt_edges, jnp.log1p(byt),
                                 side="right").astype(jnp.uint32),
        "hbin": jnp.searchsorted(t.hour_edges, hour,
                                 side="right").astype(jnp.uint32),
        "pclass": pclass.astype(jnp.uint32),
        "proto": t.proto_remap[proto.astype(jnp.int32)].astype(jnp.uint32),
    })
    return _splitmix64_bucket(hi, lo, salt, n_buckets)


class DnsStreamTables(NamedTuple):
    hour_edges: jax.Array
    flen_edges: jax.Array
    partial_hi: jax.Array     # uint32 [U] per-unique-qname key partials
    partial_lo: jax.Array


def build_dns_stream_tables(edges: dict, qnames: np.ndarray) -> DnsStreamTables:
    """Frozen edges + per-UNIQUE qname partial keys (tld, nlabels,
    ebin, slbin at their DNS_SPEC shifts) — host string work is
    O(uniques), as in the trained-vocab dns path."""
    from onix.pipelines.words import DNS_SPEC

    b = _dns_unique_bins(qnames, edges)
    sh = DNS_SPEC.shifts()
    bits = dict(DNS_SPEC.fields)
    partial = np.zeros(len(qnames), np.int64)
    for name in ("tld", "nlabels", "ebin", "slbin"):
        # Same bit masking as WordSpec.pack, same shifts by definition.
        partial |= (b[name] & ((1 << bits[name]) - 1)) << sh[name]
    hi, lo = _partial_halves(partial)
    return DnsStreamTables(
        hour_edges=_edges1d(edges, "hour"),
        flen_edges=_edges1d(edges, "frame_len"),
        partial_hi=jnp.asarray(hi), partial_lo=jnp.asarray(lo))


@functools.partial(jax.jit, static_argnames=("salt", "n_buckets"))
def dns_stream_buckets(t: DnsStreamTables, codes, qtype, rcode, flen,
                       hour, *, salt: int, n_buckets: int) -> jax.Array:
    from onix.pipelines.words import DNS_SPEC

    hi, lo = _pack64(DNS_SPEC, {
        "tld": jnp.zeros_like(codes).astype(jnp.uint32),
        "rcode": rcode.astype(jnp.uint32),
        "qtype": qtype.astype(jnp.uint32),
        "nlabels": jnp.zeros_like(codes).astype(jnp.uint32),
        "ebin": jnp.zeros_like(codes).astype(jnp.uint32),
        "slbin": jnp.zeros_like(codes).astype(jnp.uint32),
        "hbin": jnp.searchsorted(t.hour_edges, hour,
                                 side="right").astype(jnp.uint32),
        "flbin": jnp.searchsorted(t.flen_edges, flen,
                                  side="right").astype(jnp.uint32),
    })
    c = codes.astype(jnp.int32)
    hi = hi | t.partial_hi[c]
    lo = lo | t.partial_lo[c]
    return _splitmix64_bucket(hi, lo, salt, n_buckets)


class ProxyStreamTables(NamedTuple):
    hour_edges: jax.Array
    uri_hi: jax.Array         # uint32 [Uu] per-unique-URI partials
    uri_lo: jax.Array
    host_hi: jax.Array        # uint32 [Uh]
    host_lo: jax.Array
    ua_hi: jax.Array          # uint32 [Ua]
    ua_lo: jax.Array


def build_proxy_stream_tables(edges: dict, uris: np.ndarray,
                              hosts: np.ndarray,
                              agents: np.ndarray) -> ProxyStreamTables:
    from onix.pipelines.words import (_IP_RE, _UA_RARE, _categorical,
                                      PROXY_SPEC)
    from onix.utils.features import digitize, entropy_array

    shift = PROXY_SPEC.shifts()
    uri_len = np.fromiter((len(str(u)) for u in uris), np.float64,
                          len(uris))
    ulbin = digitize(uri_len, edges["uri_len"]).astype(np.int64)
    uebin = digitize(entropy_array(uris).astype(np.float64),
                     edges["uri_entropy"]).astype(np.int64)
    uri_p = ((uebin & 63) << shift["uebin"]
             | (ulbin & 63) << shift["ulbin"])
    host_p = (np.fromiter((int(bool(_IP_RE.match(str(h)))) for h in hosts),
                          np.int64, len(hosts)) << shift["hostip"])
    ua = _categorical(np.asarray(agents, dtype=object), "ua_common", edges,
                      _UA_RARE)
    ua_p = (ua & 1023) << shift["ua"]
    uh, ul = _partial_halves(uri_p)
    hh, hl = _partial_halves(host_p)
    ah, al = _partial_halves(ua_p)
    return ProxyStreamTables(
        hour_edges=_edges1d(edges, "hour"),
        uri_hi=jnp.asarray(uh), uri_lo=jnp.asarray(ul),
        host_hi=jnp.asarray(hh), host_lo=jnp.asarray(hl),
        ua_hi=jnp.asarray(ah), ua_lo=jnp.asarray(al))


@functools.partial(jax.jit, static_argnames=("salt", "n_buckets"))
def proxy_stream_buckets(t: ProxyStreamTables, uri_c, host_c, ua_c,
                         respcode, hour, *, salt: int,
                         n_buckets: int) -> jax.Array:
    from onix.pipelines.words import PROXY_SPEC

    rc = respcode.astype(jnp.int32)
    hi, lo = _pack64(PROXY_SPEC, {
        "hbin": jnp.searchsorted(t.hour_edges, hour,
                                 side="right").astype(jnp.uint32),
        "uebin": jnp.zeros_like(rc).astype(jnp.uint32),
        "ulbin": jnp.zeros_like(rc).astype(jnp.uint32),
        "hostip": jnp.zeros_like(rc).astype(jnp.uint32),
        "ua": jnp.zeros_like(rc).astype(jnp.uint32),
        "cclass": (rc // 100).astype(jnp.uint32),
    })
    u = uri_c.astype(jnp.int32)
    h = host_c.astype(jnp.int32)
    a = ua_c.astype(jnp.int32)
    hi = hi | t.uri_hi[u] | t.host_hi[h] | t.ua_hi[a]
    lo = lo | t.uri_lo[u] | t.host_lo[h] | t.ua_lo[a]
    return _splitmix64_bucket(hi, lo, salt, n_buckets)


def flow_stream_bottom_k(
    tables: FlowDeviceTables,
    table_flat: jax.Array,     # f32 [D_x * V_x] extended score table
    cols: dict,                # numpy columns (synth/ingest schema)
    *,
    v_x: int,
    unseen_w: int,
    unseen_d: int,
    tol: float,
    max_results: int,
    chunk: int = 1 << 21,
) -> scoring.TopK:
    """Fused words→map→score→select for one streamed flow chunk,
    entirely on device: eight raw columns go up, `max_results` winners
    come back. Selection runs through the shared exact scan
    (scoring._scan_bottom_k), so tie rules, padding semantics, and the
    two-phase merge match every other selection entry point. `cols`
    may be raw numpy columns or a stage_flow_cols dict."""
    if not cols.get("_staged"):
        cols = stage_flow_cols(cols)
    with telemetry.TRACER.span("scan.dispatch",
                               events=cols["sip_u32"].shape[0]):
        return _flow_stream_scan(
            tables, table_flat,
            cols["sip_u32"], cols["dip_u32"], cols["sport"], cols["dport"],
            cols["proto_id"], cols["hour"], cols["ibyt"], cols["ipkt"],
            v_x=v_x, unseen_w=unseen_w, unseen_d=unseen_d, tol=tol,
            max_results=max_results, chunk=chunk)
