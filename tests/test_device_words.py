"""On-device flow word creation (onix/pipelines/device_words.py).

Contract: the device transform (compact-key packing + sorted-table
lookups) maps every event to the SAME trained (doc, word) ids as the
host path (flow_words_from_arrays + CorpusBundle lookups), including
unseen words, unseen documents, and unknown protocols; and the fused
stream selection returns the same winners as the host-mapped scan.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from onix.models import scoring
from onix.pipelines import device_words as dw
from onix.pipelines.corpus_build import _sorted_table_lookup, build_corpus
from onix.pipelines.scale import _words_from_cols
from onix.pipelines.synth import SYNTH_ARRAYS


def _trained(n=20_000, n_hosts=300, seed=3):
    cols = SYNTH_ARRAYS["flow"](n, n_hosts=n_hosts, n_anomalies=40,
                                seed=seed)
    wt = _words_from_cols("flow", cols)
    bundle = build_corpus(wt)
    return cols, wt, bundle


def _host_idx(bundle, wt_stream, v_x, unseen_w, unseen_d):
    wid = bundle.word_ids_packed(wt_stream.word_key, fill=unseen_w)
    did = bundle.doc_ids_u32(wt_stream.ip_u32, fill=unseen_d)
    return did * np.int32(v_x) + wid


def test_device_idx_matches_host_mapping():
    cols, wt, bundle = _trained()
    v = bundle.corpus.n_vocab
    v_x, unseen_w, unseen_d = v + 1, v, bundle.corpus.n_docs
    tables = dw.build_flow_tables(bundle, wt.edges,
                                  list(cols["proto_classes"]))
    # A FRESH chunk (different seed): mixes seen and unseen ips/words.
    cols2 = SYNTH_ARRAYS["flow"](10_000, n_hosts=300, n_anomalies=25,
                                 seed=77)
    wt2 = _words_from_cols("flow", cols2, edges=dict(wt.edges))
    want = _host_idx(bundle, wt2, v_x, unseen_w, unseen_d)
    m = cols2["sip_u32"].shape[0]
    got_s, got_d = dw._flow_flat_idx(
        tables, v_x, unseen_w, unseen_d,
        jnp.asarray(cols2["sip_u32"]), jnp.asarray(cols2["dip_u32"]),
        jnp.asarray(cols2["sport"]), jnp.asarray(cols2["dport"]),
        jnp.asarray(cols2["proto_id"].astype(np.int32)),
        jnp.asarray(cols2["hour"]),
        jnp.asarray(cols2["ibyt"].astype(np.float32)),
        jnp.asarray(cols2["ipkt"].astype(np.float32)))
    # WordTable layout is [src tokens | dst tokens] with the same word.
    np.testing.assert_array_equal(np.asarray(got_s), want[:m])
    np.testing.assert_array_equal(np.asarray(got_d), want[m:])


def test_device_unseen_and_unknown_proto():
    cols, wt, bundle = _trained(n=5_000, n_hosts=100)
    v = bundle.corpus.n_vocab
    v_x, unseen_w, unseen_d = v + 1, v, bundle.corpus.n_docs
    # Declare one extra caller proto class absent from the fitted
    # table: events carrying it must map to the UNSEEN word row.
    classes = list(cols["proto_classes"]) + ["NEWPROTO"]
    tables = dw.build_flow_tables(bundle, wt.edges, classes)
    n = 64
    sip = np.full(n, np.uint32(0xDEAD0001))      # never trained
    dip = np.full(n, np.uint32(0xDEAD0002))
    got_s, got_d = dw._flow_flat_idx(
        tables, v_x, unseen_w, unseen_d,
        jnp.asarray(sip), jnp.asarray(dip),
        jnp.asarray(np.full(n, 40000, np.int32)),
        jnp.asarray(np.full(n, 50000, np.int32)),
        jnp.asarray(np.full(n, len(classes) - 1, np.int32)),
        jnp.asarray(np.full(n, 12.5, np.float32)),
        jnp.asarray(np.full(n, 1000.0, np.float32)),
        jnp.asarray(np.full(n, 10.0, np.float32)))
    np.testing.assert_array_equal(np.asarray(got_s),
                                  np.full(n, unseen_d * v_x + unseen_w))
    np.testing.assert_array_equal(np.asarray(got_d),
                                  np.full(n, unseen_d * v_x + unseen_w))


def test_fused_stream_selection_matches_host_path():
    cols, wt, bundle = _trained()
    rng = np.random.default_rng(9)
    d = bundle.corpus.n_docs
    v = bundle.corpus.n_vocab
    v_x, unseen_w, unseen_d = v + 1, v, d
    d_x = d + 1
    table = jnp.asarray(rng.random(d_x * v_x).astype(np.float32))
    tables = dw.build_flow_tables(bundle, wt.edges,
                                  list(cols["proto_classes"]))
    cols2 = SYNTH_ARRAYS["flow"](30_000, n_hosts=300, n_anomalies=30,
                                 seed=101)
    wt2 = _words_from_cols("flow", cols2, edges=dict(wt.edges))
    idx = _host_idx(bundle, wt2, v_x, unseen_w, unseen_d)
    m = cols2["sip_u32"].shape[0]
    want = scoring.table_pair_bottom_k(
        table, jnp.asarray(idx[:m]), jnp.asarray(idx[m:]),
        tol=1.0, max_results=200)
    got = dw.flow_stream_bottom_k(
        tables, table, cols2, v_x=v_x, unseen_w=unseen_w,
        unseen_d=unseen_d, tol=1.0, max_results=200)
    np.testing.assert_array_equal(np.asarray(got.indices),
                                  np.asarray(want.indices))
    np.testing.assert_array_equal(np.asarray(got.scores),
                                  np.asarray(want.scores))


@pytest.mark.parametrize("host", ["0", "1"])
def test_scale_runner_device_words(tmp_path, host, monkeypatch):
    """The scale runner produces equivalent artifacts with words on
    host or device (identical winners at this scale), and records the
    mode."""
    from onix.pipelines import scale

    monkeypatch.setenv("ONIX_HOST_WORDS", host)
    out = tmp_path / f"scale_{host}.json"
    doc = scale.run_scale(30_000, train_events=15_000, n_sweeps=8,
                          seed=5, out_path=out)
    assert doc["words_mode"] == ("host" if host == "1" else "device")
    assert doc["planted_in_bottom_k"] > 0
    if host == "0":
        assert doc["walls_seconds"].get("stream_words_map", 0.0) < 0.5


def test_scale_runner_device_vs_host_same_winners(tmp_path, monkeypatch):
    from onix.pipelines import scale

    res = {}
    for host in ("0", "1"):
        monkeypatch.setenv("ONIX_HOST_WORDS", host)
        res[host] = scale.run_scale(30_000, train_events=15_000,
                                    n_sweeps=8, seed=5)
    assert (res["0"]["planted_in_bottom_k"]
            == res["1"]["planted_in_bottom_k"])
    assert res["0"]["selected_score_range"] == res["1"]["selected_score_range"]


def _trained_dt(datatype, n=15_000, n_hosts=300, seed=3):
    cols = SYNTH_ARRAYS[datatype](n, n_hosts=n_hosts, n_anomalies=40,
                                  seed=seed)
    wt = _words_from_cols(datatype, cols)
    bundle = build_corpus(wt)
    return cols, wt, bundle


@pytest.mark.parametrize("datatype", ["dns", "proxy"])
def test_dns_proxy_fused_matches_host_path(datatype):
    cols, wt, bundle = _trained_dt(datatype)
    rng = np.random.default_rng(13)
    d = bundle.corpus.n_docs
    v = bundle.corpus.n_vocab
    v_x, unseen_w, unseen_d = v + 1, v, d
    table = jnp.asarray(rng.random((d + 1) * v_x).astype(np.float32))
    if datatype == "dns":
        tables = dw.build_dns_tables(bundle, wt.edges)
        fused = dw.dns_stream_bottom_k
    else:
        tables = dw.build_proxy_tables(bundle, wt.edges)
        fused = dw.proxy_stream_bottom_k
    cols2 = SYNTH_ARRAYS[datatype](12_000, n_hosts=300, n_anomalies=25,
                                   seed=171)
    wt2 = _words_from_cols(datatype, cols2, edges=dict(wt.edges))
    idx = _host_idx(bundle, wt2, v_x, unseen_w, unseen_d)
    want = scoring.table_bottom_k(table, jnp.asarray(idx), tol=1.0,
                                  max_results=150)
    got = fused(tables, table, cols2, wt.edges, v_x=v_x,
                unseen_w=unseen_w, unseen_d=unseen_d, tol=1.0,
                max_results=150)
    np.testing.assert_array_equal(np.asarray(got.indices),
                                  np.asarray(want.indices))
    np.testing.assert_array_equal(np.asarray(got.scores),
                                  np.asarray(want.scores))


def test_dns_out_of_compact_range_maps_unseen():
    cols, wt, bundle = _trained_dt("dns", n=4_000)
    v = bundle.corpus.n_vocab
    v_x, unseen_w, unseen_d = v + 1, v, bundle.corpus.n_docs
    tables = dw.build_dns_tables(bundle, wt.edges)
    d_x = bundle.corpus.n_docs + 1
    # Score table where the unseen cell is uniquely identifiable.
    table = np.ones(d_x * v_x, np.float32)
    table[unseen_d * v_x + unseen_w] = 1e-6
    n = 32
    cols2 = {
        "client_u32": np.full(n, np.uint32(0xDEAD0001)),
        "qname_codes": np.zeros(n, np.int64),
        "qnames": np.asarray(["x.evil.biz"], dtype=object),
        "qtype": np.full(n, 70_000, np.int64),     # > compact 8-bit range
        "rcode": np.zeros(n, np.int64),
        "frame_len": np.full(n, 120.0, np.float64),
        "hour": np.full(n, 12.0, np.float32),
    }
    got = dw.dns_stream_bottom_k(tables, jnp.asarray(table), cols2,
                                 wt.edges, v_x=v_x, unseen_w=unseen_w,
                                 unseen_d=unseen_d, tol=1.0, max_results=8)
    s = np.asarray(got.scores)
    # Guard against vacuous pass: a regression that maps these events
    # to a trained row yields all-inf results here.
    assert np.isfinite(s).any()
    assert np.allclose(s[np.isfinite(s)], 1e-6)


@pytest.mark.parametrize("datatype", ["dns", "proxy"])
def test_scale_runner_device_words_dns_proxy(tmp_path, datatype,
                                             monkeypatch):
    from onix.pipelines import scale

    res = {}
    for host in ("0", "1"):
        monkeypatch.setenv("ONIX_HOST_WORDS", host)
        res[host] = scale.run_scale(24_000, train_events=12_000,
                                    n_sweeps=8, seed=5, datatype=datatype)
        assert res[host]["words_mode"] == ("host" if host == "1"
                                           else "device")
    assert (res["0"]["planted_in_bottom_k"]
            == res["1"]["planted_in_bottom_k"])
    assert (res["0"]["selected_score_range"]
            == res["1"]["selected_score_range"])


def test_host_words_env_spellings(tmp_path, monkeypatch):
    """Device words are the DEFAULT; ONIX_HOST_WORDS=1 pins the host
    cross-check arm."""
    from onix.pipelines import scale

    monkeypatch.delenv("ONIX_HOST_WORDS", raising=False)
    m = scale.run_scale(20_000, train_events=10_000, n_sweeps=6, seed=5)
    assert m["words_mode"] == "device"
    monkeypatch.setenv("ONIX_HOST_WORDS", "1")
    m = scale.run_scale(20_000, train_events=10_000, n_sweeps=6, seed=5)
    assert m["words_mode"] == "host"


def test_staged_cols_match_raw_cols_path():
    """Double-buffered staging (stage_flow_cols + device_put in flight)
    must select exactly the winners the raw-numpy-cols call does."""
    cols, wt, bundle = _trained(n=8_000, n_hosts=150)
    rng = np.random.default_rng(4)
    v = bundle.corpus.n_vocab
    d = bundle.corpus.n_docs
    v_x, unseen_w, unseen_d = v + 1, v, d
    table = jnp.asarray(rng.random((d + 1) * v_x).astype(np.float32))
    tables = dw.build_flow_tables(bundle, wt.edges,
                                  list(cols["proto_classes"]))
    cols2 = SYNTH_ARRAYS["flow"](6_000, n_hosts=150, n_anomalies=10,
                                 seed=31)
    raw = dw.flow_stream_bottom_k(
        tables, table, cols2, v_x=v_x, unseen_w=unseen_w,
        unseen_d=unseen_d, tol=1.0, max_results=100)
    staged = dw.flow_stream_bottom_k(
        tables, table, dw.stage_flow_cols(cols2), v_x=v_x,
        unseen_w=unseen_w, unseen_d=unseen_d, tol=1.0, max_results=100)
    np.testing.assert_array_equal(np.asarray(staged.indices),
                                  np.asarray(raw.indices))
    np.testing.assert_array_equal(np.asarray(staged.scores),
                                  np.asarray(raw.scores))


def test_device_splitmix64_matches_host_hash():
    """The 32-bit-limb splitmix64 (streaming bucket path) is
    bit-identical to streaming._bucket_of_keys on the full int64 key
    range every word spec can emit."""
    import functools

    import jax

    from onix.pipelines.streaming import _bucket_of_keys, _datatype_salt

    rng = np.random.default_rng(0)
    keys = rng.integers(0, 1 << 52, 50_000).astype(np.int64)
    for dt in ("flow", "dns", "proxy"):
        salt = _datatype_salt(dt)
        for nb in (1 << 12, 1 << 15):
            want = _bucket_of_keys(keys, salt, nb)
            got = np.asarray(jax.jit(functools.partial(
                dw._splitmix64_bucket, salt=salt, n_buckets=nb))(
                jnp.asarray((keys >> 32).astype(np.uint32)),
                jnp.asarray((keys & 0xFFFFFFFF).astype(np.uint32))))
            np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("datatype", ["flow", "dns", "proxy"])
def test_stream_bucket_program_matches_host(datatype):
    """The fused streaming bucket program (binning → full-spec key →
    splitmix64) agrees with the host words+hash path per token, up to
    the documented f32 bin-edge caveat (<=1e-4 of tokens here)."""
    from onix.pipelines import columnar
    from onix.pipelines.streaming import _bucket_of_keys, _datatype_salt
    from onix.pipelines.synth import SYNTH

    nb = 1 << 13
    day, _ = SYNTH[datatype](n_events=15_000, n_hosts=200,
                             n_anomalies=15, seed=3)
    cols = columnar.FRAME_COLS[datatype](day)
    wt = columnar.words_from_cols(datatype, cols, edges=None)
    edges = wt.edges
    wt2 = columnar.words_from_cols(datatype, cols, edges=edges)
    salt = _datatype_salt(datatype)
    want = _bucket_of_keys(wt2.word_key, salt, nb)
    if datatype == "flow":
        t = dw.build_flow_stream_tables(edges, list(cols["proto_classes"]))
        got = np.asarray(dw.flow_stream_buckets(
            t, jnp.asarray(np.asarray(cols["sport"], np.int32)),
            jnp.asarray(np.asarray(cols["dport"], np.int32)),
            jnp.asarray(np.asarray(cols["proto_id"], np.int32)),
            jnp.asarray(np.asarray(cols["hour"], np.float32)),
            jnp.asarray(np.asarray(cols["ibyt"], np.float32)),
            jnp.asarray(np.asarray(cols["ipkt"], np.float32)),
            salt=salt, n_buckets=nb))
        got = np.concatenate([got, got])      # [src|dst] token layout
    elif datatype == "dns":
        t = dw.build_dns_stream_tables(edges, cols["qnames"])
        got = np.asarray(dw.dns_stream_buckets(
            t, jnp.asarray(np.asarray(cols["qname_codes"], np.int32)),
            jnp.asarray(np.asarray(cols["qtype"], np.int32)),
            jnp.asarray(np.asarray(cols["rcode"], np.int32)),
            jnp.asarray(np.asarray(cols["frame_len"], np.float32)),
            jnp.asarray(np.asarray(cols["hour"], np.float32)),
            salt=salt, n_buckets=nb))
    else:
        t = dw.build_proxy_stream_tables(edges, cols["uris"],
                                         cols["hosts"], cols["agents"])
        got = np.asarray(dw.proxy_stream_buckets(
            t, jnp.asarray(np.asarray(cols["uri_codes"], np.int32)),
            jnp.asarray(np.asarray(cols["host_codes"], np.int32)),
            jnp.asarray(np.asarray(cols["ua_codes"], np.int32)),
            jnp.asarray(np.asarray(cols["respcode"], np.int32)),
            jnp.asarray(np.asarray(cols["hour"], np.float32)),
            salt=salt, n_buckets=nb))
    mismatches = int((got != want).sum())
    assert mismatches <= max(2, len(want) // 10_000), mismatches


def test_scale_flow_table_build_failure_degrades_to_host(monkeypatch):
    """A trained flow vocabulary the compact keys cannot carry must
    degrade the (default) device path to the host arm mid-run —
    announced, never a crash — mirroring the dns/proxy upfront gate."""
    from onix.pipelines import device_words, scale

    def boom(*a, **kw):
        raise ValueError("synthetic compact-key overflow")

    monkeypatch.delenv("ONIX_HOST_WORDS", raising=False)
    monkeypatch.setattr(device_words, "build_flow_tables", boom)
    m = scale.run_scale(20_000, train_events=10_000, n_sweeps=6, seed=5)
    assert m["words_mode"] == "host"
    assert m["planted_in_bottom_k"] > 0


# ---------------------------------------------------------------------------
# _lookup_sorted (ISSUE 27): exact against numpy's search in every
# corner, in both of its forms, and no loop under the look-up scopes.
# ---------------------------------------------------------------------------


def _lookup_case(dtype, n_table, scenario):
    """(table, ids, keys, fill) of one corner. Tables ascend; ids are
    any int32 at all, so a right answer is no accident of small ids."""
    rng = np.random.default_rng([n_table, len(scenario)])
    lo, hi = int(np.iinfo(dtype).min), int(np.iinfo(dtype).max)
    n_keys = n_table // 2 if scenario == "short_block" else 3000

    def draw(low, high, size):
        return rng.integers(low, high, size=size, dtype=np.int64,
                            endpoint=True)

    if scenario == "extremes":      # the type's greatest and least key
        ends = [hi, lo, -1 if lo < 0 else 0][:n_table]
        table = np.unique(np.concatenate(
            [ends, draw(lo, hi, n_table - len(ends))]))
    else:                           # room below the first, above the last
        table = np.unique(draw(lo + 1000, hi - 1000, n_table + n_table // 8
                               + 1))[:n_table]
    if scenario == "dup_table":     # pairs of equals
        table = np.repeat(table[::2], 2)[:len(table)]
    ids = draw(-2 ** 31, 2 ** 31 - 1, len(table)).astype(np.int32)
    present = table[draw(0, len(table) - 1, n_keys)]
    absent = np.setdiff1d(draw(lo, hi, 2 * n_keys + 8), table)[:n_keys]
    if scenario == "all_present":
        keys = present
    elif scenario == "all_absent":
        keys = absent
    else:
        corners = np.array(
            [lo, hi, 0, -1 if lo < 0 else hi, table[0], table[-1],
             max(table[0] - 1, lo), min(table[-1] + 1, hi),
             table[len(table) // 2], table[len(table) // 2]] * 3)
        keys = np.where(rng.random(n_keys) < 0.6, present, absent)
        keys[:len(corners)] = corners[:n_keys]     # repeated keys too
    fill = {"mixed": len(table), "extremes": -1, "all_absent": -2 ** 31,
            "all_present": 2 ** 31 - 1, "short_block": 0,
            "dup_table": -7}[scenario]
    return table.astype(dtype), ids, keys.astype(dtype), fill


@pytest.mark.parametrize("scenario", ["mixed", "extremes", "all_absent",
                                      "all_present", "short_block",
                                      "dup_table"])
@pytest.mark.parametrize("n_table,form", [
    (1, "compare"), (1, "join"), (2, "compare"), (2, "join"),
    (349, "compare"), (349, "join"), (200_023, "join")])
@pytest.mark.parametrize("dtype", [np.uint32, np.int32])
def test_lookup_sorted_is_exact(dtype, n_table, form, scenario,
                                monkeypatch):
    # The product's lengths take their own form; the small tables are
    # also driven through the other one.
    if dw.lookup_form(n_table) != form:
        monkeypatch.setattr(dw, "_COMPARE_MAX",
                            0 if form == "join" else 1 << 30)
    table, ids, keys, fill = _lookup_case(dtype, n_table, scenario)
    assert dw.lookup_form(len(table)) == form
    # The host twin: np.searchsorted and an equality test.
    want = _sorted_table_lookup(table, keys, ids, fill)[0]
    if scenario == "all_absent":
        assert (want == fill).all()
    if scenario == "all_present":
        assert np.isin(keys, table).all()
    got = jax.jit(dw._lookup_sorted, static_argnums=3)(
        jnp.asarray(table), jnp.asarray(ids), jnp.asarray(keys), fill)
    assert got.dtype == jnp.int32 and got.shape == keys.shape
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("n", [1, 127, 128, 129, 16_385, 250_001])
def test_running_sum_is_cumsum_mod_2_to_32(n):
    x = np.random.default_rng(n).integers(-2 ** 31, 2 ** 31, size=n,
                                          dtype=np.int64).astype(np.int32)
    got = jax.jit(dw._running_sum)(jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(got),
                                  np.cumsum(x, dtype=np.int32))


def _take_case(dtype, n_table, n_idx):
    """A table of `n_table` 4-byte entries with the values a select
    keeps and a product would not, and indices into it: both ends,
    repeats, and ones only `table[idx]`'s own rules put in range."""
    rng = np.random.default_rng(n_table + n_idx)
    if dtype == np.float32:
        table = rng.standard_normal(n_table).astype(np.float32)
        odd = np.array([np.inf, -0.0, 1e-40, np.nan, -np.inf],
                       np.float32).view(np.int32)
        odd[3] |= 0x1234                          # a NaN with a payload
    else:
        table = rng.integers(-2 ** 31, 2 ** 31, n_table).astype(np.int32)
        odd = np.array([-1, -2 ** 31, 2 ** 31 - 1, 0x40000000, -7],
                       np.int32)
    where = rng.permutation(n_table)[:len(odd)]
    table.view(np.int32)[where] = odd[:len(where)]
    idx = rng.integers(0, n_table, n_idx)
    corners = np.array([0, n_table - 1, 0, n_table - 1, *where, *where,
                        n_table // 2, -1, -n_table, n_table,
                        -n_table - 5, 2 ** 31 - 1, -2 ** 31])
    idx[:len(corners)] = corners[:n_idx]
    return table, idx.astype(np.int32)


@pytest.mark.parametrize("n_idx", [1, 1000, 1024])
@pytest.mark.parametrize("n_table,form", [
    (1, "compare"), (1, "rows"), (127, "compare"), (127, "rows"),
    (128, "compare"), (128, "rows"), (129, "compare"), (129, "rows"),
    (512, "compare"), (512, "rows"), (2048, "compare"), (2048, "rows"),
    (70_001, "rows"), ((1 << 24) + 129, "rows")])
@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_take_is_table_of_idx_bit_for_bit(dtype, n_table, form, n_idx,
                                          monkeypatch):
    # The product's lengths take their own form; the short tables are
    # also driven through the other one. Runs of 256: a block of 1024
    # indices goes through the row form's inner scan, the others whole.
    if dw.take_form(n_table) != form:
        monkeypatch.setattr(dw, "_TAKE_COMPARE_MAX", 0)
    monkeypatch.setattr(dw, "_TAKE_RUN", 256)
    assert dw.take_form(n_table) == form
    table, idx = _take_case(dtype, n_table, n_idx)
    got = jax.jit(lambda t, i: dw._take(t)(i))(jnp.asarray(table),
                                               jnp.asarray(idx))
    assert got.dtype == table.dtype and got.shape == idx.shape
    # What `table[idx]` gives, by its own rules for the indices ...
    want = np.asarray(jnp.asarray(table)[jnp.asarray(idx)])
    np.testing.assert_array_equal(np.asarray(got).view(np.int32),
                                  want.view(np.int32))
    # ... and, where numpy has the same rules, what numpy gives.
    ok = (idx >= -n_table) & (idx < n_table)
    np.testing.assert_array_equal(
        np.asarray(got).view(np.int32)[ok], table[idx[ok]].view(np.int32))


def test_take_runs_are_the_products_own_length():
    """A block of 2^21 indices, the scan's own, splits into whole runs:
    the row form's inner scan is what the product runs."""
    assert (1 << 21) % dw._TAKE_RUN == 0 and (1 << 21) > dw._TAKE_RUN
    table, idx = _take_case(np.float32, 70_001, 2 * dw._TAKE_RUN)
    jaxpr = jax.make_jaxpr(lambda t, i: dw._take(t)(i))(table, idx)
    assert [e.params["length"] for e in jaxpr.jaxpr.eqns
            if e.primitive.name == "scan"] == [2]
    got = jax.jit(lambda t, i: dw._take(t)(i))(table, idx)
    ok = (idx >= 0) & (idx < len(table))
    np.testing.assert_array_equal(np.asarray(got).view(np.int32)[ok],
                                  table[idx[ok]].view(np.int32))


@pytest.mark.parametrize("datatype", ["flow", "dns", "proxy"])
def test_stream_scan_is_bit_equal_to_the_flat_gather_scan(datatype,
                                                          monkeypatch):
    """Each `*_stream_scan` returns the winners and the scores of the
    same program with `table[idx]` read by XLA's own gather."""
    from tests.test_trace_scopes import _scan_call
    fn, args, kw = _scan_call(datatype)

    # A function of its own: jit's trace cache is keyed by the function,
    # and the product's must not be filled from the patched source.
    def flat_scan(*a, **k):
        return fn.__wrapped__(*a, **k)

    flat = jax.jit(flat_scan, static_argnames=tuple(kw))
    with monkeypatch.context() as m:
        m.setattr(dw, "_take", lambda table: lambda idx: table[idx])
        want = flat(*args, **kw)
        per_element = f"(tensor<{args[1].shape[0]}xf32>, tensor<2048x1xi32>)"
        assert per_element in flat.lower(*args, **kw).as_text()
    assert per_element not in fn.lower(*args, **kw).as_text()
    got = fn(*args, **kw)
    np.testing.assert_array_equal(np.asarray(got.indices),
                                  np.asarray(want.indices))
    np.testing.assert_array_equal(np.asarray(got.scores).view(np.int32),
                                  np.asarray(want.scores).view(np.int32))
    assert np.isfinite(np.asarray(got.scores)).sum() == kw["max_results"]


def _eqns_under(jaxpr, scope, inside=False):
    """Every equation of `jaxpr` (sub-programs included) that was
    traced under a name scope starting with `scope`."""
    for eqn in jaxpr.eqns:
        under = inside or scope in str(eqn.source_info.name_stack)
        if under:
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns_under(sub, scope, under)


@pytest.mark.parametrize("datatype", ["flow", "dns", "proxy"])
def test_no_loop_under_the_lookup_scopes(datatype):
    """No binary search came back: under `onix.words.lookup_word` and
    `onix.words.lookup_doc` the traced program holds no loop, and the
    compiled one still carries every scan scope of its datatype."""
    import functools

    from tests.test_trace_scopes import SCOPES_OF, _scan_call, _scopes_in

    fn, args, kw = _scan_call(datatype)
    jaxpr = jax.make_jaxpr(functools.partial(fn, **kw))(*args).jaxpr
    for scope in ("onix.words.lookup_word", "onix.words.lookup_doc"):
        prims = {e.primitive.name for e in _eqns_under(jaxpr, scope)}
        assert prims, scope                      # the scope is there
        assert not prims & {"while", "scan", "cond"}, (scope, prims)
    # ... and the walk does see a loop where there is one.
    assert "scan" in {e.primitive.name
                      for e in _eqns_under(jaxpr, "", inside=True)}
    assert _scopes_in(fn.lower(*args, **kw).compile().as_text()) \
        == SCOPES_OF[datatype]


@pytest.mark.parametrize("datatype", ["flow", "dns", "proxy"])
def test_table_build_records_the_lookup_forms(datatype):
    from onix.utils import telemetry

    telemetry.reset_for_tests()
    cols, wt, bundle = _trained_dt(datatype)
    extra = [list(cols["proto_classes"])] if datatype == "flow" else []
    getattr(dw, f"build_{datatype}_tables")(bundle, wt.edges, *extra)
    (span,) = [s for s in telemetry.TRACER.spans()
               if s.name == "scan.tables"]
    n_w, n_d = bundle.corpus.n_vocab, bundle.corpus.n_docs
    assert span.attrs == {"datatype": datatype, "words": n_w, "docs": n_d,
                          "word": dw.lookup_form(n_w),
                          "doc": dw.lookup_form(n_d)}
    assert {span.attrs["word"], span.attrs["doc"]} <= {"compare", "join"}


# ---------------------------------------------------------------------------
# The datatype-keyed entry (ISSUE 29): TABLE_FNS / STAGE_FNS / SCAN_FNS
# are the named functions, reached without naming a datatype.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("datatype", ["flow", "dns", "proxy"])
def test_keyed_entry_is_bit_equal_to_the_named_functions(datatype):
    cols, wt, bundle = _trained_dt(datatype, n=9_000)
    d, v = bundle.corpus.n_docs, bundle.corpus.n_vocab
    table = jnp.asarray(np.random.default_rng(17).random(
        (d + 1) * (v + 1)).astype(np.float32))
    kw = dict(v_x=v + 1, unseen_w=v, unseen_d=d, tol=1.0, max_results=120)
    cols2 = SYNTH_ARRAYS[datatype](7_000, n_hosts=300, n_anomalies=20,
                                   seed=41)
    build = getattr(dw, f"build_{datatype}_tables")
    fused = getattr(dw, f"{datatype}_stream_bottom_k")
    if datatype == "flow":
        want = fused(build(bundle, wt.edges, list(cols2["proto_classes"])),
                     table, cols2, **kw)
    else:
        want = fused(build(bundle, wt.edges), table, cols2, wt.edges, **kw)
    # As a driver spells it: no datatype outside the three look-ups; the
    # tables that need no chunk are built without one.
    chunk = cols2 if datatype in dw.TABLES_FROM_CHUNK else None
    tables = dw.TABLE_FNS[datatype](bundle, wt.edges, chunk)
    got = dw.SCAN_FNS[datatype](tables, table,
                                dw.STAGE_FNS[datatype](cols2, wt.edges),
                                wt.edges, **kw)
    np.testing.assert_array_equal(np.asarray(got.indices),
                                  np.asarray(want.indices))
    np.testing.assert_array_equal(np.asarray(got.scores),
                                  np.asarray(want.scores))
    assert np.isfinite(np.asarray(got.scores)).sum() == 120
    assert set(dw.TABLE_FNS) == set(dw.STAGE_FNS) == set(dw.SCAN_FNS)
