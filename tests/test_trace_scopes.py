"""The names the work carries (ISSUE 26): `onix.*` device scopes in the
compiled scan and sweep programs, and the program spans around them.

Contract: a scope is metadata (the `op_name` of each compiled
instruction) and a span is host bookkeeping - neither changes what a
program computes; every scope of docs/OBSERVABILITY.md's table is in
the compiled text of the program it names; and `_stream_score`'s three
stream walls are filled by the spans' clock whether telemetry records
or not.
"""

import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from onix.config import LDAConfig
from onix.corpus import anomaly_corpus
from onix.parallel.mesh import make_mesh
from onix.parallel.sharded_gibbs import ShardedGibbsLDA
from onix.pipelines import device_words as dw
from onix.pipelines.corpus_build import build_corpus
from onix.pipelines import scale
from onix.pipelines.scale import _words_from_cols
from onix.pipelines.synth import SYNTH_ARRAYS
from onix.utils import obs, telemetry

SCAN_SCOPES = {"onix.words.bin", "onix.words.lookup_word",
               "onix.words.lookup_doc", "onix.score.gather", "onix.select"}
# The datatypes with dictionary-coded string columns gather their
# per-unique partial keys under a scope of their own (ISSUE 29).
DICT_SCOPES = SCAN_SCOPES | {"onix.words.dict_gather"}
SCOPES_OF = {"flow": SCAN_SCOPES, "dns": DICT_SCOPES, "proxy": DICT_SCOPES}
SWEEP_SCOPES = {"onix.sweep.gather", "onix.sweep.sample",
                "onix.sweep.scatter", "onix.sweep.nwk", "onix.sweep.loglik"}


def _toy(datatype, n=6_000, max_results=40):
    """One toy chunk of `datatype`, its trained bundle, a random score
    table and the keywords every `*_stream_bottom_k` takes."""
    cols = SYNTH_ARRAYS[datatype](n, n_hosts=200, n_anomalies=20, seed=3)
    wt = _words_from_cols(datatype, cols)
    bundle = build_corpus(wt)
    d, v = bundle.corpus.n_docs, bundle.corpus.n_vocab
    table = jnp.asarray(np.random.default_rng(5).random(
        (d + 1) * (v + 1)).astype(np.float32))
    kw = dict(v_x=v + 1, unseen_w=v, unseen_d=d, tol=1.0,
              max_results=max_results)
    return cols, wt, bundle, table, kw


def _scan_call(datatype):
    """(jitted scan, its arguments) for one toy chunk of `datatype`."""
    cols, wt, bundle, table, kw = _toy(datatype)
    kw["chunk"] = 1 << 11
    if datatype == "flow":
        st = dw.stage_flow_cols(cols)
        tables = dw.build_flow_tables(bundle, wt.edges,
                                      list(cols["proto_classes"]))
        names = ("sip_u32", "dip_u32", "sport", "dport", "proto_id", "hour",
                 "ibyt", "ipkt")
        fn = dw._flow_stream_scan
    elif datatype == "dns":
        st = dw.stage_dns_cols(cols, wt.edges)
        tables = dw.build_dns_tables(bundle, wt.edges)
        names = ("partial_u", "client_u32", "qname_codes", "qtype", "rcode",
                 "frame_len", "hour")
        fn = dw._dns_stream_scan
    else:
        st = dw.stage_proxy_cols(cols, wt.edges)
        tables = dw.build_proxy_tables(bundle, wt.edges)
        names = ("uri_p", "host_p", "ua_p", "client_u32", "uri_codes",
                 "host_codes", "ua_codes", "respcode", "hour")
        fn = dw._proxy_stream_scan
    return fn, (tables, table, *(st[k] for k in names)), kw


def _fit_model(n_sweeps=2):
    corpus, _ = anomaly_corpus(n_docs=40, n_vocab=60, mean_doc_len=30,
                               n_topics=4, n_anomalies=4, seed=2)
    cfg = LDAConfig(n_topics=5, n_sweeps=n_sweeps, burn_in=1,
                    block_size=256, seed=4)
    return corpus, ShardedGibbsLDA(cfg, corpus.n_vocab,
                                   mesh=make_mesh(dp=1, mp=1))


def _superstep_call():
    corpus, model = _fit_model()
    sc = model.prepare(corpus)
    docs, words, mask = model.device_corpus(sc)
    assert model.dp1_fast
    return (model._superstep, (model.init_state(sc), docs, words, mask, 0),
            dict(n_steps=1, with_initial_ll=False))


def _scopes_in(text: str) -> set[str]:
    found = set()
    for op_name in re.findall(r'op_name="([^"]*)"', text):
        found.update(p for p in op_name.split("/") if p.startswith("onix."))
    return found


@pytest.mark.parametrize("program,want", [
    ("flow", SCAN_SCOPES), ("dns", DICT_SCOPES), ("proxy", DICT_SCOPES),
    ("superstep", SWEEP_SCOPES)])
def test_every_scope_is_in_the_compiled_program(program, want):
    fn, args, kw = (_superstep_call() if program == "superstep"
                    else _scan_call(program))
    text = fn.lower(*args, **kw).compile().as_text()
    assert _scopes_in(text) == want


@pytest.mark.parametrize("datatype", ["flow", "dns", "proxy"])
def test_no_per_element_gather_under_the_read_scopes(datatype):
    """ISSUE 30: the score table and the dictionaries are read through
    `device_words._take`. Under `onix.score.gather` and
    `onix.words.dict_gather` the program still has ops, none of them a
    gather of one scalar per index; and the lowered text holds no such
    gather from the score table's operand, whatever scope it is in."""
    import functools

    from tests.test_device_words import _eqns_under
    fn, args, kw = _scan_call(datatype)
    jaxpr = jax.make_jaxpr(functools.partial(fn, **kw))(*args).jaxpr
    reads = {"onix.score.gather"} | (SCOPES_OF[datatype]
                                     & {"onix.words.dict_gather"})
    for scope in reads:
        eqns = list(_eqns_under(jaxpr, scope))
        assert eqns, scope
        slices = [tuple(e.params["slice_sizes"]) for e in eqns
                  if e.primitive.name == "gather"]
        assert (1,) not in slices, (scope, slices)
        # The toy score table is long enough for the row form, the toy
        # dictionaries are compared whole.
        assert slices == ([(1, 128)] * (2 if datatype == "flow" else 1)
                          if scope == "onix.score.gather" else []), slices
    table = args[1]
    assert dw.take_form(table.shape[0]) == "rows"
    text = fn.lower(*args, **kw).as_text()
    gathers = re.findall(r'"stablehlo\.gather"\(.*', text)
    assert gathers                          # the winners' are still there
    per_element = [g for g in gathers
                   if re.search(r"slice_sizes = array<i64: 1>", g)]
    from_table = f"(tensor<{table.shape[0]}xf32>"
    assert not [g for g in per_element if from_table in g]
    assert any("slice_sizes = array<i64: 1, 128>" in g for g in gathers)


def test_a_scope_makes_metadata_part_of_the_compile_cache_key():
    """The persistent compile cache leaves `op_name` out of its key by
    default, so a process could load an executable compiled from
    scope-less source and trace every op unscoped (seen on the chip,
    PR 26). Tracing a scope turns the flag on before the program it is
    in is compiled."""
    flag = "jax_compilation_cache_include_metadata_in_key"
    jax.config.update(flag, False)

    @jax.jit
    def f(x):
        with obs.device_scope("onix.select"):
            return x + 1

    text = f.lower(jnp.zeros(4)).compile().as_text()
    assert getattr(jax.config, flag) is True
    assert "onix.select" in _scopes_in(text)


_TWIN = """
import contextlib, sys, jax, jax.numpy as jnp
from onix.utils.obs import device_scope
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
scope = (device_scope if sys.argv[2] == "scoped"
         else lambda name: contextlib.nullcontext())
@jax.jit
def f(x):
    with scope("onix.select"):
        return jnp.sort(x * 2 + 1)
print("onix.select" in f.lower(jnp.zeros(1000)).compile().as_text())
"""


def test_scopes_survive_a_cache_filled_from_scopeless_source(tmp_path):
    """Two processes, one persistent compile cache: the first compiles
    the arithmetic without a scope (the parent commit, as it were), the
    second with one. The second has to run its own executable."""
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(pathlib.Path(__file__).resolve().parents[1]))
    said = [subprocess.run(
        [sys.executable, "-c", _TWIN, str(tmp_path), mode], env=env,
        capture_output=True, text=True, timeout=120).stdout.split()[-1:]
        for mode in ("plain", "scoped")]
    assert said == [["False"], ["True"]]
    assert any(tmp_path.iterdir())          # the cache was in use


@pytest.fixture
def telemetry_off(monkeypatch):
    """ONIX_TELEMETRY=0 as a process would see it at start."""
    def switch(off: bool):
        if off:
            monkeypatch.setenv("ONIX_TELEMETRY", "0")
        else:
            monkeypatch.delenv("ONIX_TELEMETRY", raising=False)
        telemetry.reset_for_tests()
        assert telemetry.TRACER.enabled is (not off)
    yield switch
    monkeypatch.delenv("ONIX_TELEMETRY", raising=False)
    telemetry.reset_for_tests()


@pytest.mark.parametrize("datatype", ["flow", "dns"])
def test_winners_bit_identical_with_telemetry_off(datatype, telemetry_off):
    cols, wt, bundle, table, kw = _toy(datatype)
    got = {}
    for off in (False, True):
        telemetry_off(off)
        if datatype == "flow":
            tables = dw.build_flow_tables(bundle, wt.edges,
                                          list(cols["proto_classes"]))
            top = dw.flow_stream_bottom_k(tables, table, cols, **kw)
        else:
            tables = dw.build_dns_tables(bundle, wt.edges)
            top = dw.dns_stream_bottom_k(tables, table, cols, wt.edges, **kw)
        got[off] = (np.asarray(top.indices), np.asarray(top.scores))
        names = [s.name for s in telemetry.TRACER.spans()]
        assert ("scan.dispatch" in names) is (not off)
    np.testing.assert_array_equal(got[False][0], got[True][0])
    np.testing.assert_array_equal(got[False][1], got[True][1])


def test_state_after_two_sweeps_bit_identical_with_telemetry_off(
        telemetry_off):
    got = {}
    for off in (False, True):
        telemetry_off(off)
        corpus, model = _fit_model(n_sweeps=2)
        fit = model.fit(corpus)
        got[off] = [np.asarray(a) for a in fit["state"]] + [fit["theta"]]
        names = {s.name for s in telemetry.TRACER.spans()}
        if off:
            assert not names
        else:
            assert {"fit.prepare", "fit.device_corpus", "fit.init_state",
                    "fit.superstep", "fit.wait", "fit.estimates"} <= names
    for a, b in zip(got[False], got[True]):
        np.testing.assert_array_equal(a, b)


def test_fit_spans_count_the_work_at_their_boundary():
    telemetry.reset_for_tests()
    corpus, model = _fit_model(n_sweeps=3)
    seen = []
    model.fit(corpus, callback=lambda s, st: seen.append(s))
    spans = {}
    for s in telemetry.TRACER.spans():
        spans.setdefault(s.name, []).append(s)
    slots = -(-corpus.n_tokens // 256) * 256
    assert spans["fit.prepare"][0].attrs == {
        "tokens": corpus.n_tokens, "docs": corpus.n_docs, "shards": 1,
        "tokens_max_shard": corpus.n_tokens,
        "tokens_min_shard": corpus.n_tokens,
        "pad_slots": slots - corpus.n_tokens}
    # One chain's n_wk [V, K] and n_k [K], int32: what a merge moves.
    assert spans["fit.supersteps"][0].attrs == {
        "sweeps": 3, "merge_form": "sync",
        "merge_bytes_per_sweep": (corpus.n_vocab * 5 + 5) * 4,
        "ndk_form": "rows", "ndk_group": 1,
        "ndk_rows_packed": corpus.n_docs}
    assert spans["fit.init_state"][0].attrs["resumed"] is False
    assert spans["fit.init_state"][0].attrs["bytes"] > 0
    assert spans["fit.device_corpus"][0].attrs["bytes"] > 0
    # A callback makes every sweep a dispatch of its own.
    assert [s.attrs for s in spans["fit.superstep"]] == [
        {"start": i, "sweeps": 1, "with_initial_ll": i == 0}
        for i in range(3)]
    assert len(spans["fit.wait"]) == 3
    assert [s.attrs["sweep"] for s in spans["fit.notify"]] == seen == [0, 1, 2]


@pytest.mark.parametrize("off", [False, True])
def test_stream_walls_filled_and_one_dispatch_per_chunk(off, telemetry_off,
                                                        monkeypatch):
    telemetry_off(off)
    seen = {}
    stream_score = scale._stream_score

    def keep_walls(*a, walls, **kw):
        seen["walls"] = walls           # unrounded, filled in place
        return stream_score(*a, walls=walls, **kw)

    monkeypatch.setattr(scale, "_stream_score", keep_walls)
    m = scale.run_scale(90_000, train_events=30_000, n_hosts=300,
                        n_sweeps=4, datatype="flow")
    # The walls come from the spans' clock, recorded or not.
    for k in ("stream_synth", "stream_words_map", "stream_score"):
        assert seen["walls"][k] > 0
        assert m["walls_seconds"][k] == round(seen["walls"][k], 2)
    spans = telemetry.TRACER.spans()
    if off:
        assert not spans
        return
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    # Chunk 0 is the training window (ids reused); two chunks stream.
    assert [s.attrs["events"] for s in by_name["scan.dispatch"]] == [
        30_000, 30_000]
    assert [s.attrs["chunk"] for s in by_name["scan.synth"]] == [1, 2]
    assert [s.attrs["chunk"] for s in by_name["scan.fetch"]] == [0, 1, 2]
    assert len(by_name["scan.stage"]) == 2
    stage_ids = {s.span_id for s in by_name["scan.stage"]}
    puts = by_name["scan.h2d_put"]
    assert len(puts) == 2 * 8 and {p.parent_id for p in puts} == stage_ids
    assert sum(p.attrs["bytes"] for p in puts) == 2 * 30_000 * 8 * 4
    assert "scan.checkpoint" not in by_name       # no resume dir given


@pytest.mark.parametrize("datatype,sizes", [
    ("dns", ("names",)), ("proxy", ("uris", "hosts", "agents"))])
def test_partials_span_is_a_child_of_stage_and_counts_the_dictionaries(
        datatype, sizes):
    """`scan.partials` holds the per-unique string work alone, inside
    `scan.stage` and beside the `scan.h2d_put` children, with the
    dictionary sizes as attributes."""
    telemetry.reset_for_tests()
    cols, wt, *_ = _toy(datatype)
    dw.STAGE_FNS[datatype](cols, wt.edges)
    by_name = {}
    for s in telemetry.TRACER.spans():
        by_name.setdefault(s.name, []).append(s)
    (stage,), (partials,) = by_name["scan.stage"], by_name["scan.partials"]
    assert partials.parent_id == stage.span_id
    assert {p.parent_id for p in by_name["scan.h2d_put"]} == {stage.span_id}
    dicts = {"names": "qnames"}
    assert partials.attrs == {k: len(cols[dicts.get(k, k)]) for k in sizes}
    assert 0 <= partials.dur_s <= stage.dur_s
