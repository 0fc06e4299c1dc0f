"""Driver `dayscan` and the proxy reference at a tiny size on the CPU
(ISSUE 29): the `proxy-scan` cell as files alone, its controls, the
proxy word broken underneath, `dayscan` against `scan` on the two
datatypes both can run, and the reference's words against the program's
host path."""

import json
import pathlib

import numpy as np
import pytest

from benchmark import harness

ROOT = pathlib.Path(__file__).resolve().parents[2]
SEED = 3000000019
TINY = dict(n_hosts=300, block_size=4096, max_results=50,
            scan_model_sweeps=6, burn_in=3)
# Fewer planted events than winners: the planted all carry the unseen
# word whatever becomes of their fields, so a broken word shows only in
# the winners that are ordinary events.
MIX = {"chunk_events": 60000, "train_events": 8000, "anomalies": 20,
       "data_seed": 7, "order_blocks": 16, "trace_chunks": 2}


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """The repo's three configurations cut to a CPU's size, each under
    both scan drivers where both can run it; nothing but files."""
    root = tmp_path_factory.mktemp("dayscan_pr")
    own = root / "added"
    for d in ("configs", "traffic"):
        (own / d).mkdir(parents=True)
    configs = ("flow-k20", "dns-k20", "proxy-k20")
    for name in configs:
        cfg = json.loads((ROOT / "benchmark/configs" / f"{name}.json")
                         .read_text())
        cfg.update(TINY)
        (own / "configs" / f"{name}-tiny.json").write_text(json.dumps(cfg))
    real = json.loads((ROOT / "benchmark/traffic/dayscan-1e8.json")
                      .read_text())
    assert set(MIX) | {"driver", "what"} == set(real)
    for driver in ("scan", "dayscan"):
        (own / f"traffic/{driver}-tiny.json").write_text(
            json.dumps(dict(MIX, driver=driver)))
    cells = [{"name": f"{c.split('-')[0]}.{d}", "config": f"{c}-tiny",
              "traffic": f"{d}-tiny", "chips": 1, "why": "test"}
             for c in configs for d in ("scan", "dayscan")
             if (c, d) != ("proxy-k20", "scan")]
    (root / "BENCHMARK.json").write_text(json.dumps({
        "command": ["python3", "benchmark/run.py"], "paths": ["added"],
        "run_seconds": 1,
        "configs": [{"name": f"{c}-tiny", "source": "test",
                     "file": f"added/configs/{c}-tiny.json",
                     "reduced": [], "why": "test"} for c in configs],
        "workloads": cells,
        "end_to_end": [
            {"name": "scan_events_per_s", "unit": "events/s",
             "better": "higher", "bound": 0.05, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1,
             "source": "host_clock"}],
        "per_layer": [{"name": "stage_s_per_chunk", "unit": "s",
                       "better": "lower", "source": "host_clock",
                       "layer": "host staging",
                       "moves": "scan_events_per_s"}]}))
    return harness.Manifest(root / "BENCHMARK.json")


def run(tiny, cell, **kw):
    return harness.run_cell(cell, SEED, 0.5, False, manifest=tiny,
                            require_chip=False, **kw)


def test_the_proxy_cell_of_files_alone_runs_a_short_window(tiny):
    line = run(tiny, "proxy.dayscan")
    assert line["correct"], line["check"]
    assert line["attempted"] >= 2 and line["failed"] == 0
    assert set(line["metrics"]) == {"scan_events_per_s", "setup_s"}
    for number in ("score_gap", "winner_gap", "answer_mismatch",
                   "replay_mismatch", "compiles_in_window"):
        assert line["check"][number]["value"] == 0, line["check"]
    assert line["check"]["winners_due"]["value"] == TINY["max_results"]
    assert line["window"]["items_per_call"] == MIX["chunk_events"]
    assert {"synth", "model_front", "model_fit", "tables", "deal", "warmup",
            "dispatch", "stage", "fetch", "chunk"} <= set(
                line["window"]["spans"])


def test_each_control_of_the_proxy_cell_comes_out_as_not_correct(tiny):
    controls = {"bf16_table": "score_gap", "half_chunk": "winner_gap"}
    line = run(tiny, "proxy.dayscan", control=",".join(controls))
    assert line["correct"], line["check"]
    for name, number in controls.items():
        got = line["controls"][name]
        row = got["check"][number]
        assert not got["correct"] and row["value"] > row["limit"], got


@pytest.mark.parametrize("fault", ["field_shifted", "column_dropped"])
def test_a_broken_proxy_word_is_not_correct(tiny, monkeypatch, fault):
    """The URI's entropy bin packed one bit off, or the agents' column
    left out of the key: the winners' scores are no longer the
    reference's."""
    from onix.pipelines import device_words as dw
    real = dw.proxy_partial_keys

    def broken(uris, hosts, agents, edges):
        uri_p, host_p, ua_p = real(uris, hosts, agents, edges)
        if fault == "field_shifted":
            at = dw._PROXY_UEBIN_SHIFT
            uri_p = (uri_p & ~(7 << at)) | (((uri_p >> at) & 7) << (at + 1))
        else:
            ua_p = np.zeros_like(ua_p)
        return uri_p, host_p, ua_p
    monkeypatch.setattr(dw, "proxy_partial_keys", broken)
    line = run(tiny, "proxy.dayscan")
    row = line["check"]["score_gap"]
    assert not line["correct"] and row["value"] > row["limit"], line["check"]


@pytest.mark.parametrize("datatype", ["flow", "dns"])
def test_dayscan_answers_as_scan_does(tiny, monkeypatch, datatype):
    """Same seed, same files: every chunk's winners and scores, and the
    numbers compared, are those of `drivers/scan.py` (the case that lets
    a later `benchmark` PR fold the two cells onto `dayscan`)."""
    from onix.pipelines import device_words as dw
    name = f"{datatype}_stream_bottom_k"
    real = getattr(dw, name)
    seen = []

    def recording(*a, **kw):
        top = real(*a, **kw)
        seen.append((np.asarray(top.indices), np.asarray(top.scores)))
        return top
    monkeypatch.setattr(dw, name, recording)
    got = {}
    for driver in ("scan", "dayscan"):
        seen.clear()
        line = run(tiny, f"{datatype}.{driver}", control="half_chunk")
        assert line["correct"], line["check"]
        got[driver] = (list(seen), line)
    (a, line_a), (b, line_b) = got["scan"], got["dayscan"]
    # The warm-up's answer, the first timed one, and the control's: the
    # window may hold more chunks in one run than in the other.
    assert len(a) >= 4 and len(b) >= 4
    for (ia, sa), (ib, sb) in zip([*a[:2], a[-1]], [*b[:2], b[-1]]):
        np.testing.assert_array_equal(ia, ib)
        np.testing.assert_array_equal(sa, sb)
    assert (a[0][0] >= 0).sum() == TINY["max_results"]
    for number in ("winners_due", "kth_score", "score_gap", "winner_gap",
                   "answer_mismatch", "replay_mismatch"):
        assert (line_a["check"][number]["value"]
                == line_b["check"][number]["value"]), number
    assert (line_a["controls"]["half_chunk"]["check"]
            == line_b["controls"]["half_chunk"]["check"])
    assert line_a["window"]["n_vocab"] == line_b["window"]["n_vocab"]


def test_dayscan_names_no_datatype_but_for_its_reference():
    import re
    src = (ROOT / "benchmark/drivers/dayscan.py").read_text()
    code = src.split('"""', 2)[2]
    assert re.findall(r"\b(?:flow|dns|proxy)\w*", code) == [
        "proxy", "proxy_scan_check"]
    ref = (ROOT / "benchmark/reference/proxy_scan_check.py").read_text()
    assert not re.search(r"^\s*(from|import)\s+onix", ref, re.M)


def _seeded_proxy_day():
    """A trained window and a later day that holds what the window never
    saw: agents outside the fitted table, raw-IP hosts, new clients."""
    from onix.pipelines.corpus_build import build_corpus
    from onix.pipelines.synth import SYNTH_ARRAYS
    from onix.pipelines.words import proxy_words_from_arrays

    def events(cols, n=None):
        m = len(cols["hour"])
        return {k: (v[:n] if len(v) == m else v) for k, v in cols.items()
                if k != "anomaly_idx"}
    train = SYNTH_ARRAYS["proxy"](12_000, n_hosts=200, n_anomalies=40,
                                  seed=11)
    wt = proxy_words_from_arrays(**events(train, 11_000))
    bundle = build_corpus(wt)
    day = SYNTH_ARRAYS["proxy"](9_000, n_hosts=260, n_anomalies=300,
                                seed=12)
    # A common agent on a raw-IP host, a rare agent on a named one, a
    # response code of no class the model holds, and a negative one.
    n_bg = len(day["hour"]) - 300
    day["host_codes"][:50] = day["host_codes"][-1]
    day["ua_codes"][50:100] = day["ua_codes"][-1]
    day["respcode"][100:110] = 503
    day["respcode"][110:120] = -1
    assert n_bg > 120
    return bundle, wt, events(day)


def test_the_reference_words_are_the_host_paths():
    from benchmark.reference import proxy_scan_check
    from onix.pipelines.words import _UA_RARE, proxy_words_from_arrays

    config = json.loads((ROOT / "benchmark/configs/proxy-k20.json")
                        .read_text())
    assert config["ua_rare"] == _UA_RARE
    bundle, wt, day = _seeded_proxy_day()
    v = bundle.corpus.n_vocab
    k = 4
    rng = np.random.default_rng(0)
    model = {"theta": rng.dirichlet(np.ones(k), bundle.corpus.n_docs)
             .astype(np.float32),
             "phi_wk": rng.dirichlet(np.ones(v), k).T.astype(np.float32),
             "word_key_sorted": np.asarray(bundle.word_key_sorted),
             "word_key_ids": np.asarray(bundle.word_key_ids),
             "doc_u32_sorted": np.asarray(bundle.doc_u32_sorted),
             "doc_u32_ids": np.asarray(bundle.doc_u32_ids),
             "edges": wt.edges}
    n = len(day["hour"])
    got = proxy_scan_check.word_ids(config, model, day, n, block=4096)
    wt_day = proxy_words_from_arrays(**day, edges=dict(wt.edges))
    want = bundle.word_ids_packed(wt_day.word_key, fill=v)
    # A negative response code is the one place where the host path's
    # pack (which masks the class to its field) and the scan part ways;
    # the scan's rule is the configuration's: the extra row.
    negative = np.asarray(day["respcode"]) < 0
    assert negative.sum() == 10 and (got[negative] == v).all()
    np.testing.assert_array_equal(got[~negative], want[~negative])
    # Not vacuous: seen words, unseen ones, and both planted kinds.
    assert (got < v).sum() > 5_000 and (got == v).sum() >= 300
    assert (got[:50] == v).all()             # raw-IP host: never trained
    assert (got[50:100] == v).all()          # agent outside the table
    # ... and the scores are the table's, client by client.
    scores = np.asarray(proxy_scan_check.all_scores(
        dict(config, tol=2.0), model, day, n, block=4096))
    theta_x, phi_x = proxy_scan_check.extend_for_unseen(
        model["theta"], model["phi_wk"])
    did = bundle.doc_ids_u32(np.asarray(day["client_u32"], np.uint32),
                             fill=len(theta_x) - 1)
    assert (did == len(theta_x) - 1).any()   # clients the model never saw
    np.testing.assert_allclose(
        scores, np.einsum("nk,nk->n", theta_x[did], phi_x[got]), rtol=1e-5)
