"""Driver `stream`, the stream's reference and its readers at a tiny size
on the CPU (ISSUE 35): the `flow-stream-catchup` cell as files alone
through the temporary-directory route, each control, the program held to
`reference/stream_check.py` on seeded data, and a program broken
underneath."""

import json
import pathlib

import numpy as np
import pytest

from benchmark import harness

ROOT = pathlib.Path(__file__).resolve().parents[2]
SEED = 3000000019
TINY = dict(n_hosts=2000, n_buckets=2048, max_results=50, n_topics=6,
            tol=0.25, check={"sample_docs": 5000, "sample_big": 4})
MIX = dict(batch_events=3000, superstep_batches=3, backlog_batches=6,
           anomalies=20, data_seed=7, order_blocks=16)
CONTROLS = {"lam_stale": "lam_gap", "cold_start": "store_mismatch",
            "half_scored": "winner_gap", "bf16_estep": "lam_gap"}


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """The repo's own configuration and mix cut to a CPU's size; nothing
    but files."""
    root = tmp_path_factory.mktemp("stream_pr")
    own = root / "added"
    for d in ("configs", "traffic"):
        (own / d).mkdir(parents=True)
    cfg = json.loads((ROOT / "benchmark/configs/flow-stream-svi.json")
                     .read_text())
    cfg.update(TINY)
    (own / "configs/stream-tiny.json").write_text(json.dumps(cfg))
    mix = json.loads((ROOT / "benchmark/traffic/stream-5min-backlog.json")
                     .read_text())
    assert mix["driver"] == "stream" and set(MIX) < set(mix)
    mix.update(MIX)
    (own / "traffic/stream-tiny.json").write_text(json.dumps(mix))
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    (root / "BENCHMARK.json").write_text(json.dumps({
        "command": ["python3", "benchmark/run.py"], "paths": ["added"],
        "run_seconds": 1,
        "configs": [{"name": "stream-tiny", "source": "test",
                     "file": "added/configs/stream-tiny.json",
                     "reduced": [], "why": "test"}],
        "workloads": [{"name": "stream", "config": "stream-tiny",
                       "traffic": "stream-tiny", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "scan_events_per_s", "unit": "events/s",
             "better": "higher", "bound": 0.05, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1,
             "source": "host_clock"}],
        "per_layer": [dict(m, workloads=["stream"])
                      for m in real["per_layer"]
                      if m.get("workloads") == ["flow-stream-catchup"]]}))
    return harness.Manifest(root / "BENCHMARK.json")


@pytest.fixture(scope="module")
def line(tiny):
    return harness.run_cell("stream", SEED, 0.5, False, manifest=tiny,
                            require_chip=False, control=",".join(CONTROLS))


def test_the_stream_cell_of_files_alone_runs_a_short_window(line):
    assert line["correct"], line["check"]
    assert line["attempted"] >= 2 and line["failed"] == 0
    assert set(line["metrics"]) == {"scan_events_per_s", "setup_s"}
    for number in ("doc_mismatch", "store_mismatch", "winner_gap",
                   "answer_mismatch",
                   "replay_mismatch", "compiles_in_window",
                   "new_docs_in_window"):
        assert line["check"][number]["value"] == 0, line["check"]
    assert line["check"]["n_due"]["value"] == TINY["max_results"]
    w = line["window"]
    assert w["batches_per_call"] == 3 and w["items_per_call"] == 9000
    assert w["events"] == w["supersteps"] * 9000
    assert len(w["token_passes_by_call"]) == w["supersteps"]
    assert all(p >= 4 * 3 * 6000 for p in w["token_passes_by_call"])
    assert w["counters"]["stream.estep_iters"] >= 4 * w["batches"]
    assert {"synth", "deal", "first_batch", "first_sight", "superstep",
            "replay", "reference"} <= set(w["spans"])


@pytest.mark.parametrize("name", sorted(CONTROLS))
def test_each_control_of_the_stream_cell_comes_out_as_not_correct(line, name):
    got = line["controls"][name]
    row = got["check"][CONTROLS[name]]
    assert not got["correct"] and row["value"] > row["limit"], got


def test_the_reference_imports_nothing_of_the_program():
    src = (ROOT / "benchmark/reference/stream_check.py").read_text()
    imports = [ln for ln in src.splitlines()
               if ln.lstrip().startswith(("import ", "from "))]
    assert imports and not any("onix" in ln or "benchmark" in ln
                               for ln in imports), imports


@pytest.fixture(scope="module")
def stream(tiny):
    """A scorer driven as the driver drives it, kept for the tests that
    look inside: two groups of three batches, the state before the last
    batch, and what the last superstep left."""
    from onix.pipelines.synth import SYNTH_ARRAYS

    driver = tiny.load("drivers", "stream")
    # Every document in the sample: the passes due are then the slowest
    # document's, whichever it is.
    config = dict(tiny.config("stream-tiny"),
                  check={"sample_docs": 5000, "sample_big": 4})
    cols = SYNTH_ARRAYS["flow"](18000, n_hosts=2000, n_anomalies=20, seed=7)
    batches = driver.make_batches(cols, 3000, 6)
    scorer = driver.make_scorer(config, 3)
    scorer.process_many(batches[:1])
    scorer.process_many(batches[:3], stage_next=batches[3:])
    results = scorer.process_many(batches[3:])
    lam0, store0, _ = scorer.before_last_batch
    model = {"edges": scorer.edges, "salt": scorer._salt,
             "n_buckets": scorer.n_buckets,
             "doc_keys": np.asarray(scorer.docs.keys)}
    before = {"lam": np.asarray(lam0), "gamma": np.asarray(store0),
              "step": int(scorer.state.step) - 1,
              "corpus_docs": scorer.docs.n_docs}
    return (driver, config, scorer, batches, model, before,
            driver.left_by(scorer, results[-1], 3000))


def test_the_program_agrees_with_the_reference_on_seeded_data(stream):
    from benchmark.reference import stream_check
    driver, config, _, batches, model, before, left = stream
    got = stream_check.compare(config, model, batches[-1][1], before, left, 5)
    assert got["doc_mismatch"] == got["answer_mismatch"] == 0
    assert got["pass_gap"] <= 1 and got["winner_gap"] == 0
    assert got["store_mismatch"] == 0
    assert got["gamma_gap"] < 2e-3 and got["lam_gap"] < 1e-5
    assert got["score_gap"] < 1e-5 and got["n_due"] == 50
    assert driver.judged(config, model, batches[-1][1], before, left,
                         5).correct


def test_the_reference_words_and_documents_are_the_host_paths(stream):
    """The reference's own buckets and document ids against the
    program's host path (numpy words, the packed key hashed on the
    host): other code, the same answers."""
    from benchmark.reference import stream_check
    from onix.pipelines import columnar
    from onix.pipelines.streaming import _bucket_of_keys
    _, _, scorer, batches, model, _, left = stream
    cols = batches[-1][1]
    words = columnar.words_from_cols("flow", cols, edges=scorer.edges)
    want = _bucket_of_keys(words.word_key, scorer._salt, scorer.n_buckets)
    got = stream_check.buckets(model, cols)
    assert (np.concatenate([got, got]) == want).mean() > 0.999
    ids = stream_check.doc_ids(model, cols)
    np.testing.assert_array_equal(ids, scorer.docs.ids(words.ip_u32))
    np.testing.assert_array_equal(ids, left["doc_ids"])


@pytest.mark.parametrize("fault,number", [
    ("ids_shifted", "doc_mismatch"), ("store_not_written", "gamma_gap"),
    ("store_wiped", "store_mismatch"),
    ("winners_unsorted", "answer_mismatch"),
    ("scores_of_old_model", "score_gap")])
def test_what_a_broken_program_would_leave_is_not_correct(stream, fault,
                                                          number):
    driver, config, _, batches, model, before, left = stream
    left = dict(left)
    if fault == "ids_shifted":
        left["doc_ids"] = np.roll(left["doc_ids"], 1)
    elif fault == "store_not_written":
        left["gamma"] = before["gamma"]
    elif fault == "store_wiped":
        touched = np.zeros(len(left["gamma"]), bool)
        touched[np.unique(left["doc_ids"])] = True
        left["gamma"] = np.where(touched[:, None], left["gamma"], 2.2)
    elif fault == "winners_unsorted":
        left["indices"] = left["indices"][::-1]
        left["scores"] = left["scores"][::-1]
    else:
        left["lam"] = before["lam"]
    got = driver.judged(config, model, batches[-1][1], before, left, 5)
    row = got.as_dict()[number]
    assert not got.correct and row["value"] > row["limit"], got.as_dict()


def test_the_stream_readers_read_what_the_driver_leaves(tiny):
    """`per_batch`, `window_counter` and `stream_roofline` on a made-up
    run: the arithmetic, and nothing where there is nothing to read."""
    run = {"manifest": tiny, "spans": harness.Spans(),
           "window": {"batches_per_call": 4, "batches": 8,
                      "counters": {"stream.estep_iters": 56}}}
    run["spans"].add("first_batch", 1.0, 3.5)
    per_batch = tiny.load("readers", "per_batch")
    spec = {"reader": "per_batch",
            "of": {"reader": "span_median", "span": "first_batch"}}
    assert per_batch.read(run, spec) == 2.5 / 4
    assert per_batch.read(dict(run, window={}), spec) is None
    spec["of"]["span"] = "none"
    assert per_batch.read(run, spec) is None
    counter = tiny.load("readers", "window_counter")
    spec = {"counter": "stream.estep_iters", "per": "batches"}
    assert counter.read(run, spec) == 7.0
    assert counter.read({"window": {"batches": 8}}, spec) is None
    assert counter.read({}, spec) is None
    roof = tiny.load("readers", "stream_roofline")
    assert roof.estep_bytes_per_token_pass(20) == 328.0
    assert roof.read(run, {"over": "window", "module_match": "x"}) is None


def test_the_repo_manifest_lists_the_stream_cell_and_its_metrics():
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in real["workloads"]
                if w["name"] == "flow-stream-catchup")
    assert cell == dict(cell, config="flow-stream-svi", chips=1,
                        traffic="stream-5min-backlog")
    rate = next(m for m in real["end_to_end"]
                if m["name"] == "scan_events_per_s")
    assert rate["workloads"][-1] == "flow-stream-catchup"
    mine = {m["name"]: m for m in real["per_layer"]
            if m.get("workloads") == ["flow-stream-catchup"]}
    assert set(mine) == {
        "estep_s_per_batch", "lambda_s_per_batch", "stream_docs_s_per_batch",
        "stream_select_s_per_batch", "stream_stage_s_per_batch",
        "estep_iters_per_batch", "device_idle_pct.stream", "estep_roofline",
        "stream_mfu", "stream_first_batch_s"}
    assert mine["stream_first_batch_s"]["moves"] == "setup_s"
    mix = json.loads((ROOT / "benchmark/traffic/stream-5min-backlog.json")
                     .read_text())
    assert mix["batch_events"] == 3472222 == 10 ** 9 // 288
    assert mix["superstep_batches"] in (8, 16) and mix["backlog_batches"] == 16
