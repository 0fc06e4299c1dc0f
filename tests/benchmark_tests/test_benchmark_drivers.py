"""The drivers at a tiny size on the CPU: a short window through each,
a cell that exists as files alone, the controls, and the timed path
broken underneath. Each has to end with `correct` as it should be."""

import json
import pathlib

import numpy as np
import pytest

from benchmark import harness

ROOT = pathlib.Path(__file__).resolve().parents[2]
SEED = 3000000019


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A manifest in a directory of its own whose cells are nothing but
    files: two configurations, two mixes and a metric a later PR might
    add, found by name. No file of the benchmark is edited for them."""
    root = tmp_path_factory.mktemp("later_pr")
    own = root / "added"
    for d in ("configs", "traffic", "metrics"):
        (own / d).mkdir(parents=True)
    for name, n_hosts in (("flow-k20", 300), ("dns-k20", 300)):
        cfg = json.loads((ROOT / "benchmark/configs" / f"{name}.json")
                         .read_text())
        cfg.update(n_hosts=n_hosts, block_size=4096, max_results=50,
                   scan_model_sweeps=6, burn_in=3)
        cfg["limits"].update(move_gap=0.1, loglik_gap=0.1)
        (own / "configs" / f"{name}-tiny.json").write_text(json.dumps(cfg))
    (own / "traffic/fit-tiny.json").write_text(json.dumps({
        "driver": "fit", "base_events": 20000, "base_hosts": 300,
        "base_anomalies": 30, "copies": 3, "open_at_callback": 2,
        "check_blocks": 4, "trace_sweeps": 2}))
    (own / "traffic/scan-tiny.json").write_text(json.dumps({
        "driver": "scan", "chunk_events": 60000, "train_events": 8000,
        "anomalies": 90, "data_seed": 7, "order_blocks": 16,
        "trace_chunks": 2}))
    (own / "metrics/fetch_s.json").write_text(json.dumps(
        {"reader": "span_median", "span": "fetch"}))
    e2e = lambda n, u, cells: {"name": n, "unit": u, "better": "higher",
                               "bound": 0.05, "source": "host_clock",
                               "workloads": cells}
    (root / "BENCHMARK.json").write_text(json.dumps({
        "command": ["python3", "benchmark/run.py"], "paths": ["added"],
        "run_seconds": 1,
        "configs": [{"name": f"{n}-tiny", "source": "test",
                     "file": f"added/configs/{n}-tiny.json",
                     "reduced": [], "why": "test"}
                    for n in ("flow-k20", "dns-k20")],
        "workloads": [
            {"name": "fit", "config": "flow-k20-tiny", "traffic": "fit-tiny",
             "chips": 1, "why": "test"},
            {"name": "scan", "config": "flow-k20-tiny",
             "traffic": "scan-tiny", "chips": 1, "why": "test"},
            {"name": "dns", "config": "dns-k20-tiny", "traffic": "scan-tiny",
             "chips": 1, "why": "test"}],
        "end_to_end": [
            e2e("fit_tokens_per_s", "tokens/s", ["fit"]),
            e2e("scan_events_per_s", "events/s", ["scan", "dns"]),
            {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1,
             "source": "host_clock"}],
        "per_layer": [{"name": "fetch_s", "unit": "s", "better": "lower",
                       "source": "host_clock", "layer": "device",
                       "moves": "scan_events_per_s"}]}))
    return harness.Manifest(root / "BENCHMARK.json")


def run(tiny, cell, **kw):
    return harness.run_cell(cell, SEED, 0.5, False, manifest=tiny,
                            require_chip=False, **kw)


@pytest.mark.parametrize("cell,rate", [("fit", "fit_tokens_per_s"),
                                       ("scan", "scan_events_per_s"),
                                       ("dns", "scan_events_per_s")])
def test_a_cell_of_files_alone_runs_a_short_window(tiny, cell, rate, capsys):
    line = run(tiny, cell)
    assert line["correct"], line["check"]
    assert line["attempted"] >= 2 and line["failed"] == 0
    assert set(line["metrics"]) == {rate, "setup_s"}
    assert line["metrics"][rate]["value"] > 0
    assert line["check"]["compiles_in_window"]["value"] == 0
    harness.print_result(line)
    out, err = capsys.readouterr()
    assert json.loads(out.strip().splitlines()[-1])["correct"] is True
    assert list(json.loads(out.strip().splitlines()[-1]))[-1] == "check"
    assert err.strip().splitlines()[-1] == "correct True"


@pytest.mark.parametrize("cell,controls", [
    ("fit", {"half_kept": "move_gap", "counts_stale": "count_mismatch",
             "token_shift": "loglik_gap", "reference": None}),
    ("scan", {"bf16_table": "score_gap", "half_chunk": "winner_gap"}),
    ("dns", {"bf16_table": "score_gap", "half_chunk": "winner_gap"})])
def test_each_control_comes_out_as_not_correct(tiny, cell, controls):
    """The run itself stays correct; judged again with a control in the
    program's place it is not, by the number that control is there for.
    The sound reference in the program's place is correct."""
    line = run(tiny, cell, control=",".join(controls))
    assert line["correct"], line["check"]
    for name, number in controls.items():
        got = line["controls"][name]
        if number is None:
            assert got["correct"], got
            continue
        row = got["check"][number]
        assert not got["correct"] and row["value"] > row["limit"], got


def _break_sweep(monkeypatch, fault):
    import jax.numpy as jnp

    from onix.models import lda_gibbs
    make = lda_gibbs.make_sweep_kernel

    def broken(**kw):
        kernel = make(**kw)

        def run_kernel(z, n_dk, n_wk, n_k, key, docs, words, mask):
            if fault == "state_unchanged":
                return z, n_dk, n_wk, n_k, key
            if fault == "half_left_out":
                half = jnp.arange(mask.shape[-1]) < mask.shape[-1] // 2
                return kernel(z, n_dk, n_wk, n_k, key, docs, words,
                              mask * half)
            out = kernel(z, n_dk, n_wk, n_k, key, docs, words, mask)
            z2 = jnp.where(mask > 0, (out[0] + 1) % kw["k_topics"], out[0])
            return (z2,) + tuple(out[1:])
        return run_kernel
    monkeypatch.setattr(lda_gibbs, "make_sweep_kernel", broken)


@pytest.mark.parametrize("fault,number", [
    ("state_unchanged", "move_gap"), ("half_left_out", "move_gap"),
    ("token_altered", "count_mismatch")])
def test_a_broken_sweep_is_not_correct(tiny, monkeypatch, fault, number):
    _break_sweep(monkeypatch, fault)
    line = run(tiny, "fit")
    row = line["check"][number]
    assert not line["correct"] and row["value"] > row["limit"]


@pytest.mark.parametrize("fault,number", [
    ("state_unchanged", "answer_mismatch"), ("half_left_out", "winner_gap"),
    ("answer_altered", "winner_gap")])
def test_a_broken_scan_is_not_correct(tiny, monkeypatch, fault, number):
    from onix.models import scoring
    from onix.pipelines import device_words as dw
    real = dw.flow_stream_bottom_k

    def broken(tables, table, cols, **kw):
        if fault == "state_unchanged":      # the running bottom-k never moves
            return scoring._empty_topk(kw["max_results"])
        if fault == "half_left_out":
            n = cols["sip_u32"].shape[0] // 2
            cols = {k: (v[:n] if hasattr(v, "shape") else v)
                    for k, v in cols.items()}
            return real(tables, table, cols, **kw)
        top = real(tables, table, cols, **kw)
        return scoring.TopK(top.scores, top.indices + 1)
    monkeypatch.setattr(dw, "flow_stream_bottom_k", broken)
    line = run(tiny, "scan")
    row = line["check"][number]
    assert not line["correct"] and row["value"] > row["limit"]


def test_no_chip_no_result(capsys):
    from benchmark import run as entry
    with pytest.raises(SystemExit) as e:
        entry.sys.exit(entry.main(["--workload", "flow-fit", "--seed", "1",
                                   "--seconds", "1", "--trace", "0"]))
    assert e.value.code == 3 and capsys.readouterr().out == ""


def test_every_seed_deals_the_same_events_in_another_order():
    scan = harness.Manifest().load("drivers", "scan")
    cols = {"a": np.arange(10), "b": np.arange(10) * 2.0, "names": ["x"],
            "anomaly_idx": np.array([9])}
    one, two = scan.deal(cols, 10, 5, 1), scan.deal(cols, 10, 5, 2)
    assert sorted(one["a"]) == sorted(two["a"]) == list(range(10))
    assert one["a"].tolist() != two["a"].tolist()
    assert (one["b"] == one["a"] * 2.0).all() and one["names"] == ["x"]
    assert "anomaly_idx" not in one


def test_seed_folds_into_int32_and_tiling_offsets_documents():
    assert 0 <= harness.fold_seed(2 ** 31 + 12345) < 2 ** 31
    assert harness.fold_seed(7) == 7
    mf = harness.Manifest()
    fit = mf.load("drivers", "fit")
    from onix.corpus import Corpus
    base = Corpus(np.array([0, 1, 1]), np.array([2, 0, 1]), 2, 3)
    tiled = fit.tile_corpus(base, 3)
    assert tiled.n_docs == 6 and tiled.n_vocab == 3
    assert tiled.doc_ids.tolist() == [0, 1, 1, 2, 3, 3, 4, 5, 5]
    assert tiled.word_ids.tolist() == [2, 0, 1] * 3
