"""Driver `fit_dp` at a tiny size on a dp=4 CPU mesh: a cell that exists
as files alone, its five controls, the timed path broken underneath,
and the share tied to the whole: with one chip the doc-sharded
reference's numbers are the one-chip reference's."""

import json
import pathlib

import numpy as np
import pytest

from benchmark import harness

ROOT = pathlib.Path(__file__).resolve().parents[2]
SEED = 3300000023
METRICS = ("merge_s_per_sweep", "scatter_s_per_sweep", "fit_prepare_s")


@pytest.fixture(scope="module")
def tiny(tmp_path_factory, eight_devices):
    """`flow-k20-dp4` and `fit-3e8-dp4` cut to a toy, in a directory of
    their own, found by name; a one-chip cell of the same driver and one
    of driver `fit` beside it."""
    root = tmp_path_factory.mktemp("dp_cell")
    own = root / "added"
    for d in ("configs", "traffic", "metrics"):
        (own / d).mkdir(parents=True)
    cfg = json.loads((ROOT / "benchmark/configs/flow-k20-dp4.json")
                     .read_text())
    cfg.update(n_hosts=300, block_size=4096, burn_in=3)
    cfg["limits"].update(move_gap=0.1, loglik_gap=0.1)
    (own / "configs/dp-tiny.json").write_text(json.dumps(cfg))
    mix = json.loads((ROOT / "benchmark/traffic/fit-3e8-dp4.json")
                     .read_text())
    mix.update(base_events=20000, base_hosts=300, base_anomalies=30,
               copies=3, check_blocks=2)
    (own / "traffic/fit-dp-tiny.json").write_text(json.dumps(mix))
    (own / "traffic/fit-tiny.json").write_text(json.dumps(
        dict(mix, driver="fit")))
    for name in METRICS:
        (own / "metrics" / f"{name}.json").write_text(
            (ROOT / "benchmark/metrics" / f"{name}.json").read_text())
    cells = [("dp4", "fit-dp-tiny", 4), ("dp1", "fit-dp-tiny-1", 1),
             ("one", "fit-tiny", 1)]
    (own / "traffic/fit-dp-tiny-1.json").write_text(json.dumps(mix))
    (root / "BENCHMARK.json").write_text(json.dumps({
        "command": ["python3", "benchmark/run.py"], "paths": ["added"],
        "run_seconds": 1,
        "configs": [{"name": "dp-tiny", "source": "test",
                     "file": "added/configs/dp-tiny.json",
                     "reduced": [], "why": "test"}],
        "workloads": [{"name": n, "config": "dp-tiny", "traffic": t,
                       "chips": c, "why": "test"} for n, t, c in cells],
        "end_to_end": [
            {"name": "fit_tokens_per_s", "unit": "tokens/s",
             "better": "higher", "bound": 0.05, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1,
             "source": "host_clock"}],
        "per_layer": [{"name": n, "unit": "s", "better": "lower",
                       "source": "device_trace", "layer": "test",
                       "moves": "fit_tokens_per_s"} for n in METRICS]}))
    return harness.Manifest(root / "BENCHMARK.json")


def run(tiny, cell, **kw):
    return harness.run_cell(cell, SEED, 0.5, False, manifest=tiny,
                            require_chip=False, **kw)


EXACT = ("layout_mismatch", "count_mismatch", "acc_mismatch", "doc_split",
         "replica_mismatch", "compiles_in_window")


@pytest.mark.parametrize("cell,chips", [("dp4", 4), ("dp1", 1)])
def test_a_doc_sharded_cell_of_files_alone_runs_a_short_window(tiny, cell,
                                                               chips):
    line = run(tiny, cell)
    assert line["correct"], line["check"]
    assert line["attempted"] >= 2 and line["failed"] == 0
    assert set(line["metrics"]) == {"fit_tokens_per_s", "setup_s"}
    for name in EXACT:
        assert line["check"][name] == {"value": 0.0, "limit": 0.0}, name
    win = line["window"]
    # A chip's share of a call: what the roofline and the peak divide by.
    assert win["items_per_call"] == win["tokens"] / win["sweeps"] / chips
    assert win["shards"] == chips == len(win["tokens_per_shard"])
    assert sum(win["tokens_per_shard"]) * win["sweeps"] == win["tokens"]
    # The deal is balanced by document lengths: within a few per cent.
    assert max(win["tokens_per_shard"]) <= 1.1 * min(win["tokens_per_shard"])
    # Two head blocks a chip were judged, less their padding.
    assert line["check"]["checked_tokens"]["value"] <= chips * 2 * 4096
    assert line["check"]["checked_tokens"]["value"] > chips * 4096


CONTROLS = {"half_kept": "move_gap", "counts_stale": "count_mismatch",
            "token_shift": "loglik_gap", "merge_dropped": "count_mismatch",
            "reference": None}


@pytest.mark.parametrize("cell", ["dp4", "dp1"])
def test_each_control_comes_out_as_not_correct(tiny, cell):
    """The run itself stays correct; judged again with a control in the
    program's place it is not, by the number that control is there for.
    The sound reference in the program's place is correct. With one
    chip a merge has no peer to drop: that control is then sound."""
    line = run(tiny, cell, control=",".join(CONTROLS))
    assert line["correct"], line["check"]
    for name, number in CONTROLS.items():
        got = line["controls"][name]
        if number is None or (name == "merge_dropped" and cell == "dp1"):
            assert got["correct"], (name, got)
            continue
        row = got["check"][number]
        assert not got["correct"] and row["value"] > row["limit"], got
    if cell == "dp4":
        row = line["controls"]["merge_dropped"]["check"]["replica_mismatch"]
        assert row["value"] > row["limit"]


def _break(monkeypatch, fault):
    """Faults of the doc-sharded path itself, under the public `fit`."""
    import jax
    import jax.numpy as jnp

    from onix.parallel import sharded_gibbs as sg
    if fault == "merge_of_one_chip":
        # The sum over the chips hears of chip 0's changes alone. (A
        # merge that leaves the copies unequal cannot be written:
        # `shard_map` checks that statically. The control stands for it.)
        real = jax.lax.psum

        def one_chip(x, axes):
            first = jax.lax.axis_index("dp") == 0
            return real(jax.tree.map(
                lambda a: jnp.where(first, a, jnp.zeros_like(a)), x), axes)
        monkeypatch.setattr(jax.lax, "psum", one_chip)
    elif fault == "document_split":
        real = sg.shard_corpus

        def split(corpus, n_data, *a, **kw):
            sc = real(corpus, n_data, *a, **kw)
            # One token of chip 0 is handed to chip 1's first document
            # slot that chip 0 also names: a document on two chips.
            doc = sc.doc_map[0, sc.doc_blocks[0, 0, 0, 0]]
            sc.doc_map[1, sc.doc_blocks[1, 0, 0, 0]] = doc
            return sc
        monkeypatch.setattr(sg, "shard_corpus", split)
    else:
        assert fault == "token_altered"
        from onix.models import lda_gibbs
        make = lda_gibbs.make_sweep_kernel

        def broken(**kw):
            kernel = make(**kw)

            def run_kernel(z, n_dk, n_wk, n_k, key, docs, words, mask):
                out = kernel(z, n_dk, n_wk, n_k, key, docs, words, mask)
                z2 = jnp.where(mask > 0, (out[0] + 1) % kw["k_topics"],
                               out[0])
                return (z2,) + tuple(out[1:])
            return run_kernel
        monkeypatch.setattr(lda_gibbs, "make_sweep_kernel", broken)


@pytest.mark.parametrize("fault,number", [
    ("merge_of_one_chip", "count_mismatch"), ("document_split", "doc_split"),
    ("token_altered", "count_mismatch")])
def test_a_broken_doc_sharded_fit_is_not_correct(tiny, monkeypatch, fault,
                                                 number):
    _break(monkeypatch, fault)
    line = run(tiny, "dp4")
    row = line["check"][number]
    assert not line["correct"] and row["value"] > row["limit"], line["check"]


def test_with_one_chip_the_numbers_are_the_one_chip_references(tiny):
    """The share tied to the whole: the same seed through driver `fit`
    and through `fit_dp` on one chip is the same corpus, layout and
    chain, so the exact numbers agree and the statistics of the head
    blocks are `fit_check`'s over the same head, to the bit."""
    from benchmark.reference import fit_check, fit_dp_check
    dp, one = run(tiny, "dp1"), run(tiny, "one")
    assert dp["correct"] and one["correct"]
    for name in ("layout_mismatch", "count_mismatch", "acc_mismatch"):
        assert dp["check"][name] == one["check"][name]
    assert dp["window"]["tokens"] / dp["window"]["sweeps"] == \
        one["window"]["tokens"] / one["window"]["sweeps"]
    # The statistics on one state, through both references.
    rng = np.random.default_rng(5)
    k, v, d, m, b = 5, 11, 7, 3, 64
    head = {"docs": rng.integers(0, d, (m, b)).astype(np.int32),
            "words": rng.integers(0, v, (m, b)).astype(np.int32),
            "mask": (rng.random((m, b)) < 0.9).astype(np.float32),
            "z_before": rng.integers(0, k, (m, b)).astype(np.int32),
            "z_after": rng.integers(0, k, (m, b)).astype(np.int32)}
    live = head["mask"] > 0
    before = {"n_dk": fit_check.hist2(head["docs"][live],
                                      head["z_before"][live], d, k) + 3,
              "n_wk": fit_check.hist2(head["words"][live],
                                      head["z_before"][live], v, k) + 3}
    before["n_k"] = before["n_wk"].sum(axis=0)
    kw = dict(alpha=1.2, eta=0.01, n_vocab=v)
    want = fit_check.sampler_stats(
        head, {x: a[:0] for x, a in head.items()}, before, before, **kw)
    got = fit_dp_check.sampler_stats(
        [head], dict(before, n_dk=before["n_dk"][None]), **kw)
    assert got == want and got["n_tokens"] == float(live.sum())


def test_the_tiled_corpus_is_counted_from_one_site():
    mf = harness.Manifest()
    fit, fit_dp = mf.load("drivers", "fit"), mf.load("drivers", "fit_dp")
    from onix.corpus import Corpus
    base = Corpus(np.array([0, 1, 1, 1], np.int32),
                  np.array([2, 0, 1, 1], np.int32), 3, 4)
    tiled = fit.tile_corpus(base, 3)
    docs, words = fit_dp.tiled_counts(base, 3)
    np.testing.assert_array_equal(
        docs, np.bincount(tiled.doc_ids, minlength=tiled.n_docs))
    np.testing.assert_array_equal(
        words, np.bincount(tiled.word_ids, minlength=tiled.n_vocab))


def test_the_reference_imports_nothing_of_the_program():
    import ast
    for name in ("fit_dp_check", "fit_check"):
        tree = ast.parse((ROOT / "benchmark/reference" / f"{name}.py")
                         .read_text())
        mods = {n.module for n in ast.walk(tree)
                if isinstance(n, ast.ImportFrom)} | {
            a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
            for a in n.names}
        assert not any(m.split(".")[0] in ("onix", "jax") for m in mods), mods


def test_the_exact_checks_see_a_fault_in_any_chip():
    from benchmark.reference import fit_dp_check as ref
    mask = np.array([[1, 1, 1, 0], [0, 0, 0, 0]], np.float32)
    assert ref.live_prefix(mask) == (3, 0)
    assert ref.live_prefix(mask[:, ::-1]) == (3, 2)     # a hole in front
    docs = np.array([0, 1, 1, 0, 0], np.int32)
    words = np.array([2, 2, 0, 1, 0], np.int32)
    z = np.array([1, 0, 1, 5, 9], np.int32)             # the fourth: no topic
    dk, wk, stray = ref.shard_tables(docs, words, z, 4, 2, 3, 2)
    assert stray == 1 and dk.tolist() == [[0, 1], [1, 1]]
    assert wk.tolist() == [[0, 1], [0, 0], [1, 1]]
    doc_map = np.array([[0, 1], [2, -1]])
    tokens = [np.array([2, 1]), np.array([3, 0])]
    want_docs, want_words = np.array([2, 1, 3]), np.array([4, 2])
    assert ref.layout_and_split(tokens, want_words, doc_map, 6, 0,
                                want_docs, want_words) == (0, 0)
    both = np.array([[0, 1], [0, -1]])                  # doc 0 on two chips
    bad, split = ref.layout_and_split(tokens, want_words, both, 6, 0,
                                      want_docs, want_words)
    assert split == 1 and bad > 0
    assert ref.replica_mismatch([wk, wk.copy(), wk + 1]) == wk.size


@pytest.mark.parametrize("want", [0, 1, 2, 5])
def test_accumulators_are_held_to_their_float32_sum(want):
    """A count past 2^24 is rounded as it is folded in: the reference
    states that and holds the accumulators to it exactly."""
    from benchmark.reference import fit_dp_check as ref
    big = 2 ** 24 + 1                       # float32 reads 2^24
    before = {"n_dk": np.array([[[3, 4]]]), "n_wk": np.array([[big, 7]])}
    after = {"n_dk": np.array([[[5, 2]]]), "n_wk": np.array([[big + 2, 5]])}
    f = np.float32
    acc = {0: (np.zeros((1, 1, 2), f), np.zeros((1, 2), f)),
           1: (after["n_dk"].astype(f), after["n_wk"].astype(f)),
           2: (np.array([[[8, 6]]], f),
               before["n_wk"].astype(f) + after["n_wk"].astype(f))}
    ndk, nwk = acc.get(want, acc[2])
    assert ref.acc_mismatch(ndk, nwk, want, before, after, want) == 0
    assert ref.acc_mismatch(ndk, nwk, want + 1, before, after, want) == 1
    assert float(acc[1][1][0, 0]) != big + 2        # the rounding is real
    if want in (1, 2):
        assert ref.acc_mismatch(ndk, nwk + f(4), want, before, after,
                                want) == 2
