"""Driver `stream_churn`, its reference and its reader at a tiny size on
the CPU: the `flow-stream-churn` cell as files alone through the
temporary-directory route (fresh addresses in every file, the table held
to `stream_max_docs`, a regrow and an eviction in the window), each
control, the reference's LRU against `np.lexsort`, and the repo's
manifest."""

import json
import pathlib
import time

import numpy as np
import pytest

from benchmark import harness

ROOT = pathlib.Path(__file__).resolve().parents[2]
SEED = 3000000021
TINY = dict(n_hosts=500, n_buckets=2048, max_results=50, n_topics=6,
            tol=0.25, stream_max_docs=1000,
            check={"sample_docs": 5000, "sample_big": 4})
MIX = dict(batch_events=3000, superstep_batches=3, backlog_batches=6,
           fresh_clients_per_batch=62, setup_fresh_clients_per_batch=100,
           anomalies=20, data_seed=7, order_blocks=16)
# Each control, and the number it is due to push past its limit.
CONTROLS = {"evict_newest": "evict_mismatch", "rows_unmoved": "evict_mismatch",
            "stamps_unwritten": "evict_mismatch", "bf16_estep": "lam_gap",
            "cold_start": "store_mismatch"}
EVICTION = ("evict_newest", "rows_unmoved", "stamps_unwritten")
NEW = {"stream_growth_s_per_batch", "stream_evict_s_per_batch",
       "new_docs_per_batch", "evicted_docs_per_batch",
       "idle_probe_host_s_per_batch"}


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """The repo's own configuration and mix cut to a CPU's size; nothing
    but files."""
    root = tmp_path_factory.mktemp("churn_pr")
    own = root / "added"
    for d in ("configs", "traffic"):
        (own / d).mkdir(parents=True)
    cfg = json.loads((ROOT / "benchmark/configs/flow-stream-svi-churn.json")
                     .read_text())
    cfg.update(TINY)
    (own / "configs/churn-tiny.json").write_text(json.dumps(cfg))
    mix = json.loads((ROOT / "benchmark/traffic/stream-5min-churn.json")
                     .read_text())
    assert mix["driver"] == "stream_churn" and set(MIX) < set(mix)
    mix.update(MIX)
    (own / "traffic/churn-tiny.json").write_text(json.dumps(mix))
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    (root / "BENCHMARK.json").write_text(json.dumps({
        "command": ["python3", "benchmark/run.py"], "paths": ["added"],
        "run_seconds": 1,
        "configs": [{"name": "churn-tiny", "source": "test",
                     "file": "added/configs/churn-tiny.json",
                     "reduced": [], "why": "test"}],
        "workloads": [{"name": "churn", "config": "churn-tiny",
                       "traffic": "churn-tiny", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "scan_events_per_s", "unit": "events/s",
             "better": "higher", "bound": 0.05, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1,
             "source": "host_clock"}],
        "per_layer": [dict(m, workloads=["churn"])
                      for m in real["per_layer"]
                      if m.get("workloads") == ["flow-stream-churn"]]}))
    return harness.Manifest(root / "BENCHMARK.json")


@pytest.fixture(scope="module")
def line(tiny):
    return harness.run_cell("churn", SEED, 0.5, False, manifest=tiny,
                            require_chip=False, control=",".join(CONTROLS))


def test_the_churn_cell_of_files_alone_runs_a_short_window(line):
    assert line["correct"], line["check"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {"scan_events_per_s", "setup_s"}
    for number in ("doc_mismatch", "store_mismatch", "winner_gap",
                   "answer_mismatch", "replay_mismatch", "compiles_in_window",
                   "evict_mismatch", "cold_mismatch"):
        assert line["check"][number]["value"] == 0, line["check"]
    w = line["window"]
    assert w["batches_per_call"] == 3 and w["items_per_call"] == 9000
    c = w["counters"]
    assert c["stream.evictions"] >= 1 and c["stream.store_regrows"] >= 1
    assert c["stream.evicted_docs"] >= 1 and c["stream.host_probes"] >= 0
    # One client in eight of every file under an address never seen.
    assert c["stream.new_docs"] >= 0.8 * 62 * w["batches"]
    assert line["check"]["evictions_in_window"]["value"] >= 1
    assert line["check"]["evict_after"]["value"] == 750
    assert line["check"]["evict_before"]["value"] > 1000
    assert {"synth", "deal", "first_batch", "fill", "superstep",
            "snapshot", "replay", "reference"} <= set(w["spans"])


@pytest.mark.parametrize("name", sorted(CONTROLS))
def test_each_control_of_the_churn_cell_comes_out_as_not_correct(line, name):
    got = line["controls"][name]
    row = got["check"][CONTROLS[name]]
    assert not got["correct"] and row["value"] > row["limit"], got


def test_a_traced_churn_run_reads_the_new_metrics(tiny):
    """The per-layer metrics of the cell, read from what the tiny run
    leaves (no device trace on the CPU: their readers need none)."""
    run = harness.run_cell("churn", SEED + 1, 0.5, False, manifest=tiny,
                           require_chip=False)
    assert run["correct"], run["check"]
    spec = {m["name"]: json.loads(tiny.find("metrics", m["name"]).read_text())
            for m in tiny.metrics_of("churn", "per_layer")}
    assert set(spec) == NEW
    w = run["window"]
    fake = {"manifest": tiny, "window": dict(w, elapsed_s=1e12),
            "t_start": 0.0, "end_to_end": {"setup_s": 0.0}}
    traced = {"idle_probe_host_s_per_batch"}      # from the device's trace
    got = {n: tiny.load("readers", s["reader"]).read(fake, s)
           for n, s in spec.items() if n not in traced}
    assert got["new_docs_per_batch"] == w["counters"]["stream.new_docs"] \
        / w["batches"]
    assert got["evicted_docs_per_batch"] > 0
    assert got["stream_growth_s_per_batch"] > 0
    assert got["stream_evict_s_per_batch"] > 0
    assert spec["idle_probe_host_s_per_batch"]["of"] == {
        "reader": "idle_under_span", "span": "stream.probe_host"}


def test_the_window_spans_reader_reads_inside_the_window_alone(tiny):
    from onix.utils import telemetry
    reader = tiny.load("readers", "window_spans")
    spec = {"spans": ["stream.evict", "stream.regrow"], "per": "batches"}
    with telemetry.TRACER.span("stream.evict"):
        pass
    t = telemetry.TRACER.spans()[-1].t0
    run = {"t_start": t - 1.0, "end_to_end": {"setup_s": 0.5},
           "window": {"elapsed_s": 10.0, "batches": 4}}
    assert reader.read(run, spec) >= 0.0
    assert reader.read(dict(run, end_to_end={"setup_s": 2.0}), spec) == 0.0
    assert reader.read(dict(run, window={"batches": 4}), spec) is None
    assert reader.read(run, dict(spec, spans=["no.such"])) is None


def test_the_window_spans_reader_takes_a_child_away_once():
    """`less` names children that another metric counts: the parent's
    seconds less theirs, each span whole inside the window."""
    from onix.utils import telemetry
    reader = _reader("window_spans")
    with telemetry.TRACER.span("stream.doc_growth"):
        with telemetry.TRACER.span("stream.regrow"):
            time.sleep(0.02)
        time.sleep(0.01)
    regrow, growth = telemetry.TRACER.spans()[-2:]
    assert (regrow.name, growth.name) == ("stream.regrow", "stream.doc_growth")
    run = {"t_start": growth.t0 - 1.0, "end_to_end": {"setup_s": 1.0},
           "window": {"elapsed_s": growth.dur_s + 1e-3, "batches": 2}}
    spec = {"spans": ["stream.doc_growth"], "per": "batches"}
    both = reader.read(run, spec)
    assert both == pytest.approx(growth.dur_s / 2)
    less = reader.read(run, dict(spec, less=["stream.regrow"]))
    assert less == pytest.approx((growth.dur_s - regrow.dur_s) / 2)
    assert 0.0 < less < both


def _reader(name: str):
    return harness.Manifest(ROOT / "BENCHMARK.json").load("readers", name)


def test_the_driver_knows_each_files_addresses_and_stamps():
    """`Files.addresses` is the set a file's events hold, read from the
    batch's clients alone; `grown` appends a file's unseen addresses in
    ascending order; `stamps` is the last batch that held each."""
    churn = harness.Manifest(ROOT / "BENCHMARK.json").load(
        "drivers", "stream_churn")
    rng = np.random.default_rng(4)
    clients = np.arange(1000, 1040, dtype=np.uint32) + np.uint32(10 << 24)
    servers = np.array([7, 9, 11], np.uint32) + np.uint32(203 << 24)
    parts = []
    for _ in range(3):
        n = 200
        sip = rng.choice(clients[:-2], n)       # two clients never send
        dip = np.where(rng.random(n) < 0.9, rng.choice(servers, n),
                       rng.choice(clients, n))
        parts.append({"sip_u32": sip.astype(np.uint32),
                      "dip_u32": dip.astype(np.uint32)})
    files = churn.Files(parts, ("sip_u32", "dip_u32"), 11,
                        lambda f: 5 if f % 2 else 3)
    for f in range(1, 8):
        cols = files.columns_of(f)
        held = np.unique(np.concatenate([cols["sip_u32"], cols["dip_u32"]]))
        np.testing.assert_array_equal(files.addresses(f), held)
        fresh = held[(held >= churn.FRESH_BASE) & (held < 80 << 24)]
        assert 0 < len(fresh) <= (5 if f % 2 else 3)
    first = np.array([1, 2, 3], np.uint32)
    table = churn.grown(first, files, [1, 2])
    want = list(first)
    for f in (1, 2):
        want += sorted(set(files.addresses(f)) - set(want))
    np.testing.assert_array_equal(table, np.asarray(want, np.uint32))
    got = churn.stamps(table, files, 2, first)
    for key, stamp in zip(table, got):
        assert stamp == (3 if key in files.addresses(2) else
                         2 if key in files.addresses(1) else 1), key


@pytest.mark.parametrize("n,target,seed", [(50, 30, 0), (400, 300, 1),
                                           (1000, 750, 2), (7, 7, 3)])
def test_the_reference_lru_is_the_programs_lexsort(n, target, seed):
    """Survivors by stamp, ties to the lower id, from a threshold: the
    ids `np.lexsort((arange, -last_seen))` keeps, on stamps with many
    ties."""
    from benchmark.reference import stream_churn_check as ref
    stamps = np.random.default_rng(seed).integers(0, 6, n)
    want = np.sort(np.lexsort((np.arange(n), -stamps))[:target])
    np.testing.assert_array_equal(ref.survivors(stamps, target), want)


def test_the_reference_counts_each_broken_eviction():
    from benchmark.reference import stream_churn_check as ref
    rng = np.random.default_rng(5)
    n, k, max_docs, alpha = 120, 4, 100, 1.2
    before = {"keys": rng.permutation(10_000)[:n].astype(np.uint32),
              "last_seen": rng.integers(1, 9, n),
              "gamma": rng.random((n, k)).astype(np.float32)}
    keep = ref.due(before, max_docs)
    assert len(keep) == 75
    after = {"keys": before["keys"][keep],
             "gamma": np.full((128, k), np.float32(alpha + 1.0)),
             "last_seen": np.zeros(128, np.int32)}
    after["gamma"][:75] = before["gamma"][keep]
    after["last_seen"][:75] = before["last_seen"][keep]
    assert ref.compare(before, after, max_docs, alpha)["evict_mismatch"] == 0
    for control in EVICTION:
        bad = ref.broken(before, after, max_docs, alpha, control)
        assert ref.compare(before, bad, max_docs, alpha)["evict_mismatch"] > 0
    warm = dict(after, gamma=after["gamma"].copy())
    warm["gamma"][100] = alpha          # a cold row that is not cold
    assert ref.compare(before, warm, max_docs, alpha)["evict_mismatch"] == 1


def test_the_reference_imports_nothing_of_the_program():
    src = (ROOT / "benchmark/reference/stream_churn_check.py").read_text()
    imports = [ln for ln in src.splitlines()
               if ln.lstrip().startswith(("import ", "from "))]
    assert imports and not any("onix" in ln or "benchmark" in ln
                               for ln in imports), imports


def test_the_repo_manifest_lists_the_churn_cell_its_mix_and_metrics():
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in real["workloads"]
                if w["name"] == "flow-stream-churn")
    assert cell == dict(cell, config="flow-stream-svi-churn", chips=1,
                        traffic="stream-5min-churn")
    assert real["workloads"][-1] is cell or real["workloads"][-1] == cell
    rate = next(m for m in real["end_to_end"]
                if m["name"] == "scan_events_per_s")
    assert rate["workloads"][-2:] == ["flow-stream-churn",
                                      "flow-stream-catchup"]
    mine = {m["name"]: m for m in real["per_layer"]
            if m.get("workloads") == ["flow-stream-churn"]}
    assert set(mine) == NEW
    assert all(m["moves"] == "scan_events_per_s" for m in mine.values())
    assert mine["idle_probe_host_s_per_batch"]["source"] == "device_trace"
    for name in ("compile_s", "compile_cold_s"):
        m = next(m for m in real["per_layer"] if m["name"] == name)
        assert m["workloads"][-1] == "flow-stream-churn"
    config = json.loads((ROOT / "benchmark/configs/flow-stream-svi-churn.json")
                        .read_text())
    base = json.loads((ROOT / "benchmark/configs/flow-stream-svi.json")
                      .read_text())
    for key in ("n_topics", "n_hosts", "tokens_per_event", "n_buckets",
                "alpha", "eta", "svi_tau0", "svi_kappa", "svi_local_iters",
                "svi_meanchange_tol", "svi_warm_iters", "max_results", "tol",
                "batch_events", "day_events"):
        assert config[key] == base[key], key
    assert config["stream_max_docs"] == 2 ** 20
    # The catch-up's limits, gamma_gap read on this cell, and the two
    # exact counts of the bounded table.
    assert config["limits"] == dict(base["limits"], gamma_gap=0.008,
                                    evict_mismatch=0, cold_mismatch=0)
    assert set(config["reduced"]) == {"day_events"}
    mix = json.loads((ROOT / "benchmark/traffic/stream-5min-churn.json")
                     .read_text())
    assert mix["batch_events"] == 3472222 == 10 ** 9 // 288
    assert (mix["superstep_batches"], mix["backlog_batches"]) == (8, 16)
    assert mix["fresh_clients_per_batch"] * 8 == config["n_hosts"]
    assert mix["trace_supersteps"] == 2
