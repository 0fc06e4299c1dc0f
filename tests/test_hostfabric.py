"""Multi-host fit fabric (r21): SIGKILL chaos, quarantine-and-resume,
checkpoint-shard topology refusal, rebalance.

The tier-1 tests here run REAL worker processes over a localhost
jax.distributed coordinator (CPU backend, gloo collectives) and prove
the robustness contract end to end:

* a worker takes a real SIGKILL mid-superstep, the coordinator detects
  it through the heartbeat lease, quarantines the dead host's shard
  assignment with a sidecar, and a same-topology restart resumes from
  the last common superstep-boundary checkpoint shard — BIT-IDENTICAL
  (sync merge) / within the 5% ll band (async τ=1) versus the
  fault-free in-process dp=2 fit of the same corpus;
* a changed topology (host count) refuses resume loudly with a
  per-field fingerprint diff;
* --rebalance re-shards a dead host's corpus onto the survivors behind
  a deliberate fingerprint bump, stamped in the topology claim.

Heavier fleets are behind the `multihost` marker (opt-in via
ONIX_MULTIHOST_TESTS=1, conftest auto-skip — same discipline as `tpu`).
"""

import dataclasses
import json

import numpy as np
import pytest

import jax

from onix import checkpoint as ckpt
from onix.config import LDAConfig
from onix.corpus import anomaly_corpus, synthetic_lda_corpus
from onix.parallel import hostfabric
from onix.parallel.mesh import make_mesh
from onix.parallel.sharded_gibbs import ShardedGibbsLDA
from onix.utils.obs import counters

# One corpus + config shared by the chaos tests; small enough that a
# 2-worker fabric fit (spawn + compile + 6 sweeps) stays ~10-20s.
CFG = LDAConfig(n_topics=4, n_sweeps=6, burn_in=2, block_size=256,
                superstep=2, seed=1, checkpoint_every=2)
# The lease is what a loaded machine breaks: with six test workers and
# two spawned fit workers sharing the cores, a live worker's beat
# thread (GIL-starved during XLA compiles besides) misses a 4 s lease,
# the coordinator declares a death nobody inflicted and restarts (the
# fabric survives that — but the tests assert exactly ONE death, the
# one we inflicted, or none). 20 s costs each drill with a kill that
# long to detect it and is far past any starvation seen; the beat
# stays short so that a live worker renews often.
FABRIC_KW = dict(n_hosts=2, local_devices=1, lease_s=20.0, beat_s=0.3,
                 collective_deadline_s=60.0, timeout_s=240.0)
KILL = {"host": 1, "after_sweep": 2}


@pytest.fixture(scope="module")
def corpus():
    c, _, _ = synthetic_lda_corpus(n_docs=24, n_vocab=40, n_topics=4,
                                   mean_doc_len=30, seed=3)
    return c


def _ref_fit(corpus, cfg, dp=2):
    mesh = make_mesh(dp=dp, mp=1, devices=jax.devices()[:dp])
    return ShardedGibbsLDA(cfg, corpus.n_vocab, mesh=mesh).fit(corpus)


def _host_counter(name):
    return counters.get(f"host.{name}")


@pytest.mark.faults
def test_sigkill_quarantine_resume_sync_bitidentical(
        corpus, tmp_path, monkeypatch):
    """The headline chaos drill: real SIGKILL on worker 1 mid-superstep;
    lease-based death detection; shard quarantined with a sidecar; the
    same-topology restart resumes from the last common superstep
    boundary and finishes BIT-IDENTICAL to the fault-free fit."""
    from onix.utils import telemetry

    tel = tmp_path / "tel"
    tel.mkdir()
    # The recorder is a process global: an earlier test file of this
    # worker may have routed it elsewhere, switched it off or used up
    # its dump cap.
    telemetry.reset_for_tests()
    monkeypatch.setenv("ONIX_TELEMETRY_DIR", str(tel))
    ref = _ref_fit(corpus, CFG)
    before = {k: _host_counter(k) for k in
              ("death_detected", "quarantined", "kill_delivered",
               "restarts")}
    wd = tmp_path / "fabric"
    out = hostfabric.run_fit(corpus, CFG, wd, kill_plan=KILL, **FABRIC_KW)
    m = out["manifest"]

    # Death detected via the heartbeat lease; one same-topology restart.
    assert len(m["deaths"]) == 1 and m["deaths"][0]["host"] == 1
    assert m["restarts"] == 1 and m["generations"] == 2
    assert m["rebalanced"] is False
    for k in before:
        assert _host_counter(k) - before[k] == 1, k
    # Generation 0 started clean; generation 1 resumed from a
    # superstep-boundary checkpoint shard, never from scratch.
    assert m["resume_sweeps"][0] == -1
    assert m["resume_sweeps"][1] >= 0

    # Same-topology resume is bit-identical to the fault-free run.
    assert np.array_equal(ref["theta"], out["theta"])
    assert np.array_equal(ref["phi_wk"], out["phi_wk"])

    # Quarantine evidence: the dead host's shard assignment moved into
    # the dead-letter dir with its sidecar naming the expired lease.
    names = sorted(p.name for p in (wd / "quarantine").iterdir())
    assert "shard-host1.json" in names
    sidecar = next(p for p in (wd / "quarantine").iterdir()
                   if p.name.endswith(".quarantine.json"))
    side = json.loads(sidecar.read_text())
    assert "heartbeat lease expired" in side["error"]
    # Ledger marker: the dead incarnation's claim digest is pinned.
    assert list((wd / "shards" / ".onix_claims").glob("*.quarantined"))

    # Flight-recorder postmortem dumped at detection time.
    assert any("host-death" in p.name for p in tel.iterdir())

    # Same workdir, different host count: resume refused loudly with
    # the per-field diff, pointing at --rebalance.
    with pytest.raises(ckpt.TopologyMismatch, match="n_hosts"):
        hostfabric.run_fit(corpus, CFG, wd, **{**FABRIC_KW, "n_hosts": 3})


@pytest.mark.faults
def test_sigkill_async_tau1_resume_in_band(corpus, tmp_path, monkeypatch):
    """The async τ=1 arm of the same drill, with an injected host:merge
    fault riding ONIX_FAULT_PLAN: the collective retry absorbs the
    raise, the SIGKILL death still resumes, and the final ll lands in
    the 5% band of the fault-free async fit."""
    acfg = dataclasses.replace(CFG, merge_form="async", merge_staleness=1)
    ref = _ref_fit(corpus, acfg)
    # Fires once per worker process at the first superstep >= sweep 2 —
    # inside the bounded collective retry, pre-mutation, so the second
    # attempt replays the identical non-donating dispatch.
    monkeypatch.setenv("ONIX_FAULT_PLAN", "host:merge@2=raise")
    wd = tmp_path / "fabric"
    out = hostfabric.run_fit(corpus, acfg, wd, kill_plan=KILL, **FABRIC_KW)
    m = out["manifest"]
    assert len(m["deaths"]) == 1 and m["restarts"] == 1
    assert m["merge_form"] == "async" and m["merge_staleness"] == 1
    # Worker-side evidence travels out through the result shards.
    assert m["counters"].get("host.merge_retry", 0) >= 1
    assert m["counters"].get("host.ckpt_shards", 0) >= 1
    ref_ll = ref["ll_history"][-1][1]
    fab_ll = out["ll_history"][-1][1]
    assert abs(fab_ll - ref_ll) <= 0.05 * abs(ref_ll), (ref_ll, fab_ll)


@pytest.mark.faults
def test_torn_host_ckpt_excluded_from_resume(corpus, tmp_path, monkeypatch):
    """host:ckpt=torn leaves a shard's npz without its json in EVERY
    worker; the torn sweep must vanish from the common-resume set while
    the fit itself completes untouched."""
    tcfg = dataclasses.replace(CFG, n_sweeps=4)
    # Shards land labeled by the LAST sweep of each superstep segment
    # (1 and 3 here); @2 fires at the first save with sweep >= 2 = 3.
    monkeypatch.setenv("ONIX_FAULT_PLAN", "host:ckpt@2=torn")
    wd = tmp_path / "fabric"
    out = hostfabric.run_fit(corpus, tcfg, wd, **FABRIC_KW)
    m = out["manifest"]
    assert m["restarts"] == 0 and not m["deaths"]
    fp = hostfabric.fabric_fingerprint(tcfg, 2, 1, corpus.n_docs,
                                       corpus.n_vocab, corpus.n_tokens)
    for host in (0, 1):
        sweeps = ckpt.intact_sweeps(wd / "ckpt" / fp / f"host-{host}")
        assert 3 not in sweeps, sweeps
        assert ckpt.load_at(wd / "ckpt" / fp / f"host-{host}", 3) is None
    # The surviving earlier boundary is still common to all hosts.
    assert ckpt.latest_common_sweep(wd / "ckpt" / fp, 2) == 1


def test_rebalance_on_death(tmp_path):
    """A dead host under on_death='rebalance': the corpus re-shards onto
    the survivor behind a deliberate fingerprint bump (stamped as
    rebalanced_from in the topology claim), and the rebalanced model is
    the fault-free fit of the topology it fell back to, to the bit."""
    corpus, _ = anomaly_corpus(n_docs=48, n_vocab=96, n_topics=4,
                               mean_doc_len=60, n_anomalies=10, seed=5)
    rcfg = dataclasses.replace(CFG, n_sweeps=8, burn_in=4)
    ref = _ref_fit(corpus, rcfg, dp=1)
    before = _host_counter("rebalance")
    wd = tmp_path / "fabric"
    out = hostfabric.run_fit(corpus, rcfg, wd, kill_plan=KILL,
                             on_death="rebalance", **FABRIC_KW)
    m = out["manifest"]

    assert m["rebalanced"] is True
    assert m["topology"]["n_hosts"] == 1       # completed on the survivor
    assert _host_counter("rebalance") - before == 1
    # The bump is deliberate and auditable: the displaced 2-host
    # topology is stamped into the new claim.
    topo = json.loads((wd / "ckpt" / "topology.json").read_text())
    assert topo["n_hosts"] == 1
    assert topo["rebalanced_from"]["n_hosts"] == 2
    # A re-sharded corpus is a NEW fingerprint — the rebalanced
    # generation starts clean rather than misreading 2-host shards.
    assert m["resume_sweeps"][-1] == -1

    # The survivor starts clean on the whole corpus, so its model is
    # the in-process one-host fit, bit for bit: the same contract the
    # same-topology drill holds the two-host fit to. (A band against
    # the TWO-host fit compares two chains, not a fault with its
    # absence: after these 8 sweeps neither has converged, and dp=2,
    # whose shards sample against each other's stale counts, climbs
    # slower: ll -2.943 against -2.713 here, -2.690 against -2.691
    # after 64 sweeps.)
    assert np.array_equal(ref["theta"], out["theta"])
    assert np.array_equal(ref["phi_wk"], out["phi_wk"])
    assert out["ll_history"][-1] == ref["ll_history"][-1]


# ---------------------------------------------------------------------------
# Process-free contracts (fingerprints, topology file, pre-r21 layout)
# ---------------------------------------------------------------------------


def test_fabric_fingerprint_refuses_host_resplit(corpus):
    """2 hosts × 1 device and 1 host × 2 devices are the SAME dp=2 mesh
    but different shard files — the fingerprint must split them."""
    fp21 = hostfabric.fabric_fingerprint(CFG, 2, 1, corpus.n_docs,
                                         corpus.n_vocab, corpus.n_tokens)
    fp12 = hostfabric.fabric_fingerprint(CFG, 1, 2, corpus.n_docs,
                                         corpus.n_vocab, corpus.n_tokens)
    fp31 = hostfabric.fabric_fingerprint(CFG, 3, 1, corpus.n_docs,
                                         corpus.n_vocab, corpus.n_tokens)
    assert len({fp21, fp12, fp31}) == 3


def test_topology_claim_semantics(tmp_path):
    topo2 = {"n_hosts": 2, "local_devices": 1, "fingerprint": "aaa"}
    topo3 = {"n_hosts": 3, "local_devices": 1, "fingerprint": "bbb"}
    # Unclaimed root: check passes through, claim writes.
    assert ckpt.check_topology(tmp_path, topo2) is None
    ckpt.claim_topology(tmp_path, topo2)
    assert ckpt.check_topology(tmp_path, topo2)["n_hosts"] == 2
    # Matching re-claim is a no-op; mismatch refuses with the diff.
    ckpt.claim_topology(tmp_path, topo2)
    with pytest.raises(ckpt.TopologyMismatch) as ei:
        ckpt.claim_topology(tmp_path, topo3)
    msg = str(ei.value)
    assert "n_hosts" in msg and "--rebalance" in msg
    # Forced re-claim (the rebalance path) stamps the displaced claim.
    stored = ckpt.claim_topology(tmp_path, topo3, force=True)
    assert stored["n_hosts"] == 3
    assert stored["rebalanced_from"]["n_hosts"] == 2
    # A second forced bump records the LATEST displaced topology, not a
    # chain (the full history lives in the manifest/ledger).
    topo1 = {"n_hosts": 1, "local_devices": 1, "fingerprint": "ccc"}
    stored = ckpt.claim_topology(tmp_path, topo1, force=True)
    assert stored["rebalanced_from"]["n_hosts"] == 3
    assert "rebalanced_from" not in stored["rebalanced_from"]


def test_torn_and_missing_shards_break_common_sweep(tmp_path):
    arrays = {"x": np.arange(4)}
    for host, sweeps in (("host-0", (2, 4)), ("host-1", (2, 4))):
        for s in sweeps:
            ckpt.save(tmp_path / host, s, arrays, {"fingerprint": "f"})
    assert ckpt.latest_common_sweep(tmp_path, 2) == 4
    # Tear host 1's sweep-4 json: 4 is no longer common; 2 still is.
    (tmp_path / "host-1" / "ckpt-000004.json").unlink()
    assert ckpt.intact_sweeps(tmp_path / "host-1") == [2]
    assert ckpt.latest_common_sweep(tmp_path, 2) == 2
    assert ckpt.load_at(tmp_path / "host-1", 4) is None
    # A third host with no shards at all: nothing is common.
    assert ckpt.latest_common_sweep(tmp_path, 3) is None


def test_pre_r21_single_process_layout_unchanged(tmp_path):
    """The single-process checkpoint contract (save/load_latest, no
    topology file) must keep working exactly as before the fabric."""
    arrays = {"z": np.arange(6, dtype=np.int32)}
    ckpt.save(tmp_path, 3, arrays, {"fingerprint": "solo", "sweep": 3})
    ckpt.save(tmp_path, 5, arrays, {"fingerprint": "solo", "sweep": 5})
    got = ckpt.load_latest(tmp_path)
    assert got is not None and got.meta["sweep"] == 5
    np.testing.assert_array_equal(got.arrays["z"], arrays["z"])
    # No topology.json was ever required or created by that path.
    assert not (tmp_path / ckpt.TOPOLOGY_FILE).exists()
    assert ckpt.check_topology(tmp_path, {"n_hosts": 1}) is None
    # load_at reads the same pre-r21 pair by exact sweep.
    assert ckpt.load_at(tmp_path, 3).meta["sweep"] == 3


# ---------------------------------------------------------------------------
# Heavier fleet — opt-in (ONIX_MULTIHOST_TESTS=1)
# ---------------------------------------------------------------------------


@pytest.mark.multihost
def test_three_host_sigkill_resume_bitidentical(tmp_path):
    """3-worker fleet, SIGKILL on host 2 mid-superstep, same-topology
    restart: still bit-identical to the in-process dp=3 fit."""
    corpus, _, _ = synthetic_lda_corpus(n_docs=36, n_vocab=60, n_topics=4,
                                        mean_doc_len=40, seed=7)
    ref = _ref_fit(corpus, CFG, dp=3)
    wd = tmp_path / "fabric"
    # 3 compiling workers on a small host starve heartbeat threads far
    # longer than 2 do — a generous lease keeps the only death the one
    # we inflict (a false-positive death is survivable but would break
    # the exact-count assert below).
    out = hostfabric.run_fit(
        corpus, CFG, wd, kill_plan={"host": 2, "after_sweep": 2},
        **{**FABRIC_KW, "n_hosts": 3, "lease_s": 10.0, "beat_s": 0.5,
           "timeout_s": 480.0})
    m = out["manifest"]
    assert len(m["deaths"]) == 1 and m["deaths"][0]["host"] == 2
    assert m["restarts"] == 1
    assert np.array_equal(ref["theta"], out["theta"])
    assert np.array_equal(ref["phi_wk"], out["phi_wk"])
