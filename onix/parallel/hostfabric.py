"""Multi-process fit fabric: coordinator, heartbeats, worker lifecycle.

ROADMAP item 4's missing half: `mesh.multihost_init` can join a
process-spanning mesh, but nothing drove one. This module does — N
worker PROCESSES (local subprocesses in tier-1, real hosts opt-in by
exporting ONIX_HOSTFABRIC_COORD and launching
`python -m onix.parallel.hostfabric --workdir W --host-id I` per host)
each own `local_devices` devices of one global (dp, mp=1) mesh and run
the UNCHANGED ShardedGibbsLDA superstep program over it, so the τ-ring
merge semantics (sync fold bit-identical, async τ≥1 inside the 5% ll
band) carry from virtual devices to processes with no new math.

The robustness contract (docs/ROBUSTNESS.md "multi-host fit fault
domain"):

- Each worker claims its corpus shard through the mpingest ClaimStore
  ledger and renews the claim lease from its heartbeat thread — shard
  ownership and liveness ride the SAME atomic-JSON file discipline as
  every other ledger in the repo (r9/r19).
- Workers heartbeat `hb/host-<i>.json` (atomic rename) every beat_s;
  the coordinator declares a host dead only when its lease
  (`lease_s` since the last beat) expires — a SIGKILLed worker, a
  worker that took an injected `host:death`, and a worker frozen past
  its own collective watchdog all converge to the same lease-expiry
  signal.
- On death the coordinator SIGKILLs the survivors (they are wedged in
  a collective with a dead peer anyway), quarantines the dead host's
  shard assignment with a sidecar (resilience.quarantine_file +
  ClaimStore.mark_quarantined), and either respawns the SAME topology
  — which resumes every worker from the newest sweep checkpointed
  intact by ALL hosts, bit-identical (sync) / in-band (async) to the
  fault-free run — or, only when rebalance was requested explicitly,
  re-shards the full corpus over the survivors behind a deliberate
  topology + fingerprint bump (checkpoint.claim_topology force=True).
  A topology change is NEVER resumed silently: checkpoint.
  check_topology refuses with a field diff (rc=3 from workers).
- Per-host checkpoint shards: each worker saves the LOCAL rows of the
  dp-sharded state plus the replicated tables through the ordinary
  checkpoint.save discipline into `ckpt/<fp>/host-<i>/`; resume is
  coordinator-decided (checkpoint.latest_common_sweep) so every shard
  restarts at the SAME superstep boundary.
- Collective calls get a bounded deadline + one retry before a worker
  declares a peer dead (`host.collective_deadline`, `host.peer_dead`);
  fault sites `host:death`, `host:merge`, `host:ckpt` ride
  ONIX_FAULT_PLAN pre-mutation like every prior site.

jax is imported lazily: a spawned worker must let the coordinator's
env (JAX_PLATFORMS, XLA_FLAGS device count) reach process start before
any backend is created.
"""

from __future__ import annotations

import json
import os
import pathlib
import signal
import socket
import subprocess
import sys
import threading
import time

import numpy as np

from onix.utils.obs import counters

# State fields sharded over dp on dim 0 (mp=1 fabric); everything else
# in ShardedGibbsState is replicated across the mesh.
_SHARDED_DIM0 = ("z", "n_dk", "keys", "acc_ndk")

_FATAL_RCS = {3: "topology refused", 4: "checkpoint shard load failed",
              5: "shard claim refused"}


class FabricError(RuntimeError):
    """Unrecoverable fabric failure (bad worker exit, restart budget
    exhausted, fabric timeout)."""


class HostDead(FabricError):
    """A host's heartbeat lease expired and the death policy was
    'fail' (or the fabric cannot restart, e.g. externally-launched
    workers)."""


class HostPeerDead(FabricError):
    """Raised inside a WORKER when a collective failed past its
    bounded deadline + retry — the peer is presumed dead; the
    coordinator's lease detection owns recovery."""


def require_cpu_coordinator() -> None:
    """Refuse to coordinate from a process that holds an accelerator.

    A chip belongs to one process: once this process has initialised
    JAX on a TPU, workers that need the chips
    (ONIX_FABRIC_WORKER_PLATFORM=tpu) fail or hang, and the default CPU
    workers would fit off-device while the caller's manifest lists TPU
    devices. Either way the fit would not be what the caller thinks, so
    raise before anything is spawned."""
    import jax
    backend = jax.default_backend()
    if backend != "cpu":
        raise FabricError(
            f"the fit fabric's coordinator is running in a process "
            f"whose JAX backend is {backend!r}: that process holds the "
            "accelerator, so fit workers cannot have it, and CPU "
            "workers would fit off-device unseen. Launch the "
            "coordinator under JAX_PLATFORMS=cpu (set "
            "ONIX_FABRIC_WORKER_PLATFORM=tpu to fit on this host's "
            "chips), or fit in-process (fit_hosts=1)")


# ---------------------------------------------------------------------------
# Shared identity: fingerprint + topology
# ---------------------------------------------------------------------------


def fabric_fingerprint(cfg, n_hosts: int, local_devices: int,
                       n_docs: int, n_vocab: int, n_tokens: int) -> str:
    """The fabric's resume identity — mirrors ShardedGibbsLDA.fit's
    fingerprint (same config hash, mesh shape, layout, resolved
    sampler + merge forms) and adds the HOST split: per-host shards
    written by a 2×1 fabric must refuse a 1×2 fabric even though both
    are a dp=2 mesh, because the shard files hold different row
    ranges. Computed identically by coordinator and workers (both
    resolve forms through the shared lda_gibbs resolvers on the same
    backend)."""
    from onix import checkpoint as ckpt
    from onix.models import lda_gibbs

    n_data = n_hosts * local_devices
    d_local = max(1, -(-n_docs // n_data))
    s_step = cfg.superstep or lda_gibbs.SUPERSTEP_DEFAULT
    sampler_form, sparse_active, _ = lda_gibbs.resolve_sampler(
        cfg, k_topics=cfg.n_topics)
    tau = int(cfg.merge_staleness) if cfg.merge_form == "async" else 0
    extra = {"mesh": [n_data, 1], "layout": 4,
             "hosts": [n_hosts, local_devices],
             **lda_gibbs.sampler_fingerprint(sampler_form, sparse_active,
                                             cfg.sparse_mh),
             **lda_gibbs.merge_fingerprint(cfg.merge_form, tau)}
    return ckpt.fingerprint(cfg, n_data * d_local, n_vocab, n_tokens,
                            extra=extra, superstep=s_step)


def _topology(n_hosts: int, local_devices: int, fp: str) -> dict:
    return {"n_hosts": int(n_hosts), "local_devices": int(local_devices),
            "fingerprint": fp}


# ---------------------------------------------------------------------------
# Workdir layout
# ---------------------------------------------------------------------------


def _spec_path(workdir: pathlib.Path) -> pathlib.Path:
    return workdir / "fabric.json"


def _shard_path(workdir: pathlib.Path, host_id: int) -> pathlib.Path:
    return workdir / "shards" / f"shard-host{host_id}.json"


def _hb_path(workdir: pathlib.Path, host_id: int) -> pathlib.Path:
    return workdir / "hb" / f"host-{host_id}.json"


def _result_path(workdir: pathlib.Path, host_id: int) -> pathlib.Path:
    return workdir / "result" / f"host-{host_id}.npz"


def _atomic_json(path: pathlib.Path, payload: dict) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_name(path.name + ".tmp")
    tmp.write_text(json.dumps(payload, indent=2))
    os.replace(tmp, path)


def _load_spec(workdir: pathlib.Path) -> dict:
    return json.loads(_spec_path(workdir).read_text())


def _save_corpus(workdir: pathlib.Path, corpus) -> None:
    tmp = workdir / "corpus.npz.tmp"
    with open(tmp, "wb") as f:
        np.savez(f, doc_ids=corpus.doc_ids, word_ids=corpus.word_ids,
                 n_docs=np.int64(corpus.n_docs),
                 n_vocab=np.int64(corpus.n_vocab))
    os.replace(tmp, workdir / "corpus.npz")


def _load_corpus(workdir: pathlib.Path):
    from onix.corpus import Corpus
    with np.load(workdir / "corpus.npz") as z:
        return Corpus(doc_ids=z["doc_ids"], word_ids=z["word_ids"],
                      n_docs=int(z["n_docs"]), n_vocab=int(z["n_vocab"]))


# ---------------------------------------------------------------------------
# Heartbeats (worker side)
# ---------------------------------------------------------------------------


class HeartbeatWriter:
    """Worker-side heartbeat lease: an atomic-JSON beat every `beat_s`
    from a daemon thread, carrying the fit's progress (sweep, status)
    for the coordinator and the chaos tests. The beat thread ALSO
    renews the worker's shard-claim lease (os.utime on the ClaimStore
    claim file) so shard ownership and liveness expire together."""

    GUARDED_BY = {"sweep": "_lock", "status": "_lock",
                  "_lease_path": "_lock"}

    def __init__(self, path: pathlib.Path, host_id: int, beat_s: float):
        self.path = pathlib.Path(path)
        self.host_id = int(host_id)
        self.beat_s = float(beat_s)
        self._lock = threading.Lock()
        self.sweep = -1
        self.status = "starting"
        self._lease_path: pathlib.Path | None = None
        self._beats = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name=f"hb-host{host_id}")

    def start(self) -> None:
        self._write()
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._write()

    def set_sweep(self, sweep: int) -> None:
        with self._lock:
            self.sweep = int(sweep)
        self._write()

    def set_status(self, status: str) -> None:
        with self._lock:
            self.status = status
        self._write()

    def attach_lease(self, claim_path: pathlib.Path) -> None:
        with self._lock:
            self._lease_path = pathlib.Path(claim_path)

    def _write(self) -> None:
        with self._lock:
            self._beats += 1
            payload = {"host": self.host_id, "pid": os.getpid(),
                       "beats": self._beats, "sweep": self.sweep,
                       "status": self.status, "ts": time.time()}
            lease = self._lease_path
        _atomic_json(self.path, payload)
        if lease is not None:
            try:
                os.utime(lease)
            except OSError:
                pass    # claim rotated (commit/quarantine) — benign

    def _run(self) -> None:
        while not self._stop.wait(self.beat_s):
            self._write()


def _read_heartbeat(path: pathlib.Path) -> dict | None:
    try:
        return json.loads(path.read_text())
    except (OSError, ValueError):
        return None


# ---------------------------------------------------------------------------
# Worker: shard extraction / restoration
# ---------------------------------------------------------------------------


def _local_block(a) -> tuple[np.ndarray, int]:
    """This process's contiguous dim-0 rows of a dp-sharded global
    array, plus the global row offset. Device order is process-major
    (make_mesh over jax.devices()), so the addressable shards form one
    contiguous row range."""
    shards = sorted(a.addressable_shards,
                    key=lambda s: s.index[0].start or 0)
    row0 = shards[0].index[0].start or 0
    return np.concatenate([np.asarray(s.data) for s in shards],
                          axis=0), int(row0)


def _put_from_local(local: np.ndarray, full_dim0: int, mesh, spec,
                    row0: int):
    """Rebuild a global dp-sharded array from this process's LOCAL
    rows (a checkpoint shard). The callback only ever materializes
    addressable blocks; a block outside [row0, row0+rows) means the
    shard was written by a different host slot — refuse."""
    import jax
    from jax.sharding import NamedSharding

    shape = (int(full_dim0),) + tuple(local.shape[1:])

    def cb(idx):
        s0 = idx[0]
        lo = 0 if s0.start is None else s0.start
        hi = shape[0] if s0.stop is None else s0.stop
        if lo < row0 or hi > row0 + local.shape[0]:
            raise RuntimeError(
                f"checkpoint shard covers rows [{row0}, "
                f"{row0 + local.shape[0]}), mesh wants [{lo}, {hi}) — "
                "shard written by a different host slot")
        return local[(slice(lo - row0, hi - row0),) + tuple(idx[1:])]

    return jax.make_array_from_callback(shape, NamedSharding(mesh, spec),
                                        cb)


def _extract_shard(state) -> tuple[dict, int]:
    """Host arrays for this worker's checkpoint shard: local rows of
    the dp-sharded fields, full copies of the replicated ones."""
    arrays, row0 = {}, 0
    for name, val in state._asdict().items():
        if name in _SHARDED_DIM0:
            arrays[name], row0 = _local_block(val)
        else:
            arrays[name] = np.asarray(val)
    return arrays, row0


def _state_from_shard(engine, saved, n_data: int):
    """Rebuild the global device state from one host's checkpoint
    shard (raises RuntimeError when the shard's rows don't cover this
    process's mesh slots)."""
    from jax.sharding import PartitionSpec as P

    from onix.parallel.sharded_gibbs import (ShardedGibbsState,
                                             put_global)
    specs = engine._specs()
    row0 = int(saved.meta["row0"])
    out = {}
    for name, spec in specs.items():
        a = saved.arrays[name]
        if name in _SHARDED_DIM0:
            out[name] = _put_from_local(a, n_data, engine.mesh, spec,
                                        row0)
        else:
            out[name] = put_global(a, engine.mesh, spec or P())
    return ShardedGibbsState(**out)


def _block_with_deadline(out, seconds: float, hb: HeartbeatWriter) -> None:
    """block_until_ready with a hard wall: a collective whose peer
    died never completes, so past the deadline the worker exits
    abruptly (rc 82) and lets the coordinator's lease detection own
    recovery — there is no safe way to unwind a wedged collective
    in-process."""
    import jax

    done = threading.Event()

    def _reap():
        if not done.wait(seconds):
            counters.inc("host.collective_deadline")
            hb.set_status("collective-deadline")
            os._exit(82)

    t = threading.Thread(target=_reap, daemon=True)
    t.start()
    try:
        jax.block_until_ready(out)
    finally:
        done.set()


# ---------------------------------------------------------------------------
# Worker main
# ---------------------------------------------------------------------------


def run_worker(workdir: str | pathlib.Path, host_id: int) -> int:
    """One fabric worker: claim shard, join the mesh, fit with
    per-segment heartbeats + guarded collectives + per-host checkpoint
    shards, write the result shard. Returns a process exit code
    (0 ok; 3 topology refused; 4 shard load failed; 5 claim refused)."""
    workdir = pathlib.Path(workdir)
    spec = _load_spec(workdir)
    hb = HeartbeatWriter(_hb_path(workdir, host_id), host_id,
                         spec["beat_s"])
    hb.start()
    try:
        return _worker_body(workdir, int(host_id), spec, hb)
    finally:
        hb.stop()


def _worker_body(workdir: pathlib.Path, host_id: int, spec: dict,
                 hb: HeartbeatWriter) -> int:
    from onix.ingest.mpingest import ClaimStore

    shard_file = _shard_path(workdir, host_id)
    store = ClaimStore(shard_file.parent, lease_seconds=spec["lease_s"])
    digest = store.try_claim(shard_file)
    if digest is None:
        hb.set_status("claim-refused")
        print(f"hostfabric host {host_id}: shard claim refused "
              f"({shard_file})", file=sys.stderr)
        return 5
    hb.attach_lease(store.dir / f"{digest}.claim")

    coord = os.environ.get("ONIX_HOSTFABRIC_COORD")
    if not coord:
        print("hostfabric worker needs ONIX_HOSTFABRIC_COORD",
              file=sys.stderr)
        return 2
    hb.set_status("init")
    from onix.parallel import mesh as mesh_mod
    mesh_mod.multihost_init(coord, spec["n_hosts"], host_id,
                            init_timeout_s=int(spec.get("init_timeout_s",
                                                        120)))

    from onix import checkpoint as ckpt
    from onix.config import LDAConfig
    cfg = LDAConfig(**spec["lda"])
    corpus = _load_corpus(workdir)
    fp = fabric_fingerprint(cfg, spec["n_hosts"], spec["local_devices"],
                            corpus.n_docs, corpus.n_vocab,
                            corpus.n_tokens)
    try:
        ckpt.check_topology(workdir / "ckpt",
                            _topology(spec["n_hosts"],
                                      spec["local_devices"], fp))
    except ckpt.TopologyMismatch as e:
        hb.set_status("topology-refused")
        print(f"hostfabric host {host_id}: {e}", file=sys.stderr)
        return 3

    from onix.parallel.mesh import make_mesh
    from onix.parallel.sharded_gibbs import ShardedGibbsLDA
    n_data = spec["n_hosts"] * spec["local_devices"]
    mesh = make_mesh(dp=n_data, mp=1)
    engine = ShardedGibbsLDA(cfg, corpus.n_vocab, mesh=mesh)
    sc = engine.prepare(corpus)
    hb.set_status("compile")
    docs, words, mask = engine.device_corpus(sc)

    shard_dir = workdir / "ckpt" / fp / f"host-{host_id}"
    resume_sweep = int(spec.get("resume_sweep", -1))
    state, start = None, 0
    if resume_sweep >= 0:
        saved = ckpt.load_at(shard_dir, resume_sweep)
        if saved is not None and saved.meta.get("fingerprint") == fp:
            try:
                state = _state_from_shard(engine, saved, n_data)
            except RuntimeError as e:
                print(f"hostfabric host {host_id}: {e}", file=sys.stderr)
                state = None
        if state is None:
            hb.set_status("shard-load-failed")
            print(f"hostfabric host {host_id}: cannot resume sweep "
                  f"{resume_sweep} from {shard_dir}", file=sys.stderr)
            return 4
        start = resume_sweep + 1
    if state is None:
        state = engine.init_state(sc, device_blocks=(docs, words, mask))

    from onix.models.lda_gibbs import (SUPERSTEP_DEFAULT, plan_segments,
                                       run_fit_segments)
    from onix.utils import faults, telemetry
    s_step = cfg.superstep or SUPERSTEP_DEFAULT
    ckpt_every = cfg.checkpoint_every or s_step
    n_sweeps = int(spec.get("n_sweeps") or cfg.n_sweeps)
    deadline_s = float(spec.get("collective_deadline_s", 120.0))

    def save_shard(st, sweep):
        mode = faults.fire("host", "ckpt", index=sweep)
        arrays, row0 = _extract_shard(st)
        ckpt.save(shard_dir, sweep, arrays,
                  {"fingerprint": fp, "engine": "hostfabric",
                   "host": host_id, "row0": row0})
        counters.inc("host.ckpt_shards")
        if mode == "torn":
            # Render the mid-save crash: the npz renamed durable, the
            # json never written — latest_common_sweep must skip it.
            (shard_dir / f"ckpt-{sweep:06d}.json").unlink(missing_ok=True)

    def superstep(st, s0, n, with_init):
        try:
            faults.fire("host", "death", index=s0)
        except faults.InjectedFault:
            # Simulated sudden host death: no cleanup, no checkpoint —
            # the coordinator's lease detection absorbs it exactly as
            # it absorbs a real SIGKILL.
            hb.set_status("injected-death")
            os._exit(81)
        hb.set_sweep(s0)
        with telemetry.TRACER.span("host.superstep"):
            err = None
            for _ in range(2):
                try:
                    faults.fire("host", "merge", index=s0)
                    out = engine._superstep_shardmap(
                        st, docs, words, mask, s0, n_steps=n,
                        with_initial_ll=with_init)
                    _block_with_deadline(out, deadline_s, hb)
                    return out
                except RuntimeError as e:   # InjectedFault, XLA errors
                    counters.inc("host.merge_retry")
                    err = e
            counters.inc("host.peer_dead")
            hb.set_status("peer-dead")
            raise HostPeerDead(f"host {host_id}: collective failed "
                               f"twice at sweep {s0}") from err

    hb.set_status("fit")
    segments = plan_segments(start, n_sweeps, s_step,
                             checkpoint_every=ckpt_every)
    state, ll_history = run_fit_segments(
        state, start, segments,
        superstep_fn=superstep,
        initial_ll_fn=lambda st: engine._ll(st, docs, words, mask),
        checkpoint_every=ckpt_every,
        checkpoint_dir=shard_dir,
        save_fn=save_shard,
        fault_sweep=None, notify=None)

    hb.set_status("result")
    _write_result(workdir, host_id, spec, state, sc, ll_history)
    store.commit(digest)
    hb.set_status("done")
    return 0


def _write_result(workdir: pathlib.Path, host_id: int, spec: dict,
                  state, sc, ll_history) -> None:
    """Atomic per-host result shard: every host ships its local doc
    rows; host 0 additionally ships the replicated word tables, the
    doc map, and the ll series (identical on every host)."""
    res = _result_path(workdir, host_id)
    res.parent.mkdir(parents=True, exist_ok=True)
    import jax
    n_dk, row0 = _local_block(state.n_dk)
    acc_ndk, _ = _local_block(state.acc_ndk)
    device = jax.local_devices()[0]
    payload = {"n_dk": n_dk, "acc_ndk": acc_ndk,
               "row0": np.int64(row0), "n_acc": np.asarray(state.n_acc),
               "n_hosts": np.int64(spec["n_hosts"]),
               "host": np.int64(host_id),
               # What this worker actually fitted on — the manifest
               # records it, so a fit is never attributed to devices
               # it did not run on.
               "platform": np.str_(device.platform),
               "device_kind": np.str_(device.device_kind),
               # This worker's host.* counter snapshot (merge retries,
               # shard saves, ...) — counters live per process, so the
               # coordinator can only surface them in the manifest if
               # the result shard carries them out.
               "host_counters": np.str_(
                   json.dumps(counters.snapshot("host.")))}
    if host_id == 0:
        payload.update(
            n_wk=np.asarray(state.n_wk),
            acc_nwk=np.asarray(state.acc_nwk),
            n_k=np.asarray(state.n_k),
            doc_map=np.asarray(sc.doc_map),
            ll_sweeps=np.asarray([s for s, _ in ll_history], np.int64),
            ll_values=np.asarray([v for _, v in ll_history], np.float64))
    tmp = res.with_name(res.name + ".tmp")
    with open(tmp, "wb") as f:
        np.savez(f, **payload)
    os.replace(tmp, res)


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        description="onix hostfabric worker (one host of a "
                    "multi-process fit)")
    ap.add_argument("--workdir", required=True)
    ap.add_argument("--host-id", type=int, required=True)
    args = ap.parse_args(argv)
    return run_worker(args.workdir, args.host_id)


# ---------------------------------------------------------------------------
# Coordinator
# ---------------------------------------------------------------------------


class _KillWatcher(threading.Thread):
    """Chaos hook: delivers ONE real SIGKILL to a worker's process
    group the moment its heartbeat reports reaching `after_sweep` —
    i.e. mid-superstep, the hardest point to die at."""

    def __init__(self, coord: "FabricCoordinator", host: int,
                 after_sweep: int):
        super().__init__(daemon=True, name="fabric-kill-watcher")
        self.coord = coord
        self.host = host
        self.after_sweep = after_sweep
        self._halt = threading.Event()

    def halt(self) -> None:
        self._halt.set()

    def run(self) -> None:
        while not self._halt.wait(self.coord.beat_s / 4):
            beat = _read_heartbeat(_hb_path(self.coord.workdir, self.host))
            if beat is None or beat.get("sweep", -1) < self.after_sweep:
                continue
            with self.coord._lock:
                if self.coord.kill_delivered:
                    return
                self.coord.kill_delivered = True
                proc = self.coord._procs.get(self.host)
            if proc is not None and proc.poll() is None:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except (ProcessLookupError, PermissionError):
                    pass
                counters.inc("host.kill_delivered")
            return


class FabricCoordinator:
    """Spawns, monitors, and (on death) restarts or rebalances the
    worker fleet; assembles the final estimates from the per-host
    result shards. Lives in the CALLING process (tests, scale.py) so
    its `host.*` counters and flight-recorder dumps are visible
    there."""

    GUARDED_BY = {"kill_delivered": "_lock", "deaths": "_lock",
                  "restarts": "_lock", "_procs": "_lock"}

    def __init__(self, corpus, cfg, workdir, *, n_hosts=2,
                 local_devices=1, n_sweeps=None, on_death="restart",
                 max_restarts=2, rebalance=False, lease_s=6.0,
                 beat_s=0.5, collective_deadline_s=120.0,
                 init_timeout_s=120, timeout_s=900.0, kill_plan=None,
                 worker_env=None, spawn=True):
        if on_death not in ("restart", "rebalance", "fail"):
            raise ValueError(f"unknown on_death policy {on_death!r}")
        if n_hosts < 1:
            raise ValueError("n_hosts must be >= 1")
        self.corpus = corpus
        import dataclasses
        # The resume contract needs superstep-boundary checkpoints;
        # default the cadence to one checkpoint per superstep.
        from onix.models.lda_gibbs import SUPERSTEP_DEFAULT
        s_step = cfg.superstep or SUPERSTEP_DEFAULT
        self.cfg = (cfg if cfg.checkpoint_every
                    else dataclasses.replace(cfg, checkpoint_every=s_step))
        self.workdir = pathlib.Path(workdir)
        self.n_hosts = int(n_hosts)
        self.local_devices = int(local_devices)
        self.n_sweeps = int(n_sweeps if n_sweeps is not None
                            else self.cfg.n_sweeps)
        self.on_death = on_death
        self.max_restarts = int(max_restarts)
        self.rebalance = bool(rebalance)
        self.lease_s = float(lease_s)
        self.beat_s = float(beat_s)
        self.collective_deadline_s = float(collective_deadline_s)
        self.init_timeout_s = int(init_timeout_s)
        self.timeout_s = float(timeout_s)
        self.kill_plan = kill_plan
        self.worker_env = worker_env or {}
        self.spawn = bool(spawn)
        if not self.spawn and on_death != "fail":
            # Externally-launched workers cannot be respawned from
            # here; detection still works, recovery is the operator's.
            self.on_death = "fail"
        self._lock = threading.Lock()
        self.kill_delivered = kill_plan is None
        self.deaths: list[dict] = []
        self.restarts = 0
        self.rebalanced = False
        self._procs: dict[int, subprocess.Popen] = {}
        self._generation = 0
        self._resume_sweeps: list[int] = []

    # -- identity ---------------------------------------------------------

    def _fingerprint(self) -> str:
        return fabric_fingerprint(self.cfg, self.n_hosts,
                                  self.local_devices,
                                  self.corpus.n_docs,
                                  self.corpus.n_vocab,
                                  self.corpus.n_tokens)

    # -- lifecycle --------------------------------------------------------

    def run(self) -> dict:
        from onix import checkpoint as ckpt
        from onix.utils import telemetry

        require_cpu_coordinator()
        t0 = time.monotonic()
        self.workdir.mkdir(parents=True, exist_ok=True)
        _save_corpus(self.workdir, self.corpus)
        fp = self._fingerprint()
        topo = _topology(self.n_hosts, self.local_devices, fp)
        # Raises TopologyMismatch on a changed-topology resume unless
        # the caller asked for the deliberate rebalance bump.
        stored = ckpt.claim_topology(self.workdir / "ckpt", topo,
                                     force=self.rebalance)
        if stored.get("rebalanced_from"):
            self.rebalanced = True
        gen_walls = []
        with telemetry.TRACER.span("host.fit"):
            while True:
                g0 = time.monotonic()
                fp = self._fingerprint()
                resume = ckpt.latest_common_sweep(
                    self.workdir / "ckpt" / fp, self.n_hosts)
                resume = -1 if resume is None else int(resume)
                self._resume_sweeps.append(resume)
                self._write_generation(fp, resume)
                watcher = None
                if self.spawn:
                    self._spawn_workers()
                    if not self.kill_delivered:
                        watcher = _KillWatcher(self,
                                               self.kill_plan["host"],
                                               self.kill_plan["after_sweep"])
                        watcher.start()
                try:
                    dead = self._monitor()
                finally:
                    if watcher is not None:
                        watcher.halt()
                gen_walls.append(round(time.monotonic() - g0, 3))
                if dead is None:
                    break
                self._handle_death(dead)
                with self._lock:
                    self.restarts += 1
                    n_restarts = self.restarts
                if self.on_death == "fail":
                    raise HostDead(
                        f"host {dead} heartbeat lease expired "
                        f"(generation {self._generation})")
                if n_restarts > self.max_restarts:
                    raise FabricError(
                        f"restart budget exhausted "
                        f"({self.max_restarts}) after host {dead} died")
                if self.on_death == "rebalance":
                    self.n_hosts -= 1
                    if self.n_hosts < 1:
                        raise FabricError("no surviving hosts to "
                                          "rebalance onto")
                    counters.inc("host.rebalance")
                    self.rebalanced = True
                    fp = self._fingerprint()
                    ckpt.claim_topology(
                        self.workdir / "ckpt",
                        _topology(self.n_hosts, self.local_devices, fp),
                        force=True)
                else:
                    counters.inc("host.restarts")
                self._generation += 1
            theta, phi_wk, ll_history = self._assemble()
        manifest = {
            "topology": _topology(self.n_hosts, self.local_devices,
                                  self._fingerprint()),
            "merge_form": self.cfg.merge_form,
            "merge_staleness": (self.cfg.merge_staleness
                                if self.cfg.merge_form == "async" else 0),
            "n_sweeps": self.n_sweeps,
            "generations": self._generation + 1,
            "deaths": list(self.deaths),
            "restarts": self.restarts,
            "rebalanced": self.rebalanced,
            "resume_sweeps": list(self._resume_sweeps),
            # The devices the workers report having fitted on.
            "workers": [{"platform": p, "device_kind": k}
                        for p, k in self._worker_devices],
            # Coordinator-side host.* counters (death detection,
            # quarantine, restarts) merged with the final generation's
            # worker-side ones (merge retries, shard saves) carried out
            # through the result shards — counters are per process.
            "counters": _merge_counters(
                counters.snapshot("host."),
                getattr(self, "_worker_counters", {})),
            "walls": {"total_s": round(time.monotonic() - t0, 3),
                      "generations_s": gen_walls},
        }
        _atomic_json(self.workdir / "manifest.json", manifest)
        return {"theta": theta, "phi_wk": phi_wk,
                "ll_history": ll_history, "manifest": manifest}

    def _write_generation(self, fp: str, resume_sweep: int) -> None:
        import dataclasses
        for i in range(self.n_hosts):
            _atomic_json(_shard_path(self.workdir, i),
                         {"host": i, "n_hosts": self.n_hosts,
                          "generation": self._generation,
                          "fingerprint": fp,
                          "rebalanced": self.rebalanced})
        if self.spawn:
            for res in self.workdir.glob("result/host-*.npz"):
                res.unlink(missing_ok=True)
        _atomic_json(_spec_path(self.workdir), {
            "n_hosts": self.n_hosts,
            "local_devices": self.local_devices,
            "lda": dataclasses.asdict(self.cfg),
            "n_sweeps": self.n_sweeps,
            "resume_sweep": resume_sweep,
            "lease_s": self.lease_s,
            "beat_s": self.beat_s,
            "collective_deadline_s": self.collective_deadline_s,
            "init_timeout_s": self.init_timeout_s,
            "generation": self._generation,
        })

    def _spawn_workers(self) -> None:
        import onix
        port = _free_port()
        root = pathlib.Path(onix.__file__).resolve().parents[1]
        (self.workdir / "log").mkdir(exist_ok=True)
        procs = {}
        worker_platform = os.environ.get("ONIX_FABRIC_WORKER_PLATFORM")
        tpu_port0 = _free_port() if worker_platform == "tpu" else 0
        for i in range(self.n_hosts):
            env = dict(os.environ)
            env.update(self.worker_env.get(i, {}))
            if worker_platform == "tpu":
                # Operator-gated TPU split: each worker owns
                # local_devices chips of THIS host via the documented
                # single-host multi-process envs. The coordinator does
                # not hold the TPU itself (require_cpu_coordinator) —
                # libtpu chips are exclusive.
                env["JAX_PLATFORMS"] = "tpu"
                env.update(_tpu_split_env(i, self.n_hosts,
                                          self.local_devices, tpu_port0))
            else:
                # Default: CPU workers with gloo collectives — safe on
                # any machine, and the tier-1 chaos surface.
                env["JAX_PLATFORMS"] = (worker_platform
                                        or env.get("JAX_PLATFORMS")
                                        or "cpu")
                env["XLA_FLAGS"] = _xla_flags_with_device_count(
                    env.get("XLA_FLAGS"), self.local_devices)
            env["ONIX_HOSTFABRIC_COORD"] = f"127.0.0.1:{port}"
            env["PYTHONPATH"] = (str(root) + os.pathsep
                                 + env.get("PYTHONPATH", ""))
            log = open(self.workdir / "log" / f"host-{i}.log", "ab")
            try:
                proc = subprocess.Popen(
                    [sys.executable, "-m", "onix.parallel.hostfabric",
                     "--workdir", str(self.workdir), "--host-id", str(i)],
                    stdout=log, stderr=subprocess.STDOUT, env=env,
                    start_new_session=True)
            finally:
                log.close()
            procs[i] = proc
        with self._lock:
            self._procs = procs

    def _monitor(self) -> int | None:
        """Poll heartbeats + worker exits until the generation either
        completes (returns None) or a host's lease expires (returns
        the dead host id). Fatal worker exit codes raise."""
        from onix import checkpoint as ckpt

        spawn_ts = time.time()
        deadline = time.monotonic() + self.timeout_s
        beats_seen: dict[int, int] = {}
        poll_s = min(self.beat_s, 0.25)
        while True:
            time.sleep(poll_s)
            if time.monotonic() > deadline:
                self._kill_all()
                raise FabricError(
                    f"fabric timed out after {self.timeout_s}s "
                    f"(generation {self._generation})")
            with self._lock:
                procs = dict(self._procs)
            if self.spawn:
                rcs = {i: p.poll() for i, p in procs.items()}
            else:
                # Externally-launched workers: a result shard present
                # is the only success signal the coordinator can see.
                rcs = {i: (0 if _result_path(self.workdir, i).exists()
                           else None) for i in range(self.n_hosts)}
            for i, rc in rcs.items():
                if rc in _FATAL_RCS:
                    self._kill_all()
                    tail = self._log_tail(i)
                    if rc == 3:
                        raise ckpt.TopologyMismatch(
                            f"worker {i} refused the topology:\n{tail}")
                    raise FabricError(f"worker {i} failed "
                                      f"({_FATAL_RCS[rc]}):\n{tail}")
            if all(rc == 0 for rc in rcs.values()):
                return None
            now = time.time()
            for i in (procs if self.spawn else range(self.n_hosts)):
                if rcs.get(i) == 0:
                    continue        # finished cleanly — never "dead"
                hb_path = _hb_path(self.workdir, i)
                try:
                    last = max(hb_path.stat().st_mtime, spawn_ts)
                except OSError:
                    last = spawn_ts
                if now - last > self.lease_s:
                    return i
                beat = _read_heartbeat(hb_path)
                if beat and beat.get("beats", 0) > beats_seen.get(i, 0):
                    beats_seen[i] = beat["beats"]
                    counters.inc("host.heartbeats")

    def _handle_death(self, dead: int) -> None:
        from onix.ingest.mpingest import ClaimStore, _digest
        from onix.utils import resilience, telemetry

        beat = _read_heartbeat(_hb_path(self.workdir, dead)) or {}
        with self._lock:
            self.deaths.append({"host": dead,
                                "generation": self._generation,
                                "last_sweep": beat.get("sweep", -1),
                                "last_status": beat.get("status")})
        counters.inc("host.death_detected")
        telemetry.RECORDER.dump(
            "host-death",
            extra={"host": dead, "generation": self._generation,
                   "last_beat": beat})
        self._kill_all()
        # Quarantine the dead incarnation's shard assignment: the
        # ledger marker pins that exact claim signature dead-lettered;
        # the sidecar + moved file keep the evidence. The NEXT
        # generation rewrites the shard file (fresh mtime → fresh
        # claimable digest), mirroring mpingest's re-delivery rule.
        shard_file = _shard_path(self.workdir, dead)
        store = ClaimStore(shard_file.parent,
                           lease_seconds=self.lease_s)
        sig = None
        try:
            digest, sig = _digest(shard_file)
            store.mark_quarantined(
                digest, {"host": dead, "reason": "heartbeat-lease-expired",
                         "generation": self._generation,
                         "path": str(shard_file)})
        except FileNotFoundError:
            digest = None
        resilience.quarantine_file(
            shard_file, self.workdir / "quarantine",
            error=f"host {dead} heartbeat lease expired mid-fit "
                  f"(last status {beat.get('status')!r}, sweep "
                  f"{beat.get('sweep', -1)})",
            attempts=self.restarts + 1,
            sig=[digest] if digest else None)
        counters.inc("host.quarantined")

    def _kill_all(self) -> None:
        if not self.spawn:
            return
        with self._lock:
            procs = dict(self._procs)
        for proc in procs.values():
            if proc.poll() is None:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except (ProcessLookupError, PermissionError):
                    pass
        for proc in procs.values():
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                pass

    def _log_tail(self, host: int, lines: int = 25) -> str:
        try:
            text = (self.workdir / "log" / f"host-{host}.log"
                    ).read_text(errors="replace")
        except OSError:
            return "<no log>"
        return "\n".join(text.splitlines()[-lines:])

    # -- result assembly --------------------------------------------------

    def _assemble(self):
        parts = []
        devices: set[tuple[str, str]] = set()
        self._worker_counters: dict[str, int] = {}
        for i in range(self.n_hosts):
            with np.load(_result_path(self.workdir, i)) as z:
                parts.append({k: z[k] for k in z.files})
            devices.add((str(parts[-1].pop("platform")),
                         str(parts[-1].pop("device_kind"))))
            raw = parts[-1].pop("host_counters", None)
            if raw is not None:
                for k, v in json.loads(str(raw)).items():
                    self._worker_counters[k] = \
                        self._worker_counters.get(k, 0) + int(v)
        self._worker_devices = sorted(devices)
        for i, part in enumerate(parts):
            if int(part["n_hosts"]) != self.n_hosts:
                raise FabricError(
                    f"result shard {i} written for a "
                    f"{int(part['n_hosts'])}-host fleet, expected "
                    f"{self.n_hosts}")
        parts.sort(key=lambda p: int(p["row0"]))
        n_dk = np.concatenate([p["n_dk"] for p in parts], axis=0)
        acc_ndk = np.concatenate([p["acc_ndk"] for p in parts], axis=0)
        head = next(p for p in parts if int(p["host"]) == 0)
        theta, phi_wk = _assemble_estimates(
            self.cfg, self.corpus.n_vocab, self.corpus.n_docs,
            head["doc_map"], int(head["n_acc"]), n_dk, acc_ndk,
            head["n_wk"], head["acc_nwk"])
        ll_history = list(zip((int(s) for s in head["ll_sweeps"]),
                              (float(v) for v in head["ll_values"])))
        return theta, phi_wk, ll_history


def _assemble_estimates(cfg, n_vocab: int, n_docs: int, doc_map,
                        n_acc: int, n_dk, acc_ndk, n_wk, acc_nwk):
    """ShardedGibbsLDA.estimates' exact math over host arrays gathered
    from the result shards (the coordinator never builds a device
    state)."""
    from onix.parallel.sharded_gibbs import chunked_to_global_nwk

    use_acc = n_acc > 0
    denom = max(float(n_acc), 1.0)
    ndk_s = acc_ndk / denom if use_acc else n_dk.astype(np.float64)
    nwk_c = acc_nwk / denom if use_acc else n_wk.astype(np.float64)
    n_chains = ndk_s.shape[1]
    valid = doc_map >= 0
    thetas, phis = [], []
    for ch in range(n_chains):
        nwk = chunked_to_global_nwk(nwk_c[:, ch], n_vocab)
        ndk = np.zeros((n_docs, cfg.n_topics))
        ndk[doc_map[valid]] = ndk_s[:, ch][valid]
        thetas.append((ndk + cfg.alpha)
                      / (ndk.sum(-1, keepdims=True)
                         + cfg.n_topics * cfg.alpha))
        phis.append((nwk + cfg.eta) / (nwk.sum(0, keepdims=True)
                                       + n_vocab * cfg.eta))
    theta = np.stack(thetas).astype(np.float32)
    phi_wk = np.stack(phis).astype(np.float32)
    if n_chains == 1:
        return theta[0], phi_wk[0]
    return theta, phi_wk


def _merge_counters(coord: dict, workers: dict) -> dict:
    """Coordinator and worker processes increment DISJOINT host.*
    counters, but sum defensively in case a name ever lands on both."""
    out = dict(coord)
    for k, v in workers.items():
        out[k] = out.get(k, 0) + int(v)
    return out


def _xla_flags_with_device_count(base: str | None, n: int) -> str:
    flags = [f for f in (base or "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    flags.append(f"--xla_force_host_platform_device_count={n}")
    return " ".join(flags)


def _tpu_split_env(host_id: int, n_hosts: int, local_devices: int,
                   tpu_port0: int) -> dict[str, str]:
    """Per-worker env for the documented single-host multi-process TPU
    split: each worker sees its own `local_devices` chips and the
    runtime's own process mesh (TPU_PROCESS_ADDRESSES / PORT / task id)
    is wired alongside jax.distributed. Topology-shaped bounds vars
    (TPU_PROCESS_BOUNDS et al.) are hardware-specific; operators set
    them through `worker_env` when their slice needs them."""
    chips = range(host_id * local_devices, (host_id + 1) * local_devices)
    addresses = ",".join(f"localhost:{tpu_port0 + i}"
                         for i in range(n_hosts))
    return {
        "TPU_VISIBLE_DEVICES": ",".join(str(c) for c in chips),
        "TPU_PROCESS_ADDRESSES": addresses,
        "TPU_PROCESS_PORT": str(tpu_port0 + host_id),
        "CLOUD_TPU_TASK_ID": str(host_id),
    }


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def run_fit(corpus, cfg, workdir, **kwargs) -> dict:
    """Run one multi-host fit end-to-end; returns
    {"theta", "phi_wk", "ll_history", "manifest"} — the same estimate
    payload ShardedGibbsLDA.fit yields, assembled from the per-host
    result shards. See FabricCoordinator for the keyword surface
    (n_hosts, local_devices, on_death, rebalance, kill_plan, ...)."""
    return FabricCoordinator(corpus, cfg, workdir, **kwargs).run()


if __name__ == "__main__":
    sys.exit(main())
