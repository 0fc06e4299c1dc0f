"""Device-mesh construction and sharding helpers.

The reference's distributed backend is MPI + ssh + a shared filesystem
(SURVEY.md §2.3): ranks are launched by `mpiexec -machinefile NODES`,
collectives are MPI_Reduce/MPI_Bcast of dense K×V float matrices. The
TPU-native equivalent is a `jax.sharding.Mesh` over the pod slice with
XLA collectives over ICI — `psum` replaces MPI_Reduce+Bcast, and there
is no launcher because the TPU multi-host runtime (jax.distributed)
owns process placement.

Axes:
- ``dp`` — data parallel: documents/tokens sharded (the reference's only
  model-math parallelism, SURVEY.md §2.2).
- ``mp`` — model parallel: vocabulary sharded, for K×V matrices that
  outgrow one chip's HBM (SURVEY.md §5.7 — the honest "tensor" axis of
  LDA).
"""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh

DP_AXIS = "dp"
MP_AXIS = "mp"
DCN_AXIS = "dcn"        # across-slice axis (data-center network)
# Data-parallel collective axes for a multislice mesh: psum over both
# rides ICI within a slice and DCN across slices; XLA decomposes the
# collective hierarchically.
DATA_AXES = (DCN_AXIS, DP_AXIS)


def make_mesh(dp: int | None = None, mp: int = 1,
              devices: list | None = None) -> Mesh:
    """Build a (dp, mp) mesh from available devices.

    With `dp=None`, all remaining devices go to the data axis. On a real
    slice the device order from `jax.devices()` follows the ICI torus, so
    neighboring dp shards are ICI neighbors and the per-sweep psum of
    topic sufficient statistics rides ICI (BASELINE.json north star:
    "topic-sufficient-statistics allreduced over ICI").
    """
    devs = devices if devices is not None else jax.devices()
    n = len(devs)
    if dp is None:
        if n % mp:
            raise ValueError(f"{n} devices not divisible by mp={mp}")
        dp = n // mp
    need = dp * mp
    if need > n:
        raise ValueError(f"mesh {dp}x{mp} needs {need} devices, have {n}")
    grid = np.asarray(devs[:need]).reshape(dp, mp)
    return Mesh(grid, (DP_AXIS, MP_AXIS))


def make_multislice_mesh(dcn: int, dp: int | None = None, mp: int = 1,
                         devices: list | None = None) -> Mesh:
    """(dcn, dp, mp) mesh spanning `dcn` slices.

    The reference's 20-node MPI job treats all ranks as one flat ring;
    on multislice TPU the topology is two-tier — ICI within a slice, DCN
    between slices (SURVEY.md §2.3) — so the slice axis is explicit and
    OUTERMOST: psum over (dcn, dp) lets XLA reduce within each slice
    over ICI first and exchange only the reduced K×V stats over DCN.

    On real multislice hardware, pass `devices` grouped slice-major
    (jax.devices() already is); for CPU/fake-device tests any ordering
    works and the axis is purely logical.
    """
    devs = devices if devices is not None else jax.devices()
    n = len(devs)
    if n % dcn:
        raise ValueError(f"{n} devices not divisible by dcn={dcn}")
    per_slice = n // dcn
    if dp is None:
        if per_slice % mp:
            raise ValueError(
                f"{per_slice} devices/slice not divisible by mp={mp}")
        dp = per_slice // mp
    need = dcn * dp * mp
    if need > n:
        raise ValueError(f"mesh {dcn}x{dp}x{mp} needs {need} devices, "
                         f"have {n}")
    grid = np.asarray(devs[:need]).reshape(dcn, dp, mp)
    return Mesh(grid, (DCN_AXIS, DP_AXIS, MP_AXIS))


def data_axes_of(mesh: Mesh) -> tuple[str, ...]:
    """The data-parallel axis names present in `mesh` (dcn first)."""
    return tuple(a for a in DATA_AXES if a in mesh.shape)


def multihost_init(coordinator: str | None = None,
                   num_processes: int | None = None,
                   process_id: int | None = None,
                   init_timeout_s: int | None = None) -> bool:
    """Initialize the multi-host runtime. Returns True when this process
    is part of a multi-process job after the call.

    Replaces the reference's ssh + machinefile launch (SURVEY.md §3.1):
    on a TPU pod each host calls this once with no arguments —
    `jax.distributed.initialize` auto-detects the coordinator from the
    pod metadata — and the runtime wires up DCN/ICI; there is no
    external launcher to maintain. Off-pod (CPU soak tests, the
    2-process suite in tests/test_multihost.py) pass all three
    arguments explicitly, exactly as `mesh.coordinator/num_processes/
    process_id` feed them from the config.

    Failure RAISES: a pod job continuing single-process after a botched
    init would silently train on 1/N of the data (the round-2
    `except: pass` bug, VERDICT weak #7). The only swallowed case is
    the explicit single-process one: no arguments given and no
    multi-host environment detected, where running solo is the
    requested behavior.
    """
    # Probe via jax.distributed.is_initialized, NOT process_count():
    # process_count() instantiates the XLA backend, after which
    # jax.distributed.initialize refuses to run at all.
    if jax.distributed.is_initialized():
        return jax.process_count() > 1
    explicit = coordinator is not None
    if explicit:
        # Explicit init is how CPU fabrics launch (hostfabric workers,
        # the 2-process suite); cross-process CPU collectives ride gloo,
        # jax's default implementation.
        kw = ({"initialization_timeout": init_timeout_s}
              if init_timeout_s else {})
        jax.distributed.initialize(coordinator_address=coordinator,
                                   num_processes=num_processes,
                                   process_id=process_id, **kw)
        return jax.process_count() > 1
    # Auto mode: only a real multi-host environment should initialize.
    # jax.distributed.initialize() raises on single-host CPU/GPU dev
    # boxes (no cluster-detection env) — treat exactly that as "running
    # solo was requested", WARN so a botched cluster launch is visible
    # in every rank's log (a silent solo rank trains on 1/N of the
    # data), and re-raise anything else.
    try:
        jax.distributed.initialize()
    except (RuntimeError, ValueError) as e:
        msg = str(e).lower()
        # "must be called before any JAX calls": the backend is already
        # up in this process. In a genuinely solo session (the sharded
        # engine invoked mid-process, tests) that is a benign no-op —
        # but if the environment says this process is one rank of a
        # multi-process job, running solo would silently train on 1/N
        # of the data (the round-2 bug), so it must still RAISE.
        solo_shaped = ("detect" in msg or "coordinator_address" in msg
                       or "single-process" in msg or "called before" in msg)
        if solo_shaped and not _cluster_env_says_multiprocess():
            import sys
            print("multihost_init: no multi-host environment detected; "
                  f"running single-process ({e})", file=sys.stderr)
            return False
        raise
    return jax.process_count() > 1


def _cluster_env_says_multiprocess() -> bool:
    """True when launcher env vars claim >1 processes — the guard that
    keeps auto-mode's solo fallback from swallowing a real pod/cluster
    rank's init failure."""
    import os
    for var in ("JAX_NUM_PROCESSES", "SLURM_NTASKS",
                "OMPI_COMM_WORLD_SIZE", "PMI_SIZE"):
        try:
            if int(os.environ.get(var, "1")) > 1:
                return True
        except ValueError:
            pass
    return False
