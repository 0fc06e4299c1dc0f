"""Worker process for tests/test_multihost.py — NOT a test module.

Each of the two workers joins a jax.distributed job over localhost
(CPU backend, 2 local devices each), builds the GLOBAL dp=4 mesh
through onix's own helpers, and runs a psum across all four shards —
the same collective the sharded Gibbs engine's sufficient-statistics
allreduce rides (SURVEY.md §2.3). Prints MULTIHOST_OK on success; any
failure exits nonzero with a traceback.
"""

import sys

import numpy as np

import jax

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
from jax.experimental import multihost_utils  # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from onix.parallel.mesh import DP_AXIS, make_mesh, multihost_init  # noqa: E402


def main() -> None:
    pid = int(sys.argv[1])
    addr = sys.argv[2]
    assert multihost_init(coordinator=addr, num_processes=2,
                          process_id=pid), "did not become multi-process"
    assert jax.process_count() == 2, jax.process_count()
    assert len(jax.devices()) == 4, jax.devices()       # 2 hosts x 2 local
    assert jax.local_device_count() == 2

    # Cross-process allgather: every process sees both contributions.
    g = multihost_utils.process_allgather(jnp.array([float(pid + 1)]))
    assert g.ravel().tolist() == [1.0, 2.0], g

    # Global mesh from onix's own constructor + a dp psum across hosts:
    # process-local shards [1,1] and [2,2] must reduce to 6 everywhere.
    mesh = make_mesh(dp=4)
    sharding = NamedSharding(mesh, P(DP_AXIS))
    local = np.full((2, 3), float(pid + 1), np.float32)
    arr = jax.make_array_from_process_local_data(sharding, local)
    out = jax.jit(jax.shard_map(lambda x: jax.lax.psum(x, DP_AXIS),
                                mesh=mesh, in_specs=P(DP_AXIS),
                                out_specs=P()))(arr)
    np.testing.assert_allclose(np.asarray(out.addressable_data(0)), 6.0)
    print("MULTIHOST_OK", flush=True)


if __name__ == "__main__":
    main()
