"""Child process for tests/test_multihost.py — one of N jax.distributed
processes on the CPU backend (gloo collectives).

Replaces the reference's multi-node story — k8s `replicas` of predictor pods
behind a Service (reference proto/seldon_deployment.proto:48,
SeldonDeploymentOperatorImpl.java:402-437) — with the framework's actual
mechanism: `initialize_distributed` (parallel/mesh.py) wiring jax.distributed
so a mesh spans processes and XLA collectives cross the process boundary
(DCN-equivalent). Run via the parent test, never directly by pytest.

Prints two RESULT lines the parent asserts on:
  RESULT sum <pid> <global sum>          — data collective across processes
  RESULT model <pid> <csv of local out>  — iris_mlp forward, batch sharded
"""

import sys

import jax

# the launcher pins JAX_PLATFORMS=cpu; the CPU collectives backend has no
# env var and must be chosen before any backend init
jax.config.update("jax_cpu_collectives_implementation", "gloo")

from seldon_core_tpu.parallel.mesh import initialize_distributed  # noqa: E402

initialize_distributed()  # reads JAX_COORDINATOR_ADDRESS/_NUM_PROCESSES/_ID

import numpy as np  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402


def main() -> None:
    pid = jax.process_index()
    devs = jax.devices()  # GLOBAL device list across all processes
    n = len(devs)
    assert jax.process_count() >= 2, "test requires a real multi-process run"
    mesh = Mesh(np.asarray(devs).reshape(n), ("data",))
    shard = NamedSharding(mesh, P("data"))

    # --- leg 1: one data-axis collective crossing the process boundary.
    # Each process holds only ITS half of the batch; the jitted global sum
    # is correct only if the psum actually crossed processes.
    global_shape = (2 * n, 4)
    full = np.arange(np.prod(global_shape), dtype=np.float32).reshape(global_shape)
    rows_per_proc = global_shape[0] // jax.process_count()
    local = full[pid * rows_per_proc : (pid + 1) * rows_per_proc]
    arr = jax.make_array_from_process_local_data(shard, local, global_shape)

    @jax.jit
    def global_sum(x):
        return jnp.sum(x * 2.0 + 1.0)

    print(f"RESULT sum {pid} {float(global_sum(arr))!r}", flush=True)

    # --- leg 2: the serving math — a zoo model forward with the batch
    # sharded over both processes, params replicated (deterministic same-seed
    # build per process, the way every replica boots from the same CR).
    from seldon_core_tpu.models.zoo import get_model

    ms = get_model("iris_mlp", seed=3)
    params = jax.device_put(ms.params, NamedSharding(mesh, P()))
    x_full = np.linspace(-1.0, 1.0, global_shape[0] * 4, dtype=np.float32).reshape(
        global_shape[0], 4
    )
    x_local = x_full[pid * rows_per_proc : (pid + 1) * rows_per_proc]
    x = jax.make_array_from_process_local_data(shard, x_local, x_full.shape)

    fwd = jax.jit(ms.apply_fn, out_shardings=shard)
    out = fwd(params, x)
    # each process reports its addressable rows; the parent stitches and
    # compares against the single-process forward
    local_rows = np.concatenate(
        [np.asarray(s.data) for s in sorted(out.addressable_shards, key=lambda s: s.index[0].start or 0)]
    )
    flat = ",".join(f"{v:.6f}" for v in local_rows.ravel())
    print(f"RESULT model {pid} {flat}", flush=True)


if __name__ == "__main__":
    main()
