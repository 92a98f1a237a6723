"""Thin wrappers over the jax parallelism APIs this repo uses.

The repo targets one jax release (pinned in ``requirements-ci.txt``);
these helpers fix the options every call site wants — all-Auto mesh
axes, unchecked-replication ``shard_map``, the gloo CPU transport — so
parallelism code states them once.
"""

from __future__ import annotations

import jax


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with all-Auto axis types."""
    return jax.make_mesh(
        axis_shapes, axis_names, devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


def shard_map(f, *, mesh, in_specs, out_specs):
    """Unchecked-replication ``jax.shard_map``."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def set_mesh(mesh):
    """Context manager installing ``mesh`` as the ambient mesh."""
    return jax.set_mesh(mesh)


def ambient_mesh():
    """The mesh installed by :func:`set_mesh`, or None when unset."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def distributed_initialize(coordinator_address: str, num_processes: int,
                           process_id: int) -> None:
    """Multi-process (multi-host) runtime init that works on CPU.

    ``jax.distributed.initialize`` alone is not enough on the CPU
    backend: without a CPU collectives implementation every cross-process
    computation fails with "Multiprocess computations aren't implemented
    on the CPU backend".  This selects the gloo transport first (real
    accelerator backends ignore it) and then initializes the distributed
    runtime, so the same launch code drives a CPU test fleet and a TPU
    pod.  The coordinator, process count and id are always explicit: a
    machine without a metadata server cannot look them up.

    Must run BEFORE any jax computation; per-process device counts (e.g.
    ``--xla_force_host_platform_device_count``) must already be in
    XLA_FLAGS.  Raises whatever ``jax.distributed.initialize`` raises —
    callers treating multi-process support as optional should catch and
    skip.
    """
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)


def distributed_shutdown() -> None:
    """Tear down the distributed runtime; a no-op when never initialized."""
    try:
        jax.distributed.shutdown()
    except RuntimeError:
        pass
