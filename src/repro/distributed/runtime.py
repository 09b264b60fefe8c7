"""Multi-controller runtime: one context object owns the process topology.

Every layer that used to hardcode single-controller assumptions (eval
sharding, the training launcher, checkpointing, mesh construction) consumes
a :class:`DistributedContext` instead of calling ``jax.process_*`` or
``jax.local_devices()`` ad hoc. The context owns

* ``(host_id, n_hosts)`` — this process's coordinates,
* the **global mesh** accessors (:meth:`data_mesh` over every device in
  the job, :meth:`stripe_mesh` with exactly one device per host — the mesh
  the cross-host eval reduction runs over),
* the **local devices** this process can address,
* the **striping contract**: :meth:`owned_shards` makes process ``i`` own
  shards ``i, i+P, i+2P, ...`` — the same interleaving
  ``synthetic_detection.batches(host_id, n_hosts)`` and
  ``lm_data.batch_at(host_id, n_hosts)`` already use for data, so shard
  ownership and data ownership follow ONE contract.

Construction: :func:`initialize` wires ``jax.distributed.initialize`` when
launched as one process of a multi-process job (enabling the gloo CPU
collectives backend first, so ``JAX_PLATFORMS=cpu`` jobs get REAL
cross-process collectives); without a coordinator it degrades to the
single-host identity context ``(host_id=0, n_hosts=1)`` and every consumer
behaves exactly as before. :func:`get_context` returns the process-wide
context, deriving the identity context on first use if :func:`initialize`
was never called.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Sequence

import numpy as np


def _enable_cpu_collectives() -> None:
    """Select the gloo CPU collectives backend — REQUIRED before the first
    backend touch, or multi-process ``JAX_PLATFORMS=cpu`` jobs fail with
    "Multiprocess computations aren't implemented on the CPU backend".
    Harmless on accelerator backends."""
    import jax

    jax.config.update("jax_cpu_collectives_implementation", "gloo")


@dataclass(frozen=True)
class DistributedContext:
    """This process's coordinates in the job, plus mesh/ownership accessors.

    ``host_id``/``n_hosts`` mirror ``jax.process_index()`` /
    ``jax.process_count()``; the identity context is ``(0, 1)``.
    """

    host_id: int
    n_hosts: int

    def __post_init__(self):
        if not 0 <= self.host_id < self.n_hosts:
            raise ValueError(
                f"host_id {self.host_id} out of range for {self.n_hosts} hosts"
            )

    # ------------------------------------------------------------ devices --

    @property
    def is_multi_controller(self) -> bool:
        return self.n_hosts > 1

    @property
    def global_devices(self) -> tuple:
        """Every device in the job, across all hosts."""
        import jax

        return tuple(jax.devices())

    @property
    def local_devices(self) -> tuple:
        """The devices THIS process can address."""
        import jax

        return tuple(jax.local_devices())

    # ------------------------------------------------------------- meshes --

    def data_mesh(self, axis_name: str = "data"):
        """1-D mesh over ALL global devices — the batch axis of
        data-parallel training spans every host's devices."""
        import jax

        return jax.sharding.Mesh(np.asarray(self.global_devices), (axis_name,))

    def stripe_mesh(self, axis_name: str = "data"):
        """1-D mesh with exactly ONE device per host, ordered by host id —
        the mesh the cross-host eval-stat reduction runs over (each host
        contributes one padded row; the collective crosses process
        boundaries, unlike ``meshes.local_device_mesh``'s local subset)."""
        per_host: dict = {}
        for d in self.global_devices:
            per_host.setdefault(d.process_index, d)
        missing = [h for h in range(self.n_hosts) if h not in per_host]
        if missing:
            raise RuntimeError(
                f"no devices visible for hosts {missing} — was "
                "jax.distributed.initialize called on every process?"
            )
        devs = [per_host[h] for h in sorted(per_host)]
        import jax

        return jax.sharding.Mesh(np.asarray(devs), (axis_name,))

    # ---------------------------------------------------------- ownership --

    def owned_shards(self, n_shards: int) -> list:
        """Shard ids THIS host walks: ``host_id, host_id+P, ...`` — the
        ``batches(host_id, n_hosts)`` striping contract applied to shard
        ownership. Single-controller: every shard."""
        return list(range(self.host_id, n_shards, self.n_hosts))

    def validate_shard_count(self, n_shards: int) -> None:
        """Reject shard counts that don't divide evenly across hosts.

        The striping itself never duplicates work, but ``n_shards %
        n_hosts != 0`` silently skews it — some hosts walk one shard more
        than others, and an ``n_shards < n_hosts`` launch leaves whole
        hosts idle while looking healthy. Refuse loudly instead."""
        if self.is_multi_controller and (
            n_shards < self.n_hosts or n_shards % self.n_hosts != 0
        ):
            raise ValueError(
                f"n_shards={n_shards} does not stripe evenly over "
                f"{self.n_hosts} hosts — pass a multiple of n_hosts so "
                "every host owns the same number of shards (shard s "
                "belongs to host s % n_hosts)"
            )

    # --------------------------------------------------------- data plane --

    def global_batch(self, batch: Any, sharding) -> Any:
        """Assemble per-host local batches into dim-0-sharded GLOBAL
        arrays: host ``h`` contributes its local rows, the global leading
        dim is ``local_rows * n_hosts``. ``sharding`` must be a
        ``NamedSharding`` that partitions dim 0 over a mesh spanning every
        host (e.g. ``data_mesh``). Single-controller: plain device put."""
        import jax
        import jax.numpy as jnp

        if not self.is_multi_controller:
            return jax.tree_util.tree_map(jnp.asarray, batch)

        def put(x):
            x = np.asarray(x)
            global_shape = (x.shape[0] * self.n_hosts,) + x.shape[1:]
            return jax.make_array_from_process_local_data(
                sharding, x, global_shape
            )

        return jax.tree_util.tree_map(put, batch)

    def barrier(self, tag: str) -> None:
        """Block until every host reaches ``tag`` (no-op single-host)."""
        if self.is_multi_controller:
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices(tag)

    def describe(self) -> str:
        return f"host {self.host_id}/{self.n_hosts}"


# ------------------------------------------------------------ construction --

_CTX: Optional[DistributedContext] = None


def initialize(
    *,
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> DistributedContext:
    """Build (and install as process-wide) the runtime context.

    With a ``coordinator_address`` (``host:port``): enables the CPU
    collectives backend, calls ``jax.distributed.initialize`` and returns
    the real multi-controller context. Without one: the identity context.
    Call BEFORE any other jax backend use (device queries included) —
    jax.distributed can only initialize against an untouched backend.
    """
    global _CTX
    import jax

    if coordinator_address is not None:
        _enable_cpu_collectives()
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    _CTX = DistributedContext(
        host_id=jax.process_index(), n_hosts=jax.process_count()
    )
    return _CTX


def get_context() -> DistributedContext:
    """The process-wide context; derives the live (usually identity)
    context from jax process state if :func:`initialize` was never called."""
    global _CTX
    if _CTX is None:
        import jax

        _CTX = DistributedContext(
            host_id=jax.process_index(), n_hosts=jax.process_count()
        )
    return _CTX
