"""Production mesh construction.

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state (smoke tests and benches must keep seeing the
single real CPU device; only launch/dryrun.py requests 512 placeholder
host devices via XLA_FLAGS before any jax import).

Both constructors route through the :class:`DistributedContext`, so under
a multi-controller launch the mesh axes span EVERY host's devices — not
just ``jax.local_devices()`` — and shardings built on them address the
whole job."""
from __future__ import annotations

from repro.distributed import runtime
from repro.distributed.meshes import make_mesh


def make_production_mesh(*, multi_pod: bool = False, ctx=None):
    """Single pod: (data=16, model=16) over 256 chips (one TPU v5e pod).
    Multi-pod: (pod=2, data=16, model=16) over 512 chips — the 'pod' axis
    composes with 'data' for hierarchical gradient reduction (DCN hop)."""
    ctx = ctx or runtime.get_context()
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes, devices=ctx.global_devices)


def make_host_mesh(n_data: int = 1, n_model: int = 1, *, ctx=None):
    """Tiny mesh over the job's devices (tests/examples). Multi-controller:
    the data axis crosses process boundaries, so a (n_hosts, 1) mesh from a
    2-process CPU launch really sees both hosts' devices."""
    ctx = ctx or runtime.get_context()
    return make_mesh((n_data, n_model), ("data", "model"), devices=ctx.global_devices)
