"""Collective-communication helpers for the 1000+ node posture.

Three tools, all shard_map-based so the HLO carries REAL collectives that
the roofline parser (benchmarks/roofline.py) can account:

* ``int8_psum`` — int8 error-feedback gradient all-reduce: quantize the
  local shard, reduce-scatter int8 payloads (4x fewer bytes on the wire
  than f32), dequantize + sum locally, all-gather int8 results. The
  paper's FXP8 philosophy applied to the DP collective.
* ``hierarchical_psum`` — reduce-scatter within the pod ('data'), then
  all-reduce the pod-partials over the 'pod' axis (DCN), then all-gather
  within the pod. Moves the slow inter-pod hop to 1/N_data of the bytes.
* ``overlap_allgather_matmul`` — the classic collective-matmul pattern:
  x sharded on the contraction dim, one shard's matmul is computed per
  step while the next shard is being collective-permuted in — compute
  hides the ICI latency. XLA's latency-hiding scheduler does this
  automatically for simple cases; the explicit version is for the §Perf
  loop where we control the schedule.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# ------------------------------------------------------------- int8 psum --


def _q8(x, axis=-1):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    q = jnp.round(x / jnp.maximum(scale, 1e-12)).astype(jnp.int8)
    return q, scale


def int8_psum(mesh: Mesh, axis_name: str = "data"):
    """Returns f(x_local) that all-reduces a REPLICATED-shape f32 array over
    `axis_name` while moving int8 on the wire. Inside shard_map:
    quantize → all_to_all (scatter blocks) → local f32 sum → quantize →
    all_gather. Error relative to exact psum is bounded by 2 rounding steps
    (~1e-2 relative; error feedback at the optimizer absorbs it)."""
    n = mesh.shape[axis_name]

    def inner(x):
        orig_shape = x.shape
        flat = x.reshape(-1)
        pad = (-flat.size) % n
        flat = jnp.pad(flat, (0, pad)).reshape(n, -1)
        q, s = _q8(flat)  # per-row scale
        # reduce_scatter: row i of every peer lands on peer i
        qs = jax.lax.all_to_all(q[:, None], axis_name, 0, 0)[:, 0]
        ss = jax.lax.all_to_all(s[:, None], axis_name, 0, 0)[:, 0]
        local = jnp.sum(qs.astype(jnp.float32) * ss, axis=0)  # exact f32 sum
        q2, s2 = _q8(local[None])
        qg = jax.lax.all_gather(q2[0], axis_name)
        sg = jax.lax.all_gather(s2[0], axis_name)
        out = (qg.astype(jnp.float32) * sg).reshape(-1)
        return out[: int(np.prod(orig_shape))].reshape(orig_shape)

    spec = P()  # replicated in/out; the wire format is the int8 payload
    other = tuple(a for a in mesh.axis_names if a != axis_name)
    return shard_map(
        inner, mesh=mesh, in_specs=spec, out_specs=spec,
        check_vma=False,
    )


# ------------------------------------------------------ hierarchical psum --


def hierarchical_psum(mesh: Mesh):
    """psum over ('pod', 'data') done as reduce_scatter(data) →
    psum(pod) → all_gather(data): the inter-pod (DCN) hop moves 1/N_data
    of the bytes. Input/output replicated over both axes."""
    assert "pod" in mesh.axis_names, "hierarchical psum needs a multi-pod mesh"
    nd = mesh.shape["data"]

    def inner(x):
        orig_shape = x.shape
        flat = x.reshape(-1)
        pad = (-flat.size) % nd
        flat = jnp.pad(flat, (0, pad)).reshape(nd, -1)
        mine = jax.lax.all_to_all(flat[:, None], "data", 0, 0)[:, 0]
        part = jnp.sum(mine, axis=0)  # my 1/nd slice, summed intra-pod
        part = jax.lax.psum(part, "pod")  # DCN hop on the slice only
        out = jax.lax.all_gather(part, "data").reshape(-1)
        return out[: int(np.prod(orig_shape))].reshape(orig_shape)

    return shard_map(inner, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)


# ------------------------------------------------- eval-stat all-gather --


def eval_stats_allgather(mesh: Mesh, axis_name: str = "data"):
    """The sharded-mAP reduction: every shard holds one padded row of
    per-prediction match statistics (global image index, class, score, TP
    flag, valid mask — any dict of equal-leading-dim arrays) plus its local
    per-class ground-truth counts. Returns ``f(rows, counts) ->
    (gathered_rows, total_counts)`` where ``rows`` leaves are (k, cap)
    arrays sharded over ``axis_name`` (one shard per device), gathered back
    replicated, and ``counts`` is (k, C) sharded the same way and
    all-reduced with an exact integer psum.

    This is the collective `repro.eval.sharded` pools through before the AP
    sweep: all_gather moves the (score, TP) lists, psum moves the recall
    denominators — both exact (int / bit-preserved payloads), so the pooled
    PR curve is bit-identical to the single-host evaluation."""

    def inner(rows, counts):
        g = jax.tree_util.tree_map(
            lambda r: jax.lax.all_gather(r, axis_name, axis=0, tiled=True), rows
        )
        total = jax.lax.psum(counts, axis_name)[0]
        return g, total

    return shard_map(
        inner,
        mesh=mesh,
        in_specs=(P(axis_name), P(axis_name)),
        out_specs=(P(), P()),
        check_vma=False,
    )


# ------------------------------------------- all-gather/matmul overlapping --


def overlap_allgather_matmul(mesh: Mesh, axis_name: str = "model"):
    """y = x @ w with w row-sharded over `axis_name`: per step, matmul the
    resident shard while collective-permuting the next one in (bidirectional
    ring). Equivalent to all_gather(w) @ — but the gather is hidden behind
    the MXU. Returns f(x, w_sharded)->(y replicated)."""
    n = mesh.shape[axis_name]

    def inner(x, w):
        # x: (m, k_local * n) replicated; w: (k_local, out) local shard
        k_local = w.shape[0]
        idx = jax.lax.axis_index(axis_name)

        def step(carry, i):
            acc, w_cur = carry
            src = (idx - i) % n  # whose shard we hold at step i
            xs = jax.lax.dynamic_slice_in_dim(x, src * k_local, k_local, axis=1)
            acc = acc + xs @ w_cur
            w_nxt = jax.lax.ppermute(
                w_cur, axis_name, [(j, (j + 1) % n) for j in range(n)]
            )
            return (acc, w_nxt), None

        acc0 = jnp.zeros((x.shape[0], w.shape[1]), w.dtype)
        (acc, _), _ = jax.lax.scan(step, (acc0, w), jnp.arange(n))
        return jax.lax.psum(acc, axis_name) / n  # replicas agree; psum folds them
        # NB: every rank computed the FULL sum (each saw all shards), so the
        # psum/n is a consistency fold, not part of the math.

    return shard_map(
        inner,
        mesh=mesh,
        in_specs=(P(), P(axis_name, None)),
        out_specs=P(),
        check_vma=False,
    )
