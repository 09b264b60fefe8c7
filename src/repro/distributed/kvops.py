"""Sequence-sharded KV-cache writes (§Perf OPT5).

GSPMD lowers a dynamic_update_slice at a traced position into a
select(broadcast(pred)) over the ENTIRE buffer when the updated dim is
sharded (it cannot prove which shard owns the write) — measured on
zamba2-7b long_500k decode as a full-cache f32 copy + a full-cache pred
mask (+11 GB/chip on a 6 GB cache; EXPERIMENTS.md §Perf). This module
writes the token row with an ownership check INSIDE shard_map: each seq
shard compares the write position against its own range and does a local,
tiny read-modify-write. No masks, no full-buffer copies.

Falls back to plain indexed update when no mesh context is installed or
the seq dim is not sharded.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as shd


def _b_axis(rules, mesh, b: int):
    ax = rules.get("batch")
    if ax is None:
        return None
    axes = (ax,) if isinstance(ax, str) else ax
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return ax if b % n == 0 else None


def cache_write(buf: jax.Array, val: jax.Array, layer: jax.Array, pos) -> jax.Array:
    """buf (L, B, S, ...) with S possibly sharded; val (B, s_new, ...);
    layer scalar i; pos scalar write offset. Returns updated buf."""
    mesh = shd.current_mesh()
    rules = shd.current_rules()
    s_new = val.shape[1]
    fallback = lambda: jax.lax.dynamic_update_slice(
        buf, val[None].astype(buf.dtype), (layer, 0, pos) + (0,) * (buf.ndim - 3)
    )
    if (
        mesh is None
        or rules is None
        or jnp.ndim(pos) != 0
        or rules.get("kv_seq") not in mesh.axis_names
    ):
        return fallback()
    seq_axis = rules["kv_seq"]
    n = mesh.shape[seq_axis]
    S = buf.shape[2]
    if S % n != 0 or S // n < s_new:
        return fallback()
    local_len = S // n
    b_ax = _b_axis(rules, mesh, buf.shape[1])

    def local(buf_l, val_l, i, p):
        lo = jax.lax.axis_index(seq_axis).astype(p.dtype) * local_len
        rel = p - lo
        ok = (rel >= 0) & (rel <= local_len - s_new)
        relc = jnp.clip(rel, 0, local_len - s_new)
        start = (i, 0, relc) + (0,) * (buf_l.ndim - 3)
        sizes = (1, val_l.shape[0], s_new) + buf_l.shape[3:]
        cur = jax.lax.dynamic_slice(buf_l, start, sizes)
        new = jnp.where(ok, val_l[None].astype(buf_l.dtype), cur)
        return jax.lax.dynamic_update_slice(buf_l, new, start)

    spec_buf = P(None, b_ax, seq_axis, *([None] * (buf.ndim - 3)))
    spec_val = P(b_ax, *([None] * (val.ndim - 1)))
    return shard_map(
        local,
        mesh=mesh,
        in_specs=(spec_buf, spec_val, P(), P()),
        out_specs=spec_buf,
        check_vma=False,
    )(buf, val, layer, pos)
