"""shard_map distributed embedding lookup + lm_head (§Perf OPT4).

Why: under GSPMD, the VJP of a plain gather into a (vocab, d_model)-sharded
table is a scatter-add whose output the partitioner materializes REPLICATED
(then reshards) — on llama3-405b train_4k that is 2x 8.4 GB f32 of
replicated embedding/lm_head gradients living in the microbatch-loop state
(measured; EXPERIMENTS.md §Perf iteration 3). Writing the lookup/projection
as shard_map makes the gradients SHARDED BY CONSTRUCTION:

  lookup:  each vocab shard all-gathers its table slice's d_model shards
           (small: |V|/16 x D), serves the tokens it owns, psum over the
           vocab axis. Transpose: local scatter-add into the shard's rows +
           reduce-scatter of the d_model gather — grads arrive (V/16, D/16).
  lm_head: gather W's d_model shards -> local (D, V/16) matmul -> logits
           vocab-sharded, NO psum. Transpose reduce-scatters dW.

Falls back to plain gather/matmul when no mesh context is installed (CPU
smoke tests, single-device serving).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as shd


def _batch_spec(rules, mesh, batch_dim: int):
    """Batch spec, dropped to replicated when the batch doesn't divide the
    mesh axes (long_500k decodes with global_batch=1)."""
    b = rules.get("batch")
    if b is None:
        return None
    axes = (b,) if isinstance(b, str) else b
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return b if batch_dim % n == 0 else None


def embed_lookup(tokens: jax.Array, table: jax.Array) -> jax.Array:
    """tokens (B, S) int32, table (V, D) -> (B, S, D).

    Distributed path when a mesh context is installed: table sharded
    (vocab -> model, embed -> data|None per the rules)."""
    mesh = shd.current_mesh()
    rules = shd.current_rules()
    if mesh is None or rules is None or "model" not in mesh.axis_names:
        return table[tokens]
    v_axis = rules.get("vocab")
    d_axis = rules.get("embed")
    if v_axis is None:
        return table[tokens]
    b_axis = _batch_spec(rules, mesh, tokens.shape[0])
    V = table.shape[0]
    n_v = mesh.shape[v_axis] if isinstance(v_axis, str) else 1
    if V % n_v != 0:
        return table[tokens]
    v_shard = V // n_v

    def local(tok, tab):
        # tab: (V/nv, D/nd) -> gather D so each vocab shard holds full rows
        if d_axis is not None:
            tab = jax.lax.all_gather(tab, d_axis, axis=1, tiled=True)
        lo = jax.lax.axis_index(v_axis) * v_shard
        rel = tok - lo
        ok = (rel >= 0) & (rel < v_shard)
        x = tab[jnp.clip(rel, 0, v_shard - 1)] * ok[..., None].astype(tab.dtype)
        return jax.lax.psum(x, v_axis)

    return shard_map(
        local,
        mesh=mesh,
        in_specs=(P(b_axis, None), P(v_axis, d_axis)),
        out_specs=P(b_axis, None, None),
        check_vma=False,
    )(tokens, table)


def lm_head(x: jax.Array, w: jax.Array) -> jax.Array:
    """x (B, S, D) @ w (D, V) -> logits (B, S, V) f32, vocab-sharded.

    Distributed path: w sharded (embed -> data|None, vocab -> model)."""
    mesh = shd.current_mesh()
    rules = shd.current_rules()
    if mesh is None or rules is None or "model" not in mesh.axis_names:
        return (x @ w).astype(jnp.float32)
    v_axis = rules.get("vocab")
    d_axis = rules.get("embed")
    if v_axis is None or w.shape[1] % mesh.shape[v_axis] != 0:
        return (x @ w).astype(jnp.float32)
    b_axis = _batch_spec(rules, mesh, x.shape[0])

    def local(xl, wl):
        if d_axis is not None:
            wl = jax.lax.all_gather(wl, d_axis, axis=0, tiled=True)
        return (xl @ wl).astype(jnp.float32)  # (B/., S, V/nv) — no psum

    return shard_map(
        local,
        mesh=mesh,
        in_specs=(P(b_axis, None, None), P(d_axis, v_axis)),
        out_specs=P(b_axis, None, v_axis),
        check_vma=False,
    )(x, w)
