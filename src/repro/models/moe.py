"""Mixture-of-Experts layers (deepseek-moe-16b: 2 shared + 64 routed top-6
fine-grained; olmoe-1b-7b: 64 routed top-8).

Dispatch uses sort-based grouping with a fixed per-expert capacity
(dropped-token MoE): static shapes for jit, experts sharded over the
'experts' logical axis (→ 'model' mesh axis). The router's top-k mask is the
paper's "enable map" at tile granularity — routing IS activation gating
(DESIGN.md §4): experts only compute on tokens whose gate is nonzero, the
MoE analogue of the gated one-to-all product's zero-activation gating.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import LMConfig
from repro.models import layers as L


def moe_init(key, cfg: LMConfig) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    dt = cfg.param_dtype
    ks = jax.random.split(key, 5)
    p = {
        "router": L._init(ks[0], (d, e), jnp.float32),  # router math in f32
        "experts": {
            "wi": L._init(ks[1], (e, d, f), dt),
            "wg": L._init(ks[2], (e, d, f), dt),
            "wo": L._init(ks[3], (e, f, d), dt),
        },
    }
    if cfg.n_shared_experts:
        p["shared"] = L.mlp_init(ks[4], cfg, d_ff=f * cfg.n_shared_experts)
    return p


def moe_axes(cfg: LMConfig) -> dict:
    a = {
        "router": ("embed", None),
        # experts shard over 'model'; the per-expert FFN dims get their own
        # logical axis (expert_mlp -> replicated) — two dims of one tensor
        # cannot both land on the 'model' mesh axis
        "experts": {
            "wi": ("experts", "embed", "expert_mlp"),
            "wg": ("experts", "embed", "expert_mlp"),
            "wo": ("experts", "expert_mlp", "embed"),
        },
    }
    if cfg.n_shared_experts:
        a["shared"] = L.mlp_axes(cfg)
    return a


def _capacity(n_tokens: int, cfg: LMConfig) -> int:
    cap = int(np.ceil(cfg.top_k * n_tokens / cfg.n_experts * cfg.capacity_factor))
    return max(cap, 8)


def route(x2d: jax.Array, router_w: jax.Array, cfg: LMConfig):
    """x2d (T, D) → (expert_ids (T,k), gates (T,k), aux_loss)."""
    logits = (x2d.astype(jnp.float32) @ router_w).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, cfg.top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    # load-balance auxiliary loss (Switch-style)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(ids, cfg.n_experts, dtype=jnp.float32), axis=1), axis=0
    ) / cfg.top_k
    aux = cfg.n_experts * jnp.sum(me * ce)
    return ids, gates, aux


def dispatch_group(ids: jax.Array, n_tokens: int, cfg: LMConfig):
    """Sort-based grouping. ids (T, k) → per-slot token index (E*C,) and a
    validity/gate-slot map back to (T, k)."""
    E, k = cfg.n_experts, cfg.top_k
    C = _capacity(n_tokens, cfg)
    flat_e = ids.reshape(-1)  # (T*k,)
    flat_tok = jnp.repeat(jnp.arange(n_tokens), k)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    sorted_tok = flat_tok[order]
    # rank of each entry within its expert group
    group_start = jnp.searchsorted(sorted_e, jnp.arange(E))
    rank = jnp.arange(n_tokens * k) - group_start[sorted_e]
    keep = rank < C
    slot = sorted_e * C + rank  # destination slot in (E*C)
    slot = jnp.where(keep, slot, E * C)  # overflow bucket
    slot_token = jnp.full((E * C + 1,), n_tokens, jnp.int32)  # n_tokens = pad row
    slot_token = slot_token.at[slot].set(sorted_tok.astype(jnp.int32))
    # map back: for each (token, k) entry, which slot served it (or -1)
    entry_slot = jnp.full((n_tokens * k,), -1, jnp.int32)
    entry_slot = entry_slot.at[order].set(jnp.where(keep, slot, -1).astype(jnp.int32))
    return slot_token[: E * C], entry_slot.reshape(n_tokens, k), C


def moe_mlp(x: jax.Array, p: dict, cfg: LMConfig):
    """x (B, S, D) → (out, aux_loss)."""
    b, s, d = x.shape
    t = b * s
    x2d = x.reshape(t, d)
    ids, gates, aux = route(x2d, p["router"], cfg)
    slot_token, entry_slot, C = dispatch_group(ids, t, cfg)

    x_pad = jnp.concatenate([x2d, jnp.zeros((1, d), x2d.dtype)], axis=0)
    grouped = x_pad[slot_token].reshape(cfg.n_experts, C, d)  # (E, C, D)

    ew = p["experts"]
    hg = jnp.einsum("ecd,edf->ecf", grouped, ew["wg"])
    hi = jnp.einsum("ecd,edf->ecf", grouped, ew["wi"])
    ho = jnp.einsum("ecf,efd->ecd", jax.nn.silu(hg) * hi, ew["wo"])  # (E, C, D)
    ho_flat = ho.reshape(cfg.n_experts * C, d)

    # combine: each (token, k) entry pulls its slot's output, scaled by gate
    safe_slot = jnp.maximum(entry_slot, 0)
    pulled = ho_flat[safe_slot]  # (T, k, D)
    valid = (entry_slot >= 0).astype(pulled.dtype)[..., None]
    out = jnp.sum(pulled * valid * gates[..., None].astype(pulled.dtype), axis=1)

    if cfg.n_shared_experts:
        out = out + L.mlp(x2d, p["shared"])
    return out.reshape(b, s, d).astype(x.dtype), aux


def moe_layer_init(key, cfg: LMConfig) -> dict:
    k1, k2 = jax.random.split(key)
    return {
        "attn": L.attn_init(k1, cfg),
        "moe": moe_init(k2, cfg),
        "ln1": jnp.ones((cfg.d_model,), cfg.param_dtype),
        "ln2": jnp.ones((cfg.d_model,), cfg.param_dtype),
    }


def moe_layer_axes(cfg: LMConfig) -> dict:
    return {
        "attn": L.attn_axes(cfg),
        "moe": moe_axes(cfg),
        "ln1": (None,),
        "ln2": (None,),
    }


def moe_block(x, lp, cfg: LMConfig, *, positions, kv=None, cache_pos=None, causal=True):
    h, new_kv = L.attention(
        L.rmsnorm(x, lp["ln1"], cfg.norm_eps),
        lp["attn"],
        cfg,
        positions=positions,
        causal=causal,
        kv_cache=kv,
        cache_pos=cache_pos,
    )
    x = x + h
    mo, _aux = moe_mlp(L.rmsnorm(x, lp["ln2"], cfg.norm_eps), lp["moe"], cfg)
    return x + mo, new_kv


# ----------------------------------------- expert parallelism (§Perf OPT6) --
# The jnp-level moe_mlp above lets GSPMD distribute the dispatch gather,
# which materializes an all-gather of EVERY token on EVERY expert shard
# (T x D bytes x model-axis). But with tokens sharded over 'data' and
# experts over 'model', each device ALREADY holds (its tokens x its
# experts): the only communication MoE fundamentally needs is the combine
# reduction over the expert axis. This shard_map version does exactly
# that — local routing, local dispatch restricted to the shard's experts,
# local expert FFNs, then one psum('model') of the (T_local, D) output:
# per-device collective bytes drop from T*D (gather) to T_local*D (psum).


def _dispatch_group_masked(ids, keep_entry, n_tokens: int, n_experts: int,
                           top_k: int, capacity: int):
    """dispatch_group over a LOCAL expert range: entries with
    keep_entry=False (expert lives on another shard) are dropped."""
    E, k, C = n_experts, top_k, capacity
    flat_e = jnp.where(keep_entry.reshape(-1), ids.reshape(-1), E)  # E = drop
    flat_tok = jnp.repeat(jnp.arange(n_tokens), k)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    sorted_tok = flat_tok[order]
    group_start = jnp.searchsorted(sorted_e, jnp.arange(E))
    rank = jnp.arange(n_tokens * k) - group_start[jnp.clip(sorted_e, 0, E - 1)]
    keep = (rank < C) & (sorted_e < E)
    slot = jnp.where(keep, sorted_e * C + rank, E * C)
    slot_token = jnp.full((E * C + 1,), n_tokens, jnp.int32)
    slot_token = slot_token.at[slot].set(sorted_tok.astype(jnp.int32))
    entry_slot = jnp.full((n_tokens * k,), -1, jnp.int32)
    entry_slot = entry_slot.at[order].set(jnp.where(keep, slot, -1).astype(jnp.int32))
    return slot_token[: E * C], entry_slot.reshape(n_tokens, k)


def moe_mlp_ep(x: jax.Array, p: dict, cfg: LMConfig):
    """Expert-parallel moe_mlp. Falls back to moe_mlp when no mesh context
    (CPU tests / single device) or the shapes don't divide the mesh."""
    from repro.distributed import sharding as shd

    mesh = shd.current_mesh()
    rules = shd.current_rules()
    b, s, d = x.shape
    t = b * s
    e_axis = rules.get("experts") if rules else None
    if (
        mesh is None
        or e_axis not in getattr(mesh, "axis_names", ())
        or cfg.n_experts % mesh.shape[e_axis] != 0
    ):
        return moe_mlp(x, p, cfg)
    batch_rule = rules.get("batch")
    b_axes = (batch_rule,) if isinstance(batch_rule, str) else (batch_rule or ())
    n_data = 1
    for a in b_axes:
        n_data *= mesh.shape[a]
    if t % max(n_data, 1) != 0:
        return moe_mlp(x, p, cfg)
    M = mesh.shape[e_axis]
    E_l = cfg.n_experts // M
    t_l = t // max(n_data, 1)
    C = max(int(np.ceil(cfg.top_k * t_l / cfg.n_experts * cfg.capacity_factor)), 8)

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def local(x2d, router_w, wi, wg, wo, shared):
        ids, gates, aux = route(x2d, router_w, cfg)  # local tokens, all E
        m_idx = jax.lax.axis_index(e_axis)
        lo = m_idx * E_l
        keep = (ids >= lo) & (ids < lo + E_l)
        slot_token, entry_slot = _dispatch_group_masked(
            ids - lo, keep, x2d.shape[0], E_l, cfg.top_k, C
        )
        x_pad = jnp.concatenate([x2d, jnp.zeros((1, d), x2d.dtype)], axis=0)
        grouped = x_pad[slot_token].reshape(E_l, C, d)
        hg = jnp.einsum("ecd,edf->ecf", grouped, wg)
        hi = jnp.einsum("ecd,edf->ecf", grouped, wi)
        ho = jnp.einsum("ecf,efd->ecd", jax.nn.silu(hg) * hi, wo)
        ho_flat = ho.reshape(E_l * C, d)
        safe = jnp.maximum(entry_slot, 0)
        pulled = ho_flat[safe]
        valid = (entry_slot >= 0).astype(pulled.dtype)[..., None]
        out = jnp.sum(pulled * valid * gates[..., None].astype(pulled.dtype), axis=1)
        out = jax.lax.psum(out, e_axis)  # combine across expert shards
        if cfg.n_shared_experts:
            out = out + L.mlp(x2d, shared)
        aux = jax.lax.pmean(aux, e_axis)
        return out, aux

    bspec = batch_rule
    shared_p = p.get("shared")
    shared_specs = jax.tree_util.tree_map(lambda _: P(None, None), shared_p) if shared_p else None
    out2d, aux = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(bspec, None), P(None, None), P(e_axis, None, None),
                  P(e_axis, None, None), P(e_axis, None, None), shared_specs),
        out_specs=(P(bspec, None), P()),
        check_vma=False,
    )(x.reshape(t, d), p["router"], p["experts"]["wi"], p["experts"]["wg"],
      p["experts"]["wo"], shared_p)
    return out2d.reshape(b, s, d).astype(x.dtype), aux


def moe_block_ep(x, lp, cfg: LMConfig, *, positions, kv=None, cache_pos=None, causal=True):
    h, new_kv = L.attention(
        L.rmsnorm(x, lp["ln1"], cfg.norm_eps),
        lp["attn"],
        cfg,
        positions=positions,
        causal=causal,
        kv_cache=kv,
        cache_pos=cache_pos,
    )
    x = x + h
    mo, _aux = moe_mlp_ep(L.rmsnorm(x, lp["ln2"], cfg.norm_eps), lp["moe"], cfg)
    return x + mo, new_kv
