"""Bitmask-compressed sparse-weight matmul — the paper's weight format
(§III-B.2) applied to transformer FFN layers.

W (K, N) with fine-grained pruning is stored in HBM as {bit-packed mask,
packed nonzero values}; the kernel decodes each (KBLK, NBLK) tile in VMEM
and feeds the MXU. HBM weight traffic = compressed bytes — for a
memory-bound decode/serving step this directly shrinks the roofline memory
term by (1 − density) · 8/9-ish, mirroring the paper's −59.1% DRAM claim.

Grid (n, m, k): k innermost so the f32 accumulator tile stays in VMEM
scratch until the K reduction completes.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import auto_interpret, refuse_compiled_decoder


class PackedMatmulWeights(NamedTuple):
    maskp: jax.Array  # (KB, NB, KBLK//8, NBLK) uint8, bits packed over K
    vals: jax.Array  # (KB, NB, VPAD) — same dtype as original weights
    shape: tuple  # (K, N) original
    kblk: int
    nblk: int

    @property
    def compressed_bytes(self) -> int:
        return self.maskp.size + self.vals.size * self.vals.dtype.itemsize


def pack_weights(w: np.ndarray, *, kblk: int = 512, nblk: int = 256) -> PackedMatmulWeights:
    w = np.asarray(w)
    k, n = w.shape
    k_p = (k + kblk - 1) // kblk * kblk
    n_p = (n + nblk - 1) // nblk * nblk
    wp = np.zeros((k_p, n_p), w.dtype)
    wp[:k, :n] = w
    kb_t, nb_t = k_p // kblk, n_p // nblk

    maskp = np.zeros((kb_t, nb_t, kblk // 8, nblk), np.uint8)
    vals_list = {}
    vpad = 1
    for kb in range(kb_t):
        for nb in range(nb_t):
            blk = wp[kb * kblk : (kb + 1) * kblk, nb * nblk : (nb + 1) * nblk]
            mask = (blk != 0).astype(np.uint8).reshape(kblk // 8, 8, nblk)
            for b in range(8):
                maskp[kb, nb] |= (mask[:, b, :] << b).astype(np.uint8)
            v = blk[blk != 0].ravel()
            vals_list[(kb, nb)] = v
            vpad = max(vpad, v.size)
    vals = np.zeros((kb_t, nb_t, vpad), w.dtype)
    for (kb, nb), v in vals_list.items():
        vals[kb, nb, : v.size] = v
    return PackedMatmulWeights(
        maskp=jnp.asarray(maskp), vals=jnp.asarray(vals), shape=(k, n), kblk=kblk, nblk=nblk
    )


def _kernel(x_ref, maskp_ref, vals_ref, out_ref, acc_ref, *, kb_total: int):
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # decode this (KBLK, NBLK) weight tile from the compressed form
    words = maskp_ref[0, 0]  # (KBLK//8, NBLK) uint8
    k8, nblk = words.shape
    expanded = jnp.repeat(words, 8, axis=0)  # (KBLK, NBLK)
    shifts = (jax.lax.broadcasted_iota(jnp.int32, (k8 * 8, nblk), 0) % 8).astype(jnp.uint8)
    bits = ((expanded >> shifts) & 1).astype(jnp.int32)
    flat = bits.reshape(-1)
    idx = jnp.cumsum(flat) - 1
    vals = vals_ref[0, 0]
    gathered = jnp.take(vals, jnp.clip(idx, 0, vals.shape[0] - 1))
    dense = jnp.where(flat > 0, gathered.astype(jnp.float32), 0.0).reshape(k8 * 8, nblk)

    acc_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.float32), dense, preferred_element_type=jnp.float32
    )

    @pl.when(kb == kb_total - 1)
    def _store():
        out_ref[...] = acc_ref[...]


def bitmask_matmul_pallas(
    x: jax.Array, packed: PackedMatmulWeights, *, mblk: int = 256, interpret: bool | None = None
) -> jax.Array:
    interpret = auto_interpret(interpret)
    refuse_compiled_decoder("bitmask_matmul_pallas", interpret)
    m, k = x.shape
    k_orig, n_orig = packed.shape
    assert k == k_orig, (k, k_orig)
    kblk, nblk = packed.kblk, packed.nblk
    kb_t = packed.maskp.shape[0]
    nb_t = packed.maskp.shape[1]
    m_p = (m + mblk - 1) // mblk * mblk
    k_p = kb_t * kblk
    if (m_p, k_p) != (m, k):
        x = jnp.pad(x, ((0, m_p - m), (0, k_p - k)))

    grid = (nb_t, m_p // mblk, kb_t)
    out = pl.pallas_call(
        functools.partial(_kernel, kb_total=kb_t),
        grid=grid,
        in_specs=[
            pl.BlockSpec((mblk, kblk), lambda nb, mb, kb: (mb, kb)),
            pl.BlockSpec((1, 1, kblk // 8, nblk), lambda nb, mb, kb: (kb, nb, 0, 0)),
            pl.BlockSpec((1, 1, packed.vals.shape[-1]), lambda nb, mb, kb: (kb, nb, 0)),
        ],
        out_specs=pl.BlockSpec((mblk, nblk), lambda nb, mb, kb: (mb, nb)),
        out_shape=jax.ShapeDtypeStruct((m_p, nb_t * nblk), jnp.float32),
        scratch_shapes=[pltpu.VMEM((mblk, nblk), jnp.float32)],
        interpret=interpret,
    )(x, packed.maskp, packed.vals)
    return out[:m, :n_orig]
