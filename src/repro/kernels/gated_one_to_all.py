"""Pallas TPU kernel for the gated one-to-all product (paper §III-B.1).

TPU-native reformulation of the ASIC dataflow
---------------------------------------------
The ASIC walks nonzero weights one per cycle, broadcasting each against a
576-neuron spatial tile ("one-to-all") with clock-gated accumulates. On TPU
the same decomposition groups by kernel TAP (the (r,c) position in the 3×3
window): for each tap,

    out[(y,x), k] += spikes_shifted_by_tap[(y,x), c] @ W[tap][c, k]

is a (BH·BW, C) × (C, K_BLK) MXU matmul. The sparsity mechanisms map as:

  * zero-WEIGHT skipping  → a tap whose (C × K_BLK) weight block is entirely
    zero is skipped via ``pl.when`` (block-granular analogue of the per-
    weight cycle skip; TPU is SIMD so element-level skip cannot win).
  * bit-mask compression  → weights live in HBM as {bit-packed mask,
    packed nonzero int8 values}; the kernel decodes them ONCE per K-block
    into VMEM scratch (grid order: K outer / spatial-block inner — the
    paper's KTBC order!) and reuses the decoded block across every spatial
    tile. HBM weight traffic is the COMPRESSED size, the paper's −59.1%.
  * zero-ACTIVATION gating → spikes are int8 {0,1}; the multiply itself
    gates, and activation storage is 1 byte (the ASIC used 1 bit; int8 is
    the TPU-native gateable width).
  * spatial parallelism   → one grid step computes an entire 32×18 block-
    convolution tile (576 outputs = the paper's 576 PEs), lanes/sublanes
    replacing the PE array.

Block convolution (paper §II-B) is inherited from the host-side layout: each
spatial tile arrives replicate-padded and independent, so the kernel never
communicates across tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import auto_interpret, refuse_compiled_decoder

# paper tile: 32 wide × 18 tall = 576 PEs
BLOCK_H = 18
BLOCK_W = 32


def _kernel(
    tap_any_ref,  # SMEM (1, taps) int32 — any nonzero weight at tap?
    spikes_ref,  # VMEM (1, BH+2p, BW+2p, C) int8
    maskp_ref,  # VMEM (1, taps, C // 8, KBLK) uint8 — bit-packed over C
    vals_ref,  # VMEM (1, VPAD) int8 — packed nonzero weights, this K-block
    out_ref,  # VMEM (1, BH, BW, KBLK) int32
    wdense_ref,  # scratch VMEM (taps, C, KBLK) int8 — decoded weights
    acc_ref,  # scratch VMEM (BH*BW, KBLK) int32
    *,
    taps: int,
    kh: int,
    kw: int,
    bh: int,
    bw: int,
):
    nb = pl.program_id(1)  # spatial tile index (innermost — weight reuse)

    # ---- decode compressed weights once per K-block (paper: weights stay
    # resident on-chip and are reused across every tile and time step) ----
    @pl.when(nb == 0)
    def _decode():
        words = maskp_ref[0]  # (taps, C//8, KBLK) uint8
        c8 = words.shape[1]
        kblk = words.shape[2]
        # unpack bits along the C axis: bit c lives in word c//8 at position c%8
        expanded = jnp.repeat(words, 8, axis=1)  # (taps, C, KBLK)
        shifts = (jax.lax.broadcasted_iota(jnp.int32, (taps, c8 * 8, kblk), 1) % 8).astype(
            jnp.uint8
        )
        bits = ((expanded >> shifts) & 1).astype(jnp.int32)
        flat = bits.reshape(-1)
        idx = jnp.cumsum(flat) - 1  # position into packed values
        vals = vals_ref[0]
        gathered = jnp.take(vals, jnp.clip(idx, 0, vals.shape[0] - 1), axis=0)
        dense = jnp.where(flat > 0, gathered.astype(jnp.int32), 0)
        wdense_ref[...] = dense.reshape(taps, c8 * 8, kblk).astype(jnp.int8)

    acc_ref[...] = jnp.zeros_like(acc_ref)

    # ---- per-tap gated one-to-all accumulation ----
    for tap in range(taps):
        r, c = tap // kw, tap % kw

        @pl.when(tap_any_ref[0, tap] > 0)  # zero-weight tap: skip entirely
        def _tap(tap=tap, r=r, c=c):
            window = spikes_ref[0, r : r + bh, c : c + bw, :]  # (BH, BW, C)
            s = window.reshape(bh * bw, window.shape[-1])
            w = wdense_ref[tap]  # (C, KBLK) int8
            acc_ref[...] += jax.lax.dot_general(
                s,
                w,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )

    out_ref[0] = acc_ref[...].reshape(bh, bw, acc_ref.shape[-1])


def gated_one_to_all_pallas(
    spike_blocks: jax.Array,  # (NB, BH+2p, BW+2p, C) int8, replicate-padded
    maskp: jax.Array,  # (KB, taps, C//8, KBLK) uint8
    vals: jax.Array,  # (KB, VPAD) int8
    tap_any: jax.Array,  # (KB, taps) int32
    *,
    kh: int,
    kw: int,
    bh: int = BLOCK_H,
    bw: int = BLOCK_W,
    kblk: int,
    interpret: bool | None = None,
) -> jax.Array:
    """Run the kernel. Returns (NB, BH, BW, KB*KBLK) int32 partial sums.

    Interpret mode only: its in-kernel decoder has no TPU lowering, so
    ``interpret=False`` (the auto-detected value on a TPU) raises."""
    interpret = auto_interpret(interpret)
    refuse_compiled_decoder("gated_one_to_all_pallas", interpret)
    nb_total, ph, pw, cin = spike_blocks.shape
    kb_total, taps, c8, kblk_ = maskp.shape
    assert kblk_ == kblk and taps == kh * kw and c8 * 8 == cin
    assert ph == bh + kh - 1 and pw == bw + kw - 1

    grid = (kb_total, nb_total)  # K outer, spatial inner → KTBC order
    out = pl.pallas_call(
        functools.partial(_kernel, taps=taps, kh=kh, kw=kw, bh=bh, bw=bw),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, taps), lambda kb, nb: (kb, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, ph, pw, cin), lambda kb, nb: (nb, 0, 0, 0)),
            pl.BlockSpec((1, taps, c8, kblk), lambda kb, nb: (kb, 0, 0, 0)),
            pl.BlockSpec((1, vals.shape[1]), lambda kb, nb: (kb, 0)),
        ],
        out_specs=pl.BlockSpec((1, bh, bw, kblk), lambda kb, nb: (nb, 0, 0, kb)),
        out_shape=jax.ShapeDtypeStruct((nb_total, bh, bw, kb_total * kblk), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((taps, cin, kblk), jnp.int8),
            pltpu.VMEM((bh * bw, kblk), jnp.int32),
        ],
        interpret=interpret,
    )(tap_any, spike_blocks, maskp, vals)
    return out
