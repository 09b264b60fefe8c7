"""Lane-dense encode kernel: the 8-bit encoding layer (RGB frames → C spike
channels: u8 quantisation, 3×3 block convolution, FXP rescale, tdBN, LIF)
in ONE dispatch that reads the frames as they lie in memory and writes
spikes and membrane in NHWC element order, (N·H, W·C) with W·C lanes.

Why a kernel of its own
-----------------------
The encoding layer has 3 input channels and 16 outputs. Laid out channel-
minor, as the blocked spike-layer kernel (fused_pipeline.py) lays out its
tiles, every array of this layer has a minor dimension of 3, 8 or 16, and a
TPU pads a minor dimension to 128 lanes: 8–40× the bytes in every copy
around the kernel. A TPU keeps (N, H, W, 3) frames planar, W minor; this
kernel reads them in that order (a (N, 3, H, W) view, the same bytes), and
its outputs keep NHWC element order with the W·C values of a frame row
side by side in the minor dimension.

Bit-serial encode in one dispatch: the 8-bit input folds its 8 bit planes
*into the input values* — Σ_b 2^b·conv(plane_b, W) = conv(Σ_b 2^b·plane_b,
W) = conv(u8, W) by linearity over exact integers — so the paper's §III-C.2
bit-serial layer is ONE dispatch over the u8 pixel values, quantised in
the kernel with the same f32 ops as ``core.plan._quantize_input_u8``.

Block convolution as a banded matmul
------------------------------------
Paper §II-B: each bh×bw block is convolved with replicate padding at its
OWN border. For kernel row ``dy`` a static band matrix ``B_dy`` of shape
``(bw·Cin, bw·C)`` maps one block-row segment of input pixels to its
outputs: ``B_dy[x'·Cin + c, x·C + k] = Σ_{dx: clamp(x+dx−p) = x'}
W[dy, dx, c, k]``. The W-direction replicate halo folds into it: at x = 0
both the dx = 0 and dx = 1 taps read column 0, so their weights ADD in one
entry (the same at x = bw−1). A 3×3 kernel folds at most two int8
weights, |sum| ≤ 254, which bf16 holds exactly (8 significant bits cover
every integer up to 256); u8 pixels are exact in bf16 too. So each dot is
a single-pass bf16 MXU dot with f32 accumulation, and every partial sum
stays below 2^24 (27·255·127 < 2^20): the accumulator is the exact
integer conv in any order — the integer the dense executor and the
reference compute. Pruned taps are zeros of ``B_dy``.

A grid step owns one segment (bw pixels × R frame rows, R whole blocks
high) and reads the 128-pixel planar tile around it: the dot's left side
is the tile's three channels side by side, (R, 3·128), with the lanes of
other segments masked to zero, against ``B_dy`` laid out for that tile
(:func:`tile_bands`). The H-direction halo is a clamped row shift inside
the tile, where each block's first and last rows repeat — no neighbour
rows, no HBM copy.

The epilogue (FXP rescale, tdBN affine, LIF over ``t_out`` steps from one
drive) is fused_pipeline's op chain (``bn_drive``, ``lif_step``) on
``(R, bw·C)`` tiles whose affine rows repeat the C channel values across
the bw pixels — element-wise the same floats, so the kernel is bit-
identical to the blocked kernel and the dense executor.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import auto_interpret
from .fused_pipeline import AFFINE_ROWS, bn_drive, lif_step

LANES = 128  # pixels of the planar input tile (the TPU's lane width)
# sublane offset of the tile in the row-shift scratch: an aligned store,
# then the loads one row above and one row below it
_ROW_PAD = 8


def band_matrices(w_q: np.ndarray, bw: int) -> np.ndarray:
    """(3, kw, Cin, C) int8 weights → (3, bw·Cin, bw·C) band matrices, rows
    (x', c), with the block's W-direction replicate halo folded in (module
    docstring). Host-side, at trace time: inference weights are static.
    Raises if a folded entry is not exact in bf16."""
    w = np.asarray(w_q, np.int32)
    kh, kw, cin, kout = w.shape
    pad = (kw - 1) // 2
    bands = np.zeros((kh, bw, cin, bw, kout), np.int32)
    for x in range(bw):
        for dx in range(kw):
            src = min(max(x + dx - pad, 0), bw - 1)
            bands[:, src, :, x, :] += w[:, dx]
    if np.abs(bands).max(initial=0) > 256:
        raise ValueError(
            f"encode band matrices fold more than two weights (kw={kw}, "
            f"bw={bw}): the folded sums are not exact in bf16"
        )
    return bands.reshape(kh, bw * cin, bw * kout).astype(np.float32)


def input_tile(w: int, bw: int) -> int:
    """Pixels of the planar input tile a grid step reads: one lane width of
    whole segments where the row allows it, else the whole row."""
    return LANES if w % LANES == 0 and LANES % bw == 0 else w


def tile_bands(bands: np.ndarray, cin: int, wt: int) -> np.ndarray:
    """Band matrices with rows in the kernel's left-side lane order
    (c, p) for the pixels p of a ``wt``-pixel tile: row (c, p) holds
    segment pixel ``p mod bw`` of channel c. Every segment of the tile
    shares them; the kernel masks the lanes of the other segments."""
    kh, rows, lanes = bands.shape
    bw = rows // cin
    b = bands.reshape(kh, bw, cin, lanes).transpose(0, 2, 1, 3)
    return np.tile(b, (1, 1, wt // bw, 1)).reshape(kh, cin * wt, lanes)


def affine_lanes(affine: jax.Array, kout: int, bw: int) -> jax.Array:
    """The (KB, AFFINE_ROWS, KBLK) parameter bundle (ops.affine_bundle) as
    (AFFINE_ROWS, bw·C) rows: lane ``x·C + k`` holds channel ``k``'s value."""
    rows = affine.transpose(1, 0, 2).reshape(AFFINE_ROWS, -1)[:, :kout]
    return jnp.tile(rows, (1, bw))


def row_block(h: int, bh: int) -> int:
    """Frame rows per grid step: whole conv blocks and whole int8 sublane
    tiles (32 rows) where the frame allows it, else the whole frame."""
    r = math.lcm(bh, 32)
    return r if h % r == 0 else h


def _kernel(
    x_ref,  # VMEM (1, Cin, R, wt) f32 frames in [0, 1], planar
    bands_ref,  # VMEM (3, Cin·wt, bw·C) bf16 from tile_bands
    aff_ref,  # VMEM (AFFINE_ROWS, bw·C) f32
    *refs,  # [v0 (R, bw·C) f32], spk (t_out, R, bw·C) int8, mem, lhs, xs
    bh: int,
    bw: int,
    t_out: int,
    bn_scale: float,
    threshold: float,
    leak: float,
    reset: str,
    v_init: float,
    cold: bool,
):
    if cold:
        spk_ref, mem_ref, lhs_ref, xs_ref = refs
    else:
        v0_ref, spk_ref, mem_ref, lhs_ref, xs_ref = refs
    _, cin, rows, wt = x_ref.shape
    per_tile = wt // bw
    seg = pl.program_id(2) % per_tile  # this step's segment of the tile

    # once per input tile (the first of its segments): quantise, shift the
    # rows for each kernel row dy, and lay the channels side by side
    @pl.when(seg == 0)
    def _stage():
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, wt), 0) % bh
        lo = _ROW_PAD
        for c in range(cin):
            # _quantize_input_u8's f32 ops; the values are the u8 integers
            xq = jnp.clip(jnp.round(x_ref[0, c] * 255.0), 0, 255)
            xs_ref[lo : lo + rows] = xq
            # H-direction replicate halo: a block's first row has no row
            # above it and its last row none below, so they repeat
            above = jnp.where(row == 0, xq, xs_ref[lo - 1 : lo - 1 + rows])
            below = jnp.where(row == bh - 1, xq, xs_ref[lo + 1 : lo + 1 + rows])
            for dy, xd in enumerate((above, xq, below)):
                lhs_ref[dy, :, c * wt : (c + 1) * wt] = xd

    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, cin * wt), 1)
    lane_seg = lane % wt // bw
    acc = None
    for dy in range(3):  # one banded dot per kernel row
        win = jnp.where(lane_seg == seg, lhs_ref[dy], 0.0).astype(jnp.bfloat16)
        part = jax.lax.dot_general(
            win, bands_ref[dy], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc = part if acc is None else acc + part

    scale = aff_ref[0:1, :]  # (1, bw·C), repeated per pixel of the segment
    mean = aff_ref[1:2, :]
    rinv = aff_ref[2:3, :]
    gamma = aff_ref[3:4, :]
    beta = aff_ref[4:5, :]
    drives = bn_drive(acc, scale, mean, rinv, gamma, beta, bn_scale)
    v = jnp.full(drives.shape, v_init, jnp.float32) if cold else v0_ref[...]
    for t in range(t_out):  # one drive, t_out LIF steps
        spiked, v = lif_step(v, drives, threshold=threshold, leak=leak,
                             reset=reset)
        spk_ref[t] = spiked.astype(jnp.int8)
    mem_ref[...] = v


def encode_pallas(
    frames: jax.Array,  # (N, Cin, H, W) f32 in [0, 1]: planar frames
    bands: jax.Array,  # (3, Cin·wt, bw·C) bf16 from tile_bands
    affine: jax.Array,  # (AFFINE_ROWS, bw·C) f32 from affine_lanes
    v0: jax.Array | None,  # (N·H, W·C) f32, None = cold at v_init
    *,
    bh: int,
    bw: int,
    t_out: int,
    bn_scale: float,
    threshold: float,
    leak: float,
    reset: str = "hard",
    v_init: float = 0.0,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """One dispatch for the whole encoding layer; grid = (frame, row block,
    segment). Returns (spikes (t_out, N·H, W·C) int8, membrane (N·H, W·C)
    f32), NHWC element order."""
    interpret = auto_interpret(interpret)
    n, cin, h, w = frames.shape
    kh, k_rows, lanes = bands.shape
    wt = k_rows // cin
    assert kh == 3 and wt == input_tile(w, bw), (kh, wt, w, bw)
    assert affine.shape == (AFFINE_ROWS, lanes), affine.shape
    assert h % bh == 0 and w % bw == 0, (h, w, bh, bw)
    rows = row_block(h, bh)
    per_tile = wt // bw
    rblocks = h // rows
    cold = v0 is None

    def out_block(i, r, j):
        return (i * rblocks + r, j)

    row_spec = pl.BlockSpec((rows, lanes), out_block)
    in_specs = [
        pl.BlockSpec((1, cin, rows, wt), lambda i, r, j: (i, 0, r, j // per_tile)),
        pl.BlockSpec((kh, k_rows, lanes), lambda i, r, j: (0, 0, 0)),
        pl.BlockSpec((AFFINE_ROWS, lanes), lambda i, r, j: (0, 0)),
    ]
    inputs = [frames, bands, affine]
    if not cold:
        in_specs.append(row_spec)
        inputs.append(v0)
    spk, mem = pl.pallas_call(
        functools.partial(
            _kernel, bh=bh, bw=bw, t_out=t_out, bn_scale=bn_scale,
            threshold=threshold, leak=leak, reset=reset, v_init=v_init,
            cold=cold,
        ),
        grid=(n, rblocks, w // bw),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((t_out, rows, lanes),
                         lambda i, r, j: (0, *out_block(i, r, j))),
            row_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t_out, n * h, w // bw * lanes), jnp.int8),
            jax.ShapeDtypeStruct((n * h, w // bw * lanes), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((kh, rows, cin * wt), jnp.float32),
            pltpu.VMEM((rows + 2 * _ROW_PAD, wt), jnp.float32),
        ],
        # the staged tile is reused by the segments after the first
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3),
        interpret=interpret,
        name="encode_lane_dense",
    )(*inputs)
    return spk, mem
