"""Jitted wrappers around the Pallas kernels: host-side packing (bitmask
compression, block layout) + dispatch + unpacking.

These are the public entry points; `ref.py` holds the pure-jnp oracles each
wrapper is tested against (interpret mode on CPU, real TPU lowering on HW).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import encode_pipeline as ep
from . import fused_pipeline as fp
from . import gated_one_to_all as g2a
from . import spike_lif as sl
from . import bitmask_matmul as bmm
from .backend import auto_interpret


# ---------------------------------------------------------------------------
# Packing for the gated one-to-all kernel
# ---------------------------------------------------------------------------


class PackedConvWeights(NamedTuple):
    maskp: jax.Array  # (KB, taps, C8, KBLK) uint8 bit-packed over C
    vals: jax.Array  # (KB, VPAD) int8
    tap_any: jax.Array  # (KB, taps) int32
    kh: int
    kw: int
    cin: int  # padded input channels
    kout: int  # true output channels
    kblk: int
    # taps with ANY nonzero weight across ALL K-blocks, as a static tuple —
    # known at pack time, so the fused kernel skips dead taps at TRACE time
    # (no per-tap runtime cond; a pruned 3×3 often kills whole taps)
    tap_alive: tuple = ()

    @property
    def compressed_bytes(self) -> int:
        """HBM bytes the kernel actually reads for weights (the Fig 17
        accounting): packed mask bits + padded nonzero values."""
        return self.maskp.size + self.vals.size


def pack_conv_weights(
    w_int8: np.ndarray, *, kblk: int = 128, vpad: int | None = None
) -> PackedConvWeights:
    """w_int8: (kh, kw, Cin, K) int8 (zeros = pruned). Host-side pack.

    ``vpad`` fixes the padded length of each K-block's packed-value vector
    (useful to give every layer of a plan the same VPAD). The kernel's
    decode clips gather indices into ``vals`` — an nnz that exceeds VPAD
    would silently read garbage — so an insufficient ``vpad`` raises here,
    at pack time, instead.
    """
    w = np.asarray(w_int8)
    kh, kw, cin, k = w.shape
    taps = kh * kw
    cin_p = int(np.ceil(cin / 8)) * 8
    k_p = int(np.ceil(k / kblk)) * kblk
    wp = np.zeros((kh, kw, cin_p, k_p), np.int8)
    wp[:, :, :cin, :k] = w
    kb_total = k_p // kblk

    maskp = np.zeros((kb_total, taps, cin_p // 8, kblk), np.uint8)
    vals_list = []
    tap_any = np.zeros((kb_total, taps), np.int32)
    for kb in range(kb_total):
        wb = wp[:, :, :, kb * kblk : (kb + 1) * kblk].reshape(taps, cin_p, kblk)
        mask = (wb != 0).astype(np.uint8)
        tap_any[kb] = mask.reshape(taps, -1).any(axis=1).astype(np.int32)
        # pack bits along C: bit c -> word c//8, position c%8
        m = mask.reshape(taps, cin_p // 8, 8, kblk)
        for b in range(8):
            maskp[kb] |= (m[:, :, b, :] << b).astype(np.uint8)
        vals_list.append(wb[wb != 0].ravel())
    max_nnz = max((v.size for v in vals_list), default=0)
    if vpad is None:
        vpad = max(max_nnz, 1)
    elif vpad < max_nnz:
        raise ValueError(
            f"vpad={vpad} < max per-K-block nnz={max_nnz}: the kernel's "
            "clipped gather would silently read garbage values"
        )
    vpad = max(vpad, 1)
    vals = np.zeros((kb_total, vpad), np.int8)
    for kb, v in enumerate(vals_list):
        vals[kb, : v.size] = v
    return PackedConvWeights(
        maskp=jnp.asarray(maskp),
        vals=jnp.asarray(vals),
        tap_any=jnp.asarray(tap_any),
        kh=kh,
        kw=kw,
        cin=cin_p,
        kout=k,
        kblk=kblk,
        tap_alive=tuple(int(t) for t in np.flatnonzero(tap_any.any(axis=0))),
    )


def unpack_conv_weights(pw: PackedConvWeights) -> np.ndarray:
    """Inverse of :func:`pack_conv_weights`: reconstruct the dense int8
    kernel (kh, kw, cin_padded, kout) from {maskp, vals}. Host-side; used
    by the pack→unpack round-trip property tests — the compressed form
    must be information-preserving for every sparsity pattern, or the
    kernel is silently computing with a different model."""
    maskp = np.asarray(pw.maskp)
    vals = np.asarray(pw.vals)
    kb_total, taps, c8, kblk = maskp.shape
    cin_p = c8 * 8
    w = np.zeros((taps, cin_p, kb_total * kblk), np.int8)
    for kb in range(kb_total):
        # unpack bit c%8 of word c//8 back to channel c (pack order)
        bits = np.stack(
            [(maskp[kb] >> b) & 1 for b in range(8)], axis=2
        )  # (taps, C8, 8, KBLK)
        mask = bits.reshape(taps, cin_p, kblk).astype(bool)
        block = np.zeros((taps, cin_p, kblk), np.int8)
        block[mask] = vals[kb, : int(mask.sum())]  # C-order, matching pack
        w[:, :, kb * kblk : (kb + 1) * kblk] = block
    return w.reshape(pw.kh, pw.kw, cin_p, kb_total * kblk)[..., : pw.kout]


def validate_packed(pw: PackedConvWeights) -> None:
    """Check that every K-block's nonzero count fits the packed-value
    buffer. The kernel clips gather indices into ``vals`` (it cannot
    bounds-check inside the grid), so an overflowing block silently reads
    the last value — validate host-side and raise instead."""
    maskp = np.asarray(pw.maskp)
    vpad = int(pw.vals.shape[1])
    nnz_per_kb = np.unpackbits(maskp.reshape(maskp.shape[0], -1), axis=1).sum(axis=1)
    worst = int(nnz_per_kb.max()) if nnz_per_kb.size else 0
    if worst > vpad:
        raise ValueError(
            f"packed weights invalid: K-block nnz={worst} exceeds VPAD={vpad}; "
            "repack with a larger vpad (kernel would silently read garbage)"
        )


def _macro_grid(nbh: int, nbw: int, mr: int, mc: int) -> tuple[int, int]:
    """Macro-tile grid (GH, GW): how many mr×mc block groups cover an
    nbh×nbw block grid (ragged edges round UP — the layout zero-pads)."""
    return -(-nbh // mr), -(-nbw // mc)


def _block_layout(
    spikes: jax.Array, *, bh: int, bw: int, pad: int, cin_p: int,
    mr: int = 1, mc: int = 1,
) -> jax.Array:
    """NHWC int8 spikes → (N*GH*GW*mr*mc, bh+2p, bw+2p, Cp) replicate-padded
    independent blocks (block convolution, paper §II-B), ordered so every
    mr×mc MACRO-TILE of the block grid is contiguous along the block axis —
    the fused kernel's grid step then covers one macro group with a single
    dynamic slice. Ragged block grids (nbh % mr or nbw % mc nonzero) are
    zero-padded with whole garbage blocks that ``_unblock`` strips; each
    block still carries its OWN replicate-padded halo, so the macro
    ordering never changes numerics."""
    n, h, w, c = spikes.shape
    if h % bh or w % bw:
        raise ValueError(f"({h},{w}) not divisible by block ({bh},{bw})")
    x = spikes
    if c < cin_p:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, cin_p - c)))
    nbh, nbw = h // bh, w // bw
    x = x.reshape(n, nbh, bh, nbw, bw, cin_p).transpose(0, 1, 3, 2, 4, 5)
    if mr > 1 or mc > 1:
        gh, gw = _macro_grid(nbh, nbw, mr, mc)
        if (gh * mr, gw * mc) != (nbh, nbw):
            x = jnp.pad(
                x,
                ((0, 0), (0, gh * mr - nbh), (0, gw * mc - nbw))
                + ((0, 0),) * 3,
            )
        x = x.reshape(n, gh, mr, gw, mc, bh, bw, cin_p)
        x = x.transpose(0, 1, 3, 2, 4, 5, 6, 7)  # groups outer, tile inner
    x = x.reshape(-1, bh, bw, cin_p)
    if pad:
        x = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)), mode="edge")
    return x


@functools.partial(
    jax.jit,
    static_argnames=(
        "kh",
        "kw",
        "kblk",
        "bh",
        "bw",
        "interpret",
        "out_h",
        "out_w",
        "batch",
        "kout",
    ),
)
def _dispatch(spike_blocks, pw_maskp, pw_vals, pw_tap_any, *, kh, kw, kblk, bh, bw, out_h, out_w, batch, kout, interpret):
    out = g2a.gated_one_to_all_pallas(
        spike_blocks,
        pw_maskp,
        pw_vals,
        pw_tap_any,
        kh=kh,
        kw=kw,
        bh=bh,
        bw=bw,
        kblk=kblk,
        interpret=interpret,
    )
    nbh, nbw = out_h // bh, out_w // bw
    out = out.reshape(batch, nbh, nbw, bh, bw, -1).transpose(0, 1, 3, 2, 4, 5)
    out = out.reshape(batch, out_h, out_w, -1)
    return out[..., :kout]


def gated_conv(
    spikes: jax.Array,
    pw: PackedConvWeights,
    *,
    bh: int = g2a.BLOCK_H,
    bw: int = g2a.BLOCK_W,
    interpret: bool | None = None,
) -> jax.Array:
    """Sparse-compressed block convolution of int8 spikes. NHWC → NHWK int32.

    The leading axis is a plain batch: callers fold extra grid dimensions
    (e.g. SNN time steps, bit-serial planes) into it so the whole T·N·blocks
    volume runs through ONE pallas_call."""
    interpret = auto_interpret(interpret)
    n, h, w, _ = spikes.shape
    pad = (pw.kh - 1) // 2
    blocks = _block_layout(spikes.astype(jnp.int8), bh=bh, bw=bw, pad=pad, cin_p=pw.cin)
    return _dispatch(
        blocks,
        pw.maskp,
        pw.vals,
        pw.tap_any,
        kh=pw.kh,
        kw=pw.kw,
        kblk=pw.kblk,
        bh=bh,
        bw=bw,
        out_h=h,
        out_w=w,
        batch=n,
        kout=pw.kout,
        interpret=interpret,
    )


# ---------------------------------------------------------------------------
# Fused layer pipeline: conv → FXP rescale → tdBN affine → LIF, one dispatch
# ---------------------------------------------------------------------------


def _block_layout_nohalo(
    x: jax.Array, *, bh: int, bw: int, cpad: int, mr: int = 1, mc: int = 1
) -> jax.Array:
    """NHWC f32 → (N*GH*GW*mr*mc, bh, bw, Cp) independent blocks, channel-
    padded, macro-ordered like :func:`_block_layout` (the membrane layout —
    no conv halo)."""
    return _block_layout(x, bh=bh, bw=bw, pad=0, cin_p=cpad, mr=mr, mc=mc)


def _unblock(
    xb: jax.Array, *, n: int, h: int, w: int, mr: int = 1, mc: int = 1
) -> jax.Array:
    """(N*GH*GW*mr*mc, bh, bw, C) macro-ordered blocks → NHWC (leading axes
    preserved). Inverts :func:`_block_layout`: undoes the macro grouping,
    then strips the zero-padded ragged-edge blocks by slicing to (h, w)."""
    bh, bw = xb.shape[-3], xb.shape[-2]
    lead = xb.shape[:-4]
    L = len(lead)
    nbh, nbw = h // bh, w // bw
    gh, gw = _macro_grid(nbh, nbw, mr, mc)
    cc = xb.shape[-1]
    xb = xb.reshape(lead + (n, gh, gw, mr, mc, bh, bw, cc))
    # (..., n, gh, gw, mr, mc, bh, bw, C) → (..., n, gh, mr, bh, gw, mc, bw, C)
    perm = tuple(range(L)) + tuple(L + i for i in (0, 1, 3, 5, 2, 4, 6, 7))
    xb = xb.transpose(perm)
    xb = xb.reshape(lead + (n, gh * mr * bh, gw * mc * bw, cc))
    return xb[..., :h, :w, :]


def affine_bundle(
    pw: PackedConvWeights,
    scale: jax.Array,  # () f32 — FXP dequant scale (per-tensor)
    mean: jax.Array,  # (C,) f32 — tdBN running mean
    var: jax.Array,  # (C,) f32 — tdBN running var
    gamma: jax.Array,
    beta: jax.Array,
    *,
    eps: float = 1e-5,
) -> jax.Array:
    """Pack the per-channel pipeline constants into the kernel's
    (KB, 5, KBLK) bundle: [FXP scale, mean, rsqrt(var+eps), gamma, beta].

    ``rsqrt(var+eps)`` is precomputed here — it is a deterministic
    element-wise function, so the kernel multiplying by it is bit-identical
    to ``tdbn_apply`` computing it inline. Channels padded past the true
    layer width get (mean 0, var 1, gamma 0, beta 0): their outputs are
    garbage-free zeros and are stripped by the caller anyway."""
    kb_total = pw.maskp.shape[0]
    kblk = pw.kblk
    kp = kb_total * kblk
    kout = mean.shape[0]

    def padc(v, fill):
        return jnp.concatenate([v, jnp.full((kp - kout,), fill, v.dtype)]) if kp > kout else v

    rinv = jax.lax.rsqrt(var + eps)
    rows = jnp.stack(
        [
            jnp.broadcast_to(scale.astype(jnp.float32), (kp,)),
            padc(mean.astype(jnp.float32), 0.0),
            padc(rinv.astype(jnp.float32), 1.0),
            padc(gamma.astype(jnp.float32), 0.0),
            padc(beta.astype(jnp.float32), 0.0),
        ]
    )  # (5, KP)
    return rows.reshape(fp.AFFINE_ROWS, kb_total, kblk).transpose(1, 0, 2)


@functools.partial(
    jax.jit,
    static_argnames=(
        "kh",
        "kw",
        "kblk",
        "bh",
        "bw",
        "nbt",
        "mr",
        "mc",
        "t_out",
        "tap_alive",
        "bn_scale",
        "threshold",
        "leak",
        "reset",
        "out_h",
        "out_w",
        "batch",
        "kout",
        "interpret",
    ),
)
def _dispatch_fused(
    spike_blocks,
    maskp,
    vals,
    affine,
    v0_rows,
    wdense,
    *,
    kh,
    kw,
    kblk,
    bh,
    bw,
    nbt,
    mr,
    mc,
    t_out,
    tap_alive,
    bn_scale,
    threshold,
    leak,
    reset,
    out_h,
    out_w,
    batch,
    kout,
    interpret,
):
    spk, mem = fp.fused_pipeline_pallas(
        spike_blocks,
        maskp,
        vals,
        affine,
        v0_rows,
        kh=kh,
        kw=kw,
        bh=bh,
        bw=bw,
        kblk=kblk,
        nbt=nbt,
        bpg=mr * mc,
        t_out=t_out,
        tap_alive=tap_alive,
        bn_scale=bn_scale,
        threshold=threshold,
        leak=leak,
        reset=reset,
        wdense=wdense,
        interpret=interpret,
    )
    blocks = (-1, bh, bw, mem.shape[-1])
    spk = _unblock(spk.reshape((t_out,) + blocks).astype(jnp.float32),
                   n=batch, h=out_h, w=out_w, mr=mr, mc=mc)
    mem = _unblock(mem.reshape(blocks), n=batch, h=out_h, w=out_w, mr=mr, mc=mc)
    return spk[..., :kout], mem[..., :kout]


def _normalize_tiling(
    nbt: int, mrows: int, mcols: int, nbh: int, nbw: int
) -> tuple[int, int, int]:
    """Clamp a requested (nbt, mrows×mcols) tiling to a layer's nbh×nbw
    block grid. A bare ``nbt`` with no macro shape (the legacy flat-group
    form, still used by direct callers) maps to a 1×nbt row macro-tile;
    macro axes clamp to the grid, and nbt drops to the largest divisor of
    the macro size. Pure dispatch shaping — never affects numerics."""
    if mrows * mcols == 1 and nbt > 1:
        mrows, mcols = 1, nbt
    mrows = max(1, min(mrows, nbh))
    mcols = max(1, min(mcols, nbw))
    bpg = mrows * mcols
    nbt = max(1, min(nbt, bpg))
    while bpg % nbt:
        nbt -= 1
    return nbt, mrows, mcols


def fused_conv_bn_lif(
    x_t: jax.Array,  # (t_in, N, H, W, C) {0,1} spikes
    pw: PackedConvWeights,
    affine: jax.Array,  # (KB, 5, KBLK) from affine_bundle
    *,
    v0: jax.Array | None,  # (N, H, W, Kout) f32 initial membrane, None=cold
    out_t: int,
    bn_scale: float,
    threshold: float,
    leak: float,
    reset: str = "hard",
    v_init: float = 0.0,
    bh: int = g2a.BLOCK_H,
    bw: int = g2a.BLOCK_W,
    nbt: int = 1,
    mrows: int = 1,
    mcols: int = 1,
    predecode: bool = True,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """The whole per-layer pipeline (conv → FXP rescale → tdBN affine → LIF
    over ``out_t`` steps) in ONE Pallas dispatch. Returns
    (spikes (out_t, N, H, W, Kout) f32 {0,1}, final membrane (N, H, W, Kout) f32).

    Binary spike layers only: the 8-bit encoding layer has its own lane-
    dense kernel (:func:`encode_conv_bn_lif`).

    ``mrows``/``mcols`` select the MACRO-TILE: each grid step processes an
    mrows×mcols group of spatial blocks (whole block-rows, or r×c groups),
    with ``nbt`` blocks stacked per MXU dot inside the group — the grid
    shrinks by mrows·mcols, amortizing per-step overhead at large inputs.
    Ragged block grids zero-pad whole blocks that are stripped on the way
    out. Passing only ``nbt`` (no macro shape) keeps the legacy flat
    grouping as a 1×nbt macro-tile. Tiling NEVER changes numerics.

    ``predecode=True`` (default) runs the bitmask decoder stage host-side at
    trace time — inference weights are static, so the decode is paid once
    per compile instead of once per frame — and hands the kernel the dense
    per-K-block weights. ``predecode=False`` keeps the decoder inside the
    kernel (once per K-block per call, the paper's on-chip decode for
    streaming weights); both are bit-identical and tested against each
    other.
    """
    interpret = auto_interpret(interpret)
    wdense = None
    if predecode:
        kb_total = pw.maskp.shape[0]
        kp_tot = kb_total * pw.kblk
        wd = unpack_conv_weights(pw).reshape(pw.kh * pw.kw, pw.cin, pw.kout)
        wd = np.pad(wd, ((0, 0), (0, 0), (0, kp_tot - pw.kout)))
        wdense = jnp.asarray(
            wd.reshape(pw.kh * pw.kw, pw.cin, kb_total, pw.kblk).transpose(2, 0, 1, 3)
        )
    t_in, n, h, w, _ = x_t.shape
    nbt, mrows, mcols = _normalize_tiling(nbt, mrows, mcols, h // bh, w // bw)
    pad = (pw.kh - 1) // 2
    flat = _block_layout(
        x_t.reshape((t_in * n,) + x_t.shape[2:]).astype(jnp.int8),
        bh=bh,
        bw=bw,
        pad=pad,
        cin_p=pw.cin,
        mr=mrows,
        mc=mcols,
    )
    nb = flat.shape[0] // t_in
    blocks = flat.reshape((t_in, nb) + flat.shape[1:])
    kp = pw.maskp.shape[0] * pw.kblk
    if v0 is None:
        # cold start at v_init (conversion's θ/2 rounding trick); padded
        # channels/blocks get it too but are sliced away on the way out
        v0b = jnp.full((nb * bh * bw, kp), v_init, jnp.float32)
    else:
        v0b = _block_layout_nohalo(
            v0.astype(jnp.float32), bh=bh, bw=bw, cpad=kp, mr=mrows, mc=mcols
        ).reshape(nb * bh * bw, kp)
    return _dispatch_fused(
        blocks,
        None if predecode else pw.maskp,
        None if predecode else pw.vals,
        affine,
        v0b,
        wdense,
        kh=pw.kh,
        kw=pw.kw,
        kblk=pw.kblk,
        bh=bh,
        bw=bw,
        nbt=nbt,
        mr=mrows,
        mc=mcols,
        t_out=out_t,
        tap_alive=tuple(pw.tap_alive),
        bn_scale=bn_scale,
        threshold=threshold,
        leak=leak,
        reset=reset,
        out_h=h,
        out_w=w,
        batch=n,
        kout=pw.kout,
        interpret=interpret,
    )


# ---------------------------------------------------------------------------
# Encoding layer: RGB frames → spikes in one lane-dense dispatch
# ---------------------------------------------------------------------------
#
# The lane-dense layout: an NHWC array (…, N, H, W, C) viewed as
# (…, N, H, W·C) — the same elements in the same order, a frame row's W·C
# values side by side in the minor dimension.


def lane_dense_nhwc(x: jax.Array, c: int) -> jax.Array:
    """(…, H, W·C) → (…, H, W, C): the NHWC shape of a lane-dense array (a
    reshape; on a TPU a relayout copy, so the serving step keeps the
    lane-dense shape)."""
    return x.reshape(x.shape[:-1] + (-1, c))


def lane_dense_maxpool(spk: jax.Array, c: int) -> jax.Array:
    """2×2 max-pool (the spike OR gate) of lane-dense spikes (…, H, W·C)
    with H and W even → NHWC (…, H/2, W/2, C). Row pairs split the row
    axis; column pairs are neighbouring C-lane groups."""
    *lead, h, lanes = spk.shape
    y = spk.reshape(*lead, h // 2, 2, lanes).max(axis=-2)
    return y.reshape(*lead, h // 2, lanes // (2 * c), 2, c).max(axis=-2)


@functools.partial(
    jax.jit,
    static_argnames=("bh", "bw", "t_out", "bn_scale", "threshold", "leak",
                     "reset", "v_init", "interpret"),
)
def _dispatch_encode(frames, bands, affine, v0, *, bh, bw, t_out, bn_scale,
                     threshold, leak, reset, v_init, interpret):
    n, h, w, _ = frames.shape
    if v0 is not None:
        v0 = v0.astype(jnp.float32).reshape(n * h, -1)
    spk, mem = ep.encode_pallas(
        # the planar view: a TPU keeps the frames W-minor, so this
        # transpose reads them as they lie
        frames.astype(jnp.float32).transpose(0, 3, 1, 2), bands, affine, v0,
        bh=bh, bw=bw, t_out=t_out, bn_scale=bn_scale, threshold=threshold,
        leak=leak, reset=reset, v_init=v_init, interpret=interpret,
    )
    return spk.reshape(t_out, n, h, -1), mem.reshape(n, h, -1)


def encode_conv_bn_lif(
    frames: jax.Array,  # (N, H, W, Cin) f32 in [0, 1]
    w_q: jax.Array,  # (3, 3, Cin, C) int8 quantized weights
    affine: jax.Array,  # (KB, 5, KBLK) from affine_bundle
    *,
    v0: jax.Array | None,  # (N, H, W·C) or (N, H, W, C); None = cold
    out_t: int,
    bn_scale: float,
    threshold: float,
    leak: float,
    reset: str = "hard",
    v_init: float = 0.0,
    bh: int = g2a.BLOCK_H,
    bw: int = g2a.BLOCK_W,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """The 8-bit encoding layer (u8 quantisation → block conv → FXP
    rescale → tdBN affine → LIF over ``out_t`` steps from one drive) in ONE
    lane-dense Pallas dispatch (kernels/encode_pipeline.py). Returns lane-
    dense (spikes (out_t, N, H, W·C) int8 {0,1}, final membrane (N, H, W·C)
    f32), bit-identical in NHWC element order to the unfused chain's on
    finite frames. The band matrices are built from the static weights at
    trace time, as the blocked path predecodes its weights."""
    cin, kout = w_q.shape[-2:]
    wt = ep.input_tile(frames.shape[2], bw)
    bands = ep.tile_bands(ep.band_matrices(np.asarray(w_q), bw), cin, wt)
    return _dispatch_encode(
        frames, jnp.asarray(bands, jnp.bfloat16),
        ep.affine_lanes(affine, kout, bw), v0,
        bh=bh, bw=bw, t_out=out_t, bn_scale=bn_scale, threshold=threshold,
        leak=leak, reset=reset, v_init=v_init,
        interpret=auto_interpret(interpret),
    )


# ---------------------------------------------------------------------------
# Fused LIF
# ---------------------------------------------------------------------------


def fused_lif(
    psum_t: jax.Array,  # (T, M, C) f32 synaptic inputs
    *,
    threshold: float = 0.5,
    leak: float = 0.25,
    mblk: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """LIF over T fully fused in VMEM (no HBM round-trip of the membrane
    potential between steps). Returns int8 spikes (T, M, C)."""
    return sl.fused_lif_pallas(
        psum_t, threshold=threshold, leak=leak, mblk=mblk, interpret=auto_interpret(interpret)
    )


# ---------------------------------------------------------------------------
# Bitmask sparse matmul (paper's format applied to LM FFN weights)
# ---------------------------------------------------------------------------


def pack_matmul_weights(w: np.ndarray, *, kblk: int = 512, nblk: int = 256):
    return bmm.pack_weights(w, kblk=kblk, nblk=nblk)


def bitmask_matmul(
    x: jax.Array,
    packed,
    *,
    mblk: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """x (M, K) f32/bf16 × bitmask-compressed W (K, N) → (M, N) f32."""
    return bmm.bitmask_matmul_pallas(x, packed, mblk=mblk, interpret=auto_interpret(interpret))
