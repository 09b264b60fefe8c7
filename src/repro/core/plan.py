"""Whole-detector compression plan + pluggable conv executors.

This is the bridge between the paper's compressed dataflow and the model:
``build_plan`` walks the full ``snn_yolo`` parameter tree ONCE and
precompiles every conv layer into a :class:`CompressedLayerPlan` —

    prune (already applied to params) → quantize (FXP8, per-tensor
    symmetric) → bitmask-pack ({maskp, vals, tap_any} + K-blocking
    metadata, paper §III-B.2)

— so inference never touches dense float weights. Which engine actually
runs each conv is a pluggable *executor*, selected by
``SNNDetConfig.conv_exec``:

  * ``dense``  — ``lax.conv`` / block-conv oracle on the dequantized
                 weights (the numerical reference).
  * ``gated``  — the literal shift-accumulate gated one-to-all product
                 (paper-faithful dataflow, exact accumulate accounting).
  * ``pallas`` — the compressed Pallas TPU kernel: weights stream from HBM
                 in bitmask-compressed form and are decoded once per
                 K-block in VMEM (paper's −59.1% weight traffic).

Executors consume the full time-major activation volume ``(T, N, H, W, C)``
and fold T (and, for the 8-bit encoding layer, the bit-serial plane axis)
into the batch, so mixed time steps batch through ONE ``pallas_call`` whose
grid spans T·N·spatial-blocks instead of a Python vmap over T.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import bitserial
from . import block_conv as bc
from . import pruning, quant
from . import spike_conv as sc
from repro.kernels import autotune
from repro.kernels import ops as kops


class CompressedLayerPlan(NamedTuple):
    """One conv layer, compiled for the compressed gated one-to-all path."""

    name: str
    packed: kops.PackedConvWeights  # bitmask-compressed int8 weights
    scale: jax.Array  # () f32 — dequant scale (FXP8 per-tensor)
    w_q: jax.Array  # (kh, kw, cin, kout) int8 dense — gated/dense reference
    in_bits: int  # 1 = binary spikes, 8 = multibit input (bit-serial)
    nnz: int  # true nonzero count (accumulate accounting)
    # dispatch tiling for the fused pipeline kernel — autotuned per layer
    # shape (kernels/autotune.py); NEVER affects numerics, only wall-clock
    tile: autotune.TileConfig = autotune.DEFAULT_TILE

    @property
    def dense_bytes(self) -> int:
        return int(np.prod(self.w_q.shape))

    @property
    def compressed_bytes(self) -> int:
        return int(self.packed.compressed_bytes)


class DetectorPlan(NamedTuple):
    layers: dict  # name -> CompressedLayerPlan
    block_hw: tuple  # (bh, bw) spatial block for every executor

    @property
    def dense_bytes(self) -> int:
        return sum(lp.dense_bytes for lp in self.layers.values())

    @property
    def compressed_bytes(self) -> int:
        return sum(lp.compressed_bytes for lp in self.layers.values())

    def summary(self) -> dict:
        """JSON-serializable per-layer compression report (nnz, density,
        dense vs packed bytes, FXP scale) plus totals — what the conversion
        front-end embeds in its checkpoint report and ``examples/
        convert_ann_detector.py`` prints."""
        layers = {
            name: {
                "shape": list(lp.w_q.shape),
                "nnz": int(lp.nnz),
                "density": round(lp.nnz / max(1, lp.dense_bytes), 4),
                "dense_bytes": lp.dense_bytes,
                "compressed_bytes": lp.compressed_bytes,
                "scale": float(np.asarray(lp.scale)),
                "in_bits": lp.in_bits,
            }
            for name, lp in self.layers.items()
        }
        return {
            "block_hw": list(self.block_hw),
            "layers": layers,
            "dense_bytes": self.dense_bytes,
            "compressed_bytes": self.compressed_bytes,
            "compression_ratio": round(
                self.dense_bytes / max(1, self.compressed_bytes), 3
            ),
        }


# ------------------------------------------------------------------ build --


def build_layer_plan(
    name: str,
    w: jax.Array,
    *,
    kblk: int = 128,
    weight_bits: int = 8,
    in_bits: int = 1,
    vpad: int | None = None,
    tile: autotune.TileConfig | None = None,
) -> CompressedLayerPlan:
    """Quantize + bitmask-pack one HWIO kernel tensor. Must run outside jit
    (packing is host-side numpy). Raises if any K-block's nnz would overflow
    the packed-value buffer (the kernel cannot bounds-check its gather).

    ``tile`` (autotuned dispatch shape) overrides ``kblk`` — the packed
    K-block width is itself a tuning knob; any choice is bit-exact."""
    qw = quant.quantize(w, bits=weight_bits)
    w_q = np.asarray(qw.q).reshape(w.shape)
    kout = w.shape[-1]
    if tile is not None:
        kblk = tile.kblk
    kblk_l = min(kblk, -(-kout // 8) * 8)  # small layers: one tight K-block
    # pack_conv_weights itself raises on vpad overflow; validate_packed
    # stays available for externally-constructed PackedConvWeights
    packed = kops.pack_conv_weights(w_q, kblk=kblk_l, vpad=vpad)
    return CompressedLayerPlan(
        name=name,
        packed=packed,
        scale=qw.scale.reshape(()),
        w_q=jnp.asarray(w_q),
        in_bits=in_bits,
        nnz=int(np.count_nonzero(w_q)),
        tile=tile or autotune.TileConfig(kblk=kblk_l, nbt=autotune.DEFAULT_TILE.nbt),
    )


def _layer_shapes_for(cfg) -> dict:
    """Per-layer :class:`~repro.kernels.autotune.LayerShape` map for the
    autotune-cache lookup. Falls back to {} for configs the topology walk
    does not understand — those layers just run at DEFAULT_TILE."""
    try:
        return autotune.detector_layer_shapes(cfg)
    except Exception:
        return {}


def build_plan(
    params: Any,
    cfg,
    *,
    kblk: int = 128,
    prune_rate: float | None = None,
    tile_cache: dict | None = None,
) -> DetectorPlan:
    """Compile the whole detector parameter tree in one pass.

    ``params`` is the ``snn_yolo.init_params`` tree (name -> {"w", ...}).
    ``prune_rate`` optionally applies fine-grained magnitude pruning to the
    spatial (3×3) kernels first — pass the SAME pruned tree to the dense
    oracle when checking parity. The encoding layer is marked 8-bit input
    (RGB); every other layer consumes binary spikes.

    ``tile_cache``: shape→TileConfig entries for the fused kernel's
    dispatch tiling. ``None`` consults the persisted autotune cache
    (``kernels/autotune.py``; missing/stale caches fall back to default
    tilings); pass ``{}`` to force defaults. Tiling never changes numerics.
    """
    if not cfg.weight_bits:
        # the compressed path is FXP-int8 by construction; quantizing a
        # float-weight config silently would diverge from its dense baseline
        raise ValueError(
            "build_plan requires quantized weights (cfg.weight_bits > 0); "
            "weight_bits=0 means float weights, which only conv_exec='dense' runs"
        )
    shapes = _layer_shapes_for(cfg)
    layers = {}
    for name, layer_p in params.items():
        w = layer_p["w"]
        if prune_rate is not None and pruning.is_spatial_kernel(w):
            w = pruning.prune_by_rate(w, prune_rate)
        shape = shapes.get(name)
        tile = autotune.lookup(shape, tile_cache) if shape is not None else None
        layers[name] = build_layer_plan(
            name,
            w,
            kblk=kblk,
            weight_bits=cfg.weight_bits,
            in_bits=8 if name == "encode" else 1,
            tile=tile,
        )
    return DetectorPlan(layers=layers, block_hw=tuple(cfg.block_hw))


# -------------------------------------------------------------- executors --

# Registry: name -> fn(x_t (T,N,H,W,C) f32, CompressedLayerPlan, cfg) -> f32
CONV_EXECUTORS: dict[str, Callable] = {}


def register_conv_executor(name: str):
    def deco(fn):
        CONV_EXECUTORS[name] = fn
        return fn

    return deco


def run_conv(x_t: jax.Array, lp: CompressedLayerPlan, cfg) -> jax.Array:
    """Dispatch one conv layer through the configured executor."""
    try:
        fn = CONV_EXECUTORS[cfg.conv_exec]
    except KeyError:
        raise ValueError(
            f"unknown conv_exec={cfg.conv_exec!r}; registered: {sorted(CONV_EXECUTORS)}"
        ) from None
    return fn(x_t, lp, cfg)


def _fold_t(x_t: jax.Array) -> tuple[jax.Array, tuple]:
    t, n = x_t.shape[:2]
    return x_t.reshape((t * n,) + x_t.shape[2:]), (t, n)


def _unfold_t(y: jax.Array, tn: tuple) -> jax.Array:
    t, n = tn
    return y.reshape((t, n) + y.shape[1:])


def _quantize_input_u8(x: jax.Array) -> jax.Array:
    """[0,1] float → uint8 grid (the paper's 8-bit RGB input). Exact for
    images that already live on the k/255 grid."""
    return jnp.clip(jnp.round(x * 255.0), 0, 255).astype(jnp.uint8)


@register_conv_executor("dense")
def _exec_dense(x_t: jax.Array, lp: CompressedLayerPlan, cfg) -> jax.Array:
    """Oracle: dense conv on the int8 weights, dequantized AFTER the
    accumulation.

    All three executors accumulate integer-valued f32 (binary spikes ×
    int8 weights; every partial sum < 2^24 is exact in f32 regardless of
    summation order) and apply the FXP scale exactly once on the final
    integer — so dense, gated and the Pallas kernel agree BIT-EXACTLY,
    which is what the conformance suite (tests/conformance/) asserts.
    Scaling the weights first instead would make the result depend on the
    executor's float summation order (observed: ~1-ulp drift between the
    pre-refactor dense oracle and the Pallas kernel)."""
    w_int = lp.w_q.astype(jnp.float32)
    bh, bw = cfg.block_hw
    x, tn = _fold_t(x_t)
    if lp.in_bits == 8:
        # the paper's 8-bit RGB contract: inputs are quantized to the
        # uint8 grid (exact for k/255-grid frames), convolved as integers
        x = _quantize_input_u8(x).astype(jnp.float32)
        out_scale = lp.scale / 255.0
    else:
        out_scale = lp.scale
    if cfg.use_block_conv and w_int.shape[0] > 1:
        y = bc.block_conv2d(x, w_int, block_h=bh, block_w=bw)
    else:
        y = bc.conv2d(x, w_int)
    return _unfold_t(y * out_scale, tn)


def _blocked_gated(
    x: jax.Array,
    w: jax.Array,
    bh: int,
    bw: int,
    tap_alive: tuple | None = None,
) -> jax.Array:
    """Shift-accumulate gated one-to-all over independent replicate-padded
    blocks. Each live tap slices its aligned window straight out of the
    padded block and contracts input channels with one matmul — the same
    one-to-all broadcast as :func:`spike_conv.gated_one_to_all`, minus the
    zero-fill scatter per tap and the SAME-conv-then-crop waste (only the
    bh×bw interior is ever computed). ``tap_alive`` (pack-time liveness)
    skips fully-pruned taps at trace time. Integer-valued f32 accumulation
    is order-independent, so all of this is bit-exact with the literal
    shift-accumulate reference."""
    kh, kw = int(w.shape[0]), int(w.shape[1])
    if kh == 1 and kw == 1:
        # pointwise conv sees no block borders — skip the block round-trip
        # (two transposes) and contract channels in place
        return x @ w[0, 0].astype(jnp.float32)
    taps = tuple(range(kh * kw)) if tap_alive is None else tap_alive
    if len(taps) == kh * kw:
        # every gate open — the one-to-all visit order degenerates to the
        # full tap set, which is exactly the dense blocked conv (same
        # integer-exact accumulation, no im2col copy)
        return bc.block_conv2d(x, w.astype(jnp.float32), block_h=bh, block_w=bw)
    pad = (kh - 1) // 2
    xb = bc.to_blocks(x, bh, bw)
    n, nbh, nbw, _, _, c = xb.shape
    flat = xb.reshape(n * nbh * nbw, bh, bw, c)
    if pad:
        flat = jnp.pad(flat, ((0, 0), (pad, pad), (pad, pad), (0, 0)), mode="edge")
    m = flat.shape[0]
    kout = w.shape[-1]
    if not taps:  # fully-pruned layer: all taps gated off
        out = jnp.zeros((m, bh, bw, kout), jnp.float32)
    else:
        # all live taps in ONE contraction: stack each tap's window along a
        # new axis (im2col over live taps only) and contract (live·cin) at
        # once — integer-valued f32 partial sums stay exact (|acc| bounded
        # by live·cin·127 « 2^24), so the single dot is bit-identical to
        # the tap-by-tap shift-accumulate
        wins = [
            jax.lax.slice(flat, (0, t // kw, t % kw, 0),
                          (m, t // kw + bh, t % kw + bw, c))
            for t in taps
        ]
        patches = jnp.stack(wins, axis=-2)  # (m, bh, bw, live, cin)
        s2 = patches.reshape(m * bh * bw, len(taps) * c)
        w2 = jnp.stack([w[t // kw, t % kw] for t in taps])
        w2 = w2.reshape(len(taps) * c, kout).astype(jnp.float32)
        out = (s2 @ w2).reshape(m, bh, bw, kout)
    out = out.reshape(n, nbh, nbw, bh, bw, kout)
    return bc.from_blocks(out)


@register_conv_executor("gated")
def _exec_gated(x_t: jax.Array, lp: CompressedLayerPlan, cfg) -> jax.Array:
    """Paper-faithful shift-accumulate reference over the blocked layout.

    Accumulates the int8 weights as integer-valued f32 (exact) and scales
    the final integer once — see :func:`_exec_dense` for why this makes
    every executor bit-identical.

    The 8-bit encoding layer folds its bit-serial planes by conv linearity
    — conv(Σ_b 2^b·plane_b, w) = Σ_b 2^b·conv(plane_b, w) — into ONE gated
    pass over the integer-valued maps, exactly as the fused Pallas kernel
    does. :func:`repro.core.bitserial.bitserial_conv` remains the literal
    plane-serial reference and the two are asserted equal in tests; the
    accumulate accounting (nnz × bits_in) is analytic and unchanged."""
    w_int = lp.w_q.astype(jnp.float32)
    bh, bw = cfg.block_hw
    alive = tuple(lp.packed.tap_alive)
    x, tn = _fold_t(x_t)
    if lp.in_bits == 8:
        x = _quantize_input_u8(x).astype(jnp.float32)
        y = _blocked_gated(x, w_int, bh, bw, alive) * (lp.scale / 255.0)
    else:
        y = _blocked_gated(x, w_int, bh, bw, alive) * lp.scale
    return _unfold_t(y, tn)


def precompute_affines(plan: DetectorPlan, params, bn_state, cfg) -> dict:
    """Affine parameter bundles for every fused-eligible layer, built ONCE.

    The bundle (FXP scale / tdBN mean / rsqrt(var+eps) / gamma / beta, laid
    out per K-block — see :func:`repro.kernels.ops.affine_bundle`) depends
    only on the weights and calibrated BN statistics, never on the frames.
    Rebuilding it inside the per-frame step costs a dozen small XLA ops per
    layer that cannot fuse into the pallas_call consuming them; a compile-
    once detector hoists the whole set here instead and threads the result
    through ``forward(..., affines=...)``. Callers own staleness: the
    bundles describe THESE params/bn_state (CompiledDetector fingerprints
    the inputs and refuses on a swap)."""
    out = {}
    for name, lp in plan.layers.items():
        p = params.get(name)
        st = (bn_state or {}).get(name)
        if p is None or st is None or "gamma" not in p:
            continue
        scale_eff = lp.scale / 255.0 if lp.in_bits == 8 else lp.scale
        out[name] = kops.affine_bundle(
            lp.packed, scale_eff, st["mean"], st["var"], p["gamma"], p["beta"]
        )
    return out


def run_fused(
    x_t: jax.Array,
    lp: CompressedLayerPlan,
    cfg,
    *,
    gamma: jax.Array,
    beta: jax.Array,
    mean: jax.Array,
    var: jax.Array,
    v0: jax.Array | None,
    out_t: int,
    affine: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """The whole per-layer pipeline of a binary spike layer — conv → FXP
    rescale → tdBN inference affine → LIF over ``out_t`` steps — in ONE
    fused Pallas dispatch (kernels/fused_pipeline.py), membrane resident in
    VMEM across T.

    Returns (spikes (out_t, N, H, W, C) f32 {0,1}, final membrane
    (N, H, W, C) f32) — drop-in for the unfused conv → ``tdbn_apply``
    (training=False) → ``lif_over_time`` chain, BIT-IDENTICAL to it (same
    float ops in the same order; integer conv accumulation is
    order-independent). Dispatch tiling comes from ``lp.tile``
    (autotuned). The 8-bit encoding layer runs through :func:`run_encode`.

    ``affine``: optional precomputed parameter bundle (see
    :func:`precompute_affines`) — compile-once callers hoist the per-layer
    bundle build out of the frame loop; when None it is built inline from
    the gamma/beta/mean/var arguments (identical values either way)."""
    assert lp.in_bits == 1, "the encoding layer runs through run_encode"
    bh, bw = cfg.block_hw
    interpret = getattr(cfg, "kernel_interpret", None)
    if affine is None:
        affine = kops.affine_bundle(lp.packed, lp.scale, mean, var, gamma, beta)
    return kops.fused_conv_bn_lif(
        x_t,
        lp.packed,
        affine,
        v0=v0,
        out_t=out_t,
        bn_scale=1.0 * cfg.threshold,  # tdbn_apply's alpha(=1)·threshold
        threshold=cfg.threshold,
        leak=cfg.leak,
        reset=getattr(cfg, "reset", "hard"),
        v_init=getattr(cfg, "v_init", 0.0),
        bh=bh,
        bw=bw,
        nbt=lp.tile.nbt,
        mrows=lp.tile.mrows,
        mcols=lp.tile.mcols,
        interpret=interpret,
    )


def run_encode(
    x_t: jax.Array,
    lp: CompressedLayerPlan,
    cfg,
    *,
    gamma: jax.Array,
    beta: jax.Array,
    mean: jax.Array,
    var: jax.Array,
    v0: jax.Array | None,
    out_t: int,
    affine: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """The 8-bit encoding layer (``x_t``: (1, N, H, W, 3) frames in [0, 1])
    in ONE lane-dense Pallas dispatch (kernels/encode_pipeline.py): u8
    quantisation (:func:`_quantize_input_u8`'s f32 ops, in the kernel), the
    conv over the u8 values — the exact fold of the 8 bit-serial planes —
    FXP rescale, tdBN affine and LIF over ``out_t`` steps from one drive.

    Returns lane-dense (spikes (out_t, N, H, W·C) int8 {0,1}, membrane
    (N, H, W·C) f32): NHWC element order, bit-identical to the unfused
    chain's on finite frames (``kops.lane_dense_nhwc`` gives the NHWC
    shape). ``v0`` may be either shape."""
    assert lp.in_bits == 8 and x_t.shape[0] == 1, (lp.in_bits, x_t.shape)
    bh, bw = cfg.block_hw
    if affine is None:
        affine = kops.affine_bundle(
            lp.packed, lp.scale / 255.0, mean, var, gamma, beta
        )
    return kops.encode_conv_bn_lif(
        x_t[0],
        lp.w_q,
        affine,
        v0=v0,
        out_t=out_t,
        bn_scale=1.0 * cfg.threshold,  # tdbn_apply's alpha(=1)·threshold
        threshold=cfg.threshold,
        leak=cfg.leak,
        reset=getattr(cfg, "reset", "hard"),
        v_init=getattr(cfg, "v_init", 0.0),
        bh=bh,
        bw=bw,
        interpret=getattr(cfg, "kernel_interpret", None),
    )


@register_conv_executor("pallas")
def _exec_pallas(x_t: jax.Array, lp: CompressedLayerPlan, cfg) -> jax.Array:
    """Compressed Pallas kernel. T (and bit-serial planes for the 8-bit
    encoding layer) fold into the kernel's spatial-block grid, so the whole
    (T·N·blocks) volume is ONE pallas_call.

    Pointwise (1×1) spike layers — the detection head — bypass the kernel:
    with no spatial taps to gate and no halo, the blocked dispatch is pure
    layout overhead around a single channel contraction, so the executor
    contracts in place (integer-valued f32 matmul — bit-identical to the
    kernel's accumulation, which the conformance suite asserts)."""
    bh, bw = cfg.block_hw
    interpret = getattr(cfg, "kernel_interpret", None)
    x, tn = _fold_t(x_t)
    if lp.in_bits != 8 and lp.w_q.shape[0] == 1 and lp.w_q.shape[1] == 1:
        y = (x @ lp.w_q[0, 0].astype(jnp.float32)) * lp.scale
        return _unfold_t(y, tn)
    if lp.in_bits == 8:
        planes = bitserial.to_bitplanes(_quantize_input_u8(x))  # (8, TN, H, W, C)
        bits, m = planes.shape[0], planes.shape[1]
        flat = planes.reshape((bits * m,) + planes.shape[2:])
        acc = kops.gated_conv(flat.astype(jnp.int8), lp.packed, bh=bh, bw=bw, interpret=interpret)
        acc = acc.reshape((bits, m) + acc.shape[1:])
        weights = (2 ** jnp.arange(bits, dtype=jnp.int32)).reshape(bits, 1, 1, 1, 1)
        y_int = jnp.sum(acc * weights, axis=0)
        y = y_int.astype(jnp.float32) * (lp.scale / 255.0)
    else:
        acc = kops.gated_conv(x.astype(jnp.int8), lp.packed, bh=bh, bw=bw, interpret=interpret)
        y = acc.astype(jnp.float32) * lp.scale
    return _unfold_t(y, tn)
