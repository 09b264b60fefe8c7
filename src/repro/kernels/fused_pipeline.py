"""Fused layer-pipeline Pallas kernel (the whole per-layer dataflow in one
dispatch): gated one-to-all conv → FXP rescale → tdBN (inference affine) →
LIF spike/reset, for ALL T time steps, with the membrane accumulator
resident in VMEM scratch across the T loop.

Why fusion is the paper's real speedup
--------------------------------------
The ASIC never materializes per-time-step activations off-chip: spikes flow
PE→PE and the membrane potential lives in PE registers for the whole T loop.
The unfused executor pipeline pays exactly that cost in software — every
layer round-trips (T, N, H, W, C) activations and LIF membranes through HBM
between a conv `pallas_call`, an XLA tdBN, and an XLA LIF scan. This kernel
collapses the full per-layer pipeline into ONE `pallas_call`:

    for t in range(T):                      # static unrolled, T ≤ 4
        acc   = Σ_tap spikes_t ⋆ W[tap]     # MXU dot per live tap, exact
        y     = acc * fxp_scale             # FXP8 dequant (once, exact)
        y     = c·((y − μ)·rsqrt(σ²+ε))·γ+β # tdBN inference affine
        v     = v·leak + y                  # LIF — v NEVER leaves VMEM
        s_t   = v ≥ θ ; v *= (1 − s_t)      # spike + hard reset

Bit-exactness contract: every float op above is the SAME op in the SAME
order as the unfused `core.plan` → `core.lif.tdbn_apply` → `core.lif.
lif_over_time` pipeline (integer conv accumulation is order-independent;
the affine/LIF chain is element-wise), so fused output is BIT-IDENTICAL to
the dense oracle — tests/conformance/ asserts it against the goldens.

Mixed time steps: a layer with in_T=1, out_T=T (the paper's §II-A mixed
schedule, e.g. conv_block) computes the conv ONCE and reuses the rescaled+
normalized drive for every LIF step — the membrane loop is the only per-T
work.

The 8-bit encoding layer runs in a kernel of its own
(kernels/encode_pipeline.py): this one serves the binary spike layers.

Grid/tiling: grid = (K-blocks, spatial macro-tiles) — K outer, spatial
inner, the paper's KTBC order, so compressed weights are decoded once per
K-block and reused across every spatial tile and time step. Each grid step
processes a MACRO-TILE of ``bpg = mrows·mcols`` spatial blocks (a whole
row of blocks, or an r×c block group — the host layout in ops.py makes
the group contiguous along the block axis): the step runs ``bpg//nbt``
groups of ``nbt`` stacked blocks, each group one MXU dot per live tap
followed by the FXP rescale, tdBN affine and LIF update over its rows.
Macro-tiles cut the number of grid steps, and so their fixed per-step
cost, at large inputs.
Blocks stay independent (each carries its own replicate-padded halo), so
any macro shape is bit-exact with the one-block-per-step dispatch.
``(kblk, nbt, mrows×mcols)`` are the per-layer-shape autotuning knobs
swept by `kernels/autotune.py`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import auto_interpret, refuse_compiled_decoder

# rows of the per-K-block affine parameter bundle (see _affine_bundle in
# ops.py): FXP scale, tdBN mean, rsqrt(var+eps), gamma, beta
AFFINE_ROWS = 5


def _rounded(x: jax.Array) -> jax.Array:
    """Mark ``x`` as a value whose rounded f32 bit pattern the reference
    chain materializes (a product that feeds an add/sub).

    Inside one fused computation XLA/LLVM contracts ``a*b + c`` into an FMA
    (single rounding). On the CPU backend this happens at codegen, below
    HLO, and is measured to survive EVERY in-graph barrier — a bitcast
    round-trip, even ``optimization_barrier`` — so this marker cannot (and
    does not need to) pin eager per-op rounding. What keeps the executors
    bit-identical is that the production dense/gated references are jitted
    graphs of the same ops, so XLA contracts them the same way; the
    conformance suite asserts that end-to-end parity at 0.0. The bitcast
    round-trip is kept because on an actual TPU lowering (Mosaic, not
    interpret mode) the integer view does force materialization, keeping
    the kernel's rounding aligned with its jitted references there too."""
    return jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(x, jnp.int32), jnp.float32
    )


def bn_drive(acc, scale, mean, rinv, gamma, beta, bn_scale: float):
    """FXP rescale of the integer conv accumulator, then the tdBN inference
    affine — op-for-op the unfused core.plan executor + core.lif.tdbn_apply
    (training=False). _rounded pins every product that feeds an add/sub —
    see its docstring: without it XLA contracts mul+add into FMAs, a silent
    1-ulp drift that can flip spikes sitting exactly at threshold. Shared
    by every fused kernel, so all of them run the same chain."""
    y_all = _rounded(acc * scale)
    x_hat = _rounded((y_all - mean) * rinv)
    return _rounded((bn_scale * x_hat) * gamma) + beta


def lif_step(v, y, *, threshold: float, leak: float, reset: str):
    """One LIF step on a tile: leak, integrate the drive ``y``, spike at
    ``threshold``, reset. Returns (spiked bool, new membrane)."""
    v = _rounded(v * leak) + y
    spiked = v >= threshold
    if reset == "soft":
        # reset by subtraction: where(s, v−θ, v) ≡ v − s·θ for s ∈ {0,1}
        # (s·θ is exactly 0 or θ, so one subtraction either way —
        # bit-identical to core.lif.lif_step's soft branch)
        return spiked, jnp.where(spiked, v - threshold, v)
    # hard reset: where(s, 0, v) ≡ v·(1−s) for s ∈ {0,1} (no arithmetic →
    # no rounding, so no _rounded barrier needed; ±0.0 both propagate as
    # exact zero through v·leak + y)
    return spiked, jnp.where(spiked, 0.0, v)


def _kernel(
    spikes_ref,  # VMEM (t_in, bpg, BH+2p, BW+2p, C) int8
    *refs,  # packed mode: maskp, vals, affine, v0, spk, mem, wdense + xs scratch
    #         predecoded mode: wdense, affine, v0, spk, mem + xs scratch
    taps: int,
    kw: int,
    bh: int,
    bw: int,
    bpg: int,  # spatial blocks per grid step (the macro-tile, mrows·mcols)
    nbt: int,  # blocks stacked per MXU dot; divides bpg
    t_in: int,
    t_out: int,
    tap_alive: tuple,  # taps with any nonzero weight (static, pack-time)
    bn_scale: float,  # alpha * threshold (tdBN), a trace-time constant
    threshold: float,
    leak: float,
    reset: str,
    predecode: bool,
):
    if predecode:
        # decoder stage already ran (static weights decode once, at plan/
        # trace time — see fused_conv_bn_lif); the kernel consumes the
        # VMEM-resident dense K-block directly
        wdense_ref, affine_ref, v0_ref, spk_ref, mem_ref, xs_ref = refs
    else:
        (maskp_ref, vals_ref, affine_ref, v0_ref, spk_ref, mem_ref,
         wdense_ref, xs_ref) = refs
        nbg = pl.program_id(1)  # spatial group index (innermost)

        # ---- decode compressed weights once per K-block (paper: weights
        # stay resident on-chip, reused across tiles and time steps).
        # Interpret mode only (see backend.refuse_compiled_decoder). ----
        @pl.when(nbg == 0)
        def _decode():
            words = maskp_ref[0]  # (taps, C//8, KBLK) uint8
            c8 = words.shape[1]
            kblk = words.shape[2]
            expanded = jnp.repeat(words, 8, axis=1)  # (taps, C, KBLK)
            shifts = (
                jax.lax.broadcasted_iota(jnp.int32, (taps, c8 * 8, kblk), 1) % 8
            ).astype(jnp.uint8)
            bits = ((expanded >> shifts) & 1).astype(jnp.int32)
            flat = bits.reshape(-1)
            idx = jnp.cumsum(flat) - 1  # position into packed values
            vals = vals_ref[0]
            gathered = jnp.take(vals, jnp.clip(idx, 0, vals.shape[0] - 1), axis=0)
            dense = jnp.where(flat > 0, gathered.astype(jnp.int32), 0)
            wdense_ref[...] = dense.reshape(taps, c8 * 8, kblk).astype(jnp.int8)

    kblk = wdense_ref.shape[-1]
    cin = spikes_ref.shape[-1]
    rows = nbt * bh * bw  # membrane/output rows of one dot group

    # The spike tile is widened to f32 once, in VMEM scratch: Mosaic slices
    # f32 windows at any (row, column) offset, where int8 windows at the
    # column offsets tap % kw are refused. Each window is cast to bf16 after
    # slicing, so every dot is a single-pass bf16 MXU dot with f32
    # accumulation: spikes {0,1} and int8 weights are exact in bf16, and
    # every partial sum stays below 2^24 (live·C·127), so the f32
    # accumulation is integer-exact in any order.
    xs_ref[...] = spikes_ref[...].astype(jnp.float32)
    # predecoded input carries a leading (1,) K-block axis; scratch doesn't
    w_tap = [
        (wdense_ref[0, t] if predecode else wdense_ref[t]).astype(jnp.bfloat16)
        for t in tap_alive
    ]

    scale = affine_ref[0, 0:1, :]  # (1, KBLK) — FXP scale (row-broadcast)
    mean = affine_ref[0, 1:2, :]
    rinv = affine_ref[0, 2:3, :]  # rsqrt(var + eps), precomputed
    gamma = affine_ref[0, 3:4, :]
    beta = affine_ref[0, 4:5, :]

    # ---- the macro-tile runs as bpg//nbt dot groups. Each group is one
    # (t_in·nbt·bh·bw, C)×(C, KBLK) MXU dot per live tap (the shifted
    # window of that tap, read straight from the widened tile), summed —
    # then the FXP rescale, tdBN affine and LIF over T run on the group's
    # rows and store them. Dead taps (every weight pruned — common for the
    # 80%-pruned 3×3 kernels) are dropped at TRACE time via ``tap_alive``
    # (liveness is a pack-time property, so no runtime cond). Every step
    # after the dot is element-wise, so running it per group is
    # bit-identical to running it over the whole macro-tile. ----
    for g0 in range(0, bpg, nbt):  # static unroll: bpg//nbt dot groups
        acc = jnp.zeros((t_in * rows, kblk), jnp.float32)
        for j, tap in enumerate(tap_alive):
            dy, dx = tap // kw, tap % kw
            win = xs_ref[:, g0 : g0 + nbt, dy : dy + bh, dx : dx + bw, :]
            acc = acc + jax.lax.dot_general(
                win.reshape(t_in * rows, cin).astype(jnp.bfloat16),
                w_tap[j],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        drives = bn_drive(acc, scale, mean, rinv, gamma, beta, bn_scale)

        r0 = g0 * bh * bw
        v = v0_ref[r0 : r0 + rows, :]
        for t in range(t_out):  # T ≤ 4: unrolled, v stays in VREGs/VMEM
            # mixed time steps (in_T=1 → out_T=T): one conv drive, T LIF steps
            y = drives[0:rows] if t_in == 1 else drives[t * rows : (t + 1) * rows]
            spiked, v = lif_step(v, y, threshold=threshold, leak=leak, reset=reset)
            spk_ref[t, r0 : r0 + rows, :] = spiked.astype(jnp.int8)
        mem_ref[r0 : r0 + rows, :] = v


def fused_pipeline_pallas(
    spike_blocks: jax.Array,  # (t_in, NB, BH+2p, BW+2p, C) int8
    maskp: jax.Array | None,  # (KB, taps, C//8, KBLK) uint8 (packed mode)
    vals: jax.Array | None,  # (KB, VPAD) int8 (packed mode)
    affine: jax.Array,  # (KB, AFFINE_ROWS, KBLK) f32
    v0_rows: jax.Array,  # (NB·BH·BW, KB*KBLK) f32
    *,
    kh: int,
    kw: int,
    bh: int,
    bw: int,
    kblk: int,
    nbt: int,
    t_out: int,
    tap_alive: tuple,
    bn_scale: float,
    threshold: float,
    leak: float,
    reset: str = "hard",
    bpg: int | None = None,  # macro-tile: blocks per grid step (default nbt)
    wdense: jax.Array | None = None,  # (KB, taps, C, KBLK) int8 (predecoded)
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """One fused dispatch for a whole layer. Returns
    (spikes (t_out, NB·BH·BW, KB*KBLK) int8, membrane (NB·BH·BW, KB*KBLK) f32),
    rows ordered (block, bh, bw) — the same order as ``v0_rows``.

    Weights arrive either compressed (``maskp``/``vals`` — the kernel runs
    the bitmask decoder once per K-block, the paper's on-chip decode) or
    predecoded (``wdense`` — the decoder stage ran ahead of the kernel; for
    static inference weights it then runs once per COMPILE, not per frame).
    Both modes compute bit-identically in interpret mode; only the
    predecoded mode lowers for the TPU, so compressed weights with
    ``interpret=False`` raise.

    ``bpg`` spatial blocks — the macro-tile, e.g. mrows·mcols contiguous
    blocks of the block grid (callers order/pad the block axis so each
    macro group is contiguous and bpg divides NB) — are processed per grid
    step; within a step the conv runs as ``bpg//nbt`` groups of ``nbt``
    stacked blocks each. Grid order is K-blocks outer / macro-tiles inner
    so the decoded weight block is reused across every spatial tile and
    time step.
    """
    interpret = auto_interpret(interpret)
    predecode = wdense is not None
    if not predecode:
        refuse_compiled_decoder("fused_pipeline_pallas(maskp, vals)", interpret)
    t_in, nb_total, ph, pw, cin = spike_blocks.shape
    if bpg is None:
        bpg = nbt
    if predecode:
        kb_total, taps, cin_, kblk_ = wdense.shape
        assert cin_ == cin, (cin_, cin)
    else:
        kb_total, taps, c8, kblk_ = maskp.shape
        assert c8 * 8 == cin
    assert kblk_ == kblk and taps == kh * kw
    assert ph == bh + kh - 1 and pw == bw + kw - 1
    assert bpg % nbt == 0, (bpg, nbt)
    assert nb_total % bpg == 0, (nb_total, bpg)
    assert t_in == t_out or t_in == 1, (t_in, t_out)
    assert affine.shape == (kb_total, AFFINE_ROWS, kblk)
    assert spike_blocks.dtype == jnp.int8, spike_blocks.dtype
    m = bpg * bh * bw  # membrane/output rows per grid step

    xs_scratch = pltpu.VMEM((t_in, bpg, ph, pw, cin), jnp.float32)
    if predecode:
        w_specs = [pl.BlockSpec((1, taps, cin, kblk), lambda kb, nb: (kb, 0, 0, 0))]
        w_inputs = (wdense,)
        scratch = [xs_scratch]
    else:
        w_specs = [
            pl.BlockSpec((1, taps, cin // 8, kblk), lambda kb, nb: (kb, 0, 0, 0)),
            pl.BlockSpec((1, vals.shape[1]), lambda kb, nb: (kb, 0)),
        ]
        w_inputs = (maskp, vals)
        scratch = [pltpu.VMEM((taps, cin, kblk), jnp.int8), xs_scratch]

    grid = (kb_total, nb_total // bpg)  # K outer, macro inner → KTBC order
    spk, mem = pl.pallas_call(
        functools.partial(
            _kernel,
            taps=taps,
            kw=kw,
            bh=bh,
            bw=bw,
            bpg=bpg,
            nbt=nbt,
            t_in=t_in,
            t_out=t_out,
            tap_alive=tuple(tap_alive),
            bn_scale=bn_scale,
            threshold=threshold,
            leak=leak,
            reset=reset,
            predecode=predecode,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((t_in, bpg, ph, pw, cin), lambda kb, nb: (0, nb, 0, 0, 0)),
            *w_specs,
            pl.BlockSpec((1, AFFINE_ROWS, kblk), lambda kb, nb: (kb, 0, 0)),
            pl.BlockSpec((m, kblk), lambda kb, nb: (nb, kb)),
        ],
        out_specs=[
            pl.BlockSpec((t_out, m, kblk), lambda kb, nb: (0, nb, kb)),
            pl.BlockSpec((m, kblk), lambda kb, nb: (nb, kb)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t_out, nb_total * bh * bw, kb_total * kblk), jnp.int8),
            jax.ShapeDtypeStruct((nb_total * bh * bw, kb_total * kblk), jnp.float32),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
    )(spike_blocks, *w_inputs, affine, v0_rows)
    return spk, mem
