"""Pallas kernel validation: interpret-mode execution vs pure-jnp oracles,
swept over shapes/dtypes/sparsity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.bitmask_matmul import pack_weights


def _sparse_int8_weights(key, kh, kw, cin, k, density):
    rng = np.random.default_rng(key)
    w = rng.integers(-127, 128, (kh, kw, cin, k)).astype(np.int8)
    mask = rng.random((kh, kw, cin, k)) < density
    return (w * mask).astype(np.int8)


class TestGatedOneToAllKernel:
    @pytest.mark.parametrize(
        "cin,k,density",
        [(8, 16, 0.2), (16, 8, 0.5), (3, 40, 0.3), (32, 32, 0.05), (8, 8, 1.0)],
    )
    def test_matches_block_conv_3x3(self, cin, k, density):
        w = _sparse_int8_weights(cin * 7 + k, 3, 3, cin, k, density)
        pw = ops.pack_conv_weights(w, kblk=8)
        rng = np.random.default_rng(0)
        spikes = jnp.asarray(rng.integers(0, 2, (2, 18, 32, cin)), jnp.int8)
        got = ops.gated_conv(spikes, pw)
        want = ref.gated_conv_ref(spikes, jnp.asarray(w))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=0.5)

    def test_1x1_kernel(self):
        w = _sparse_int8_weights(3, 1, 1, 16, 24, 0.7)
        pw = ops.pack_conv_weights(w, kblk=8)
        spikes = jnp.asarray(np.random.default_rng(1).integers(0, 2, (1, 18, 32, 16)), jnp.int8)
        got = ops.gated_conv(spikes, pw)
        want = ref.gated_conv_ref(spikes, jnp.asarray(w))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=0.5)

    def test_multi_spatial_blocks(self):
        """Input larger than one 32×18 tile → independent block conv."""
        w = _sparse_int8_weights(9, 3, 3, 8, 16, 0.3)
        pw = ops.pack_conv_weights(w, kblk=16)
        spikes = jnp.asarray(np.random.default_rng(2).integers(0, 2, (2, 36, 64, 8)), jnp.int8)
        got = ops.gated_conv(spikes, pw)
        want = ref.gated_conv_ref(spikes, jnp.asarray(w))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=0.5)

    def test_all_zero_weights(self):
        w = np.zeros((3, 3, 8, 8), np.int8)
        pw = ops.pack_conv_weights(w, kblk=8)
        spikes = jnp.ones((1, 18, 32, 8), jnp.int8)
        got = ops.gated_conv(spikes, pw)
        assert np.all(np.asarray(got) == 0)

    def test_multiple_k_blocks(self):
        w = _sparse_int8_weights(5, 3, 3, 8, 40, 0.25)
        pw = ops.pack_conv_weights(w, kblk=16)  # 40 -> 3 blocks of 16
        assert pw.maskp.shape[0] == 3
        spikes = jnp.asarray(np.random.default_rng(3).integers(0, 2, (1, 18, 32, 8)), jnp.int8)
        got = ops.gated_conv(spikes, pw)
        want = ref.gated_conv_ref(spikes, jnp.asarray(w))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=0.5)

    def test_compressed_bytes_smaller_than_dense(self):
        w = _sparse_int8_weights(11, 3, 3, 64, 64, 0.2)
        pw = ops.pack_conv_weights(w, kblk=64)
        dense_bytes = w.size
        assert pw.compressed_bytes < 0.5 * dense_bytes  # ~0.325 at 20% density


class TestFusedLIFKernel:
    @pytest.mark.parametrize("t,m,c", [(3, 100, 16), (1, 7, 8), (4, 600, 32)])
    def test_matches_scan_oracle(self, t, m, c):
        x = jax.random.normal(jax.random.PRNGKey(t * m), (t, m, c))
        got = ops.fused_lif(x)
        want = ref.fused_lif_ref(x)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_threshold_leak_variants(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (3, 64, 8)) * 0.5
        got = ops.fused_lif(x, threshold=0.3, leak=0.5)
        want = np.asarray(
            ref.fused_lif_ref(x, threshold=0.3, leak=0.5)
            if False
            else None
        )
        from repro.core import lif as lifm

        spikes, _ = lifm.lif_over_time(x, threshold=0.3, leak=0.5)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(spikes.astype(jnp.int8)))


class TestFusedPipelineKernel:
    """The fused conv→FXP→tdBN→LIF dispatch vs the unfused op chain it
    replaces, bit-for-bit — and predecode (decoder stage hoisted to trace
    time) vs in-kernel decode, which must be indistinguishable."""

    def _setup(self, *, kh, cin, kout, t_in, t_out, h=12, w=16, bh=6, bw=8,
               seed=0):
        from repro.core import block_conv as bc
        from repro.core import lif as lifm

        rng = np.random.default_rng(seed)
        w_int = _sparse_int8_weights(seed + 1, kh, kh, cin, kout, 0.3)
        pw = ops.pack_conv_weights(w_int, kblk=8)
        scale = jnp.float32(1.0 / 128)
        mean = jnp.asarray(rng.normal(size=kout), jnp.float32)
        var = jnp.asarray(rng.random(kout) + 0.5, jnp.float32)
        gamma = jnp.asarray(rng.normal(size=kout), jnp.float32)
        beta = jnp.asarray(rng.normal(size=kout), jnp.float32)
        affine = ops.affine_bundle(pw, scale, mean, var, gamma, beta)
        x_t = jnp.asarray(rng.integers(0, 2, (t_in, 2, h, w, cin)), jnp.float32)
        thr, leak = 0.5, 0.25

        def unfused(x_t):
            """The op chain the kernel replaces — conv → FXP scale → tdBN
            (training=False) → hard-reset LIF — run EAGERLY, op by op: each
            primitive is its own dispatch and rounds separately. This is the
            strictest reference there is: inside any jitted graph XLA/LLVM
            contracts mul+add into FMAs (single rounding) and no in-graph
            barrier stops it on CPU, so the fused kernel's *membranes* may
            sit a few ulp off this chain while its integer surfaces (conv
            accumulators, spike trains) are exact by construction."""
            t, n = x_t.shape[:2]
            y = bc.block_conv2d(
                x_t.reshape((t * n,) + x_t.shape[2:]),
                jnp.asarray(w_int, jnp.float32), block_h=bh, block_w=bw,
            ) * scale
            y = y.reshape((t, n) + y.shape[1:])
            p = lifm.TdBNParams(gamma=gamma, beta=beta)
            st = lifm.TdBNState(mean=mean, var=var, count=jnp.zeros((), jnp.int32))
            y, _ = lifm.tdbn_apply(p, st, y, threshold=thr, training=False)
            if t == 1 and t_out > 1:
                y = jnp.broadcast_to(y, (t_out,) + y.shape[1:])
            v = jnp.zeros(y.shape[1:], jnp.float32)
            spikes = []
            for k in range(t_out):  # eager LIF: mul, add, cmp, where — one
                v = v * leak + y[k]  # dispatch each, like lif_step unfused
                s = (v >= thr).astype(jnp.float32)
                spikes.append(s)
                v = jnp.where(s > 0, 0.0, v)
            return jnp.stack(spikes), v

        def fused(x_t, predecode):
            return ops.fused_conv_bn_lif(
                x_t, pw, affine, v0=None, out_t=t_out, bn_scale=thr,
                threshold=thr, leak=leak, bh=bh, bw=bw,
                nbt=2, predecode=predecode,
            )

        return x_t, unfused, fused

    @pytest.mark.parametrize(
        "kh,cin,kout,t_in,t_out",
        [(3, 8, 16, 2, 2), (1, 16, 8, 3, 3), (3, 8, 8, 1, 3)],
    )
    def test_matches_unfused_chain(self, kh, cin, kout, t_in, t_out):
        """Spike trains must be BIT-EXACT against the eager unfused chain;
        membranes within a few ulp (FMA contraction inside the fused graph
        single-rounds mul+add where the eager chain rounds twice — see the
        unfused docstring). Exact membrane parity against the *production*
        dense executor — where both sides are jitted and contract
        identically — is asserted at 0.0 diff by the conformance suite."""
        x_t, unfused, fused = self._setup(
            kh=kh, cin=cin, kout=kout, t_in=t_in, t_out=t_out
        )
        spk_w, mem_w = unfused(x_t)  # eagerly, NOT jitted — see docstring
        spk_g, mem_g = fused(x_t, predecode=True)
        np.testing.assert_array_equal(np.asarray(spk_g), np.asarray(spk_w))
        np.testing.assert_allclose(
            np.asarray(mem_g), np.asarray(mem_w), atol=1e-6, rtol=0
        )

    @pytest.mark.parametrize(
        "kh,cin,kout,t_in,t_out",
        [(3, 8, 16, 2, 2), (1, 16, 8, 3, 3), (3, 3, 8, 1, 3)],
    )
    def test_predecode_equals_in_kernel_decode(self, kh, cin, kout, t_in, t_out):
        """The docstring promise: decoder-in-kernel (streaming weights) and
        predecoded (static weights, decode at trace time) are bit-identical."""
        x_t, _, fused = self._setup(
            kh=kh, cin=cin, kout=kout, t_in=t_in, t_out=t_out
        )
        spk_p, mem_p = fused(x_t, predecode=True)
        spk_k, mem_k = fused(x_t, predecode=False)
        np.testing.assert_array_equal(np.asarray(spk_p), np.asarray(spk_k))
        np.testing.assert_array_equal(np.asarray(mem_p), np.asarray(mem_k))

    def test_encode_in_bits8_matches_bitserial_reference(self):
        """The lane-dense encode kernel over u8 values ≡ the literal 8-plane
        bit-serial accumulation (conv linearity over exact integers), run
        through the eager unfused affine/LIF chain: spikes bit-exact,
        membranes within the eager chain's few ulp (see ``unfused``)."""
        from repro.core import bitserial, block_conv as bc
        from repro.core import lif as lifm

        rng = np.random.default_rng(3)
        w_int = _sparse_int8_weights(4, 3, 3, 3, 8, 0.5)
        pw = ops.pack_conv_weights(w_int, kblk=8)
        scale = jnp.float32(1.0 / 128 / 255)
        mean, var, gamma, beta = (
            jnp.asarray(v, jnp.float32) for v in (
                rng.normal(size=8) * 8, rng.random(8) * 64 + 1,
                rng.normal(size=8), rng.normal(size=8)))
        affine = ops.affine_bundle(pw, scale, mean, var, gamma, beta)
        x_u8 = jnp.asarray(rng.integers(0, 256, (2, 12, 16, 3)), jnp.uint8)
        spk, mem = ops.encode_conv_bn_lif(
            x_u8 / jnp.float32(255), jnp.asarray(w_int), affine, v0=None,
            out_t=2, bn_scale=0.5,
            threshold=0.5, leak=0.25, bh=6, bw=8)

        planes = bitserial.to_bitplanes(x_u8).astype(jnp.float32)
        acc = sum((2**b) * bc.block_conv2d(
            planes[b], jnp.asarray(w_int, jnp.float32), block_h=6, block_w=8)
            for b in range(8))
        p = lifm.TdBNParams(gamma=gamma, beta=beta)
        st = lifm.TdBNState(mean=mean, var=var, count=jnp.zeros((), jnp.int32))
        y, _ = lifm.tdbn_apply(p, st, (acc * scale)[None], threshold=0.5,
                               training=False)
        v = jnp.zeros(y.shape[1:], jnp.float32)
        for t in range(2):
            v = v * 0.25 + y[0]
            s_t = v >= 0.5
            np.testing.assert_array_equal(
                np.asarray(ops.lane_dense_nhwc(spk[t], 8)), np.asarray(s_t))
            v = jnp.where(s_t, 0.0, v)
        assert 0 < float(jnp.mean(spk)) < 1  # neither silent nor saturated
        np.testing.assert_allclose(np.asarray(ops.lane_dense_nhwc(mem, 8)),
                                   np.asarray(v), atol=1e-6, rtol=0)


class TestMacroTileFusedPipeline:
    """Macro-tiling (mrows×mcols blocks per grid step) is pure dispatch
    layout: every macro shape — including ragged ones that zero-pad the
    block grid — must be BIT-identical to the single-block-per-step path,
    for both reset modes and any T."""

    def _run(self, *, h, w, t_in, t_out, reset, mrows, mcols, nbt=None,
             bh=6, bw=8, kh=3, cin=8, kout=16, seed=0, v0=None):
        rng = np.random.default_rng(seed)
        w_int = _sparse_int8_weights(seed + 1, kh, kh, cin, kout, 0.3)
        pw = ops.pack_conv_weights(w_int, kblk=8)
        affine = ops.affine_bundle(
            pw,
            jnp.float32(1.0 / 128),
            jnp.asarray(rng.normal(size=kout), jnp.float32),
            jnp.asarray(rng.random(kout) + 0.5, jnp.float32),
            jnp.asarray(rng.normal(size=kout), jnp.float32),
            jnp.asarray(rng.normal(size=kout), jnp.float32),
        )
        x_t = jnp.asarray(rng.integers(0, 2, (t_in, 2, h, w, cin)), jnp.float32)
        return ops.fused_conv_bn_lif(
            x_t, pw, affine, v0=v0, out_t=t_out, bn_scale=0.5, threshold=0.5,
            leak=0.25, reset=reset,
            bh=bh, bw=bw, nbt=nbt if nbt is not None else mrows * mcols,
            mrows=mrows, mcols=mcols,
        )

    @pytest.mark.parametrize("t_in,t_out", [(1, 1), (3, 3), (1, 3)])
    @pytest.mark.parametrize("reset", ["hard", "soft"])
    def test_macro_tile_bit_equals_single_block(self, t_in, t_out, reset):
        """2×2 macro-tile over an exactly-divisible 4×4 block grid vs the
        single-block baseline: spikes AND membranes bit-equal."""
        kw = dict(h=24, w=32, t_in=t_in, t_out=t_out, reset=reset)
        spk_b, mem_b = self._run(mrows=1, mcols=1, **kw)
        spk_m, mem_m = self._run(mrows=2, mcols=2, **kw)
        np.testing.assert_array_equal(np.asarray(spk_m), np.asarray(spk_b))
        np.testing.assert_array_equal(np.asarray(mem_m), np.asarray(mem_b))

    @pytest.mark.parametrize("mrows,mcols", [(2, 2), (1, 3), (3, 1), (4, 4)])
    def test_ragged_block_grid(self, mrows, mcols):
        """18×24 at 6×8 blocks is a 3×3 block grid — NOT divisible by any
        of these macro shapes, so whole zero blocks are padded in and
        stripped out. Still bit-exact (macros > grid clip to it)."""
        kw = dict(h=18, w=24, t_in=3, t_out=3, reset="hard")
        spk_b, mem_b = self._run(mrows=1, mcols=1, **kw)
        spk_m, mem_m = self._run(mrows=mrows, mcols=mcols, **kw)
        np.testing.assert_array_equal(np.asarray(spk_m), np.asarray(spk_b))
        np.testing.assert_array_equal(np.asarray(mem_m), np.asarray(mem_b))

    def test_dot_granularity_inside_macro(self):
        """nbt (blocks per MXU dot) sweeps independently of the macro
        shape; every divisor of the macro-tile size is bit-equal."""
        kw = dict(h=24, w=32, t_in=3, t_out=3, reset="soft")
        spk_b, mem_b = self._run(mrows=1, mcols=1, **kw)
        for nbt in (1, 2, 4):
            spk_m, mem_m = self._run(mrows=2, mcols=2, nbt=nbt, **kw)
            np.testing.assert_array_equal(np.asarray(spk_m), np.asarray(spk_b))
            np.testing.assert_array_equal(np.asarray(mem_m), np.asarray(mem_b))

    def test_warm_membrane_macro(self):
        """v0-carrying (streaming session) dispatch under a macro-tile."""
        rng = np.random.default_rng(7)
        v0 = jnp.asarray(rng.normal(size=(2, 24, 32, 16)) * 0.3, jnp.float32)
        kw = dict(h=24, w=32, t_in=3, t_out=3, reset="hard", v0=v0)
        spk_b, mem_b = self._run(mrows=1, mcols=1, **kw)
        spk_m, mem_m = self._run(mrows=4, mcols=2, **kw)
        np.testing.assert_array_equal(np.asarray(spk_m), np.asarray(spk_b))
        np.testing.assert_array_equal(np.asarray(mem_m), np.asarray(mem_b))

    def test_legacy_flat_nbt_maps_to_row_macro(self):
        """Bare nbt>1 with no macro shape keeps working (normalized to a
        1×nbt macro-tile) and stays bit-equal to nbt=1."""
        kw = dict(h=24, w=32, t_in=3, t_out=3, reset="hard")
        spk_b, mem_b = self._run(mrows=1, mcols=1, **kw)
        spk_f, mem_f = self._run(mrows=1, mcols=1, nbt=4, **kw)
        np.testing.assert_array_equal(np.asarray(spk_f), np.asarray(spk_b))
        np.testing.assert_array_equal(np.asarray(mem_f), np.asarray(mem_b))


class TestBitmaskMatmulKernel:
    @pytest.mark.parametrize(
        "m,k,n,density", [(32, 64, 48, 0.2), (100, 128, 64, 0.5), (16, 512, 256, 0.1)]
    )
    def test_matches_dense(self, m, k, n, density):
        rng = np.random.default_rng(m + k + n)
        w = rng.standard_normal((k, n)).astype(np.float32)
        w[rng.random((k, n)) >= density] = 0.0
        packed = pack_weights(w, kblk=min(64, k), nblk=min(32, n))
        x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
        got = ops.bitmask_matmul(x, packed, mblk=32)
        want = ref.bitmask_matmul_ref(x, jnp.asarray(w))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-3)

    def test_compression_ratio(self):
        rng = np.random.default_rng(0)
        w = rng.standard_normal((512, 512)).astype(np.float32)
        w[rng.random(w.shape) >= 0.2] = 0.0
        packed = pack_weights(w, kblk=128, nblk=128)
        dense_bytes = w.size * 4
        # f32 values: 0.2*4 bytes + 1/8 mask byte per element ≈ 0.93/4 of dense
        assert packed.compressed_bytes < 0.35 * dense_bytes


class TestCompiledDecoderRefused:
    """The in-kernel bitmask decoders have no TPU lowering: asked for a
    compiled kernel (``interpret=False``) they raise, before tracing any
    kernel, instead of returning something else."""

    @pytest.mark.parametrize("kernel", ["gated_conv", "fused_packed", "bitmask_matmul"])
    def test_raises_without_interpret(self, kernel):
        w = _sparse_int8_weights(1, 3, 3, 8, 8, 0.3)
        pw = ops.pack_conv_weights(w, kblk=8)
        spikes = jnp.ones((1, 12, 16, 8), jnp.float32)
        with pytest.raises(NotImplementedError, match="no TPU lowering"):
            if kernel == "gated_conv":
                ops.gated_conv(spikes.astype(jnp.int8), pw, bh=6, bw=8,
                               interpret=False)
            elif kernel == "fused_packed":
                affine = ops.affine_bundle(
                    pw, jnp.float32(1.0), *(jnp.ones(8, jnp.float32),) * 4)
                ops.fused_conv_bn_lif(
                    spikes[None], pw, affine, v0=None, out_t=1, bn_scale=0.5,
                    threshold=0.5, leak=0.25, bh=6, bw=8, predecode=False,
                    interpret=False)
            else:
                packed = pack_weights(np.eye(64, dtype=np.float32), kblk=64, nblk=64)
                ops.bitmask_matmul(jnp.ones((8, 64)), packed, interpret=False)


class TestFusedKernelVsDenseOracle:
    """The fused kernels in interpret mode — the blocked spike-layer body
    (tap-shifted windows, one MXU dot per live tap) and the lane-dense
    encode — against the dense executor's unfused conv → tdBN → LIF layer,
    both jitted through ``snn_yolo``'s layer functions, as the detector's
    forward runs them. Spikes and membranes (in NHWC element order) must be
    bit-identical for every layer kind the detector has."""

    @pytest.mark.parametrize(
        "kh,cin,kout,t_in,t_out,in_bits,tile",
        [
            (3, 8, 16, 3, 3, 1, (16, 1, 1, 1)),  # 3×3, one block per step
            (3, 16, 24, 1, 3, 1, (8, 2, 2, 2)),  # 3×3, T 1→3, 2×2 macro, 3 K-blocks
            (1, 24, 16, 3, 3, 1, (16, 4, 2, 2)),  # 1×1, macro-tile, one dot
            (3, 3, 16, 1, 1, 8, (16, 1, 1, 4)),  # encode: u8 input (no tiling)
            (3, 3, 8, 1, 3, 8, (8, 2, 2, 2)),  # rate-coded encode, T 1→3
        ],
    )
    def test_bit_identical_to_dense_layer(self, kh, cin, kout, t_in, t_out,
                                          in_bits, tile):
        import dataclasses

        from repro.core import plan as cplan
        from repro.kernels.autotune import TileConfig
        from repro.models import snn_yolo as sy

        rng = np.random.default_rng(kh * 100 + cin + t_in)
        cfg = sy.SNNDetConfig(input_hw=(24, 32), block_hw=(6, 8),
                              use_block_conv=True, conv_exec="pallas")
        w = rng.normal(size=(kh, kh, cin, kout)).astype(np.float32)
        w[rng.random(w.shape) < 0.6] = 0.0  # pruned, whole taps may die
        name = "encode" if in_bits == 8 else "layer"
        lp = cplan.build_layer_plan(name, jnp.asarray(w), in_bits=in_bits,
                                    tile=TileConfig(*tile))
        plan = cplan.DetectorPlan(layers={name: lp}, block_hw=cfg.block_hw)
        layer_p = {"w": jnp.asarray(w),
                   "gamma": jnp.asarray(rng.normal(size=kout), jnp.float32),
                   "beta": jnp.asarray(rng.normal(size=kout), jnp.float32)}
        layer_s = {"mean": jnp.asarray(rng.normal(size=kout) * 4, jnp.float32),
                   "var": jnp.asarray(rng.random(kout) * 16 + 1, jnp.float32),
                   "count": jnp.zeros((), jnp.int32)}
        if in_bits == 8:  # images on the u8 grid, the encode layer's input
            x_t = rng.integers(0, 256, (t_in, 2, 24, 32, cin)) / 255.0
        else:
            x_t = rng.integers(0, 2, (t_in, 2, 24, 32, cin))
        x_t = jnp.asarray(x_t, jnp.float32)

        def layer(c):
            if in_bits == 8:  # encode and its pool, as the forward runs them
                return jax.jit(lambda x: sy._encode_pool(
                    x, layer_p, layer_s, c, False, out_t=t_out, plan=plan,
                    v0=None, affine=None, taps=None)[1:])
            return jax.jit(lambda x: sy._conv_bn_act(
                x, layer_p, layer_s, c, False, out_t=t_out, name=name, plan=plan
            ))

        spk_p, _, mem_p = layer(cfg)(x_t)
        spk_d, _, mem_d = layer(dataclasses.replace(cfg, conv_exec="dense"))(x_t)
        if in_bits == 8:
            mem_p = ops.lane_dense_nhwc(mem_p, kout)
        assert spk_p.shape == (t_out, 2, 24, 32, kout)
        assert 0 < float(spk_d.mean()) < 1  # spikes neither silent nor saturated
        np.testing.assert_array_equal(np.asarray(spk_p), np.asarray(spk_d))
        np.testing.assert_array_equal(np.asarray(mem_p), np.asarray(mem_d))


class TestLaneDenseEncode:
    """The lane-dense encode kernel (interpret mode) against the dense
    executor's unfused encode layer, both jitted through
    ``snn_yolo._encode_pool``: spikes, membranes (NHWC element order) and
    pooled spikes bit-identical over 3 frames with the membrane carried,
    frames at 0 and 255, and ±127 weights on every tap of two channels so
    the folded edge entries of the band matrices reach ±254."""

    @staticmethod
    def _encode(rng, *, hw, block, kout=16, reset, v_init, out_t):
        import dataclasses

        from repro.core import plan as cplan
        from repro.kernels import encode_pipeline as ep
        from repro.models import snn_yolo as sy

        cfg = sy.SNNDetConfig(input_hw=hw, block_hw=block, use_block_conv=True,
                              conv_exec="pallas", reset=reset, v_init=v_init)
        w = rng.normal(size=(3, 3, 3, kout)).astype(np.float32)
        w[rng.random(w.shape) < 0.6] = 0.0
        top = np.abs(w).max()
        w[..., 0], w[..., 1] = top, -top  # quantize to +127 / -127
        lp = cplan.build_layer_plan("encode", jnp.asarray(w), in_bits=8)
        bands = ep.band_matrices(np.asarray(lp.w_q), block[1])
        assert np.abs(bands).max() == 254
        plan = cplan.DetectorPlan(layers={"encode": lp}, block_hw=block)
        layer_p = {"w": jnp.asarray(w),
                   "gamma": jnp.asarray(rng.normal(size=kout), jnp.float32),
                   "beta": jnp.asarray(rng.normal(size=kout), jnp.float32)}
        layer_s = {"mean": jnp.asarray(rng.normal(size=kout) * 600, jnp.float32),
                   "var": jnp.asarray(rng.random(kout) * 4e5 + 1, jnp.float32),
                   "count": jnp.zeros((), jnp.int32)}

        def layer(c):
            return jax.jit(lambda x, v0: sy._encode_pool(
                x, layer_p, layer_s, c, False, out_t=out_t, plan=plan, v0=v0,
                affine=None, taps=None))

        return layer(cfg), layer(dataclasses.replace(cfg, conv_exec="dense"))

    @pytest.mark.parametrize("out_t,v_init", [(1, 0.0), (3, 0.25)])
    @pytest.mark.parametrize("reset", ["hard", "soft"])
    @pytest.mark.parametrize("hw,block", [((24, 32), (6, 8)),
                                          ((36, 64), (18, 32))])
    def test_bit_identical_to_dense_over_frames(self, hw, block, reset,
                                                out_t, v_init):
        rng = np.random.default_rng(hw[1] + out_t)
        pallas, dense = self._encode(rng, hw=hw, block=block, reset=reset,
                                     v_init=v_init, out_t=out_t)
        frames = rng.integers(0, 256, (3, 3) + hw + (3,)) / 255.0
        frames[:, 0], frames[:, 1] = 0.0, 1.0  # u8 values 0 and 255
        v_p = v_d = None
        for k in range(3):  # the membrane carries from frame to frame
            x_t = jnp.asarray(frames[k][None], jnp.float32)
            pool_p, spk_p, _, v_p = pallas(x_t, v_p)
            pool_d, spk_d, _, v_d = dense(x_t, v_d)
            assert v_p.shape == (3, hw[0], hw[1] * 16)
            assert 0 < float(spk_d.mean()) < 1
            np.testing.assert_array_equal(np.asarray(spk_p), np.asarray(spk_d))
            np.testing.assert_array_equal(
                np.asarray(ops.lane_dense_nhwc(v_p, 16)), np.asarray(v_d))
            np.testing.assert_array_equal(np.asarray(pool_p), np.asarray(pool_d))

    @pytest.mark.parametrize("converted", [False, True])
    def test_detector_heads_match_dense(self, converted):
        """The whole detector at 48×64 (8×8 encode blocks), streamed over 2
        frames: heads and final membranes of the pallas executor equal the
        dense oracle's. ``converted``: the ANN→SNN conversion settings —
        soft reset, v_init, rate-coded encode, rate-gated pools."""
        import dataclasses

        from repro.configs import get_config, smoke_config
        from repro.core import pruning
        from repro.models import snn_yolo as sy

        cfg = dataclasses.replace(
            smoke_config(get_config("snn-det")), input_hw=(48, 64),
            use_block_conv=True)
        if converted:
            cfg = dataclasses.replace(cfg, reset="soft", v_init=0.25,
                                      rate_encode=True, pool_mode="rate")
        params, bn = sy.init_params(jax.random.PRNGKey(1), cfg)
        params = pruning.prune_tree(params, 0.8)
        rng = np.random.default_rng(1)
        frames = jnp.asarray(rng.integers(0, 256, (2, 2, 48, 64, 3)) / 255.0,
                             jnp.float32)
        bn = sy.calibrate_bn_state(params, bn, frames[0], cfg, iters=2)
        out = {}
        for ex in ("dense", "pallas"):
            det = sy.compile_detector(dataclasses.replace(cfg, conv_exec=ex),
                                      params, bn)
            sess = det.new_session(batch=2)
            heads = [np.asarray(sess.step(f).head) for f in frames]
            # NHWC element order: the pallas encode leaf is (N, H, W·C)
            mem = {k: np.asarray(v).reshape(
                       v.shape[:2] + (-1, params[k]["w"].shape[-1]))
                   for k, v in sess.state.items()}
            out[ex] = heads, mem
        for k in range(2):
            np.testing.assert_array_equal(out["pallas"][0][k], out["dense"][0][k])
        for k, v in out["dense"][1].items():
            np.testing.assert_array_equal(out["pallas"][1][k], v, err_msg=k)
