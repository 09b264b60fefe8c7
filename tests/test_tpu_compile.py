"""The fused kernels compile for a TPU v5e at the paper config's layer shapes.

Nothing runs: the installed TPU compiler compiles for a chip that is
described (``v5e:2x2``) and not attached, and refuses what the chip's
compiler would refuse (unsupported Mosaic shape casts, unaligned blocks,
too much VMEM). Interpret mode on the CPU accepts all of those, so these
compiles are the only guard on the chip body between chip runs.

The topology is described inside a fixture, never while the module is
imported: only one process at a time may load the TPU library, and it
holds it until it exits.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.snn_det import CONFIG
from repro.kernels import autotune, ops
from repro.kernels import encode_pipeline as ep
from repro.kernels import fused_pipeline as fp

# encode (the lane-dense kernel, u8 input), and three layers of the blocked
# kernel: conv_block (3×3, T 1→3), a 256-channel 3×3 and a 1×1
LAYERS = ("encode", "conv_block", "stage3/main_a", "stage2/agg")
# the serving step's megabatch: 8 camera streams
SERVE_BATCH = 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A described-chip compile cannot be read back from the persistent
    cache without a chip; keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _layer_args(shape: autotune.LayerShape, sharding):
    """Shapes of one fused dispatch as ``core.plan.build_plan`` sets it up
    for the layer: the tile it looks up, K-blocks as it packs them."""
    tile = autotune.lookup(shape)
    kblk = min(tile.kblk, -(-shape.kout // 8) * 8)
    kb = -(-shape.kout // kblk)
    cin = -(-shape.cin // 8) * 8
    nbt, mr, mc = ops._normalize_tiling(
        tile.nbt, tile.mrows, tile.mcols,
        shape.h // shape.bh, shape.w // shape.bw,
    )
    gh, gw = ops._macro_grid(shape.h // shape.bh, shape.w // shape.bw, mr, mc)
    nb = gh * gw * mr * mc
    taps = shape.kh * shape.kw
    args = (
        jax.ShapeDtypeStruct(
            (shape.t_in, nb, shape.bh + shape.kh - 1, shape.bw + shape.kw - 1, cin),
            jnp.int8, sharding=sharding),
        jax.ShapeDtypeStruct((kb, fp.AFFINE_ROWS, kblk), jnp.float32, sharding=sharding),
        jax.ShapeDtypeStruct((nb * shape.bh * shape.bw, kb * kblk), jnp.float32,
                             sharding=sharding),
        jax.ShapeDtypeStruct((kb, taps, cin, kblk), jnp.int8, sharding=sharding),
    )
    statics = dict(kh=shape.kh, kw=shape.kw, bh=shape.bh, bw=shape.bw, kblk=kblk,
                   nbt=nbt, bpg=mr * mc, t_out=shape.t_out,
                   tap_alive=tuple(range(taps)))
    return args, statics


def _encode_args(sharding):
    """Shapes of the encode layer's dispatch in the serving step: the
    megabatch's frames, the band matrices, the affine rows and the carried
    lane-dense membrane."""
    h, w = CONFIG.input_hw
    bw, c = CONFIG.block_hw[1], CONFIG.stem_channels
    wt = ep.input_tile(w, bw)

    def shape(s, dtype):
        return jax.ShapeDtypeStruct(s, dtype, sharding=sharding)

    return (shape((SERVE_BATCH, h, w, 3), jnp.float32),
            shape((3, 3 * wt, bw * c), jnp.bfloat16),
            shape((fp.AFFINE_ROWS, bw * c), jnp.float32),
            shape((SERVE_BATCH, h, w * c), jnp.float32))


@pytest.mark.parametrize("layer", LAYERS)
def test_fused_kernel_compiles_for_v5e(layer, one_chip, no_compile_cache):
    if layer == "encode":
        args = _encode_args(one_chip)

        def dispatch(frames, bands, affine, v0):
            return ops._dispatch_encode(
                frames, bands, affine, v0, bh=CONFIG.block_hw[0],
                bw=CONFIG.block_hw[1], t_out=1, bn_scale=0.5, threshold=0.5,
                leak=0.25, reset="hard", v_init=0.0, interpret=False,
            )
    else:
        shape = autotune.detector_layer_shapes(CONFIG)[layer]
        args, statics = _layer_args(shape, one_chip)

        def dispatch(x, affine, v0, wdense):
            return fp.fused_pipeline_pallas(
                x, None, None, affine, v0, wdense=wdense, bn_scale=0.5,
                threshold=0.5, leak=0.25, interpret=False, **statics,
            )

    compiled = jax.jit(dispatch).lower(*args).compile()
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()
