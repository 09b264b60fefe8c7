"""Per-layer block/grid-shape autotuning for the fused pipeline kernel.

SpikeX-style (arXiv 2505.12292) insight: sparse-SNN speedups come from
block/tiling-shape co-optimization, not arithmetic — the same fused kernel
can be dispatched with different K-block widths (``kblk``, the packed
weight-block granularity), macro-tile shapes (``mrows × mcols``, how many
spatial conv blocks one grid step owns — whole rows of blocks or r×c
groups, collapsing the grid at large inputs), and MXU dot granularities
(``nbt``, how many of the macro-tile's blocks each dot stacks; divides
``mrows·mcols``). None of these knobs changes numerics (integer
accumulation is order-independent, the affine/LIF chain is element-wise),
so tiling is a pure wall-clock search problem.

This module sweeps candidate :class:`TileConfig` s per LAYER SHAPE,
measures the fused dispatch with the same median-of-k wall-clock harness
the kernel benchmarks use (``measure``), and persists the winners in a
deterministic shape→config JSON cache that ``core/plan.py`` consults at
compile time:

    python -m repro.kernels.autotune            # retune the default shapes
    python -m repro.kernels.autotune --input-hw 96x128

Cache contract (tests/test_autotune.py):
  * deterministic — the same entries serialize to byte-identical files
    (sorted keys, fixed separators, no timestamps or wall-clock values);
  * safe — a missing, stale (version-bumped) or corrupt cache silently
    falls back to :data:`DEFAULT_TILE`, and tile choice NEVER changes
    numerics, only speed.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import time
import warnings
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

DEFAULT_CACHE_PATH = os.path.join(os.path.dirname(__file__), "autotune_cache.json")
CACHE_ENV_VAR = "REPRO_AUTOTUNE_CACHE"
CACHE_VERSION = 2  # v2: macro-tile axis (mrows/mcols) joined the search

KBLK_CANDIDATES = (32, 64, 128)
NBT_CANDIDATES = (1, 2, 4, 8, 16)
# macro-tile edge lengths tried along each block-grid axis (must divide
# the grid edge to be enumerated — ragged macros are legal but waste pad)
MACRO_CANDIDATES = (1, 2, 4, 8, 16, 32)
# dots-per-grid-step granularities tried inside a macro-tile
DOT_GROUP_CANDIDATES = (1, 2, 4)
# candidate tilings must keep (spikes + weights + scratch) under VMEM
VMEM_BUDGET_BYTES = 12 * 2**20
# walls within this fraction of the fastest candidate count as a tie —
# break toward the LARGEST macro-tile (fewest grid steps): per-step
# overhead amortization is the monotone effect the sweep exists to
# exploit, and sub-noise argmin would otherwise pick shapes at random
TIE_MARGIN = 0.05


class TileConfig(NamedTuple):
    """One fused-kernel dispatch shape. ``kblk``: packed K-block width
    (output channels decoded/computed per grid step); ``mrows × mcols``:
    macro-tile of spatial conv blocks each grid step owns; ``nbt``:
    blocks stacked per MXU dot (divides ``mrows·mcols``)."""

    kblk: int = 128
    nbt: int = 1
    mrows: int = 1
    mcols: int = 1


DEFAULT_TILE = TileConfig()


class LayerShape(NamedTuple):
    """Everything the tuner needs to reconstruct a layer's dispatch —
    and the cache key. Batch-agnostic: tuned at N=1; ``nbt`` stays valid
    for larger batches (the block axis only grows)."""

    kh: int
    kw: int
    cin: int  # true (unpadded) input channels
    kout: int  # true output channels
    in_bits: int  # 1 = binary spikes (the only input the tuned kernel takes)
    t_in: int
    t_out: int
    h: int  # feature-map resolution the layer runs at
    w: int
    bh: int  # conv block (grid tile) shape
    bw: int

    @property
    def key(self) -> str:
        return (
            f"conv{self.kh}x{self.kw}_ci{self.cin}_co{self.kout}"
            f"_ib{self.in_bits}_t{self.t_in}-{self.t_out}"
            f"_hw{self.h}x{self.w}_blk{self.bh}x{self.bw}"
        )

    @property
    def n_blocks(self) -> int:
        return (self.h // self.bh) * (self.w // self.bw)


# ------------------------------------------------------------------ cache --


def cache_path(path: str | None = None) -> str:
    return path or os.environ.get(CACHE_ENV_VAR) or DEFAULT_CACHE_PATH


# Paths already complained about — a stale/corrupt cache is consulted once
# per LAYER at plan-build time, so an unguarded warn would fire ~27× per
# detector compile. One warning per cache path per process is enough.
_warned_paths: set[str] = set()


def _warn_once(path: str, detail: str) -> None:
    if path in _warned_paths:
        return
    _warned_paths.add(path)
    warnings.warn(
        f"autotune cache {path!r} ignored ({detail}); all layers fall back "
        f"to the default tiling {tuple(DEFAULT_TILE)} — numerics are "
        "unaffected, only speed. Regenerate with `python -m "
        "repro.kernels.autotune`.",
        RuntimeWarning,
        stacklevel=3,
    )


def load_cache(path: str | None = None) -> dict[str, TileConfig]:
    """Load the shape→tile cache. A missing, corrupt, or version-stale file
    yields {} — callers then run every layer at :data:`DEFAULT_TILE`, which
    is always numerically identical, just untuned. A cache file that EXISTS
    but can't be used (corrupt JSON, version mismatch) warns once per
    process with the path and the found-vs-expected version; a simply
    missing file stays silent (the untuned default is a supported state)."""
    p = cache_path(path)
    try:
        with open(p) as f:
            raw = json.load(f)
    except FileNotFoundError:
        return {}
    except (OSError, json.JSONDecodeError) as e:
        _warn_once(p, f"corrupt: {e}")
        return {}
    if not isinstance(raw, dict) or raw.get("version") != CACHE_VERSION:
        found = raw.get("version") if isinstance(raw, dict) else type(raw).__name__
        _warn_once(
            p, f"version mismatch: found {found!r}, expected {CACHE_VERSION!r}"
        )
        return {}
    out = {}
    for key, cfgd in raw.get("entries", {}).items():
        try:
            out[key] = TileConfig(
                kblk=int(cfgd["kblk"]),
                nbt=int(cfgd["nbt"]),
                mrows=int(cfgd.get("mrows", 1)),
                mcols=int(cfgd.get("mcols", 1)),
            )
        except (KeyError, TypeError, ValueError):
            continue  # one bad entry falls back; the rest stay usable
    return out


def save_cache(entries: dict[str, TileConfig], path: str | None = None) -> str:
    """Serialize deterministically: sorted keys, fixed separators, ONLY the
    chosen configs (never wall-clock samples) — so identical shape sets
    always produce byte-identical cache files."""
    p = cache_path(path)
    payload = {
        "version": CACHE_VERSION,
        "entries": {
            key: {
                "kblk": int(t.kblk),
                "nbt": int(t.nbt),
                "mrows": int(t.mrows),
                "mcols": int(t.mcols),
            }
            for key, t in sorted(entries.items())
        },
    }
    blob = json.dumps(payload, indent=1, sort_keys=True) + "\n"
    with open(p, "w") as f:
        f.write(blob)
    return p


@functools.lru_cache(maxsize=4)
def _default_cache_cached(path: str, mtime: float) -> tuple:
    return tuple(load_cache(path).items())


def lookup(shape: LayerShape, cache: dict[str, TileConfig] | None = None) -> TileConfig:
    """Resolve a layer shape to its tuned tile; DEFAULT_TILE when untuned.
    ``cache=None`` loads the default cache file (mtime-invalidated)."""
    if cache is None:
        p = cache_path()
        try:
            mtime = os.path.getmtime(p)
        except OSError:
            return DEFAULT_TILE
        cache = dict(_default_cache_cached(p, mtime))
    return cache.get(shape.key, DEFAULT_TILE)


# -------------------------------------------------------------- measuring --


def measure(fn: Callable[[], jax.Array], *, iters: int = 5, warmup: int = 1) -> float:
    """Median wall-clock of ``fn`` (which must return a jax array to block
    on) — the same median-of-k discipline as benchmarks/e2e_detector.py,
    shared here so kernel_bench and the tuner time dispatches identically."""
    for _ in range(warmup):
        jax.block_until_ready(fn())
    walls = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        walls.append(time.perf_counter() - t0)
    return float(np.median(walls))


def _macro_shapes(nbh: int, nbw: int) -> list[tuple[int, int]]:
    """Macro-tile shapes tried for an nbh×nbw block grid: grow along the
    row first (contiguous blocks), then stack whole rows — i.e. (1, c)
    for c | nbw, then (r, nbw) for r | nbh. This chain covers everything
    from single-block to whole-grid without a quadratic sweep."""
    mcs = [m for m in MACRO_CANDIDATES if m <= nbw and nbw % m == 0]
    mrs = [m for m in MACRO_CANDIDATES if m <= nbh and nbh % m == 0]
    shapes = [(1, mc) for mc in mcs]
    shapes += [(mr, nbw) for mr in mrs if mr > 1 and nbw in mcs]
    return shapes


def candidates(shape: LayerShape) -> list[TileConfig]:
    """Legal tile configs for a layer shape: kblk clipped to the padded
    output width (one tight block minimum, matching build_layer_plan),
    macro-tile shapes from :func:`_macro_shapes` (row-first chain up to
    the whole block grid), nbt a divisor of the macro-tile size keeping
    the per-step dot count small, all capped by a crude VMEM model."""
    kout8 = -(-shape.kout // 8) * 8
    kblks = sorted({min(kb, kout8) for kb in KBLK_CANDIDATES})
    nbh, nbw = shape.h // shape.bh, shape.w // shape.bw
    out = []
    cin_p = -(-shape.cin // 8) * 8
    ph, pw = shape.bh + shape.kh - 1, shape.bw + shape.kw - 1
    for kblk in kblks:
        for mr, mc in _macro_shapes(nbh, nbw):
            bpg = mr * mc
            vmem = (
                shape.t_in * bpg * ph * pw * cin_p  # int8 spike tile
                + shape.t_in * bpg * ph * pw * cin_p * 4  # its f32 widening
                + shape.kh * shape.kw * cin_p * kblk * 2  # maskp+decoded w
                + bpg * shape.bh * shape.bw * kblk * (4 + 4 + shape.t_out)
            )
            if vmem > VMEM_BUDGET_BYTES:
                continue
            nbts = sorted({bpg // g for g in DOT_GROUP_CANDIDATES if bpg % g == 0})
            for nbt in nbts:
                out.append(TileConfig(kblk=kblk, nbt=nbt, mrows=mr, mcols=mc))
    return out or [DEFAULT_TILE]


def _synthetic_layer(shape: LayerShape, rng: np.random.Generator):
    """Deterministic synthetic weights + activations at the layer's shape
    and the paper's sparsity regime (~80% pruned 3×3 kernels)."""
    w = rng.integers(-127, 128, (shape.kh, shape.kw, shape.cin, shape.kout))
    density = 0.2 if shape.kh > 1 else 0.6
    w[rng.random(w.shape) > density] = 0
    w = w.astype(np.int8)
    x = rng.random((shape.t_in, 1, shape.h, shape.w, shape.cin)) < 0.25
    return w, jnp.asarray(x, jnp.float32)


def tune_layer(
    shape: LayerShape,
    *,
    threshold: float = 0.5,
    leak: float = 0.25,
    measure_fn: Callable | None = None,
    iters: int = 5,
) -> tuple[TileConfig, dict[str, float]]:
    """Sweep candidate tilings for one layer shape; return (winner, record
    of wall-clock per candidate). ``measure_fn(tile, run) -> seconds`` is
    injectable so tests can drive selection deterministically."""
    from . import ops  # lazy: ops imports nothing from here

    rng = np.random.default_rng(0)
    w, x_t = _synthetic_layer(shape, rng)
    record: dict[str, float] = {}
    walls_by_tile: list[tuple[TileConfig, float]] = []
    for tile in candidates(shape):
        packed = ops.pack_conv_weights(w, kblk=tile.kblk)
        kp = packed.maskp.shape[0] * packed.kblk
        affine = ops.affine_bundle(
            packed,
            jnp.float32(1.0 / 128),
            jnp.zeros((shape.kout,)),
            jnp.ones((shape.kout,)),
            jnp.ones((shape.kout,)),
            jnp.zeros((shape.kout,)),
        )

        # measure the JITTED dispatch: production plans run fused layers
        # inside one jitted detector graph, so the eager python/layout
        # overhead of a bare call (~1ms, constant across tiles) would
        # otherwise drown the real per-tile differences in a shared floor
        @functools.partial(jax.jit, static_argnums=())
        def _fused(x, packed=packed, affine=affine, tile=tile):
            spk, mem = ops.fused_conv_bn_lif(
                x,
                packed,
                affine,
                v0=None,
                out_t=shape.t_out,
                bn_scale=threshold,
                threshold=threshold,
                leak=leak,
                bh=shape.bh,
                bw=shape.bw,
                nbt=tile.nbt,
                mrows=tile.mrows,
                mcols=tile.mcols,
            )
            return mem

        def run():
            return _fused(x_t)

        wall = (
            measure_fn(tile, run)
            if measure_fn is not None
            else measure(run, iters=iters)
        )
        record[f"kblk{tile.kblk}_nbt{tile.nbt}_mt{tile.mrows}x{tile.mcols}"] = wall
        walls_by_tile.append((tile, wall))
    if not walls_by_tile:
        return DEFAULT_TILE, record
    best_wall = min(w for _, w in walls_by_tile)
    # noise-aware winner: among walls within TIE_MARGIN of the fastest,
    # take the largest macro-tile (then coarsest dots, then widest kblk)
    near = [(t, w) for t, w in walls_by_tile if w <= best_wall * (1 + TIE_MARGIN)]
    best = max(near, key=lambda tw: (tw[0].mrows * tw[0].mcols, tw[0].nbt,
                                     tw[0].kblk))[0]
    return best, record


def detector_layer_shapes(cfg) -> dict[str, LayerShape]:
    """Every conv layer of an ``SNNDetConfig`` that the tiled fused kernel
    runs, as :class:`LayerShape` s: the head has no tdBN/LIF and is not
    fused, and the 8-bit encoding layer has its own untiled kernel
    (kernels/encode_pipeline.py)."""
    from repro.models import snn_yolo as sy  # lazy: avoid import cycle

    bh, bw = cfg.block_hw
    out = {}
    for spec in sy.layer_specs(cfg):
        if spec.name == "head" or spec.bits_in != 1:
            continue
        out[spec.name] = LayerShape(
            kh=spec.k,
            kw=spec.k,
            cin=spec.cin,
            kout=spec.cout,
            in_bits=spec.bits_in,
            t_in=spec.t_in,
            t_out=spec.t_out,
            h=spec.h,
            w=spec.w,
            bh=bh,
            bw=bw,
        )
    return out


def tune_detector(
    cfg,
    *,
    measure_fn: Callable | None = None,
    iters: int = 5,
    verbose: bool = True,
) -> dict[str, TileConfig]:
    """Tune every distinct fused layer shape of a detector config; returns
    cache entries (key → TileConfig)."""
    entries: dict[str, TileConfig] = {}
    for name, shape in sorted(detector_layer_shapes(cfg).items()):
        if shape.key in entries:
            continue
        tile, record = tune_layer(
            shape,
            threshold=cfg.threshold,
            leak=cfg.leak,
            measure_fn=measure_fn,
            iters=iters,
        )
        entries[shape.key] = tile
        if verbose:
            walls = ", ".join(f"{k}={v*1e3:.2f}ms" for k, v in sorted(record.items()))
            print(
                f"  {name:20s} {shape.key}\n    -> kblk={tile.kblk} "
                f"nbt={tile.nbt} macro={tile.mrows}x{tile.mcols}   ({walls})"
            )
    return entries


def check_cache(cfgs, path: str | None = None) -> list[str]:
    """Return the cache keys required by ``cfgs`` that the committed cache
    is MISSING (empty list = fully covered). A stale or corrupt cache
    loads as {} and therefore reports every key missing — exactly the
    state `make check-autotune` exists to catch, since lookup() would
    silently fall back to DEFAULT_TILE for all of them."""
    cache = load_cache(path)
    missing = []
    for cfg in cfgs:
        for name, shape in sorted(detector_layer_shapes(cfg).items()):
            if shape.key not in cache and shape.key not in missing:
                missing.append(shape.key)
    return missing


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--input-hw", default=None,
                    help="HxW override for the tuned config (e.g. 96x128)")
    ap.add_argument("--out", default=None, help="cache path (default: packaged)")
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument(
        "--check", action="store_true",
        help="don't tune: fail (exit 1) if the committed cache is missing "
        "entries for the benchmarked configs (default + --input-hw)",
    )
    args = ap.parse_args(argv)

    import dataclasses

    from benchmarks.e2e_detector import reduced_config

    cfgs = [reduced_config()]
    if args.input_hw:
        h, w = (int(v) for v in args.input_hw.lower().split("x"))
        cfgs.append(dataclasses.replace(cfgs[0], input_hw=(h, w)))

    if args.check:
        missing = check_cache(cfgs, args.out)
        if missing:
            print(f"autotune cache {cache_path(args.out)} is missing "
                  f"{len(missing)} entr{'y' if len(missing) == 1 else 'ies'}:")
            for key in missing:
                print(f"  {key}")
            print("regenerate with: python -m repro.kernels.autotune"
                  + (f" --input-hw {args.input_hw}" if args.input_hw else ""))
            return 1
        print(f"autotune cache covers all {len(cfgs)} benchmarked config(s)")
        return 0

    entries = load_cache(args.out)
    for cfg in cfgs:
        print(f"tuning {cfg.arch_id} @ {cfg.input_hw[0]}x{cfg.input_hw[1]}")
        entries.update(tune_detector(cfg, iters=args.iters))
    path = save_cache(entries, args.out)
    print(f"wrote {len(entries)} entries -> {path}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
