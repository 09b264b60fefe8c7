"""Mesh-sharded mAP evaluation: stripe the val split, reduce exactly.

``harness.evaluate_detector`` scores the whole split on one host. Full-scale
configs need the same treatment the training data already gets
(``synthetic_detection.batches`` host striping): split the images across
shards, run each shard's forward→decode→NMS through the compile-once
executor plan, and reduce the pooled per-class (score, TP) lists before the
AP sweep. The reduction is EXACT — the pooled precision-recall curve (and
therefore mAP) is bit-identical to the single-host evaluation:

* shard s of k owns global image indices s, s+k, s+2k, ... (the
  ``batches(host_id, n_hosts)`` striping contract, via
  ``synthetic_detection.eval_shard_indices``),
* VOC greedy matching is per-image, so it shards embarrassingly; each
  shard emits flat per-prediction records (global image index, class,
  score, TP flag) plus its per-class ground-truth counts,
* records are gathered — through a REAL device collective
  (``distributed.collectives.eval_stats_allgather``: all_gather for the
  lists, integer psum for the recall denominators) when a mesh is
  available, plain host concatenation otherwise — and re-sorted by global
  image index (stable), which reconstructs the single-host pooling order
  EXACTLY, so score ties resolve identically and the AP sweep
  (``detection_map.average_precision``) sees the same sequence bit for bit.

The same code runs on 1 CPU device (host gather), N simulated CPU devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=N`` — the
``sharded-eval-sim`` CI lane), a real single-process multi-device mesh,
and a multi-CONTROLLER job (one process per host, launched through
``distributed.runtime.initialize``): process ``i`` owns shards
``i, i+P, i+2P, ...`` per :meth:`DistributedContext.owned_shards`, walks
ONLY those stripes, and the per-host merged records reduce through the
same ``eval_stats_allgather`` collective — run over the context's
:meth:`~repro.distributed.runtime.DistributedContext.stripe_mesh` (one
device per host, crossing process boundaries) instead of
``local_device_mesh``'s local subset. The stable re-sort by global image
index makes host/shard interleaving invisible, so the multi-host report is
bit-identical to the single-host one (the ``distributed-smoke`` CI lane's
gate). ``n_shards`` must stripe evenly over the hosts
(``n_shards % n_hosts == 0``) — anything else skews ownership and is
refused loudly.

Scores travel as float32 — the detector's native dtype, so the device hop
is bit-preserving. (Hand-crafted float64 scores that are not
float32-representable would be rounded; detector outputs never are.)
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Iterable, Mapping, Optional, Sequence

import numpy as np

from repro.data import synthetic_detection as sd
from repro.eval import detection_map as dm


@dataclass(frozen=True)
class ShardedEvalConfig:
    """How to split and reduce one evaluation.

    * ``n_shards`` — stripe count; shard s owns image indices s, s+k, ...
    * ``axis_name`` — mesh axis the reduction collective runs over.
    * ``batch`` — per-shard forward chunk size (outputs are bitwise
      invariant to batch grouping, so this only trades memory for speed).
    * ``use_device_mesh`` — None: use the device collective when
      ``n_shards`` devices are visible, else gather on host. True forces
      the collective (raises without enough devices); False forces host.
    """

    n_shards: int = 1
    axis_name: str = "data"
    batch: int = 8
    use_device_mesh: Optional[bool] = None

    def __post_init__(self):
        if self.n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {self.n_shards}")


@dataclass
class ShardStats:
    """One shard's flat match records + recall denominators.

    ``image_idx``/``cls``/``score``/``tp`` align per pooled prediction;
    within a shard they are appended in ascending global image order, and
    within one image in the (class-major, detection-order) order
    ``detection_map.evaluate_detections`` pools in — so a stable re-sort of
    the concatenated shards by ``image_idx`` IS the single-host order.
    """

    image_idx: np.ndarray  # (P,) int32 global image index per prediction
    cls: np.ndarray  # (P,) int32
    score: np.ndarray  # (P,) float32
    tp: np.ndarray  # (P,) bool
    n_gt: np.ndarray  # (C,) int32 per-class ground-truth count
    n_images: int

    @classmethod
    def empty(cls, num_classes: int) -> "ShardStats":
        return cls(
            image_idx=np.zeros(0, np.int32), cls=np.zeros(0, np.int32),
            score=np.zeros(0, np.float32), tp=np.zeros(0, bool),
            n_gt=np.zeros(num_classes, np.int32), n_images=0,
        )


def match_stats(
    predictions: Iterable,
    ground_truths: Iterable[Mapping[str, Any]],
    image_indices: Sequence[int],
    *,
    num_classes: int,
    iou_threshold: float = 0.5,
) -> ShardStats:
    """Greedy-match one shard's (predictions, ground_truths) — exactly the
    per-image half of ``detection_map.evaluate_detections`` — and record
    every pooled entry with its GLOBAL image index for the exact reduce."""
    idx_out: list = []
    cls_out: list = []
    score_out: list = []
    tp_out: list = []
    n_gt = np.zeros(num_classes, np.int32)
    n_images = 0
    preds = list(predictions)
    gts = list(ground_truths)
    if not len(preds) == len(gts) == len(image_indices):
        raise ValueError(
            f"pairing mismatch: {len(preds)} predictions, {len(gts)} "
            "ground truths, "
            f"{len(image_indices)} image indices — images align by position"
        )
    for pred, gt, g_idx in zip(preds, gts, image_indices):
        n_images += 1
        pred = dm._as_image_preds(pred)
        p_boxes = np.asarray(pred["boxes"], np.float64).reshape(-1, 4)
        p_scores = np.asarray(pred["scores"], np.float64).reshape(-1)
        p_cls = np.asarray(pred["classes"], np.int64).reshape(-1)
        g_boxes = np.asarray(gt["boxes"], np.float64).reshape(-1, 4)
        g_cls = np.asarray(gt["classes"], np.int64).reshape(-1)
        for c in range(num_classes):
            n_gt[c] += int(np.sum(g_cls == c))
            sel = p_cls == c
            if not np.any(sel):
                continue
            tp = dm.match_image(
                p_boxes[sel], p_scores[sel], g_boxes[g_cls == c],
                iou_threshold=iou_threshold,
            )
            k = int(np.sum(sel))
            idx_out.extend([int(g_idx)] * k)
            cls_out.extend([c] * k)
            score_out.extend(p_scores[sel].tolist())
            tp_out.extend(tp.tolist())
    return ShardStats(
        image_idx=np.asarray(idx_out, np.int32),
        cls=np.asarray(cls_out, np.int32),
        score=np.asarray(score_out, np.float32),
        tp=np.asarray(tp_out, bool),
        n_gt=n_gt,
        n_images=n_images,
    )


# ------------------------------------------------------------------ reduce --


def _gather_host(stats: Sequence[ShardStats]) -> ShardStats:
    """Reference reduction: plain concatenation + integer sum."""
    return ShardStats(
        image_idx=np.concatenate([s.image_idx for s in stats]),
        cls=np.concatenate([s.cls for s in stats]),
        score=np.concatenate([s.score for s in stats]),
        tp=np.concatenate([s.tp for s in stats]),
        n_gt=np.sum([s.n_gt for s in stats], axis=0).astype(np.int32),
        n_images=sum(s.n_images for s in stats),
    )


@functools.lru_cache(maxsize=None)
def _mesh_gather_fn(n_shards: int, axis_name: str):
    """(mesh row sharding, jitted gather) for an n_shards-way reduction —
    cached so repeated sharded evaluations (run_pipeline scores 5+ times)
    reuse one jit entry instead of recompiling the collective per call.
    The local device topology is fixed for the process lifetime, so the
    cache can never go stale."""
    import jax
    from jax.sharding import NamedSharding

    from repro.distributed import collectives as C
    from repro.distributed import sharding as shd
    from repro.distributed.meshes import local_device_mesh

    mesh = local_device_mesh(n_shards, axis_name)
    rules = shd.default_rules(mesh)
    row_sharding = NamedSharding(mesh, shd.spec_for(("batch",), rules))
    return row_sharding, jax.jit(C.eval_stats_allgather(mesh, axis_name))


def _gather_mesh(stats: Sequence[ShardStats], axis_name: str) -> ShardStats:
    """The device reduction: pad each shard's records to a common capacity,
    place row s on device s (``distributed.sharding`` logical-batch rule),
    all-gather the rows / psum the counts through
    ``collectives.eval_stats_allgather``, and unpad with the gathered valid
    mask. Bit-preserving: int/bool payloads plus float32 scores."""
    import jax

    k = len(stats)
    cap = max(1, max(s.image_idx.size for s in stats))

    def pad(x, fill=0):
        out = np.full((cap,), fill, dtype=x.dtype)
        out[: x.size] = x
        return out

    rows = {
        "image_idx": np.stack([pad(s.image_idx) for s in stats]),
        "cls": np.stack([pad(s.cls) for s in stats]),
        "score": np.stack([pad(s.score) for s in stats]),
        "tp": np.stack([pad(s.tp) for s in stats]),
        "valid": np.stack(
            [pad(np.ones(s.image_idx.size, bool), fill=False) for s in stats]
        ),
        # n_images rides along so the reduce is self-describing even for
        # shards that produced zero predictions
        "n_images": np.asarray([[s.n_images] for s in stats], np.int32),
    }
    counts = np.stack([s.n_gt for s in stats]).astype(np.int32)

    row_sharding, gather_fn = _mesh_gather_fn(k, axis_name)
    rows_dev = {f: jax.device_put(v, row_sharding) for f, v in rows.items()}
    counts_dev = jax.device_put(counts, row_sharding)
    gathered, total_gt = gather_fn(rows_dev, counts_dev)
    g = {f: np.asarray(v) for f, v in gathered.items()}
    valid = g["valid"].astype(bool)
    return ShardStats(
        image_idx=np.concatenate([g["image_idx"][s][valid[s]] for s in range(k)]),
        cls=np.concatenate([g["cls"][s][valid[s]] for s in range(k)]),
        score=np.concatenate([g["score"][s][valid[s]] for s in range(k)]),
        tp=np.concatenate([g["tp"][s][valid[s]].astype(bool) for s in range(k)]),
        n_gt=np.asarray(total_gt, np.int32),
        n_images=int(g["n_images"].sum()),
    )


@functools.lru_cache(maxsize=None)
def _process_gather_fn(n_hosts: int, axis_name: str):
    """(stripe mesh, jitted gather) for the cross-host reduction — one
    device per host, cached per (n_hosts, axis) like :func:`_mesh_gather_fn`
    (the process topology is fixed for the process lifetime)."""
    import jax

    from repro.distributed import collectives as C
    from repro.distributed import runtime

    mesh = runtime.get_context().stripe_mesh(axis_name)
    return mesh, jax.jit(C.eval_stats_allgather(mesh, axis_name))


def _gather_process(local: ShardStats, ctx, axis_name: str) -> ShardStats:
    """The multi-controller reduction: every host contributes ONE row (its
    merged owned-shard records) to the ``eval_stats_allgather`` collective
    over the context's stripe mesh. Two phases: an int all-gather agrees on
    the padded row capacity (hosts own different record counts), then the
    padded rows gather and the GT counts psum — both exact, so this is the
    cross-process twin of :func:`_gather_mesh`."""
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    mesh, gather_fn = _process_gather_fn(ctx.n_hosts, axis_name)
    sharding = NamedSharding(mesh, P(axis_name))
    n = ctx.n_hosts

    def to_global(arr):  # local (1, ...) row -> (n_hosts, ...) global array
        return jax.make_array_from_process_local_data(
            sharding, arr, (n,) + arr.shape[1:]
        )

    sizes, _ = gather_fn(
        {"n": to_global(np.array([[local.image_idx.size]], np.int32))},
        to_global(np.zeros((1, 1), np.int32)),
    )
    cap = max(1, int(np.asarray(sizes["n"]).max()))

    def pad(x, fill=0):
        out = np.full((1, cap), fill, dtype=x.dtype)
        out[0, : x.size] = x
        return out

    rows = {
        "image_idx": pad(local.image_idx),
        "cls": pad(local.cls),
        "score": pad(local.score),
        "tp": pad(local.tp),
        "valid": pad(np.ones(local.image_idx.size, bool), fill=False),
        "n_images": np.asarray([[local.n_images]], np.int32),
    }
    counts = local.n_gt[None].astype(np.int32)
    gathered, total_gt = gather_fn(
        {f: to_global(v) for f, v in rows.items()}, to_global(counts)
    )
    g = {f: np.asarray(v) for f, v in gathered.items()}
    valid = g["valid"].astype(bool)
    return ShardStats(
        image_idx=np.concatenate([g["image_idx"][h][valid[h]] for h in range(n)]),
        cls=np.concatenate([g["cls"][h][valid[h]] for h in range(n)]),
        score=np.concatenate([g["score"][h][valid[h]] for h in range(n)]),
        tp=np.concatenate([g["tp"][h][valid[h]].astype(bool) for h in range(n)]),
        n_gt=np.asarray(total_gt, np.int32),
        n_images=int(g["n_images"].sum()),
    )


def _pick_gather(eval_cfg: ShardedEvalConfig) -> str:
    if eval_cfg.n_shards == 1:
        return "host"  # nothing to reduce; no collective either way
    use = eval_cfg.use_device_mesh
    if use is None:
        import jax

        use = len(jax.devices()) >= eval_cfg.n_shards
    return "mesh" if use else "host"


def pool_stats(
    stats: Sequence[ShardStats],
    *,
    num_classes: int,
    iou_threshold: float = 0.5,
    eval_cfg: Optional[ShardedEvalConfig] = None,
    ctx=None,
) -> dict:
    """Reduce per-shard stats and sweep AP — the sharded back half of
    ``detection_map.evaluate_detections``, bit-identical to it.

    Single-controller (``stats`` holds EVERY shard): gathers via the device
    collective or on host per ``eval_cfg``. Multi-controller (``stats``
    holds only this host's owned shards): host-merges the local shards,
    then reduces across processes through :func:`_gather_process` over the
    context's stripe mesh. Either way the pooled records stable-sort by
    global image index: shards hold disjoint, internally-ascending index
    sets, so the re-sorted sequence is exactly the order the single-host
    evaluator pooled in (same tie resolution, same cumsum, same envelope).
    Returns the ``evaluate_detections`` report dict plus
    ``n_shards``/``n_hosts``/``gather``.
    """
    from repro.distributed import runtime

    ctx = ctx or runtime.get_context()
    if ctx.is_multi_controller:
        eval_cfg = eval_cfg or ShardedEvalConfig(n_shards=len(stats) * ctx.n_hosts)
        gather = "process"
        local = (
            _gather_host(stats) if stats else ShardStats.empty(num_classes)
        )
        merged = _gather_process(local, ctx, eval_cfg.axis_name)
        n_shards = eval_cfg.n_shards
    else:
        eval_cfg = eval_cfg or ShardedEvalConfig(n_shards=len(stats))
        gather = _pick_gather(eval_cfg)
        merged = (
            _gather_mesh(stats, eval_cfg.axis_name) if gather == "mesh"
            else _gather_host(stats)
        )
        n_shards = len(stats)
    order = np.argsort(merged.image_idx, kind="stable")
    cls = merged.cls[order]
    score = merged.score[order]
    tp = merged.tp[order]
    aps = []
    n_pred = []
    for c in range(num_classes):
        sel = cls == c
        n_pred.append(int(np.sum(sel)))
        aps.append(dm.average_precision(score[sel], tp[sel], int(merged.n_gt[c])))
    present = [a for a in aps if not np.isnan(a)]
    return {
        "map": float(np.mean(present)) if present else float("nan"),
        "per_class_ap": aps,
        "n_gt": merged.n_gt.astype(np.int64).tolist(),
        "n_pred": n_pred,
        "n_images": int(merged.n_images),
        "iou_threshold": float(iou_threshold),
        "n_shards": n_shards,
        "n_hosts": ctx.n_hosts,
        "gather": gather,
    }


def _same_ap(a: float, b: float) -> bool:
    return a == b or (np.isnan(a) and np.isnan(b))


def reports_identical(a: Mapping[str, Any], b: Mapping[str, Any]) -> bool:
    """The bit-identical contract, as one canonical predicate: NaN-aware
    exact equality of two evaluation reports on every shared key (mAP,
    per-class AP, GT/prediction counts, image count, IoU threshold) —
    sharded-only keys like ``n_shards``/``gather`` are ignored. Used by the
    ``benchmarks/eval_map.py --shards`` parity gate and the test suite."""
    return (
        _same_ap(a["map"], b["map"])
        and len(a["per_class_ap"]) == len(b["per_class_ap"])
        and all(_same_ap(x, y) for x, y in zip(a["per_class_ap"], b["per_class_ap"]))
        and a["n_gt"] == b["n_gt"]
        and a["n_pred"] == b["n_pred"]
        and a["n_images"] == b["n_images"]
        and a["iou_threshold"] == b["iou_threshold"]
    )


# ------------------------------------------------------------- evaluators --


def evaluate_predictions_sharded(
    predictions: Sequence,
    ground_truths: Sequence[Mapping[str, Any]],
    *,
    num_classes: int,
    iou_threshold: float = 0.5,
    eval_cfg: Optional[ShardedEvalConfig] = None,
    ctx=None,
) -> dict:
    """Sharded scoring of ALREADY-COMPUTED predictions (the serve
    ``--eval-map`` path and the shard-reduction property tests): stripe the
    paired lists across ``eval_cfg.n_shards``, match per shard, reduce.
    Bit-identical to ``detection_map.evaluate_detections`` on the same
    pairing for any shard count, including empty shards — PROVIDED scores
    are float32-representable (detector outputs always are; pooled scores
    travel as float32, so hand-computed float64 scores that differ only
    past float32 precision would collapse into ties here while the
    unsharded evaluator still ranks them apart).

    Multi-controller: this host matches only its OWNED shards
    (``ctx.owned_shards``) and the reduce crosses processes — every host
    must call with the SAME (predictions, ground_truths) pairing and
    returns the same full report."""
    from repro.distributed import runtime

    ctx = ctx or runtime.get_context()
    eval_cfg = eval_cfg or ShardedEvalConfig(n_shards=max(1, ctx.n_hosts))
    ctx.validate_shard_count(eval_cfg.n_shards)
    predictions = list(predictions)
    ground_truths = list(ground_truths)
    if len(predictions) != len(ground_truths):
        raise ValueError(
            f"{len(predictions)} predictions vs {len(ground_truths)} ground "
            "truths — the pairing aligns by position"
        )
    n = len(predictions)
    stats = []
    for s in ctx.owned_shards(eval_cfg.n_shards):
        idx = sd.eval_shard_indices(n, s, eval_cfg.n_shards)
        stats.append(
            match_stats(
                [predictions[i] for i in idx],
                [ground_truths[i] for i in idx],
                idx,
                num_classes=num_classes,
                iou_threshold=iou_threshold,
            )
        )
    return pool_stats(
        stats, num_classes=num_classes, iou_threshold=iou_threshold,
        eval_cfg=eval_cfg, ctx=ctx,
    )


def evaluate_detector_sharded(
    det,
    *,
    n_images: int = 32,
    split: str = "val",
    iou_threshold: float = 0.5,
    eval_cfg: Optional[ShardedEvalConfig] = None,
    source=None,
    ctx=None,
) -> dict:
    """Sharded ``harness.evaluate_detector``: each shard materializes only
    its stripe of the eval split (``source`` — any
    ``repro.data.detection_datasets.DetectionSource``; the synthetic
    generator by default. Both the generator and the file-backed loaders
    are deterministic per (split, index), so no shared filesystem is
    needed), runs forward→decode→NMS through the compile-once executor
    plan in ``eval_cfg.batch`` chunks, and the match stats reduce through
    ``pool_stats``. mAP is bit-identical to the single-host path for any
    shard count (per-image outputs are bitwise invariant to batch grouping:
    integer-domain conv accumulation plus elementwise float stages).

    Each owned shard's forward runs on its own local device (the j-th
    owned shard on ``ctx.local_devices[j % n_local]``), so on a host with
    one device per shard the shards' forwards occupy every chip.

    Multi-controller: process ``i`` walks ONLY its owned shards
    ``i, i+P, ...`` (``ctx.owned_shards``) — forward work scales with
    1/n_hosts wall-clock — and the reduce crosses processes through the
    context's stripe mesh; every host returns the same full report.
    ``eval_cfg`` defaults to one shard per host; an uneven
    ``n_shards % n_hosts`` raises (``ctx.validate_shard_count``)."""
    import jax

    from repro.distributed import runtime

    ctx = ctx or runtime.get_context()
    eval_cfg = eval_cfg or ShardedEvalConfig(n_shards=max(1, ctx.n_hosts))
    ctx.validate_shard_count(eval_cfg.n_shards)
    cfg = det.cfg
    from repro.data import detection_datasets as dd
    from repro.eval.harness import grid_div

    source = source or dd.SyntheticSource()
    cap = source.num_eval_images(split)
    if cap is not None:
        n_images = min(n_images, cap)
    stats = []
    devices = ctx.local_devices
    for j, s in enumerate(ctx.owned_shards(eval_cfg.n_shards)):
        device = devices[j % len(devices)]
        images, gts = source.eval_set(
            n_images, split=split, hw=cfg.input_hw, grid_div=grid_div(cfg),
            num_anchors=cfg.num_anchors, num_classes=cfg.num_classes,
            shard_id=s, n_shards=eval_cfg.n_shards,
        )
        idx = sd.eval_shard_indices(n_images, s, eval_cfg.n_shards)
        preds: list = []
        for i in range(0, len(images), eval_cfg.batch):
            dets, _ = det.detect(
                jax.device_put(images[i : i + eval_cfg.batch], device)
            )
            preds.extend(dm.detections_to_predictions(dets))
        stats.append(
            match_stats(
                preds, gts, idx,
                num_classes=cfg.num_classes, iou_threshold=iou_threshold,
            )
        )
    report = pool_stats(
        stats, num_classes=cfg.num_classes, iou_threshold=iou_threshold,
        eval_cfg=eval_cfg, ctx=ctx,
    )
    report["split"] = split
    return report
