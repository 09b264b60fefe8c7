"""Mesh-sharded mAP evaluation (repro.eval.sharded): the shard reduction
must be EXACT — for any split of detections across k shards, gathering the
pooled per-class (score, TP) lists and re-sweeping AP is bit-identical to
the unsharded sweep, including empty shards, no-prediction classes and
deliberate score ties (where pooling ORDER changes AP, so the canonical
re-sort by global image index is load-bearing). Plus: the striping contract
matches ``synthetic_detection.batches`` host striping, the sharded detector
path matches ``harness.evaluate_detector`` bitwise, and the device
collective gather (``collectives.eval_stats_allgather``) agrees with the
host gather under a real simulated multi-device mesh (subprocess, like
tests/test_distributed.py)."""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
from hyp_compat import given, settings, st

from repro.data import synthetic_detection as sd
from repro.eval import detection_map as dm
from repro.eval import sharded as se

NUM_CLASSES = 3


def _random_split(seed: int, n_images: int, *, tie_decimals: int | None = 1,
                  max_gt: int = 4, max_pred: int = 5):
    """Seeded (predictions, ground_truths) with overlapping boxes (so TPs
    exist) and — by default — scores rounded to one decimal, which forces
    the score ties that make pooling order observable in AP."""
    rng = np.random.default_rng(seed)
    preds, gts = [], []
    for _ in range(n_images):
        g = int(rng.integers(0, max_gt + 1))
        g_boxes = rng.uniform(0.2, 0.8, (g, 4)).astype(np.float32)
        g_cls = rng.integers(0, NUM_CLASSES, g)
        gts.append({"boxes": g_boxes, "classes": g_cls})
        p_extra = int(rng.integers(0, max_pred + 1))
        near = g_boxes + rng.normal(0, 0.02, g_boxes.shape).astype(np.float32)
        p_boxes = np.concatenate(
            [near, rng.uniform(0.2, 0.8, (p_extra, 4)).astype(np.float32)]
        )
        p_cls = np.concatenate([g_cls, rng.integers(0, NUM_CLASSES, p_extra)])
        scores = rng.uniform(0, 1, len(p_boxes))
        if tie_decimals is not None:
            scores = np.round(scores, tie_decimals)
        preds.append({
            "boxes": p_boxes,
            "scores": scores.astype(np.float32),
            "classes": p_cls,
        })
    return preds, gts


def assert_reports_identical(got: dict, ref: dict):
    """Bitwise (NaN-aware) equality on every shared report key — the one
    canonical predicate the eval_map parity gate also uses."""
    assert se.reports_identical(got, ref), (
        {k: got.get(k) for k in ("map", "per_class_ap", "n_gt", "n_pred",
                                 "n_images", "iou_threshold")},
        {k: ref.get(k) for k in ("map", "per_class_ap", "n_gt", "n_pred",
                                 "n_images", "iou_threshold")},
    )


class TestStripingContract:
    def test_matches_batches_host_striping(self):
        """Shard s of k owns s, s+k, s+2k, ... — the exact index set
        ``batches(host_id=s, n_hosts=k)`` consumes."""
        assert sd.eval_shard_indices(10, 1, 3) == [1, 4, 7]
        for n, k in ((10, 3), (8, 1), (2, 5), (0, 4)):
            shards = [sd.eval_shard_indices(n, s, k) for s in range(k)]
            flat = sorted(i for sh in shards for i in sh)
            assert flat == list(range(n))  # disjoint + complete
            for s, sh in enumerate(shards):
                assert all(i % k == s for i in sh)

    def test_out_of_range_shard_raises(self):
        with pytest.raises(ValueError):
            sd.eval_shard_indices(8, 3, 3)

    def test_eval_set_shards_partition_the_split(self):
        hw, grid_div = (96, 160), 16
        full, full_gts = sd.eval_set(5, hw=hw, grid_div=grid_div)
        parts = [sd.eval_set(5, hw=hw, grid_div=grid_div, shard_id=s, n_shards=2)
                 for s in range(2)]
        np.testing.assert_array_equal(parts[0][0], full[0::2])
        np.testing.assert_array_equal(parts[1][0], full[1::2])
        for got, want in zip(parts[0][1], full_gts[0::2]):
            np.testing.assert_array_equal(got["boxes"], want["boxes"])

    def test_eval_set_empty_shard(self):
        imgs, gts = sd.eval_set(2, hw=(96, 160), grid_div=16,
                                shard_id=3, n_shards=4)
        assert imgs.shape == (0, 96, 160, 3) and gts == []


class TestShardReductionExact:
    @pytest.mark.parametrize("k", [1, 2, 3, 5, 8])
    def test_bit_identical_for_any_shard_count(self, k):
        preds, gts = _random_split(seed=k, n_images=9)
        ref = dm.evaluate_detections(preds, gts, num_classes=NUM_CLASSES)
        got = se.evaluate_predictions_sharded(
            preds, gts, num_classes=NUM_CLASSES,
            eval_cfg=se.ShardedEvalConfig(n_shards=k),
        )
        assert_reports_identical(got, ref)

    def test_empty_shards(self):
        """k > n_images: the trailing shards hold zero images."""
        preds, gts = _random_split(seed=0, n_images=2)
        ref = dm.evaluate_detections(preds, gts, num_classes=NUM_CLASSES)
        got = se.evaluate_predictions_sharded(
            preds, gts, num_classes=NUM_CLASSES,
            eval_cfg=se.ShardedEvalConfig(n_shards=7),
        )
        assert_reports_identical(got, ref)

    def test_no_predictions_at_all(self):
        """Present classes with zero predictions: AP 0.0 per class, exactly
        like the unsharded evaluator."""
        _, gts = _random_split(seed=3, n_images=4, max_gt=3)
        empty = [{"boxes": np.zeros((0, 4), np.float32),
                  "scores": np.zeros(0, np.float32),
                  "classes": np.zeros(0, np.int64)} for _ in gts]
        ref = dm.evaluate_detections(empty, gts, num_classes=NUM_CLASSES)
        got = se.evaluate_predictions_sharded(
            empty, gts, num_classes=NUM_CLASSES,
            eval_cfg=se.ShardedEvalConfig(n_shards=3),
        )
        assert_reports_identical(got, ref)

    def test_empty_split(self):
        got = se.evaluate_predictions_sharded([], [], num_classes=NUM_CLASSES)
        assert np.isnan(got["map"]) and got["n_images"] == 0

    def test_mismatched_pairing_raises(self):
        preds, gts = _random_split(seed=1, n_images=3)
        with pytest.raises(ValueError):
            se.evaluate_predictions_sharded(preds[:2], gts,
                                            num_classes=NUM_CLASSES)

    def test_tie_order_is_canonical(self):
        """The regression the re-sort exists for: one class, two images,
        tied scores, FP on image 0 and TP on image 1 — the stable sort
        pools [FP, TP] (AP 0.25 over 2 GT); a shard-major concatenation
        that put image 1 first would pool [TP, FP] and report 0.5."""
        gt = {"boxes": np.array([[0.5, 0.5, 0.2, 0.2]], np.float32),
              "classes": np.array([0])}
        tp_pred = {"boxes": np.array([[0.5, 0.5, 0.2, 0.2]], np.float32),
                   "scores": np.array([0.7], np.float32),
                   "classes": np.array([0])}
        fp_pred = {"boxes": np.array([[0.9, 0.9, 0.05, 0.05]], np.float32),
                   "scores": np.array([0.7], np.float32),
                   "classes": np.array([0])}
        preds = [fp_pred, tp_pred]  # image 0: FP, image 1: TP, same score
        gts = [gt, gt]
        ref = dm.evaluate_detections(preds, gts, num_classes=1)
        assert ref["map"] == pytest.approx(0.25)  # FP pools first
        got = se.evaluate_predictions_sharded(
            preds, gts, num_classes=1,
            eval_cfg=se.ShardedEvalConfig(n_shards=2),
        )
        assert_reports_identical(got, ref)

    @settings(max_examples=30, deadline=None)
    @given(
        st.integers(0, 10_000),
        st.integers(0, 8),
        st.integers(1, 6),
        st.sampled_from([None, 0, 1]),
    )
    def test_reduction_property(self, seed, n_images, k, tie_decimals):
        """For ANY split of detections across k shards, pooling the
        per-class score/TP lists and sweeping AP is bit-identical to the
        unsharded sweep — across image counts (including 0 and < k),
        shard counts and tie densities (decimals=0 makes almost every
        score collide)."""
        preds, gts = _random_split(seed, n_images, tie_decimals=tie_decimals)
        ref = dm.evaluate_detections(preds, gts, num_classes=NUM_CLASSES)
        got = se.evaluate_predictions_sharded(
            preds, gts, num_classes=NUM_CLASSES,
            eval_cfg=se.ShardedEvalConfig(n_shards=k),
        )
        assert_reports_identical(got, ref)


class TestShardedDetectorEval:
    @pytest.fixture(scope="class")
    def det(self):
        from repro.configs import get_config, smoke_config
        from repro.eval import harness
        from repro.serve.detector import demo_weights

        cfg = smoke_config(get_config("snn-det"))
        params, bn, _ = demo_weights(cfg)
        return harness.compile_eval_detector(cfg, params, bn)

    @pytest.mark.parametrize("k", [2, 4])
    def test_detector_sharded_matches_single_host(self, det, k):
        """End-to-end: striped eval split, per-shard forward→decode→NMS
        under the executor plan, reduced report == the legacy single-host
        ``harness.evaluate_detector`` bitwise."""
        from repro.eval import harness

        ref = harness.evaluate_detector(det, n_images=6)
        got = harness.evaluate_detector(det, n_images=6, sharded=k)
        assert got["n_shards"] == k and got["split"] == ref["split"]
        assert_reports_identical(got, ref)

    def test_batch_chunking_does_not_change_result(self, det):
        from repro.eval import harness

        a = harness.evaluate_detector(
            det, n_images=5, sharded=se.ShardedEvalConfig(n_shards=2, batch=2)
        )
        b = harness.evaluate_detector(
            det, n_images=5, sharded=se.ShardedEvalConfig(n_shards=2, batch=8)
        )
        assert_reports_identical(a, b)


_ENV = {**os.environ, "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "PYTHONPATH": "src"}


def _run(body: str):
    code = textwrap.dedent(body)
    r = subprocess.run([sys.executable, "-c", code], env=_ENV,
                       cwd=os.path.dirname(os.path.dirname(__file__)),
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    return r.stdout


def test_mesh_gather_matches_host_gather():
    """The device-collective reduction (all_gather + int psum through
    ``collectives.eval_stats_allgather`` on a simulated 8-device mesh) is
    bit-identical to both the host gather and the unsharded evaluator."""
    out = _run("""
        import sys; sys.path.insert(0, "tests")
        import numpy as np, jax
        assert len(jax.devices()) == 8, jax.devices()
        from repro.eval import detection_map as dm, sharded as se
        from test_sharded_eval import _random_split, assert_reports_identical
        preds, gts = _random_split(seed=11, n_images=10)
        ref = dm.evaluate_detections(preds, gts, num_classes=3)
        assert not np.isnan(ref["map"]) and ref["map"] > 0  # non-vacuous
        for k in (2, 4, 8):
            mesh = se.evaluate_predictions_sharded(
                preds, gts, num_classes=3,
                eval_cfg=se.ShardedEvalConfig(n_shards=k, use_device_mesh=True))
            host = se.evaluate_predictions_sharded(
                preds, gts, num_classes=3,
                eval_cfg=se.ShardedEvalConfig(n_shards=k, use_device_mesh=False))
            assert mesh["gather"] == "mesh" and host["gather"] == "host"
            assert_reports_identical(mesh, ref)
            assert_reports_identical(host, ref)
        print("MESH_GATHER_OK")
    """)
    assert "MESH_GATHER_OK" in out


def test_mesh_gather_requires_devices():
    """Forcing the collective without enough devices fails loudly (the
    parent test process runs single-device)."""
    import jax

    if len(jax.devices()) >= 4:
        pytest.skip("test process already has a multi-device backend")
    preds, gts = _random_split(seed=2, n_images=4)
    with pytest.raises(ValueError, match="devices"):
        se.evaluate_predictions_sharded(
            preds, gts, num_classes=NUM_CLASSES,
            eval_cfg=se.ShardedEvalConfig(n_shards=4, use_device_mesh=True),
        )


def test_detector_shards_forward_on_their_own_devices():
    """Each owned shard's forward runs on its own local device: shard s on
    device s, round robin once the shards outnumber the devices. A stub
    detector records where its input frames live."""
    out = _run("""
        import numpy as np, jax
        from repro.configs import get_config, smoke_config
        from repro.eval import sharded as se
        from repro.models.postprocess import Detections

        class StubDetector:
            cfg = smoke_config(get_config("snn-det"))

            def __init__(self):
                self.devices = []

            def detect(self, frames):
                self.devices.append(sorted(d.id for d in frames.devices()))
                z = np.zeros((frames.shape[0], 1))
                return Detections(np.zeros(z.shape + (4,), np.float32),
                                  z.astype(np.float32), z.astype(np.int32),
                                  z.astype(bool)), None

        det = StubDetector()
        for k, want in ((4, [0, 1, 2, 3]), (10, list(range(8)) + [0, 1])):
            det.devices.clear()
            rep = se.evaluate_detector_sharded(
                det, n_images=10, eval_cfg=se.ShardedEvalConfig(n_shards=k))
            assert rep["n_images"] == 10 and rep["n_shards"] == k
            assert det.devices == [[d] for d in want], det.devices
        print("SHARD_DEVICES_OK")
    """)
    assert "SHARD_DEVICES_OK" in out
