"""Serving launcher: --arch picks the architecture; the Engine provides
continuous batching over a fixed slot pool for BOTH workloads — LM token
requests and snn-det frame streams (compile-once detector + streaming
membrane sessions). Smoke-scale on CPU; the same driver shards
params/caches over the production mesh on real hardware (launch/dryrun.py
proves those shardings compile).

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \
      --requests 8 --slots 4 --max-new 16
  PYTHONPATH=src python -m repro.launch.serve --arch snn-det \
      --requests 8 --slots 4 --frames 3 [--conv-exec gated|pallas|dense] \
      [--max-queue 16 --on-full reject|shed-oldest]
  PYTHONPATH=src python -m repro.launch.serve --arch snn-det --eval-map \
      --checkpoint /tmp/snn_det_ckpt [--dataset coco:<instances.json>]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import numpy as np

from repro.configs import ALL_IDS, get_config, smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import zoo
from repro.serve import AdmissionPolicy, Engine, FrameRequest, Request


def _admission(args):
    if args.max_queue is None:
        return None
    return AdmissionPolicy(max_queue=args.max_queue, on_full=args.on_full)


def _report_rejections(eng):
    if eng.rejected:
        print(f"  rejected {len(eng.rejected)} requests at admission "
              f"(rids {[r.rid for r in eng.rejected]})")


def _serve_lm(cfg, args):
    api = zoo.get_api(cfg)
    params = api.init_params(jax.random.PRNGKey(0))
    eng = Engine(cfg, params, n_slots=args.slots, max_seq=args.max_seq,
                 admission=_admission(args))

    rng = np.random.default_rng(0)
    for r in range(args.requests):
        plen = int(rng.integers(3, 32))
        eng.submit(Request(rid=r, prompt=list(rng.integers(1, cfg.vocab_size, plen)),
                           max_new_tokens=args.max_new))
    _report_rejections(eng)
    t0 = time.time()
    done = eng.run()
    dt = time.time() - t0
    assert len(done) == args.requests - len(eng.rejected)
    total = args.max_new * len(done)
    print(f"{args.arch}: served {len(done)} requests "
          f"({total} new tokens) in {dt:.1f}s — {total/dt:.1f} tok/s")
    for r in sorted(done, key=lambda r: r.rid)[:3]:
        print(f"  req {r.rid}: {r.out}")


def _serve_detector(cfg, args):
    from repro.data import detection_datasets as dd
    from repro.eval import harness
    from repro.models import snn_yolo as sy
    from repro.serve.detector import DetectorEngineCore, demo_weights, synth_streams
    from repro.serve.trace import Tracer

    source = dd.parse_dataset_spec(args.dataset)
    if args.checkpoint:
        # trained weights: the checkpoint's config sidecar replaces the
        # --arch smoke config (input size / channels must match the saved
        # tree); --conv-exec still overrides the executor if given
        cfg, params, bn, step = harness.restore_detector_checkpoint(args.checkpoint)
        if args.conv_exec:
            cfg = dataclasses.replace(cfg, conv_exec=args.conv_exec)
        rng = np.random.default_rng(0)
        print(f"restored checkpoint step {step} from {args.checkpoint} "
              f"({cfg.arch_id}, input {cfg.input_hw}, "
              f"conv_exec {cfg.conv_exec}, weight_bits {cfg.weight_bits})")
    else:
        cfg = dataclasses.replace(cfg, conv_exec=args.conv_exec or "gated")
        params, bn, rng = demo_weights(cfg)
    if args.eval_map and args.checkpoint:
        # real weights + --eval-map: compile with EVALUATION postprocess
        # settings (low threshold, deep budget) so the reported number is
        # the same mAP the accuracy harness would report — and is checked
        # against it bit-exactly below
        det = harness.compile_eval_detector(cfg, params, bn)
    else:
        det = sy.compile_detector(cfg, params, bn)
    core = DetectorEngineCore(det, n_slots=args.slots, tracer=Tracer(enabled=True))
    eng = Engine(core=core, admission=_admission(args))
    gts = None
    n_requests = args.requests
    if args.eval_map:
        # serve the val split (one frame per request — each admission
        # cold-starts its slot) and score the SERVED detections
        cap = source.num_eval_images("val")
        if cap is not None and cap < n_requests:
            print(f"  ({args.dataset} has {cap} val images; serving all of them)")
            n_requests = cap
        images, gts = source.eval_set(
            n_requests, hw=cfg.input_hw, grid_div=harness.grid_div(cfg),
            num_anchors=cfg.num_anchors, num_classes=cfg.num_classes,
        )
        streams = [img[None] for img in images]
    else:
        streams = synth_streams(rng, n_requests, args.frames, cfg.input_hw)
    for r, frames in enumerate(streams):
        eng.submit(FrameRequest(rid=r, frames=frames))
    _report_rejections(eng)
    t0 = time.time()
    done = eng.run()
    dt = time.time() - t0
    assert len(done) == n_requests - len(eng.rejected)
    total_frames = sum(len(r.out) for r in done)
    summary = eng.tracer.summary()
    tick = summary["spans"]["tick"]
    print(f"{args.arch}[{cfg.conv_exec}]: served {len(done)} streams "
          f"({total_frames} frames) in {dt:.1f}s — {total_frames/dt:.1f} frames/s, "
          f"tick p50 {tick['p50_ms']:.1f}ms p95 {tick['p95_ms']:.1f}ms "
          f"(first tick included)")
    print(f"  tracer: {json.dumps(summary)}")
    for r in sorted(done, key=lambda r: r.rid)[:3]:
        counts = [int(d.count) for d in r.out]
        print(f"  req {r.rid}: {len(r.out)} frames, detections/frame {counts}")
    if gts is not None:
        from repro.eval import detection_map as dm
        from repro.eval import sharded as se

        if eng.rejected:
            raise SystemExit(
                "--eval-map scores every val image; don't bound --max-queue "
                "below --requests"
            )

        preds = [r.out[0] for r in sorted(done, key=lambda r: r.rid)]
        if args.eval_shards > 1:
            from repro.distributed import runtime

            # score the served detections through the mesh-sharded reduction
            # (striped match stats, collective gather) — bit-identical to
            # the single-host sweep below for any shard count; the context
            # routes shard ownership under a multi-controller launch
            rep = se.evaluate_predictions_sharded(
                preds, gts, num_classes=cfg.num_classes, iou_threshold=0.5,
                eval_cfg=se.ShardedEvalConfig(n_shards=args.eval_shards),
                ctx=runtime.get_context(),
            )
            shard_note = f" ({rep['n_shards']} shards, {rep['gather']} gather)"
        else:
            rep = dm.evaluate_detections(
                preds, gts, num_classes=cfg.num_classes, iou_threshold=0.5
            )
            shard_note = ""
        weights_note = (
            "restored trained weights" if args.checkpoint else
            f"at the serving score threshold ({det.score_threshold}) — demo "
            "weights are random-calibrated; pass --checkpoint <dir> for "
            "representative accuracy"
        )
        print(f"  served-detections mAP@0.5 {rep['map']:.3f} over "
              f"{rep['n_images']} val frames ({args.dataset})"
              f"{shard_note} — {weights_note}")
        if args.checkpoint:
            # the end-to-end contract: the mAP of detections that went
            # through admission/slot batching must equal the accuracy
            # harness scoring the same weights on the same split, bit for
            # bit (per-image outputs are batch-grouping invariant)
            ref = harness.evaluate_detector(det, n_images=n_requests,
                                            source=source)
            identical = se.reports_identical(rep, ref)
            print(f"  harness parity: served {rep['map']!r} vs harness "
                  f"{ref['map']!r} — "
                  f"{'BIT-IDENTICAL' if identical else 'MISMATCH'}")
            if not identical:
                raise SystemExit(
                    "served-detections mAP does not match "
                    "harness.evaluate_detector on the restored weights"
                )
    return eng, done


def main(argv=None):
    """Serve per the command line. For ``--arch snn-det`` returns the
    ``(engine, finished requests)`` pair, so a caller can check what was
    served (``chip_smoke.py`` compares it with a dense replay)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ALL_IDS), required=True)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--frames", type=int, default=3,
                    help="frames per stream (snn-det requests)")
    ap.add_argument("--conv-exec", default=None,
                    choices=["dense", "gated", "pallas"],
                    help="detector conv executor (snn-det only; default: "
                         "gated, or the checkpoint's own executor with "
                         "--checkpoint)")
    ap.add_argument("--checkpoint", default=None, metavar="DIR",
                    help="restore trained params/BN (and config) from a "
                         "detector checkpoint dir — written by "
                         "eval/harness.run_pipeline(ckpt_dir=...), "
                         "benchmarks/eval_map.py --ckpt-dir or "
                         "examples/train_snn_detector.py — instead of "
                         "random-calibrated demo weights (snn-det only)")
    ap.add_argument("--dataset", default="synthetic",
                    help="--eval-map split: synthetic | coco:<instances."
                         "json> | voc:<dir> (snn-det only)")
    ap.add_argument("--eval-map", action="store_true",
                    help="serve the val split and report mAP@0.5 of the "
                         "SERVED detections (snn-det only); with "
                         "--checkpoint the score uses evaluation "
                         "postprocess settings and is asserted bit-exact "
                         "against harness.evaluate_detector")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="admission control: bound the submit queue at this "
                         "many waiting requests (default: unbounded)")
    ap.add_argument("--on-full", default="reject",
                    choices=["reject", "shed-oldest"],
                    help="full-queue policy with --max-queue: refuse new "
                         "requests, or shed the oldest queued ones")
    ap.add_argument("--eval-shards", type=int, default=1,
                    help="score the served detections through the "
                         "mesh-sharded mAP reduction (with --eval-map)")
    ap.add_argument("--full-config", action="store_true")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if not args.full_config:
        cfg = smoke_config(cfg)
    if args.arch == "snn-det":
        return _serve_detector(cfg, args)
    else:
        _serve_lm(cfg, args)


if __name__ == "__main__":
    main()
