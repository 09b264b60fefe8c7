"""Run the paper-width spiking detector on a TPU through its serving entry
points, and check what it serves.

    python chip_smoke.py             # one chip: serve streams, check them
    python chip_smoke.py --chips 4   # four chips: sharded evaluation only

One chip: ``repro.launch.serve`` (``--arch snn-det --full-config
--conv-exec pallas``) builds the 576×1024 detector of ``configs/snn_det.py``
from seeded random weights (pruned 80%, FXP8, tdBN calibrated), compiles
it, and serves multi-frame streams through the Engine. The script then
checks that

* the compiled serving step holds one Mosaic kernel (``tpu_custom_call``)
  per fused conv→tdBN→LIF layer, so no layer runs in interpret mode, and
* the served heads and detections equal a replay of the same frames
  through the dense executor under ``jax.default_matmul_precision
  ("highest")``, bit for bit (:data:`HEAD_TOLERANCE`).

Four chips: full-width sharded evaluation with four shards, each shard's
forward on its own chip, must give a report bit-identical to the one-chip
evaluation of the same images.

Earlier lines report the device, compile seconds, counts and differences.
The last line is ``{"ok": true, "device": {...}}``. The script exits
nonzero, and prints no such line, when JAX finds no TPU, when the
repository's sources are not beside it, or when a check fails. Everything
runs in this one process: a child process could not reach the chip.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

# Largest |served head − dense replay head| accepted. Both executors
# accumulate every conv exactly (binary spikes or u8 pixels times int8
# weights, integer-valued partial sums below 2^24) and then apply the same
# f32 rescale → tdBN → LIF operations in the same order, so the heads are
# expected to be bit-identical.
HEAD_TOLERANCE = 0.0

# Sharded evaluation: images evaluated, and frames per forward.
EVAL_IMAGES = 8
EVAL_BATCH = 2

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _watch_compiles() -> dict:
    """Collect backend-compile seconds per jitted function and persistent
    compile-cache hits and misses, from JAX's monitoring events. A miss is
    written to the cache only when its compile took at least
    ``jax_persistent_cache_min_compile_time_secs``."""
    import jax

    stats = {"compile_s": {}, "cache_hits": 0, "cache_misses": 0}

    def on_duration(event, duration, **kw):
        if event == _BACKEND_COMPILE:
            name = kw.get("fun_name", "?")
            stats["compile_s"][name] = stats["compile_s"].get(name, 0.0) + duration

    def on_event(event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            stats["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            stats["cache_misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    return stats


def count_kernels(det, cap: int) -> int:
    """``tpu_custom_call`` ops in the compiled serving step of ``det`` at
    capacity ``cap`` — the program ``DetectorEngineCore.step`` runs."""
    import jax.numpy as jnp

    h, w = det.cfg.input_hw
    frames = jnp.zeros((cap, h, w, 3), jnp.float32)
    active = jnp.ones((cap,), bool)
    compiled = det._masked_step_fn.lower(
        det.params, det.bn_state, frames, det.zero_state(cap), active, active
    ).compile()
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def dense_replay(det, done) -> dict:
    """Replay every served stream through the dense executor at highest
    matmul precision, one streaming session per request, and compare heads
    and detections with what the pallas engine served."""
    import jax

    from repro.models import snn_yolo as sy

    dense = sy.compile_detector(
        dataclasses.replace(det.cfg, conv_exec="dense"), det.params, det.bn_state,
        anchors=det.anchors, score_threshold=det.score_threshold,
        iou_threshold=det.iou_threshold, max_detections=det.max_detections,
    )
    out = {"max_head_diff": 0.0, "head_values_differing": 0, "head_values": 0,
           "detections_served": 0, "detections_differing": 0, "finite": True}
    with jax.default_matmul_precision("highest"):
        for req in done:
            sess = dense.new_session(1)
            for f, frame in enumerate(req.frames):
                ref = sess.step(frame[None])
                ref_head = np.asarray(ref.head[0])
                got_head = np.asarray(req.heads[f])
                out["finite"] &= bool(np.isfinite(got_head).all())
                out["max_head_diff"] = max(
                    out["max_head_diff"], float(np.abs(got_head - ref_head).max())
                )
                out["head_values_differing"] += int((got_head != ref_head).sum())
                out["head_values"] += got_head.size
                served = req.out[f]
                out["detections_served"] += int(np.asarray(served.valid).sum())
                out["detections_differing"] += _differing_detections(
                    served, [np.asarray(x[0]) for x in ref.detections])
    return out


def _differing_detections(got, want) -> int:
    """Detection slots whose validity differs, or that are valid in ``got``
    with a different box, score or class in ``want`` (Detections fields:
    boxes, scores, classes, valid)."""
    valid = np.asarray(got.valid)
    same = valid == want[3]
    for g, w in zip(got[:3], want[:3]):
        g = np.asarray(g).reshape(valid.size, -1)
        same &= (g == w.reshape(valid.size, -1)).all(axis=1) | ~valid
    return int((~same).sum())


def serve_phase(*, full_config: bool = True, requests: int = 6, frames: int = 3,
                slots: int = 4) -> dict:
    """Serve ``requests`` streams of ``frames`` frames each through
    ``launch/serve.py`` on the pallas executor; count the serving step's
    kernels and compare the served outputs with the dense replay."""
    from repro.launch import serve

    argv = ["--arch", "snn-det", "--conv-exec", "pallas", "--requests",
            str(requests), "--frames", str(frames), "--slots", str(slots)]
    t0 = time.perf_counter()
    eng, done = serve.main(argv + (["--full-config"] if full_config else []))
    serve_s = time.perf_counter() - t0
    core = eng.core
    det = core.det
    summary = {
        "input_hw": tuple(det.cfg.input_hw),
        "requests_done": len(done),
        "frames_served": sum(len(r.out) for r in done),
        "serve_s": serve_s,
        "first_tick_s": core.step_wall[0],
        "tracer": eng.tracer.summary(),
        "fused_layers": sum(1 for n in det.plan.layers if "gamma" in det.params[n]),
        "tpu_custom_calls": count_kernels(det, core.cap),
    }
    _check(summary["requests_done"] == requests,
           f"{summary['requests_done']} of {requests} requests finished")
    _check(summary["frames_served"] == requests * frames,
           f"{summary['frames_served']} of {requests * frames} frames served")
    summary.update(dense_replay(det, done))
    return summary


def sharded_phase(*, full_config: bool = True) -> dict:
    """Evaluate the same :data:`EVAL_IMAGES` images on one device and as
    four shards, each shard's forward on its own device; report whether the
    two reports are bit-identical and which devices ran the forwards."""
    from repro.configs import get_config, smoke_config
    from repro.eval import harness
    from repro.eval import sharded as se
    from repro.serve.detector import demo_weights

    cfg = get_config("snn-det")
    if not full_config:
        cfg = smoke_config(cfg)
    cfg = dataclasses.replace(cfg, conv_exec="pallas")
    params, bn, _ = demo_weights(cfg)
    det = harness.compile_eval_detector(cfg, params, bn)

    forward_devices: list = []
    detect = det.detect

    def recording_detect(frames):
        dets, head = detect(frames)
        forward_devices.append(sorted(d.id for d in head.devices()))
        return dets, head

    det.detect = recording_detect
    one = harness.evaluate_detector(det, n_images=EVAL_IMAGES, batch=EVAL_BATCH)
    one_devices = list(forward_devices)
    forward_devices.clear()
    four = harness.evaluate_detector(
        det, n_images=EVAL_IMAGES,
        sharded=se.ShardedEvalConfig(n_shards=4, batch=EVAL_BATCH),
    )
    return {
        "input_hw": tuple(cfg.input_hw),
        "n_images": one["n_images"],
        "one_chip": {k: one[k] for k in ("map", "n_pred", "n_gt")},
        "four_shards": {k: four[k] for k in ("map", "n_pred", "n_gt", "gather")},
        "one_chip_forward_devices": one_devices,
        "four_shard_forward_devices": list(forward_devices),
        "reports_identical": se.reports_identical(one, four),
    }


def check_sharded(summary: dict, devices) -> None:
    """The checks of :func:`sharded_phase`'s summary: a device-collective
    reduction, the one-device forwards on ``devices[0]``, shard s's forwards
    on ``devices[s]``, and bit-identical reports."""
    _check(summary["four_shards"]["gather"] == "mesh",
           "the 4-shard reduction did not run as a device collective")
    _check(bool(summary["one_chip_forward_devices"])
           and all(d == [devices[0].id]
                   for d in summary["one_chip_forward_devices"]),
           "the 1-chip evaluation ran forwards off its chip")
    _check(summary["four_shard_forward_devices"]
           == [[d.id] for d in devices[:4]],
           "the 4 shards' forwards did not each run on their own chip")
    _check(summary["reports_identical"],
           "4-shard report differs from the 1-chip report")


def _print(summary: dict) -> None:
    for k, v in summary.items():
        print(f"  {k}: {v!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded evaluation across four chips")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch import compile_cache
    except ImportError as e:
        print(f"chip_smoke: FAIL: the repository's sources are not beside "
              f"this script ({e})", file=sys.stderr)
        return 1
    if not Path(compile_cache.__file__).resolve().is_relative_to(ROOT / "src"):
        print(f"chip_smoke: FAIL: imported repro from {compile_cache.__file__}, "
              f"not from {ROOT / 'src'}", file=sys.stderr)
        return 1
    cache_dir = compile_cache.enable_compile_cache()
    import jax

    from repro.kernels.backend import auto_interpret

    devices = jax.devices()
    dev = devices[0]
    print(f"platform {dev.platform}  device_kind {dev.device_kind}  "
          f"devices {len(devices)}")
    print(f"compile cache {cache_dir}")
    if dev.platform != "tpu":
        print("chip_smoke: FAIL: JAX found no TPU", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: FAIL: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    stats = _watch_compiles()
    try:
        _check(not auto_interpret(), "Pallas kernels would run in interpret mode")
        if args.chips == 4:
            print("phase: sharded evaluation, 4 shards on 4 chips vs 1 chip")
            summary = sharded_phase()
            _print(summary)
            check_sharded(summary, devices)
        else:
            print("phase: serve full-width streams (pallas) + dense replay")
            summary = serve_phase()
            _print(summary)
            _check(summary["input_hw"] == (576, 1024), "not the paper's input size")
            _check(summary["tpu_custom_calls"] == summary["fused_layers"],
                   f"{summary['tpu_custom_calls']} tpu_custom_call ops for "
                   f"{summary['fused_layers']} fused layers")
            _check(summary["finite"], "served heads hold non-finite values")
            _check(summary["max_head_diff"] <= HEAD_TOLERANCE,
                   f"served heads differ from the dense replay by up to "
                   f"{summary['max_head_diff']} (tolerance {HEAD_TOLERANCE})")
            _check(summary["detections_differing"] == 0,
                   f"{summary['detections_differing']} served detections "
                   "differ from the dense replay")
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    finally:
        print(f"compile seconds by function: "
              f"{ {k: round(v, 3) for k, v in sorted(stats['compile_s'].items())} }")
        print(f"compile cache hits {stats['cache_hits']}  "
              f"misses {stats['cache_misses']}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
