"""Detection-serving benchmark: frame streams through the megabatched Engine.

Two sections, both written into ``BENCH_serve.json``:

* ``executors`` — for each conv executor, compiles the smoke-scale detector
  once, serves a fixed set of concurrent :class:`FrameRequest` streams
  through the Engine's continuous-batching loop, and records throughput
  (frames/sec) plus per-tick latency percentiles (p50/p95 of the engine
  tracer's ``tick`` span, jit warm-up included). Also asserts that every
  executor's served raw heads match the dense executor's exactly (the
  compile-once path may not drift from the oracle under slot batching /
  membrane carryover).

* ``load`` — the load generator: N fully-resident concurrent streams
  (``--streams 64 256 [1024]``) megabatched through one engine tick per
  frame, recording p50/p95 tick latency and the tracer's summary,
  aggregate frames/s and per-stream fps (the paper's target is 29 fps/stream sustained across
  >= 64 streams). A sample of served streams is asserted BIT-IDENTICAL to
  an independent per-stream DetectorSession replay — megabatching, row
  remapping and the double-buffered upload may not change a single bit.

  PYTHONPATH=src python -m benchmarks.serve_bench [--streams 64 256] [--fast]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import numpy as np

# served heads must match the dense executor's BIT-EXACTLY — integer-domain
# accumulation makes every executor identical (tests/conformance/)
PARITY_ATOL = 0.0
EXECUTORS = ("dense", "gated", "pallas")
LOAD_EXECUTOR = "gated"  # fastest CPU executor at smoke scale (BENCH_e2e)


def _run_executors(base, params, bn, streams, *, requests, slots, frames):
    import dataclasses as dc

    from repro.models import snn_yolo as sy
    from repro.serve import DetectorEngineCore, Engine, FrameRequest
    from repro.serve.trace import Tracer

    out = {}
    served_heads = {}
    for ex in EXECUTORS:
        cfg = dc.replace(base, conv_exec=ex)
        det = sy.compile_detector(cfg, params, bn)
        eng = Engine(core=DetectorEngineCore(det, n_slots=slots,
                                             tracer=Tracer(enabled=True)))
        reqs = [FrameRequest(rid=r, frames=s) for r, s in enumerate(streams)]
        for fr in reqs:
            eng.submit(fr)
        t0 = time.perf_counter()
        done = eng.run()
        dt = time.perf_counter() - t0
        assert len(done) == requests and done.status == "drained"
        served_heads[ex] = {fr.rid: np.stack(fr.heads) for fr in reqs}
        diff = max(
            float(np.abs(served_heads[ex][rid] - served_heads["dense"][rid]).max())
            for rid in served_heads[ex]
        )
        assert diff <= PARITY_ATOL, f"{ex} served heads diverge from dense: {diff}"
        tick = eng.tracer.summary()["spans"]["tick"]
        out[ex] = {
            "frames_per_s": requests * frames / dt,
            "wall_s": dt,
            "tick_p50_ms": tick["p50_ms"],
            "tick_p95_ms": tick["p95_ms"],
            "max_abs_diff_vs_dense": diff,
        }
        r = out[ex]
        print(f"  {ex:7s} {r['frames_per_s']:7.1f} frames/s  "
              f"tick p50 {r['tick_p50_ms']:6.1f}ms  p95 {r['tick_p95_ms']:6.1f}ms  "
              f"max|Δ| vs dense {diff:.2e}")
    return out


def _run_load(base, params, bn, *, n_streams, frames, parity_streams):
    import dataclasses as dc

    from repro.models import snn_yolo as sy
    from repro.serve import AdmissionPolicy, DetectorEngineCore, Engine, FrameRequest
    from repro.serve.detector import synth_streams
    from repro.serve.trace import Tracer

    cfg = dc.replace(base, conv_exec=LOAD_EXECUTOR)
    det = sy.compile_detector(cfg, params, bn)
    rng = np.random.default_rng(1234 + n_streams)
    streams = synth_streams(rng, n_streams, frames, base.input_hw)
    eng = Engine(
        # fully resident: true N-way concurrency
        core=DetectorEngineCore(det, n_slots=n_streams, tracer=Tracer(enabled=True)),
        admission=AdmissionPolicy(max_queue=n_streams),
    )
    reqs = [FrameRequest(rid=r, frames=s) for r, s in enumerate(streams)]
    for fr in reqs:
        assert eng.submit(fr)
    t0 = time.perf_counter()
    done = eng.run()
    dt = time.perf_counter() - t0
    assert len(done) == n_streams and done.status == "drained"

    # bit-parity gate: a sample of megabatched streams vs independent
    # per-stream session replay (exits nonzero on ANY mismatch)
    for fr in reqs[:parity_streams]:
        solo = det.new_session(batch=1)
        for k, f in enumerate(fr.frames):
            ref = np.asarray(solo.step(f[None]).head[0])
            diff = float(np.abs(fr.heads[k] - ref).max())
            assert diff <= PARITY_ATOL, (
                f"stream {fr.rid} frame {k}: megabatched head diverges from "
                f"solo DetectorSession replay by {diff}"
            )

    summary = eng.tracer.summary()
    tick = summary["spans"]["tick"]
    rec = {
        "n_streams": n_streams,
        "frames_per_stream": frames,
        "wall_s": dt,
        "frames_per_s": n_streams * frames / dt,
        "per_stream_fps": frames / dt,
        "tick_p50_ms": tick["p50_ms"],
        "tick_p95_ms": tick["p95_ms"],
        "tracer": summary,
        "parity_streams": parity_streams,
        "max_abs_diff_vs_session": 0.0,
    }
    print(f"  load {n_streams:5d} streams  {rec['frames_per_s']:8.1f} frames/s "
          f"({rec['per_stream_fps']:6.2f} fps/stream)  tick p50 "
          f"{rec['tick_p50_ms']:7.1f}ms  p95 {rec['tick_p95_ms']:7.1f}ms")
    return rec


def run(*, requests: int = 8, slots: int = 4, frames: int = 2,
        load_streams=(64, 256), load_frames: int = 4, parity_streams: int = 3,
        out_json: str = "BENCH_serve.json") -> dict:
    from repro.configs import get_config, smoke_config
    from repro.serve.detector import demo_weights, synth_streams

    base = smoke_config(get_config("snn-det"))
    params, bn, rng = demo_weights(base)
    streams = synth_streams(rng, requests, frames, base.input_hw)

    results: dict = {
        "config": {"requests": requests, "slots": slots,
                   "frames_per_stream": frames, "input_hw": list(base.input_hw),
                   "load_streams": list(load_streams),
                   "load_frames": load_frames},
        "executors": _run_executors(
            base, params, bn, streams,
            requests=requests, slots=slots, frames=frames,
        ),
        "load": {},
    }
    for n in load_streams:
        results["load"][str(n)] = _run_load(
            base, params, bn,
            n_streams=n, frames=load_frames, parity_streams=parity_streams,
        )

    if out_json:
        with open(out_json, "w") as f:
            json.dump(results, f, indent=1)
        print(f"  wrote {out_json}")
    return results


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--streams", type=int, nargs="*", default=None,
                    help="concurrent-stream counts for the load generator "
                         "(default: 64 256; the paper-scale target is 1024)")
    ap.add_argument("--frames", type=int, default=None,
                    help="frames per stream in the load section")
    ap.add_argument("--fast", action="store_true",
                    help="CI smoke: fewer frames and parity samples")
    ap.add_argument("--out", default="BENCH_serve.json")
    args = ap.parse_args(argv)

    kw: dict = {"out_json": args.out}
    if args.fast:
        kw.update(load_streams=(64,), load_frames=2, parity_streams=2)
    if args.streams is not None:
        kw["load_streams"] = tuple(args.streams)
    if args.frames is not None:
        kw["load_frames"] = args.frames
    run(**kw)


if __name__ == "__main__":
    main()
