"""The detector kind: closed-loop camera streams through the spiking
detector's ``Engine``.

Set-up makes the weights from the configuration's weight seed, builds the
program's detector (``compile_detector`` with the pallas executor) and its
``Engine`` at the cell's slot count, renders a pool of video frames from
``--seed`` and warms the serving step up. The window then serves closed-loop
camera streams: each stream submits its next clip when the last one
finishes. A frame's latency runs from when the engine could first serve it
(its clip's submission, or the delivery of the frame before it) to when its
detections are on the host. After the window a sample of the finished clips,
drawn from the seed, is replayed through the plain reference
(``reference.py``) and compared: ``head_gap`` and ``det_mismatch`` against
the configuration's limits, and ``clips_checked`` at least 1.
"""
from __future__ import annotations

import gc
import time

import numpy as np

import frames as frames_mod
import reference as ref
import work as work_mod

# the serving step's module in the device trace (``CompiledDetector._masked``)
STEP_MODULE = "jit__masked"


def program():
    """The parts of the program this kind drives."""
    from repro.models import snn_yolo
    from repro.serve import detector, engine

    return snn_yolo, detector, engine


def validate(cfg_doc: dict, traffic: dict) -> None:
    """Raise ValueError where the configuration or the traffic is not one
    this kind can run."""
    for key in ("model", "weights", "postprocess", "limits"):
        if key not in cfg_doc:
            raise ValueError(f"configuration {cfg_doc.get('name')!r} has no {key!r}")
    if set(cfg_doc["limits"]) != {"head_gap", "det_mismatch"}:
        raise ValueError(f"limits {sorted(cfg_doc['limits'])}: want head_gap, det_mismatch")
    if not traffic["slots"] >= traffic["streams"] >= 1:
        raise ValueError("traffic needs 1 <= streams <= slots")
    if traffic["clip_frames"] - traffic["first_clip_stagger"] * (traffic["streams"] - 1) < 1:
        raise ValueError("the last stream's first clip would be empty")


class Cell:
    """Everything set-up builds for one run of a cell."""

    def __init__(self, cfg_doc: dict, traffic: dict, program):
        snn_yolo, detector, engine = program
        self.cfg_doc, self.traffic = cfg_doc, traffic
        model = cfg_doc["model"]
        self.net = ref.Net.from_model(model)
        wts = cfg_doc["weights"]
        post = cfg_doc["postprocess"]
        self.post = (tuple(tuple(a) for a in post["anchors"]),
                     post["score_threshold"], post["iou_threshold"],
                     post["max_detections"])
        bits = model["weight_bits"]
        params = ref.make_params(self.net, wts["seed"], wts["prune_rate"])
        calib = frames_mod.render_pool(wts["seed"], wts["calibration_frames"],
                                       self.net.input_hw)
        bn = ref.calibrate(self.net, params, calib, bits)
        self.params, self.bn = params, bn
        self.ref_weights = ref.prepare(self.net, params, bn, bits)
        self.nnz = work_mod.nonzeros(self.ref_weights)

        self.det = snn_yolo.compile_detector(
            snn_yolo.config_from_dict(model), params, bn,
            anchors=self.post[0], score_threshold=self.post[1],
            iou_threshold=self.post[2], max_detections=self.post[3])
        self._detector, self._engine = detector, engine
        self.core = self.engine = None

    def start(self, seed: int) -> None:
        """A fresh engine at the cell's slot count, and the frame pool and
        clip sources of ``seed``."""
        traffic = self.traffic
        self.seed = seed
        slots = traffic["slots"]
        self.core = self._detector.DetectorEngineCore(self.det, n_slots=slots,
                                                      min_bucket=slots)
        self.engine = self._engine.Engine(core=self.core)
        self.pool = frames_mod.render_pool(seed, traffic["pool_frames"],
                                           self.net.input_hw,
                                           max_drift_px=traffic["max_drift_px"])
        self.sources = [
            frames_mod.ClipSource(self.pool, seed, i,
                                  clip_frames=traffic["clip_frames"],
                                  stagger=traffic["first_clip_stagger"])
            for i in range(traffic["streams"])
        ]
        self._rid = 0

    def stop(self) -> None:
        """Drop the engine and its device state."""
        self.core = self.engine = None
        gc.collect()

    def submit(self, frames_view) -> object:
        req = self._detector.FrameRequest(rid=self._rid, frames=frames_view)
        self._rid += 1
        res = self.engine.submit(req)
        if not res:
            raise RuntimeError(f"clip refused by the engine: {res.reason}")
        return req

    def warm_up(self) -> None:
        """Serve one 2-frame clip per stream to the end: compiles (or loads)
        the serving step at the cell's capacity bucket and runs the cold
        admission and the staged upload once."""
        for _ in self.sources:
            self.submit(self.pool[:2])
        result = self.engine.run()
        if not result.drained:
            raise RuntimeError("warm-up did not drain")
        self.engine.finished.clear()


class Window:
    """The measured window: closed-loop streams, per-frame latencies, and
    per-tick records."""

    def __init__(self):
        self.latencies: list[float] = []
        self.ticks: list[tuple] = []  # (t_start, t_end, step_wall, frames)
        self.clips: list = []  # every request submitted in the window
        self.elapsed = 0.0
        self.failed = 0  # clips the engine refused

    @property
    def attempted(self) -> int:
        return len(self.latencies)


def run_window(cell: Cell, seconds: float, profile=None) -> Window:
    """Serve for ``seconds``; ``profile`` (the harness's profiler window)
    traces part of it."""
    win = Window()
    streams = []  # per stream: [request, time its next frame became ready, frames seen]
    t0 = time.perf_counter()
    for src in cell.sources:
        req = cell.submit(src.next_clip())
        win.clips.append(req)
        streams.append([req, time.perf_counter(), 0])
    steps = cell.core.step_wall
    while True:
        if profile is not None:
            profile.before_tick(t0, len(win.ticks))
        n_steps = len(steps)
        ta = time.perf_counter()
        cell.engine.run(max_steps=1)
        tb = time.perf_counter()
        wall = steps[-1] if len(steps) > n_steps else 0.0
        delivered = 0
        for st in streams:
            req = st[0]
            while st[2] < len(req.out):
                win.latencies.append(tb - st[1])
                st[1] = tb
                st[2] += 1
                delivered += 1
        win.ticks.append((ta, tb, wall, delivered))
        if tb - t0 >= seconds:
            break
        for i, st in enumerate(streams):
            if st[0].done:
                req = cell.submit(cell.sources[i].next_clip())
                win.clips.append(req)
                st[:] = [req, time.perf_counter(), 0]
        if profile is not None:
            profile.after_tick(tb, len(win.ticks))
    if profile is not None:
        profile.close(len(win.ticks))
    win.elapsed = win.ticks[-1][1] - t0
    win.failed = len(cell.engine.rejected)
    return win


def end_to_end(win: Window) -> dict:
    lat = np.asarray(win.latencies, np.float64)
    frames = int(lat.size)
    return {
        "frames_per_s": frames / win.elapsed,
        "frame_p50_ms": float(np.percentile(lat, 50) * 1e3),
        "frame_p95_ms": float(np.percentile(lat, 95) * 1e3),
    }


def summary(win: Window) -> str:
    """One line on the window for standard error."""
    ticks_ms = [1e3 * (t[1] - t[0]) for t in win.ticks]
    walls_ms = [1e3 * t[2] for t in win.ticks if t[2]]
    med = np.median(ticks_ms)
    # what ticks of over twice the median took beyond it (host stalls, and
    # where the step is long enough, a clip boundary's synchronous upload),
    # and the window's time outside any tick
    stall_ms = sum(t - med for t in ticks_ms if t > 2 * med)
    outside_ms = 1e3 * win.elapsed - sum(ticks_ms)
    return (f"window: {len(win.ticks)} ticks, {len(win.latencies)} frames, "
            f"{win.elapsed:.3f} s; tick median {med:.3f} ms, "
            f"step_wall median {np.median(walls_ms):.3f} ms; longest tick "
            f"{max(ticks_ms):.3f} ms; stalls {stall_ms:.3f} ms beyond the "
            f"median in ticks over twice it, {outside_ms:.3f} ms between ticks")


def sample_clips(clips: list, seed: int, n: int) -> list:
    """Up to ``n`` finished clips drawn from the seed: from those of the
    longest length first, then from the rest."""
    done = [c for c in clips if c.done]
    rng = frames_mod.seed_rng(seed, 3)
    longest = max((len(c.frames) for c in done), default=0)
    full = [c for c in done if len(c.frames) == longest]
    rest = [c for c in done if len(c.frames) < longest]
    picked = [full[i] for i in rng.permutation(len(full))[:n]]
    picked += [rest[i] for i in rng.permutation(len(rest))[:n - len(picked)]]
    return picked


def check(cell: Cell, clips: list, expected: list | None = None) -> dict:
    """Compare what was served for ``clips`` with the reference's replay of
    them (``expected``, replayed here when not given)."""
    if expected is None:
        expected = replay(cell, clips, cell.ref_weights)
    return ref.compare([list(zip(c.heads, c.out)) for c in clips], expected)


def replay(cell: Cell, clips: list, weights: dict) -> list:
    return ref.replay(weights, [c.frames for c in clips], cell.net, cell.post,
                      batch=cell.traffic["check_clips"])


def checks_with_limits(found: dict, limits: dict, n_clips: int) -> tuple[bool, dict]:
    """Each compared number beside its upper limit, and the number of clips
    checked beside its lower one."""
    out = {k: {"value": found[k], "limit": limits[k]} for k in limits}
    ok = n_clips > 0 and all(found[k] is not None and found[k] <= limits[k]
                             for k in limits)
    out["clips_checked"] = {"value": n_clips, "limit": 1}
    return ok, out


def verify(cell: Cell, win: Window) -> tuple[bool, dict]:
    """Free the engine, then compare a sample of the window's finished clips
    with the reference: (ok, each compared number beside its limit)."""
    sampled = sample_clips(win.clips, cell.seed, cell.traffic["check_clips"])
    cell.stop()  # free the program's device state before the reference runs
    limits = cell.cfg_doc["limits"]
    # no finished clip to check: every number reads as failing
    found = check(cell, sampled) if sampled else {k: None for k in limits}
    return checks_with_limits(found, limits, len(sampled))


def trace_context(cell: Cell, win: Window, red: dict, ticks: list, peaks: dict) -> dict:
    """What the per-layer readers of this kind read: the trace reduction,
    the traced ticks, the work counter and the frames served per step."""
    return {"trace": red, "ticks": ticks, "work": work_mod.count(cell.net, cell.nnz, peaks),
            "peaks": peaks,
            "frames_per_step": sum(t[3] for t in ticks) / max(1, sum(1 for t in ticks if t[2]))}
