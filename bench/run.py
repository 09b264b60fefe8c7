"""Chip benchmark of the spiking detector's serving path.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix; the
harness reads ``bench/configs/<config>.json`` and ``bench/traffic/<traffic>
.json`` and, for ``--trace 1``, one reader ``bench/metrics/<metric>.py`` per
per-layer metric of the cell, all found by name.

Set-up makes the weights from the configuration's weight seed, builds the
program's detector (``compile_detector`` with the pallas executor) and its
``Engine`` at the cell's slot count, renders a pool of video frames from
``--seed`` and warms the serving step up. The window then serves closed-loop
camera streams for ``--seconds``: each stream submits its next clip when the
last one finishes. A frame's latency runs from when the engine could first
serve it (its clip's submission, or the delivery of the frame before it) to
when its detections are on the host. After the window a sample of the
finished clips, drawn from the seed, is replayed through the plain reference
(``reference.py``) and compared.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and ``checks`` last: each compared number with its limit).
The harness exits nonzero, and prints no such line, when JAX finds no TPU or
fewer chips than the cell asks for, or when the program's sources are not in
the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import frames as frames_mod  # noqa: E402
import reference as ref  # noqa: E402
import work as work_mod  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
# the traced part of a --trace 1 window: it starts this far into the
# window and lasts this long (both shortened for short windows)
TRACE_START_S = 1.0
TRACE_LEN_S = 3.0


class BenchError(Exception):
    """The benchmark cannot run here; nothing is printed on stdout."""


# ------------------------------------------------------------------ loading --


def load_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise BenchError(f"{path} not found") from None


def find_cell(spec: dict, name: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise BenchError(f"no workload {name!r} in BENCHMARK.json")


def cell_metrics(entries: list, cell: str) -> list:
    """The metric entries that apply to ``cell``: those without a
    ``workloads`` key and those that list it."""
    return [m for m in entries if "workloads" not in m or cell in m["workloads"]]


def load_reader(name: str):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"no reader {path} for metric {name!r}")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_peaks(kind: str) -> dict:
    peaks = load_json(BENCH / "peaks.json")
    if kind not in peaks:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json")
    return peaks[kind]


def import_program():
    """Put the checkout's ``src/`` first on the path and import the parts
    of the program the benchmark drives."""
    src = ROOT / "src"
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    try:
        from repro.models import snn_yolo
        from repro.serve import detector, engine
    except ImportError as e:
        raise BenchError(f"the program's sources are not in {src}: {e}") from None
    return snn_yolo, detector, engine


def enable_cache() -> None:
    """JAX's persistent compilation cache in the checkout, at a fixed path;
    every compile is kept, so a second run compiles nothing."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileWatch:
    """Counts backend compiles and persistent-cache hits and misses."""

    def __init__(self):
        import jax

        self.compiles = self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


# -------------------------------------------------------------------- cell --


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


def run_window(cell: Cell, seconds: float, *, trace_dir: str | None = None):
    """Serve for ``seconds``; with ``trace_dir``, trace part of the window
    with the JAX profiler (device only). Returns the Window and, when
    traced, [first traced tick, end tick, host start]."""
    import jax

    win = Window()
    streams = []  # per stream: [request, time its next frame became ready, frames seen]
    traced = None
    t0 = time.perf_counter()
    for src in cell.sources:
        req = cell.submit(src.next_clip())
        win.clips.append(req)
        streams.append([req, time.perf_counter(), 0])
    trace_at = min(TRACE_START_S, seconds / 4)
    trace_len = min(TRACE_LEN_S, seconds / 2)
    steps = cell.core.step_wall
    while True:
        if trace_dir is not None and traced is None and time.perf_counter() - t0 >= trace_at:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            traced = [len(win.ticks), None, time.perf_counter()]
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
        if traced is not None and traced[1] is None and tb - traced[2] >= trace_len:
            traced[1] = len(win.ticks)
            jax.profiler.stop_trace()
    if traced is not None and traced[1] is None:
        traced[1] = len(win.ticks)
        jax.profiler.stop_trace()
    win.elapsed = win.ticks[-1][1] - t0
    return win, traced


def end_to_end(win: Window) -> dict:
    lat = np.asarray(win.latencies, np.float64)
    frames = int(lat.size)
    return {
        "frames_per_s": frames / win.elapsed,
        "frame_p50_ms": float(np.percentile(lat, 50) * 1e3),
        "frame_p95_ms": float(np.percentile(lat, 95) * 1e3),
    }


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


def run_cell(cfg_doc: dict, traffic: dict, seed: int, seconds: float,
             trace: bool, peaks: dict, per_layer: list, end_metrics: list,
             program, *, log=sys.stderr) -> dict:
    """One run of a cell; returns the result object."""
    import jax

    watch = CompileWatch()
    cell = Cell(cfg_doc, traffic, program)
    cell.start(seed)
    cell.warm_up()
    setup_s = time.perf_counter() - T_START
    compiles_before = watch.compiles
    tmp = tempfile.TemporaryDirectory(prefix="bench-trace-") if trace else None
    try:
        win, traced = run_window(cell, seconds, trace_dir=tmp.name if tmp else None)
        compiles_in_window = watch.compiles - compiles_before
        dev = jax.devices()[0]
        stats = dev.memory_stats() or {}
        # the allocator's peak plus what it holds reserved for the compiled
        # programs' temporaries, which it does not count as in use
        peak = int(stats.get("peak_bytes_in_use", 0)) + int(
            stats.get("peak_bytes_reserved", stats.get("bytes_reserved", 0)))
        e2e = end_to_end(win)
        ticks_ms = [1e3 * (t[1] - t[0]) for t in win.ticks]
        walls_ms = [1e3 * t[2] for t in win.ticks if t[2]]
        print(f"window: {len(win.ticks)} ticks, {len(win.latencies)} frames, "
              f"{win.elapsed:.3f} s; tick median {np.median(ticks_ms):.3f} ms, "
              f"step_wall median {np.median(walls_ms):.3f} ms; "
              f"compiles in window {compiles_in_window}; "
              f"peak bytes {peak}; cache hits {watch.hits} "
              f"misses {watch.misses}", file=log)
        print(f"setup_s {setup_s:.3f}; memory_stats {stats}", file=log)
        rejected = len(cell.engine.rejected)
        sampled = sample_clips(win.clips, seed, traffic["check_clips"])
        cell.stop()  # free the program's device state before the reference runs
        limits = cfg_doc["limits"]
        # no finished clip to check: every number reads as failing
        found = check(cell, sampled) if sampled else {k: None for k in limits}
        ok, checks = checks_with_limits(found, limits, len(sampled))
        metrics = {}
        breakdown = None
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(jax.devices()), "memory_peak_bytes": peak}
        if trace:
            import trace_reduce

            red = trace_reduce.reduce_dir(tmp.name)
            ticks = win.ticks[traced[0]:traced[1]]
            work = work_mod.count(cell.net, cell.nnz, peaks)
            frames_traced = sum(t[3] for t in ticks)
            ctx = {"trace": red, "ticks": ticks, "work": work, "peaks": peaks,
                   "frames_per_step": frames_traced / max(1, sum(1 for t in ticks if t[2]))}
            for m in per_layer:
                value = load_reader(m["name"])(ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            device["busy_s"] = red["busy_ns"] / 1e9
            device["window_s"] = red["window_ns"] / 1e9
            breakdown = {"device_ops": red["top_ops"], "idle_gaps": red["idle_gaps"]}
        else:
            values = {**e2e, "setup_s": setup_s}
            for m in end_metrics:
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    finally:
        if tmp is not None:
            tmp.cleanup()
    for k, v in checks.items():
        print(f"check {k}: {v['value']} (limit {v['limit']})", file=log)
    result = {"correct": ok and rejected == 0, "attempted": len(win.latencies),
              "failed": rejected, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        spec = load_json(ROOT / "BENCHMARK.json")
        cell = find_cell(spec, args.workload)
        cfg_doc = load_json(BENCH / "configs" / f"{cell['config']}.json")
        traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
        program = import_program()
        enable_cache()
        import jax

        devices = jax.devices()
        print(f"host cpus {sorted(os.sched_getaffinity(0))}", file=sys.stderr)
        print(f"platform {devices[0].platform}  device_kind "
              f"{devices[0].device_kind}  devices {len(devices)}", file=sys.stderr)
        if devices[0].platform != "tpu":
            raise BenchError("JAX found no TPU")
        if len(devices) < cell["chips"]:
            raise BenchError(f"the cell asks for {cell['chips']} chips; JAX "
                             f"sees {len(devices)}")
        peaks = load_peaks(devices[0].device_kind)
        result = run_cell(
            cfg_doc, traffic, args.seed, args.seconds, bool(args.trace), peaks,
            cell_metrics(spec["per_layer"], args.workload) if args.trace else [],
            cell_metrics(spec["end_to_end"], args.workload), program)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
